"""`correct` comes out true on sound runs and false on the control and on
every fault a cell can have, at sizes a CPU can hold.

The control is the program's own broken path: `faults.no_promotion`, the
protocol with remote acquires that no longer promote, which breaks the
visibility guarantee sRSP and RSP give.  The faults are planted under
the timed path: an engine that hands back its input state, one that
leaves half of the round's work out (each queue cut to half its tasks),
and one whose answer is altered where it is produced (one agent's cycle
count).  A cell runs one replica on one chip, so there is no batch of
replicas to leave half of and no exchange between chips to leave out.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.conftest import small_cell

CELLS = ("worksteal.srsp.batched", "worksteal.rsp.batched")
SEED = 2 ** 31 + 977


@pytest.fixture(scope="module")
def drivers():
    """One compiled driver per cell, shared by the module's tests."""
    cache = {}

    def get(name):
        if name not in cache:
            cell = small_cell(name)
            cache[name] = (cell, run.load_driver(cell))
        return cache[name]
    return get


def _run(cell, drv):
    return run.run_cell(cell, SEED, 0.5, False, drv=drv)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(drivers, name):
    res = _run(*drivers(name))
    assert res["correct"], res["checks"]
    assert res["checks"]["ref_mismatches"]["of"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    from repro.workloads import faults, harness
    cell = small_cell(name)
    proto = faults.no_promotion(
        harness.resolve_proto(cell.traffic["scenario"]))
    res = _run(cell, run.load_driver(cell, proto))
    assert not res["correct"]
    assert res["checks"]["ref_mismatches"]["value"] > 0


def _state_unchanged(drv, engine):
    def call(inp):
        return inp.state if hasattr(inp, "state") else inp
    return call


def _half_work(drv, engine):
    def call(inp):
        st = inp.state
        return engine(inp._replace(state=st._replace(qsize=st.qsize // 2)))
    return call


def _answer_altered(drv, engine):
    def call(inp):
        out = engine(inp)
        c = out.store.counters
        cycles = c.cycles.at[..., 0].add(1.0)
        return out._replace(store=out.store._replace(
            counters=c._replace(cycles=cycles)))
    return call


FAULTS = {"state_unchanged": _state_unchanged,
          "half_work": _half_work,
          "answer_altered": _answer_altered}
CASES = [(c, f) for c in CELLS for f in sorted(FAULTS)]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(drivers, name, fault, monkeypatch):
    cell, drv = drivers(name)
    monkeypatch.setattr(drv, "call", FAULTS[fault](drv, drv.call))
    res = _run(cell, drv)
    assert not res["correct"], res["checks"]
