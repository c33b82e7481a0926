"""Engine grid for `reader_lock`: batched and fused, scalar and vmapped,
against the serial engine (tests/engine_grid.py)."""
import pytest

import engine_grid as G
from engine_grid import serial_runs  # noqa: F401  (fixture)


@pytest.mark.parametrize("engine", G.FAST_ENGINES)
@pytest.mark.parametrize("scenario", G.SCENARIOS)
@pytest.mark.parametrize("workload", ["reader_lock"])
def test_fast_engine_equals_serial(serial_runs, workload, scenario, engine):
    G.check_fast_engine(serial_runs, workload, scenario, engine)


@pytest.mark.parametrize("engine", G.FAST_ENGINES)
@pytest.mark.parametrize("scenario", G.MANY_SCENARIOS)
@pytest.mark.parametrize("workload", ["reader_lock"])
def test_fast_engine_many_equals_solo_serial(serial_runs, workload, scenario,
                                             engine):
    G.check_fast_engine_many(serial_runs, workload, scenario, engine)
