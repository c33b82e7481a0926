"""Engine grid for `worksteal`: batched and fused against the serial engine
under every scenario (tests/engine_grid.py).  `worksteal` draws its initial
state on the host, so it has no vmapped replica twin."""
import pytest

import engine_grid as G
from engine_grid import serial_runs  # noqa: F401  (fixture)


@pytest.mark.parametrize("engine", G.FAST_ENGINES)
@pytest.mark.parametrize("scenario", G.SCENARIOS)
@pytest.mark.parametrize("workload", ["worksteal"])
def test_fast_engine_equals_serial(serial_runs, workload, scenario, engine):
    G.check_fast_engine(serial_runs, workload, scenario, engine)
