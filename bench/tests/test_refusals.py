"""A run that cannot measure what its cell asks for exits nonzero and
prints no result: on a CPU, with a REPRO_* switch set, and in a
directory that holds only BENCHMARK.json and the benchmark's files."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

ARGS = ["--workload", "worksteal.srsp.batched", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _bench(cwd, **env):
    e = {k: v for k, v in os.environ.items() if not k.startswith("REPRO_")}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("env", [{}, {"REPRO_KERNEL_MODE": "pallas"},
                                 {"REPRO_NO_FUSE": "1"}])
def test_refused_without_a_tpu_or_with_a_switch(env):
    p = _bench(ROOT, **env)
    assert p.returncode != 0
    assert p.stdout == ""


def test_refused_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""
