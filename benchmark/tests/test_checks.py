"""The comparison that decides `correct`: sound runs pass, the bf16-wire
control and every fault the cell can have fail. Whole runs on the CPU
(4 virtual devices) with the chip check skipped; the lax ring stands in
for the Pallas kernel there."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]

# the faults each cell can have: on one rank an allreduce is its input,
# so a call that returns its input unchanged, or that skips the exchange,
# is right there
CELLS = {
    "ar.lat.w4": ["unchanged", "half_batch", "no_exchange", "altered",
                  "not_placed"],
    "ar.bw.w4": ["unchanged", "half_batch", "no_exchange", "altered",
                 "not_placed"],
    "ar.lat.w1": ["half_batch", "altered", "not_placed"],
    "ar.hostbuf.lat.w1": ["half_batch", "altered", "not_placed",
                          "not_copied_back"],
}


def cpu_env(tmp_path) -> dict:
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return env


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("drive")
    out = {}
    for cell, faults in CELLS.items():
        proc = subprocess.run(
            [sys.executable, str(BENCH / "tests" / "drive.py"), cell,
             "sound", "control", *faults],
            capture_output=True, text=True, env=cpu_env(tmp), timeout=600,
            cwd=BENCH.parent)
        assert proc.returncode == 0, proc.stderr[-3000:]
        for line in proc.stdout.splitlines():
            case, rc, js = line.split(" ", 2)
            out[cell, case] = (int(rc), json.loads(js))
    return out


@pytest.mark.parametrize("cell", list(CELLS))
def test_sound_run_is_correct(results, cell):
    rc, res = results[cell, "sound"]
    assert rc == 0 and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("cell", list(CELLS))
def test_bf16_wire_control_fails(results, cell):
    rc, res = results[cell, "control"]
    assert rc == 0 and res["correct"] is False, res
    gap = res["checks"]["max_gap"]
    assert gap["value"] > 10 * gap["limit"]


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in CELLS.items()
                                        for f in fs])
def test_fault_fails(results, cell, fault):
    rc, res = results[cell, fault]
    assert rc == 0 and res["correct"] is False, res
