"""Smoke test of the runnable example: train + checkpoint + resume on a
virtual mesh (subprocess — the example configures its own devices)."""

import os
import pathlib
import subprocess
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_train_lm_checkpoint_resume():
    with tempfile.TemporaryDirectory() as td:
        ck = pathlib.Path(td) / "ckpt"
        cmd = [sys.executable, "examples/train_lm.py", "--steps", "3",
               "--ckpt", str(ck), "--cpu-devices", "8"]
        env = {"PATH": "/usr/bin:/bin", "HOME": os.environ.get("HOME", td),
               "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"}
        out1 = pathlib.Path(td) / "run1.log"
        with open(out1, "w") as f:
            subprocess.run(cmd, stdout=f, stderr=subprocess.STDOUT,
                           timeout=420, cwd=str(REPO), env=env)
        t1 = out1.read_text()
        assert "saved" in t1, t1[-1500:]
        out2 = pathlib.Path(td) / "run2.log"
        with open(out2, "w") as f:
            subprocess.run(cmd, stdout=f, stderr=subprocess.STDOUT,
                           timeout=420, cwd=str(REPO), env=env)
        t2 = out2.read_text()
        assert "resumed from" in t2, t2[-1500:]
        assert "step_000006" in t2
