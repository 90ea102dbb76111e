"""``chip_smoke.py`` off the chip: its phase functions at the reduced
``dlrm-criteo`` config on the CPU backend, and its refusal to run (or to
claim success) anywhere but on a TPU next to the repo's sources."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs.dlrm_criteo import make_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_and_serve_phases_reduced():
    cs = _chip_smoke()
    logs = []
    with cs.CompileMonitor() as monitor:
        trained = cs.train_phase(make_config(reduced=True), batch=256,
                                 steps=2, monitor=monitor, log=logs.append)
        res = trained[-1]
        assert [h["step"] for h in res["history"]["search"]] == [1, 2]
        assert [h["step"] for h in res["history"]["retrain"]] == [1, 2]
        out = cs.serve_phase(trained, p99_rows=64, bulk_rows=256,
                             request_rows=40, n_requests=3,
                             bulk_request_rows=512, monitor=monitor,
                             log=logs.append)
    assert out["max_ulp"] <= 1
    assert out["max_prob_err"] <= cs.PROB_ATOL
    text = "\n".join(logs)
    assert "compiles after warm-up 0" in text
    assert "search step 2 loss" in text and "retrain step 2 loss" in text
    assert monitor.seconds > 0


@pytest.mark.multidevice
def test_sharded_serve_phase_reduced():
    cs = _chip_smoke()
    logs = []
    out = cs.sharded_serve_phase(make_config(reduced=True), p99_rows=64,
                                 bulk_rows=256, request_rows=40, n_requests=2,
                                 bulk_request_rows=512, log=logs.append)
    # on the CPU backend both programs round identically: bit-exact
    for comms in ("psum", "a2a"):
        assert out[comms]["lookup_differ"] == 0
        assert out[comms]["logit_rows_differ"] == 0
    assert "'spilled': 0" not in "\n".join(logs)


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_a_host_without_tpu():
    proc = _run(SCRIPT, ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "platform 'cpu'" in proc.stderr


def test_refuses_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = _run(str(lone), str(tmp_path))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "src/repro" in proc.stderr
