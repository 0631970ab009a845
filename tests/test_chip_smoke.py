"""chip_smoke.py has no CPU fallback: where JAX shows no TPU it fails,
names the platform it found, and prints no result."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def test_fails_on_cpu_naming_the_platform():
    proc = subprocess.run(
        [sys.executable, _SMOKE], cwd=_REPO, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out
    assert "platform=cpu" in out and "not 'tpu'" in out, out
    assert '"ok"' not in proc.stdout  # no result line
    # it got no further than the first stage
    assert "py-plane" not in out and "native-plane" not in out


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(_SMOKE, tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_result_line_has_the_contract_keys_and_no_others():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    line = smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
