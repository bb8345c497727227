"""``chip_smoke.py`` on the CPU: it refuses to report a result without a TPU,
and its ``--cpu-rehearsal`` runs every phase of the chip path at a small
scale.  Each run is a fresh process, as the script is run on the chip."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=REPO,
                          env=env, capture_output=True, text=True, timeout=600)


def test_chip_smoke_fails_without_tpu():
    out = _run()
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_cpu_rehearsal_passes():
    out = _run("--cpu-rehearsal")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    text = out.stdout
    assert "stream.async: 7 columns bit-exact" in text
    for q in (1, 6):
        assert f"fused Q{q} warm" in text and f"materialized Q{q} warm" in text
    assert "serve: 8/8 requests served bit-exact, no errors" in text
