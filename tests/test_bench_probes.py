"""The optional bench probes (heal bandwidth, quorum latency) are part
of the driver-recorded artifact every round — pin that they execute and
return sane shapes so a refactor can't silently turn BENCH_rNN.json's
extras into error strings."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_probe(expr: str, timeout: int) -> str:
    env = dict(os.environ)
    # A developer's exported bench knobs must not turn the probes into
    # None (BENCH_TINY et al. disable them by design).
    for knob in ("BENCH_TINY", "BENCH_QUORUM", "BENCH_HEAL"):
        env.pop(knob, None)
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        f"import sys; sys.path.insert(0, {REPO!r}); "
        "import json, bench; "
        f"print(json.dumps({expr}))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1]


@pytest.mark.timeout(240)
def test_bench_quorum_probe():
    import json

    out = json.loads(_run_probe("bench._bench_quorum()", timeout=180))
    assert "error" not in out, out
    assert out["rounds"] == 20
    assert 0 < out["p50_ms"] <= out["max_ms"] < 20_000


@pytest.mark.slow
def test_bench_heal_probe():
    import json

    out = json.loads(_run_probe("bench._bench_heal()", timeout=400))
    assert "error" not in out, out
    assert out["checksum_ok"] is True
    assert out["gb_per_s"] > 0
