"""chip_smoke.py rehearsed on the CPU, and the contracts around it: no
``ok`` line without a TPU, ``import torchft_tpu`` touches no backend, one
compile cache placeable from outside."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.mark.timeout(300)
def test_default_path_rehearsal_kill_heal_both_ways(tmp_path, monkeypatch):
    """The whole default path at llama_debug size on the CPU — two replica
    groups through LighthouseServer / train_hsdp.py / Manager, each
    SIGKILLed once — through the test-only ``Size`` argument. run_ft makes
    every assertion of the real run that does not need the chip."""
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    # One device per child: the suite's eight virtual devices would only
    # make the four trainer starts compile longer, next to timing tests.
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1"
    )
    # The cache of the children: not the checkout's.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    device = chip_smoke.run_ft(chip_smoke.DEBUG)
    assert device["platform"] == "cpu"

    ft = tmp_path / "out" / "ft"
    journals = [
        [json.loads(line) for line in open(ft / f"journal_replica{g}_rank0.jsonl")]
        for g in range(2)
    ]
    t_heal = []
    for g, events in enumerate(journals):
        kinds = [e["event"] for e in events]
        # Each group was the victim once and the donor once (a heal at
        # step 0 is the initial sync of the first quorum, not a recovery).
        heals = [
            e for e in events
            if e["event"] == "heal_done" and e["attrs"]["max_step"] > 0
        ]
        assert len(heals) == 1, (g, heals)
        t_heal.append(heals[0]["ts"])
        assert kinds.count("heal_send_done") >= 1, g
        # Two incarnations journaled under one slot.
        ids = {e["replica_id"] for e in events if ":" in e["replica_id"]}
        assert len(ids) == 2, ids
    assert t_heal[1] < t_heal[0]  # the peer first, then the chip group


def test_without_tpu_exits_nonzero_and_prints_no_ok_line():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=100,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_import_torchft_tpu_initialises_no_backend():
    code = (
        "import torchft_tpu, jax; "
        "assert not jax._src.xla_bridge._backends, "
        "jax._src.xla_bridge._backends"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=100,
    )
    assert proc.returncode == 0, proc.stderr


_CACHE_PROBE = (
    "import jax, _train_common as t; t.enable_compile_cache(); "
    "print(jax.config.jax_compilation_cache_dir)"
)


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_compile_cache_is_one_place(tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing (JAX
    reads the variable itself); unset, the cache is a fixed directory of
    the checkout — the path is part of the cache key."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "elsewhere")
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=100,
    )
    assert proc.returncode == 0, proc.stderr
    want = (
        str(tmp_path / "elsewhere") if placed
        else os.path.join(REPO, ".jax_cache")
    )
    assert proc.stdout.strip() == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
