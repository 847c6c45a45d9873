"""Step-level MFU/roofline accounting for the trainers' ``TORCHFT_PERF``
step log.

Two layers:

- **Measured cost**: :func:`compiled_cost` reads XLA's own cost analysis
  (flops, bytes accessed) plus memory analysis (temp/arg/output bytes)
  off a lowered+compiled executable, tolerant of backends that return
  lists or partial keys. Known caveat: XLA counts a ``lax.scan`` body
  ONCE, so scanned programs under-report. (A count made from the
  model's shapes is the benchmark's: ``benchmark/arch/*/flops.py``.)
- **Peaks/roofline**: bf16 peak TFLOP/s and HBM GB/s per TPU
  generation, and :func:`roofline` combining achieved FLOP/s with the
  program's arithmetic intensity into an MFU and an attainable-roofline
  fraction.

``record_jit_cost`` is the trainer entry point: gated on the
``TORCHFT_PERF`` knob, it lowers the jitted step once at compile time,
stores the cost in a process-local registry, and journals a
``perf_model`` event so tools/perf_report.py can put MFU next to ms.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from . import knobs
from .telemetry import get_event_log

__all__ = [
    "PEAK_BF16_TFLOPS",
    "PEAK_HBM_GBPS",
    "peak_tflops",
    "peak_hbm_gbps",
    "compiled_cost",
    "perf_enabled",
    "record_jit_cost",
    "step_metrics",
    "get_step_cost",
    "reset_step_costs",
    "roofline",
]

# Published bf16 peak per chip, by device_kind substring (first match
# wins, so "v5p" must precede "v5").
PEAK_BF16_TFLOPS = [
    ("v6", 918.0),  # Trillium
    ("v5p", 459.0),
    ("v5", 197.0),  # v5e / v5 lite
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]

# Published HBM bandwidth per chip (GB/s), for the roofline's memory
# ceiling. Same matching rules as the TFLOP table.
PEAK_HBM_GBPS = [
    ("v6", 1640.0),
    ("v5p", 2765.0),
    ("v5", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
]


def _lookup(table, device_kind: str) -> Optional[float]:
    kind = (device_kind or "").lower()
    for key, val in table:
        if key in kind:
            return val
    if "tpu" in kind:
        # A TPU that is not in the table is an error, not a missing MFU:
        # add its published peaks above.
        raise ValueError(f"no published peak for TPU kind {device_kind!r}")
    return None


def peak_tflops(device_kind: str) -> Optional[float]:
    """bf16 peak TFLOP/s for a jax ``device_kind``; None off-TPU (CPU
    proxy runs report raw FLOP/s but no MFU — there is no honest peak);
    raises for a TPU kind the table does not know."""
    return _lookup(PEAK_BF16_TFLOPS, device_kind)


def peak_hbm_gbps(device_kind: str) -> Optional[float]:
    """HBM GB/s for a jax ``device_kind``; None off-TPU."""
    return _lookup(PEAK_HBM_GBPS, device_kind)


def compiled_cost(compiled) -> Dict[str, Any]:
    """flops/bytes from XLA cost analysis + temp bytes from memory
    analysis, tolerant of backends that return lists or partial keys."""
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # noqa: BLE001 - record, don't die
        out["cost_error"] = str(e)[:120]
    try:
        ma = compiled.memory_analysis()
        out["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
        out["argument_bytes"] = int(
            getattr(ma, "argument_size_in_bytes", 0)
        )
        out["output_bytes"] = int(getattr(ma, "output_size_in_bytes", 0))
    except Exception as e:  # noqa: BLE001
        out["memory_error"] = str(e)[:120]
    return out


def roofline(
    flops: float,
    bytes_accessed: float,
    dt_s: float,
    device_kind: str,
    n_devices: int = 1,
) -> Dict[str, Any]:
    """Achieved FLOP/s vs the device roofline.

    ``mfu`` is achieved / bf16-peak. ``roofline_frac`` is achieved /
    min(peak_flops, AI * peak_bw) — 1.0 means the step runs at whichever
    ceiling (compute or memory) its arithmetic intensity allows, so a
    low MFU with a high roofline_frac says "memory-bound, not slow".
    Off-TPU both are None; tflops_per_s is always reported."""
    out: Dict[str, Any] = {
        "tflops_per_s": (flops / dt_s / 1e12) if dt_s > 0 else None,
        "mfu": None,
        "roofline_frac": None,
        "ai": (flops / bytes_accessed) if bytes_accessed > 0 else None,
    }
    peak_tf = peak_tflops(device_kind)
    if dt_s <= 0 or peak_tf is None:
        return out
    achieved = flops / dt_s  # flops/s
    peak_flops_s = peak_tf * 1e12 * n_devices
    out["mfu"] = achieved / peak_flops_s
    bw = peak_hbm_gbps(device_kind)
    if bw is not None and out["ai"] is not None:
        attainable = min(peak_flops_s, out["ai"] * bw * 1e9 * n_devices)
        if attainable > 0:
            out["roofline_frac"] = achieved / attainable
    return out


# Process-local registry of compile-time step costs, keyed by the name
# the trainer registered ("ddp_step", "diloco_inner_step", ...).
_COST_LOCK = threading.Lock()
_STEP_COSTS: Dict[str, Dict[str, Any]] = {}


# Persistent-compilation-cache hits seen by this process (JAX's own
# monitoring event); the listener is installed on first use and stays.
_cache_hits = 0
_cache_listening = False


def _count_cache_hits() -> int:
    global _cache_listening
    if not _cache_listening:
        import jax.monitoring

        def _on_event(event: str, **_kw: Any) -> None:
            global _cache_hits
            if event == "/jax/compilation_cache/cache_hits":
                _cache_hits += 1

        jax.monitoring.register_event_listener(_on_event)
        _cache_listening = True
    return _cache_hits


def perf_enabled() -> bool:
    return knobs.get_bool("TORCHFT_PERF")


def get_step_cost(name: str) -> Optional[Dict[str, Any]]:
    with _COST_LOCK:
        rec = _STEP_COSTS.get(name)
        return dict(rec) if rec else None


def reset_step_costs() -> None:
    with _COST_LOCK:
        _STEP_COSTS.clear()


def record_jit_cost(
    name: str,
    jitted_fn,
    *args,
    tokens_per_step: Optional[int] = None,
    force: bool = False,
    **kwargs,
) -> Optional[Dict[str, Any]]:
    """Lower+compile ``jitted_fn`` on ``args`` once (the shapes the
    trainer warms up with, so XLA's compile cache absorbs the cost),
    record its FLOPs/bytes, and journal a ``perf_model`` event.

    No-op returning None unless the ``TORCHFT_PERF`` knob is set (or
    ``force``): drills and benches that don't ask for MFU pay nothing.
    Failures degrade to None — perf accounting must never kill a
    trainer."""
    if not (force or perf_enabled()):
        return None
    try:
        import time

        import jax

        hits0 = _count_cache_hits()
        t0 = time.perf_counter()
        compiled = jitted_fn.lower(*args, **kwargs).compile()
        compile_s = time.perf_counter() - t0
        cost = compiled_cost(compiled)
        devs = jax.devices()
        rec: Dict[str, Any] = {
            "name": name,
            "platform": devs[0].platform if devs else "unknown",
            "device_kind": devs[0].device_kind if devs else "unknown",
            "n_devices": len(devs),
            "tokens_per_step": tokens_per_step,
            # Proof of what was compiled, for chip_smoke.py: seconds spent
            # in lower+compile, whether the persistent cache served it, and
            # how many compiled Pallas kernels the program holds (interpret
            # mode lowers to plain HLO and counts 0).
            "compile_s": compile_s,
            "cache_hit": _count_cache_hits() > hits0,
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
            **cost,
        }
    except Exception:  # noqa: BLE001 - accounting is best-effort
        return None
    with _COST_LOCK:
        _STEP_COSTS[name] = rec
    log = get_event_log()
    if log is not None:
        log.emit(
            "perf_model",
            name=name,
            flops=rec.get("flops"),
            bytes_accessed=rec.get("bytes_accessed"),
            temp_bytes=rec.get("temp_bytes"),
            platform=rec["platform"],
            device_kind=rec["device_kind"],
            n_devices=rec["n_devices"],
            tokens_per_step=tokens_per_step,
            compile_s=rec["compile_s"],
            cache_hit=rec["cache_hit"],
            tpu_custom_calls=rec["tpu_custom_calls"],
        )
    return rec


def step_metrics(name: str, dt_s: float) -> Optional[Dict[str, Any]]:
    """MFU/roofline for one wall-clock step of the registered program;
    None when the cost was never recorded (knob off, or lowering
    failed). CPU-proxy honesty: off-TPU ``mfu`` stays None and callers
    should print the raw TFLOP/s instead of inventing a peak."""
    rec = get_step_cost(name)
    if rec is None or dt_s <= 0:
        return None
    flops = float(rec.get("flops") or 0.0)
    out = roofline(
        flops,
        float(rec.get("bytes_accessed") or 0.0),
        dt_s,
        rec.get("device_kind", ""),
        int(rec.get("n_devices") or 1),
    )
    tok = rec.get("tokens_per_step")
    out["tokens_per_s"] = (tok / dt_s) if tok else None
    return out


def format_step_metrics(m: Optional[Dict[str, Any]]) -> str:
    """One-line suffix for trainer step logs: empty when accounting is
    off, else e.g. `` perf[0.42 TF/s mfu=1.2% roofline=3.4%]``."""
    if not m:
        return ""
    parts = []
    if m.get("tflops_per_s") is not None:
        parts.append(f"{m['tflops_per_s']:.3g} TF/s")
    if m.get("mfu") is not None:
        parts.append(f"mfu={m['mfu'] * 100:.2f}%")
    if m.get("roofline_frac") is not None:
        parts.append(f"roofline={m['roofline_frac'] * 100:.1f}%")
    if m.get("tokens_per_s"):
        parts.append(f"{m['tokens_per_s']:.0f} tok/s")
    return f" perf[{' '.join(parts)}]" if parts else ""
