"""The grouped matmuls' share of their roofline in an expert layer of
three-matrix SiLU-gated experts that holds a share of them: the least time
the chip needs for the matmuls of the rows the traced steps really filled
(the architecture's flops.py: ``gmm_flops_per_step`` / ``gmm_bytes_per_step``
at the mean ``moe_held_share`` those steps counted, so a router that sends
more or fewer rows here than a uniform one moves the work with the time;
three matmuls an assignment, forward and the two backward products,
nothing recomputed; compute-bound at 2,048 rows an expert) over the device
time of XLA's ``ragged-dot`` kernels (``moe_gmm_ms``'s pattern, which
times remat's second forward too). ``moe_gmm_roofline`` reads the same of
a layer that holds all its experts. None where the configuration holds all
its experts, the step counts no held share or the trace has no such
kernel."""

from benchmark import readers
from benchmark.metrics import moe_gmm_ms


def traced_held_share(run):
    """Mean over the traced steps of the step's own ``moe_held_share``."""
    shares = [
        r["counters"]["moe_held_share"] for r in run["records"]
        if r.get("traced") and "moe_held_share" in r.get("counters", {})
    ]
    return sum(shares) / len(shares) if shares else None


def read(run):
    if "expert_parallel_chips" not in run["cell"].config:
        return None
    ms, share = moe_gmm_ms.read(run), traced_held_share(run)
    if ms is None or share is None:
        return None
    mix = run["cell"].mix
    b, s = int(mix["batch"]), int(mix["seq"])
    ops = readers.kernel_work(run, "gmm_flops_per_step", b, s, share)
    nbytes = readers.kernel_work(run, "gmm_bytes_per_step", b, s, share)
    if ops is None or nbytes is None:
        return None
    least = max(
        ops / readers.peak(run, "bf16_flops_per_s"),
        nbytes / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
