"""Model FLOP/s utilization: the run's tokens/s/chip times the model
FLOPs per token (flops.py: matmul parameters without the embedding
table, causal attention, nothing recomputed) over the chip's published
bf16 peak. An end-to-end utilization, not a kernel's roofline share."""

from benchmark import flops, readers


def read(run):
    cell = run["cell"]
    per_token = flops.model_flops_per_token(cell.config, int(cell.mix["seq"]))
    return 100.0 * run["tok_s_chip"] * per_token / readers.peak(run, "bf16_flops_per_s")
