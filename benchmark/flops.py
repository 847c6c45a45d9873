"""Operations and bytes a step requires, computed from shapes.

The yardstick for `mfu_pct` and the kernels' roofline shares. Model FLOPs
are what the forward and backward passes of the published mathematics
need: every matrix multiplication's parameters except the embedding table
(a lookup, not a matmul), causal attention, and nothing recomputed. The
program's own `perf.flops_per_step` (6 x all parameters) counts the table
and would count inactive experts, and XLA's cost analysis counts remat's
recomputation and gives Pallas calls nothing: neither is used here.

Takes the configuration file's published keys, not a LlamaConfig.
"""

from __future__ import annotations

from typing import Any, Dict


def _head_dim(c: Dict[str, Any]) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_matmul_params(c: Dict[str, Any]) -> int:
    """wq, wk, wv, wo and the three SwiGLU projections of one block."""
    h, d = c["hidden_size"], _head_dim(c)
    q = c["num_attention_heads"] * d
    kv = c["num_key_value_heads"] * d
    attn = h * q + 2 * h * kv + q * h
    return attn + 3 * h * c["intermediate_size"]


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters that multiply activations: the blocks and the output
    head (tied or not, the head is a matmul; the input table is not)."""
    return (
        c["num_hidden_layers"] * layer_matmul_params(c)
        + c["hidden_size"] * c["vocab_size"]
    )


def total_params(c: Dict[str, Any]) -> int:
    """Every trained value: what crosses the replica axis each step."""
    h = c["hidden_size"]
    table = h * c["vocab_size"]
    head = 0 if c["tie_word_embeddings"] else table
    norms = (2 * c["num_hidden_layers"] + 1) * h
    return c["num_hidden_layers"] * layer_matmul_params(c) + table + head + norms


def attention_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Causal attention, forward and backward, per token: QK^T and PV are
    each 2*seq*width multiply-adds' worth per query over the full square,
    half of it under the causal mask; backward is twice forward."""
    width = c["num_attention_heads"] * _head_dim(c)
    forward = 2.0 * seq * width  # two matmuls x (2 * seq * width / 2)
    return 3.0 * forward * c["num_hidden_layers"]


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return 6.0 * matmul_params(c) + attention_flops_per_token(c, seq)


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What the attention kernels of one step must compute (no
    recomputation): the attention term of the model FLOPs, all tokens."""
    return attention_flops_per_token(c, seq) * batch * seq


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """q, k, v read and o written in bf16 forward; q, k, v, o, do read and
    dq, dk, dv written backward. Far below the compute bound."""
    d = _head_dim(c)
    q = batch * seq * c["num_attention_heads"] * d * 2
    kv = batch * seq * c["num_key_value_heads"] * d * 2
    forward = 2 * q + 2 * kv
    backward = 4 * q + 4 * kv
    return float((forward + backward) * c["num_hidden_layers"])


QUANT_BLOCK = 512  # values per scale, ops/quantization.py BLOCK


def quant_bytes_per_step(c: Dict[str, Any], bits: int = 8) -> float:
    """HBM bytes the device quantize path must move per step: quantize
    reads the fp32 gradient and writes the payload and one fp32 scale per
    block; dequantize reads those and writes fp32."""
    n = total_params(c)
    payload = n * bits / 8 + 4 * n / QUANT_BLOCK
    return 2.0 * (4 * n + payload)
