"""Operations and bytes a step of a Nemotron-H hybrid requires, computed
from shapes. Model FLOPs count the ACTIVE matmul parameters a token (both
projections of a Mamba-2 mixer; attention; the router, the shared expert
and the share of its six experts that a uniform router sends to the
experts held here; the head; not the embedding table), the causal
scores of the attention layers and the chunked state-space scan; nothing
recomputed. ``total_params`` counts every trained value of the chip's
share.

Takes the configuration file's keys, not a LlamaConfig: the file's
``n_routed_experts`` is the number of experts HELD, ``expert_parallel_chips``
times that the router's width (the adapter says so).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

_dense = cells.arch_module("dense_decoder", "flops")


def _count(c: Dict[str, Any], kind: str) -> int:
    return c["hybrid_override_pattern"].count(kind)


def _d_inner(c: Dict[str, Any]) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def _conv_dim(c: Dict[str, Any]) -> int:
    return _d_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def _router_width(c: Dict[str, Any]) -> int:
    return c["n_routed_experts"] * c["expert_parallel_chips"]


def mamba_matmul_params(c: Dict[str, Any]) -> int:
    """in_proj ([z | xBC | dt]) and out_proj of one mixer."""
    h = c["hidden_size"]
    return h * (_d_inner(c) + _conv_dim(c) + c["mamba_num_heads"]) + _d_inner(c) * h


def mamba_params(c: Dict[str, Any]) -> int:
    """One Mamba-2 layer: the projections, the convolution's kernel and
    bias, A_log, D and dt_bias a head, the gated norm's weight, the pre-norm."""
    return (
        mamba_matmul_params(c) + (c["conv_kernel"] + 1) * _conv_dim(c)
        + 3 * c["mamba_num_heads"] + _d_inner(c) + c["hidden_size"]
    )


def attention_matmul_params(c: Dict[str, Any]) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def expert_params(c: Dict[str, Any]) -> int:
    """up and down of one routed expert."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: Dict[str, Any]) -> int:
    return 2 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * _router_width(c)


def expert_layer_params(c: Dict[str, Any]) -> int:
    """The held experts, the shared expert, the router over all the
    experts, its selection bias, the pre-norm."""
    return (
        c["n_routed_experts"] * expert_params(c) + shared_params(c)
        + router_params(c) + _router_width(c) + c["hidden_size"]
    )


def total_params(c: Dict[str, Any]) -> int:
    h = c["hidden_size"]
    return (
        _count(c, "M") * mamba_params(c)
        + _count(c, "*") * (attention_matmul_params(c) + h)
        + _count(c, "E") * expert_layer_params(c)
        + 2 * h * c["vocab_size"] + h
    )


def held_share(c: Dict[str, Any]) -> float:
    """The share of a token's assignments a uniform router sends here."""
    return c["n_routed_experts"] / _router_width(c)


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activations on this chip."""
    expert_layer = (
        router_params(c) + shared_params(c)
        + c["num_experts_per_tok"] * held_share(c) * expert_params(c)
    )
    return (
        _count(c, "M") * mamba_matmul_params(c)
        + _count(c, "*") * attention_matmul_params(c)
        + _count(c, "E") * expert_layer
        + c["hidden_size"] * c["vocab_size"]
    )


def _attention_view(c: Dict[str, Any]) -> Dict[str, Any]:
    """The keys the dense decoder's attention counts read, for the
    pattern's attention layers alone."""
    return {
        "num_hidden_layers": _count(c, "*"),
        **{k: c[k] for k in ("hidden_size", "head_dim", "num_attention_heads",
                             "num_key_value_heads")},
    }


def ssd_flops_per_token(c: Dict[str, Any]) -> float:
    """One mixer's chunked scan, forward, a token (arXiv:2405.21060
    section 6; a multiply-add is two): inside the chunk the scores C.B
    and their product with x over the causal half of a chunk's Q
    positions, Q/2 (G N + H P) multiply-adds; the token's write to its
    chunk's state and its read of the entering state, H P N each."""
    q, g, n = c["chunk_size"], c["n_groups"], c["ssm_state_size"]
    hp = _d_inner(c)
    return 2.0 * (q / 2 * (g * n + hp) + 2 * hp * n)


def ssd_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The scans of one step: forward and twice that backward."""
    return 3.0 * ssd_flops_per_token(c) * batch * seq * _count(c, "M")


def ssd_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What a scan that kept everything else on the chip would move: x, B
    and C in bf16 and dt in float32 read, y written in bf16, forward;
    those and y's gradient read and the four gradients written, backward.
    Above the compute bound's time on a v5e: the scan is memory-bound."""
    ins = 2 * _conv_dim(c) + 4 * c["mamba_num_heads"]  # x, B, C, dt a token
    y = 2 * _d_inner(c)
    forward = ins + y
    backward = ins + y + ins
    return float((forward + backward) * batch * seq * _count(c, "M"))


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return (
        6.0 * active_matmul_params(c)
        + _dense.attention_flops_per_token(_attention_view(c), seq)
        + 3.0 * ssd_flops_per_token(c) * _count(c, "M")
    )


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_flops_per_step(_attention_view(c), batch, seq)


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_bytes_per_step(_attention_view(c), batch, seq)
