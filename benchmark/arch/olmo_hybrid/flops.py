"""Operations and bytes a step of an Olmo-Hybrid model requires, computed
from shapes. Model FLOPs count the matmul parameters a token (a
gated-delta mixer's seven projections, the attention's four, the SwiGLU
feed-forward, the head; not the embedding table), the causal scores of the
full-attention layers, the chunked delta rule and the short convolution;
nothing recomputed. ``total_params`` counts every trained value of the
chip's share.

Takes the configuration file's keys, not a LlamaConfig: the file's four
head counts are the heads HELD, ``head_parallel_chips`` times that the
published count (the adapter says so), and a head is ``hidden_size`` over
the published count wide.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

_dense = cells.arch_module("dense_decoder", "flops")

CHUNK = 64  # the chunk of the algorithm that is counted (flash-linear-attention's)


def _count(c: Dict[str, Any], kind: str) -> int:
    return c["layer_types"].count(kind)


def _head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // (c["num_attention_heads"] * c["head_parallel_chips"])


def _key_dim(c: Dict[str, Any]) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def _value_dim(c: Dict[str, Any]) -> int:
    return c["linear_num_value_heads"] * c["linear_value_head_dim"]


def _conv_dim(c: Dict[str, Any]) -> int:
    return 2 * _key_dim(c) + _value_dim(c)


def linear_matmul_params(c: Dict[str, Any]) -> int:
    """W_q, W_k, W_v, W_g, W_a, W_b and W_o of one gated-delta mixer."""
    h, heads = c["hidden_size"], c["linear_num_key_heads"]
    return h * (2 * _key_dim(c) + 2 * _value_dim(c) + 2 * heads) + _value_dim(c) * h


def linear_params(c: Dict[str, Any]) -> int:
    """One mixer: the projections, the convolution's taps, A_log and
    dt_bias a head, the per-head norm's one vector."""
    return (
        linear_matmul_params(c) + c["linear_conv_kernel_dim"] * _conv_dim(c)
        + 2 * c["linear_num_key_heads"] + c["linear_value_head_dim"]
    )


def attention_matmul_params(c: Dict[str, Any]) -> int:
    h, d = c["hidden_size"], _head_dim(c)
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def attention_params(c: Dict[str, Any]) -> int:
    """The four projections and the two whole-projection QK-norms."""
    d = _head_dim(c)
    return attention_matmul_params(c) + (
        c["num_attention_heads"] + c["num_key_value_heads"]
    ) * d


def mlp_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters that multiply one token's activations on this chip."""
    return (
        _count(c, "linear_attention") * linear_matmul_params(c)
        + _count(c, "full_attention") * attention_matmul_params(c)
        + c["num_hidden_layers"] * mlp_params(c)
        + c["hidden_size"] * c["vocab_size"]
    )


def total_params(c: Dict[str, Any]) -> int:
    """Mixers, feed-forwards, a norm after each, the table, the head and
    the final norm."""
    h = c["hidden_size"]
    return (
        _count(c, "linear_attention") * linear_params(c)
        + _count(c, "full_attention") * attention_params(c)
        + c["num_hidden_layers"] * (mlp_params(c) + 2 * h)
        + 2 * h * c["vocab_size"] + h
    )


def _attention_view(c: Dict[str, Any]) -> Dict[str, Any]:
    """The keys the dense decoder's attention counts read, for the
    full-attention layers alone."""
    return {
        "num_hidden_layers": _count(c, "full_attention"),
        "head_dim": _head_dim(c),
        **{k: c[k] for k in ("hidden_size", "num_attention_heads",
                             "num_key_value_heads")},
    }


def gdn_flops_per_token(c: Dict[str, Any]) -> float:
    """One mixer's chunked delta rule, forward, a token (a multiply-add is
    two), in chunks of C = 64 and over the causal half of a chunk where a
    product is triangular: K K^T, Q K^T and W = T (..K) at C/2 d_k a head
    each; U = T (..V) and (Q K^T) V' at C/2 d_v each; T = (I + A)^-1 by
    substitution, C^2/6; and a token's three passes over its chunk's
    entering state, W S, Q S and K^T V', d_k d_v each."""
    heads = c["linear_num_key_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    half = CHUNK / 2
    macs = 3 * half * dk + 2 * half * dv + CHUNK * CHUNK / 6 + 3 * dk * dv
    return 2.0 * macs * heads


def conv_flops_per_token(c: Dict[str, Any]) -> float:
    """One mixer's depthwise convolution over [q | k | v], forward."""
    return 2.0 * c["linear_conv_kernel_dim"] * _conv_dim(c)


def gdn_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The delta rules of one step: forward and twice that backward."""
    return 3.0 * gdn_flops_per_token(c) * batch * seq * _count(c, "linear_attention")


def gdn_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What a delta rule that kept everything else on the chip would move:
    q, k and v in bf16 and g and beta in float32 read, o written in bf16,
    forward; those and o's gradient read and the five gradients written,
    backward. Above the compute bound's time on a v5e: memory-bound."""
    ins = 2 * _conv_dim(c) + 2 * 4 * c["linear_num_key_heads"]  # q, k, v, g, beta
    out = 2 * _value_dim(c)
    forward = ins + out
    backward = ins + out + ins
    return float((forward + backward) * batch * seq * _count(c, "linear_attention"))


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return (
        6.0 * matmul_params(c)
        + _dense.attention_flops_per_token(_attention_view(c), seq)
        + 3.0 * (gdn_flops_per_token(c) + conv_flops_per_token(c))
        * _count(c, "linear_attention")
    )


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_flops_per_step(_attention_view(c), batch, seq)


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_bytes_per_step(_attention_view(c), batch, seq)
