"""Operations and bytes a step of an LFM2 mixture-of-experts decoder
requires, computed from shapes. Model FLOPs count the ACTIVE matmul
parameters a token (both projections of a short-convolution operator; the
attention's four; the dense feed-forward; an expert layer's router and the
share of its experts a token that a uniform router sends to the experts
held here; the tied head; not the embedding lookup), the causal scores of
the attention layers and the short convolutions' elementwise products;
nothing recomputed. ``total_params`` counts every trained value of the
chip's share, the tied table once.

Takes the configuration file's keys, not a LlamaConfig: the file's
``num_experts`` is the number of experts HELD, ``expert_parallel_chips``
times that the router's width, ``layer_types`` and ``num_dense_layers``
those of the layers run (the adapter says so).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

_dense = cells.arch_module("dense_decoder", "flops")


def _count(c: Dict[str, Any], kind: str) -> int:
    return c["layer_types"].count(kind)


def _expert_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def _head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def _router_width(c: Dict[str, Any]) -> int:
    return c["num_experts"] * c["expert_parallel_chips"]


def conv_matmul_params(c: Dict[str, Any]) -> int:
    """in_proj (hidden to [B | C | u]) and out_proj of one operator."""
    h = c["hidden_size"]
    return h * 3 * h + h * h


def conv_params(c: Dict[str, Any]) -> int:
    """The projections and the depthwise kernel of ``conv_L_cache`` taps."""
    return conv_matmul_params(c) + c["conv_L_cache"] * c["hidden_size"]


def attention_matmul_params(c: Dict[str, Any]) -> int:
    h, d = c["hidden_size"], _head_dim(c)
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def attention_params(c: Dict[str, Any]) -> int:
    """The four projections and the two per-head norms' vectors."""
    return attention_matmul_params(c) + 2 * _head_dim(c)


def dense_ffn_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict[str, Any]) -> int:
    """gate, up and down of one routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * _router_width(c)


def expert_ffn_params(c: Dict[str, Any]) -> int:
    """The held experts, the router over all the experts, its selection bias."""
    return c["num_experts"] * expert_params(c) + router_params(c) + _router_width(c)


def total_params(c: Dict[str, Any]) -> int:
    """Two pre-norms a layer, the final norm, the tied table once."""
    h = c["hidden_size"]
    return (
        _count(c, "conv") * conv_params(c)
        + _count(c, "full_attention") * attention_params(c)
        + c["num_dense_layers"] * dense_ffn_params(c)
        + _expert_layers(c) * expert_ffn_params(c)
        + 2 * c["num_hidden_layers"] * h
        + h * c["vocab_size"] + h
    )


def held_share(c: Dict[str, Any]) -> float:
    """The share of a token's assignments a uniform router sends here."""
    return c["num_experts"] / _router_width(c)


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activations on this chip."""
    expert_layer = (
        router_params(c) + c["num_experts_per_tok"] * held_share(c) * expert_params(c)
    )
    return (
        _count(c, "conv") * conv_matmul_params(c)
        + _count(c, "full_attention") * attention_matmul_params(c)
        + c["num_dense_layers"] * dense_ffn_params(c)
        + _expert_layers(c) * expert_layer
        + c["hidden_size"] * c["vocab_size"]
    )


def _attention_view(c: Dict[str, Any]) -> Dict[str, Any]:
    """The keys the dense decoder's attention counts read, for the
    attention layers alone, at this model's head width."""
    return {
        "num_hidden_layers": _count(c, "full_attention"),
        "head_dim": _head_dim(c),
        **{k: c[k] for k in ("hidden_size", "num_attention_heads",
                             "num_key_value_heads")},
    }


def short_conv_flops_per_token(c: Dict[str, Any]) -> float:
    """One operator between its projections, a token, forward: B*u, the
    taps' L multiplies and L-1 adds, C*w, a channel."""
    return float((2 * c["conv_L_cache"] + 1) * c["hidden_size"])


def short_conv_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and the backward pass's products (the two gates' and the
    taps' transposes and the kernel's gradient: about twice forward)."""
    return 3.0 * short_conv_flops_per_token(c) * batch * seq * _count(c, "conv")


def short_conv_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What a fused pass between the projections would move, in bf16:
    forward, in_proj's 3H-wide output read once and the H-wide product
    written once; backward, those 3H and the product's gradient read and
    the 3H-wide gradient written. Nothing recomputed is counted, nothing
    kept in float32. Far above the compute bound's time: memory-bound."""
    h = c["hidden_size"]
    forward = 2 * (3 * h + h)
    backward = 2 * (3 * h + h + 3 * h)
    return float((forward + backward) * batch * seq * _count(c, "conv"))


def _held_rows(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Assignments a step that land on the held experts: ``share`` of them
    all, a layer (what the step counts as ``moe_held_share``); a uniform
    router's share where none is given."""
    share = held_share(c) if share is None else share
    return batch * seq * c["num_experts_per_tok"] * share


def gmm_flops_per_step(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """The grouped matmuls over the held dispatch's filled rows: gate, up
    and down of every assignment that lands here, forward and the two
    backward products, nothing recomputed."""
    rows = _held_rows(c, batch, seq, share)
    return 3.0 * 2.0 * expert_params(c) * rows * _expert_layers(c)


def gmm_bytes_per_step(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Each of the three matmuls [R, k] x [n, k, m] -> [R, m] in bf16 reads
    two of (rows in, rows out, weights) and writes the third, once
    forward and twice backward."""
    rows = _held_rows(c, batch, seq, share)
    h, i = c["hidden_size"], c["moe_intermediate_size"]
    one = rows * (h + i) + c["num_experts"] * h * i
    return float(3 * 3 * 2 * one * _expert_layers(c))


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return (
        6.0 * active_matmul_params(c)
        + _dense.attention_flops_per_token(_attention_view(c), seq)
        + 3.0 * short_conv_flops_per_token(c) * _count(c, "conv")
    )


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_flops_per_step(_attention_view(c), batch, seq)


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_bytes_per_step(_attention_view(c), batch, seq)
