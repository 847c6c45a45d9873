"""Operations and bytes a step of Ouro's looped language model requires,
computed from shapes. Model FLOPs count the matmul parameters that multiply
one token's activations, EVERY VISIT of them: ``total_ut_steps`` visits of
each layer's attention projections and feed-forward, as many passes of the
untied head (every step's states go through it for the loss) and of the
exit gate; and the score entries the causal mask keeps, S(S+1)/2 a head,
sequence, layer and step. Not the embedding lookup, the norms or the exit
distribution; nothing recomputed. ``total_params`` counts every trained
value ONCE: a looped layer is one layer's parameters however often it runs.

Takes the configuration file's keys, not a LlamaConfig.
"""

from __future__ import annotations

from typing import Any, Dict


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """W_q and W_o as wide as the heads; W_k and W_v as the key/value heads."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv


def ffn_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: Dict[str, Any]) -> int:
    """A published layer: the attention's four projections, the
    feed-forward's three matrices and four norms (one each side of each
    sub-layer): 51,388,416 at the published widths."""
    return attention_matmul_params(c) + ffn_params(c) + 4 * c["hidden_size"]


def gate_params(c: Dict[str, Any]) -> int:
    """The exit gate: Linear(hidden -> 1) with its bias."""
    return c["hidden_size"] + 1


def total_params(c: Dict[str, Any]) -> int:
    """The layers (once), the final norm, the gate, the table and the head."""
    h = c["hidden_size"]
    return (
        c["num_hidden_layers"] * layer_params(c)
        + h + gate_params(c) + 2 * h * c["vocab_size"]
    )


def layer_visits(c: Dict[str, Any]) -> int:
    """How often a step runs a layer: every layer, every loop step."""
    return c["total_ut_steps"] * c["num_hidden_layers"]


def active_matmul_params(c: Dict[str, Any]) -> int:
    """Parameters that multiply one token's activations, counted a visit."""
    h = c["hidden_size"]
    return (
        layer_visits(c) * (attention_matmul_params(c) + ffn_params(c))
        + c["total_ut_steps"] * (h * c["vocab_size"] + h)
    )


def kept_entries(seq: int) -> int:
    """Score entries the causal mask keeps, a head and sequence."""
    return seq * (seq + 1) // 2


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """QK^T and PV forward over the kept entries a head (2 matmuls x 2 FLOP
    an entry and head width), twice that backward (dQ, dK, dV, dP; the score
    recomputation is the kernel's own and not counted), every layer visit."""
    a_visit = 3.0 * 4.0 * kept_entries(seq) * c["num_attention_heads"] * c["head_dim"] * batch
    return a_visit * layer_visits(c)


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """A visit's bf16 q, k, v read and o written forward; q, k, v, o, do
    read and dq, dk, dv written backward: far under the compute bound's
    time."""
    d = c["head_dim"]
    q = batch * seq * c["num_attention_heads"] * d * 2
    kv = batch * seq * c["num_key_value_heads"] * d * 2
    return float(((2 * q + 2 * kv) + (4 * q + 4 * kv)) * layer_visits(c))


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return 6.0 * active_matmul_params(c) + flash_flops_per_step(c, 1, seq) / seq
