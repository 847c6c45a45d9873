"""Operations and bytes a block-diffusion training step of an SDAR
mixture-of-experts decoder requires, computed from shapes. A step of
``batch`` sequences of ``seq`` data tokens runs TWO streams of ``seq``
positions through the trunk (the noisy x_t and the clean x_0) and the
noisy stream's rows alone through the head; a "token" below is a DATA
token, as ``tok_s_chip`` counts them, so a trunk parameter multiplies two
rows a token. Model FLOPs count the active matmul parameters (the
attention's four projections; the router and the share of a row's experts
that a uniform router sends to the experts held here; the untied head
once; not the embedding lookup) and the score entries the block-diffusion
mask keeps, L^2 + L*b a head and sequence; nothing recomputed.
``total_params`` counts every trained value of the chip's share.

Takes the configuration file's keys, not a LlamaConfig: the file's
``num_experts`` is the number of experts HELD, ``expert_parallel_chips``
times that the router's width (the adapter says so).
"""

from __future__ import annotations

from typing import Any, Dict

STREAMS = 2  # rows through the trunk a data token


def _router_width(c: Dict[str, Any]) -> int:
    return c["num_experts"] * c["expert_parallel_chips"]


def attention_matmul_params(c: Dict[str, Any]) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def attention_params(c: Dict[str, Any]) -> int:
    """The four projections and the two per-head norms' vectors."""
    return attention_matmul_params(c) + 2 * c["head_dim"]


def expert_params(c: Dict[str, Any]) -> int:
    """gate, up and down of one routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * _router_width(c)


def total_params(c: Dict[str, Any]) -> int:
    """A layer: attention, the router over all the experts, the experts
    held, two pre-norms. Then the final norm, the table and the head."""
    h = c["hidden_size"]
    layer = (
        attention_params(c) + router_params(c)
        + c["num_experts"] * expert_params(c) + 2 * h
    )
    return c["num_hidden_layers"] * layer + h + 2 * h * c["vocab_size"]


def active_params(c: Dict[str, Any]) -> int:
    """Of ``total_params``, what one token of a layer-complete model
    meets: its ``num_experts_per_tok`` experts of each layer's."""
    idle = (c["num_experts"] - c["num_experts_per_tok"]) * expert_params(c)
    return total_params(c) - c["num_hidden_layers"] * idle


def held_share(c: Dict[str, Any]) -> float:
    """The share of a row's assignments a uniform router sends here."""
    return c["num_experts"] / _router_width(c)


def kept_entries(c: Dict[str, Any], seq: int) -> int:
    """Score entries the mask keeps, a head and sequence: clean on clean
    (L^2 + L b) / 2, noisy on clean (L^2 - L b) / 2, noisy on noisy L b."""
    return seq * seq + seq * c["block_length"]


def trunk_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters that multiply one ROW's activations in the trunk."""
    expert_layer = (
        router_params(c) + c["num_experts_per_tok"] * held_share(c) * expert_params(c)
    )
    return c["num_hidden_layers"] * (attention_matmul_params(c) + expert_layer)


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The attention of a step over the kept entries: QK^T and PV forward
    (2 matmuls x 2 FLOP an entry and head width), twice that backward
    (dQ, dK, dV, dP; the score recomputation is the kernel's own and not
    counted)."""
    entries = kept_entries(c, seq) * c["num_attention_heads"] * batch
    return 3.0 * 4.0 * entries * c["head_dim"] * c["num_hidden_layers"]


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """bf16 q, k, v, o of both streams read or written once forward and
    q, k, v, o, do read and dq, dk, dv written once backward: far under
    the compute bound's time."""
    d, rows = c["head_dim"], STREAMS * seq * batch
    q = rows * c["num_attention_heads"] * d
    kv = rows * c["num_key_value_heads"] * d
    forward = 2 * (2 * q + 2 * kv)
    backward = 2 * (4 * q + 4 * kv)
    return float((forward + backward) * c["num_hidden_layers"])


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """A data token: both streams' rows through the trunk, one row through
    the head, its share of the kept score entries."""
    attention = flash_flops_per_step(c, 1, seq) / seq
    return (
        6.0 * (STREAMS * trunk_matmul_params(c) + c["hidden_size"] * c["vocab_size"])
        + attention
    )


def _held_rows(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Assignments a step that land on the held experts, a layer:
    ``share`` of those of both streams' rows (what the step counts as
    ``moe_held_share``); a uniform router's share where none is given."""
    share = held_share(c) if share is None else share
    return STREAMS * batch * seq * c["num_experts_per_tok"] * share


def gmm_flops_per_step(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """The grouped matmuls over the held dispatch's filled rows: gate, up
    and down of every assignment that lands here, forward and the two
    backward products, nothing recomputed."""
    rows = _held_rows(c, batch, seq, share)
    return 3.0 * 2.0 * expert_params(c) * rows * c["num_hidden_layers"]


def gmm_bytes_per_step(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Each of the three matmuls [R, k] x [n, k, m] -> [R, m] in bf16 reads
    two of (rows in, rows out, weights) and writes the third, once
    forward and twice backward."""
    rows = _held_rows(c, batch, seq, share)
    h, i = c["hidden_size"], c["moe_intermediate_size"]
    one = rows * (h + i) + c["num_experts"] * h * i
    return float(3 * 3 * 2 * one * c["num_hidden_layers"])
