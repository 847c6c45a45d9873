"""Operations and bytes a step of an AFMoE decoder requires, computed from
shapes. Model FLOPs count the ACTIVE matmul parameters a token (the
attention's five projections, the output gate's among them; the dense
feed-forward; the router, the shared expert and the share of a token's
experts that a uniform router sends to the experts held here; the untied
head; not the embedding lookup, its scale, the norms or the gate's
elementwise product) and the score entries the masks KEEP: S(S+1)/2 a head
and sequence in a global layer, the band's w(w+1)/2 + (S-w)w in a sliding
one. Never the tiles a kernel runs, so a share of this work cannot pass 100
by skipping less; nothing recomputed. ``total_params`` counts every trained
value of the chip's share.

Takes the configuration file's keys, not a LlamaConfig: the file's
``num_experts`` is the number of experts HELD, ``expert_parallel_chips``
times that the router's width (the adapter says so).
"""

from __future__ import annotations

from typing import Any, Dict


def _router_width(c: Dict[str, Any]) -> int:
    return c["num_experts"] * c["expert_parallel_chips"]


def window_layers(c: Dict[str, Any]) -> int:
    return c["layer_types"].count("sliding_attention")


def global_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - window_layers(c)


def expert_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """W_q, W_g and W_o as wide as the heads; W_k and W_v as the key/value heads."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 3 * h * q + 2 * h * kv


def dense_ffn_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict[str, Any]) -> int:
    """gate, up and down of one routed expert (and of the shared one)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * _router_width(c)


def total_params(c: Dict[str, Any]) -> int:
    """An attention sub-layer: the five projections, the two per-head QK
    norms, a norm before and one after. A feed-forward sub-layer: the dense
    matrices, or the router with its selection bias, the experts held and
    the shared one; a norm before and one after. Then the final norm, the
    table and the head."""
    h = c["hidden_size"]
    attention = attention_matmul_params(c) + 2 * c["head_dim"] + 2 * h
    sparse = (
        router_params(c) + _router_width(c)
        + (c["num_experts"] + c["num_shared_experts"]) * expert_params(c) + 2 * h
    )
    return (
        c["num_hidden_layers"] * attention
        + c["num_dense_layers"] * (dense_ffn_params(c) + 2 * h)
        + expert_layers(c) * sparse
        + h + 2 * h * c["vocab_size"]
    )


def held_share(c: Dict[str, Any]) -> float:
    """The share of a token's assignments a uniform router sends here."""
    return c["num_experts"] / _router_width(c)


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activations on this chip."""
    sparse = router_params(c) + (
        c["num_shared_experts"] + c["num_experts_per_tok"] * held_share(c)
    ) * expert_params(c)
    return (
        c["num_hidden_layers"] * attention_matmul_params(c)
        + c["num_dense_layers"] * dense_ffn_params(c)
        + expert_layers(c) * sparse
        + c["hidden_size"] * c["vocab_size"]
    )


def global_kept_entries(seq: int) -> int:
    """Score entries the causal mask keeps, a head and sequence."""
    return seq * (seq + 1) // 2


def window_kept_entries(c: Dict[str, Any], seq: int) -> int:
    """Score entries the band keeps, a head and sequence: row i keeps
    min(i + 1, w) keys (31,458,304 at 16,384 under 2,048)."""
    w = min(c["sliding_window"], seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _score_flops(c: Dict[str, Any], entries: int, batch: int) -> float:
    """QK^T and PV forward over ``entries`` kept entries a head (2 matmuls
    x 2 FLOP an entry and head width), twice that backward (dQ, dK, dV, dP;
    the score recomputation is the kernel's own and not counted)."""
    return 3.0 * 4.0 * entries * c["num_attention_heads"] * c["head_dim"] * batch


def _qkvo_bytes(c: Dict[str, Any], batch: int, seq: int) -> float:
    """One layer's bf16 q, k, v read and o written forward; q, k, v, o, do
    read and dq, dk, dv written backward: far under the compute bound's
    time."""
    d = c["head_dim"]
    q = batch * seq * c["num_attention_heads"] * d * 2
    kv = batch * seq * c["num_key_value_heads"] * d * 2
    return float((2 * q + 2 * kv) + (4 * q + 4 * kv))


def swa_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The sliding layers' attention alone, over the band's kept entries."""
    return _score_flops(c, window_kept_entries(c, seq), batch) * window_layers(c)


def swa_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _qkvo_bytes(c, batch, seq) * window_layers(c)


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Every attention layer's, global (the causal family's) and sliding."""
    return (
        _score_flops(c, global_kept_entries(seq), batch) * global_layers(c)
        + swa_flops_per_step(c, batch, seq)
    )


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _qkvo_bytes(c, batch, seq) * c["num_hidden_layers"]


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return 6.0 * active_matmul_params(c) + flash_flops_per_step(c, 1, seq) / seq


def _held_rows(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Assignments a step that land on the held experts, a layer:
    ``share`` of them all (what the step counts as ``moe_held_share``); a
    uniform router's share where none is given."""
    share = held_share(c) if share is None else share
    return batch * seq * c["num_experts_per_tok"] * share


def gmm_flops_per_step(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """The grouped matmuls over the held dispatch's filled rows: gate, up
    and down of every assignment that lands here, forward and the two
    backward products, nothing recomputed. The shared expert's matmuls are
    plain ones and not in it."""
    rows = _held_rows(c, batch, seq, share)
    return 3.0 * 2.0 * expert_params(c) * rows * expert_layers(c)


def gmm_bytes_per_step(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Each of the three matmuls [R, k] x [n, k, m] -> [R, m] in bf16 reads
    two of (rows in, rows out, weights) and writes the third, once
    forward and twice backward."""
    rows = _held_rows(c, batch, seq, share)
    h, i = c["hidden_size"], c["moe_intermediate_size"]
    one = rows * (h + i) + c["num_experts"] * h * i
    return float(3 * 3 * 2 * one * expert_layers(c))
