"""Operations and bytes a step of OLMoE's decoder requires, computed from
shapes: the dense decoder's counts with the MLP replaced by a router and
the experts a token is sent to. Model FLOPs count the ACTIVE matmul
parameters (``num_experts_per_tok`` experts, the router, attention, the
head; not the embedding table), causal attention, and nothing
recomputed; ``total_params`` counts every trained value, all experts.

Takes the configuration file's published keys, not a LlamaConfig.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

_dense = cells.arch_module("dense_decoder", "flops")
attention_flops_per_token = _dense.attention_flops_per_token
flash_flops_per_step = _dense.flash_flops_per_step
flash_bytes_per_step = _dense.flash_bytes_per_step


def attention_params(c: Dict[str, Any]) -> int:
    """wq, wk, wv, wo of one block."""
    h = c["hidden_size"]
    d = c.get("head_dim") or h // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def expert_params(c: Dict[str, Any]) -> int:
    """gate, up and down of one expert."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * c["num_experts"]


def active_matmul_params(c: Dict[str, Any]) -> int:
    """Parameters that multiply one token's activations."""
    layer = (
        attention_params(c) + router_params(c)
        + c["num_experts_per_tok"] * expert_params(c)
    )
    return c["num_hidden_layers"] * layer + c["hidden_size"] * c["vocab_size"]


def total_params(c: Dict[str, Any]) -> int:
    """Every trained value: what crosses the replica axis each step. Four
    norms a block (attention, MLP, query, key; the last two as wide as
    their projections) and the final one."""
    h = c["hidden_size"]
    d = c.get("head_dim") or h // c["num_attention_heads"]
    layer = (
        attention_params(c) + router_params(c)
        + c["num_experts"] * expert_params(c)
        + 2 * h + (c["num_attention_heads"] + c["num_key_value_heads"]) * d
    )
    table = h * c["vocab_size"]
    head = 0 if c["tie_word_embeddings"] else table
    return c["num_hidden_layers"] * layer + table + head + h


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return 6.0 * active_matmul_params(c) + attention_flops_per_token(c, seq)


def _assignments(c: Dict[str, Any], batch: int, seq: int) -> int:
    return batch * seq * c["num_experts_per_tok"]


def gmm_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The grouped matmuls of one step, nothing recomputed: gate, up and
    down of every assignment, forward and the two backward products."""
    per_assignment = 2.0 * expert_params(c)  # a multiply-add per weight
    return 3.0 * per_assignment * _assignments(c, batch, seq) * c["num_hidden_layers"]


def gmm_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Each of the three matmuls [R, k] x [E, k, n] -> [R, n] in bf16 reads
    two of (rows in, rows out, weights) and writes the third, once
    forward and twice backward: 3 x 2 B x (R(k + n) + E k n). Under half
    the compute bound's time on a v5e at 2,048 rows an expert."""
    rows = _assignments(c, batch, seq)
    h, i = c["hidden_size"], c["intermediate_size"]
    one = rows * (h + i) + c["num_experts"] * h * i
    return float(3 * 3 * 2 * one * c["num_hidden_layers"])
