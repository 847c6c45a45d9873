"""Operations and bytes a step of a JoyAI-LLM-Flash decoder requires,
computed from shapes. Model FLOPs count the ACTIVE matmul parameters a
token (latent attention's five projections in every block, the prediction
modules' blocks among them; the dense feed-forward; an expert layer's
router, its shared experts and the share of a token's routed experts that
a uniform router sends to the experts held here; each module's joining
projection; the untied head once a prediction, so 1 +
``num_nextn_predict_layers`` times; not the embedding lookups) and the
causal scores of every attention layer at the heads' two widths (192-wide
queries and keys, 128-wide values); nothing recomputed.
``total_params`` counts every trained value of the chip's share.

Takes the configuration file's keys, not a LlamaConfig: the file's
``n_routed_experts`` is the number of experts HELD,
``expert_parallel_chips`` times that the router's width (the adapter says
so).
"""

from __future__ import annotations

from typing import Any, Dict


def _router_width(c: Dict[str, Any]) -> int:
    return c["n_routed_experts"] * c["expert_parallel_chips"]


def _modules(c: Dict[str, Any]) -> int:
    return c["num_nextn_predict_layers"]


def _expert_layers(c: Dict[str, Any]) -> int:
    """Layers with experts: the stack's after the leading dense ones and
    one a prediction module."""
    return c["num_hidden_layers"] - c["first_k_dense_replace"] + _modules(c)


def attention_layers(c: Dict[str, Any]) -> int:
    """One a layer of the stack and one a prediction module."""
    return c["num_hidden_layers"] + _modules(c)


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one latent attention."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    return (
        h * rq + rq * heads * c["qk_head_dim"]
        + h * (rkv + c["qk_rope_head_dim"])
        + rkv * heads * (c["qk_nope_head_dim"] + c["v_head_dim"])
        + heads * c["v_head_dim"] * h
    )


def attention_params(c: Dict[str, Any]) -> int:
    """The five projections and the two bottlenecks' norms."""
    return attention_matmul_params(c) + c["q_lora_rank"] + c["kv_lora_rank"]


def dense_ffn_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict[str, Any]) -> int:
    """gate, up and down of one routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: Dict[str, Any]) -> int:
    """The shared experts of a layer: one expert of their summed width."""
    return c["n_shared_experts"] * expert_params(c)


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * _router_width(c)


def expert_ffn_params(c: Dict[str, Any]) -> int:
    """The held experts, the shared ones, the router over all the experts
    and its selection bias."""
    return (
        c["n_routed_experts"] * expert_params(c) + shared_params(c)
        + router_params(c) + _router_width(c)
    )


def module_params(c: Dict[str, Any]) -> int:
    """A prediction module beside its block: the joining projection and
    the norms of its two inputs."""
    h = c["hidden_size"]
    return 2 * h * h + 2 * h


def total_params(c: Dict[str, Any]) -> int:
    """Every block two pre-norms; then the final norm, the table and the
    head (both shared with the modules)."""
    h = c["hidden_size"]
    return (
        attention_layers(c) * (attention_params(c) + 2 * h)
        + c["first_k_dense_replace"] * dense_ffn_params(c)
        + _expert_layers(c) * expert_ffn_params(c)
        + _modules(c) * module_params(c)
        + h + 2 * h * c["vocab_size"]
    )


def active_params(c: Dict[str, Any]) -> int:
    """What one token of a layer-complete model meets in the stack's
    blocks, its ``num_experts_per_tok`` routed experts of each layer's:
    the table, the head and the prediction modules not counted (the
    published "A2.7B" counts so)."""
    h = c["hidden_size"]
    expert_layer = (
        c["num_experts_per_tok"] * expert_params(c) + shared_params(c)
        + router_params(c) + _router_width(c)
    )
    return (
        c["num_hidden_layers"] * (attention_params(c) + 2 * h)
        + c["first_k_dense_replace"] * dense_ffn_params(c)
        + (c["num_hidden_layers"] - c["first_k_dense_replace"]) * expert_layer
    )


def held_share(c: Dict[str, Any]) -> float:
    """The share of a token's assignments a uniform router sends here."""
    return c["n_routed_experts"] / _router_width(c)


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activations on this chip, the
    head once a prediction."""
    expert_layer = (
        router_params(c) + shared_params(c)
        + c["num_experts_per_tok"] * held_share(c) * expert_params(c)
    )
    h = c["hidden_size"]
    return (
        attention_layers(c) * attention_matmul_params(c)
        + c["first_k_dense_replace"] * dense_ffn_params(c)
        + _expert_layers(c) * expert_layer
        + _modules(c) * 2 * h * h
        + (1 + _modules(c)) * h * c["vocab_size"]
    )


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The causal attention of a step: seq^2 / 2 score entries a head and
    sequence; forward QK^T over ``qk_head_dim`` and PV over ``v_head_dim``
    (2 FLOP a multiply-add), backward dQ and dK over the first and dP and
    dV over the second: three times forward. The score recomputation is
    the kernel's own and not counted, nor what a kernel pads."""
    entries = seq * seq / 2.0 * c["num_attention_heads"] * batch
    forward = 2.0 * (c["qk_head_dim"] + c["v_head_dim"])
    return 3.0 * forward * entries * attention_layers(c)


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """bf16, once each: forward q, k_nope, v read, the one rotary key a
    position read, o written; backward q, k_nope, v, o, do and the rotary
    key read and dq, dk_nope, dv and the rotary key's gradient written.
    Far under the compute bound's time."""
    rows, heads = batch * seq, c["num_attention_heads"]
    q = rows * heads * c["qk_head_dim"]
    k = rows * heads * c["qk_nope_head_dim"]
    v = rows * heads * c["v_head_dim"]
    shared = rows * c["qk_rope_head_dim"]
    forward = q + k + shared + 2 * v
    backward = 2 * q + 2 * k + 2 * shared + 4 * v
    return float(2 * (forward + backward) * attention_layers(c))


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return 6.0 * active_matmul_params(c) + flash_flops_per_step(c, 1, seq) / seq


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
    one = rows * (h + i) + c["n_routed_experts"] * h * i
    return float(3 * 3 * 2 * one * _expert_layers(c))
