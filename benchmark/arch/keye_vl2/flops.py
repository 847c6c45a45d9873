"""Operations and bytes a training step of Keye-VL-2.0's language model
requires, computed from shapes. Model FLOPs count the active matmul
parameters (the attention's four projections and the indexer's three; the
router and the share of a token's experts that a uniform router sends to
the experts held here; the untied head; not the embedding lookup), the
SELECTED score entries of the attention (a row t keeps min(topk, t + 1) of
its keys: the architecture requires no other entry of the main attention)
and ALL causal entries of the indexer's score pass (every query scores
every earlier key: that is the mechanism); nothing recomputed.
``total_params`` counts every trained value of the chip's share.

Takes the configuration file's keys, not a LlamaConfig: the file's
``num_experts`` is the number of experts HELD, ``expert_parallel_chips``
times that the router's width (the adapter says so).
"""

from __future__ import annotations

from typing import Any, Dict


def _router_width(c: Dict[str, Any]) -> int:
    return c["num_experts"] * c["expert_parallel_chips"]


def attention_matmul_params(c: Dict[str, Any]) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def indexer_matmul_params(c: Dict[str, Any]) -> int:
    """W_qI [H, 16 x 64], W_kI [H, 64] and W_w [H, 16]."""
    sa, h = c["sa_config"], c["hidden_size"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return h * heads * width + h * width + h * heads


def attention_params(c: Dict[str, Any]) -> int:
    """The four projections, the two per-head norms' vectors, the
    indexer's three matrices and its LayerNorm's scale and bias."""
    return (
        attention_matmul_params(c) + 2 * c["head_dim"]
        + indexer_matmul_params(c) + 2 * c["sa_config"]["indexer_head_dim"]
    )


def expert_params(c: Dict[str, Any]) -> int:
    """gate, up and down of one routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * _router_width(c)


def total_params(c: Dict[str, Any]) -> int:
    """A layer: attention with its indexer, the router over all the
    experts, the experts held, two pre-norms. Then the final norm, the
    table and the head."""
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
    """The share of a token's assignments a uniform router sends here."""
    return c["num_experts"] / _router_width(c)


def causal_entries(seq: int) -> int:
    return seq * (seq + 1) // 2


def selected_entries(c: Dict[str, Any], seq: int) -> int:
    """Score entries the selection keeps, a head and sequence: row t keeps
    min(topk, t + 1) keys (31,458,304 of the causal 134,225,920 at
    16,384 under topk = 2,048: 23.4%)."""
    k = min(c["sa_config"]["topk"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def trunk_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activations in the trunk."""
    expert_layer = (
        router_params(c) + c["num_experts_per_tok"] * held_share(c) * expert_params(c)
    )
    return c["num_hidden_layers"] * (
        attention_matmul_params(c) + indexer_matmul_params(c) + expert_layer
    )


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The attention of a step over the SELECTED entries: QK^T and PV
    forward (2 matmuls x 2 FLOP an entry and head width), twice that
    backward (dQ, dK, dV, dP; the score recomputation is the kernel's own
    and not counted). The kernels compute every causal tile that holds a
    selected entry, all of them under a scattered selection, so their
    share of this roofline reads low by construction."""
    entries = selected_entries(c, seq) * c["num_attention_heads"] * batch
    return 3.0 * 4.0 * entries * c["head_dim"] * c["num_hidden_layers"]


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """bf16 q, k, v, o read or written once forward and q, k, v, o, do read
    and dq, dk, dv written once backward, and the packed selection (a bit
    an entry of the square) read once each way: far under the compute
    bound's time."""
    d, rows = c["head_dim"], seq * batch
    q = rows * c["num_attention_heads"] * d
    kv = rows * c["num_key_value_heads"] * d
    forward = 2 * (2 * q + 2 * kv)
    backward = 2 * (4 * q + 4 * kv)
    packed = 2 * batch * seq * seq // 8
    return float((forward + backward + packed) * c["num_hidden_layers"])


def index_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The indexer's passes over ALL causal entries: the score pass qI kI^T
    forward (2 FLOP an entry, index head and width), twice that backward
    (dqI and dkI), and the head-summed probabilities the indexer's loss
    needs, one QK^T of the main heads (2 FLOP an entry, head and head
    width). The elementwise work (ReLU, the weighted sum, the softmax of I)
    is not counted."""
    sa = c["sa_config"]
    entries = causal_entries(seq) * batch
    scores = 3.0 * 2.0 * entries * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    probs = 2.0 * entries * c["num_attention_heads"] * c["head_dim"]
    return (scores + probs) * c["num_hidden_layers"]


def index_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What the indexer's passes must move: the float32 scores written once
    and read once by the selection (4 B a causal entry each way), G written
    and read once in bf16, the main heads' bf16 q and k read once for the
    probabilities, the indexer's own operands and gradients (small)."""
    sa = c["sa_config"]
    entries = causal_entries(seq) * batch
    rows = seq * batch
    heads = rows * (c["num_attention_heads"] + c["num_key_value_heads"]) * c["head_dim"] * 2
    own = rows * (sa["indexer_num_heads"] + 1) * sa["indexer_head_dim"] * (2 + 4)
    return float((entries * (4 + 4 + 2 + 2) + heads + own) * c["num_hidden_layers"])


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """A token: its row through the trunk and the head, its share of the
    selected score entries of the attention and of all causal entries of
    the indexer's passes."""
    attention = (flash_flops_per_step(c, 1, seq) + index_flops_per_step(c, 1, seq)) / seq
    return 6.0 * (trunk_matmul_params(c) + c["hidden_size"] * c["vocab_size"]) + attention


def _held_rows(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Assignments a step that land on the held experts, a layer:
    ``share`` of all of them (what the step counts as ``moe_held_share``);
    a uniform router's share where none is given."""
    share = held_share(c) if share is None else share
    return batch * seq * c["num_experts_per_tok"] * share


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
