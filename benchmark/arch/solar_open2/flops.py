"""Operations and bytes a step of a Solar-Open2 model requires, computed
from shapes. Model FLOPs count the ACTIVE matmul parameters a token (a Kimi
delta mixer's nine projections; the gated attention's five; an expert
layer's router, its shared expert and the share of a token's routed experts
that a uniform router sends to the experts held here; the head; not the
embedding table), the causal scores of the attention layers, the chunked
delta rule and the short convolution; nothing recomputed. ``total_params``
counts every trained value of the chip's share.

Takes the configuration file's keys, not a LlamaConfig: the file's head
counts are the heads HELD and ``n_routed_experts`` the experts HELD,
``expert_parallel_chips`` times that the router's width (the adapter says
so).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

_dense = cells.arch_module("dense_decoder", "flops")

CHUNK = 64  # the chunk of the algorithm that is counted (flash-linear-attention's)


def _attention_layers(c: Dict[str, Any]) -> int:
    return len(c["gqa_layers"])


def _kda_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - _attention_layers(c)


def _kda_dims(c: Dict[str, Any]):
    """(heads held, a head's width, all held heads' channels)."""
    linear = c["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"], linear["num_heads"] * linear["head_dim"]


def _router_width(c: Dict[str, Any]) -> int:
    return c["n_routed_experts"] * c["expert_parallel_chips"]


def kda_matmul_params(c: Dict[str, Any]) -> int:
    """W_q, W_k, W_v and W_o, the decay's and the gate's low-rank pairs
    (down to one head's width, up to all the held channels) and W_beta."""
    h = c["hidden_size"]
    heads, d, channels = _kda_dims(c)
    return 4 * h * channels + 2 * (h * d + d * channels) + h * heads


def kda_params(c: Dict[str, Any]) -> int:
    """One mixer: the projections, the convolution's taps over [q | k | v],
    A_log a head, dt_bias and the gate's bias a channel, the per-head
    norm's one vector."""
    heads, d, channels = _kda_dims(c)
    taps = c["linear_attn_config"]["short_conv_kernel_size"]
    return kda_matmul_params(c) + taps * 3 * channels + heads + 2 * channels + d


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """W_q, the gate's W_g (as wide), W_k, W_v and W_o."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + q * h


def expert_params(c: Dict[str, Any]) -> int:
    """gate, up and down of one routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: Dict[str, Any]) -> int:
    """The shared experts of a layer: one expert of their summed width."""
    return c["n_shared_experts"] * expert_params(c)


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * _router_width(c)


def expert_layer_params(c: Dict[str, Any]) -> int:
    """The held experts, the shared one, the router over all the experts
    and its selection bias."""
    return (
        c["n_routed_experts"] * expert_params(c) + shared_params(c)
        + router_params(c) + _router_width(c)
    )


def total_params(c: Dict[str, Any]) -> int:
    """Mixers and expert layers, a pre-norm each, the table, the head and
    the final norm."""
    h = c["hidden_size"]
    return (
        _kda_layers(c) * (kda_params(c) + h)
        + _attention_layers(c) * (attention_matmul_params(c) + h)
        + c["num_hidden_layers"] * (expert_layer_params(c) + h)
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
        _kda_layers(c) * kda_matmul_params(c)
        + _attention_layers(c) * attention_matmul_params(c)
        + c["num_hidden_layers"] * expert_layer
        + c["hidden_size"] * c["vocab_size"]
    )


def _attention_view(c: Dict[str, Any]) -> Dict[str, Any]:
    """The keys the dense decoder's attention counts read, for the
    attention layers alone."""
    return {
        "num_hidden_layers": _attention_layers(c),
        **{k: c[k] for k in ("hidden_size", "head_dim", "num_attention_heads",
                             "num_key_value_heads")},
    }


def kda_flops_per_token(c: Dict[str, Any]) -> float:
    """One mixer's chunked delta rule, forward, a token (a multiply-add is
    two), in chunks of C = 64 and over the causal half of a chunk where a
    product is triangular: the decayed K K^T and Q K^T (a channel's decay
    is a factor of each multiply-add, not a count of its own) and
    W = T (..K) at C/2 d a head each; U = T (..V) and (Q K^T) V' at C/2 d
    each; T = (I + A)^-1 by substitution, C^2/6; and a token's three passes
    over its chunk's entering state, W S, Q S and K^T V', d^2 each. The
    count of the algorithm, whatever implements it."""
    heads, d, _ = _kda_dims(c)
    half = CHUNK / 2
    macs = 5 * half * d + CHUNK * CHUNK / 6 + 3 * d * d
    return 2.0 * macs * heads


def conv_flops_per_token(c: Dict[str, Any]) -> float:
    """One mixer's depthwise convolution over [q | k | v], forward."""
    return 2.0 * c["linear_attn_config"]["short_conv_kernel_size"] * 3 * _kda_dims(c)[2]


def kda_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """The delta rules of one step: forward and twice that backward."""
    return 3.0 * kda_flops_per_token(c) * batch * seq * _kda_layers(c)


def kda_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What a delta rule that kept everything else on the chip would move:
    q, k and v in bf16, the log-decay of every key channel and beta in
    float32 read, o written in bf16, forward; those and o's gradient read
    and the five gradients written, backward. Above the compute bound's
    time on a v5e: memory-bound."""
    heads, _, channels = _kda_dims(c)
    ins = 2 * 3 * channels + 4 * channels + 4 * heads  # q, k, v; g; beta
    out = 2 * channels
    forward = ins + out
    backward = ins + out + ins
    return float((forward + backward) * batch * seq * _kda_layers(c))


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return (
        6.0 * active_matmul_params(c)
        + _dense.attention_flops_per_token(_attention_view(c), seq)
        + 3.0 * (kda_flops_per_token(c) + conv_flops_per_token(c)) * _kda_layers(c)
    )


def flash_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_flops_per_step(_attention_view(c), batch, seq)


def flash_bytes_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    return _dense.flash_bytes_per_step(_attention_view(c), batch, seq)


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
    return 3.0 * 2.0 * expert_params(c) * rows * c["num_hidden_layers"]


def gmm_bytes_per_step(c: Dict[str, Any], batch: int, seq: int, share=None) -> float:
    """Each of the three matmuls [R, k] x [n, k, m] -> [R, m] in bf16 reads
    two of (rows in, rows out, weights) and writes the third, once
    forward and twice backward. At 410 rows an expert the weights are most
    of the bytes and the two bounds stand a fifth apart on a v5e (the
    operations' time the larger)."""
    rows = _held_rows(c, batch, seq, share)
    h, i = c["hidden_size"], c["moe_intermediate_size"]
    one = rows * (h + i) + c["n_routed_experts"] * h * i
    return float(3 * 3 * 2 * one * c["num_hidden_layers"])
