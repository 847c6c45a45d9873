"""Llama-3-style decoder-only transformer, TPU-first.

Design notes (why this is not a torch port):
- flax.linen + einsum contractions keep every FLOP on the MXU; compute in
  bfloat16, params in float32 (standard TPU mixed precision).
- The layer stack is an ``nn.scan`` over a single remat'd block (one XLA
  while-loop body compiled once regardless of depth, its parameters stacked
  along the scanned axis) or, for the unlike layers of a ``layer_pattern``, a
  Python loop over ``MixerLayer``s, each its own module, remat'd alone. A
  looped model (``loop_steps`` > 1) runs that pattern stack inside a second
  kind of scan, over the loop's steps with the parameters broadcast: one
  copy of the layers in the program and in the tree, visited several times.
- Attention is pluggable: ``dense`` (single-chip / short context) or
  ``ring`` (context parallelism over a mesh axis via shard_map + ppermute —
  see torchft_tpu/parallel/ring_attention.py). Long-context is first-class,
  not an afterthought.
- Sharding is by parameter-path rules (torchft_tpu/parallel/sharding.py),
  so the model itself stays mesh-agnostic; pjit + the rules place every
  matmul shard on the right chips.

Reference parity: the reference repo trains external models (torchtitan
Llama for HSDP, a CIFAR CNN in train_ddp.py:116-146); this module provides
the in-repo flagship for the BASELINE.json HSDP Llama-3-8B config.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from torchft_tpu.models.gated_delta import (
    GatedDeltaConfig,
    GatedDeltaMixer,
    KDAConfig,
    KimiDeltaMixer,
)
from torchft_tpu.models.mamba2 import Mamba2Config, Mamba2Mixer, conv_kernel_init
from torchft_tpu.models.mla import LatentAttention, MLAConfig

Dtype = Any

logger = logging.getLogger(__name__)
_ATTN_NOTED: set = set()


def _note_attention(
    asked: str, traced: str, seq_len: int, tiles: Optional[tuple] = None,
    window: Optional[int] = None, topk: Optional[int] = None,
) -> None:
    """Says once per (asked, traced, seq_len), at trace time, which
    attention implementation a step really took, of a windowed layer its
    window (``window=4096``), of a selected one the keys a query keeps
    (``topk=2048``) and, of a flash kernel, the tiles it chose
    (``tiles=1024x1024``) — 'flash' routes to dense below ``flash_min_seq``
    or on unsupported tilings, and a chip run must be able to prove which
    branch it compiled."""
    key = (asked, traced, seq_len)
    if key not in _ATTN_NOTED:
        _ATTN_NOTED.add(key)
        logger.log(
            logging.INFO if asked == traced else logging.WARNING,
            "attention: asked=%s traced=%s seq=%d%s%s%s", asked, traced, seq_len,
            " window=%d" % window if window else "",
            " topk=%d" % topk if topk else "",
            " tiles=%dx%d" % tiles if tiles else "",
        )


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    tie_embeddings: bool = False
    # The embedding table's initial standard deviation. None: flax's
    # default, variance 1/hidden_size, under which the first block's
    # residual stream is its sub-layers' outputs and hardly the token's own
    # row. 1.0 (T5's and PaLM's unit-variance table) keeps the token's row
    # the larger part of the stream through a shallow stack: a stack that
    # holds a share of each layer's experts (``experts_held``) needs it to
    # keep its routing spread under random weights (PERF.md section 6, PR 60).
    embed_init_std: Optional[float] = None
    # What the embedded rows are multiplied by where they are read, in
    # ``dtype`` (muP's sqrt(hidden_size): AFMoE's ``mup_enabled``). 1.0: no
    # multiply is emitted.
    embed_scale: float = 1.0
    remat: bool = True
    # 'dense' | 'flash' | 'ring' | 'ulysses'. flash = Pallas on-chip blocked attention
    # (ops/flash_attention.py, dense fallback for odd seq lens); ring
    # shards the sequence over the 'sp' mesh axis.
    attn_impl: str = "dense"
    # Below this sequence length the 'flash' impl routes to dense (measured
    # v5e crossover; the blocked kernel wins from ~2k and is mandatory past
    # dense's O(S^2) memory wall).
    flash_min_seq: int = 2048
    # The LARGEST flash kernel tile a call may take (q rows / kv cols per
    # VMEM block), not the tile: ops/flash_attention.py:choose_tiles picks
    # under them from the call's own shape (1024 where it divides the
    # sequence, else 512, else this bound itself; the attention note says
    # which, ``tiles=``). Set lower only to force small tiles (the CPU
    # tests' 32; on-chip grid experiments).
    flash_block_q: int = 1024
    flash_block_k: int = 1024
    # Mixture of experts: num_experts == 0 -> dense MLP. Experts shard over
    # the 'ep' mesh axis (parallel/sharding.py); dispatch/combine are dense
    # one-hot einsums so XLA derives the all-to-all from the shardings.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Per-sequence expert buffer = capacity_factor * S * k / E tokens;
    # overflow tokens pass through the residual only (standard GShard drop),
    # top-k gates renormalised, Switch's top-1 balance term. None = no
    # capacity: every assignment is computed by a sorted (dropless)
    # dispatch, the gates are the softmax's own values and the balance
    # term counts all k choices (OLMoE, arXiv:2409.02060).
    expert_capacity_factor: Optional[float] = 1.25
    # Load-balancing auxiliary loss coefficient: without it routing
    # collapses onto a few experts. MoEMLP sows the term under
    # "intermediates"; the train loss adds coef * mean over layers
    # (parallel/train.py:_loss_and_metrics).
    router_aux_coef: float = 0.01
    # Router z-loss coefficient: mean_t logsumexp(router logits)^2, sown
    # and averaged over layers like the balance term. 0 = not in the loss.
    router_z_coef: float = 0.0
    # True: RMSNorm (learned scale) over the WHOLE query projection and
    # over the whole key projection, before the split into heads and before
    # RoPE (OLMoE's form). "head": over each head's ``head_dim`` values, one
    # learned vector for the queries and one for the keys, shared by the
    # heads, before RoPE (LFM2's form).
    qk_norm: Any = False
    # False: attention without rotary embeddings (a hybrid whose
    # state-space layers carry the order, Nemotron-H). The flag is the
    # global kind's ('*' of a ``layer_pattern``, and the scanned block's);
    # the windowed kind 'W' carries its own and is always rotated.
    rope: bool = True
    # The windowed kind's window: a 'W' layer's row i keeps the keys j <= i
    # with i - j < ``sliding_window``, the position itself counted
    # (SmallThinker's ``sliding_window_size``).
    sliding_window: Optional[int] = None
    # A learned sparse attention (DeepSeek Sparse Attention, DeepSeek-V3.2,
    # in its masked training form; Keye-VL-2.0's ``sa_config``): the global
    # attention kind gains an indexer of ``indexer_heads`` heads of
    # ``indexer_head_dim`` on ONE shared index key a position, which scores
    # every earlier key, I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]);
    # a query attends to its ``sparse_topk`` best keys (all of them while
    # it has no more; ties to the lower index); and the indexer learns from
    # ``indexer_loss_coef`` x the KL of the attention's head-summed
    # probabilities over that selection against softmax(I) there, a mean
    # over the rows and the layers (sown as ``dsa_index_kl``). The indexer
    # reads the layer's normed input detached and nothing else of the loss
    # reaches it; the selection is not differentiable. None: dense.
    # ops/sparse_index.py has the passes, ops/flash_attention.py the
    # kernels' family (``flash_attention_selected``).
    sparse_topk: Optional[int] = None
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    indexer_loss_coef: float = 1.0
    # Multimodal rotary positions (Qwen2-VL's M-RoPE, chunked sections): a
    # token's position is three ids (temporal, height, width), and of the
    # head's ``head_dim / 2`` frequency pairs the first ``mrope_section[0]``
    # take the temporal id, the next ``[1]`` the height id, the last ``[2]``
    # the width id. ``positions`` is then [3, B, S] ([B, S]: all three
    # equal, which is the plain rotary embedding, bit for bit). An
    # indexer's rotary takes the temporal id. None: one id a token.
    mrope_section: Optional[tuple] = None
    # True: the attention's output is gated elementwise before W_o,
    # W_o(Y * sigmoid(x W_gate)), x the layer's (normed) input and W_gate
    # as wide as W_q (arXiv:2505.06708's head-specific elementwise gate;
    # Solar-Open2's ``use_gqa_gate``).
    attn_gate: bool = False
    # A stack of unlike layers, one character a layer, each layer ONE mixer
    # between a pre-norm and the residual add: 'M' a Mamba-2 mixer
    # (``mamba``), 'E' the expert layer, '*' attention (rotary where
    # ``rope``; latent attention where ``mla`` is set, models/mla.py),
    # 'W' attention under a sliding window (``sliding_window``; rotary
    # whatever ``rope`` says, never latent),
    # 'C' a gated short convolution of ``SHORT_CONV_TAPS`` taps,
    # 'G' a gated-delta linear attention (``gated_delta``,
    # models/gated_delta.py), 'K' a Kimi delta attention (``kda``, the same
    # file: a decay a key channel), 'D' a dense SwiGLU feed-forward. A model
    # whose published layer is an operator and a feed-forward is two
    # characters a layer ("CD", "*E", "GD", "KE", "WE").
    # None: ``num_layers`` scanned blocks of attention + MLP.
    layer_pattern: Optional[str] = None
    mamba: Optional[Mamba2Config] = None
    mla: Optional[MLAConfig] = None
    gated_delta: Optional[GatedDeltaConfig] = None
    kda: Optional[KDAConfig] = None
    # Where a ``layer_pattern`` layer's RMSNorm stands. False: before the
    # mixer, x + mixer(norm(x)). True: after it, x + norm(mixer(x)), the
    # residual stream itself feeding the mixer (OLMo 2's layer,
    # arXiv:2501.00656 section 3). "both": one before and a second
    # (``post_norm``) after, x + post_norm(mixer(norm(x))), four norms a
    # published layer of two sub-layers (AFMoE's sandwich).
    norm_after_mixer: Any = False
    # The dense feed-forward's width where it is not the experts'
    # (``intermediate_size`` is then an expert's). None: one width for both.
    dense_intermediate_size: Optional[int] = None
    # The dropless expert layer's variants (DeepSeek-V3's router, Nemotron's
    # experts). 'sigmoid': the k experts are the top-k of sigmoid(logits) +
    # a selection bias (a parameter that gets no gradient), the gates those
    # sigmoids without the bias, divided by their sum and times
    # ``routed_scaling``. 'relu2': down(relu(up(x))^2), no gate
    # matrix. 'reglu': down(relu(gate(x)) * up(x)), SwiGLU's three matrices
    # under a ReLU (SmallThinker's sparse experts). ``shared_expert_size`` >
    # 0: one such expert of that width every token passes through, added to
    # the routed result.
    router_score: str = "softmax"
    routed_scaling: float = 1.0
    # What the sigmoid router adds to the chosen scores' sum before it
    # divides by it (Nemotron's code has none; LFM2's 1e-6).
    gate_eps: float = 1e-20
    # > 0: the step moves each expert layer's selection bias after the
    # optimizer update, out of the gradient (parallel/train.py:
    # ``make_train_step``; DeepSeek-V3 arXiv:2412.19437 section 2.1.2), by
    # this much towards the experts the router under-used, and the layer
    # sows the assignments each expert got (``moe_load``).
    router_bias_update_rate: float = 0.0
    expert_act: str = "swiglu"
    shared_expert_size: int = 0
    # True: an expert layer's router reads the normed input of the
    # attention sub-layer BEFORE it (SmallThinker's pre-attention router:
    # a deployment fetches the chosen experts while the attention runs),
    # its experts their own normed input. The router's kernel is then a
    # parameter of that attention sub-layer (``layers_<i>/router``), which
    # hands its float32 logits [B, S, E] on to the 'E' that follows; under
    # per-sub-layer remat they are the attention sub-layer's second output
    # and so SAVED for the backward pass, 4 E bytes a token and layer, not
    # recomputed. A ``layer_pattern`` stack's, every 'E' after a '*' or 'W'.
    router_ahead: bool = False
    # (first, count): this layer HOLDS experts first .. first+count-1 of
    # ``num_experts`` (a chip's share under expert parallelism). It routes
    # over all of them, computes the assignments that land on its own, and
    # returns that partial sum (plus the shared expert). None: all.
    experts_held: Optional[tuple] = None
    # Initial scale of the projections that write the residual stream
    # (Nemotron-H's rescale_prenorm_residual: 1/sqrt(layers)).
    residual_init_scale: float = 1.0
    # True: the softmax router's k chosen probabilities are divided by
    # their sum (Qwen3-MoE's and SDAR's ``norm_topk_prob``); OLMoE keeps
    # the softmax's own values. The sigmoid router always renormalises.
    norm_topk_prob: bool = False
    # What a training step predicts. "next_token": every position its
    # successor under the causal mask. "block_diffusion" (SDAR
    # arXiv:2510.06303, Block Diffusion arXiv:2503.09573): the trunk runs
    # TWO streams of L positions laid end to end, a noisy x_t (positions
    # masked to ``mask_token_id`` with a probability t drawn a block of
    # ``block_length`` from U(``diffusion_t_min``, ``diffusion_t_max``))
    # and the clean x_0, both at rotary positions 0..L-1 under
    # ``block_diffusion_mask``, and the loss is the cross-entropy of the
    # noisy stream's masked positions against x_0, weighed by 1/t
    # (parallel/train.py:_loss_and_metrics). The noise is a pure function
    # of the batch's own tokens (``diffusion_streams``).
    objective: str = "next_token"
    block_length: int = 0
    mask_token_id: int = 0
    diffusion_t_min: float = 0.0
    diffusion_t_max: float = 1.0
    # Multi-token prediction (DeepSeek-V3 arXiv:2412.19437 section 2.2): after the
    # stack, ``mtp_layers`` modules in a row (``MTPModule``), the k-th fed
    # by the one before it (the first by the stack's output before the
    # final norm) and the embedding of the token k places on; each is a
    # block of ``MTP_BLOCK`` and predicts the token k + 1 places on
    # through the SHARED final norm, table and head. The loss is the main
    # one plus ``mtp_loss_coef`` times the modules' mean
    # (parallel/train.py:_loss_and_metrics). Training only: logits are the
    # main head's. A ``layer_pattern`` stack's.
    mtp_layers: int = 0
    mtp_loss_coef: float = 0.3
    # A looped language model (Ouro, arXiv:2510.25741; the Universal
    # Transformer's recurrence over depth): the WHOLE ``layer_pattern``
    # stack is applied ``loop_steps`` times on ONE set of parameters,
    # x_t = final_norm(stack(x_{t-1})), x_0 the embedded tokens, the final
    # norm inside the loop (the next step reads the normed states). More
    # than one step also makes the exit gate exist: one linear map to a
    # value with a bias (``ExitGate``: one leaf, the bias the kernel's last
    # row) on every step's normed states, z_t, whose sigmoids turn the steps
    # into a distribution over depths a token, p_t = sigma(z_t) prod_{j<t}
    # (1 - sigma(z_j)), the last step taking what is left. The training
    # loss is then the expectation under p of the steps' cross-entropies
    # (one head, shared) less ``loop_entropy_coef`` x p's entropy
    # (parallel/train.py:_loss_and_metrics); logits are the last step's.
    # A ``layer_pattern`` stack's.
    loop_steps: int = 1
    loop_entropy_coef: float = 0.0
    # Bound by parallel.train when attn_impl is 'ring' or 'ulysses'.
    attn_fn: Optional[Callable[..., jax.Array]] = None

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads


def llama3_8b(**overrides: Any) -> LlamaConfig:
    return dataclasses.replace(LlamaConfig(), **overrides)


def llama_small(**overrides: Any) -> LlamaConfig:
    """~125M model for single-chip benchmarking."""
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=768,
        intermediate_size=2048,
        num_layers=12,
        num_heads=12,
        num_kv_heads=4,
        head_dim=64,
        max_seq_len=2048,
    )
    return dataclasses.replace(cfg, **overrides)


def olmoe_1b_7b(**overrides: Any) -> LlamaConfig:
    """OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json;
    arXiv:2409.02060) at its published sizes: 64 experts of width 1024,
    top-8 dropless and not renormalised, full-width QK-norm, both router
    losses with the paper's coefficients. 6.9B parameters, 1.3B active:
    override ``num_layers`` for what one chip holds."""
    cfg = LlamaConfig(
        vocab_size=50304,
        hidden_size=2048,
        intermediate_size=1024,
        num_layers=16,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        max_seq_len=4096,
        rope_theta=10000.0,
        norm_eps=1e-5,
        num_experts=64,
        num_experts_per_tok=8,
        expert_capacity_factor=None,
        router_aux_coef=0.01,
        router_z_coef=0.001,
        qk_norm=True,
    )
    return dataclasses.replace(cfg, **overrides)


def nemotron3_nano(**overrides: Any) -> LlamaConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (config.json of model_type
    ``nemotron_h``; Nemotron-H arXiv:2504.03624, Mamba-2 arXiv:2405.21060)
    at its published sizes: 52 layers of ONE mixer each (23 Mamba-2, 23
    expert layers of 128 relu^2 experts with a sigmoid router, top-6 and
    a shared expert, 6 rope-free GQA attentions). 31.6B parameters:
    override ``layer_pattern``, ``experts_held`` and ``vocab_size`` for
    what one chip holds (docs/DESIGN.md, "A stack of unlike layers")."""
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    cfg = LlamaConfig(
        vocab_size=131072,
        hidden_size=2688,
        intermediate_size=1856,
        num_layers=len(pattern),
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        max_seq_len=262144,
        norm_eps=1e-5,
        rope=False,
        layer_pattern=pattern,
        mamba=Mamba2Config(),
        num_experts=128,
        num_experts_per_tok=6,
        expert_capacity_factor=None,
        router_score="sigmoid",
        routed_scaling=2.5,
        expert_act="relu2",
        shared_expert_size=3712,
        router_aux_coef=1e-4,
        router_z_coef=0.0,
        residual_init_scale=len(pattern) ** -0.5,
    )
    return dataclasses.replace(cfg, **overrides)


def nemotron_h_debug(**overrides: Any) -> LlamaConfig:
    """Tiny hybrid (pattern MEMEM*EME, 16 experts of which 4 are held) for
    tests and ``train_hsdp.py --model nemotron_h``."""
    pattern = "MEMEM*EME"
    cfg = nemotron3_nano(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        num_layers=len(pattern),
        layer_pattern=pattern,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        mamba=Mamba2Config(
            num_heads=8, head_dim=16, n_groups=2, state_size=16, chunk_size=16
        ),
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        shared_expert_size=96,
        residual_init_scale=len(pattern) ** -0.5,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def lfm2_8b_a1b(**overrides: Any) -> LlamaConfig:
    """LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B config.json, model_type
    ``lfm2_moe``) at its published sizes: 24 layers of an operator and a
    feed-forward each (18 gated short convolutions of 3 taps, 6 rotary GQA
    attentions of 32 heads on 8 at head width 64 with per-head QK norms;
    two leading dense SwiGLU feed-forwards of width 7168, then 32 SiLU-gated
    experts of width 1792, 4 a token, sigmoid router with a selection bias
    the step updates), tied head. 8.34B parameters, 1.56B active: override
    ``layer_pattern``, ``experts_held`` and ``vocab_size`` for what one
    chip holds. ``num_layers`` counts the published layers, the pattern
    their sub-layers. The bias update's rate is DeepSeek-V3's (LFM2's is
    not published)."""
    # Two characters a published layer: its operator ('*' at the six
    # attention layers, 'C' elsewhere), then its feed-forward ('D' in the
    # two leading layers, 'E' after).
    pattern = "".join(
        ("*" if i in (2, 6, 10, 14, 18, 21) else "C") + ("D" if i < 2 else "E")
        for i in range(24)
    )
    cfg = LlamaConfig(
        vocab_size=65536,
        hidden_size=2048,
        intermediate_size=1792,
        dense_intermediate_size=7168,
        num_layers=24,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        max_seq_len=128000,
        rope_theta=1e6,
        norm_eps=1e-5,
        tie_embeddings=True,
        qk_norm="head",
        layer_pattern=pattern,
        num_experts=32,
        num_experts_per_tok=4,
        expert_capacity_factor=None,
        router_score="sigmoid",
        routed_scaling=1.0,
        gate_eps=1e-6,
        router_aux_coef=0.0,
        router_z_coef=0.0,
        router_bias_update_rate=1e-3,
    )
    return dataclasses.replace(cfg, **overrides)


def lfm2_moe_debug(**overrides: Any) -> LlamaConfig:
    """Tiny LFM2 (published layers 1-5: conv + dense, attention + experts,
    three of conv + experts; 16 experts of which 4 are held) for tests and
    ``train_hsdp.py --model lfm2_moe``."""
    cfg = lfm2_8b_a1b(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        dense_intermediate_size=160,
        num_layers=5,
        layer_pattern="CD*ECECECE",
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def sdar_30b_a3b(**overrides: Any) -> LlamaConfig:
    """SDAR-30B-A3B-Chat (JetLM/SDAR-30B-A3B-Chat config.json, model_type
    ``sdar_moe``; arXiv:2510.06303) at its published sizes: 48 layers of
    rotary GQA attention (32 heads on 4 at head width 128, per-head QK
    norms) and 128 SiLU-gated experts of width 768, 8 a token under a
    softmax router with renormalised gates, an untied 151,936-row head;
    trained by block diffusion in blocks of 4. 30.5B parameters, 3.35B
    active: override ``num_layers``, ``experts_held`` and ``vocab_size``
    for what one chip holds. The mask token, the block length, t's
    interval (Block Diffusion's clipped schedule for blocks of 4,
    U[0.45, 0.95]) and the balance coefficient are not in the published
    file
    (benchmark/configs/sdar-30b-a3b-l6e16.json, ``assumed``). The stack is
    spelt as a pattern, "*E" a published layer: each sub-layer is then
    remat'd alone and its gradient meets the optimizer as soon as it
    exists, where one scanned block of both keeps the stacked gradient
    and a whole block's activations (19.1 GB against 13.0 for the
    benchmark's six layers, by the v5e compiler's own account)."""
    cfg = LlamaConfig(
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=768,
        num_layers=48,
        layer_pattern="*E" * 48,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        max_seq_len=32768,
        rope_theta=1e6,
        norm_eps=1e-6,
        qk_norm="head",
        num_experts=128,
        num_experts_per_tok=8,
        expert_capacity_factor=None,
        norm_topk_prob=True,
        router_aux_coef=0.001,
        router_z_coef=0.0,
        objective="block_diffusion",
        block_length=4,
        mask_token_id=151669,
        diffusion_t_min=0.45,
        diffusion_t_max=0.95,
    )
    return dataclasses.replace(cfg, **overrides)


def sdar_moe_debug(**overrides: Any) -> LlamaConfig:
    """Tiny SDAR (2 layers, 16 experts of which 4 are held, blocks of 4,
    the vocabulary's last row the mask token) for tests and
    ``train_hsdp.py --model sdar_moe``."""
    cfg = sdar_30b_a3b(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        num_layers=2,
        layer_pattern="*E*E",
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        mask_token_id=255,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def joyai_llm_flash(**overrides: Any) -> LlamaConfig:
    """JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash config.json, model_type
    ``joyai_llm_flash``, 48B-A2.7B; every layer's equations are
    DeepSeek-V3's, arXiv:2412.19437 sections 2.1 and 2.2) at its published
    sizes: 40 layers of latent attention (32 heads, a 128-wide rope-free
    and a 64-wide rotary part a query and key, 128-wide values, ranks 1536
    and 512, interleaved rotary pairs at theta 3.2e7) and a feed-forward,
    dense (7168) in the first layer, then 256 SiLU-gated experts of width
    768, 8 a token by sigmoid scores plus a selection bias the step
    updates, gates renormalised and scaled 2.5, one shared expert; one
    multi-token-prediction module; an untied 129,280-row head. Override
    ``num_layers`` with ``layer_pattern``, ``experts_held`` and
    ``vocab_size`` for what one chip holds. The bias update's rate and the
    module's coefficient are DeepSeek-V3's (the published file has
    neither; benchmark/configs/joyai-llm-flash-l6e8.json, ``assumed``)."""
    cfg = LlamaConfig(
        vocab_size=129280,
        hidden_size=2048,
        intermediate_size=768,
        dense_intermediate_size=7168,
        num_layers=40,
        # Two characters a published layer: its attention, its feed-forward.
        layer_pattern="*D" + "*E" * 39,
        num_heads=32,
        num_kv_heads=32,
        head_dim=64,  # the published key: the rotary part's width
        max_seq_len=131072,
        rope_theta=3.2e7,
        norm_eps=1e-6,
        mla=MLAConfig(),
        num_experts=256,
        num_experts_per_tok=8,
        expert_capacity_factor=None,
        router_score="sigmoid",
        routed_scaling=2.5,
        shared_expert_size=768,
        router_aux_coef=0.0,
        router_z_coef=0.0,
        router_bias_update_rate=1e-3,
        mtp_layers=1,
    )
    return dataclasses.replace(cfg, **overrides)


def joyai_flash_debug(**overrides: Any) -> LlamaConfig:
    """Tiny JoyAI-LLM-Flash (a dense layer and two expert layers, 16
    experts of which 4 are held, one prediction module) for tests and
    ``train_hsdp.py --model joyai_flash``."""
    cfg = joyai_llm_flash(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        dense_intermediate_size=160,
        num_layers=3,
        layer_pattern="*D*E*E",
        num_heads=4,
        num_kv_heads=4,
        head_dim=8,
        max_seq_len=128,
        mla=MLAConfig(
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
        ),
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        shared_expert_size=48,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def olmo_hybrid_7b(**overrides: Any) -> LlamaConfig:
    """Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json, model_type
    ``olmo_hybrid``; Gated DeltaNet arXiv:2412.06464) at its published
    sizes: 32 layers of a mixer and an 11008-wide SwiGLU feed-forward each,
    the mixer a gated-delta linear attention (30 heads, keys of 96 and
    values of 192, a 4-tap convolution, beta up to 2) three layers in four
    and a rope-free full attention (30 heads on 30 of width 128, one
    RMSNorm over the whole query projection and one over the key's) the
    fourth; every sub-layer's norm AFTER its mixer (OLMo 2's layer,
    arXiv:2501.00656); an untied 100,352-row head. 7.43B parameters:
    override ``layer_pattern``, the heads and ``vocab_size`` for what one
    chip holds. The norm's place, the QK-norm's form and the absent
    rotary embedding are not keys of the published file
    (benchmark/configs/olmo-hybrid-7b-l4h15.json, ``assumed``)."""
    cfg = LlamaConfig(
        vocab_size=100352,
        hidden_size=3840,
        intermediate_size=11008,
        num_layers=32,
        # Two characters a published layer: its mixer, its feed-forward.
        layer_pattern="GDGDGD*D" * 8,
        num_heads=30,
        num_kv_heads=30,
        head_dim=128,
        max_seq_len=65536,
        norm_eps=1e-6,
        qk_norm=True,
        rope=False,
        gated_delta=GatedDeltaConfig(),
        norm_after_mixer=True,
    )
    return dataclasses.replace(cfg, **overrides)


def olmo_hybrid_debug(**overrides: Any) -> LlamaConfig:
    """Tiny Olmo-Hybrid (one period: three gated-delta layers and an
    attention, a feed-forward after each) for tests and
    ``train_hsdp.py --model olmo_hybrid``."""
    cfg = olmo_hybrid_7b(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=160,
        num_layers=4,
        layer_pattern="GDGDGD*D",
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        max_seq_len=128,
        gated_delta=GatedDeltaConfig(num_heads=4, key_head_dim=8, value_head_dim=16),
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def solar_open2_250b(**overrides: Any) -> LlamaConfig:
    """Solar-Open2-250B (upstage/Solar-Open2-250B config.json, model_type
    ``solar_open2``, 250B-A15B; Kimi Delta Attention arXiv:2510.26692, the
    attention gate arXiv:2505.06708) at its published sizes: 48 layers of a
    mixer and an expert layer each, every sub-layer pre-normed; the mixer a
    rope-free GQA attention (64 heads on 8 of width 128) with an elementwise
    sigmoid output gate at layers 0, 4, 8, ... and a Kimi delta attention
    (64 heads, keys and values of 128, a 4-tap convolution, a decay a key
    channel, beta up to 2) at the other three of every four; 320 SiLU-gated
    experts of width 1280, 8 a token by sigmoid scores plus a selection
    bias that nothing moves (the published file names no rate), gates
    renormalised, one shared expert; an untied 196,608-row head. Override
    ``layer_pattern``, the heads, ``experts_held`` and ``vocab_size`` for
    what one chip holds. The router's form, the gate's and the mixer's
    low-rank projections are not keys of the published file
    (benchmark/configs/solar-open2-250b-l4h8e8.json, ``assumed``)."""
    cfg = LlamaConfig(
        vocab_size=196608,
        hidden_size=4096,
        intermediate_size=1280,
        num_layers=48,
        # Two characters a published layer: its mixer, its expert layer.
        layer_pattern="*EKEKEKE" * 12,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=1048576,
        norm_eps=1e-5,
        rope=False,
        attn_gate=True,
        kda=KDAConfig(),
        num_experts=320,
        num_experts_per_tok=8,
        expert_capacity_factor=None,
        router_score="sigmoid",
        routed_scaling=1.0,
        shared_expert_size=1280,
        router_aux_coef=0.0,
        router_z_coef=0.0,
    )
    return dataclasses.replace(cfg, **overrides)


def solar_open2_debug(**overrides: Any) -> LlamaConfig:
    """Tiny Solar-Open2 (one period: a gated attention and three Kimi delta
    attentions, an expert layer after each; 16 experts of which 4 are held)
    for tests and ``train_hsdp.py --model solar_open2_debug``."""
    cfg = solar_open2_250b(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        num_layers=4,
        layer_pattern="*EKEKEKE",
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        kda=KDAConfig(num_heads=4, head_dim=16),
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        shared_expert_size=48,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def smallthinker_21b(**overrides: Any) -> LlamaConfig:
    """SmallThinker-21BA3B-Instruct (PowerInfer/SmallThinker-21BA3B-Instruct
    config.json; arXiv:2507.20984) at its published sizes: 52 layers of an
    attention (28 heads on 4 of width 128, no bias, no QK norm) and an
    expert layer each; one layer in four (``sliding_window_layout`` and
    ``rope_layout`` 0: layers 0, 4, 8, ...) a global attention WITHOUT
    rotary embedding, the other three a sliding window of 4,096 with it
    (theta 1.5e6); 64 ReGLU experts of width 768, 6 a token by the top
    logits and a softmax over those six, no shared expert, the router
    reading the layer's normed input BEFORE its attention; an untied
    151,936-row head. 21.5B parameters, 3.3B active: override
    ``layer_pattern``, ``experts_held`` and ``vocab_size`` for what one
    chip holds. The balance term's form and coefficient are not keys of
    the published file (benchmark/configs/smallthinker-21b-l8e8.json,
    ``assumed``)."""
    cfg = LlamaConfig(
        vocab_size=151936,
        hidden_size=2560,
        intermediate_size=768,
        num_layers=52,
        # Two characters a published layer: its attention ('*' global and
        # rope-free, 'W' windowed and rotary), its expert layer.
        layer_pattern="*EWEWEWE" * 13,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        max_seq_len=16384,
        rope_theta=1.5e6,
        norm_eps=1e-6,
        rope=False,
        sliding_window=4096,
        router_ahead=True,
        num_experts=64,
        num_experts_per_tok=6,
        expert_capacity_factor=None,
        norm_topk_prob=True,
        expert_act="reglu",
        router_aux_coef=0.001,
        router_z_coef=0.0,
    )
    return dataclasses.replace(cfg, **overrides)


def smallthinker_debug(**overrides: Any) -> LlamaConfig:
    """Tiny SmallThinker (one period: a global rope-free attention and
    three windowed rotary ones over 16 positions, an expert layer after
    each; 16 experts of which 4 are held) for tests and
    ``train_hsdp.py --model smallthinker_debug``."""
    cfg = smallthinker_21b(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        num_layers=4,
        layer_pattern="*EWEWEWE",
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        sliding_window=16,
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def trinity_mini(**overrides: Any) -> LlamaConfig:
    """Trinity-Mini (arcee-ai/Trinity-Mini config.json, model_type ``afmoe``,
    26B-A3B) at its published sizes: 32 layers of an attention (32 heads on
    4 of width 128, per-head QK norms, an elementwise sigmoid output gate)
    and a feed-forward, each sub-layer between a norm before AND a norm
    after it; three layers in four a sliding window of 2,048 with rotary
    embedding (theta 1e4), the fourth a global attention without; two
    leading dense SwiGLU feed-forwards of width 6144, then 128 SiLU-gated
    experts of width 1024, 8 a token by sigmoid scores plus a selection
    bias the step moves, gates renormalised and scaled 2.826, one shared
    expert; the embedded rows times sqrt(2048) (muP); an untied 200,192-row
    head. Override ``layer_pattern``, ``experts_held`` and ``vocab_size``
    for what one chip holds. The gate, the QK norms, the norms' places and
    the rope-free global layers are the model's code and no key of the
    published file (benchmark/configs/trinity-mini-l5e16.json,
    ``assumed``)."""
    # Two characters a published layer: its attention ('*' at layers 3, 7,
    # ..., 'W' elsewhere), then its feed-forward ('D' in the two leading
    # layers, 'E' after).
    pattern = "".join(
        ("*" if i % 4 == 3 else "W") + ("D" if i < 2 else "E") for i in range(32)
    )
    cfg = LlamaConfig(
        vocab_size=200192,
        hidden_size=2048,
        intermediate_size=1024,
        dense_intermediate_size=6144,
        num_layers=32,
        layer_pattern=pattern,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        max_seq_len=131072,
        rope_theta=10000.0,
        norm_eps=1e-5,
        embed_scale=2048 ** 0.5,
        rope=False,
        sliding_window=2048,
        qk_norm="head",
        attn_gate=True,
        norm_after_mixer="both",
        num_experts=128,
        num_experts_per_tok=8,
        expert_capacity_factor=None,
        router_score="sigmoid",
        routed_scaling=2.826,
        shared_expert_size=1024,
        router_aux_coef=0.0,
        router_z_coef=0.0,
        router_bias_update_rate=1e-3,
    )
    return dataclasses.replace(cfg, **overrides)


def trinity_debug(**overrides: Any) -> LlamaConfig:
    """Tiny Trinity (published layers 1-5: a windowed attention and a dense
    feed-forward, then one period of windowed, global, windowed, windowed
    over 16 positions with an expert layer after each; 16 experts of which
    4 are held) for tests and ``train_hsdp.py --model trinity_debug``."""
    cfg = trinity_mini(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        dense_intermediate_size=160,
        num_layers=5,
        layer_pattern="WDWE*EWEWE",
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        embed_scale=8.0,
        sliding_window=16,
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        shared_expert_size=48,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def keye_vl2_30b_a3b(**overrides: Any) -> LlamaConfig:
    """Keye-VL-2.0-30B-A3B's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B
    config.json, model_type ``KeyeVL2``) at its published sizes: 48 layers,
    each a rotary GQA attention (32 heads on 4 at head width 128, per-head
    QK norms, theta 1e7, multimodal rotary sections [16, 24, 24]) whose
    keys a 16-head indexer of width 64 selects, 2,048 a query
    (``sa_config``: DeepSeek Sparse Attention), and 128 SiLU-gated experts
    of width 768, 8 a token under a softmax router with renormalised gates;
    an untied 151,936-row head. The vision tower is no part of it: what it
    asks of the language model, three-component positions, is. Override
    ``num_layers``, ``experts_held`` and ``vocab_size`` for what one chip
    holds. The QK norms, the indexer's LayerNorm, rotary and scale, its
    loss and both loss coefficients are not in the published file
    (benchmark/configs/keye-vl2-30b-a3b-l6e16.json, ``assumed``)."""
    cfg = LlamaConfig(
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=768,
        num_layers=48,
        layer_pattern="*E" * 48,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        max_seq_len=262144,
        rope_theta=1e7,
        mrope_section=(16, 24, 24),
        norm_eps=1e-6,
        qk_norm="head",
        sparse_topk=2048,
        indexer_heads=16,
        indexer_head_dim=64,
        indexer_loss_coef=1.0,
        num_experts=128,
        num_experts_per_tok=8,
        expert_capacity_factor=None,
        norm_topk_prob=True,
        router_aux_coef=0.001,
        router_z_coef=0.0,
    )
    return dataclasses.replace(cfg, **overrides)


def keye_vl2_debug(**overrides: Any) -> LlamaConfig:
    """Tiny Keye-VL-2.0 language model (2 layers, a 2-head indexer of width
    8 that keeps 16 keys a query, sections [2, 3, 3], 16 experts of which
    4 are held) for tests and ``train_hsdp.py --model keye_vl2_debug``."""
    cfg = keye_vl2_30b_a3b(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=48,
        num_layers=2,
        layer_pattern="*E*E",
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        mrope_section=(2, 3, 3),
        sparse_topk=16,
        indexer_heads=2,
        indexer_head_dim=8,
        num_experts=16,
        num_experts_per_tok=3,
        experts_held=(0, 4),
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def ouro_2_6b(**overrides: Any) -> LlamaConfig:
    """Ouro-2.6B (ByteDance/Ouro-2.6B config.json, model_type ``ouro``;
    "Scaling Latent Reasoning via Looped Language Models",
    arXiv:2510.25741) at its published sizes: 48 layers of a rotary
    multi-head attention (16 heads on 16 of width 128, theta 1e6) and a
    SwiGLU feed-forward of width 5,632, each sub-layer between a norm
    before AND a norm after it, the whole stack applied ``total_ut_steps``
    = 4 times on the same weights with the final norm inside the loop, an
    exit gate over the four depths and the expected-cross-entropy-less-
    entropy loss (``loop_steps``, ``loop_entropy_coef``); an untied
    49,152-row head. Override ``layer_pattern`` for what one chip holds.
    The norms' places, the gate and the loss's coefficient are not in the
    published file (benchmark/configs/ouro-2.6b-l6t4.json, ``assumed``)."""
    cfg = LlamaConfig(
        vocab_size=49152,
        hidden_size=2048,
        intermediate_size=5632,
        num_layers=48,
        layer_pattern="*D" * 48,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        max_seq_len=65536,
        rope_theta=1e6,
        norm_eps=1e-6,
        norm_after_mixer="both",
        embed_init_std=1.0,
        loop_steps=4,
        loop_entropy_coef=0.05,
    )
    return dataclasses.replace(cfg, **overrides)


def ouro_debug(**overrides: Any) -> LlamaConfig:
    """Tiny Ouro (2 layers applied 4 times) for tests and
    ``train_hsdp.py --model ouro_debug``."""
    cfg = ouro_2_6b(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=160,
        num_layers=2,
        layer_pattern="*D*D",
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        max_seq_len=128,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def llama_moe_debug(**overrides: Any) -> LlamaConfig:
    """Tiny MoE config (4 experts, top-2) for tests and the ep dryrun."""
    cfg = llama_debug(num_experts=4, num_experts_per_tok=2)
    return dataclasses.replace(cfg, **overrides)


def llama_debug(**overrides: Any) -> LlamaConfig:
    """Tiny config for tests and the driver's dryrun (CPU-friendly)."""
    cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


def rope_table(
    positions: jax.Array, head_dim: int, theta: float, dtype: Dtype,
    mrope_section: Optional[tuple] = None,
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables of shape [..., head_dim/2] for given positions.
    With ``mrope_section`` (``LlamaConfig.mrope_section``) the positions are
    [3, B, S] (or [B, S]: the three ids equal) and frequency pair i takes
    the id of its section: tables [B, S, head_dim/2] still."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    at = positions[..., None]
    if mrope_section is not None:
        if sum(mrope_section) != head_dim // 2 or len(mrope_section) != 3:
            raise ValueError(
                f"mrope_section {mrope_section!r}: three sections that add up "
                f"to the head's {head_dim // 2} frequency pairs"
            )
        if positions.ndim == 3:
            component = [c for c, n in enumerate(mrope_section) for _ in range(n)]
            # [head_dim/2, B, S] -> [B, S, head_dim/2]: pair i's own id.
            at = jnp.moveaxis(positions[jnp.asarray(component)], 0, -1)
    angles = at.astype(jnp.float32) * freqs
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotary embedding on the last dim of x: [B, S, H, Dh]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain GQA attention, causal or under a boolean ``mask`` [S,S] (True:
    kept). q: [B,S,Hq,Dh], k/v: [B,S,Hkv,Dh].

    Single large einsum pair so XLA tiles it onto the MXU; softmax in fp32.
    """
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, dh)
    scale = dh**-0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if mask is None and causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, s, hq, dh)


def block_diffusion_mask(stream_len: int, block_length: int) -> jax.Array:
    """[2L,2L] boolean, True where a query row sees a key column, over the
    two streams [noisy x_t | clean x_0] of L positions in blocks of b: a
    noisy query sees the noisy keys of its own block and the clean keys of
    strictly earlier blocks; a clean query the clean keys of its own and
    earlier blocks; nothing clean sees anything noisy."""
    at = jnp.arange(2 * stream_len)
    noisy, blk = at < stream_len, at % stream_len // block_length
    (qn, kn), (qb, kb) = ((v[:, None], v[None, :]) for v in (noisy, blk))
    return jnp.where(qn, jnp.where(kn, qb == kb, kb < qb), ~kn & (kb <= qb))


def block_diffusion_attention(cfg: LlamaConfig, rows: int) -> tuple:
    """(branch, kept share) of the two-stream attention over ``rows`` = 2L
    rows: 'flash' where the kernels take the shape (the share of the score
    entries in the tiles they CHOOSE to run that the mask keeps) or 'dense'
    (the whole 2L x 2L square is computed)."""
    from torchft_tpu.ops import flash_attention as fa

    L, b = rows // 2, cfg.block_length
    if rows % 2 or b <= 0 or L % b:
        raise ValueError(
            f"block diffusion: {rows} rows are not two streams of whole "
            f"blocks of {b}"
        )
    tiles = _block_diffusion_tiles(cfg, rows)
    if tiles is not None:
        kept, run = fa.block_diffusion_tiles(L, b, tiles[0])
        return "flash", kept / run
    return "dense", (L * L + L * b) / (rows * rows)


def _block_diffusion_tiles(cfg: LlamaConfig, rows: int) -> Optional[tuple]:
    """The tiles the block-diffusion kernels take for ``rows`` = 2L rows
    (one square tile, so under both bounds), None where dense runs."""
    from torchft_tpu.ops import flash_attention as fa

    if cfg.attn_impl != "flash" or rows < cfg.flash_min_seq:
        return None
    block = min(cfg.flash_block_q, cfg.flash_block_k)
    return fa.choose_tiles(
        "block_diffusion", rows // 2, (cfg.head_dim,), block, block,
        block_length=cfg.block_length,
    )


def window_mask(seq_len: int, window: int) -> jax.Array:
    """[S,S] boolean, True where a query row sees a key column under a
    sliding window: j <= i and i - j < ``window``."""
    back = jnp.arange(seq_len)[:, None] - jnp.arange(seq_len)[None, :]
    return (back >= 0) & (back < window)


def window_attention(cfg: LlamaConfig, seq_len: int) -> tuple:
    """(tiles, kept share) of a windowed layer's attention over
    ``seq_len`` rows: the tiles the banded flash kernels choose and the
    share of the score entries in the tiles they run that the band keeps,
    or (None, share of the whole S x S square) where dense attention under
    ``window_mask`` runs."""
    from torchft_tpu.ops import flash_attention as fa

    window = cfg.sliding_window
    if window is None or window < 1:
        raise ValueError(f"a windowed layer needs sliding_window, not {window!r}")
    tiles = None
    if cfg.attn_impl == "flash" and seq_len >= cfg.flash_min_seq:
        tiles = fa.choose_tiles(
            "window", seq_len, (cfg.head_dim,), cfg.flash_block_q, cfg.flash_block_k,
            window=window,
        )
    if tiles is not None:
        kept, run = fa.window_tiles(
            seq_len, window, cfg.flash_block_q, cfg.flash_block_k
        )
        return tiles, kept / run
    return None, fa.window_kept(seq_len, window) / (seq_len * seq_len)


def selected_dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, kept: jax.Array
) -> tuple:
    """Plain GQA attention under a boolean selection ``kept`` [B,S,S] (True:
    row t attends to column s): ``(out [B,S,Hq,Dh], lse [B,Hq,S] fp32)``,
    lse a constant to differentiation. What the selected flash kernels
    compute, for the sequences they do not take."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, s, hkv, hq // hkv, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * dh**-0.5
    scores = jnp.where(kept[:, None, None], scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    probs = jnp.exp(scores - lse[..., None]).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, s, hq, dh)
    return out, jax.lax.stop_gradient(lse.reshape(b, hq, s))


def selected_attention_tiles(cfg: LlamaConfig, seq_len: int) -> Optional[tuple]:
    """The tiles the selected flash kernels take for ``seq_len`` rows, None
    where dense attention under the unpacked selection runs."""
    from torchft_tpu.ops import flash_attention as fa

    if cfg.attn_impl != "flash" or seq_len < cfg.flash_min_seq:
        return None
    return fa.choose_tiles(
        "selected", seq_len, (cfg.head_dim,), cfg.flash_block_q, cfg.flash_block_k
    )


# What a selected attention layer keeps of its forward pass under remat, so
# that the backward's second forward does not score and select again: the
# packed selection (32 MiB a layer at 16,384 positions), the table of tile
# pairs to run and the rows' log-sum-exp of the index scores.
SELECTION_NAMES = ("dsa_words", "dsa_runs", "dsa_lse_index")


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dtype = x.dtype
        x = x.astype(jnp.float32)
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype
        )
        norm = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps
        )
        return (norm * scale).astype(dtype)


class ExitGate(nn.Module):
    """A looped stack's exit gate (``LlamaConfig.loop_steps``): one linear
    map to a value with a bias, z = w . x + b a position, in float32 at the
    highest precision (one value a row costs nothing, and the gate learns
    from small differences). ONE leaf, ``kernel`` [H + 1, 1]: rows 0..H-1
    are w (lecun-normal), the LAST row is b (0). As a leaf of its own b is
    one number whose gradient, a sum over every position of terms of both
    signs, passes through zero from seed to seed, so that no relative error
    of it is bounded (PERF.md section 6, PR 71); as a row of the kernel it
    is compared, clipped and saved with the vector it belongs to."""

    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        width = x.shape[-1]

        def init(key, shape, dtype):
            w = nn.initializers.lecun_normal()(key, (width, 1), dtype)
            return jnp.concatenate([w, jnp.zeros((1, 1), dtype)])

        kernel = self.param("kernel", init, (width + 1, 1), self.param_dtype)
        kernel = kernel.astype(jnp.float32)
        z = jnp.dot(
            x.astype(jnp.float32), kernel[:width, 0],
            precision=jax.lax.Precision.HIGHEST,
        )
        return z + kernel[width, 0]


class Indexer(nn.Module):
    """A selected attention's indexer (``LlamaConfig.sparse_topk``): from
    the layer's normed input, DETACHED by the caller, ``indexer_heads``
    index queries of ``indexer_head_dim`` a position, ONE index key a
    position under a LayerNorm, both rotated over their whole width at the
    temporal position, and a float32 weight a position and head, scaled by
    heads^-1/2 width^-1/2."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array) -> tuple:
        cfg = self.cfg
        heads, width = cfg.indexer_heads, cfg.indexer_head_dim
        kw = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        q = nn.DenseGeneral(features=(heads, width), axis=-1, name="wq_index", **kw)(x)
        k = nn.Dense(width, name="wk_index", **kw)(x)
        k = nn.LayerNorm(
            epsilon=1e-6, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="k_index_norm",
        )(k)
        weights = nn.Dense(heads, name="w_index", **kw)(x).astype(jnp.float32)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k[:, :, None], cos, sin)[:, :, 0]
        return q, k, weights * (heads**-0.5 * width**-0.5)


class Attention(nn.Module):
    """GQA attention of one of three kinds: the global one (causal; rotary
    where ``cfg.rope``); ``window=True``, the windowed one (causal within
    ``cfg.sliding_window`` positions; always rotary); and, under
    ``cfg.sparse_topk``, the global one over the keys its ``Indexer``
    selects (``cos`` and ``sin`` are then pairs: the heads' tables and the
    indexer's)."""

    cfg: LlamaConfig
    window: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, cos: Any, sin: Any) -> jax.Array:
        cfg = self.cfg
        selected = cfg.sparse_topk is not None and not self.window
        if selected:
            (cos, cos_index), (sin, sin_index) = cos, sin
        elif cfg.sparse_topk is not None:
            cos, sin = cos[0], sin[0]
        dense = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            features=(heads, cfg.head_dim),
            axis=-1,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name=name,
        )
        q = dense(cfg.num_heads, "wq")(x)
        k = dense(cfg.num_kv_heads, "wk")(x)
        v = dense(cfg.num_kv_heads, "wv")(x)
        if cfg.qk_norm == "head":
            per_head = lambda name: RMSNorm(  # noqa: E731
                cfg.norm_eps, cfg.param_dtype, name=name
            )
            q, k = per_head("q_norm")(q), per_head("k_norm")(k)
        elif cfg.qk_norm:
            whole = lambda t, name: RMSNorm(  # noqa: E731
                cfg.norm_eps, cfg.param_dtype, name=name
            )(t.reshape(*t.shape[:2], -1)).reshape(t.shape)
            q, k = whole(q, "q_norm"), whole(k, "k_norm")
        if cfg.rope or self.window:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if self.window:
            if cfg.attn_impl not in ("flash", "dense") or cfg.objective != "next_token":
                raise ValueError(
                    f"windowed attention under attn_impl={cfg.attn_impl!r}, "
                    f"objective={cfg.objective!r}: the band mask exists for "
                    "'flash' and 'dense' under 'next_token' (the ring's hops "
                    "and the all-to-all know no window)"
                )
            seq = q.shape[1]
            tiles, kept = window_attention(cfg, seq)
            # The schedule is this layer's, so the share is counted here.
            self.sow("intermediates", "swa_kept_share", jnp.float32(kept))
            _note_attention(
                f"{cfg.attn_impl}/window", f"{'flash' if tiles else 'dense'}/window",
                seq, tiles, cfg.sliding_window,
            )
            with jax.named_scope("attention/window"):
                if tiles is not None:
                    from torchft_tpu.ops.flash_attention import (
                        flash_attention_window,
                    )

                    out = flash_attention_window(
                        q, k, v, window=cfg.sliding_window,
                        block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                    )
                else:
                    out = dense_attention(
                        q, k, v, mask=window_mask(seq, cfg.sliding_window)
                    )
        elif selected:
            out = self._selected(x, q, k, v, cos_index, sin_index)
        elif cfg.objective == "block_diffusion":
            # x holds [x_t | x_0]: two streams, one mask over both.
            if cfg.attn_impl not in ("flash", "dense"):
                raise ValueError(
                    f"block diffusion under attn_impl={cfg.attn_impl!r}: the "
                    "two-stream mask exists for 'flash' and 'dense'"
                )
            rows = q.shape[1]
            traced, kept = block_diffusion_attention(cfg, rows)
            # The schedule is this layer's, so the share is counted here
            # (read where a step collects what its layers sow).
            self.sow("intermediates", "bd_kept_share", jnp.float32(kept))
            tiles = _block_diffusion_tiles(cfg, rows)
            _note_attention(
                f"{cfg.attn_impl}/block_diffusion", f"{traced}/block_diffusion",
                rows, tiles,
            )
            if tiles is not None:
                from torchft_tpu.ops.flash_attention import (
                    flash_attention_block_diffusion,
                )

                out = flash_attention_block_diffusion(
                    q, k, v, block_length=cfg.block_length, block=tiles[0]
                )
            else:
                out = dense_attention(
                    q, k, v,
                    mask=block_diffusion_mask(rows // 2, cfg.block_length),
                )
        elif cfg.attn_impl in ("ring", "ulysses"):
            assert cfg.attn_fn is not None, (
                f"{cfg.attn_impl} attention needs cfg.attn_fn"
            )
            _note_attention(cfg.attn_impl, cfg.attn_impl, q.shape[1])
            out = cfg.attn_fn(q, k, v)
        elif cfg.attn_impl == "flash":
            from torchft_tpu.ops.flash_attention import (
                choose_tiles,
                flash_attention,
            )

            tiles = choose_tiles(
                "causal", q.shape[1], (cfg.head_dim,),
                cfg.flash_block_q, cfg.flash_block_k,
            )
            if q.shape[1] >= cfg.flash_min_seq and tiles is not None:
                _note_attention("flash", "flash", q.shape[1], tiles)
                out = flash_attention(
                    q, k, v,
                    block_q=cfg.flash_block_q,
                    block_k=cfg.flash_block_k,
                )
            else:
                _note_attention("flash", "dense", q.shape[1])
                out = dense_attention(q, k, v)
        else:
            _note_attention(cfg.attn_impl, "dense", q.shape[1])
            out = dense_attention(q, k, v)
        if cfg.attn_gate:
            with jax.named_scope("attention/gate"):
                gate = dense(cfg.num_heads, "wg")(x).astype(jnp.float32)
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(cfg.dtype)
        return nn.DenseGeneral(
            features=cfg.hidden_size,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="wo",
        )(out)

    def _selected(self, x, q, k, v, cos_index, sin_index):
        """The attention over each query's selected keys (the class's third
        kind): (a) the indexer scores every earlier key, (b) each row's
        ``sparse_topk`` best are selected and packed, (c) the selected flash
        family (below ``flash_min_seq`` or where it takes no tiles: dense
        attention under the same selection) runs over them, (d) the
        indexer's loss against the attention's own probabilities is sown."""
        from jax.ad_checkpoint import checkpoint_name

        from torchft_tpu.ops import sparse_index as dsa
        from torchft_tpu.ops.flash_attention import flash_attention_selected

        cfg = self.cfg
        if cfg.attn_impl not in ("flash", "dense") or cfg.objective != "next_token":
            raise ValueError(
                f"selected attention under attn_impl={cfg.attn_impl!r}, "
                f"objective={cfg.objective!r}: the selection exists for "
                "'flash' and 'dense' under 'next_token' (no ring, all-to-all "
                "or two-stream form of it is built)"
            )
        if min(cfg.sparse_topk, cfg.indexer_heads, cfg.indexer_head_dim) < 1:
            raise ValueError(
                f"sparse_topk {cfg.sparse_topk} under an indexer of "
                f"{cfg.indexer_heads} heads of {cfg.indexer_head_dim}"
            )
        batch, seq = q.shape[:2]
        tiles = selected_attention_tiles(cfg, seq)
        _note_attention(
            f"{cfg.attn_impl}/selected", f"{'flash' if tiles else 'dense'}/selected",
            seq, tiles, topk=cfg.sparse_topk,
        )
        with jax.named_scope("attention/selected"):
            detached = jax.lax.stop_gradient(x)
            q_index, k_index, weights = Indexer(cfg, name="indexer")(
                detached, cos_index, sin_index
            )
            # Constants going in: the selection has no derivative to trace.
            scores = dsa.index_scores(
                *map(jax.lax.stop_gradient, (q_index, k_index, weights))
            )
            words, runs, lse_index = dsa.select(
                scores, cfg.sparse_topk, *(tiles or (seq, seq))
            )
            words, runs, lse_index = (
                checkpoint_name(a, name)
                for a, name in zip((words, runs, lse_index), SELECTION_NAMES)
            )
            if tiles is not None:
                out, lse = flash_attention_selected(
                    q, k, v, words, runs,
                    block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                )
            else:
                out, lse = selected_dense_attention(q, k, v, dsa.unpack(words, seq))
            loss = dsa.index_kl(
                q_index, k_index, weights, jax.lax.stop_gradient(q),
                jax.lax.stop_gradient(k), lse, words, lse_index,
            )
            nq, nk = runs.shape[1:]
            causal = (
                jnp.arange(nk)[None, :] * (seq // nk)
                <= jnp.arange(nq)[:, None] * (seq // nq) + seq // nq - 1
            )
            self.sow("intermediates", "dsa_index_kl", loss)
            self.sow("intermediates", "dsa_kept_share", (
                jnp.sum(jax.lax.population_count(words), dtype=jnp.float32)
                / (batch * (seq * (seq + 1) // 2))
            ))
            self.sow("intermediates", "dsa_tiles_run_share", (
                jnp.sum(runs * causal, dtype=jnp.float32) / (batch * jnp.sum(causal))
            ))
        return out


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        proj = lambda f, name: nn.Dense(  # noqa: E731
            f,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name=name,
        )
        width = cfg.dense_intermediate_size or cfg.intermediate_size
        gate = proj(width, "gate")(x)
        up = proj(width, "up")(x)
        return proj(cfg.hidden_size, "down")(nn.silu(gate) * up)


def router_dense(cfg: LlamaConfig) -> nn.Dense:
    """An expert layer's router, in fp32 for a numerically stable softmax
    and top-k: a child ``router`` of the module that calls it."""
    return nn.Dense(
        cfg.num_experts,
        use_bias=False,
        dtype=jnp.float32,
        param_dtype=cfg.param_dtype,
        name="router",
    )


@jax.custom_vjp
def _sorted_rows(x: jax.Array, order: jax.Array, inv: jax.Array) -> jax.Array:
    """Row ``order[i] // k`` of ``x`` [T, H] for each of the T*k sorted
    assignments: every token's row k times, grouped by expert. ``inv`` is
    the inverse permutation of ``order``. The transpose is written out as
    a gather by ``inv`` and a sum over a token's k copies: autodiff's own
    is a scatter-add, which a TPU runs row by row."""
    return x[order // (order.shape[0] // x.shape[0])]


def _sorted_rows_fwd(x, order, inv):
    return _sorted_rows(x, order, inv), (inv, x.shape[0])


def _sorted_rows_bwd(res, g):
    inv, tokens = res
    per_token = g[inv].reshape(tokens, -1, g.shape[-1])
    return per_token.sum(axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


@jax.custom_vjp
def _permute_rows(x: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``x[perm]`` for a permutation ``perm`` with inverse ``inv``: the
    transpose is ``g[inv]``, a gather too."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv):
    return x[perm], inv


def _permute_rows_bwd(inv, g):
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _held_tile(rows: int) -> int:
    """The row tile the held dispatch's loops step by, read from the
    buffer's length: ``HELD_ROW_TILE`` where it divides ``rows``, else the
    whole buffer (one tile: a buffer under a tile, the small models')."""
    return HELD_ROW_TILE if rows % HELD_ROW_TILE == 0 else rows


# What a row of the held dispatch's buffer holds where no tile was run.
# Zero is what the whole-buffer formula gave such a row, so nothing after
# it has to know; a test sets NaN here to show that no such row is read.
_UNFILLED = 0.0


def _filled_tiles(n_tiles, fn, *operands, over=0):
    """``fn`` row tile by row tile over the first ``n_tiles`` (uint32)
    tiles of ``operands`` (arrays of as many rows each: the buffer's R, or
    the step's T tokens): one ``while`` whose trip count the program reads,
    so the traced text is one body whatever the length is. ``fn`` takes the
    operands' tiles and returns a tuple of arrays of as many rows. Its
    first ``over`` results are written over the first ``over`` operands (of
    their shape and type), in place where nothing reads the operand
    afterwards: their rows of the tiles not run stay what the operand held,
    which is what a grouped matmul left past its groups. The other results
    start as buffers of ``_UNFILLED``. A length of one tile
    (``_held_tile``) is run whole, with no loop. Counting in uint32 keeps
    jax from wrapping every start in the arithmetic of a negative index."""
    rows = operands[0].shape[0]
    tile = _held_tile(rows)
    if tile == rows:
        return fn(*operands)
    outs = jax.eval_shape(fn, *(
        jax.ShapeDtypeStruct((tile,) + o.shape[1:], o.dtype) for o in operands
    ))
    if len(outs) > over:
        # The fill waits for the operands: buffers of a constant can be set
        # up at any time, and the v5e compiler set up every layer's before
        # the first layer ran (5.4 GB in smallthinker-raw's step; PERF.md
        # section 6, PR 64).
        fill, operands = jax.lax.optimization_barrier(
            (jnp.float32(_UNFILLED), operands)
        )
    zero = jnp.uint32(0)

    def body(i, results):
        start = i * jnp.uint32(tile)
        at = lambda a: (start,) + (zero,) * (a.ndim - 1)  # noqa: E731
        new = fn(*(
            jax.lax.dynamic_slice(o, at(o), (tile,) + o.shape[1:])
            for o in results[:over] + operands[over:]
        ))
        return tuple(
            jax.lax.dynamic_update_slice(buf, part, at(buf))
            for buf, part in zip(results, new)
        )

    return jax.lax.fori_loop(zero, n_tiles, body, operands[:over] + tuple(
        jnp.full((rows,) + o.shape[1:], fill.astype(o.dtype)) for o in outs[over:]
    ))


def _take_rows(a, at):
    """``a[at[:, 0]]`` for a column ``at`` [n, 1] of row numbers (uint32,
    all of them rows of ``a``): ``lax.gather`` itself, which traces to one
    instruction where indexing wraps it in the masking of an index out of
    bounds."""
    return jax.lax.gather(
        a, at, slice_sizes=(1,) + a.shape[1:],
        dimension_numbers=jax.lax.GatherDimensionNumbers(
            tuple(range(1, a.ndim)), (0,), (0,)
        ),
        mode="promise_in_bounds",
    )


@functools.partial(jax.jit, static_argnames="rows")
def _touched_tokens(slot, rows):
    """The token side of the held dispatch, from ``slot`` [T, K] (each
    assignment's row of the ``rows``-row buffer, ``rows`` for one that has
    none), as columns [T, 1] (``_take_rows``): ``order``, the tokens that
    hold at least one row first, in ascending order; ``place``, a token's
    place in that order; ``has``, whether it holds a row; and the number
    of token tiles that hold all of them (uint32 like ``slot``:
    ``_filled_tiles``), with the share of T that is. Jitted like
    ``_gather_sum``: one function of the step's text for the layers of one
    shape."""
    tokens = slot.shape[0]
    has = jnp.any(slot < rows, axis=1, keepdims=True)
    upto = jnp.cumsum(has, axis=0, dtype=jnp.uint32)
    order = jnp.argsort(~has, axis=0, stable=True).astype(jnp.uint32)
    tile = _held_tile(tokens)
    n_tiles = (upto[-1, 0] + (tile - 1)) // tile
    share = n_tiles * (tile / tokens) if tile < tokens else jnp.ones(())
    return (order, jnp.maximum(upto, 1) - 1, has, n_tiles), share


@jax.custom_vjp
def _held_rows(x, token, slot, n_tiles, touched):
    """Row ``token[r]`` of ``x`` [T, H] for each row r of the first
    ``n_tiles`` tiles of the held dispatch's R-row buffer. ``slot`` [T, K]
    is each assignment's row in the buffer, R for one that has none
    (uint32, like ``token`` and ``n_tiles``: ``_filled_tiles``);
    ``touched`` is ``_touched_tokens``' of it. The transpose gathers by
    ``slot`` from the row gradients and sums a token's K copies: a gather
    again, where autodiff's own is a scatter-add."""
    return _filled_tiles(n_tiles, lambda tok: (x[tok],), token)[0]


def _held_rows_fwd(x, token, slot, n_tiles, touched):
    return _held_rows(x, token, slot, n_tiles, touched), (slot, touched)


@functools.partial(jax.jit, static_argnames="dtype")
def _gather_sum(rows, slot, weights, touched, dtype):
    """out[t] = sum_k weights[t, k] * rows[slot[t, k]] (``weights`` None:
    ones) summed in float32 and cast to ``dtype``, a slot past the rows
    (R: no row) adding zero. It runs over the tokens that hold a row
    (``touched``: ``_touched_tokens``), a tile of them at a time: the
    tile's K slots and weights are read by its tokens, one [tile, H]
    gather a k (the [T, K, H] tensor of all of them at once is K times a
    layer's activations, most of it zero), a slot past the rows reading
    the last row and a select dropping what it read, the sum over k in the
    order k = 0 .. K-1. A tile's result goes into a compact buffer at the
    tile's own offset, of which no row of a tile not run is read, and ONE
    [T, H] gather puts every token's row in its place, a token that holds
    none getting exact zeros. Three times in every layer and K gathers
    each time, so written to trace short: jitted, so that the passes and
    the layers of one shape are one function of the step's text, which
    they call; the columns split once; the gathers ``lax.gather`` itself."""
    order, place, has, n_tiles = touched
    # The loop's operand is the order alone, which the routing gives long
    # before the rows are there: the compact buffer's fill waits for both
    # (``_filled_tiles`` ties it to the operand).
    order, rows = jax.lax.optimization_barrier((order, rows))
    last = rows.shape[0] - 1
    columns = lambda a: jnp.split(a, a.shape[1], axis=1)  # noqa: E731

    def tile(tokens):
        at = _take_rows(slot, tokens)
        parts = [
            jnp.where(keep, _take_rows(rows, k), 0).astype(jnp.float32)
            for k, keep in zip(columns(jnp.minimum(at, last)), columns(at <= last))
        ]
        if weights is not None:
            parts = [
                part * w
                for part, w in zip(parts, columns(_take_rows(weights, tokens)))
            ]
        return (sum(parts[1:], parts[0]).astype(dtype),)

    (compact,) = _filled_tiles(n_tiles, tile, order)
    return jnp.where(has, _take_rows(compact, place), 0)


def _held_rows_bwd(res, g):
    slot, touched = res
    return _gather_sum(g, slot, None, touched, g.dtype), None, None, None, None


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


@jax.custom_vjp
def _held_rows_twice(xs, n_tiles):
    """The held buffer's rows for each of the two grouped matmuls that
    read them (gate and up): their two gradients are summed over the first
    ``n_tiles`` tiles, where autodiff's own sum walks all R rows."""
    return xs, xs


def _held_rows_twice_bwd(n_tiles, gs):
    return _filled_tiles(n_tiles, lambda a, b: (a + b,), *gs, over=1) + (None,)


_held_rows_twice.defvjp(
    lambda xs, n_tiles: ((xs, xs), n_tiles), _held_rows_twice_bwd
)


def _expert_act(kind: str, *hidden):
    """What an expert puts between its matmuls, over ``hidden`` = (gate,
    up) for a ``GATED_EXPERT_ACTS`` kind and (up,) for relu^2."""
    if kind == "relu2":
        (up,) = hidden
        return jnp.square(nn.relu(up))
    gate, up = hidden
    return GATED_EXPERT_ACTS[kind](gate) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_act(kind, n_tiles, *hidden):
    """``_expert_act`` over the first ``n_tiles`` row tiles of the held
    buffer's hidden rows, and its gradient over the same tiles."""
    return _filled_tiles(n_tiles, lambda *h: (_expert_act(kind, *h),), *hidden)[0]


def _held_act_fwd(kind, n_tiles, *hidden):
    return _held_act(kind, n_tiles, *hidden), (n_tiles, hidden)


def _held_act_bwd(kind, res, g):
    n_tiles, hidden = res

    def tile(g, *h):
        return jax.vjp(functools.partial(_expert_act, kind), *h)[1](g)

    # the gradients take the place of ``g`` and of the first hidden rows
    return (None,) + tuple(
        _filled_tiles(n_tiles, tile, g, *hidden, over=len(hidden))
    )


_held_act.defvjp(_held_act_fwd, _held_act_bwd)


@jax.custom_vjp
def _combine_held(ys, gates, slot, rows, valid, n_tiles, touched):
    """out[t] = sum_k gates[t, k] * ys[slot[t, k]] in float32, a row past
    the buffer (``slot`` = R) counting as zero, over the tokens that hold
    a row (``touched``: ``_gather_sum``). ``rows`` [R] is each buffer
    row's assignment (token * K + k), ``valid`` [R] whether the row holds
    one. Both transposes are written from the buffer's side, over its
    first ``n_tiles`` tiles: row gathers and a gather of scalars, no
    scatter-add."""
    return _gather_sum(ys, slot, gates, touched, jnp.float32)


def _combine_held_fwd(ys, gates, slot, rows, valid, n_tiles, touched):
    out = _combine_held(ys, gates, slot, rows, valid, n_tiles, touched)
    return out, (ys, gates, slot, rows, valid, n_tiles)


def _combine_held_bwd(res, g):
    ys, gates, slot, rows, valid, n_tiles = res
    flat_gates = gates.reshape(-1)

    def tile(ys, rows, valid):
        g_rows = g[rows // gates.shape[1]]  # [tile, H] float32
        row_gate = jnp.where(valid, flat_gates[rows], 0.0)
        dots = jnp.sum(ys.astype(jnp.float32) * g_rows, axis=-1)
        return (
            (g_rows * row_gate[:, None]).astype(ys.dtype),
            jnp.where(valid, dots, 0.0),
        )

    d_ys, dots = _filled_tiles(n_tiles, tile, ys, rows, valid, over=1)
    d_gates = jnp.where(slot < dots.shape[0], dots[slot], 0.0)
    return d_ys, d_gates.astype(gates.dtype), None, None, None, None, None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


# The three-matrix experts' activations, by ``LlamaConfig.expert_act``.
GATED_EXPERT_ACTS = {"swiglu": nn.silu, "reglu": nn.relu}

# The held dispatch's static row buffer, as a multiple of the rows a
# uniform router sends to the experts held (MoEMLP._sorted_held says why 4).
HELD_ROW_FACTOR = 4.0
# From this many rows up the buffer is a whole number of tiles of as many:
# XLA's grouped matmul takes its row tile from the divisors of the buffer's
# length. Over 13,112 rows (8 x 11 x 149) it ran at 4 TFLOP/s on a v5e, over
# 13,312 (26 x 512) at 90, the same 8 groups of [4096, 1280] weights (my chip
# run, PR 58). The buffers of the cells before it are multiples already.
HELD_ROW_TILE = 512


def held_buffer_rows(cfg: LlamaConfig, tokens: int) -> int:
    """Rows of the held dispatch's static buffer for ``tokens`` tokens a
    step: ``HELD_ROW_FACTOR`` times the uniform router's share of the
    T*K assignments, a multiple of 8 and, from ``HELD_ROW_TILE`` rows up, of
    that; T*K at most."""
    assignments = tokens * cfg.num_experts_per_tok
    share = -(-assignments * cfg.experts_held[1] // cfg.num_experts)
    rows = -(-int(HELD_ROW_FACTOR * share) // 8) * 8
    if rows >= HELD_ROW_TILE:
        rows = -(-rows // HELD_ROW_TILE) * HELD_ROW_TILE
    return min(assignments, rows)


class MoEMLP(nn.Module):
    """Mixture-of-experts MLP: a float32 softmax router, top-k experts per
    token, SwiGLU experts stacked as [E, H, I] weights. Two dispatches,
    chosen by ``cfg.expert_capacity_factor``:

    - a number: GShard-style dense dispatch. Routing is one-hot
      dispatch/combine tensors [B,S,E,C] and the expert FFN batched
      einsums, so sharding the E dim over the 'ep' mesh axis makes XLA
      insert the all-to-all. Tokens beyond an expert's per-sequence
      capacity C are dropped (they contribute only through the residual),
      the top-k gates are renormalised, the balance term is Switch's
      (top-1 fractions).
    - None: sorted, dropless. The T*k assignments are stable-sorted by
      expert, each expert multiplies its own contiguous rows (a grouped
      matmul over ``group_sizes``), and the rows go back by the inverse
      permutation. Memory is O(T*k*H) whatever the routing, nothing is
      dropped, the gates are the softmax's own values and the balance
      term counts all k choices: OLMoE's layer (arXiv:2409.02060).

    Variants of the dropless form (the LlamaConfig fields say what each
    computes): ``router_score="sigmoid"`` (a selection bias chooses, the
    sigmoids weigh), ``expert_act="relu2"`` (two matrices an expert) or
    ``"reglu"`` (SwiGLU's three under a ReLU), ``router_ahead`` (the logits
    are handed in),
    ``shared_expert_size`` (one expert every token passes through) and
    ``experts_held`` (the layer holds a share of the experts, routes over
    all of them and returns its own part: ``_sorted_held``).

    Sown per layer under "intermediates" (parallel/train.py reads them by
    name): ``router_aux``, ``router_z`` (softmax router only),
    ``moe_max_load`` (largest expert's assignments over the mean),
    ``moe_dropped`` (assignments not computed) and, from a layer that
    holds a share, ``moe_held_share`` (the share of all assignments that
    landed on it), ``moe_held_run_share`` (the share of its row buffer
    in the row tiles its loops ran) and ``moe_held_token_run_share`` (the
    share of the step's tokens in the token tiles its token side ran);
    where ``router_bias_update_rate`` > 0, ``moe_load`` (the assignments
    each of the E experts got, a vector). The reference has no MoE/EP
    anywhere (SURVEY.md §2.3).
    """

    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, x: jax.Array, router_logits: Optional[jax.Array] = None
    ) -> jax.Array:
        """``router_logits`` [B,S,E] float32: logits computed ahead of this
        layer, by the attention sub-layer before it
        (``LlamaConfig.router_ahead``); None: the layer's own router reads
        ``x``."""
        cfg = self.cfg
        E = cfg.num_experts
        K = cfg.num_experts_per_tok
        if K > E:
            raise ValueError(
                f"num_experts_per_tok ({K}) > num_experts ({E})"
            )
        H = x.shape[-1]
        held = E if cfg.experts_held is None else cfg.experts_held[1]

        if router_logits is None:
            router_logits = router_dense(cfg)(x.astype(jnp.float32))  # [B,S,E]
        if cfg.router_score == "sigmoid":
            with jax.named_scope("moe/route"):
                scores = jax.nn.sigmoid(router_logits)
                bias = self.param(
                    "router_bias", nn.initializers.zeros, (E,), cfg.param_dtype
                )
                # The bias chooses and does not weigh: no gradient reaches it.
                _, gate_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), K)
                gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
                gate_vals = cfg.routed_scaling * (
                    gate_vals / (gate_vals.sum(-1, keepdims=True) + cfg.gate_eps)
                )
                # The balance term's P_e: the scores as shares of their sum.
                probs = scores / scores.sum(-1, keepdims=True)
        else:
            probs = jax.nn.softmax(router_logits, axis=-1)
            gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [B,S,K]
            if cfg.norm_topk_prob:
                gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
            lse = jax.nn.logsumexp(router_logits, axis=-1)
            self.sow("intermediates", "router_z", jnp.mean(jnp.square(lse)))

        # lecun_normal reads an [E, H, I] stack's leading axis as part of
        # the fan-in, so E experts start sqrt(E) smaller than one would: the
        # layer that holds all its experts keeps that (its accepted cell's
        # weights are drawn so); a held share starts each expert from its
        # own fan-in.
        expert = lambda shape, name, scale=1.0: self.param(  # noqa: E731
            name,
            nn.initializers.lecun_normal() if cfg.experts_held is None
            else nn.initializers.variance_scaling(
                scale * scale, "fan_in", "normal", batch_axis=(0,)
            ),
            shape, cfg.param_dtype,
        ).astype(cfg.dtype)
        gated = cfg.expert_act in GATED_EXPERT_ACTS
        if not gated and cfg.expert_act != "relu2":
            raise ValueError(f"expert_act {cfg.expert_act!r}")
        weights = (
            (expert((held, H, cfg.intermediate_size), "experts_gate"),)
            if gated else ()
        ) + (
            expert((held, H, cfg.intermediate_size), "experts_up"),
            expert(
                (held, cfg.intermediate_size, H), "experts_down",
                cfg.residual_init_scale,
            ),
        )
        if cfg.experts_held is not None:
            if cfg.expert_capacity_factor is not None:
                raise ValueError("a share of the experts needs the dropless dispatch")
            out = self._sorted_held(x, probs, gate_vals, gate_idx, weights)
        elif cfg.expert_capacity_factor is None:
            out = self._sorted(x, probs, gate_vals, gate_idx, weights)
        elif cfg.expert_act == "swiglu":
            out = self._capacity(x, probs, gate_vals, gate_idx, *weights)
        else:
            raise ValueError("the capacity dispatch computes SwiGLU experts only")
        if cfg.shared_expert_size:
            with jax.named_scope("moe/shared"):
                out = out + self._shared(x)
        return out

    def _ffn(self, xs, weights, gmm):
        """One expert's feed-forward over its rows, ``gmm`` the (grouped)
        matmul: SwiGLU or ReGLU over (gate, up, down) or relu^2 over (up,
        down)."""
        if len(weights) == 3:
            w_gate, w_up, w_down = weights
            act = GATED_EXPERT_ACTS[self.cfg.expert_act]
            return gmm(act(gmm(xs, w_gate)) * gmm(xs, w_up), w_down)
        w_up, w_down = weights
        return gmm(jnp.square(nn.relu(gmm(xs, w_up))), w_down)

    def _shared(self, x):
        """The expert every token passes through, of the routed experts'
        form at width ``shared_expert_size``."""
        cfg = self.cfg
        proj = lambda f, name, scale=1.0: nn.Dense(  # noqa: E731
            f, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.variance_scaling(
                scale * scale, "fan_in", "truncated_normal"
            ),
            name=name,
        )
        names = (
            ("shared_gate", "shared_up") if cfg.expert_act in GATED_EXPERT_ACTS
            else ("shared_up",)
        )
        weights = tuple(proj(cfg.shared_expert_size, n) for n in names) + (
            proj(x.shape[-1], "shared_down", cfg.residual_init_scale),
        )
        return self._ffn(x, weights, lambda a, layer: layer(a))

    def _sow_routing(self, probs, load, fractions, dropped) -> None:
        """``load`` [E]: assignments routed to each expert; ``fractions``
        [E]: the f_e of the balance term E * sum_e f_e * P_e (1 at uniform
        routing), P_e the mean router probability of e."""
        E = load.shape[0]
        p_e = probs.reshape(-1, E).mean(axis=0)
        self.sow("intermediates", "router_aux", E * jnp.sum(fractions * p_e))
        self.sow("intermediates", "moe_max_load", load.max() * E / load.sum())
        self.sow("intermediates", "moe_dropped", dropped)

    def _sow_load(self, load) -> None:
        """``moe_load`` [E]: the assignments each of ALL the experts got
        from this step's tokens, held here or not, for the step's
        selection-bias update. Only a model that asks for the update sows
        it, so that the others' programs stay what they were."""
        if self.cfg.router_bias_update_rate:
            self.sow("intermediates", "moe_load", load)

    def _sorted(self, x, probs, gate_vals, gate_idx, weights):
        cfg = self.cfg
        E, K, H = cfg.num_experts, cfg.num_experts_per_tok, x.shape[-1]
        T = x.shape[0] * x.shape[1]
        flat_idx = gate_idx.reshape(T * K)
        # A compare-and-sum, not bincount's scatter-add.
        group_sizes = jnp.sum(
            flat_idx[:, None] == jnp.arange(E, dtype=flat_idx.dtype)[None, :],
            axis=0, dtype=jnp.int32,
        )
        load = group_sizes.astype(jnp.float32)
        self._sow_routing(probs, load, load / (T * K), jnp.zeros(()))
        self._sow_load(load)

        order = jnp.argsort(flat_idx, stable=True)  # sorted row -> assignment
        inv = jnp.argsort(order)  # assignment -> sorted row
        xs = _sorted_rows(x.reshape(T, H).astype(cfg.dtype), order, inv)
        gmm = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
            a, w, group_sizes, preferred_element_type=cfg.dtype
        )
        ys = self._ffn(xs, weights, gmm)  # [T*K,H]
        y = _permute_rows(ys, inv, order).reshape(T, K, H)
        out = jnp.einsum(
            "tkh,tk->th", y, gate_vals.reshape(T, K),
            preferred_element_type=jnp.float32,
        )
        return out.reshape(x.shape).astype(x.dtype)

    def _sorted_held(self, x, probs, gate_vals, gate_idx, weights):
        """The sorted dispatch of a layer that holds ``count`` of the
        experts. The T*K assignments are sorted by held expert, those of
        absent experts last; the first R rows of that order are the
        buffer the grouped matmuls run over, and what they give goes back
        to its tokens weighted by the gates. What absent experts would
        have added is left out.

        R is static: a uniform router sends T*K*count/E rows here, and R is
        ``HELD_ROW_FACTOR`` (4) times that in whole row tiles
        (``held_buffer_rows``), T*K at most. A full T*K
        buffer would make every grouped matmul E/count (16) times longer
        for rows that are never filled; four times the uniform share is
        far above what a router under its balance term sends (the sown
        ``moe_held_share`` says how far). An assignment to a held expert
        that does not fit is not computed and is counted in ``moe_dropped``,
        which a run checks is 0.

        The grouped matmuls stop at the rows filled by their group sizes.
        Whatever else has R rows (the gather into the buffer, the
        activation and its gradient, the row gradients' sum, the combine's
        transpose) runs over the first ceil(rows filled / tile) row tiles,
        in loops whose trip count is read from the step's own ``n_fit``
        (``_filled_tiles``; the sown ``moe_held_run_share`` is the share of
        R they ran). What brings a token's K rows back, forward and as the
        transpose of the gather into the buffer, runs over the tokens that
        hold a row, in token tiles, and places the result once
        (``_gather_sum``; the sown ``moe_held_token_run_share`` is the
        share of T it ran). The two argsorts still walk the T*K
        assignments."""
        cfg = self.cfg
        first, count = cfg.experts_held
        E, K, H = cfg.num_experts, cfg.num_experts_per_tok, x.shape[-1]
        T = x.shape[0] * x.shape[1]
        R = held_buffer_rows(cfg, T)
        with jax.named_scope("moe/route"):
            flat_idx = gate_idx.reshape(T * K)
            all_sizes = jnp.sum(
                flat_idx[:, None] == jnp.arange(E, dtype=flat_idx.dtype)[None, :],
                axis=0, dtype=jnp.int32,
            )
            sizes = all_sizes[first : first + count]
            # Group sizes cut to the buffer: the last groups lose what is past R.
            ends = jnp.minimum(jnp.cumsum(sizes), R)
            fit = jnp.diff(ends, prepend=0)
            n_fit = ends[-1]
            load = sizes.astype(jnp.float32)
            p_e = probs.reshape(-1, E).mean(axis=0)
            self.sow(
                "intermediates", "router_aux",
                E * jnp.sum(all_sizes.astype(jnp.float32) / (T * K) * p_e),
            )
            self.sow(
                "intermediates", "moe_max_load",
                load.max() * count / jnp.maximum(load.sum(), 1.0),
            )
            self.sow("intermediates", "moe_dropped", load.sum() - n_fit)
            self.sow("intermediates", "moe_held_share", load.sum() / (T * K))
            tile = _held_tile(R)
            n_tiles = (n_fit.astype(jnp.uint32) + (tile - 1)) // tile
            self.sow(
                "intermediates", "moe_held_run_share",
                n_tiles * (tile / R) if tile < R else jnp.ones(()),
            )
            self._sow_load(all_sizes.astype(jnp.float32))

            local = flat_idx - first
            key = jnp.where((local >= 0) & (local < count), local, count)
            order = jnp.argsort(key, stable=True)  # sorted row -> assignment
            inv = jnp.argsort(order)  # assignment -> sorted row
            rows = order[:R].astype(jnp.uint32)
            valid = jnp.arange(R) < n_fit
            slot = jnp.where(inv < n_fit, inv, R).astype(jnp.uint32).reshape(T, K)
            touched, token_share = _touched_tokens(slot, R)
            self.sow("intermediates", "moe_held_token_run_share", token_share)
        with jax.named_scope("moe/experts"):
            xs = _held_rows(
                x.reshape(T, H).astype(cfg.dtype), rows // K, slot, n_tiles,
                touched,
            )
            gmm = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
                a, w, fit, preferred_element_type=cfg.dtype
            )
            reads = _held_rows_twice(xs, n_tiles) if len(weights) == 3 else (xs,)
            hidden = [gmm(a, w) for a, w in zip(reads, weights[:-1])]  # [R,I] each
            ys = gmm(_held_act(cfg.expert_act, n_tiles, *hidden), weights[-1])
            out = _combine_held(
                ys, gate_vals.reshape(T, K), slot, rows, valid, n_tiles, touched
            )
        return out.reshape(x.shape).astype(x.dtype)

    def _capacity(self, x, probs, gate_vals, gate_idx, w_gate, w_up, w_down):
        cfg = self.cfg
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        B, S, H = x.shape
        C = max(int(cfg.expert_capacity_factor * S * K / E), 1)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )

        # Capacity-bounded positions: k-th choices are lower priority than
        # all (k-1)-th choices (carried counts), tokens in sequence order.
        counts = jnp.zeros((B, E), jnp.float32)
        load = jnp.zeros((E,), jnp.float32)
        dispatch = jnp.zeros((B, S, E, C), jnp.float32)
        combine = jnp.zeros((B, S, E, C), jnp.float32)
        for k in range(K):  # K is tiny (2); static unroll
            mk = jax.nn.one_hot(gate_idx[..., k], E, dtype=jnp.float32)
            pos = counts[:, None, :] + jnp.cumsum(mk, axis=1) - mk  # [B,S,E]
            keep = mk * (pos < C)
            counts = counts + keep.sum(axis=1)
            load = load + mk.sum(axis=(0, 1))
            if k == 0:  # Switch: f_e = share of tokens whose TOP choice is e
                top1_fractions = mk.mean(axis=(0, 1))
            pos_tok = (pos * keep).sum(-1).astype(jnp.int32)  # [B,S]
            slot = jax.nn.one_hot(pos_tok, C, dtype=jnp.float32)  # [B,S,C]
            disp_k = keep[..., None] * slot[:, :, None, :]  # [B,S,E,C]
            dispatch = dispatch + disp_k
            combine = combine + disp_k * gate_vals[..., k][..., None, None]
        self._sow_routing(
            probs, load, top1_fractions, B * S * K - counts.sum()
        )

        xe = jnp.einsum(
            "bsec,bsh->bech", dispatch.astype(cfg.dtype), x.astype(cfg.dtype)
        )  # [B,E,C,H]
        hidden = nn.silu(
            jnp.einsum("bech,ehi->beci", xe, w_gate)
        ) * jnp.einsum("bech,ehi->beci", xe, w_up)
        ye = jnp.einsum("beci,eih->bech", hidden, w_down)  # [B,E,C,H]

        out = jnp.einsum("bsec,bech->bsh", combine.astype(cfg.dtype), ye)
        return out.astype(x.dtype)


class Block(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, x: jax.Array, cos: jax.Array, sin: jax.Array
    ) -> jax.Array:
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, cfg.param_dtype, name="attn_norm")(x), cos, sin
        )
        mlp_cls = MoEMLP if cfg.num_experts > 0 else MLP
        x = x + mlp_cls(cfg, name="mlp")(
            RMSNorm(cfg.norm_eps, cfg.param_dtype, name="mlp_norm")(x)
        )
        return x


# The gated short convolution's taps (LFM2's ``conv_L_cache``): one value
# is in use, so it is a constant and no field of the configuration.
SHORT_CONV_TAPS = 3
_SHORT_CONV_NOTED: set = set()


def _note_short_conv(taps: int, seq_len: int) -> None:
    """Says once per (taps, seq_len), at trace time, which form of the
    gated short convolution a step took (plain XLA is the only one)."""
    key = (taps, seq_len)
    if key not in _SHORT_CONV_NOTED:
        _SHORT_CONV_NOTED.add(key)
        logger.info("short_conv: traced=xla taps=%d seq=%d", taps, seq_len)


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution (``Lfm2ShortConv``): for x [B, S, H]

        [b | c | u] = in_proj(x)                   H -> 3H, no bias
        w_t = sum_j k_j * (b * u)_{t-(L-1)+j}      depthwise, causal, L taps,
                                                   zero before the sequence
        out = out_proj(c * w)                      H -> H

    No activation and no state beyond L-1 positions. The projections run
    in the compute type; both gates and the taps are float32 products
    (one elementwise pass between the two matmuls for XLA to fuse)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg, f32 = self.cfg, jnp.float32
        taps, seq, h = SHORT_CONV_TAPS, x.shape[1], x.shape[-1]
        dense = lambda f, name: nn.Dense(  # noqa: E731
            f, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name=name,
        )
        _note_short_conv(taps, seq)
        b, c, u = jnp.split(dense(3 * h, "in_proj")(x), 3, axis=-1)
        kernel = self.param(
            "conv_kernel", conv_kernel_init(taps), (taps, h), cfg.param_dtype
        )
        with jax.named_scope("short_conv/gated"):
            v = jnp.pad(
                b.astype(f32) * u.astype(f32), ((0, 0), (taps - 1, 0), (0, 0))
            )
            w = sum(v[:, j : j + seq] * kernel[j].astype(f32) for j in range(taps))
            y = (c.astype(f32) * w).astype(cfg.dtype)
        return dense(h, "out_proj")(y)


class MixerLayer(nn.Module):
    """One layer of a ``layer_pattern`` stack: x + mixer(RMSNorm(x)),
    under ``norm_after_mixer`` x + RMSNorm(mixer(x)) or, where that is
    "both", x + RMSNorm(mixer(RMSNorm(x))), the second norm named
    ``post_norm``; the mixer named for its kind so that the sharding rules
    find it. Under
    ``cfg.router_ahead`` an attention layer ('*', 'W') also holds the
    router of the expert layer after it and returns (x, that router's
    float32 logits from its own normed input), and an expert layer is
    handed them as ``router_logits``."""

    cfg: LlamaConfig
    kind: str

    @nn.compact
    def __call__(
        self, x: jax.Array,
        cos: Optional[jax.Array] = None, sin: Optional[jax.Array] = None,
        router_logits: Optional[jax.Array] = None,
    ) -> Any:
        cfg = self.cfg
        norm = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="norm")
        place = cfg.norm_after_mixer
        if place not in (False, True, "both"):
            raise ValueError(f"norm_after_mixer {place!r} is none of False, True, 'both'")
        if place == "both":
            post_norm = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="post_norm")

            def after(y):
                with jax.named_scope("norm/post"):
                    return post_norm(y)

            h = norm(x)
        else:
            h, after = (x, norm) if place else (norm(x), lambda y: y)
        if self.kind == "M":
            return x + after(Mamba2Mixer(
                cfg.mamba, cfg.hidden_size, cfg.norm_eps, cfg.residual_init_scale,
                cfg.dtype, cfg.param_dtype, name="mamba",
            )(h))
        if self.kind == "G":
            return x + after(GatedDeltaMixer(
                cfg.gated_delta, cfg.hidden_size, cfg.norm_eps,
                cfg.dtype, cfg.param_dtype, name="gdn",
            )(h))
        if self.kind == "K":
            return x + after(KimiDeltaMixer(
                cfg.kda, cfg.hidden_size, cfg.norm_eps,
                cfg.dtype, cfg.param_dtype, name="kda",
            )(h))
        if self.kind == "E":
            return x + after(MoEMLP(cfg, name="mlp")(h, router_logits))
        if self.kind == "D":
            return x + after(MLP(cfg, name="mlp")(h))
        if self.kind == "C":
            return x + after(ShortConvMixer(cfg, name="conv")(h))
        if self.kind in ATTENTION_KINDS:
            if self.kind == "W":
                mixer = Attention(cfg, window=True, name="attn")
            else:
                mixer = (Attention if cfg.mla is None else LatentAttention)(cfg, name="attn")
            out = x + after(mixer(h, cos, sin))
            if not cfg.router_ahead:
                return out
            with jax.named_scope("moe/router_ahead"):
                return out, router_dense(cfg)(h.astype(jnp.float32))
        raise ValueError(
            f"layer kind {self.kind!r} is none of 'M', 'G', 'K', 'E', 'D', 'C', "
            "'*', 'W'"
        )


# The kinds of a ``layer_pattern`` that are attention: the global one and
# the windowed one. They are handed the rotary tables (where any layer of
# theirs is rotary) and, under ``router_ahead``, hold the next 'E's router.
ATTENTION_KINDS = "*W"


def _stack_layers(cfg: LlamaConfig, pattern: str, x: jax.Array, rotary: tuple,
                  name: Callable[[int], str]) -> jax.Array:
    """``x`` through the layers of ``pattern``, ``layers_<i>`` of the
    module that calls it, each remat'd alone. Only an attention layer is
    handed the rotary tables: the other kinds' calls (and a rope-free
    stack's) stay as they were. Under ``router_ahead`` an attention
    layer's second output is carried to the expert layer after it. One
    call makes one module a character and visits each once; a looped model
    calls it once too, as the body of ``Transformer._looped``'s scan over
    the steps, which is what visits the modules again."""
    # Unlike layers cannot share one scanned body: each is its own module,
    # remat'd alone (outside a scan XLA would otherwise merge the
    # recomputation with the forward pass and keep every layer's
    # activations: prevent_cse stays on).
    layer = MixerLayer
    if cfg.remat and cfg.sparse_topk is not None:
        # The selection is kept: the second forward neither scores nor selects.
        layer = nn.remat(MixerLayer, policy=jax.checkpoint_policies.save_only_these_names(
            *SELECTION_NAMES
        ))
    elif cfg.remat:
        layer = nn.remat(MixerLayer)
    ahead = None
    for i, kind in enumerate(pattern):
        mod = layer(cfg, kind, name=name(i))
        if kind in ATTENTION_KINDS:
            x = mod(x, *rotary)
            if cfg.router_ahead:
                x, ahead = x
        elif kind == "E" and cfg.router_ahead:
            if ahead is None:
                raise ValueError(
                    f"router_ahead: layer {i} of {pattern!r} is an expert layer "
                    "with no attention layer before it to hold its router"
                )
            x, ahead = mod(x, None, None, ahead), None
        else:
            x = mod(x)
        # A layer's output is the tensor its successors read, which
        # per-sub-layer remat saves anyway. Without the barrier XLA fuses the
        # residual adds ACROSS layers wherever [B, S, H] and [B S, H] are one
        # layout (one sequence a device): the final hidden states become one
        # fusion over every expert layer's K gathered [S, H] parts, all
        # alive until the forward pass ends (1.2 GiB a layer by the v5e
        # compiler's account at 16,384 x 2560: 19.5 GiB for eight layers on
        # a chip that hands out 15.75, 10.4 with the barrier). Where the
        # compiler does not fuse past the reshape (two sequences a device
        # and more) the barrier moves the compiler's account of the step by
        # at most 0.3% (PERF.md section 6, PR 60).
        x = jax.lax.optimization_barrier(x)
    return x


# A prediction module's block, as a ``layer_pattern``: attention, then the
# expert layer. One value is in use, so it is a constant and no field of
# the configuration.
MTP_BLOCK = "*E"


class MTPModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3 arXiv:2412.19437
    section 2.2): for ``h`` [B, S, H], the hidden states it continues
    (before any final norm), and ``emb`` [B, S, H], the embedding of the
    token one place further on than those states have seen,

        h' = eh_proj([RMSNorm(h) | RMSNorm(emb)])        2H -> H, no bias

    then one block of ``MTP_BLOCK``, the stack's own layer classes.
    What comes out goes through the model's final norm and head (shared,
    ``Transformer``'s) and to the next module."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, h: jax.Array, emb: jax.Array, *rotary: jax.Array) -> jax.Array:
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)  # noqa: E731
        x = nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="eh_proj",
        )(jnp.concatenate([norm("hnorm")(h), norm("enorm")(emb)], axis=-1))
        return _stack_layers(cfg, MTP_BLOCK, x, rotary, "layers_{}".format)


class _ScanBlock(Block):
    """Block with the (carry, ys) return contract nn.scan requires."""

    @nn.compact
    def __call__(self, x, cos, sin):  # type: ignore[override]
        return super().__call__(x, cos, sin), None


_LOOP_NOTED: set = set()


def _note_loop(steps: int, layers: int, sublayers: int) -> None:
    """Says once per shape of the loop, at trace time, that a looped stack
    was traced as one scanned body."""
    key = (steps, layers, sublayers)
    if key not in _LOOP_NOTED:
        _LOOP_NOTED.add(key)
        logger.info(
            "loop: steps=%d layers=%d sublayers=%d traced=scan", steps, layers,
            sublayers,
        )


class Transformer(nn.Module):
    """Decoder-only LM. __call__(tokens [B,S], positions [B,S], or
    [3,B,S] under ``mrope_section``) -> logits.
    Under ``objective="block_diffusion"`` the S tokens are two streams of
    S/2, [x_t | x_0] (``LlamaConfig.objective``). Under ``loop_steps`` > 1
    the ``layer_pattern`` stack runs that many times on one set of
    parameters (``_looped``) and the logits are the last step's."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        return_hidden: bool = False,
        next_tokens: Optional[jax.Array] = None,
    ) -> jax.Array:
        """``return_hidden=True`` returns the post-final-norm hidden states
        [B,S,H] in cfg.dtype instead of logits — the chunked-loss path
        (parallel/train.py:_loss_fn) projects them onto the vocab in
        sequence chunks so the full [B,S,V] fp32 logits are never
        materialized. A model with ``mtp_layers`` then returns a tuple,
        the main hidden states and each prediction module's, and reads
        ``next_tokens`` [B,S], each position's successor (None: ``tokens``
        rolled by one, whose last position reads the first token; the loss
        gives that row no weight). A looped model (``loop_steps`` > 1)
        returns the pair ``(h [T,B,S,H], z [T,B,S])``: every step's normed
        states and the exit gate's float32 logits on them."""
        cfg = self.cfg
        if (
            cfg.mla is not None or cfg.mtp_layers or cfg.sparse_topk is not None
            or cfg.mrope_section is not None or cfg.loop_steps != 1
        ) and cfg.layer_pattern is None:
            raise ValueError(
                "latent attention, prediction modules, a selected attention, "
                "multimodal rotary positions and a loop over the stack are a "
                "layer_pattern stack's"
            )
        if positions is None:
            if cfg.objective == "block_diffusion":
                # Two streams, each at positions 0..L-1.
                at = jnp.tile(jnp.arange(tokens.shape[1] // 2), 2)
            else:
                at = jnp.arange(tokens.shape[1])
            positions = jnp.broadcast_to(at, tokens.shape)
        elif positions.ndim == 3 and cfg.mrope_section is None:
            raise ValueError(
                "positions of three ids a token need mrope_section"
            )
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=(
                nn.linear.default_embed_init if cfg.embed_init_std is None
                else nn.initializers.normal(cfg.embed_init_std)
            ),
            name="embed",
        )
        x = self._embedded(embed, tokens)
        if cfg.router_ahead and (cfg.layer_pattern is None or cfg.norm_after_mixer):
            raise ValueError(
                "router_ahead is a layer_pattern stack's, of pre-normed layers"
            )
        if cfg.layer_pattern is not None:
            rotary_dim = cfg.head_dim if cfg.mla is None else cfg.mla.qk_rope_head_dim
            rotary = (
                rope_table(
                    positions, rotary_dim, cfg.rope_theta, cfg.dtype, cfg.mrope_section
                )
                if cfg.rope or "W" in cfg.layer_pattern else ()
            )
            if cfg.sparse_topk is not None:
                if not rotary or cfg.mla is not None:
                    raise ValueError(
                        "sparse_topk: the selected attention is the rotary "
                        "global kind's, never the latent one's"
                    )
                # Each table beside the indexer's own, at the temporal id.
                rotary = tuple(zip(rotary, rope_table(
                    positions[0] if positions.ndim == 3 else positions,
                    cfg.indexer_head_dim, cfg.rope_theta, cfg.dtype,
                )))
            if cfg.loop_steps != 1:
                return self._looped(embed, x, rotary, return_hidden)
            x = _stack_layers(cfg, cfg.layer_pattern, x, rotary, "layers_{}".format)
            predicted = []
            # The modules serve the training loss alone (and ``init``,
            # which has to meet their parameters).
            if cfg.mtp_layers and (return_hidden or self.is_initializing()):
                nxt = jnp.roll(tokens, -1, axis=1) if next_tokens is None else next_tokens
                for k in range(cfg.mtp_layers):
                    predicted.append(MTPModule(cfg, name=f"mtp_{k}")(
                        predicted[-1] if predicted else x,
                        self._embedded(embed, nxt), *rotary
                    ))
                    nxt = jnp.roll(nxt, -1, axis=1)
            return self._head(embed, x, return_hidden, predicted)
        cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta, cfg.dtype)

        block = _ScanBlock
        if cfg.remat:
            block = nn.remat(
                _ScanBlock,
                prevent_cse=False,
                static_argnums=(),
            )
        # One compiled body for the whole stack: params get a leading
        # [num_layers] dim which the sharding rules treat as unsharded.
        stack = nn.scan(
            block,
            # intermediates: per-layer sown values (MoE router aux) come
            # out stacked along the layer dim.
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            length=cfg.num_layers,
            in_axes=(nn.broadcast, nn.broadcast),
        )(cfg, name="layers")
        x, _ = stack(x, cos, sin)
        return self._head(embed, x, return_hidden)

    def _looped(self, embed: nn.Embed, x: jax.Array, rotary: tuple, return_hidden: bool):
        """The ``layer_pattern`` stack ``loop_steps`` times on ONE set of
        parameters: x_t = final_norm(stack(x_{t-1})), and the exit gate's
        logit z_t = w_g . x_t + b_g a position. One ``nn.scan`` over the
        steps whose body is the stack and whose parameters are broadcast
        (closed over, not stacked: ``layers_<i>``, ``final_norm`` and
        ``exit_gate`` sit in the tree where an unlooped stack's do), so
        the program holds one copy of the layers however often they run,
        a shared leaf's gradient is summed over its visits by the scan's
        own backward pass, and whatever a layer sows gains a leading step
        axis. Per-sub-layer remat, the barrier after a layer and the flash
        kernels are the body's as they are an unlooped stack's."""
        cfg = self.cfg
        steps = cfg.loop_steps
        if steps < 1 or cfg.mtp_layers or cfg.objective != "next_token":
            raise ValueError(
                "a loop over the stack: loop_steps >= 1 of a next-token model "
                "without prediction modules"
            )
        _note_loop(steps, cfg.num_layers, len(cfg.layer_pattern))

        def tail(mdl, y):
            h = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(y)
            with jax.named_scope("loop/gate"):
                z = ExitGate(cfg.param_dtype, name="exit_gate")(h)
            return h, z

        if cfg.remat:
            # As a layer's: its input alone is kept for the backward pass.
            # Left to itself the norm and the gate keep four float32
            # [B, S, H] tensors a step (the cast stream, the normed rows,
            # the gate's promoted input): 2 GiB of the 16,384-token step's
            # four visits at hidden 2,048 by the v5e compiler's account.
            tail = nn.remat(tail, prevent_cse=False)

        def step(mdl, x, rotary):
            with jax.named_scope("loop/step"):
                y = _stack_layers(cfg, cfg.layer_pattern, x, rotary, "layers_{}".format)
                h, z = tail(mdl, y)
            return h, (h, z)

        _, (h, z) = nn.scan(
            step,
            variable_broadcast="params",
            variable_axes={"intermediates": 0},
            split_rngs={"params": False},
            in_axes=nn.broadcast,
            length=steps,
        )(self, x, rotary)
        if return_hidden:
            return h, z
        return self._logits(embed, h[-1])

    def _embedded(self, embed: nn.Embed, tokens: jax.Array) -> jax.Array:
        """The tokens' rows of the table, times ``embed_scale``."""
        x, scale = embed(tokens), self.cfg.embed_scale
        if scale == 1.0:
            return x
        with jax.named_scope("embed/scale"):
            return x * jnp.asarray(scale, x.dtype)

    def _head(
        self, embed: nn.Embed, x: jax.Array, return_hidden: bool, predicted=(),
    ) -> jax.Array:
        cfg = self.cfg
        final_norm = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")
        x = final_norm(x)
        if return_hidden:
            if predicted:
                return (x, *(final_norm(h) for h in predicted))
            return x
        return self._logits(embed, x)

    def _logits(self, embed: nn.Embed, x: jax.Array) -> jax.Array:
        """Float32 logits of normed states ``x`` through the head."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = embed.attend(x.astype(cfg.param_dtype))
        else:
            logits = nn.Dense(
                cfg.vocab_size,
                use_bias=False,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="lm_head",
            )(x)
        return logits.astype(jnp.float32)
