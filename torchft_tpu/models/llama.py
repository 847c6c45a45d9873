"""Llama-3-style decoder-only transformer, TPU-first.

Design notes (why this is not a torch port):
- flax.linen + einsum contractions keep every FLOP on the MXU; compute in
  bfloat16, params in float32 (standard TPU mixed precision).
- The layer stack is an ``nn.scan`` over a single remat'd block: one XLA
  while-loop body compiled once regardless of depth (fast compiles, and
  rematerialization trades HBM for FLOPs as the scaling playbook suggests).
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
import logging
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any

logger = logging.getLogger(__name__)
_ATTN_NOTED: set = set()


def _note_attention(asked: str, traced: str, seq_len: int) -> None:
    """Says once per (asked, traced, seq_len), at trace time, which
    attention implementation a step really took — 'flash' routes to dense
    below ``flash_min_seq`` or on unsupported tilings, and a chip run
    must be able to prove which branch it compiled."""
    key = (asked, traced, seq_len)
    if key not in _ATTN_NOTED:
        _ATTN_NOTED.add(key)
        logger.log(
            logging.INFO if asked == traced else logging.WARNING,
            "attention: asked=%s traced=%s seq=%d", asked, traced, seq_len,
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
    remat: bool = True
    # 'dense' | 'flash' | 'ring' | 'ulysses'. flash = Pallas on-chip blocked attention
    # (ops/flash_attention.py, dense fallback for odd seq lens); ring
    # shards the sequence over the 'sp' mesh axis.
    attn_impl: str = "dense"
    # Below this sequence length the 'flash' impl routes to dense (measured
    # v5e crossover; the blocked kernel wins from ~2k and is mandatory past
    # dense's O(S^2) memory wall).
    flash_min_seq: int = 2048
    # Flash kernel tile sizes (q rows / kv cols per VMEM block). 512x512
    # is the v5e default; exposed for on-chip grid tuning (smaller block_q
    # raises grid parallelism, larger block_k amortizes the kv sweep).
    flash_block_q: int = 512
    flash_block_k: int = 512
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
    # RMSNorm (learned scale) over the WHOLE query projection and over the
    # whole key projection, before the split into heads and before RoPE
    # (OLMoE's form; not a per-head norm).
    qk_norm: bool = False
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
    positions: jax.Array, head_dim: int, theta: float, dtype: Dtype
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables of shape [..., head_dim/2] for given positions."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs
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
) -> jax.Array:
    """Plain causal GQA attention. q: [B,S,Hq,Dh], k/v: [B,S,Hkv,Dh].

    Single large einsum pair so XLA tiles it onto the MXU; softmax in fp32.
    """
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, dh)
    scale = dh**-0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, s, hq, dh)


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


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        cfg = self.cfg
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
        if cfg.qk_norm:
            whole = lambda t, name: RMSNorm(  # noqa: E731
                cfg.norm_eps, cfg.param_dtype, name=name
            )(t.reshape(*t.shape[:2], -1)).reshape(t.shape)
            q, k = whole(q, "q_norm"), whole(k, "k_norm")
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if cfg.attn_impl in ("ring", "ulysses"):
            assert cfg.attn_fn is not None, (
                f"{cfg.attn_impl} attention needs cfg.attn_fn"
            )
            _note_attention(cfg.attn_impl, cfg.attn_impl, q.shape[1])
            out = cfg.attn_fn(q, k, v)
        elif cfg.attn_impl == "flash":
            from torchft_tpu.ops.flash_attention import (
                flash_attention,
                supports,
            )

            if q.shape[1] >= cfg.flash_min_seq and supports(
                q.shape[1], cfg.flash_block_q, cfg.flash_block_k
            ):
                _note_attention("flash", "flash", q.shape[1])
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
        return nn.DenseGeneral(
            features=cfg.hidden_size,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="wo",
        )(out)


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
        gate = proj(cfg.intermediate_size, "gate")(x)
        up = proj(cfg.intermediate_size, "up")(x)
        return proj(cfg.hidden_size, "down")(nn.silu(gate) * up)


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

    Sown per layer under "intermediates" (parallel/train.py reads them by
    name): ``router_aux``, ``router_z``, ``moe_max_load`` (largest
    expert's assignments over the mean), ``moe_dropped`` (assignments not
    computed). The reference has no MoE/EP anywhere (SURVEY.md §2.3).
    """

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        E = cfg.num_experts
        K = cfg.num_experts_per_tok
        if K > E:
            raise ValueError(
                f"num_experts_per_tok ({K}) > num_experts ({E})"
            )
        H = x.shape[-1]

        # Router in fp32 for numerically stable softmax/top-k.
        router_logits = nn.Dense(
            E,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            name="router",
        )(x.astype(jnp.float32))  # [B,S,E]
        probs = jax.nn.softmax(router_logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [B,S,K]
        lse = jax.nn.logsumexp(router_logits, axis=-1)
        self.sow("intermediates", "router_z", jnp.mean(jnp.square(lse)))

        expert = lambda shape, name: self.param(  # noqa: E731
            name, nn.initializers.lecun_normal(), shape, cfg.param_dtype
        ).astype(cfg.dtype)
        w_gate = expert((E, H, cfg.intermediate_size), "experts_gate")
        w_up = expert((E, H, cfg.intermediate_size), "experts_up")
        w_down = expert((E, cfg.intermediate_size, H), "experts_down")
        dropless = cfg.expert_capacity_factor is None
        dispatch = self._sorted if dropless else self._capacity
        return dispatch(x, probs, gate_vals, gate_idx, w_gate, w_up, w_down)

    def _sow_routing(self, probs, load, fractions, dropped) -> None:
        """``load`` [E]: assignments routed to each expert; ``fractions``
        [E]: the f_e of the balance term E * sum_e f_e * P_e (1 at uniform
        routing), P_e the mean router probability of e."""
        E = load.shape[0]
        p_e = probs.reshape(-1, E).mean(axis=0)
        self.sow("intermediates", "router_aux", E * jnp.sum(fractions * p_e))
        self.sow("intermediates", "moe_max_load", load.max() * E / load.sum())
        self.sow("intermediates", "moe_dropped", dropped)

    def _sorted(self, x, probs, gate_vals, gate_idx, w_gate, w_up, w_down):
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

        order = jnp.argsort(flat_idx, stable=True)  # sorted row -> assignment
        inv = jnp.argsort(order)  # assignment -> sorted row
        xs = _sorted_rows(x.reshape(T, H).astype(cfg.dtype), order, inv)
        gmm = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
            a, w, group_sizes, preferred_element_type=cfg.dtype
        )
        ys = gmm(nn.silu(gmm(xs, w_gate)) * gmm(xs, w_up), w_down)  # [T*K,H]
        y = _permute_rows(ys, inv, order).reshape(T, K, H)
        out = jnp.einsum(
            "tkh,tk->th", y, gate_vals.reshape(T, K),
            preferred_element_type=jnp.float32,
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


class _ScanBlock(Block):
    """Block with the (carry, ys) return contract nn.scan requires."""

    @nn.compact
    def __call__(self, x, cos, sin):  # type: ignore[override]
        return super().__call__(x, cos, sin), None


class Transformer(nn.Module):
    """Decoder-only LM. __call__(tokens [B,S], positions [B,S]) -> logits."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        return_hidden: bool = False,
    ) -> jax.Array:
        """``return_hidden=True`` returns the post-final-norm hidden states
        [B,S,H] in cfg.dtype instead of logits — the chunked-loss path
        (parallel/train.py:_loss_fn) projects them onto the vocab in
        sequence chunks so the full [B,S,V] fp32 logits are never
        materialized."""
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="embed",
        )
        x = embed(tokens)
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
        x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
        if return_hidden:
            return x
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
