"""The held expert dispatch over the rows it filled and the tokens that
hold one (``MoEMLP._sorted_held``, ``llama._filled_tiles``,
``llama._gather_sum``): values, gradients and counters against the
whole-buffer formula it replaced, which this file keeps as the oracle; and
the lowered layer, which may not grow with the number of row tiles."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import llama
from torchft_tpu.models.llama import (
    HELD_ROW_TILE,
    MoEMLP,
    held_buffer_rows,
    nemotron_h_debug,
    sdar_moe_debug,
)

E, K, FIRST, COUNT = 16, 2, 4, 2  # the layer holds experts 4 and 5 of 16


# -- the oracle: the dispatch as it was, every operation over all R rows ------


@jax.custom_vjp
def _whole_held_rows(x, token, slot):
    return x[token]


def _whole_gather_sum(rows, slot, weights=None):
    padded = jnp.concatenate([rows, jnp.zeros((1, rows.shape[-1]), rows.dtype)])
    total = jnp.zeros((slot.shape[0], rows.shape[-1]), jnp.float32)
    for k in range(slot.shape[1]):
        part = padded[slot[:, k]].astype(jnp.float32)
        total = total + (part if weights is None else part * weights[:, k, None])
    return total


_whole_held_rows.defvjp(
    lambda x, token, slot: (x[token], slot),
    lambda slot, g: (_whole_gather_sum(g, slot).astype(g.dtype), None, None),
)


@jax.custom_vjp
def _whole_combine_held(ys, gates, slot, rows, valid):
    return _whole_gather_sum(ys, slot, gates)


def _whole_combine_held_bwd(res, g):
    ys, gates, slot, rows, valid = res
    g_rows = g[rows // gates.shape[1]]
    row_gate = jnp.where(valid, gates.reshape(-1)[rows], 0.0)
    d_ys = (g_rows * row_gate[:, None]).astype(ys.dtype)
    dots = jnp.where(valid, jnp.sum(ys.astype(jnp.float32) * g_rows, axis=-1), 0.0)
    d_gates = jnp.concatenate([dots, jnp.zeros((1,), dots.dtype)])[slot]
    return d_ys, d_gates.astype(gates.dtype), None, None, None


_whole_combine_held.defvjp(
    lambda ys, gates, slot, rows, valid: (
        _whole_gather_sum(ys, slot, gates), (ys, gates, slot, rows, valid)
    ),
    _whole_combine_held_bwd,
)


class WholeBufferMoE(MoEMLP):
    """``MoEMLP`` with the held dispatch of the parent commit."""

    def _sorted_held(self, x, probs, gate_vals, gate_idx, weights):
        cfg = self.cfg
        first, count = cfg.experts_held
        n_experts, k, H = cfg.num_experts, cfg.num_experts_per_tok, x.shape[-1]
        T = x.shape[0] * x.shape[1]
        R = held_buffer_rows(cfg, T)
        flat_idx = gate_idx.reshape(T * k)
        all_sizes = jnp.sum(
            flat_idx[:, None] == jnp.arange(n_experts, dtype=flat_idx.dtype)[None, :],
            axis=0, dtype=jnp.int32,
        )
        sizes = all_sizes[first : first + count]
        ends = jnp.minimum(jnp.cumsum(sizes), R)
        fit = jnp.diff(ends, prepend=0)
        n_fit = ends[-1]
        load = sizes.astype(jnp.float32)
        self.sow("intermediates", "moe_dropped", load.sum() - n_fit)
        self.sow("intermediates", "moe_held_share", load.sum() / (T * k))
        local = flat_idx - first
        key = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        rows = order[:R]
        valid = jnp.arange(R) < n_fit
        slot = jnp.where(inv < n_fit, inv, R).reshape(T, k)
        xs = _whole_held_rows(x.reshape(T, H).astype(cfg.dtype), rows // k, slot)
        gmm = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
            a, w, fit, preferred_element_type=cfg.dtype
        )
        ys = self._ffn(xs, weights, gmm)
        out = _whole_combine_held(ys, gate_vals.reshape(T, k), slot, rows, valid)
        return out.reshape(x.shape).astype(x.dtype)


# -- a layer, and logits that send exactly n assignments to the held experts --


def layer_config(form: str, **overrides):
    base = {"gated": sdar_moe_debug, "relu2": nemotron_h_debug}[form]
    return base(**{
        "hidden_size": 32, "intermediate_size": 16, "num_experts": E,
        "num_experts_per_tok": K, "experts_held": (FIRST, COUNT),
        "dtype": jnp.float32, **overrides,
    })


def logits_for(n_held: int, tokens: int, seed: int = 0) -> jax.Array:
    """[1, tokens, E] router logits under which the top-2 of the tokens
    hold ``n_held`` assignments to the held experts in all: one a token for
    the first ``min(n_held, tokens)`` tokens, a second for the first
    ``n_held - tokens`` of them; every other choice an absent expert. Noise
    under the bumps keeps the gates unlike one another."""
    assert 0 <= n_held <= 2 * tokens
    rng = np.random.default_rng(seed)
    logits = rng.normal(0.0, 0.3, (tokens, E)).astype(np.float32)
    t = np.arange(tokens)
    first = np.where(t < n_held, FIRST + t % COUNT, t % FIRST)  # held, else 0..3
    second = np.where(t < n_held - tokens, FIRST + (t + 1) % COUNT, 8 + t % 8)
    logits[t, first] += 8.0
    logits[t, second] += 6.0
    return jnp.asarray(logits)[None]


def run(module_cls, cfg, params, x, logits):
    """The layer's output, its counters, and the gradients of a scalar of
    the output by the parameters, the input and the logits."""
    def scalar(params, x, logits):
        out, sown = module_cls(cfg).apply(
            {"params": params}, x, logits, mutable=["intermediates"]
        )
        probe = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(out.shape)
        return jnp.sum(out * probe), (out, sown["intermediates"])

    (_, (out, sown)), grads = jax.jit(
        jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True)
    )(params, x, logits)
    return out, {k: float(v[0]) for k, v in sown.items()}, grads


def inputs(cfg, tokens: int, seed: int = 1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, tokens, cfg.hidden_size))
    params = MoEMLP(cfg).init(jax.random.PRNGKey(seed + 1), x)["params"]
    return params, x


def tokens_touched(cfg, logits, n_fit: int) -> int:
    """How many tokens hold a row of the buffer, counted apart from the
    layer: the top-K of the logits, the held assignments in the order of
    their experts, the first ``n_fit`` of them."""
    first, count = cfg.experts_held
    top = np.argsort(-np.asarray(logits[0]), axis=-1, kind="stable")[:, :K]
    local = top.reshape(-1) - first
    key = np.where((local >= 0) & (local < count), local, count)
    fitted = np.argsort(key, kind="stable")[:n_fit]
    return len(np.unique(fitted // K))


def token_run_share(touched: int, tokens: int) -> float:
    return -(-touched // HELD_ROW_TILE) * HELD_ROW_TILE / tokens


def assert_same(tiled, whole):
    for got, want in zip(jax.tree_util.tree_leaves(tiled), jax.tree_util.tree_leaves(whole)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


TOKENS = 1024  # T*K = 2048 assignments, 2 of 16 experts held: R = 1024, two tiles
ROWS = 1024
N_HELD = {
    "none": 0, "one": 1, "tile-1": HELD_ROW_TILE - 1, "tile": HELD_ROW_TILE,
    "tile+1": HELD_ROW_TILE + 1, "R-1": ROWS - 1, "R": ROWS, "past-R": ROWS + 476,
}


@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("n_held", N_HELD.values(), ids=N_HELD.keys())
def test_the_tiled_dispatch_is_the_whole_buffer_formula(form, n_held):
    cfg = layer_config(form)
    assert held_buffer_rows(cfg, TOKENS) == ROWS == 2 * llama._held_tile(ROWS)
    params, x = inputs(cfg, TOKENS)
    logits = logits_for(n_held, TOKENS)
    out, sown, grads = run(MoEMLP, cfg, params, x, logits)
    want_out, want_sown, want_grads = run(WholeBufferMoE, cfg, params, x, logits)
    assert_same((out, grads), (want_out, want_grads))
    # the counters: what landed, what did not fit, how far the loops ran
    n_fit = min(n_held, ROWS)
    assert sown["moe_held_share"] == want_sown["moe_held_share"] == n_held / (TOKENS * K)
    assert sown["moe_dropped"] == want_sown["moe_dropped"] == n_held - n_fit
    tiles = -(-n_fit // HELD_ROW_TILE)
    assert sown["moe_held_run_share"] == tiles * HELD_ROW_TILE / ROWS
    # the token side: none of the tokens, the first n_held of them, all of
    # them; past R, the 750 whose row of the first held expert fitted (the
    # second expert's 274 rows that fitted are tokens among those)
    touched = tokens_touched(cfg, logits, n_fit)
    assert touched == (min(n_held, TOKENS) if n_held <= ROWS else 750)
    assert sown["moe_held_token_run_share"] == token_run_share(touched, TOKENS)
    if n_held:  # the held experts' part reached the output and the gradients
        assert float(jnp.abs(grads[0]["experts_up"]).max()) > 0


@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("n_held", [0, 5, 64, 100], ids=["none", "few", "R", "past-R"])
def test_a_buffer_under_one_tile_is_run_whole(form, n_held):
    """64 tokens: R = 64 rows, under a tile, so there is no loop and the
    layer is the whole-buffer formula itself."""
    cfg = layer_config(form)
    rows = held_buffer_rows(cfg, 64)
    assert rows == 64 == llama._held_tile(rows) < HELD_ROW_TILE
    params, x = inputs(cfg, 64)
    logits = logits_for(n_held, 64)
    out, sown, grads = run(MoEMLP, cfg, params, x, logits)
    want_out, want_sown, want_grads = run(WholeBufferMoE, cfg, params, x, logits)
    assert_same((out, grads), (want_out, want_grads))
    assert sown["moe_dropped"] == want_sown["moe_dropped"] == max(n_held - rows, 0)
    assert sown["moe_held_run_share"] == sown["moe_held_token_run_share"] == 1.0
    lowered = jax.jit(
        lambda p, x, lg: MoEMLP(cfg).apply({"params": p}, x, lg)
    ).lower(params, x, logits).as_text()
    assert "while" not in lowered


@pytest.fixture
def nan_where_no_tile_ran(monkeypatch):
    """NaN in every row a loop does not write, the token side's compact
    buffer among them. The token side is jitted and reads ``_UNFILLED`` as
    it is traced, so what was traced before is forgotten, and after."""
    monkeypatch.setattr(llama, "_UNFILLED", jnp.nan)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("n_held", [0, 1, HELD_ROW_TILE + 1], ids=["none", "one", "tile+1"])
def test_no_row_of_a_tile_not_run_reaches_a_result(form, n_held, nan_where_no_tile_ran):
    """With NaN where the loops write nothing, outputs and gradients are
    still the oracle's: what reads the buffer reads filled rows only, and
    what reads the token side's compact buffer reads the tokens of the
    tiles run only."""
    cfg = layer_config(form)
    params, x = inputs(cfg, TOKENS, seed=3)
    logits = logits_for(n_held, TOKENS, seed=3)
    out, _, grads = run(MoEMLP, cfg, params, x, logits)
    want_out, _, want_grads = run(WholeBufferMoE, cfg, params, x, logits)
    assert_same((out, grads), (want_out, want_grads))


@pytest.mark.parametrize(
    "n_held", [0, HELD_ROW_TILE + 1, ROWS], ids=["no-token", "tile+1", "every-token"]
)
def test_bfloat16_rows_agree_too(n_held):
    """The cells compute in bfloat16: the same rows, the same roundings,
    with no token holding a row, with some, and with every one."""
    cfg = layer_config("gated", dtype=jnp.bfloat16)
    params, x = inputs(cfg, TOKENS)
    logits = logits_for(n_held, TOKENS)
    out, sown, grads = run(MoEMLP, cfg, params, x, logits)
    want_out, _, want_grads = run(WholeBufferMoE, cfg, params, x, logits)
    assert_same((out, grads), (want_out, want_grads))
    assert sown["moe_held_token_run_share"] == token_run_share(n_held, TOKENS)


def test_a_token_that_holds_no_row_gets_exact_zeros():
    """Tokens past the first ``n_held`` hold no row: their outputs are
    zeros to the bit (no shared expert here), and every token before them
    got something."""
    cfg = layer_config("gated", shared_expert_size=0)
    params, x = inputs(cfg, TOKENS)
    n_held = HELD_ROW_TILE + 1
    out, _, _ = run(MoEMLP, cfg, params, x, logits_for(n_held, TOKENS))
    assert not np.asarray(out)[0, n_held:].any()
    assert np.asarray(out)[0, :n_held].any(axis=-1).all()


# -- the lowered layer does not grow with the number of tiles -----------------


def lowered_layer(cfg, tokens: int) -> str:
    """Forward and backward of one layer, lowered for the TPU (where the
    grouped matmul is one instruction) with no backend at hand."""
    x = jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.float32)
    params = jax.eval_shape(
        lambda: MoEMLP(cfg).init(jax.random.PRNGKey(0), jnp.zeros(x.shape))["params"]
    )

    def scalar(params, x):
        return jnp.sum(jnp.square(MoEMLP(cfg).apply({"params": params}, x)))

    traced = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1))).trace(params, x)
    return traced.lower(lowering_platforms=("tpu",)).as_text()


def op_counts(text: str) -> dict:
    ops = ("while", "case", "if", "gather", "ragged_dot", "dynamic_slice",
           "dynamic_update_slice", "scatter", "sort")
    return {op: len(re.findall(rf"\b(?:stablehlo|chlo)\.{op}\b\"?\(?[ %]", text)) for op in ops}


@pytest.mark.parametrize("form,matrices", [("gated", 3), ("relu2", 2)])
def test_the_lowered_layer_does_not_grow_with_the_tile_count(form, matrices):
    """Forward and backward of one layer at a buffer of 2 tiles and of 32:
    the same instructions line for line, a text that differs in its
    shapes' digits only. Three grouped matmuls a matrix (forward, and the
    two of its transpose), each over all R rows; one loop each for the
    rows in, the activation, its gradient, the combine's transpose and,
    where two matrices read the rows, the sum of their two gradients; and
    one each for the token side's two passes, the combine and the
    transpose of the rows in, each with ONE gather of T rows by H after
    it. No scatter moves a row (the one there is puts the K gates'
    gradients among the router's E scores), and no tensor is [T, K, H]."""
    cfg = layer_config(form, shared_expert_size=0)
    texts, tokens_at = {}, {tiles: tiles * TOKENS // 2 for tiles in (2, 32)}
    for tiles, tokens in tokens_at.items():
        assert held_buffer_rows(cfg, tokens) == tiles * HELD_ROW_TILE
        assert tokens == tiles * llama._held_tile(tokens)
        texts[tiles] = lowered_layer(cfg, tokens)
    counts = op_counts(texts[32])
    assert counts == op_counts(texts[2])
    assert counts["ragged_dot"] == 3 * matrices
    assert counts["while"] == matrices + 2 + 2
    assert counts["case"] == counts["if"] == 0
    rows = 32 * HELD_ROW_TILE
    assert len(re.findall(rf'"chlo.ragged_dot".*\(tensor<{rows}x', texts[32])) == 3 * matrices
    assert texts[2].count("\n") == texts[32].count("\n")
    assert abs(len(texts[32]) - len(texts[2])) <= 0.03 * len(texts[2])
    H = cfg.hidden_size
    for tiles, text in texts.items():
        tokens = tokens_at[tiles]
        gathers = re.findall(r'"stablehlo\.gather".*-> (tensor<[^>]*>)', text)
        assert len(gathers) == counts["gather"]
        assert gathers.count(f"tensor<{tokens}x{H}xf32>") == 2  # one a pass
        assert f"tensor<{tokens}x{K}x{H}x" not in text
        scattered = re.findall(r'"stablehlo\.scatter"[^\n]*\n(?:[^\n]*\n)*?\s*\}\) : \(([^\n]*)', text)
        assert len(scattered) == counts["scatter"] == 1
        assert f"x{H}x" not in scattered[0] and f"x{E}xf32>" in scattered[0]
