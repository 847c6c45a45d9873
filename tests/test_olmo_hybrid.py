"""Olmo-Hybrid-7B: the gated-delta mixer's chunked form against the
position-by-position recurrence, values and gradients; the program's stack
(the norm AFTER each mixer, a rope-free attention with a whole-projection
QK-norm) against the benchmark's plain reference at a small size on the
CPU, in float32 with seeded weights; the two head shares against the uncut
layer; the triangular inverse; a sharded mesh; the fused step,
the split step and two replicas under Managers; the adapter's refusals;
the presets and ``train_hsdp.py --model olmo_hybrid``."""

import dataclasses
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.tests import test_olmo_hybrid_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, GatedDeltaConfig, gated_delta, llama
from torchft_tpu.models.gated_delta import (
    GatedDeltaMixer,
    gated_delta_chunked,
    unit_lower_inverse,
)
from torchft_tpu.models.llama import Attention, olmo_hybrid_7b, olmo_hybrid_debug
from torchft_tpu.parallel import auto_mesh, make_mesh
from torchft_tpu.parallel.sharding import param_specs
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.test_ft_step import two_replicas
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("olmo_hybrid", "adapter")
reference = cells.arch_module("olmo_hybrid", "reference")
flops = cells.arch_module("olmo_hybrid", "flops")
tiny, PUBLISHED = _reference_tests.tiny, _reference_tests.PUBLISHED

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj


# -- (a) the chunked form is the recurrence ---------------------------------------


def _rule_inputs(seq, decay, heads=3, dk=8, dv=12, seed=0):
    """Unit keys, scaled unit queries, beta over the whole of (0, 2), and
    log-decays near 0 ('slow': alpha near 1), far below ('fast': alpha near
    0) or spread over both."""
    k = jax.random.split(jax.random.PRNGKey(seed + seq), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(k[0], (2, seq, heads, dk))) * dk ** -0.5
    key = unit(jax.random.normal(k[1], (2, seq, heads, dk)))
    v = jax.random.normal(k[2], (2, seq, heads, dv))
    lo, hi = {"slow": (-9.0, -4.0), "fast": (0.5, 2.0), "spread": (-7.0, 1.5)}[decay]
    g = -jnp.exp(jax.random.uniform(k[3], (2, seq, heads), minval=lo, maxval=hi))
    beta = 2.0 * jax.nn.sigmoid(4.0 * jax.random.normal(k[4], (2, seq, heads)))
    return (q, key, v, g, beta), jax.random.normal(k[5], (2, seq, heads, dv))


@pytest.mark.parametrize("chunk,seq,decay", [
    (16, 40, "spread"), (64, 64, "slow"), (64, 150, "spread"), (16, 5, "fast"),
    (64, 128, "fast"), (16, 64, "slow"),
])
def test_the_chunked_delta_rule_is_the_recurrence(chunk, seq, decay):
    """Two chunk sizes; one chunk, whole chunks, a ragged end, less than a
    chunk; values, the last state, and the gradient of every input."""
    args, weigh = _rule_inputs(seq, decay)
    assert float(args[4].max()) > 1.9 and float(args[4].min()) < 0.1
    recurrence = jax.vmap(reference.delta_rule)

    def scalar(rule):
        def f(*a):
            o, last = rule(*a)
            return jnp.sum(o * weigh) + jnp.sum(jnp.sin(last))
        return f

    chunked = lambda *a: gated_delta_chunked(*a, chunk, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(chunked)(*args), jax.jit(recurrence)(*args)
        grads = [
            jax.jit(jax.grad(scalar(rule), argnums=(0, 1, 2, 3, 4)))(*args)
            for rule in (chunked, recurrence)
        ]
    assert got[0].shape == want[0].shape and got[1].shape == want[1].shape
    assert jnp.allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    assert jnp.allclose(got[1], want[1], rtol=2e-4, atol=2e-5)
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert float(jnp.abs(a - b).max()) <= 2e-4 * float(jnp.abs(b).max()) + 1e-6, name


@pytest.mark.parametrize("c", [16, 64])
def test_the_triangular_inverse_and_its_backward_pass(c):
    a = jnp.tril(0.1 * jax.random.normal(jax.random.PRNGKey(c), (2, 3, c, c)), -1)
    weigh = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    dense = lambda m: jnp.linalg.inv(jnp.eye(c) + m)  # noqa: E731
    assert jnp.allclose(unit_lower_inverse(a), dense(a), atol=2e-5)
    got, want = (jax.grad(lambda m, f=f: jnp.sum(f(m) * weigh))(a)
                 for f in (unit_lower_inverse, dense))
    assert jnp.allclose(got, want, rtol=1e-4, atol=2e-4)


def test_one_key_all_through_a_chunk_under_beta_two_is_still_the_recurrence():
    """The case the triangular SOLVE is there for: A = 2 x strictly-lower
    ones, whose powers a product form cannot cancel in float32 (A^32 has
    entries of 1e27); T itself is bounded by 2."""
    a = 2.0 * jnp.tril(jnp.ones((64, 64)), -1)
    want = np.linalg.inv(np.eye(64) + np.asarray(a, np.float64))
    assert np.abs(want).max() == pytest.approx(2.0)
    assert np.allclose(unit_lower_inverse(a), want, atol=1e-4)
    (q, key, v, g, beta), _ = _rule_inputs(128, "slow", heads=2)
    key = jnp.broadcast_to(key[:, :1], key.shape)
    args = (q, key, v, jnp.zeros_like(g), jnp.full_like(beta, 2.0))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: gated_delta_chunked(*a, 64, jnp.float32))(*args)
        want = jax.jit(jax.vmap(reference.delta_rule))(*args)
    assert jnp.allclose(got[0], want[0], rtol=1e-3, atol=1e-3)
    assert jnp.allclose(got[1], want[1], rtol=1e-3, atol=1e-3)


# -- (b) the program against the reference -----------------------------------------


def _setup(c, seq, batch=2, seed=0):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


def _system(c, seq, params=None, data=None):
    model, mesh, fresh, batch = _setup(c, seq)
    params, data = params or fresh, data or batch
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            params, data)
    return params, data, float(loss), grads


@pytest.mark.parametrize("seq,index", [(40, 1), (64, 0), (130, 1)])
def test_loss_and_every_gradient_match_the_reference(seq, index):
    """One period (three gated-delta layers, one attention, a feed-forward
    after each) at less than a chunk, one chunk and a ragged third chunk."""
    c = tiny(head_parallel_index=index)
    params, data, loss, grads = _system(c, seq)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    assert loss == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    assert len(errs) == 3 * 12 + 7 + 4 * 4 + 3
    # float32's own limit here: a chunk's decay matrix is the exponential of a
    # DIFFERENCE of cumulative log-decays, which at this size's strong decays
    # (a cumulative sum in the hundreds) keeps 1e-4 of a difference near 0
    assert max(errs.values()) < 5e-3, errs
    assert max(errs.values()) < reference.GRAD_REL_L2_TOL
    assert abs(loss - float(loss_ref)) / float(loss_ref) < reference.LOSS_REL_TOL


def test_a_dropped_term_is_outside_the_references_tolerances():
    """The program with beta not doubled, and the reference without its
    decay, each against the other side as it stands."""
    c = tiny()
    params, data, loss, grads = _system(c, 64)
    off = dict(c, linear_allow_neg_eigval=False)
    _, _, loss_off, grads_off = _system(off, 64, params, data)
    ref = {
        drop: jax.jit(lambda p, b, drop=drop: reference.loss_and_grads(p, b, c, drop=drop))(
            params, data)
        for drop in (None, "decay")
    }
    assert abs(loss - float(ref[None][0])) / float(ref[None][0]) < reference.LOSS_REL_TOL
    assert abs(loss_off - float(ref[None][0])) / float(ref[None][0]) > reference.LOSS_REL_TOL
    assert abs(loss - float(ref["decay"][0])) / float(ref["decay"][0]) > reference.LOSS_REL_TOL
    finite = lambda errs: max(v for v in errs.values() if np.isfinite(v))  # noqa: E731
    assert finite(_leaf_errors(grads_off, ref[None][1])) > reference.GRAD_REL_L2_TOL
    assert finite(_leaf_errors(grads, ref["decay"][1])) > reference.GRAD_REL_L2_TOL
    # and with the flag off on both sides they agree again
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, off))(params, data)
    assert loss_off == pytest.approx(float(loss_ref), rel=1e-5)
    assert max(_leaf_errors(grads_off, grads_ref).values()) < 5e-3


@pytest.mark.parametrize("kind", ["G", "*", "D"])
def test_the_norm_stands_after_the_mixer(kind):
    """x + RMSNorm(mixer(x)): what a layer adds to the stream has a root mean
    square of exactly the norm's scale (1 at the start) a row, whatever the
    mixer and however large its input; a pre-norm layer's addition has the
    mixer's own size."""
    cfg = olmo_hybrid_debug(dtype=jnp.float32)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    rms = lambda t: jnp.sqrt(jnp.mean(jnp.square(t), axis=-1))  # noqa: E731
    after = llama.MixerLayer(cfg, kind)
    params = after.init(jax.random.PRNGKey(0), x)
    assert jnp.allclose(rms(after.apply(params, x) - x), 1.0, atol=1e-3)
    before = llama.MixerLayer(dataclasses.replace(cfg, norm_after_mixer=False), kind)
    assert jax.tree_util.tree_structure(before.init(jax.random.PRNGKey(0), x)) == (
        jax.tree_util.tree_structure(params))
    assert not jnp.allclose(rms(before.apply(params, x) - x), 1.0, atol=0.1)


# -- (c) the share of the heads tied to the model -------------------------------------


def _columns(kernel, width, index, heads):
    """Columns of head-major ``kernel`` [..., 2 x heads x width] that the
    rank ``index`` of two holds."""
    return kernel[..., index * heads * width : (index + 1) * heads * width]


def test_the_two_head_shares_of_a_linear_mixer_add_up_to_the_uncut_mixer():
    """Four heads, or two ranks of two: every head's convolution,
    recurrence, norm and gate are its own, so the partial sums W_o's rows
    give (before the layer's norm) add up exactly."""
    whole = GatedDeltaConfig(num_heads=4, key_head_dim=8, value_head_dim=16)
    half = dataclasses.replace(whole, num_heads=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 70, 64))
    mixer = lambda m: GatedDeltaMixer(m, 64, 1e-6, jnp.float32)  # noqa: E731
    params = mixer(whole).init(jax.random.PRNGKey(0), x)["params"]
    with jax.default_matmul_precision("highest"):
        want = mixer(whole).apply({"params": params}, x)
        total = 0.0
        for index in range(2):
            cut = lambda name, width: {  # noqa: E731
                "kernel": _columns(params[name]["kernel"], width, index, 2)}
            conv = params["conv_kernel"]
            own = {
                "q_proj": cut("q_proj", 8), "k_proj": cut("k_proj", 8),
                "v_proj": cut("v_proj", 16), "g_proj": cut("g_proj", 16),
                "a_proj": cut("a_proj", 1), "b_proj": cut("b_proj", 1),
                "o_proj": {"kernel": params["o_proj"]["kernel"][index * 32 : index * 32 + 32]},
                "conv_kernel": jnp.concatenate([
                    _columns(conv[:, :32], 8, index, 2), _columns(conv[:, 32:64], 8, index, 2),
                    _columns(conv[:, 64:], 16, index, 2)], axis=-1),
                "A_log": params["A_log"][2 * index : 2 * index + 2],
                "dt_bias": params["dt_bias"][2 * index : 2 * index + 2],
                "norm_scale": params["norm_scale"],
            }
            total = total + mixer(half).apply({"params": own}, x)
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.linalg.norm(total - want)) < 1e-4 * float(jnp.linalg.norm(want))


@pytest.mark.parametrize("qk_norm", [False, True])
def test_the_two_head_shares_of_the_attention(qk_norm):
    """Without the QK-norm the shares' partial sums are the uncut layer's
    output. With it, a share normalises by the RMS over the channels it
    HOLDS (the configuration's stated distortion): each share is what the
    reference computes given that share, and their sum is not the uncut
    layer's, whose statistic is over all the channels."""
    cfg = olmo_hybrid_debug(dtype=jnp.float32, qk_norm=qk_norm)
    half = dataclasses.replace(cfg, num_heads=2, num_kv_heads=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, 64))
    params = Attention(cfg).init(jax.random.PRNGKey(0), x, None, None)["params"]
    if qk_norm:
        params["q_norm"]["scale"] = 1 + 0.2 * jax.random.normal(jax.random.PRNGKey(5), (64,))
    with jax.default_matmul_precision("highest"):
        want = Attention(cfg).apply({"params": params}, x, None, None)
        total = 0.0
        for index in range(2):
            heads, held = slice(2 * index, 2 * index + 2), slice(32 * index, 32 * index + 32)
            own = {name: {"kernel": params[name]["kernel"][:, heads]}
                   for name in ("wq", "wk", "wv")}
            own["wo"] = {"kernel": params["wo"]["kernel"][heads]}
            if qk_norm:
                own["q_norm"] = {"scale": params["q_norm"]["scale"][held]}
                own["k_norm"] = {"scale": params["k_norm"]["scale"][held]}
            part = Attention(half).apply({"params": own}, x, None, None)
            if qk_norm:  # the reference's attention, given that share
                given = reference._attention(
                    x, own, tiny(head_parallel_index=index), lambda a: a)
                assert jnp.allclose(part, given, rtol=1e-4, atol=1e-5)
            total = total + part
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5) != qk_norm


# -- (d) the steps, the counters, the mesh ------------------------------------------


def test_the_step_hands_on_the_mixers_counters(caplog):
    cfg = olmo_hybrid_debug()
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    gated_delta._NOTED.clear()
    with caplog.at_level(logging.INFO, logger="torchft_tpu.models.gated_delta"):
        new, metrics = make_train_step(model, mesh, sh, donate=False)(
            state, _data(cfg.vocab_size, 2, 64))
    assert "gated_delta: traced=xla chunk=64 seq=64" in caplog.text
    assert set(metrics) == {"loss", "grad_norm", "gdn_state_abs_max", "gdn_decay_min",
                            "gdn_beta_mean"}
    assert int(new.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert 0.0 < float(metrics["gdn_decay_min"]) < 1.0
    assert 0.5 < float(metrics["gdn_beta_mean"]) < 1.5  # sigmoid's mean, doubled
    assert 0.0 < float(metrics["gdn_state_abs_max"]) < 100.0


def test_the_rules_name_the_new_leaves_and_a_sharded_mesh_computes_the_same_step():
    """fsdp=2 x tp=2 on four virtual devices against one device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = olmo_hybrid_debug(dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: build_model(cfg, None).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    specs = param_specs(shapes)
    P = jax.sharding.PartitionSpec
    mixer = specs["layers_0"]["gdn"]
    assert set(mixer) == {"q_proj", "k_proj", "v_proj", "g_proj", "a_proj", "b_proj",
                          "o_proj", "conv_kernel", "A_log", "dt_bias", "norm_scale"}
    for name in ("q_proj", "k_proj", "v_proj", "g_proj", "a_proj", "b_proj"):
        assert mixer[name]["kernel"] == P("fsdp", "tp"), name
    assert mixer["o_proj"]["kernel"] == P("tp", "fsdp")
    assert mixer["conv_kernel"] == mixer["A_log"] == mixer["dt_bias"] == P()
    assert mixer["norm_scale"] == P()
    assert specs["layers_0"]["norm"]["scale"] == specs["layers_6"]["attn"]["q_norm"]["scale"]
    assert specs["layers_6"]["attn"]["wq"]["kernel"] == P("fsdp", "tp", None)
    data = _data(cfg.vocab_size, 4, 64)
    seen = []
    for mesh in (auto_mesh(1, devices=jax.devices()[:1]), make_mesh(fsdp=2, tp=2)):
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 64))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "gdn_state_abs_max")])
    assert seen[0] == pytest.approx(seen[1], rel=2e-3)
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-4)


@pytest.mark.timeout(300)
def test_two_replicas_fed_one_batch_commit_bitwise_equal_parameters():
    """``FTStep`` over the split step (``make_split_grad_step`` then
    ``make_apply_step``): the mixers' counters stay with the step, the
    gradients ride the allreduce, and both replicas hold the same
    parameters bit for bit."""
    (losses0, leaves0), (losses1, leaves1) = two_replicas(olmo_hybrid_debug, "olmo")
    assert losses0 == losses1 and len(losses0) == 2 and losses0[0] != losses0[1]
    assert all(np.array_equal(a, b) for a, b in zip(leaves0, leaves1))


# -- (e) the file, the adapter, the presets -----------------------------------------


def test_the_count_is_the_models_own_count_of_its_tree():
    def own_count(c, seq):
        model = build_model(adapter.model_config(c, seq), None)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))
        )["params"]
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes))

    assert own_count(PUBLISHED, 256) == flops.total_params(PUBLISHED) == 766_241_946
    assert own_count(tiny(), 32) == flops.total_params(tiny())


def test_the_file_states_its_cuts_and_the_adapter_reads_every_key():
    c = PUBLISHED
    catalog = {  # the catalog row's config, every key
        "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
        "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": ["linear_attention"] * 3 + ["full_attention"],
        "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    }
    catalog["layer_types"] = catalog["layer_types"] * 8
    cut = {"num_hidden_layers", "layer_types", "num_attention_heads", "num_key_value_heads",
           "linear_num_key_heads", "linear_num_value_heads", "vocab_size"}
    assert set(c["reduced"]) == cut
    for key, value in catalog.items():
        if key in cut:
            entry = c["reduced"][key]
            assert entry["published"] == value != entry["run"] == c[key] and entry["why"]
        else:
            assert c[key] == value, key
    assert c["layer_types"] == catalog["layer_types"][:4]
    assert c["num_attention_heads"] * c["head_parallel_chips"] == 30
    assert c["linear_num_key_heads"] * c["head_parallel_chips"] == 30
    assert c["vocab_size"] * c["vocab_parallel_chips"] == 100352
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    assert c["stands_for"] and set(c["distortions"]) >= {"feed_forward_held_whole",
                                                         "qk_norm_statistic"}
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "olmo-hybrid-7b-l4h15")
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"].split(";")[0]
    cell = cells.load_cell("olmo-hybrid-raw")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"]) == (1, 2, 8192)
    assert {m["name"] for m in cell.per_layer} >= {
        "gdn_ms", "gdn_roofline", "gdn_state_abs_max", "flash_ms", "flash_roofline", "mfu_pct"}
    cfg = adapter.model_config(c, 8192)
    assert (cfg.layer_pattern, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope,
            cfg.qk_norm, cfg.norm_after_mixer, cfg.vocab_size) == (
        "GDGDGD*D", 15, 15, 128, False, True, True, 12544)
    assert cfg.gated_delta == GatedDeltaConfig(15, 96, 192, 4, True)
    assert gated_delta.CHUNK == flops.CHUNK == 64


@pytest.mark.parametrize("key,value,says", [
    ("model_type", "olmo2", "model_type"),
    ("hidden_act", "gelu", "hidden_act"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_parameters", {"rope_theta": 500000.0}, "rope_parameters"),
    ("layer_types", ["linear_attention"] * 3 + ["sliding_attention"], "layer_types"),
    ("layer_types", ["linear_attention"] * 3, "layer_types"),
    ("max_position_embeddings", 4096, "max_position_embeddings"),
    ("head_parallel_chips", 7, "head_parallel_chips"),
    ("head_parallel_index", 2, "head_parallel_index"),
    ("num_key_value_heads", 5, "key/value"),
    ("linear_num_value_heads", 30, "linear_num_value_heads"),
    ("vocab_parallel_chips", 0, "vocab_parallel_chips"),
])
def test_the_adapter_refuses_by_name_what_the_program_does_not_compute(key, value, says):
    with pytest.raises(cells.CellError, match=says):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 8192)


def test_the_adapter_refuses_a_file_that_lacks_a_key_or_has_one_to_spare(tmp_path):
    lacking = {k: v for k, v in PUBLISHED.items() if k != "linear_conv_kernel_dim"}
    with pytest.raises(cells.CellError, match="linear_conv_kernel_dim"):
        adapter.model_config(lacking, 8192)
    from tests.test_nemotron_h import _tiny_table

    with pytest.raises(cells.CellError, match="linear_conv_bias"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(linear_conv_bias=True)))
    assert cells.load_cell("w", _tiny_table(tmp_path, tiny())).arch_dir.endswith("olmo_hybrid")


def test_the_presets():
    cfg = olmo_hybrid_7b()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) == (
        3840, 32, 30, 30, 128, 11008, 100352, 65536, 1e-6)
    assert cfg.layer_pattern == "GDGDGD*D" * 8 and len(cfg.layer_pattern) == 2 * cfg.num_layers
    assert cfg.gated_delta == GatedDeltaConfig(30, 96, 192, 4, True)
    assert (cfg.gated_delta.key_dim, cfg.gated_delta.value_dim,
            cfg.gated_delta.conv_dim) == (2880, 5760, 11520)
    assert (cfg.qk_norm, cfg.rope, cfg.norm_after_mixer, cfg.tie_embeddings) == (
        True, False, True, False)
    cut = adapter.model_config(PUBLISHED, 8192)
    same = ("hidden_size", "head_dim", "intermediate_size", "norm_eps", "qk_norm", "rope",
            "norm_after_mixer", "tie_embeddings", "num_experts")
    assert all(getattr(cut, k) == getattr(cfg, k) for k in same)
    assert cut.layer_pattern == cfg.layer_pattern[:8]
    small = olmo_hybrid_debug()
    assert PRESETS["olmo_hybrid"] is olmo_hybrid_debug
    assert (small.layer_pattern, small.gated_delta.num_heads, small.norm_after_mixer) == (
        "GDGDGD*D", 4, True)
    # a model that sets none of this is what it was
    assert (llama.LlamaConfig().gated_delta, llama.LlamaConfig().norm_after_mixer) == (None, False)


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_small_preset(tmp_path):
    """``train_hsdp.py --model olmo_hybrid``: one group, the Manager in the
    loop, three committed steps on the CPU."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "olmo_hybrid", "--steps", "3",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 3, steps
    assert "gated_delta: traced=xla chunk=64 seq=32" in proc.stderr
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 3
