"""SmallThinker-21BA3B (its banded flash kernels' own tests are in
tests/test_flash_attention.py): the program's stack (a global rope-free attention and three windowed
rotary ones, an expert layer of ReGLU experts after each, its router
reading the attention's input) against the benchmark's plain reference at
a small size on the CPU, in float32 with seeded weights; the expert shares
against the uncut layer; the step's counters; a sharded mesh; the adapter's
refusals; the presets and ``train_hsdp.py --model smallthinker_debug``."""

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
from benchmark.tests import test_smallthinker_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, llama
from torchft_tpu.models.llama import (
    Attention,
    MoEMLP,
    smallthinker_21b,
    smallthinker_debug,
    window_attention,
)
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.parallel import auto_mesh, make_mesh
from torchft_tpu.parallel.sharding import param_specs
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("smallthinker", "adapter")
reference = cells.arch_module("smallthinker", "reference")
flops = cells.arch_module("smallthinker", "flops")
tiny, PUBLISHED = _reference_tests.tiny, _reference_tests.PUBLISHED

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj


# -- (b) the program against the reference -----------------------------------------


def _setup(c, seq, batch=2, seed=0, **cfg_overrides):
    # through sample_config, as every comparison with the reference is: a
    # sequence no longer than the window is compared under a quarter of itself
    cfg = dataclasses.replace(
        adapter.sample_config(adapter.model_config(c, seq), seq), remat=False, **cfg_overrides)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


def _system(c, seq, params=None, **cfg_overrides):
    model, mesh, fresh, data = _setup(c, seq, **cfg_overrides)
    params = params or fresh
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            params, data)
    return params, data, float(loss), grads


# The CPU comparison's limit on a gradient leaf: float32 on both sides, so
# what is left is the order of the sums (the worst leaf reads 1e-6 to 1e-5).
# Anything rounded to bf16 (2^-9) reads above it, and so does a band edge
# moved by one position.
CPU_GRAD_TOL = 2e-4
FLASH = dict(attn_impl="flash", flash_min_seq=32, flash_block_q=16, flash_block_k=16)


@pytest.mark.parametrize("seq,index,kernels", [
    (40, 1, {}), (64, 0, FLASH), (96, 3, FLASH), (8, 2, {}),
])
def test_loss_and_every_gradient_match_the_reference(seq, index, kernels):
    """One period (a global rope-free attention, three windowed rotary
    ones, an expert layer after each) under a window of 12: shorter than
    every sequence but the last; dense under the band mask and through the
    banded kernels; four expert ranks."""
    c = tiny(expert_parallel_index=index)
    params, data, loss, grads = _system(c, seq, **kernels)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    assert loss == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    # an attention sub-layer's 4 projections, norm and router, an expert
    # sub-layer's 3 stacks and norm, the table, the head and the final norm
    assert len(errs) == 4 * 6 + 4 * 4 + 3
    assert max(errs.values()) < CPU_GRAD_TOL, errs
    assert max(errs.values()) < reference.GRAD_REL_L2_TOL
    assert abs(loss - float(loss_ref)) / float(loss_ref) < reference.LOSS_REL_TOL


@pytest.mark.parametrize("what", ["band_edge_in", "band_edge_out", "no_window", "rope_everywhere",
                                  "no_rope", "swiglu", "router_after", "bf16"])
def test_a_moved_band_a_misplaced_router_or_a_lower_precision_fails_the_comparison(what):
    """Each departure at the program's side reads over the CPU limit: the
    comparison can see what it is there to see."""
    c, seq = tiny(), 40
    params, data, _, _ = _system(c, seq)
    _, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    base = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    changed = {
        "band_edge_in": dict(sliding_window=11),
        "band_edge_out": dict(sliding_window=13),
        "no_window": dict(sliding_window=seq),
        "rope_everywhere": dict(rope=True),
        "no_rope": dict(layer_pattern="*E*E*E*E", sliding_window=None),
        "swiglu": dict(expert_act="swiglu"),
        "router_after": dict(router_ahead=False),
        "bf16": dict(dtype=jnp.bfloat16),
    }[what]
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(dataclasses.replace(base, **changed), mesh)
    if what == "router_after":  # the same kernels, held where that stack holds them
        params = dict(params)
        for i in range(0, 8, 2):
            attn = dict(params[f"layers_{i}"])
            params[f"layers_{i + 1}"] = dict(
                params[f"layers_{i + 1}"],
                mlp=dict(params[f"layers_{i + 1}"]["mlp"], router=attn.pop("router")))
            params[f"layers_{i}"] = attn
    with jax.default_matmul_precision("highest"):
        _, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            params, data)
    if what == "router_after":
        grads = dict(grads)
        for i in range(0, 8, 2):
            mlp = dict(grads[f"layers_{i + 1}"]["mlp"])
            grads[f"layers_{i}"] = dict(grads[f"layers_{i}"], router=mlp.pop("router"))
            grads[f"layers_{i + 1}"] = dict(grads[f"layers_{i + 1}"], mlp=mlp)
    assert max(_leaf_errors(grads, grads_ref).values()) > CPU_GRAD_TOL


def test_the_router_reads_the_input_of_the_attention_before_it():
    """Another W_o in every attention leaves the FIRST layer's routing what
    it was, bit for bit (its router read the same normed embedding), though
    that layer's experts now read another input; the reference agrees with
    the changed program too."""
    c, seq = tiny(), 40
    model, mesh, params, data = _setup(c, seq)
    changed = dict(params)
    for i in range(0, 8, 2):
        attn = params[f"layers_{i}"]["attn"]
        changed[f"layers_{i}"] = dict(
            params[f"layers_{i}"], attn=dict(attn, wo={"kernel": -2.0 * attn["wo"]["kernel"]}))

    def routed(p):
        out, sown = model.apply({"params": p}, data["inputs"], mutable=["intermediates"])
        first = sown["intermediates"]["layers_1"]["mlp"]
        return out, {k: float(v[0]) for k, v in first.items()}, sown["intermediates"]

    out, first, sown = routed(params)
    out2, first2, sown2 = routed(changed)
    assert first == first2 and set(first) >= {"router_aux", "moe_held_share", "moe_max_load"}
    assert float(jnp.abs(out - out2).max()) > 1e-3
    # the later layers' routers read what the changed attentions wrote
    assert float(sown["layers_3"]["mlp"]["router_aux"][0]) != float(
        sown2["layers_3"]["mlp"]["router_aux"][0])
    # the router is the attention sub-layer's leaf, and the expert layer has none
    assert "router" in params["layers_2"] and "router" not in params["layers_3"]["mlp"]
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            changed, data)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(changed, data)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    assert max(_leaf_errors(grads, grads_ref).values()) < CPU_GRAD_TOL


def test_a_router_ahead_needs_an_attention_layer_to_hold_it():
    cfg = smallthinker_debug()
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="no attention layer before it"):
        llama.Transformer(dataclasses.replace(cfg, layer_pattern="*EE")).init(
            jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="layer_pattern stack"):
        llama.Transformer(dataclasses.replace(cfg, layer_pattern=None)).init(
            jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="sliding_window"):
        llama.Transformer(dataclasses.replace(cfg, sliding_window=None)).init(
            jax.random.PRNGKey(0), toks)
    for impl in ("ring", "ulysses"):
        with pytest.raises(ValueError, match="windowed attention under"):
            Attention(dataclasses.replace(cfg, attn_impl=impl), window=True).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)), jnp.ones((1, 8, 8)),
                jnp.zeros((1, 8, 8)))


def test_reglu_is_its_definition():
    """down(relu(gate x) * up x), every expert held and every row by hand."""
    cfg = smallthinker_debug(experts_held=None, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    layer = MoEMLP(cfg)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x).reshape(16, 64)
        rows = x.reshape(16, 64)
        logits = rows @ params["router"]["kernel"]
        for t in range(16):
            top, idx = jax.lax.top_k(logits[t], 3)
            want = jnp.zeros((64,))
            for g, e in zip(jax.nn.softmax(top), idx):
                hidden = jnp.maximum(rows[t] @ params["experts_gate"][e], 0.0) * (
                    rows[t] @ params["experts_up"][e])
                want = want + g * (hidden @ params["experts_down"][e])
            assert jnp.allclose(got[t], want, rtol=1e-4, atol=1e-5)
        silu = MoEMLP(dataclasses.replace(cfg, expert_act="swiglu")).apply({"params": params}, x)
    assert float(jnp.abs(silu.reshape(16, 64) - got).max()) > 1e-3
    with pytest.raises(ValueError, match="expert_act"):
        MoEMLP(dataclasses.replace(cfg, expert_act="geglu")).init(jax.random.PRNGKey(0), x)


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of one layer's sixteen, all handed
    the same logits (the router is replicated). The routed parts the eight
    compute are the uncut reference layer; no chip drops a row and their
    held shares are the whole."""
    whole = tiny(moe_num_primary_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    logits = 1.5 * jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
    full = MoEMLP(adapter.model_config(whole, 32))
    params = full.init(jax.random.PRNGKey(0), x, logits)["params"]
    assert "router" not in params  # handed logits, the layer holds no router
    m, r = x.reshape(-1, 64), logits.reshape(-1, 16)
    ident = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(m, r, params, whole, ident)
        assert jnp.allclose(full.apply({"params": params}, x, logits).reshape(want.shape), want,
                            rtol=1e-4, atol=1e-5)
        total, held_share = jnp.zeros_like(want), 0.0
        for index in range(8):
            share = tiny(moe_num_primary_experts=2, expert_parallel_chips=8,
                         expert_parallel_index=index)
            own = {k: v[2 * index : 2 * index + 2] for k, v in params.items()}
            out, sown = MoEMLP(adapter.model_config(share, 32)).apply(
                {"params": own}, x, logits, mutable=["intermediates"])
            sown = sown["intermediates"]
            total = total + out.reshape(want.shape)
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            # the share's own reference is the share
            assert jnp.allclose(out.reshape(want.shape),
                                reference.experts(m, r, own, share, ident)[0],
                                rtol=1e-4, atol=1e-5)
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0) and float(jnp.linalg.norm(want)) > 0.1


# -- (c) the steps, the counters, the mesh ------------------------------------------


def test_the_step_hands_on_the_bands_and_the_experts_counters(caplog):
    cfg = smallthinker_debug(**FLASH)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    llama._ATTN_NOTED.clear()
    with caplog.at_level(logging.INFO, logger="torchft_tpu.models.llama"):
        new, metrics = make_train_step(model, mesh, sh, donate=False)(
            state, _data(cfg.vocab_size, 2, 64))
    assert ("attention: asked=flash/window traced=flash/window seq=64 window=16 tiles=16x16"
            in caplog.text)
    assert "attention: asked=flash traced=flash seq=64 tiles=16x16" in caplog.text
    assert "WARNING" not in [r.levelname for r in caplog.records]
    assert set(metrics) == {
        "loss", "grad_norm", "swa_kept_share", "moe_held_share", "moe_held_run_share",
        "moe_held_token_run_share",
        "moe_dropped", "moe_max_load", "router_aux", "router_z"}
    assert int(new.step) == 1 and np.isfinite(float(metrics["loss"]))
    # four tiles a side, a sweep of two: 7 tiles of 16 x 16 hold the 904 kept entries
    assert float(metrics["swa_kept_share"]) == pytest.approx(
        (16 * 17 // 2 + 48 * 16) / (7 * 256))
    assert window_attention(cfg, 64) == ((16, 16), pytest.approx(904 / 1792))
    # below flash_min_seq: dense under the band mask, the whole square computed
    assert window_attention(smallthinker_debug(attn_impl="flash"), 64) == (
        None, pytest.approx(904 / 4096))
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0 and float(metrics["moe_dropped"]) == 0.0
    # the published cell's schedule, from shapes alone
    cut = adapter.model_config(PUBLISHED, 16384)
    assert window_attention(cut, 16384) == ((1024, 1024), pytest.approx(0.800, abs=5e-4))
    assert llama.held_buffer_rows(cut, 16384) == 49152 == 4 * 16384 * 6 // 8


def test_the_rules_name_the_router_and_a_sharded_mesh_computes_the_same_step():
    """fsdp=2 x tp=2 on four virtual devices against one device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = smallthinker_debug(dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: build_model(cfg, None).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    specs = param_specs(shapes)
    P = jax.sharding.PartitionSpec
    for i in (0, 2):  # a global layer's and a windowed one's
        assert specs[f"layers_{i}"]["router"]["kernel"] == P("fsdp", None)
        assert specs[f"layers_{i}"]["attn"]["wq"]["kernel"] == P("fsdp", "tp", None)
    assert set(specs["layers_1"]["mlp"]) == {"experts_gate", "experts_up", "experts_down"}
    data = _data(cfg.vocab_size, 4, 64)
    seen = []
    for mesh in (auto_mesh(1, devices=jax.devices()[:1]), make_mesh(fsdp=2, tp=2)):
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 64))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "router_aux")])
    assert seen[0] == pytest.approx(seen[1], rel=2e-3)
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-4)


def test_remat_saves_the_logits_and_computes_the_same_step():
    """Per-sub-layer remat: the attention sub-layer's second output is an
    input of the expert sub-layer, so the step is the unremat'd one's."""
    data = _data(256, 2, 64)
    seen = []
    for remat in (False, True):
        cfg = smallthinker_debug(dtype=jnp.float32, remat=remat)
        mesh = auto_mesh(1, devices=jax.devices()[:1])
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "moe_held_share")])
    assert seen[0] == pytest.approx(seen[1], rel=1e-5)


# -- (d) the file, the adapter, the presets -----------------------------------------


def test_the_count_is_the_models_own_count_of_its_tree():
    def own_count(c, seq):
        model = build_model(adapter.model_config(c, seq), None)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))
        )["params"]
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes))

    assert own_count(PUBLISHED, 256) == flops.total_params(PUBLISHED) == 643_852_800
    assert own_count(tiny(), 32) == flops.total_params(tiny())


def test_the_file_states_its_cuts_and_the_adapter_reads_every_key():
    c = PUBLISHED
    catalog = {  # the catalog row's config, every key
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
    }
    cut = {"num_hidden_layers", "rope_layout", "sliding_window_layout",
           "moe_num_primary_experts", "vocab_size"}
    assert set(c["reduced"]) == cut
    for key, value in catalog.items():
        if key in cut:
            entry = c["reduced"][key]
            assert entry["published"] and entry["run"] and entry["why"]
            assert c[key] != value
        else:
            assert c[key] == value, key
    assert c["rope_layout"] == c["sliding_window_layout"] == catalog["rope_layout"][:8]
    assert c["reduced"]["num_hidden_layers"]["published"] == 52
    assert c["moe_num_primary_experts"] * c["expert_parallel_chips"] == 64
    assert c["vocab_size"] * c["vocab_parallel_chips"] == 151936
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    assert c["stands_for"] and set(c["distortions"]) >= {
        "rows_per_expert", "head_share", "uniform_tokens", "host_share"}
    assert set(c["assumed"]) >= {"router input", "router", "window", "rotary", "layer form",
                                 "router_aux_loss_coef", "initial values"}
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "smallthinker-21b-l8e8")
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"].split(";")[0]
    cfg = adapter.model_config(c, 16384)
    assert (cfg.layer_pattern, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope,
            cfg.sliding_window, cfg.router_ahead, cfg.qk_norm, cfg.vocab_size,
            cfg.rope_theta, cfg.norm_eps) == (
        "*EWEWEWE" * 2, 28, 4, 128, False, 4096, True, False, 18992, 1.5e6, 1e-6)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.intermediate_size,
            cfg.shared_expert_size, cfg.router_score, cfg.norm_topk_prob, cfg.expert_act,
            cfg.router_aux_coef, cfg.expert_capacity_factor) == (
        64, 6, (0, 8), 768, 0, "softmax", True, "reglu", 0.001, None)


@pytest.mark.parametrize("key,value,says", [
    ("model_name", "smallthinker_4b_instruct", "model_name"),
    ("moe_primary_router_apply_softmax", False, "moe_primary_router_apply_softmax"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_layout", [1, 1, 1, 1, 0, 1, 1, 1], "disagree"),
    ("sliding_window_layout", [0, 1, 1, 1, 0, 1, 1], "disagree"),
    ("max_position_embeddings", 8192, "max_position_embeddings"),
    ("sliding_window_size", 0, "sliding_window_size"),
    ("expert_parallel_index", 8, "expert_parallel_index"),
    ("num_key_value_heads", 3, "num_key_value_heads"),
    ("moe_num_active_primary_experts", 65, "moe_num_active_primary_experts"),
    ("vocab_parallel_chips", 0, "vocab_parallel_chips"),
])
def test_the_adapter_refuses_by_name_what_the_program_does_not_compute(key, value, says):
    with pytest.raises(cells.CellError, match=says):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 16384)


def test_the_adapter_refuses_a_layout_off_its_period_and_a_sequence_past_the_context():
    both = lambda layout: dict(PUBLISHED, rope_layout=layout, sliding_window_layout=layout)  # noqa: E731
    with pytest.raises(cells.CellError, match="period 4"):
        adapter.model_config(both([0, 1, 1, 1, 1, 0, 1, 1]), 16384)
    with pytest.raises(cells.CellError, match="one 0 or 1 for each"):
        adapter.model_config(both([0, 1, 1, 1]), 16384)
    with pytest.raises(cells.CellError, match="one 0 or 1 for each"):
        adapter.model_config(both([0, 1, 1, 2, 0, 1, 1, 2]), 16384)
    with pytest.raises(cells.CellError, match="sequence 16385 exceeds"):
        adapter.model_config(PUBLISHED, 16385)
    assert adapter.model_config(both([1, 1, 1, 1] * 2), 16384).layer_pattern == "WE" * 8


def test_the_adapter_refuses_a_file_that_lacks_a_key_or_has_one_to_spare(tmp_path):
    lacking = {k: v for k, v in PUBLISHED.items() if k != "sliding_window_size"}
    with pytest.raises(cells.CellError, match="sliding_window_size"):
        adapter.model_config(lacking, 16384)
    with pytest.raises(cells.CellError, match="moe_num_secondary_experts"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(moe_num_secondary_experts=8)))
    assert cells.load_cell("w", _tiny_table(tmp_path, tiny())).arch_dir.endswith("smallthinker")


def test_the_harness_check_passes_at_a_small_size(tmp_path, monkeypatch):
    """``worker.reference_check`` as the chip run makes it, on a sample
    longer than the window (the chip's sample of 1,024 is shorter than
    4,096: PERF.md section 7)."""
    from benchmark import worker

    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 48)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    out = worker.reference_check(worker.Ctx(cell, 3000000001, 0, False))
    assert out["ok"] and out["grad_rel_l2_worst"] < CPU_GRAD_TOL and out["loss_rel_diff"] < 1e-5


def test_the_checks_sample_keeps_a_band_and_skips_tiles():
    """The harness samples 1,024 tokens, fewer than the window: the sample's
    model and the reference take a quarter of the sample for the window, and
    the banded kernels run it at tiles of 128 (a sweep of 3: 21 of the
    causal 36 tiles a head), so the band's edge and a skipped tile are
    inside what decides ``correct``. A sample longer than the window (the
    builder's comparison) keeps the cell's own window and tiles."""
    cfg = adapter.model_config(PUBLISHED, 16384)
    sample = adapter.sample_config(cfg, 1024)
    assert (sample.sliding_window, sample.flash_block_q, sample.flash_block_k) == (256, 128, 128)
    assert sample.attn_impl == "flash" and sample.flash_min_seq <= 1024
    assert reference.window_at(PUBLISHED, 1024) == sample.sliding_window
    assert fa.choose_tiles("window", 1024, (128,), 128, 128) == (128, 128)
    kept, run = fa.window_tiles(1024, 256, 128, 128)
    assert (kept, run) == (fa.window_kept(1024, 256), 21 * 128 * 128)
    assert kept == int(np.asarray(reference.visible(1024, 256)).sum())
    long = adapter.sample_config(cfg, 16384)
    assert (long.sliding_window, long.flash_block_q, long.flash_min_seq) == (4096, 1024, 2048)
    assert [reference.window_at(PUBLISHED, n) for n in (16384, 4097, 4096, 8, 2)] == [
        4096, 4096, 1024, 2, 1]
    # the rest of the sample's model is the cell's
    assert dataclasses.replace(
        sample, sliding_window=4096, flash_block_q=1024, flash_block_k=1024,
        flash_min_seq=cfg.flash_min_seq) == cfg


@pytest.mark.parametrize("what", ["sound", "dead_router", "dead_expert_stack", "no_band"])
def test_the_harness_check_on_a_sample_shorter_than_the_window(tmp_path, monkeypatch, what):
    """``worker.reference_check`` on 8 tokens under a published window of
    12, so a window of 2 on both sides: it passes; a leaf whose gradient
    never moves reads 1.0 and a program that runs no band reads over the
    CPU limit, and the reference's own limit refuses the dead leaf."""
    from benchmark import worker
    from torchft_tpu.parallel import train

    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 8)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    if what.startswith("dead"):
        leaf = ("router", "kernel") if what == "dead_router" else ("mlp", "experts_up")
        layer = "layers_2" if what == "dead_router" else "layers_3"
        step = train.make_grad_step

        def dead(*a, **k):
            def run(params, batch):
                loss, grads = step(*a, **k)(params, batch)
                grads = jax.tree_util.tree_map(lambda g: g, grads)
                grads[layer][leaf[0]][leaf[1]] = jnp.zeros_like(grads[layer][leaf[0]][leaf[1]])
                return loss, grads
            return run

        monkeypatch.setattr(train, "make_grad_step", dead)
    if what == "no_band":
        monkeypatch.setattr(
            cell.adapter, "sample_config", lambda cfg, seq: dataclasses.replace(cfg, sliding_window=seq))
    out = worker.reference_check(worker.Ctx(cell, 3000000001, 0, False))
    assert out["tokens"] == 8
    if what == "sound":
        assert out["ok"] and out["grad_rel_l2_worst"] < CPU_GRAD_TOL
    elif what == "no_band":
        assert out["grad_rel_l2_worst"] > 50 * CPU_GRAD_TOL
    else:
        assert out["grad_rel_l2_worst"] == pytest.approx(1.0) and not out["ok"]
        assert reference.GRAD_REL_L2_TOL < 1.0


def test_the_embedding_tables_initial_deviation_is_the_configurations():
    """``LlamaConfig.embed_init_std``: None is flax's 1/sqrt(hidden), the
    SmallThinker file asks for a unit-variance table, and no other leaf's
    initial value moves."""
    assert adapter.model_config(PUBLISHED, 16384).embed_init_std == 1.0
    assert llama.LlamaConfig().embed_init_std is None and smallthinker_21b().embed_init_std is None
    tokens = jnp.zeros((1, 8), jnp.int32)
    trees = {
        std: build_model(smallthinker_debug(embed_init_std=std), None).init(
            jax.random.PRNGKey(0), tokens)["params"]
        for std in (None, 1.0)
    }
    table = {std: np.asarray(t["embed"]["embedding"]) for std, t in trees.items()}
    assert table[None].std() == pytest.approx(64 ** -0.5, rel=0.05)
    assert table[1.0].std() == pytest.approx(1.0, rel=0.05)
    rest = {std: {k: v for k, v in t.items() if k != "embed"} for std, t in trees.items()}
    assert all(
        np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(rest[None]), jax.tree_util.tree_leaves(rest[1.0])))


def test_the_presets():
    cfg = smallthinker_21b()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps,
            cfg.rope_theta, cfg.sliding_window) == (
        2560, 52, 28, 4, 128, 768, 151936, 16384, 1e-6, 1.5e6, 4096)
    assert cfg.layer_pattern == "*EWEWEWE" * 13 and len(cfg.layer_pattern) == 2 * cfg.num_layers
    assert (cfg.rope, cfg.router_ahead, cfg.qk_norm, cfg.tie_embeddings, cfg.attn_gate) == (
        False, True, False, False, False)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.shared_expert_size,
            cfg.experts_held, cfg.expert_act, cfg.norm_topk_prob, cfg.router_score) == (
        64, 6, 0, None, "reglu", True, "softmax")
    cut = adapter.model_config(PUBLISHED, 16384)
    same = ("hidden_size", "head_dim", "intermediate_size", "norm_eps", "qk_norm", "rope",
            "rope_theta", "sliding_window", "router_ahead", "tie_embeddings", "num_experts",
            "num_experts_per_tok", "router_score", "norm_topk_prob", "expert_act",
            "router_aux_coef", "router_z_coef", "num_heads", "num_kv_heads")
    assert all(getattr(cut, k) == getattr(cfg, k) for k in same)
    assert cut.layer_pattern == cfg.layer_pattern[:16]
    small = smallthinker_debug()
    assert PRESETS["smallthinker_debug"] is smallthinker_debug
    assert PRESETS["smallthinker_21b"] is smallthinker_21b
    assert (small.layer_pattern, small.sliding_window, small.router_ahead,
            small.experts_held) == ("*EWEWEWE", 16, True, (0, 4))
    # a model that sets none of this is what it was, and no preset changed meaning
    plain = llama.LlamaConfig()
    assert (plain.sliding_window, plain.router_ahead, plain.expert_act) == (None, False, "swiglu")
    assert all("W" not in (PRESETS[n]().layer_pattern or "") for n in PRESETS
               if not n.startswith(("smallthinker", "trinity")))
    with pytest.raises(ValueError, match="'W'"):
        llama.MixerLayer(small, "Q").init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_small_preset(tmp_path):
    """``train_hsdp.py --model smallthinker_debug``: one group, the Manager
    in the loop, three committed steps on the CPU."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "smallthinker_debug", "--steps", "3",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 3, steps
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 3


def test_the_builders_long_comparison_at_a_small_size(tmp_path):
    """``tools/reference_compare.py`` as the chip run makes it at 16,384
    tokens: the cell's own model on a sequence several windows long against
    the reference in query blocks; the reference in float8 handed in the
    system's place reads an order worse."""
    from tools import reference_compare

    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    out = reference_compare.compare(cell, 96, 3000000001, query_block=32)
    assert out["ok"] and (out["tokens"], out["query_block"], out["compared"]) == (
        96, 32, "system")
    assert out["grad_rel_l2_worst"] < CPU_GRAD_TOL and out["loss_rel_diff"] < 1e-5
    assert out["leaves"] == 4 * 6 + 4 * 4 + 3
    low = reference_compare.compare(cell, 96, 3000000001, operand_dtype="float8_e4m3fn")
    assert low["compared"] == "reference in float8_e4m3fn" and low["query_block"] is None
    assert low["grad_rel_l2_worst"] > 10 * out["grad_rel_l2_worst"]
    # a program whose band is misplaced, against the reference's own
    moved = reference_compare.compare(cell, 96, 3000000001, program_window=13)
    assert moved["compared"] == "system under a window of 13"
    assert moved["grad_rel_l2_worst"] > CPU_GRAD_TOL
