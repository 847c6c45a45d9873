"""JoyAI-LLM-Flash: latent attention (a query and key of a rope-free and a
rotary part, one rotary key a position for all heads, values of a width of
their own) and a multi-token-prediction module in the program, against the
benchmark's plain reference at a small size on the CPU, in float32 with
seeded weights (the latent Pallas kernels' own tests are in
tests/test_flash_attention.py); the two rotary pairings; the shares of an expert layer against the
whole layer. The fused step, a sharded mesh, two replicas under Managers,
the harness's check with its controls, the presets and ``train_hsdp.py``:
tests/test_joyai_flash_step.py."""

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
from benchmark.tests import test_joyai_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, MLAConfig, joyai_flash_debug, joyai_llm_flash, llama
from torchft_tpu.models.llama import MoEMLP, apply_rope, rope_table
from torchft_tpu.models.mla import (
    LatentAttention,
    apply_rope_interleaved,
)
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

adapter = cells.arch_module("joyai_flash", "adapter")
reference = cells.arch_module("joyai_flash", "reference")
tiny = _reference_tests.tiny

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
# The harness's check with its controls and the cell end to end are
# collected in tests/test_joyai_flash_step.py.
IN_THE_STEP_FILE = (
    "test_the_harness_check_passes_and_float8_bf16_parameters_and_a_dead_leaf_fail",
    "test_the_cell_runs_end_to_end_on_a_tiny_table",
)
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj) and _name not in IN_THE_STEP_FILE:
        globals()[_name] = _obj


@pytest.fixture
def the_table_as_this_architecture_left_it(monkeypatch):
    """``BENCHMARK.json`` cut back to this architecture's entries. The table
    grows at the ends of its lists only, and two of the collected tests say
    that these entries ARE the ends (true of the PR that added them;
    benchmark/tests keeps them as written): what a later PR appended is not
    theirs to judge."""
    load = cells.load_json

    def through(entries, is_last):
        return entries[: max(i for i, e in enumerate(entries) if is_last(e)) + 1]

    def load_cut(path):
        table = load(path)
        if os.path.basename(path) != "BENCHMARK.json":
            return table
        return dict(
            table,
            configs=through(table["configs"], lambda e: e["name"] == "joyai-llm-flash-l6e8"),
            workloads=through(table["workloads"], lambda e: e["name"] == "joyai-raw"),
            per_layer=through(
                table["per_layer"], lambda e: e["name"] in _reference_tests.NEW_METRICS
            ),
        )

    monkeypatch.setattr(cells, "load_json", load_cut)


def _on_the_table_cut_back(name):
    collected = getattr(_reference_tests, name)

    def test(the_table_as_this_architecture_left_it):
        collected()

    test.__name__ = name
    return test


for _name in (
    "test_every_published_key_is_in_the_file_unchanged_but_the_three_reduced",
    "test_the_cell_is_found_by_its_arch_key_with_its_metrics",
):
    globals()[_name] = _on_the_table_cut_back(_name)


def _setup(c, seq, batch=2, seed=0, **cfg_overrides):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False, **cfg_overrides)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


# -- (b) the rotary pairing ---------------------------------------------------


def test_the_interleaved_rotation_is_the_references_up_to_one_layout_and_not_the_half_split():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 1, 8))
    positions = jnp.broadcast_to(jnp.arange(12), (2, 12))
    cos, sin = rope_table(positions, 8, 3.2e7, jnp.float32)
    pairs, halves = apply_rope_interleaved(x, cos, sin), apply_rope(x, cos, sin)
    want = reference._rotary_pairs(x, 3.2e7)
    # the program lays the turned pairs out [evens | odds]
    assert jnp.allclose(pairs[..., :4], want[..., 0::2], atol=1e-6)
    assert jnp.allclose(pairs[..., 4:], want[..., 1::2], atol=1e-6)
    score = lambda a, b: jnp.einsum("bqhd,bkd->bhqk", a, b[:, :, 0])  # noqa: E731
    same = score(pairs, apply_rope_interleaved(y, cos, sin))
    assert jnp.allclose(same, score(want, reference._rotary_pairs(y, 3.2e7)), atol=1e-5)
    # half-split pairs channel i with i + D/2: another rotation, other scores
    assert float(jnp.abs(same - score(halves, apply_rope(y, cos, sin))).max()) > 1e-2


# -- (c) the system against the reference --------------------------------------


def test_the_latent_mixer_is_the_references_attention(caplog):
    c = tiny()
    cfg = dataclasses.replace(adapter.model_config(c, 32), remat=False)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))
    positions = jnp.broadcast_to(jnp.arange(32), (2, 32))
    tables = rope_table(positions, 8, cfg.rope_theta, jnp.float32)
    mixer = LatentAttention(cfg)
    params = mixer.init(jax.random.PRNGKey(0), x, *tables)["params"]
    assert {k: v["kernel"].shape for k, v in params.items() if "kernel" in v} == {
        "wq_a": (64, 48), "wq_b": (48, 4, 24), "wkv_a": (64, 40), "wkv_b": (32, 4, 32),
        "wo": (4, 16, 64)}
    assert params["q_norm"]["scale"].shape == (48,) and params["kv_norm"]["scale"].shape == (32,)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(x, params, c, lambda a: a)
        got = mixer.apply({"params": params}, x, *tables)
    assert jnp.allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="latent attention under"):
        LatentAttention(dataclasses.replace(cfg, attn_impl="ring")).apply(
            {"params": params}, x, *tables)


@pytest.mark.parametrize("seq,index,attn,modules", [
    (64, 1, "flash", 1), (32, 0, "dense", 1), (24, 3, "dense", 2), (32, 2, "dense", 0)])
def test_loss_and_every_gradient_match_the_reference(seq, index, attn, modules, caplog):
    """Through ``make_grad_step`` on a plain inputs/targets/mask batch; the
    chip's share the first, a middle and the last; the kernels (interpreted,
    two tiles) and the dense fallback; one prediction module, two, none."""
    c = tiny(expert_parallel_index=index, num_nextn_predict_layers=modules,
             run={"attn_impl": attn, "compute_dtype": "float32", "param_dtype": "float32"})
    with caplog.at_level(logging.INFO, logger="torchft_tpu.models.llama"):
        llama._ATTN_NOTED.clear()
        model, mesh, params, data = _setup(
            c, seq, flash_min_seq=64, flash_block_q=32, flash_block_k=32)
        data["mask"] = data["mask"].at[1, 7].set(0)
        sh = state_shardings(model, mesh, (2, seq))
        with jax.default_matmul_precision("highest"):
            loss, grads = make_grad_step(model, mesh, sh)(params, data)
            (_, metrics), _ = make_grad_step(model, mesh, sh, with_metrics=True)(params, data)
    assert f"asked={attn}/mla traced={attn}/mla seq={seq}" in caplog.text
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    errs = {k: v for k, v in errs.items() if "router_bias" not in k}  # no gradient: 0 / 0
    # an attention layer 8 leaves with its norm, the dense feed-forward 4, an expert
    # layer 8 beside its bias; the table, the final norm and the head; a module 3 + 8 + 8
    assert len(errs) == 3 * 8 + 4 + 2 * 8 + 3 + modules * 19 and max(errs.values()) < 2e-4, errs
    assert all(float(jnp.abs(g).max()) == 0 for p, g in
               jax.tree_util.tree_leaves_with_path(grads) if "router_bias" in jax.tree_util.keystr(p))
    if modules:
        _, main, mtp = reference.losses(params, data, c)
        assert float(metrics["loss_main"]) == pytest.approx(float(main), rel=1e-5)
        assert float(metrics["loss_mtp"]) == pytest.approx(float(mtp), rel=1e-5)
        assert 0 < 0.3 * float(metrics["loss_mtp"]) / float(loss) < 0.3  # mtp_loss_share
    else:
        assert "loss_mtp" not in metrics and "mtp_0" not in params


def test_the_last_rows_successor_weighs_nothing_and_an_odd_length_is_one_chunk():
    """The prediction module's targets are the targets rolled by one, so
    its last row's target is a token from the sequence's start: that row
    is no part of its loss. And a length no chunk divides goes through the
    same head-and-loss code."""
    c = tiny()
    model, mesh, params, data = _setup(c, 32)
    sh = state_shardings(model, mesh, (2, 32))
    step = make_grad_step(model, mesh, sh, with_metrics=True)
    (_, m0), _ = step(params, data)
    total, main, mtp = reference.losses(params, data, c)
    assert float(m0["loss_mtp"]) == pytest.approx(float(mtp), rel=1e-5)
    # Under the causal mask the last input token reaches the last row alone: another token
    # there moves the main loss (that row predicts) and not the module's (its weight is 0).
    other = dict(data, inputs=data["inputs"].at[:, -1].add(1) % c["vocab_size"])
    (_, m1), _ = step(params, other)
    assert float(m1["loss_mtp"]) == pytest.approx(float(m0["loss_mtp"]), rel=1e-6)
    assert abs(float(m1["loss_main"]) - float(m0["loss_main"])) > 1e-5
    # the row before it does weigh
    before = dict(data, inputs=data["inputs"].at[:, -2].add(1) % c["vocab_size"])
    assert abs(float(step(params, before)[0][1]["loss_mtp"]) - float(m0["loss_mtp"])) > 1e-5
    odd = _setup(c, 200)  # 200 = 128 + 72: no multiple of 128 divides it
    (loss, m), _ = make_grad_step(
        odd[0], odd[1], state_shardings(odd[0], odd[1], (2, 200)), with_metrics=True
    )(odd[2], odd[3])
    ref = reference.losses(odd[2], odd[3], c)
    assert float(loss) == pytest.approx(float(ref[0]), rel=1e-4)
    assert float(m["loss_mtp"]) == pytest.approx(float(ref[2]), rel=1e-4)


def test_a_model_without_a_module_and_generation_take_the_main_head():
    cfg = joyai_flash_debug(dtype=jnp.float32)
    model = build_model(cfg, None)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    logits = model.apply({"params": params}, toks)
    assert logits.shape == (2, 16, 256) and logits.dtype == jnp.float32
    hidden, predicted = model.apply({"params": params}, toks, return_hidden=True)
    assert hidden.shape == predicted.shape == (2, 16, 64)
    # the logits are the main head's: the module's parameters do not move them
    spoiled = dict(params, mtp_0=jax.tree_util.tree_map(jnp.zeros_like, params["mtp_0"]))
    assert jnp.array_equal(model.apply({"params": spoiled}, toks), logits)
    # given the successors, the module reads them; left alone, the tokens rolled by one
    rolled = model.apply({"params": params}, toks, return_hidden=True,
                         next_tokens=jnp.roll(toks, -1, axis=1))
    assert jnp.array_equal(rolled[1], predicted)
    other = model.apply({"params": params}, toks, return_hidden=True,
                        next_tokens=jnp.roll(toks, -2, axis=1))
    assert jnp.array_equal(other[0], hidden) and not jnp.allclose(other[1], predicted)
    with pytest.raises(ValueError, match="layer_pattern stack"):
        build_model(dataclasses.replace(cfg, layer_pattern=None, num_experts=0), None).init(
            jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="under block diffusion"):
        bad = build_model(dataclasses.replace(cfg, objective="block_diffusion", block_length=4,
                                              mla=None, head_dim=16), None)
        p = bad.init(jax.random.PRNGKey(0), toks)["params"]
        mesh = auto_mesh(1, devices=jax.devices()[:1])
        make_grad_step(bad, mesh, state_shardings(bad, mesh, (2, 16)))(p, _data(256, 2, 16))


# -- (d) the expert layer ------------------------------------------------------


def test_the_four_shares_add_up_to_the_whole_layer_with_the_shared_expert_once():
    """Four chips hold eight experts each of one layer's thirty-two. The
    routed parts the four compute, plus the shared expert ONCE, are the
    uncut reference layer."""
    whole = tiny(n_routed_experts=32, expert_parallel_chips=1, expert_parallel_index=0,
                 num_experts_per_tok=6)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, whole["hidden_size"]))
    layer = MoEMLP(adapter.model_config(whole, 32))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params = dict(params, router_bias=0.05 * jax.random.normal(jax.random.PRNGKey(2), (32,)))
    m = x.reshape(-1, whole["hidden_size"])
    ident = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = reference.experts(m, params, whole, ident)
        shared = want - reference.experts(m, params, whole, ident, shared=False)
        total, with_shared, held_share = jnp.zeros_like(want), jnp.zeros_like(want), 0.0
        for index in range(4):
            share = tiny(n_routed_experts=8, expert_parallel_chips=4,
                         expert_parallel_index=index, num_experts_per_tok=6)
            own = dict(params, **{
                k: params[k][8 * index : 8 * index + 8]
                for k in ("experts_gate", "experts_up", "experts_down")})
            out, sown = MoEMLP(adapter.model_config(share, 32)).apply(
                {"params": own}, x, mutable=["intermediates"])
            sown = sown["intermediates"]
            with_shared = with_shared + out.reshape(want.shape)
            total = total + out.reshape(want.shape) - shared  # every chip computes it alike
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            # the share's own reference is the share
            assert jnp.allclose(out.reshape(want.shape),
                                reference.experts(m, own, share, ident), rtol=1e-4, atol=1e-5)
    assert jnp.allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0) and float(jnp.linalg.norm(shared)) > 0.1
    # counted four times, the shared expert would be three too many
    assert jnp.allclose(with_shared - want, 3 * shared, rtol=1e-4, atol=1e-5)


# -- (e) sharding ---------------------------------------------------------------


# -- (f) two replicas under Managers --------------------------------------------


# -- the presets and the entry point ---------------------------------------------


