"""Nemotron-H hybrid (NVIDIA-Nemotron-3-Nano-30B-A3B): the program's stack
of unlike layers against the benchmark's plain reference at a small size
on the CPU, in float32 with seeded weights; the chunked scan against the
recurrence; the selection bias; the shares of an expert layer against the
whole layer; the sharded mesh; the counts; the harness's own check."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, worker
from benchmark.tests import test_nemotron_reference as _reference_tests
from torchft_tpu.models import llama
from torchft_tpu.models.llama import MoEMLP, nemotron3_nano, nemotron_h_debug
from torchft_tpu.models.mamba2 import ssd_chunked
from torchft_tpu.parallel import auto_mesh, make_mesh
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)

adapter = cells.arch_module("nemotron_h", "adapter")
reference = cells.arch_module("nemotron_h", "reference")
flops = cells.arch_module("nemotron_h", "flops")
CONFIG_FILE = os.path.join(cells.HERE, "configs", "nemotron3-nano-30b-l9e8.json")
PUBLISHED = cells.load_json(CONFIG_FILE)

# The reference's own tests (benchmark/tests is not in tier-1's path),
# collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj


def tiny(**overrides):
    """The published file at widths a CPU test can afford: 16 experts over
    4 chips, this chip the second."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
        ssm_state_size=16, chunk_size=16, n_routed_experts=4,
        expert_parallel_chips=4, expert_parallel_index=1, num_experts_per_tok=3,
        moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def _setup(c, seq, batch=2, seed=0):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq + 1), 0, c["vocab_size"])
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((batch, seq), jnp.int32)}
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


def _reference(c, **options):
    """The reference's loss and gradients, jitted: eager, its Python loop
    over layers dispatches some thousand operations one by one."""
    return jax.jit(lambda p, b: reference.loss_and_grads(p, b, c, **options))


def _leaf_errors(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), got, want
    )
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(errs)}


def _randomise_biases(params, scale=0.3, seed=5):
    """Selection biases start at zero; give each expert layer's a value."""
    params = jax.tree_util.tree_map(lambda a: a, params)
    for i, (name, layer) in enumerate(params.items()):
        if "mlp" in layer:
            layer["mlp"]["router_bias"] = scale * jax.random.normal(
                jax.random.PRNGKey(seed + i), layer["mlp"]["router_bias"].shape
            )
    return params


@pytest.mark.parametrize("seq,index,biased", [(40, 1, False), (64, 0, True), (16, 3, True)])
def test_loss_and_every_gradient_match_the_reference(seq, index, biased):
    """Pattern MEMEM*EME, sequences of one chunk, whole chunks and a part
    of a chunk; the chip's share the first, a middle and the last."""
    c = tiny(expert_parallel_index=index)
    model, mesh, params, data = _setup(c, seq)
    if biased:
        params = _randomise_biases(params)
    sh = state_shardings(model, mesh, (2, seq))
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, sh)(params, data)
    loss_ref, grads_ref = _reference(c)(params, data)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    bias = {k for k in errs if "router_bias" in k}
    assert len(bias) == 4  # no gradient reaches a selection bias: 0 / 0
    for both in (grads, grads_ref):
        assert all(not jnp.any(layer["mlp"]["router_bias"])
                   for layer in both.values() if "mlp" in layer)
    assert max(v for k, v in errs.items() if k not in bias) < 2e-4, errs


@pytest.mark.parametrize("chunk,seq", [(8, 8), (8, 40), (16, 40), (16, 64), (16, 5)])
def test_the_chunked_scan_is_the_recurrence(chunk, seq):
    """Two chunk sizes; one chunk, whole chunks, a ragged end, less than a
    chunk. Heads 4 on 2 groups."""
    heads, width, groups, n = 4, 8, 2, 6
    k = jax.random.split(jax.random.PRNGKey(seq), 5)
    x = jax.random.normal(k[0], (2, seq, heads, width))
    delta = jax.nn.softplus(jax.random.normal(k[1], (2, seq, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(k[3], (2, seq, groups, n))
    c = jax.random.normal(k[4], (2, seq, groups, n))
    per_head = lambda m: jnp.repeat(m, heads // groups, axis=2)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = ssd_chunked(x, delta, a, b, c, chunk, jnp.float32)
        want = jax.vmap(reference.ssm_recurrent, in_axes=(0, 0, None, 0, 0))(
            x, delta, a, per_head(b), per_head(c)
        )
    assert got.shape == want.shape
    assert jnp.allclose(got, want, rtol=1e-4, atol=1e-4)


def _expert_layer(c, seq=32):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, c["hidden_size"]))
    return layer, x


def test_a_selection_bias_changes_the_choice_and_not_the_gates():
    c = tiny()
    layer, x = _expert_layer(c)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    m = x.reshape(-1, c["hidden_size"])
    s0, g0, idx0 = reference.route(m, params, c)
    biased = dict(params, router_bias=jnp.zeros(16).at[5].set(10.0))
    s1, g1, idx1 = reference.route(m, biased, c)
    assert jnp.all(jnp.any(idx1 == 5, axis=-1)) and not jnp.all(jnp.any(idx0 == 5, axis=-1))
    assert jnp.allclose(s0, s1)
    # The gates are the sigmoids at the chosen indices, without the bias,
    # over their sum, times routed_scaling_factor.
    picked = jnp.take_along_axis(s1, idx1, axis=-1)
    assert jnp.allclose(g1, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    assert jnp.allclose(g1.sum(-1), 2.5, rtol=1e-5)
    # And the program's layer computes that: expert 5 is held (4..7 here).
    with jax.default_matmul_precision("highest"):
        for p in (params, biased):
            got = layer.apply({"params": p}, x)
            want, _ = reference._experts(m, p, c, lambda a: a)
            assert jnp.allclose(got.reshape(want.shape), want, rtol=1e-4, atol=1e-5)
        assert not jnp.allclose(layer.apply({"params": params}, x),
                                layer.apply({"params": biased}, x), atol=1e-3)


def test_the_shares_add_up_to_the_whole_layer():
    """Four chips hold four experts each of one layer's sixteen. The routed
    parts the four compute, with the shared expert (which every chip
    computes alike) counted once, are the uncut reference layer."""
    whole = tiny(n_routed_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    layer, x = _expert_layer(whole)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    m = x.reshape(-1, whole["hidden_size"])
    with jax.default_matmul_precision("highest"):
        want, _ = reference._experts(m, params, whole, lambda a: a)
        shared = reference._relu2_ffn(
            m, params["shared_up"]["kernel"], params["shared_down"]["kernel"], lambda a: a
        )
        total, held_share = shared, 0.0
        for index in range(4):
            c = tiny(expert_parallel_index=index)
            part, _ = _expert_layer(c)
            own = dict(
                params,
                experts_up=params["experts_up"][4 * index : 4 * index + 4],
                experts_down=params["experts_down"][4 * index : 4 * index + 4],
            )
            out, sown = part.apply({"params": own}, x, mutable=["intermediates"])
            total = total + out.reshape(want.shape) - shared
            held_share += float(sown["intermediates"]["moe_held_share"][0])
            assert float(sown["intermediates"]["moe_dropped"][0]) == 0.0
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0)
    assert float(jnp.linalg.norm(want - shared)) > 0.3 * float(jnp.linalg.norm(shared))


def test_a_full_row_buffer_is_counted_not_hidden(monkeypatch):
    """The held dispatch's buffer is static. An assignment past it is not
    computed, and the step says how many."""
    c = tiny()
    layer, x = _expert_layer(c)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    monkeypatch.setattr(llama, "HELD_ROW_FACTOR", 0.5)
    _, sown = layer.apply({"params": params}, x, mutable=["intermediates"])
    monkeypatch.undo()
    held = float(sown["intermediates"]["moe_held_share"][0]) * 64 * 3
    rows = 24  # 0.5 x (64 tokens x 3 / 4 chips), a multiple of 8
    assert float(sown["intermediates"]["moe_dropped"][0]) == held - rows > 0
    _, sown = layer.apply({"params": params}, x, mutable=["intermediates"])
    assert float(sown["intermediates"]["moe_dropped"][0]) == 0.0


def test_the_step_hands_on_the_expert_layers_counters():
    cfg = nemotron_h_debug()
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab_size)
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((2, 64), jnp.int32)}
    _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
    assert set(metrics) == {"loss", "grad_norm", "router_aux", "moe_max_load",
                            "moe_dropped", "moe_held_share", "moe_held_run_share",
                            "moe_held_token_run_share"}
    assert float(metrics["moe_held_run_share"]) == 1.0  # a buffer under a tile, run whole
    assert float(metrics["moe_dropped"]) == 0.0
    assert float(metrics["moe_held_share"]) == pytest.approx(4 / 16, abs=0.06)
    assert float(metrics["router_aux"]) == pytest.approx(1.0, abs=0.3)
    assert 1.0 <= float(metrics["moe_max_load"]) <= 4.0


def test_a_sharded_mesh_computes_the_same_step():
    """fsdp=2 x tp=2 on four virtual devices: GSPMD partitions the scan,
    the held dispatch and the grouped matmuls (train_hsdp.py --model
    nemotron_h)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    c = tiny()
    cfg = adapter.model_config(c, 64)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0, c["vocab_size"])
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((4, 64), jnp.int32)}
    seen = []
    for mesh in (auto_mesh(1, devices=jax.devices()[:1]), make_mesh(fsdp=2, tp=2)):
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 64))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "moe_held_share")])
    assert seen[0] == pytest.approx(seen[1], rel=1e-4)


def test_the_counts_are_the_hand_count():
    """ISSUE 37's arithmetic, at the published widths of the cut file."""
    c = PUBLISHED
    assert flops.mamba_params(c) == (
        27_697_152 + 30_720 + 192 + 4_096 + 11_010_048 + 2_688)  # 38.74M
    assert flops.attention_matmul_params(c) + c["hidden_size"] == (
        11_010_048 + 2 * 688_128 + 11_010_048 + 2_688)  # 23.40M
    assert flops.expert_layer_params(c) == (
        8 * 9_977_856 + 19_955_712 + 344_064 + 128 + 2_688)  # 100.13M
    assert flops.total_params(c) == (
        4 * 38_744_896 + 23_399_040 + 4 * 100_125_440 + 2 * 44_040_192 + 2_688
    ) == 666_963_456
    # Active: both projections of four mixers (154.8M), the attention, four
    # routers + shared experts + 6 x 8/128 of an expert, the head.
    assert flops.active_matmul_params(c) == pytest.approx(318_431_232)
    model = build_model(adapter.model_config(c, 256), None)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32))
    )["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 666_963_456
    per_token = flops.model_flops_per_token(c, 8192)
    scan = 3 * flops.ssd_flops_per_token(c) * 4
    attention = 3 * 2 * 8192 * 4096
    assert per_token == pytest.approx(6 * 318_431_232 + attention + scan)
    assert flops.ssd_flops_per_step(c, 2, 8192) == pytest.approx(scan * 16384)
    assert flops.flash_flops_per_step(c, 2, 8192) == pytest.approx(attention * 16384)


def test_the_file_states_its_cuts_and_the_adapter_reads_every_key():
    c = PUBLISHED
    assert set(c["reduced"]) == {"num_hidden_layers", "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"}
    for key, cut in c["reduced"].items():
        assert cut["run"] == c[key] and cut["published"] != cut["run"] and cut["why"]
    assert c["reduced"]["hybrid_override_pattern"]["published"].startswith(
        c["hybrid_override_pattern"])
    assert c["n_routed_experts"] * c["expert_parallel_chips"] == 128
    assert c["vocab_size"] * c["vocab_parallel_chips"] == 131072
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "nemotron3-nano-30b-l9e8")
    assert set(entry["reduced"]) == set(c["reduced"])
    cfg = adapter.model_config(c, 8192)
    assert (cfg.layer_pattern, cfg.experts_held, cfg.num_experts, cfg.rope) == (
        "MEMEM*EME", (0, 8), 128, False)
    assert cfg.mamba.d_inner == 4096 != c["expand"] * c["hidden_size"]


@pytest.mark.parametrize("key,value", [
    ("mlp_hidden_act", "silu"), ("n_group", 8), ("use_conv_bias", False),
    ("hybrid_override_pattern", "MEMEM-EME"), ("norm_topk_prob", False),
    ("expert_parallel_index", 16),
])
def test_the_adapter_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(cells.CellError):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 8192)


def _tiny_table(tmp_path, config):
    """A table of one cell beside which nothing lies: the architecture and
    the traffic are the benchmark's own."""
    (tmp_path / "c.json").write_text(json.dumps(config))
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    table["configs"] = [{"name": "c", "file": "c.json"}]
    table["workloads"] = [{"name": "w", "config": "c", "traffic": "raw-2x8192", "chips": 1}]
    table["traffic_dir"] = os.path.join(cells.HERE, "traffic")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(table))
    return str(path)


def test_load_cell_refuses_a_key_the_adapter_does_not_read(tmp_path):
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    assert cell.arch_dir.endswith(os.path.join("arch", "nemotron_h"))
    with pytest.raises(cells.CellError, match="time_step_limit"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(time_step_limit=[0.0, 1.0])))


def test_the_harness_check_passes_and_skips_the_leaves_no_gradient_reaches(
    tmp_path, monkeypatch
):
    """worker.reference_check as the chip run makes it, at a small size:
    the selection biases' gradients are zero on both sides, their relative
    error 0/0, and the worst leaf is the worst of the others."""
    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 48)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    out = worker.reference_check(worker.Ctx(cell, 3000000001, 0, False))
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5
    assert "router_bias" not in out["grad_rel_l2_worst_leaf"]


def test_leaving_the_shared_expert_out_or_rounding_the_decays_fails_the_check():
    c = tiny()
    _, _, params, data = _setup(c, 64, seed=2)
    _, want = _reference(c)(params, data)
    worst = lambda got: max(  # noqa: E731
        v for v in _leaf_errors(got, want).values() if v == v
    )
    _, no_shared = _reference(c, with_shared=False)(params, data)
    assert worst(no_shared) > reference.GRAD_REL_L2_TOL
    _, rounded = _reference(c, decay_dtype=jnp.bfloat16)(params, data)
    assert worst(rounded) > 0.01  # at the published widths: PERF.md, on the chip


def test_the_presets():
    cfg = nemotron3_nano()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) == (
        2688, 52, 32, 2, 128, 1856, 131072)
    assert (cfg.layer_pattern.count("M"), cfg.layer_pattern.count("E"),
            cfg.layer_pattern.count("*")) == (23, 23, 6)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.shared_expert_size,
            cfg.routed_scaling, cfg.experts_held) == (128, 6, 3712, 2.5, None)
    assert nemotron_h_debug().layer_pattern == "MEMEM*EME"
