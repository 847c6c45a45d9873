"""The ``lfm2_moe`` architecture as the benchmark holds it: the reference's
own proofs (its short convolution against a position-by-position loop, its
rotary embedding against the rotation written out, the bias update's
rule), the configuration file against the catalog's published keys, the
counts against a hand count and the program's parameter tree, the lookup
by the ``"arch"`` key, the adapter's refusals, and the harness's own check
at a small size."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, worker

adapter = cells.arch_module("lfm2_moe", "adapter")
reference = cells.arch_module("lfm2_moe", "reference")
flops = cells.arch_module("lfm2_moe", "flops")
CONFIG_FILE = os.path.join(cells.HERE, "configs", "lfm2-8b-a1b-l5e8.json")
PUBLISHED = cells.load_json(CONFIG_FILE)
# The catalog row's `config` (model-configs guide, architectures.jsonl,
# LFM2-8B-A1B), key for key.
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
                    "conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}


def tiny(**overrides):
    """The published file at widths a CPU test can afford: 16 experts over
    4 chips, this chip the second."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=160, moe_intermediate_size=48, num_experts=4,
        expert_parallel_chips=4, expert_parallel_index=1, num_experts_per_tok=3,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def test_the_short_convolution_is_a_causal_loop_over_positions():
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    h, t = 8, 11
    p = {"in_proj": {"kernel": jax.random.normal(key[0], (h, 3 * h))},
         "out_proj": {"kernel": jax.random.normal(key[1], (h, h))},
         "conv_kernel": jax.random.normal(key[2], (3, h))}
    a = jax.random.normal(key[3], (2, t, h))
    with jax.default_matmul_precision("highest"):
        got = reference.short_conv(a, p, lambda x: x)
        b, c, u = jnp.split(a @ p["in_proj"]["kernel"], 3, axis=-1)
        v = b * u
        rows = []
        for pos in range(t):
            w = sum(p["conv_kernel"][j] * v[:, pos - 2 + j]
                    for j in range(3) if pos - 2 + j >= 0)
            rows.append((c[:, pos] * w) @ p["out_proj"]["kernel"])
        assert jnp.allclose(got, jnp.stack(rows, axis=1), rtol=1e-5, atol=1e-5)


def test_the_rotary_embedding_rotates_pairs_across_the_halves():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 8))
    got = reference._rotary(x, 1e6)
    assert jnp.allclose(got[:, 0], x[:, 0])  # position 0 is not turned
    for pos in (1, 4):
        for i in range(4):
            angle = pos / 1e6 ** (2 * i / 8)
            a, b = x[0, pos, :, i], x[0, pos, :, i + 4]
            assert jnp.allclose(got[0, pos, :, i], a * jnp.cos(angle) - b * jnp.sin(angle), atol=1e-6)
            assert jnp.allclose(got[0, pos, :, i + 4], b * jnp.cos(angle) + a * jnp.sin(angle), atol=1e-6)


@pytest.mark.parametrize("load,want", [
    ([9.0, 1.0, 5.0, 5.0], [-1.0, 1.0, 0.0, 0.0]),
    ([4.0, 4.0, 4.0, 4.0], [0.0, 0.0, 0.0, 0.0]),
    ([0.0, 0.0, 0.0, 12.0], [1.0, 1.0, 1.0, -1.0]),
])
def test_the_bias_update_moves_towards_the_under_used(load, want):
    bias = jnp.array([0.5, -0.5, 0.25, 0.0])
    got = reference.bias_update(bias, jnp.array(load), 0.01)
    assert jnp.allclose(got - bias, 0.01 * jnp.array(want))


def test_every_published_key_is_in_the_file_unchanged_but_the_five_reduced():
    c = PUBLISHED
    assert set(c["reduced"]) == {"num_hidden_layers", "layer_types", "num_dense_layers",
                                 "num_experts", "vocab_size"}
    for key, value in CATALOG.items():
        if key in c["reduced"]:
            cut = c["reduced"][key]
            assert cut["published"] == value and cut["run"] == c[key] != value and cut["why"]
        else:
            assert c[key] == value and type(c[key]) is type(value), key
    assert c["layer_types"] == CATALOG["layer_types"][1:6]
    assert c["num_experts"] * c["expert_parallel_chips"] == CATALOG["num_experts"]
    assert c["vocab_size"] * c["vocab_parallel_chips"] == CATALOG["vocab_size"]
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    assert {"assumed", "stands_for", "distortions", "source"} <= set(c)
    for key in ("tie_word_embeddings", "router_bias_update_rate", "router_aux_loss_coef"):
        assert key in c["assumed"] and key not in CATALOG
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "lfm2-8b-a1b-l5e8")
    assert entry["reduced"] == list(c["reduced"]) and entry["file"].endswith(
        "configs/lfm2-8b-a1b-l5e8.json")
    assert entry["source"] == c["source"].split(";")[0]


def test_the_cell_is_found_by_its_arch_key_with_its_metrics():
    cell = cells.load_cell("lfm2-raw")
    assert cell.arch_dir == os.path.join(cells.HERE, "arch", "lfm2_moe")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"], cell.mix["trainer"]) == (
        1, 2, 8192, "raw")
    names = {m["name"] for m in cell.per_layer}
    new = {"short_conv_ms", "short_conv_roofline", "gated_held_ms", "gated_held_share",
           "gated_held_dropped", "router_bias_abs_max", "gated_gmm_roofline"}
    assert new | {"mfu_pct", "flash_ms", "flash_roofline", "host_other_ms",
                  "hbm_reserved_gib"} <= names
    assert not names & {"step_ms", "expert_held_ms", "ssm_ms", "moe_ms"}
    for m in cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["per_layer"]:
        if m["name"] in new:
            assert (m["moves"], m["workloads"]) == ("tok_s_chip", ["lfm2-raw"])
            assert os.path.isfile(os.path.join(cells.HERE, "metrics", m["name"] + ".py"))
    cfg = cell.adapter.model_config(cell.config, 8192)
    assert (cfg.layer_pattern, cfg.experts_held, cfg.num_experts, cfg.rope, cfg.qk_norm,
            cfg.tie_embeddings, cfg.head_dim, cfg.dense_intermediate_size,
            cfg.intermediate_size, cfg.gate_eps, cfg.router_bias_update_rate) == (
        "CD*ECECECE", (0, 8), 32, True, "head", True, 64, 7168, 1792, 1e-6, 1e-3)


def test_the_grouped_matmuls_roofline_counts_the_rows_the_traced_steps_filled():
    """``gated_gmm_roofline``: the ``ragged-dot`` kernels' time against the
    work of the held share the traced steps themselves counted, not of a
    uniform router's; nothing where the step counts none."""
    from benchmark import trace_reduce
    from benchmark.metrics import gated_gmm_roofline

    cell = cells.load_cell("lfm2-raw")
    trace = trace_reduce.Trace(
        (0.0, 1.0), 1, 0.9,
        {"ragged-dot-none.3 bf16[65536,1792]{1,0:T(8,128)(2,1)} cust": 0.12,
         "ragged-dot-metadata.1 s32[8]": 0.04, "fusion.7 f32[2,8192,2048]": 0.5},
        [], {},
    )
    records = [{"traced": True, "counters": {"moe_held_share": 0.2}},
               {"traced": True, "counters": {"moe_held_share": 0.3}},
               {"traced": False, "counters": {"moe_held_share": 0.0}}]
    run = {"cell": cell, "trace": trace, "traced_steps": 2, "records": records,
           "device_kind": "TPU v5 lite",
           "peaks": cells.load_json(os.path.join(cells.HERE, "peaks.json"))}
    least_ms = flops.gmm_flops_per_step(cell.config, 2, 8192, 0.25) / 197e12 * 1e3
    assert gated_gmm_roofline.read(run) == pytest.approx(100 * least_ms / 80.0)
    assert gated_gmm_roofline.read({**run, "records": [{"traced": True, "counters": {}}]}) is None
    assert gated_gmm_roofline.read({**run, "trace": None}) is None


def test_the_counts_are_the_hand_count_and_the_parameter_trees():
    """ISSUE 41's arithmetic, at the published widths of the cut file."""
    c = PUBLISHED
    assert flops.conv_params(c) == 12_582_912 + 4_194_304 + 6_144 == 16_783_360
    assert flops.attention_params(c) == 4_194_304 + 2 * 1_048_576 + 4_194_304 + 128 == 10_485_888
    assert flops.dense_ffn_params(c) == 3 * 2048 * 7168 == 44_040_192
    assert flops.expert_params(c) == 3 * 2048 * 1792 == 11_010_048
    assert flops.expert_ffn_params(c) == 8 * 11_010_048 + 65_536 + 32
    assert flops.total_params(c) == (
        60_827_648 + 98_635_936 + 3 * 104_933_408 + 33_554_432 + 2_048) == 507_820_288
    # Active: four operators' projections (67.1M), the dense feed-forward
    # (44.0M), four routers and 4 x 8/32 of an expert each (44.3M), the
    # attention (10.5M), the tied head (33.6M).
    assert flops.active_matmul_params(c) == (
        4 * 16_777_216 + 44_040_192 + 4 * (65_536 + 11_010_048) + 10_485_760 + 33_554_432)
    from torchft_tpu.parallel.train import build_model

    model = build_model(adapter.model_config(c, 256), None)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32))
    )["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 507_820_288
    attention = 3 * 2 * 8192 * 2048
    convs = 3 * 7 * 2048 * 4
    assert flops.model_flops_per_token(c, 8192) == pytest.approx(
        6 * flops.active_matmul_params(c) + attention + convs)
    assert flops.flash_flops_per_step(c, 2, 8192) == pytest.approx(attention * 16384)
    # q and o of 32 heads, k and v of 8, at head width 64, six passes of bf16
    assert flops.flash_bytes_per_step(c, 2, 8192) == 6 * 16384 * (32 + 8) * 64 * 2
    # in once (3H), out once (H), and their gradients: 4H forward, 7H backward, bf16
    assert flops.short_conv_bytes_per_step(c, 2, 8192) == 2 * 11 * 2048 * 16384 * 4
    assert (flops.short_conv_bytes_per_step(c, 2, 8192) / 819e9
            > 100 * flops.short_conv_flops_per_step(c, 2, 8192) / 197e12)  # memory-bound
    assert flops.gmm_flops_per_step(c, 2, 8192) == 3 * 2 * 11_010_048 * 16384 * 4
    assert flops.gmm_flops_per_step(c, 2, 8192, 0.125) == 3 * 2 * 11_010_048 * 8192 * 4
    # 2,048 rows an expert: compute-bound on a v5e
    assert (flops.gmm_flops_per_step(c, 2, 8192) / 197e12
            > flops.gmm_bytes_per_step(c, 2, 8192) / 819e9)


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("conv_L_cache", 4), ("norm_topk_prob", False),
    ("use_expert_bias", False), ("model_type", "lfm2"), ("tie_word_embeddings", False),
    ("expert_parallel_index", 4), ("layer_types", ["conv", "sliding_attention", "conv",
                                                   "conv", "conv"]),
    ("num_dense_layers", 6), ("num_hidden_layers", 6), ("num_experts_per_tok", 33),
])
def test_the_adapter_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(cells.CellError):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 8192)


def test_the_adapter_refuses_a_file_of_another_architecture_and_a_long_sequence():
    nemotron = cells.load_cell("nemotron3-raw").config
    with pytest.raises(cells.CellError, match="lacks"):
        adapter.model_config(dict(nemotron), 8192)
    with pytest.raises(cells.CellError, match="max_position_embeddings"):
        adapter.model_config(dict(PUBLISHED), 128001)


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
    assert cell.arch_dir.endswith(os.path.join("arch", "lfm2_moe"))
    with pytest.raises(cells.CellError, match="rope_scaling"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(rope_scaling={"factor": 2.0})))


def test_the_harness_check_passes_and_a_lower_precision_or_a_missing_norm_fails(
    tmp_path, monkeypatch
):
    """worker.reference_check as the chip run makes it, at a small size in
    float32; then the same check with the reference computed as another
    model or in another precision handed to it in the system's place: the
    harness's own comparison says not correct, by the reference's limits."""
    from torchft_tpu.parallel import train

    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 48)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    ctx = worker.Ctx(cell, 3000000001, 0, False)
    out = worker.reference_check(ctx)
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5
    assert "router_bias" not in out["grad_rel_l2_worst_leaf"]
    assert reference.GRAD_REL_L2_MEDIAN_TOL < reference.GRAD_REL_L2_TOL == out["grad_rel_l2_tol"]

    def control(**options):
        step = jax.jit(lambda p, b: reference.loss_and_grads(p, b, ctx.config, **options))
        monkeypatch.setattr(train, "make_grad_step", lambda model, mesh, shardings: step)
        return worker.reference_check(ctx)

    unnormed = control(per_head_norm=False)
    assert not unnormed["ok"] and unnormed["grad_rel_l2_worst"] > reference.GRAD_REL_L2_TOL
    fp8, bf16 = control(operand_dtype=jnp.float8_e4m3fn), control(operand_dtype=jnp.bfloat16)
    assert fp8["grad_rel_l2_worst"] > bf16["grad_rel_l2_worst"] > 1e-3
