"""The ``ouro`` architecture as the benchmark holds it: the configuration
file against the catalog's published keys, the counts against a hand
count and the program's parameter tree, the lookup by the ``"arch"`` key,
the adapter's refusals by name, the reference's exit distribution and its
departures, the readers of the cell's new metrics."""

import math
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells

adapter = cells.arch_module("ouro", "adapter")
reference = cells.arch_module("ouro", "reference")
flops = cells.arch_module("ouro", "flops")
CONFIG_FILE = os.path.join(cells.HERE, "configs", "ouro-2.6b-l6t4.json")
PUBLISHED = cells.load_json(CONFIG_FILE)
# The catalog row's `config` (model-configs guide, architectures.jsonl,
# Ouro-2.6B), key for key.
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
REDUCED = {"num_hidden_layers": 6, "layer_types": ["full_attention"] * 6}
NEW_METRICS = {"loop_exit_entropy", "ouro_head_loss_ms", "loop_gate_ms"}


def tiny(**overrides):
    """The published file at widths a CPU test can afford: two layers
    applied four times, 4 heads of 16, float32, dense attention."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, intermediate_size=160, vocab_size=256, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
        layer_types=["full_attention"] * 2,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def test_the_file_is_the_catalogs_row_but_for_the_depth():
    for key, want in CATALOG.items():
        assert PUBLISHED[key] == REDUCED.get(key, want), key
    assert set(PUBLISHED["reduced"]) == set(REDUCED)
    assert PUBLISHED["reduced"]["num_hidden_layers"]["published"] == 48
    assert PUBLISHED["reduced"]["num_hidden_layers"]["run"] == 6
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(c for c in table["configs"] if c["name"] == "ouro-2.6b-l6t4")
    assert entry["reduced"] == list(PUBLISHED["reduced"]) and len(entry["source"]) <= 200
    assert entry["source"].startswith("https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    # every key of the file is one the adapter reads; the one key that is
    # this repository's and not config.json's is stated as assumed
    assert set(PUBLISHED) - cells.DOC_KEYS == set(adapter.KEYS)
    assert set(PUBLISHED) - cells.DOC_KEYS - set(CATALOG) == {"loop_entropy_coef"}
    assert "loop_entropy_coef" in PUBLISHED["assumed"] and PUBLISHED["loop_entropy_coef"] == 0.05


def test_the_cell_is_found_by_its_arch_key_and_reports_the_new_metrics():
    cell = cells.load_cell("ouro-raw")
    assert cell.arch_dir.endswith(os.path.join("arch", "ouro")) and cell.chips == 1
    assert cell.mix["seq"] == 8192 and cell.mix["batch"] == 2
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    assert {"flash_ms", "flash_roofline", "mfu_pct", "host_other_ms", "hbm_reserved_gib"} <= names
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(cells.HERE, "metrics", name + ".py")), name
    # head_loss_ms's own list is the benchmark's: the looped rows have a reader of their own
    assert "head_loss_ms" not in names


def test_the_counts_at_8k_are_the_published_arithmetic():
    c, s = PUBLISHED, 8192
    assert flops.attention_matmul_params(c) == 4 * 4_194_304
    assert flops.ffn_params(c) == 3 * 11_534_336
    assert flops.layer_params(c) == 51_388_416
    assert flops.total_params(c) == 509_661_185 == 6 * 51_388_416 + 201_326_592 + 2_048 + 2_049
    assert flops.layer_visits(c) == 24
    assert flops.kept_entries(s) == 33_558_528
    # 24 causal attentions of 16 heads of 128: T x L calls of the kernel
    assert flops.flash_flops_per_step(c, 1, s) == 12 * 33_558_528 * 16 * 128 * 24
    assert flops.flash_bytes_per_step(c, 2, s) == 12 * (2 * 8192 * 16 * 128 * 2) * 24
    per_token = flops.model_flops_per_token(c, s)
    matmuls = 6.0 * (24 * (51_388_416 - 8_192) + 4 * (100_663_296 + 2_048))
    assert per_token == pytest.approx(matmuls + flops.flash_flops_per_step(c, 1, s) / s)
    assert per_token == pytest.approx(12.23e9, rel=1e-3)
    # the whole model: 48 layers, once each in the tree
    whole = dict(c, num_hidden_layers=48)
    assert flops.total_params(whole) == 48 * 51_388_416 + 201_326_592 + 4_097


def test_the_counts_are_the_programs_parameter_tree():
    from torchft_tpu.parallel.train import build_model

    c = tiny()
    model = build_model(adapter.model_config(c, 32), None)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))["params"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == flops.total_params(c)
    # the layers' parameters exist ONCE however often the loop runs them
    assert flops.total_params(dict(c, total_ut_steps=8)) == flops.total_params(c)
    assert sorted(k for k in shapes if k.startswith("layers_")) == [f"layers_{i}" for i in range(4)]
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes["exit_gate"]) == {"kernel": (65, 1)}


def test_the_model_configuration_is_the_files():
    cfg = adapter.model_config(PUBLISHED, 8192)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2048, 16, 16, 128)
    assert (cfg.intermediate_size, cfg.vocab_size, cfg.num_layers) == (5632, 49152, 6)
    assert cfg.layer_pattern == "*D" * 6 and cfg.norm_after_mixer == "both"
    assert (cfg.loop_steps, cfg.loop_entropy_coef) == (4, 0.05)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings) == (1e6, 1e-6, False)
    assert cfg.attn_impl == "flash" and cfg.dtype == jnp.bfloat16 and cfg.embed_init_std == 1.0
    sample = adapter.sample_config(cfg, 1024)
    assert sample.flash_min_seq == 1024 and sample.loop_steps == 4


@pytest.mark.parametrize("change,named", [
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"layer_types": ["full_attention", "sliding_attention"]}, "layer_types"),
    ({"layer_types": ["full_attention"]}, "layer_types"),
    ({"early_exit_threshold": 0.9}, "early_exit_threshold"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"total_ut_steps": 1}, "total_ut_steps"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"loop_entropy_coef": -0.1}, "loop_entropy_coef"),
    ({"model_type": "llama"}, "model_type"),
])
def test_the_adapter_refuses_by_name_what_the_program_does_not_compute(change, named):
    with pytest.raises(cells.CellError, match=named):
        adapter.model_config(tiny(**change), 32)


def test_the_adapter_refuses_a_sequence_past_the_context_and_a_file_that_lacks_a_key():
    with pytest.raises(cells.CellError, match="max_position_embeddings"):
        adapter.model_config(PUBLISHED, 65537)
    lacking = tiny()
    del lacking["total_ut_steps"]
    with pytest.raises(cells.CellError, match="total_ut_steps"):
        adapter.model_config(lacking, 32)


def test_the_exit_distribution_by_products():
    """Four steps: p sums to 1 a token, a fresh gate gives (1/2, 1/4, 1/8,
    1/8) and 1.213 nats, a saturated one a point mass and no NaN."""
    z = jnp.zeros((4, 3))
    p = reference.exit_probabilities(z)
    assert jnp.allclose(p[:, 0], jnp.array([0.5, 0.25, 0.125, 0.125]))
    assert float(reference.entropy(p)[0]) == pytest.approx(1.2130, abs=1e-4)
    z = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 3.0
    p = reference.exit_probabilities(z)
    assert jnp.allclose(p.sum(axis=0), 1.0, atol=1e-6) and bool(jnp.all(p >= 0))
    # the last step's logit is computed and unused
    assert jnp.array_equal(p, reference.exit_probabilities(z.at[-1].set(7.0)))
    for z0, at in ((30.0, 0), (-30.0, 3)):
        p = reference.exit_probabilities(jnp.full((4, 1), z0))
        assert float(p[at, 0]) == pytest.approx(1.0, abs=1e-6)
        h, g = jax.value_and_grad(lambda z: reference.entropy(reference.exit_probabilities(z))[0])(
            jnp.full((4, 1), z0))
        assert float(h) == pytest.approx(0.0, abs=1e-6) and bool(jnp.all(jnp.isfinite(g)))
    assert float(reference.entropy(jnp.full((4, 1), 0.25))[0]) == pytest.approx(math.log(4.0))


def _worst(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), got, want)
    return max(jax.tree_util.tree_leaves(errs))


@pytest.fixture(scope="module")
def sound():
    """One layer applied four times on one seeded sample, and the
    reference's own loss and gradients there: what the blocked form and
    every departure are read against."""
    from torchft_tpu.parallel.train import build_model

    c, seq = tiny(num_hidden_layers=1, layer_types=["full_attention"]), 16
    model = build_model(adapter.model_config(c, seq), None)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, seq + 1), 0, c["vocab_size"])
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
             "mask": jnp.ones((2, seq), jnp.int32).at[0, 3].set(0)}
    params = model.init(jax.random.PRNGKey(0), batch["inputs"])["params"]
    params["exit_gate"]["kernel"] = params["exit_gate"]["kernel"].at[-1, 0].add(0.4)  # the bias
    loss, grads = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, batch)
    return c, params, batch, loss, grads


def test_the_blocked_reference_is_the_reference(sound):
    c, params, batch, loss, grads = sound
    blocked_loss, blocked = jax.jit(
        lambda p, b: reference.loss_and_grads(p, b, c, query_block=8))(params, batch)
    assert float(blocked_loss) == pytest.approx(float(loss), rel=1e-6)
    assert _worst(blocked, grads) < 1e-4
    # the gate's weights and its bias (the last row) get a gradient
    gate = grads["exit_gate"]["kernel"]
    assert float(jnp.linalg.norm(gate[:-1])) > 1e-4 and abs(float(gate[-1, 0])) > 1e-5


@pytest.mark.parametrize("departure", reference.DEPARTURES)
def test_a_departure_is_another_result(sound, departure):
    """Each named departure moves a gradient leaf by far more than float32
    rounding: ``unshared`` the layers' (three of four visits dropped),
    ``norm_outside`` everything, ``no_entropy`` and ``gate_entropy_only``
    the gate's."""
    c, params, batch, _, want = sound
    _, got = jax.jit(
        lambda p, b: reference.loss_and_grads(p, b, c, departure=departure))(params, batch)
    assert _worst(got, want) > 0.05
    if departure in ("no_entropy", "gate_entropy_only"):
        assert _worst(got["exit_gate"], want["exit_gate"]) > 0.05
    with pytest.raises(ValueError, match="none of"):
        reference.loss(params, batch, c, departure="no_such_departure")


def test_the_exit_entropys_reader_reads_its_counter_and_nothing_else():
    read = cells.load_module(os.path.join(cells.HERE, "metrics", "loop_exit_entropy.py")).read
    counters = [
        {"loop_ce_1": 10.9, "loop_exit_step_mean": 1.9, "loop_exit_entropy": 1.22},
        {"loop_ce_1": 10.7, "loop_exit_step_mean": 2.1, "loop_exit_entropy": 1.30},
        {"loop_ce_1": 10.6, "loop_exit_step_mean": 2.0, "loop_exit_entropy": 1.25},
    ]
    assert read({"records": [{"counters": c} for c in counters]}) == 1.25
    # a parent whose step counts none of it: nothing to read, nothing raised
    assert read({"records": [{"counters": {"grad_norm": 1.0}}, {}]}) is None
