"""SmallThinker, the second half of ``tests/test_smallthinker.py`` (a file
of its own so that the suite's workers share the time): the fused step's
counters, the sharding rules on a mesh, remat, the file and the adapter's
refusals, the harness's own check at a small size and on a sample shorter
than the window with its controls, the embedding table's deviation, the
presets, ``train_hsdp.py --model smallthinker_debug`` and the builder's
long comparison at a small size."""

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
from tests.harness_controls import dead_leaf, shared_check
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors
from tests.test_smallthinker import (  # noqa: F401
    CPU_GRAD_TOL,
    FLASH,
    PUBLISHED,
    adapter,
    flops,
    reference,
    tiny,
)


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


@pytest.fixture(scope="module")
def short_sample(tmp_path_factory):
    """``worker.reference_check`` on 8 tokens under a published window of
    12, so a window of 2 on both sides, run ONCE: the sound case and the
    sample every control below is read against
    (``tests/harness_controls.py``)."""
    cell = cells.load_cell("w", _tiny_table(tmp_path_factory.mktemp("short"), tiny()))
    cell.mix.update(batch=1, seq=48)
    return shared_check(cell, 8)


@pytest.mark.parametrize("what", ["sound", "dead_router", "dead_expert_stack", "no_band"])
def test_the_harness_check_on_a_sample_shorter_than_the_window(short_sample, what):
    """The check on the short sample passes; a leaf whose gradient never
    moves reads 1.0 and a program that runs no band reads over the CPU
    limit, and the reference's own limit refuses the dead leaf."""
    loss, grads = short_sample.kept["system"]
    if what == "sound":
        out = short_sample.sound
    elif what == "dead_router":
        out = short_sample.control((loss, dead_leaf(grads, "layers_2", "router", "kernel")))
    elif what == "dead_expert_stack":
        out = short_sample.control((loss, dead_leaf(grads, "layers_3", "mlp", "experts_up")))
    else:
        out = short_sample.control(short_sample.system(sliding_window=8))
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
    low = reference_compare.compare(cell, 96, 3000000001, query_block=32,
                                    operand_dtype="float8_e4m3fn")
    assert low["compared"] == "reference in float8_e4m3fn" and low["query_block"] == 32
    assert low["grad_rel_l2_worst"] > 10 * out["grad_rel_l2_worst"]
    # a program whose band is misplaced, against the reference's own
    moved = reference_compare.compare(
        cell, 96, 3000000001, query_block=32, program_window=13)
    assert moved["compared"] == "system under a window of 13"
    assert moved["grad_rel_l2_worst"] > CPU_GRAD_TOL
