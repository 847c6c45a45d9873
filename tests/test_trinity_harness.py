"""Trinity-Mini (AFMoE), the third part of ``tests/test_trinity.py`` (files of
their own so that the suite's workers share the time): the existing presets'
trees and the old placements' programs, the file and the adapter's refusals,
the harness's own check and the builder's long comparison at a small size,
the presets and ``train_hsdp.py --model trinity_debug``."""

import dataclasses
import hashlib
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.tests import test_afmoe_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, llama
from torchft_tpu.models.llama import (
    Attention,
    MixerLayer,
    MoEMLP,
    Transformer,
    trinity_debug,
    trinity_mini,
    window_attention,
    window_mask,
)
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors
from tests.test_trinity import (  # noqa: F401
    CPU_GRAD_TOL,
    FLASH,
    LEAVES,
    PUBLISHED,
    TREES,
    _equations,
    _grads,
    _tree_digest,
    adapter,
    flops,
    reference,
    tiny,
)


@pytest.mark.parametrize("name", [
    "debug", "moe", "nemotron_h", "lfm2_moe", "sdar_moe", "joyai_flash", "olmo_hybrid",
    "solar_open2_debug", "smallthinker_debug", "olmoe"])
def test_the_existing_presets_trees_are_what_they_were(name):
    overrides = dict(num_layers=1) if name == "olmoe" else {}
    assert _tree_digest(PRESETS[name](**overrides)) == TREES[name]


def test_a_scale_of_one_and_the_old_placements_emit_no_new_operation():
    """``embed_scale`` 1.0 multiplies nothing: a scale is exactly one more
    ``mul`` and nothing else. The two old norm placements are the same
    operations in another order; the sandwich is one more norm a sub-layer."""
    toks = jnp.zeros((1, 8), jnp.int32)
    base = llama.olmo_hybrid_debug(layer_pattern="*D", norm_after_mixer=False)
    plain = _equations(base, toks)
    assert _equations(dataclasses.replace(base, embed_scale=1.0), toks) == plain
    scaled = _equations(dataclasses.replace(base, embed_scale=3.0), toks)
    assert scaled == dict(plain, mul=plain["mul"] + 1)
    after = _equations(dataclasses.replace(base, norm_after_mixer=True), toks)
    assert after == plain
    both = _equations(dataclasses.replace(base, norm_after_mixer="both"), toks)
    assert both["rsqrt"] == plain["rsqrt"] + 2 and both["dot_general"] == plain["dot_general"]


def test_the_count_is_the_models_own_count_of_its_tree():
    def own_count(c, seq):
        model = build_model(adapter.model_config(c, seq), None)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))
        )["params"]
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes))

    assert own_count(PUBLISHED, 256) == flops.total_params(PUBLISHED) == 705_474_304
    assert own_count(tiny(), 32) == flops.total_params(tiny())


def test_the_file_states_its_cuts_and_the_adapter_reads_every_key():
    c = PUBLISHED
    catalog = {  # the catalog row's config, every key
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
        "vocab_size": 200192,
    }
    cut = {"num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"}
    assert set(c["reduced"]) == cut
    for key, value in catalog.items():
        if key in cut:
            entry = c["reduced"][key]
            assert entry["published"] and entry["run"] and entry["why"]
            assert c[key] != value
        else:
            assert c[key] == value, key
    assert c["layer_types"] == catalog["layer_types"][1:6]  # published layers 1-5
    assert c["num_experts"] * c["expert_parallel_chips"] == 128
    assert c["vocab_size"] * c["vocab_parallel_chips"] == 200192
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    assert c["stands_for"] and set(c["distortions"]) >= {
        "rows_per_expert", "head_share", "attention_share", "uniform_tokens", "host_share"}
    assert set(c["assumed"]) >= {
        "attention gate", "qk norms", "rope-free global layers", "rotary", "window",
        "sandwich norms", "mup_enabled", "router", "load_balance_coeff", "initial values"}
    own_code = [k for k, v in c["assumed"].items() if "no key of config.json" in v]
    assert set(own_code) >= {"attention gate", "qk norms", "rope-free global layers",
                             "sandwich norms", "router"}
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "trinity-mini-l5e16")
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"].split(";")[0]
    cfg = adapter.model_config(c, 16384)
    assert (cfg.layer_pattern, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope,
            cfg.sliding_window, cfg.qk_norm, cfg.attn_gate, cfg.norm_after_mixer,
            cfg.vocab_size, cfg.rope_theta, cfg.norm_eps, cfg.embed_scale) == (
        "WDWE*EWEWE", 32, 4, 128, False, 2048, "head", True, "both", 25024, 1e4, 1e-5,
        2048 ** 0.5)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.intermediate_size,
            cfg.dense_intermediate_size, cfg.shared_expert_size, cfg.router_score,
            cfg.routed_scaling, cfg.gate_eps, cfg.expert_act, cfg.router_aux_coef,
            cfg.router_bias_update_rate, cfg.expert_capacity_factor, cfg.embed_init_std) == (
        128, 8, (0, 16), 1024, 6144, 1024, "sigmoid", 2.826, 1e-20, "swiglu", 0.0, 1e-3,
        None, None)
    assert adapter.model_config(dict(c, mup_enabled=False), 16384).embed_scale == 1.0


@pytest.mark.parametrize("key,value,says", [
    ("model_type", "qwen3_moe", "model_type"),
    ("hidden_act", "gelu", "hidden_act"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("num_limited_groups", 2, "num_limited_groups"),
    ("num_expert_groups", 2, "num_expert_groups"),
    ("num_shared_experts", 2, "num_shared_experts"),
    ("score_func", "softmax", "score_func"),
    ("route_norm", False, "route_norm"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("max_position_embeddings", 8192, "max_position_embeddings"),
    ("sliding_window", 0, "sliding_window"),
    ("expert_parallel_index", 8, "expert_parallel_index"),
    ("num_key_value_heads", 3, "num_key_value_heads"),
    ("num_experts_per_tok", 129, "num_experts_per_tok"),
    ("vocab_parallel_chips", 0, "vocab_parallel_chips"),
    ("load_balance_coeff", -0.1, "load_balance_coeff"),
    ("num_dense_layers", 6, "num_dense_layers"),
])
def test_the_adapter_refuses_by_name_what_the_program_does_not_compute(key, value, says):
    with pytest.raises(cells.CellError, match=says):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 16384)


def test_the_adapter_refuses_a_layout_off_its_period_and_a_file_with_a_key_to_spare(tmp_path):
    s, f = "sliding_attention", "full_attention"
    with pytest.raises(cells.CellError, match="does not repeat with period"):
        adapter.model_config(dict(PUBLISHED, layer_types=[s, s, f, s, f]), 16384)
    with pytest.raises(cells.CellError, match="more than one full_attention"):
        adapter.model_config(dict(PUBLISHED, layer_types=[f, s, f, s, f]), 16384)
    with pytest.raises(cells.CellError, match="are what the stack is built from"):
        adapter.model_config(dict(PUBLISHED, layer_types=[s, s, f, s]), 16384)
    with pytest.raises(cells.CellError, match="are what the stack is built from"):
        adapter.model_config(dict(PUBLISHED, layer_types=[s, s, "conv", s, s]), 16384)
    with pytest.raises(cells.CellError, match="sequence 131073 exceeds"):
        adapter.model_config(PUBLISHED, 131073)
    # a layer's kind is its entry, wherever in a period the kept run starts
    assert adapter.model_config(
        dict(PUBLISHED, layer_types=[f, s, s, s, f]), 16384).layer_pattern == "*DWEWEWE*E"
    lacking = {k: v for k, v in PUBLISHED.items() if k != "sliding_window"}
    with pytest.raises(cells.CellError, match="sliding_window"):
        adapter.model_config(lacking, 16384)
    with pytest.raises(cells.CellError, match="router_aux_loss_coef"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(router_aux_loss_coef=0.01)))
    assert cells.load_cell("w", _tiny_table(tmp_path, tiny())).arch_dir.endswith("afmoe")


def test_the_harness_check_passes_at_a_small_size(tmp_path, monkeypatch):
    """``worker.reference_check`` as the chip run makes it, on a sample
    longer than the window."""
    from benchmark import worker

    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 48)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    out = worker.reference_check(worker.Ctx(cell, 3000000001, 0, False))
    assert out["ok"] and out["grad_rel_l2_worst"] < CPU_GRAD_TOL and out["loss_rel_diff"] < 1e-5


def test_the_checks_sample_keeps_a_band_five_tiles_wide():
    """The harness samples 1,024 tokens, fewer than the window: the sample's
    model and the reference take half of the sample for the window, and the
    banded kernels run it at tiles of 128: a query tile's sweep is five key
    tiles (30 of the causal 36 a head), as the timed step's is at 2,048
    under tiles of 512, so the band's edge and a skipped tile are inside
    what decides ``correct``. A sample longer than the window (the builder's
    comparison) keeps the cell's own window and tiles."""
    cfg = adapter.model_config(PUBLISHED, 16384)
    sample = adapter.sample_config(cfg, 1024)
    assert (sample.sliding_window, sample.flash_block_q, sample.flash_block_k) == (512, 128, 128)
    assert sample.attn_impl == "flash" and sample.flash_min_seq <= 1024
    assert reference.window_at(PUBLISHED, 1024) == sample.sliding_window
    assert fa.choose_tiles("window", 1024, (128,), 128, 128, window=512) == (128, 128)
    kept, run = fa.window_tiles(1024, 512, 128, 128)
    assert (kept, run) == (fa.window_kept(1024, 512), 30 * 128 * 128)
    assert max(fa._band_sweeps(1024, 512, 128, 128)) == 5
    assert max(fa._band_sweeps(16384, 2048, 512, 512)) == 5
    assert fa.window_tiles(16384, 2048) == (31_458_304, 150 * 512 * 512)
    long = adapter.sample_config(cfg, 16384)
    assert (long.sliding_window, long.flash_block_q, long.flash_min_seq) == (2048, 1024, 2048)
    assert window_attention(long, 16384)[0] == (512, 512)  # the bound is not the tile
    # the rest of the sample's model is the cell's
    assert dataclasses.replace(
        sample, sliding_window=2048, flash_block_q=1024, flash_block_k=1024,
        flash_min_seq=cfg.flash_min_seq) == cfg


def test_the_presets():
    cfg = trinity_mini()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.dense_intermediate_size, cfg.vocab_size,
            cfg.max_seq_len, cfg.norm_eps, cfg.rope_theta, cfg.sliding_window) == (
        2048, 32, 32, 4, 128, 1024, 6144, 200192, 131072, 1e-5, 1e4, 2048)
    assert cfg.layer_pattern == "WDWDWE*E" + "WEWEWE*E" * 7
    assert len(cfg.layer_pattern) == 2 * cfg.num_layers
    assert (cfg.rope, cfg.qk_norm, cfg.attn_gate, cfg.norm_after_mixer, cfg.tie_embeddings,
            cfg.embed_scale, cfg.router_ahead) == (
        False, "head", True, "both", False, 2048 ** 0.5, False)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.shared_expert_size,
            cfg.experts_held, cfg.expert_act, cfg.router_score, cfg.routed_scaling,
            cfg.router_bias_update_rate, cfg.router_aux_coef) == (
        128, 8, 1024, None, "swiglu", "sigmoid", 2.826, 1e-3, 0.0)
    cut = adapter.model_config(PUBLISHED, 16384)
    same = ("hidden_size", "head_dim", "intermediate_size", "dense_intermediate_size",
            "norm_eps", "qk_norm", "rope", "rope_theta", "sliding_window", "attn_gate",
            "norm_after_mixer", "embed_scale", "tie_embeddings", "num_experts",
            "num_experts_per_tok", "router_score", "routed_scaling", "gate_eps", "expert_act",
            "shared_expert_size", "router_aux_coef", "router_z_coef",
            "router_bias_update_rate", "num_heads", "num_kv_heads")
    assert all(getattr(cut, k) == getattr(cfg, k) for k in same)
    assert cut.layer_pattern == cfg.layer_pattern[2:12]  # published layers 1-5
    small = trinity_debug()
    assert PRESETS["trinity_debug"] is trinity_debug and PRESETS["trinity_mini"] is trinity_mini
    assert (small.layer_pattern, small.sliding_window, small.experts_held, small.embed_scale,
            small.norm_after_mixer) == ("WDWE*EWEWE", 16, (0, 4), 8.0, "both")
    # a model that sets none of this is what it was, and no preset changed meaning
    plain = llama.LlamaConfig()
    assert (plain.norm_after_mixer, plain.embed_scale) == (False, 1.0)
    assert all(PRESETS[n]().embed_scale == 1.0 and PRESETS[n]().norm_after_mixer in (False, True)
               for n in PRESETS if not n.startswith(("trinity", "ouro")))  # the two sandwiches
    with pytest.raises(ValueError, match="pre-normed"):
        Transformer(dataclasses.replace(small, router_ahead=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_small_preset(tmp_path):
    """``train_hsdp.py --model trinity_debug``: one group, the Manager in
    the loop, three committed steps on the CPU, the selection biases moved."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "trinity_debug", "--steps", "3",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 3 and "router_bias_abs_max" in steps[-1], steps
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 3


def test_the_builders_long_comparison_at_a_small_size(tmp_path):
    """``tools/reference_compare.py`` as the chip run makes it at 16,384
    tokens: the cell's own model on a sequence several windows long against
    the reference in query blocks; the reference under a named departure
    handed in the system's place, and a program whose band is misplaced,
    read orders worse (the reference in float8:
    ``test_rounded_operands_are_another_result``)."""
    from tools import reference_compare

    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    out = reference_compare.compare(cell, 96, 3000000001, query_block=32)
    assert out["ok"] and (out["tokens"], out["query_block"], out["compared"]) == (
        96, 32, "system")
    assert out["grad_rel_l2_worst"] < CPU_GRAD_TOL and out["loss_rel_diff"] < 1e-5
    assert out["leaves"] == LEAVES
    gone = reference_compare.compare(cell, 96, 3000000001, query_block=32, departure="no_post_norm")
    assert gone["compared"] == "reference under no_post_norm" and gone["query_block"] == 32
    assert gone["grad_rel_l2_worst"] > 100 * CPU_GRAD_TOL and not gone["ok"]
    moved = reference_compare.compare(
        cell, 96, 3000000001, query_block=32, program_window=13)
    assert moved["compared"] == "system under a window of 13"
    assert moved["grad_rel_l2_worst"] > CPU_GRAD_TOL
