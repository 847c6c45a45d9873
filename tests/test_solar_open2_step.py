"""Solar-Open2, the second half of ``tests/test_solar_open2.py`` (a file of
its own so that the suite's workers share the time): what a lower precision
or a dropped term does to the comparison with the reference, the fused
step's counters, the sharding rules on a mesh, two replicas fed one batch,
the file and the adapter's refusals, the presets and ``train_hsdp.py --model solar_open2_debug``."""

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
from benchmark.tests import test_solar_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, KDAConfig, gated_delta, llama
from torchft_tpu.models.gated_delta import KimiDeltaMixer, kda_chunked
from torchft_tpu.models.llama import (
    Attention,
    MoEMLP,
    solar_open2_250b,
    solar_open2_debug,
)
from torchft_tpu.ops import gated_delta as gdn_kernel
from torchft_tpu.ops import kda as kda_kernel
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
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors
from tests.test_solar_open2 import (  # noqa: F401
    CPU_GRAD_TOL,
    PUBLISHED,
    _system,
    _worst,
    adapter,
    flops,
    reference,
    tiny,
)


@pytest.fixture(scope="module")
def sound_sample():
    """One seeded sample of 64 tokens with the program's and the
    reference's own loss and gradients on it, computed once: a
    lower-precision program is read against this reference, a reference
    that drops a term against this program."""
    c = tiny()
    params, data, loss, grads = _system(c, 64)
    _, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    return c, params, data, loss, grads, grads_ref


@pytest.mark.parametrize("what", ["bf16_decays", "bf16_inverse", "bf16_gate",
                                  "no_kda_gate", "no_attn_gate", "no_beta_doubling"])
def test_a_lower_precision_or_a_dropped_term_fails_the_comparison(sound_sample, what, monkeypatch):
    """The program with its log-decays, its T = (I + A)^-1 or its gates
    rounded to bfloat16, or with a term left out on one side, against the
    other side as it stands: each is past the CPU limit on some gradient
    leaf (and the dropped terms past the reference's own gradient limit)."""
    c, params, data, loss, grads, grads_ref = sound_sample
    bf16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    drop = None
    if what == "bf16_decays":  # head width 16: the program runs the kernels
        real = kda_kernel.kda
        monkeypatch.setattr(
            kda_kernel, "kda",
            lambda q, k, v, g, beta, *rest: real(q, k, v, bf16(g), beta, *rest))
    elif what == "bf16_inverse":
        real = kda_kernel._unit_lower_inverse
        monkeypatch.setattr(
            kda_kernel, "_unit_lower_inverse", lambda *a: bf16(real(*a)))
    elif what == "bf16_gate":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x, real=jax.nn.sigmoid: bf16(real(x)))
    elif what == "no_beta_doubling":
        drop = "beta_doubling"  # the reference leaves it out
    else:
        drop = what[3:]
    if drop is None:  # the program departs: it is traced again under the patch
        jax.clear_caches()  # which the sound program's traces must not hide
        _, _, loss, grads = _system(c, 64, params, data)
        monkeypatch.undo()
        jax.clear_caches()  # and must not keep the rounded one
    else:  # the reference departs
        loss_ref, grads_ref = jax.jit(
            lambda p, b: reference.loss_and_grads(p, b, c, drop=drop))(params, data)
    worst = _worst(_leaf_errors(grads, grads_ref))
    assert worst > 2 * CPU_GRAD_TOL, worst
    if drop is not None:
        assert worst > reference.GRAD_REL_L2_TOL
        assert loss != float(loss_ref)


def test_the_step_hands_on_the_mixers_and_the_experts_counters(caplog):
    cfg = solar_open2_debug()
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    gated_delta._NOTED.clear()
    with caplog.at_level(logging.INFO, logger="torchft_tpu.models.gated_delta"):
        new, metrics = make_train_step(model, mesh, sh, donate=False)(
            state, _data(cfg.vocab_size, 2, 64))
    # head width 16: the kernels, through the interpreter off the chip
    assert "gated_delta: traced=kda-kernel chunk=64 seq=64" in caplog.text
    assert "WARNING" not in [r.levelname for r in caplog.records]
    assert set(metrics) == {
        "loss", "grad_norm", "kda_state_abs_max", "kda_decay_min", "kda_beta_mean",
        "moe_held_share", "moe_held_run_share",
        "moe_held_token_run_share", "moe_dropped", "moe_max_load", "router_aux"}
    assert int(new.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["kda_decay_min"]) < 1.0
    assert 0.5 < float(metrics["kda_beta_mean"]) < 1.5  # sigmoid's mean, doubled
    assert 0.0 < float(metrics["kda_state_abs_max"]) < 100.0
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0
    # nothing moves the selection biases: the published file names no rate
    moved = jax.tree_util.tree_leaves_with_path(new.params)
    assert all(not np.asarray(leaf).any() for path, leaf in moved
               if "router_bias" in jax.tree_util.keystr(path))


def test_the_rules_name_the_new_leaves_and_a_sharded_mesh_computes_the_same_step():
    """fsdp=2 x tp=2 on four virtual devices against one device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = solar_open2_debug(dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: build_model(cfg, None).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    specs = param_specs(shapes)
    P = jax.sharding.PartitionSpec
    mixer = specs["layers_2"]["kda"]
    assert set(mixer) == {"q_proj", "k_proj", "v_proj", "b_proj", "f_a_proj", "f_b_proj",
                          "g_a_proj", "g_b_proj", "o_proj", "conv_kernel", "A_log",
                          "dt_bias", "norm_scale"}
    for name in ("q_proj", "k_proj", "v_proj", "b_proj"):
        assert mixer[name]["kernel"] == P("fsdp", "tp"), name
    for name in ("f_a_proj", "g_a_proj"):
        assert mixer[name]["kernel"] == P("fsdp", None), name
    for name in ("f_b_proj", "g_b_proj"):
        assert mixer[name]["kernel"] == P(None, "tp"), name
    assert mixer["o_proj"]["kernel"] == P("tp", "fsdp")
    assert mixer["conv_kernel"] == mixer["A_log"] == mixer["dt_bias"] == P()
    assert mixer["norm_scale"] == mixer["g_b_proj"]["bias"] == P()
    attn = specs["layers_0"]["attn"]
    assert attn["wg"]["kernel"] == attn["wq"]["kernel"] == P("fsdp", "tp", None)
    data = _data(cfg.vocab_size, 4, 64)
    seen = []
    for mesh in (auto_mesh(1, devices=jax.devices()[:1]), make_mesh(fsdp=2, tp=2)):
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 64))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "kda_state_abs_max")])
    assert seen[0] == pytest.approx(seen[1], rel=2e-3)
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-4)


@pytest.mark.timeout(300)
def test_two_replicas_fed_one_batch_commit_bitwise_equal_parameters():
    """``FTStep`` over the split step (``make_split_grad_step`` then
    ``make_apply_step``): the counters stay with the step, the gradients
    ride the allreduce, and both replicas hold the same parameters bit for
    bit."""
    (losses0, leaves0), (losses1, leaves1) = two_replicas(solar_open2_debug, "solar")
    assert losses0 == losses1 and len(losses0) == 2 and losses0[0] != losses0[1]
    assert all(np.array_equal(a, b) for a, b in zip(leaves0, leaves1))


def test_the_count_is_the_models_own_count_of_its_tree():
    def own_count(c, seq):
        model = build_model(adapter.model_config(c, seq), None)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))
        )["params"]
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes))

    assert own_count(PUBLISHED, 256) == flops.total_params(PUBLISHED) == 840_875_672
    assert own_count(tiny(), 32) == flops.total_params(tiny())


def test_the_file_states_its_cuts_and_the_adapter_reads_every_key():
    c = PUBLISHED
    catalog = {  # the catalog row's config, every key
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                               "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
        "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0, "use_rope": False,
        "gqa_interval": 3, "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 320,
        "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 8,
    }
    cut = {"num_hidden_layers", "gqa_layers", "num_attention_heads", "num_key_value_heads",
           "linear_attn_config", "n_routed_experts", "vocab_size"}
    assert set(c["reduced"]) == cut
    for key, value in catalog.items():
        if key in cut:
            entry = c["reduced"][key]
            assert entry["published"] == value != entry["run"] == c[key] and entry["why"]
        else:
            assert c[key] == value, key
    # of the nested group only the count of heads is changed: no width
    assert c["linear_attn_config"] == dict(catalog["linear_attn_config"], num_heads=8)
    assert c["gqa_layers"] == [0]
    assert c["num_attention_heads"] * c["head_parallel_chips"] == 64
    assert c["num_key_value_heads"] * c["head_parallel_chips"] == 8
    assert c["linear_attn_config"]["num_heads"] * c["head_parallel_chips"] == 64
    assert c["n_routed_experts"] * c["expert_parallel_chips"] == 320
    assert c["vocab_size"] * c["vocab_parallel_chips"] == 196608
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    assert c["stands_for"] and set(c["distortions"]) >= {"rows_an_expert", "head_share_of_flops"}
    assert set(c["assumed"]) >= {"router", "shared expert", "hidden_act", "attention gate",
                                 "kda mixer", "intermediate_size"}
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "solar-open2-250b-l4h8e8")
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"].split(";")[0]
    cfg = adapter.model_config(c, 8192)
    assert (cfg.layer_pattern, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope,
            cfg.attn_gate, cfg.qk_norm, cfg.norm_after_mixer, cfg.vocab_size) == (
        "*EKEKEKE", 8, 1, 128, False, True, False, False, 24576)
    assert cfg.kda == KDAConfig(8, 128, 4, True)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.intermediate_size,
            cfg.shared_expert_size, cfg.router_score, cfg.routed_scaling, cfg.expert_act,
            cfg.router_bias_update_rate, cfg.router_aux_coef) == (
        320, 8, (0, 8), 1280, 1280, "sigmoid", 1.0, "swiglu", 0.0, 0.0)
    # four times the uniform 3,277 rows, in whole tiles of 512 (13,112 as a multiple of 8)
    assert llama.held_buffer_rows(cfg, 16384) == 13312 == 26 * llama.HELD_ROW_TILE
    assert gated_delta.CHUNK == flops.CHUNK == 64 and gated_delta.SUB == 16


@pytest.mark.parametrize("key,value,says", [
    ("model_type", "kimi_linear", "model_type"),
    ("use_rope", True, "use_rope"),
    ("use_gqa_gate", False, "use_gqa_gate"),
    ("kda_use_full_proj", True, "kda_use_full_proj"),
    ("first_k_dense_replace", 1, "first_k_dense_replace"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("gqa_layers", [1], "gqa_layers"),
    ("gqa_interval", 2, "gqa_layers"),
    ("linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 8},
     "linear_attn_config"),
    ("linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 8,
                            "num_kv_heads": 4}, "num_kv_heads"),
    ("max_position_embeddings", 4096, "max_position_embeddings"),
    ("head_parallel_chips", 0, "head_parallel_index"),
    ("head_parallel_index", 8, "head_parallel_index"),
    ("expert_parallel_index", 40, "expert_parallel_index"),
    ("num_key_value_heads", 3, "num_key_value_heads"),
    ("num_experts_per_tok", 321, "num_experts_per_tok"),
    ("vocab_parallel_chips", 0, "vocab_parallel_chips"),
])
def test_the_adapter_refuses_by_name_what_the_program_does_not_compute(key, value, says):
    with pytest.raises(cells.CellError, match=says):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 8192)


def test_the_adapter_refuses_a_file_that_lacks_a_key_or_has_one_to_spare(tmp_path):
    lacking = {k: v for k, v in PUBLISHED.items() if k != "kda_allow_neg_eigval"}
    with pytest.raises(cells.CellError, match="kda_allow_neg_eigval"):
        adapter.model_config(lacking, 8192)
    with pytest.raises(cells.CellError, match="scoring_func"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(scoring_func="softmax")))
    assert cells.load_cell("w", _tiny_table(tmp_path, tiny())).arch_dir.endswith("solar_open2")


def test_the_presets():
    cfg = solar_open2_250b()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) == (
        4096, 48, 64, 8, 128, 1280, 196608, 1048576, 1e-5)
    assert cfg.layer_pattern == "*EKEKEKE" * 12 and len(cfg.layer_pattern) == 2 * cfg.num_layers
    assert cfg.kda == KDAConfig(64, 128, 4, True)
    assert (cfg.kda.key_dim, cfg.kda.conv_dim) == (8192, 24576)
    assert (cfg.rope, cfg.attn_gate, cfg.qk_norm, cfg.tie_embeddings) == (
        False, True, False, False)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.shared_expert_size,
            cfg.routed_scaling, cfg.experts_held, cfg.router_bias_update_rate) == (
        320, 8, 1280, 1.0, None, 0.0)
    cut = adapter.model_config(PUBLISHED, 8192)
    same = ("hidden_size", "head_dim", "intermediate_size", "norm_eps", "qk_norm", "rope",
            "attn_gate", "tie_embeddings", "num_experts", "num_experts_per_tok",
            "router_score", "routed_scaling", "shared_expert_size", "expert_act",
            "router_aux_coef", "router_bias_update_rate", "gate_eps")
    assert all(getattr(cut, k) == getattr(cfg, k) for k in same)
    assert cut.layer_pattern == cfg.layer_pattern[:8]
    small = solar_open2_debug()
    assert PRESETS["solar_open2_debug"] is solar_open2_debug
    assert PRESETS["solar_open2_250b"] is solar_open2_250b
    assert (small.layer_pattern, small.kda.num_heads, small.attn_gate, small.experts_held) == (
        "*EKEKEKE", 4, True, (0, 4))
    # a model that sets none of this is what it was
    assert (llama.LlamaConfig().kda, llama.LlamaConfig().attn_gate) == (None, False)
    with pytest.raises(ValueError, match="'K'"):
        llama.MixerLayer(small, "Q").init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_small_preset(tmp_path):
    """``train_hsdp.py --model solar_open2_debug``: one group, the Manager in
    the loop, three committed steps on the CPU."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "solar_open2_debug", "--steps", "3",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 3, steps
    assert "gated_delta: traced=kda-kernel chunk=64 seq=32" in proc.stderr
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 3
