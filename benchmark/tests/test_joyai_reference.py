"""The ``joyai_flash`` architecture as the benchmark holds it: the
reference's own proofs (its rotation of adjacent pairs against a rotation
written with complex numbers, its prediction module's targets and weights
by hand), the configuration file against the catalog's published keys, the
counts against a hand count and the program's parameter tree, the lookup
by the ``"arch"`` key, the adapter's refusals, the readers of the cell's
new metrics, the harness's own check at a small size with its controls,
and the cell end to end through ``run.py`` on a tiny table."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, worker
from benchmark.tests.test_sdar_reference import _fake_run

adapter = cells.arch_module("joyai_flash", "adapter")
reference = cells.arch_module("joyai_flash", "reference")
flops = cells.arch_module("joyai_flash", "flops")
CONFIG_FILE = os.path.join(cells.HERE, "configs", "joyai-llm-flash-l6e8.json")
PUBLISHED = cells.load_json(CONFIG_FILE)
# The catalog row's `config` (model-configs guide, architectures.jsonl,
# JoyAI-LLM-Flash), key for key.
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 32000000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
NEW_METRICS = {"mla_proj_ms", "mtp_loss_share", "joyai_held_share", "joyai_held_dropped",
               "joyai_gmm_roofline"}


def tiny(**overrides):
    """The published file at widths a CPU test can afford: a dense layer
    and two expert layers, 16 experts over 4 chips, this chip the second,
    one prediction module."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, num_attention_heads=4, num_key_value_heads=4,
        head_dim=8, q_lora_rank=48, kv_lora_rank=32, qk_head_dim=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160, moe_intermediate_size=48,
        num_hidden_layers=3, n_routed_experts=4, expert_parallel_chips=4,
        expert_parallel_index=1, num_experts_per_tok=3, vocab_parallel_chips=1,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def test_the_rotation_turns_adjacent_pairs_and_a_score_sees_positions_apart():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 8))
    got = reference._rotary_pairs(x, 1e4)
    # channels (2i, 2i+1) as one complex number, times exp(i pos theta^(-2i/D))
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    angle = np.arange(12)[:, None] * 1e4 ** (-np.arange(0, 8, 2) / 8)[None, :]
    want = z * np.exp(1j * angle)[None, :, None, :]
    np.testing.assert_allclose(got[..., 0::2], want.real, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], want.imag, rtol=1e-5, atol=1e-5)
    # one query and one key everywhere: their score is a function of how
    # far apart they stand
    q = reference._rotary_pairs(jnp.broadcast_to(x[:1, :1, :1], (1, 12, 1, 8)), 1e4)
    k = reference._rotary_pairs(jnp.broadcast_to(x[1:, :1, :1], (1, 12, 1, 8)), 1e4)
    scores = jnp.einsum("bqnd,bknd->qk", q, k)
    np.testing.assert_allclose(scores[3, 1], scores[9, 7], rtol=1e-4, atol=1e-5)
    assert abs(float(scores[3, 1] - scores[3, 2])) > 1e-3


def test_the_modules_loss_is_against_the_token_two_on_and_the_last_row_weighs_nothing():
    """``losses`` by hand at one prediction module: its inputs are the
    stack's output before the final norm and the NEXT token's embedding,
    its targets the targets rolled by one, the last row's weight 0, a
    masked row's and its predecessor's too."""
    c = tiny()
    cfg = adapter.model_config(c, 16)
    from torchft_tpu.parallel.train import build_model

    model = build_model(cfg, None)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 256)
    mask = jnp.ones((2, 16), jnp.int32).at[0, 5].set(0)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:], "mask": mask}
    params = model.init(jax.random.PRNGKey(0), batch["inputs"])["params"]
    total, main, mtp = reference.losses(params, batch, c)
    assert float(total) == pytest.approx(float(main) + 0.3 * float(mtp), rel=1e-6)
    assert float(mtp) > 0 and abs(float(mtp) - float(main)) > 1e-4

    ident = lambda a: a  # noqa: E731
    x = params["embed"]["embedding"][batch["inputs"]]
    for i in range(3):
        x = reference.layer(x, params[f"layers_{2 * i}"], params[f"layers_{2 * i + 1}"], c, ident)
    m = params["mtp_0"]
    both = jnp.concatenate([
        reference._rms_norm(x, m["hnorm"]["scale"], 1e-6),
        reference._rms_norm(
            params["embed"]["embedding"][batch["targets"]], m["enorm"]["scale"], 1e-6),
    ], axis=-1)
    x2 = reference.layer(both @ m["eh_proj"]["kernel"], m["layers_0"], m["layers_1"], c, ident)
    hidden = reference._rms_norm(x2, params["final_norm"]["scale"], 1e-6)
    logp = jax.nn.log_softmax(hidden @ params["lm_head"]["kernel"], axis=-1)
    weights = np.ones((2, 16), np.float32)
    weights[:, -1] = 0  # its target lies past the batch
    weights[0, 5] = weights[0, 4] = 0  # the masked row, and the row whose target it is
    two_on = np.asarray(toks[:, 2:])  # t_{p+2} for p < 15
    picked = np.take_along_axis(np.asarray(logp)[:, :15], two_on[..., None], axis=-1)[..., 0]
    want = -(picked * weights[:, :15]).sum() / weights.sum()
    assert float(mtp) == pytest.approx(float(want), rel=1e-5)
    # a second module continues from the first's output, one token further on
    c2 = tiny(num_nextn_predict_layers=2)
    model2 = build_model(adapter.model_config(c2, 16), None)
    params2 = model2.init(jax.random.PRNGKey(0), batch["inputs"])["params"]
    assert {"mtp_0", "mtp_1"} <= set(params2)
    assert np.isfinite(float(reference.losses(params2, batch, c2)[2]))


def test_every_published_key_is_in_the_file_unchanged_but_the_three_reduced():
    c = PUBLISHED
    assert set(c["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in CATALOG.items():
        if key in c["reduced"]:
            cut = c["reduced"][key]
            assert cut["published"] == value and cut["run"] == c[key] != value and cut["why"]
        else:
            assert c[key] == value and type(c[key]) is type(value), key
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (6, 8, 16160)
    assert c["n_routed_experts"] * c["expert_parallel_chips"] == CATALOG["n_routed_experts"]
    assert c["vocab_size"] * c["vocab_parallel_chips"] == CATALOG["vocab_size"]
    assert c["num_nextn_predict_layers"] == 1 and c["arch"] == "joyai_flash"
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    # every key the published file does not have is stated as assumed
    own = set(adapter.KEYS) - set(CATALOG)
    stated = " ".join(c["assumed"])
    assert own and all(key in stated for key in own), own
    for said in ("concatenation order", "rope_interleave pairing", "initial values"):
        assert said in c["assumed"]
    assert "32 chips" in c["stands_for"] and "8 chips" in c["stands_for"]
    assert {"rows_per_expert", "attention_share", "module_share", "host_share",
            "uniform_tokens"} <= set(c["distortions"])
    assert c["run"] == {"attn_impl": "flash", "compute_dtype": "bfloat16",
                        "param_dtype": "float32"}
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "joyai-llm-flash-l6e8")
    assert entry["reduced"] == list(c["reduced"]) and len(entry["why"]) <= 200
    # The table's source is the catalog's source_url letter for letter.
    assert entry["source"] == c["source"].split(";")[0]
    assert entry["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json")
    assert table["configs"][-1] is entry and table["workloads"][-1]["name"] == "joyai-raw"
    assert len(table["workloads"][-1]["why"]) <= 200


def test_the_cell_is_found_by_its_arch_key_with_its_metrics():
    cell = cells.load_cell("joyai-raw")
    assert cell.arch_dir == os.path.join(cells.HERE, "arch", "joyai_flash")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"], cell.mix["trainer"]) == (
        1, 2, 8192, "raw")
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS | {"mfu_pct", "flash_ms", "flash_roofline", "host_other_ms",
                          "hbm_reserved_gib"} <= names
    assert not names & {"step_ms", "head_loss_ms", "gated_held_ms", "sdar_held_ms", "ssm_ms"}
    worker.load_metric_readers(cell, "")
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for m in table["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert (m["moves"], m["workloads"]) == ("tok_s_chip", ["joyai-raw"])
            assert os.path.isfile(os.path.join(cells.HERE, "metrics", m["name"] + ".py"))
    assert {m["name"] for m in table["per_layer"][-5:]} == NEW_METRICS  # at the list's end


def test_the_new_metrics_read_the_steps_counters_and_the_layers_shapes():
    from benchmark.metrics import (
        flash_ms, flash_roofline, joyai_gmm_roofline, joyai_held_dropped,
        joyai_held_share, mla_proj_ms, mtp_loss_share,
    )

    cell = cells.load_cell("joyai-raw")
    step = lambda traced, share, mtp, loss: {  # noqa: E731
        "traced": traced, "loss": loss,
        "counters": {"moe_held_share": share, "moe_dropped": 0.0, "loss_mtp": mtp},
    }
    records = [step(True, 0.02, 9.7, 12.6), step(True, 0.04, 9.6, 12.5), step(False, 0.5, 9.0, 12.0)]
    ops = {
        "ragged-dot-none.3 bf16[16384,768]{1,0:T(8,128)(2,1)} cust": 0.03,
        "ragged-dot-metadata.1 s32[9]": 0.01,
        "flash_attention_mla.14 (bf16[2,32,8192,128]{3,2,1,0:T(8,128)(2,": 0.6,
        "fusion.7 bf16[2,8192,576]{1,2,0:T(8,128)(2,1)S(1)": 0.010,    # W_kva
        "fusion.8 bf16[2,8192,32,192]{1,3,2,0:T(8,128)(2,1": 0.020,    # W_qb
        "fusion.9 bf16[2,8192,32,256]{3,1,2,0:T(8,128)(2,1": 0.030,    # W_kvb
        "fusion.10 bf16[2,8192,32,64]{1,2,3,0:T(8,128)(2,1)": 0.004,   # the queries' rotation
        "copy.3 bf16[2,8192,64]{1,0,2:T(2,128)(2,1)S(1)}": 0.001,      # the shared key
        "slice_bitcast_fusion.2 (bf16[2,32,8192,128]{3,2,1,0:T(8,128)(2,": 0.002,
        "fusion.11 (f32[1536]{0:T(1024)}, f32[2,8192]{1,0:T": 0.005,   # q_norm backward
        "fusion.12 (f32[512]{0:T(512)S(1)}, f32[2,8192]{1,0": 0.003,   # kv_norm backward
        "fusion.13 (f32[1536]{0:T(1024)}, f32[1536]{0:T(102": 0.9,     # the optimizer: not named
        "fusion.14 (f32[2,8192]{1,0:T(2,128)}, bf16[2,8192,": 0.9,     # W_qa with a norm's statistics
        "fusion.15 bf16[2,8192,2048]{2,1,0}": 0.9,                     # the rest of the block
    }
    run = _fake_run(cell, ops, records)
    assert mtp_loss_share.read(run) == pytest.approx(0.3 * 9.6 / 12.5)
    assert joyai_held_share.read(run) == 0.04 and joyai_held_dropped.read(run) == 0.0
    assert mla_proj_ms.read(run) == pytest.approx(
        (0.010 + 0.020 + 0.030 + 0.004 + 0.001 + 0.002 + 0.005 + 0.003) * 1e3 / 2)
    assert flash_ms.read(run) == pytest.approx(300.0)  # the kernels, and no glue
    # 7 attention layers of 32 heads at 192 + 128 wide, causal, three times forward
    work = 3 * 2 * (192 + 128) * (8192 * 8192 / 2) * 32 * 2 * 7
    assert flops.flash_flops_per_step(cell.config, 2, 8192) == pytest.approx(work)
    assert 28.8e12 < work < 28.9e12
    assert flash_roofline.read(run) == pytest.approx(100 * work / 197e12 / 0.3)
    # about 500 rows an expert: level with the chip's ridge, the operations
    # just above the bytes
    ops_s = flops.gmm_flops_per_step(cell.config, 2, 8192, 0.03) / 197e12
    bytes_s = flops.gmm_bytes_per_step(cell.config, 2, 8192, 0.03) / 819e9
    assert 0.8 < bytes_s / ops_s < 1.0
    least_ms = ops_s * 1e3
    assert joyai_gmm_roofline.read(run) == pytest.approx(100 * least_ms / 20.0)
    assert flops.gmm_flops_per_step(cell.config, 2, 8192, 1 / 32) == (
        3 * 2 * 3 * 2048 * 768 * 4096 * 6)
    bare = {**run, "records": [{"traced": True, "loss": 1.0, "counters": {}}]}
    assert mtp_loss_share.read(bare) is None and joyai_held_share.read(bare) is None
    assert joyai_gmm_roofline.read(bare) is None and joyai_held_dropped.read(bare) is None
    assert mla_proj_ms.read({**run, "trace": None}) is None
    # on a cell without latent ranks, or a program that counts no module: nothing, and no error
    other = {**run, "cell": cells.load_cell("lfm2-raw")}
    assert mla_proj_ms.read(other) is None and mtp_loss_share.read(other) is None


def test_the_counts_are_the_hand_count_and_the_parameter_trees():
    """ISSUE 51's arithmetic: the published model, then the cut file."""
    whole = dict(CATALOG, expert_parallel_chips=1)
    attention = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
                 + 32 * 128 * 2048)
    assert flops.attention_matmul_params(whole) == attention == 26_345_472
    assert flops.attention_params(whole) == attention + 1536 + 512
    assert flops.expert_params(whole) == 3 * 2048 * 768 == 4_718_592
    assert flops.dense_ffn_params(whole) == 3 * 2048 * 7168 == 44_040_192
    assert 48e9 < flops.total_params(whole) < 51e9
    assert 2.6e9 < flops.active_params(whole) < 3.0e9
    c = PUBLISHED
    block = attention + 2048 + 2 * 2048  # its two norms' vectors, two pre-norms
    sparse = 8 * 4_718_592 + 4_718_592 + 2048 * 256 + 256
    module = 2 * 2048 * 2048 + 2 * 2048
    assert flops.total_params(c) == (
        7 * block + 44_040_192 + 6 * sparse + module + 2048 + 2 * 2048 * 16160
    ) == 561_038_848
    from torchft_tpu.parallel.train import build_model

    model = build_model(adapter.model_config(c, 256), None)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32))
    )["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 561_038_848
    tiny_model = build_model(adapter.model_config(tiny(), 16), None)
    tiny_shapes = jax.eval_shape(
        lambda: tiny_model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    )["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(tiny_shapes)) == flops.total_params(tiny())
    # A token: seven attentions, the dense feed-forward, six expert layers
    # (router, shared expert, 8 x 8/256 of an expert), the module's joining
    # projection, the head twice; its causal scores.
    active = (7 * attention + 44_040_192 + 6 * (2048 * 256 + 4_718_592 + 4_718_592 // 4)
              + 2 * 2048 * 2048 + 2 * 2048 * 16160)
    scores = 3 * 2 * (192 + 128) * (8192 / 2) * 32 * 7
    assert flops.model_flops_per_token(c, 8192) == pytest.approx(6 * active + scores)
    assert (flops.flash_flops_per_step(c, 2, 8192) / 197e12
            > 9 * flops.flash_bytes_per_step(c, 2, 8192) / 819e9)  # compute-bound


@pytest.mark.parametrize("key,value", [
    ("model_type", "deepseek_v3"), ("attention_bias", True), ("hidden_act", "gelu"),
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8), ("topk_group", 4),
    ("norm_topk_prob", False), ("moe_layer_freq", 2), ("ep_size", 8),
    ("rope_scaling", {"type": "yarn", "factor": 40}), ("rope_interleave", False),
    ("tie_word_embeddings", True), ("expert_parallel_index", 32), ("num_experts_per_tok", 257),
    ("num_key_value_heads", 8), ("qk_head_dim", 128), ("head_dim", 128),
    ("first_k_dense_replace", 7), ("vocab_parallel_chips", 0), ("num_nextn_predict_layers", -1),
])
def test_the_adapter_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(cells.CellError, match=key):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 8192)


def test_the_adapter_refuses_a_file_of_another_architecture_and_a_long_sequence():
    sdar = cells.load_cell("sdar-raw").config
    with pytest.raises(cells.CellError, match="lacks"):
        adapter.model_config(dict(sdar), 8192)
    with pytest.raises(cells.CellError, match="max_position_embeddings"):
        adapter.model_config(dict(PUBLISHED), 131073)
    cfg = adapter.model_config(dict(PUBLISHED), 8192)
    assert cfg.layer_pattern == "*D" + "*E" * 5 and cfg.mtp_layers == 1
    assert (cfg.num_experts, cfg.experts_held, cfg.shared_expert_size) == (256, (0, 8), 768)
    assert (cfg.mla.q_lora_rank, cfg.mla.kv_lora_rank, cfg.mla.qk_head_dim) == (1536, 512, 192)


def _tiny_table(tmp_path, config, traffic_dir=None):
    """A table of one cell beside which nothing lies: the architecture and
    the metrics are the benchmark's own."""
    (tmp_path / "c.json").write_text(json.dumps(config))
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    table["configs"] = [{"name": "c", "file": "c.json"}]
    table["workloads"] = [{"name": "w", "config": "c", "chips": 1,
                           "traffic": "tiny-raw" if traffic_dir else "raw-2x8192"}]
    table["traffic_dir"] = traffic_dir or os.path.join(cells.HERE, "traffic")
    # The cell's new metrics and one a CPU run can read besides (the
    # shares of a peak need a chip's published peaks).
    table["per_layer"] = [
        dict(m, workloads=["w"]) for m in table["per_layer"]
        if m["name"] in NEW_METRICS | {"host_other_ms"}
    ]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(table))
    return str(path)


def test_load_cell_refuses_a_key_the_adapter_does_not_read(tmp_path):
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    assert cell.arch_dir.endswith(os.path.join("arch", "joyai_flash"))
    with pytest.raises(cells.CellError, match="mscale"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(mscale=1.0)))


@pytest.mark.timeout(600)
def test_the_harness_check_passes_and_float8_bf16_parameters_and_a_dead_leaf_fail(
    tmp_path, monkeypatch
):
    """worker.reference_check as the chip run makes it, at a small size in
    float32; then the same check with a planted fault handed to it in the
    system's place: the reference computed in float8, the module's loss
    left out, or one leaf's gradient left at zero. Each comes out not
    correct through the harness's own comparison, by one of the
    reference's two limits; the reference in bfloat16 reads under float8
    on both."""
    from torchft_tpu.parallel import train

    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 48)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    ctx = worker.Ctx(cell, 3000000001, 0, False)
    out = worker.reference_check(ctx)
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5
    grad_tol, loss_tol = reference.GRAD_REL_L2_TOL, reference.LOSS_REL_TOL
    assert (out["grad_rel_l2_tol"], out["loss_rel_tol"]) == (grad_tol, loss_tol)
    # A leaf whose gradient never moves reads 1.0: the limit lies under it,
    # and the median leaf's (stated for the harness's owed edit) under that.
    assert reference.GRAD_REL_L2_MEDIAN_TOL < grad_tol < 1.0

    sound = jax.jit(lambda p, b: reference.loss_and_grads(p, b, ctx.config))

    def control(spoil=lambda grads: grads, config=None, **options):
        """The check with the reference (under ``options`` or another
        ``config``, its gradients spoiled) in the system's place."""
        plain = config is None and not options
        grad = sound if plain else jax.jit(
            lambda p, b: reference.loss_and_grads(p, b, config or ctx.config, **options))

        def step(params, batch):
            loss, grads = grad(params, batch)
            return loss, spoil(grads)

        monkeypatch.setattr(train, "make_grad_step", lambda model, mesh, shardings: step)
        return worker.reference_check(ctx)

    no_module = control(config=dict(ctx.config, mtp_loss_coef=0.0))
    assert not no_module["ok"] and no_module["loss_rel_diff"] > 0.1
    fp8, bf16 = control(operand_dtype=jnp.float8_e4m3fn), control(operand_dtype=jnp.bfloat16)
    assert not fp8["ok"] and (
        fp8["grad_rel_l2_worst"] > grad_tol or fp8["loss_rel_diff"] > loss_tol)
    assert fp8["grad_rel_l2_worst"] > bf16["grad_rel_l2_worst"] > 1e-3
    assert bf16["grad_rel_l2_worst"] < grad_tol and bf16["loss_rel_diff"] < fp8["loss_rel_diff"]

    def dead(path):
        def spoil(grads):
            grads = jax.tree_util.tree_map(lambda g: g, grads)
            node = grads
            for key in path[:-1]:
                node = node[key]
            node[path[-1]] = jnp.zeros_like(node[path[-1]])
            return grads
        return spoil

    for path in (("layers_2", "attn", "wkv_b", "kernel"), ("mtp_0", "eh_proj", "kernel")):
        out = control(dead(path))
        assert not out["ok"] and out["loss_rel_diff"] == 0.0
        assert out["grad_rel_l2_worst"] == pytest.approx(1.0)
        assert out["grad_rel_l2_worst_leaf"] == "".join(f"['{k}']" for k in path)


@pytest.mark.timeout(600)
def test_the_cell_runs_end_to_end_on_a_tiny_table(tmp_path):
    """run.py on the CPU, traced: the raw trainer's window, the reference
    check, and the step's counters on the line."""
    table = _tiny_table(
        tmp_path, tiny(), os.path.join(cells.HERE, "tests", "table", "traffic"))
    doc = cells.load_json(table)
    doc["platform"] = "cpu"
    with open(table, "w") as f:
        json.dump(doc, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, whatever the test run gave itself
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--table", table,
         "--workload", "w", "--seed", "3000000001", "--seconds", "2", "--trace", "1"],
        capture_output=True, text=True, env=env, timeout=500,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 3
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert NEW_METRICS - {"mla_proj_ms", "joyai_gmm_roofline"} <= set(got)  # no device trace
    assert 0.15 < got["mtp_loss_share"] < 0.3  # 0.3 / 1.3 while neither head predicts
    assert 0.0 < got["joyai_held_share"] < 1.0 and got["joyai_held_dropped"] == 0.0
    assert "traced=" not in proc.stderr  # a WARNING only where the branch is not the one asked
