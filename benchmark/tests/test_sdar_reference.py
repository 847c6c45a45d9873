"""The ``sdar_moe`` architecture as the benchmark holds it: the reference's
own proofs (its mask against the four conditions checked entry by entry
and counted, its noise against the rule drawn by hand), the configuration
file against the catalog's published keys, the counts against a hand count
and the program's parameter tree, the lookup by the ``"arch"`` key, the
adapter's refusals, the readers of the cell's new metrics, the harness's
own check at a small size and the cell end to end through ``run.py`` on a
tiny table."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, worker

adapter = cells.arch_module("sdar_moe", "adapter")
reference = cells.arch_module("sdar_moe", "reference")
flops = cells.arch_module("sdar_moe", "flops")
CONFIG_FILE = os.path.join(cells.HERE, "configs", "sdar-30b-a3b-l6e16.json")
PUBLISHED = cells.load_json(CONFIG_FILE)
# The catalog row's `config` (model-configs guide, architectures.jsonl,
# SDAR-30B-A3B-Chat), key for key.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
NEW_METRICS = {"bd_kept_share", "diffusion_masked_share", "sdar_held_ms",
               "sdar_held_share", "sdar_gmm_roofline"}


def tiny(**overrides):
    """The published file at widths a CPU test can afford: two layers, 16
    experts over 4 chips, this chip the second, the mask token the
    vocabulary's last row."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=48, num_hidden_layers=2, num_experts=4,
        expert_parallel_chips=4, expert_parallel_index=1, num_experts_per_tok=3,
        mask_token_id=255, vocab_parallel_chips=1,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


@pytest.mark.parametrize("length,b", [(16, 4), (64, 32), (24, 1), (12, 12)])
def test_the_mask_is_the_four_conditions_and_flops_counts_its_entries(length, b):
    see = reference.visible(length, b)
    assert see.shape == (2 * length, 2 * length)
    for q in range(2 * length):
        for k in range(2 * length):
            q_noisy, k_noisy = q < length, k < length
            qb, kb = (q % length) // b, (k % length) // b
            want = (
                (q_noisy and k_noisy and kb == qb)
                or (q_noisy and not k_noisy and kb < qb)
                or (not q_noisy and not k_noisy and kb <= qb)
            )
            assert bool(see[q, k]) == want, (q, k)
    # flops.py's kept entries are a count of this very mask, and its
    # attention work is theirs: 4 FLOP an entry and head width forward
    # (QK^T and PV), twice that backward.
    c = dict(PUBLISHED, block_length=b)
    assert int(see.sum()) == flops.kept_entries(c, length) == length * length + length * b
    assert flops.flash_flops_per_step(c, 3, length) == (
        12 * int(see.sum()) * 3 * 32 * 128 * c["num_hidden_layers"])


def test_the_noise_is_the_rule_drawn_by_hand():
    c = tiny(block_length=4, diffusion_t_min=0.25, diffusion_t_max=0.75)
    x0 = jax.random.randint(jax.random.PRNGKey(2), (3, 32), 0, 255)
    batch = {"inputs": x0}
    x_t, masked, t = reference.noise(batch, c)
    key = jax.random.fold_in(jax.random.PRNGKey(0), jnp.sum(x0.astype(jnp.uint32)))
    k_t, k_u = jax.random.split(key)
    t_block = jax.random.uniform(k_t, (3, 8), jnp.float32, 0.25, 0.75)
    u = jax.random.uniform(k_u, (3, 32), jnp.float32)
    assert jnp.array_equal(t, jnp.repeat(t_block, 4, axis=1))
    assert float(t.min()) >= 0.25 and float(t.max()) < 0.75
    assert jnp.array_equal(masked, u < t) and 0 < int(masked.sum()) < 96
    assert jnp.array_equal(x_t, jnp.where(masked, 255, x0))
    # the same batch draws the same noise, another batch other noise
    again = reference.noise({"inputs": x0}, c)
    assert jnp.array_equal(again[1], masked) and jnp.array_equal(again[2], t)
    other = reference.noise({"inputs": x0.at[0, 0].add(1)}, c)
    assert not jnp.array_equal(other[2], t)


def test_every_published_key_is_in_the_file_unchanged_but_the_three_reduced():
    c = PUBLISHED
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in CATALOG.items():
        if key in c["reduced"]:
            cut = c["reduced"][key]
            assert cut["published"] == value and cut["run"] == c[key] != value and cut["why"]
        else:
            assert c[key] == value and type(c[key]) is type(value), key
    assert c["num_experts"] * c["expert_parallel_chips"] == CATALOG["num_experts"]
    assert c["vocab_size"] * c["vocab_parallel_chips"] == CATALOG["vocab_size"]
    assert c["mask_token_id"] == c["vocab_size"] - 1
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    # every key the published file does not have is stated as assumed
    own = set(adapter.KEYS) - set(CATALOG)
    stated = " ".join(c["assumed"])
    assert own and all(key in stated for key in own), own
    assert "8 chips" in c["stands_for"] and c["distortions"]
    entry = next(e for e in cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["configs"]
                 if e["name"] == "sdar-30b-a3b-l6e16")
    assert entry["reduced"] == list(c["reduced"]) and len(entry["source"]) <= 200
    # The table's source is the catalog's source_url letter for letter (the
    # driver's rule for a catalogued model); the papers are in the file's own.
    assert entry["source"] == c["source"].split(";")[0] and "2510.06303" in c["source"]


def test_the_cell_is_found_by_its_arch_key_with_its_metrics():
    cell = cells.load_cell("sdar-raw")
    assert cell.arch_dir == os.path.join(cells.HERE, "arch", "sdar_moe")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"], cell.mix["trainer"]) == (
        1, 2, 8192, "raw")
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS | {"mfu_pct", "flash_ms", "flash_roofline", "host_other_ms",
                          "hbm_reserved_gib"} <= names
    assert not names & {"step_ms", "head_loss_ms", "gated_held_ms", "moe_ms", "ssm_ms"}
    worker.load_metric_readers(cell, "")
    for m in cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert (m["moves"], m["workloads"]) == ("tok_s_chip", ["sdar-raw"])
            assert os.path.isfile(os.path.join(cells.HERE, "metrics", m["name"] + ".py"))
    cfg = cell.adapter.model_config(cell.config, 8192)
    assert (cfg.objective, cfg.block_length, cfg.mask_token_id, cfg.diffusion_t_min,
            cfg.diffusion_t_max, cfg.experts_held, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.router_score, cfg.qk_norm, cfg.tie_embeddings,
            cfg.head_dim, cfg.intermediate_size, cfg.num_layers, cfg.layer_pattern,
            cfg.router_aux_coef, cfg.norm_eps, cfg.rope_theta) == (
        "block_diffusion", 4, 18991, 0.45, 0.95, (0, 16), 128, 8, True, "softmax", "head",
        False, 128, 768, 6, "*E*E*E*E*E*E", 1e-3, 1e-6, 1e6)
    # the check's sample of 1,024 tokens is 2,048 rows: the kernels' branch
    from torchft_tpu.models.llama import block_diffusion_attention

    sample = cell.adapter.sample_config(cfg, 1024)
    assert block_diffusion_attention(sample, 2048)[0] == "flash"
    assert block_diffusion_attention(cfg, 16384) == ("flash", pytest.approx(
        (8192 * 8192 + 8192 * 4) / (288 * 512 * 512)))


def _fake_run(cell, ops, records):
    from benchmark import trace_reduce

    return {"cell": cell, "trace": trace_reduce.Trace((0.0, 1.0), 1, 0.9, ops, [], {}),
            "traced_steps": 2, "records": records, "device_kind": "TPU v5 lite",
            "peaks": cells.load_json(os.path.join(cells.HERE, "peaks.json"))}


def test_the_new_metrics_read_the_steps_counters_and_the_layers_shapes():
    """The two counters, and the held layer's three as ``lfm2-raw``'s read
    them, at the shapes of 2 x seq rows a sequence."""
    from benchmark.metrics import (
        bd_kept_share, diffusion_masked_share, sdar_gmm_roofline, sdar_held_ms,
        sdar_held_share,
    )

    cell = cells.load_cell("sdar-raw")
    records = [
        {"traced": True, "counters": {"moe_held_share": 0.10, "bd_kept_share": 0.889,
                                      "diffusion_masked_share": 0.49}},
        {"traced": True, "counters": {"moe_held_share": 0.14, "bd_kept_share": 0.889,
                                      "diffusion_masked_share": 0.51}},
        {"traced": False, "counters": {"moe_held_share": 0.0, "bd_kept_share": 0.889,
                                       "diffusion_masked_share": 0.53}},
    ]
    ops = {
        "ragged-dot-none.3 bf16[131072,768]{1,0:T(8,128)(2,1)} cust": 0.12,
        "ragged-dot-metadata.1 s32[16]": 0.04,
        "fusion.9 bf16[131072,2048]{1,0}": 0.02,       # the buffer's rows: 4 x 32,768 x 8 / 8
        "sort.2 (s32[262144]{0}, s32[262144]{0})": 0.01,  # T*K of both streams' rows
        "convert.5 bf16[16,2048,768]{2,1,0}": 0.005,
        "sort.1 (f32[2,16384,128]{2,1,0}, s32[2,16384,128])": 0.03,  # the router's top-k
        "fusion.7 f32[2,16384,2048]": 0.5,              # the rest of the block: not named
        "fusion.8 bf16[65536,2048]{1,0}": 0.7,          # lfm2's buffer, not this cell's
    }
    run = _fake_run(cell, ops, records)
    assert bd_kept_share.read(run) == 0.889
    assert diffusion_masked_share.read(run) == 0.51
    assert sdar_held_share.read(run) == 0.10
    assert sdar_held_ms.read(run) == pytest.approx((0.16 + 0.02 + 0.01 + 0.005 + 0.03) * 1e3 / 2)
    least_ms = flops.gmm_flops_per_step(cell.config, 2, 8192, 0.12) / 197e12 * 1e3
    assert sdar_gmm_roofline.read(run) == pytest.approx(100 * least_ms / 80.0)
    assert flops.gmm_flops_per_step(cell.config, 2, 8192, 0.125) == (
        3 * 2 * 3 * 2048 * 768 * 32768 * 6)
    bare = {**run, "records": [{"traced": True, "counters": {}}]}
    assert bd_kept_share.read(bare) is None and diffusion_masked_share.read(bare) is None
    assert sdar_gmm_roofline.read(bare) is None and sdar_held_share.read(bare) is None
    assert sdar_held_ms.read({**run, "trace": None}) is None
    assert sdar_held_ms.read({**run, "cell": cells.load_cell("lfm2-raw")}) is None


def test_the_counts_are_the_hand_count_and_the_parameter_trees():
    """ISSUE 43's arithmetic: the published model, then the cut file."""
    whole = dict(CATALOG, expert_parallel_chips=1, block_length=4)
    assert flops.attention_params(whole) == 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 256
    assert flops.expert_params(whole) == 3 * 2048 * 768 == 4_718_592
    assert flops.total_params(whole) == (
        48 * (18_874_624 + 262_144 + 128 * 4_718_592 + 4096) + 2048 + 2 * 311_164_928)
    assert round(flops.total_params(whole) / 1e9, 1) == 30.5
    assert round(flops.active_params(whole) / 1e9, 2) == 3.35
    c = PUBLISHED
    layer = 18_874_624 + 262_144 + 16 * 4_718_592 + 4096
    assert flops.total_params(c) == 6 * layer + 2048 + 2 * 38_895_616 == 645_623_296
    from torchft_tpu.parallel.train import build_model

    model = build_model(adapter.model_config(c, 256), None)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32))
    )["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 645_623_296
    # A data token: two rows through the trunk (attention's four, the
    # router, 8 x 16/128 of an expert), one through the head, its share of
    # the kept entries forward and twice backward.
    row = 6 * (18_874_368 + 262_144 + 4_718_592)
    attention = 12 * (8192 + 4) * 32 * 128 * 6
    assert flops.model_flops_per_token(c, 8192) == pytest.approx(
        6 * (2 * row + 38_895_616) + attention)
    assert flops.flash_flops_per_step(c, 2, 8192) == pytest.approx(attention * 16384)
    assert 39.5e12 < flops.flash_flops_per_step(c, 2, 8192) < 39.7e12
    # q, o (32 heads) and k, v (4) of 2 x 16,384 rows, six passes of bf16
    assert flops.flash_bytes_per_step(c, 2, 8192) == 6 * 6 * 32768 * (32 + 4) * 128 * 2
    assert (flops.flash_flops_per_step(c, 2, 8192) / 197e12
            > 10 * flops.flash_bytes_per_step(c, 2, 8192) / 819e9)  # compute-bound
    assert (flops.gmm_flops_per_step(c, 2, 8192) / 197e12
            > flops.gmm_bytes_per_step(c, 2, 8192) / 819e9)


@pytest.mark.parametrize("key,value", [
    ("model_type", "qwen3_moe"), ("attention_bias", True), ("hidden_act", "gelu"),
    ("norm_topk_prob", False), ("tie_word_embeddings", True), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("rope_scaling", {"factor": 2.0}), ("sliding_window", 4096),
    ("use_sliding_window", True), ("intermediate_size", 4096), ("max_window_layers", 24),
    ("expert_parallel_index", 8), ("num_experts_per_tok", 129), ("num_key_value_heads", 5),
    ("block_length", 3), ("block_length", 0), ("mask_token_id", 18992),
    ("mask_token_id", -1), ("diffusion_t_min", 1.0), ("vocab_parallel_chips", 0),
    ("diffusion_t_max", 1.5), ("diffusion_t_max", 0.45),
])
def test_the_adapter_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(cells.CellError, match=key):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 8192)


def test_the_adapter_refuses_a_file_of_another_architecture_and_a_long_sequence():
    lfm2 = cells.load_cell("lfm2-raw").config
    with pytest.raises(cells.CellError, match="lacks"):
        adapter.model_config(dict(lfm2), 8192)
    with pytest.raises(cells.CellError, match="max_position_embeddings"):
        adapter.model_config(dict(PUBLISHED), 32772)


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
    assert cell.arch_dir.endswith(os.path.join("arch", "sdar_moe"))
    with pytest.raises(cells.CellError, match="num_shared_experts"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(num_shared_experts=1)))


def test_the_harness_check_passes_and_float8_a_dropped_weight_and_a_dead_leaf_fail(
    tmp_path, monkeypatch
):
    """worker.reference_check as the chip run makes it (a plain
    inputs/targets/mask sample, parameters from ``init`` on one stream),
    at a small size in float32; then the same check with a planted fault
    handed to it in the system's place: the reference computed in float8,
    or without the 1/t, or with one leaf's gradient left at zero. Each
    comes out not correct through the harness's own comparison, by one of
    the reference's two limits; the reference in bfloat16 reads under
    float8 on both (at 64 wide its loss is over the limit sized at 2,048
    wide, so ``ok`` is not asked of it here)."""
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
    # A leaf whose gradient never moves reads 1.0: the limit lies under it.
    assert grad_tol < 1.0

    def control(spoil=lambda grads: grads, **options):
        def step(params, batch):
            loss, grads = reference.loss_and_grads(params, batch, ctx.config, **options)
            return loss, spoil(grads)

        monkeypatch.setattr(
            train, "make_grad_step", lambda model, mesh, shardings: jax.jit(step))
        return worker.reference_check(ctx)

    unweighted = control(weigh_by_t=False)
    assert not unweighted["ok"] and unweighted["loss_rel_diff"] > loss_tol
    fp8, bf16 = control(operand_dtype=jnp.float8_e4m3fn), control(operand_dtype=jnp.bfloat16)
    # (at this size by the loss; at the published widths on the chip by
    # the gradients: reference.py has the readings)
    assert not fp8["ok"] and (
        fp8["grad_rel_l2_worst"] > grad_tol or fp8["loss_rel_diff"] > loss_tol)
    assert fp8["grad_rel_l2_worst"] > bf16["grad_rel_l2_worst"] > 1e-3
    assert bf16["grad_rel_l2_worst"] < grad_tol and bf16["loss_rel_diff"] < fp8["loss_rel_diff"]

    def dead_router(grads):
        grads = jax.tree_util.tree_map(lambda g: g, grads)
        router = grads["layers_1"]["mlp"]["router"]
        router["kernel"] = jnp.zeros_like(router["kernel"])
        return grads

    dead = control(dead_router)
    assert not dead["ok"] and dead["loss_rel_diff"] == 0.0
    assert dead["grad_rel_l2_worst"] == pytest.approx(1.0)
    assert dead["grad_rel_l2_worst_leaf"] == "['layers_1']['mlp']['router']['kernel']"


@pytest.mark.timeout(600)
def test_the_cell_runs_end_to_end_on_a_tiny_table(tmp_path):
    """run.py on the CPU, traced: the raw trainer's window, the reference
    check, and the two counters of the step on the line."""
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
    assert NEW_METRICS - {"sdar_held_ms", "sdar_gmm_roofline"} <= set(got)  # no device trace
    # dense on the CPU's 128 rows: the whole square is computed
    assert got["bd_kept_share"] == pytest.approx((64 * 64 + 64 * 4) / (128 * 128))
    assert 0.55 < got["diffusion_masked_share"] < 0.9
    assert 0.0 < got["sdar_held_share"] < 1.0
    assert "traced=" not in proc.stderr  # a WARNING only where the branch is not the one asked
