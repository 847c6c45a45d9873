"""The ``keye_vl2`` architecture as the benchmark holds it: the reference's
own proofs (its selection against a sort by hand, its positions by
section), the configuration file against the catalog's published keys, the
counts against a hand count and the program's parameter tree, the lookup
by the ``"arch"`` key, the adapter's refusals by name, the sample's
``topk``, the readers of the cell's new metrics."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells

adapter = cells.arch_module("keye_vl2", "adapter")
reference = cells.arch_module("keye_vl2", "reference")
flops = cells.arch_module("keye_vl2", "flops")
CONFIG_FILE = os.path.join(cells.HERE, "configs", "keye-vl2-30b-a3b-l6e16.json")
PUBLISHED = cells.load_json(CONFIG_FILE)
# The catalog row's `config` (model-configs guide, architectures.jsonl,
# Keye-VL-2.0-30B-A3B), key for key.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 18992}
NEW_METRICS = {
    "dsa_index_ms", "dsa_select_ms", "dsa_kept_share", "dsa_tiles_run_share",
    "dsa_index_kl", "dsa_index_roofline", "keye_held_share", "keye_held_dropped", "keye_held_run_share",
    "keye_gmm_roofline", "keye_held_token_run_share", "keye_held_ms",
}


def tiny(**overrides):
    """The published file at widths a CPU test can afford: two layers, 16
    experts over 4 chips, this chip the second, a 2-head indexer of width 8
    that keeps 16 keys a query, sections [2, 3, 3] of a head of 16."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=48, num_hidden_layers=2, num_experts=4,
        expert_parallel_chips=4, expert_parallel_index=1, num_local_experts=16,
        num_experts_per_tok=3, vocab_parallel_chips=1,
        rope_scaling=dict(PUBLISHED["rope_scaling"], mrope_section=[2, 3, 3]),
        sa_config=dict(PUBLISHED["sa_config"], indexer_head_dim=8, indexer_num_heads=2,
                       topk=16),
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def test_the_file_is_the_catalogs_row_but_for_the_three_cuts():
    for key, want in CATALOG.items():
        assert PUBLISHED[key] == REDUCED.get(key, want), key
    assert set(PUBLISHED["reduced"]) == set(REDUCED)
    for key, cut in PUBLISHED["reduced"].items():
        assert cut["published"] == CATALOG[key] and cut["run"] == PUBLISHED[key]
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(c for c in table["configs"] if c["name"] == "keye-vl2-30b-a3b-l6e16")
    assert entry["reduced"] == list(PUBLISHED["reduced"]) and len(entry["source"]) <= 200
    assert set(PUBLISHED) - cells.DOC_KEYS <= set(adapter.KEYS)
    assert PUBLISHED["num_experts"] * PUBLISHED["expert_parallel_chips"] == 128
    assert PUBLISHED["vocab_size"] * PUBLISHED["vocab_parallel_chips"] == 151936


def test_the_cell_is_found_by_its_arch_key_and_reports_the_new_metrics():
    cell = cells.load_cell("keye-raw")
    assert cell.arch_dir.endswith(os.path.join("arch", "keye_vl2")) and cell.chips == 1
    assert cell.mix["seq"] == 16384 and cell.mix["batch"] == 1
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names and {"flash_ms", "flash_roofline", "mfu_pct"} <= names
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(cells.HERE, "metrics", name + ".py")), name


def test_the_counts_at_16k_are_the_published_arithmetic():
    c, s = PUBLISHED, 16384
    # a layer outside its experts: W_q, W_o, W_k, W_v, the indexer, QK norms
    assert flops.attention_params(c) == (
        2 * 8_388_608 + 2 * 1_048_576 + 2_097_152 + 131_072 + 32_768 + 128 + 256)
    assert flops.expert_params(c) == 4_718_592 and flops.router_params(c) == 262_144
    assert flops.total_params(c) == 659_190_016
    assert flops.causal_entries(s) == 134_225_920
    assert flops.selected_entries(c, s) == 2048 * 2049 // 2 + 14336 * 2048 == 31_458_304
    assert flops.selected_entries(c, s) / flops.causal_entries(s) == pytest.approx(0.2344, abs=1e-4)
    assert flops.selected_entries(c, 1024) == flops.causal_entries(1024)
    # the attention over the selected entries, the indexer over all causal ones
    assert flops.flash_flops_per_step(c, 1, s) == 12 * 31_458_304 * 32 * 128 * 6
    assert flops.index_flops_per_step(c, 1, s) == (
        (6 * 16 * 64 + 2 * 32 * 128) * 134_225_920 * 6)
    per_token = flops.model_flops_per_token(c, s)
    matmuls = 6.0 * (flops.trunk_matmul_params(c) + 2048 * 18992)
    assert per_token == pytest.approx(
        matmuls + (flops.flash_flops_per_step(c, 1, s) + flops.index_flops_per_step(c, 1, s)) / s)
    assert flops.held_share(c) == 0.125
    assert flops.gmm_flops_per_step(c, 1, s) == 6 * 4_718_592 * 16384 * 6


def test_the_counts_are_the_programs_parameter_tree():
    from torchft_tpu.parallel.train import build_model

    c = tiny()
    model = build_model(adapter.model_config(c, 32), None)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))["params"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == flops.total_params(c)
    index = shapes["layers_0"]["attn"]["indexer"]
    assert set(index) == {"wq_index", "wk_index", "k_index_norm", "w_index"}
    assert index["wq_index"]["kernel"].shape == (64, 2, 8)
    assert set(index["k_index_norm"]) == {"scale", "bias"}


@pytest.mark.parametrize("change,said", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"model_type": "qwen3_moe"}, "model_type"),
    ({"sa_config": {**PUBLISHED["sa_config"], "q_chunk_size": 256}}, "q_chunk_size"),
    ({"sa_config": {**PUBLISHED["sa_config"], "kv_chunk_size": 1024}}, "kv_chunk_size"),
    ({"sa_config": {**PUBLISHED["sa_config"], "indexer_num_kv_heads": 2}}, "indexer_num_kv_heads"),
    ({"sa_config": {**PUBLISHED["sa_config"], "topk": 0}}, "topk"),
    ({"sa_config": {k: v for k, v in PUBLISHED["sa_config"].items() if k != "topk"}}, "sa_config"),
    ({"rope_scaling": {**PUBLISHED["rope_scaling"], "mrope_section": [16, 24, 16]}}, "mrope_section"),
    ({"rope_scaling": {**PUBLISHED["rope_scaling"], "type": "yarn"}}, "rope_scaling"),
    ({"num_local_experts": 64}, "num_local_experts"),
    ({"expert_parallel_index": 8}, "expert_parallel_index"),
])
def test_the_adapter_refuses_by_name_what_the_program_does_not_compute(change, said):
    with pytest.raises(cells.CellError, match=said):
        adapter.model_config(dict(PUBLISHED, **change), 16384)


def test_the_adapter_refuses_a_sequence_the_kernels_cannot_pack():
    with pytest.raises(cells.CellError, match="cannot pack"):
        adapter.model_config(PUBLISHED, 16384 + 64)
    with pytest.raises(cells.CellError, match="max_position_embeddings"):
        adapter.model_config(PUBLISHED, 262144 * 2)
    unknown = dict(PUBLISHED, window_layers=[1])
    assert sorted(set(unknown) - cells.DOC_KEYS - set(adapter.KEYS)) == ["window_layers"]


def test_the_model_configuration_and_the_samples_topk():
    cfg = adapter.model_config(PUBLISHED, 16384)
    assert (cfg.sparse_topk, cfg.indexer_heads, cfg.indexer_head_dim) == (2048, 16, 64)
    assert cfg.mrope_section == (16, 24, 24) and cfg.rope_theta == 1e7
    assert cfg.num_experts == 128 and cfg.experts_held == (0, 16)
    assert cfg.num_experts_per_tok == 8 and cfg.layer_pattern == "*E" * 6
    sample = adapter.sample_config(cfg, 1024)
    assert sample.sparse_topk == 256 == reference.topk_at(PUBLISHED, 1024)
    assert (sample.flash_block_q, sample.flash_block_k) == (128, 128)
    assert sample.flash_min_seq <= 1024  # the kernels, not the dense fallback
    timed = adapter.sample_config(cfg, 16384)
    assert timed.sparse_topk == 2048 == reference.topk_at(PUBLISHED, 16384)
    assert timed.flash_block_q == cfg.flash_block_q


@pytest.mark.parametrize("first,off_by_one", [(0, False), (8, False), (8, True)])
def test_the_references_selection_is_a_sort_by_hand(first, off_by_one):
    rows, seq, topk = 12, 24, 5
    scores = jax.random.normal(jax.random.PRNGKey(3), (2, rows, seq))
    # ties: the lower index first (+ 0.0: lax.top_k puts -0.0 under 0.0)
    scores = jnp.round(scores * 2) / 2 + 0.0
    kept = np.asarray(reference.selection(scores, first, topk, off_by_one))
    for b in range(2):
        for r in range(rows):
            t = first + r
            order = sorted(range(t + 1), key=lambda s: (-float(scores[b, r, s]), s))
            want = set(order[:topk])
            if off_by_one and t + 1 > topk:
                want = set(order[:topk - 1]) | {order[topk]}
            assert set(np.flatnonzero(kept[b, r])) == want, (b, r)


def test_the_references_angles_take_each_pairs_id_by_section():
    c = tiny()
    pos = jnp.stack([jnp.arange(6), 10 + jnp.arange(6), 20 + jnp.arange(6)])[:, None]
    angle, angle_i = reference.angles(pos, c)
    inv = 1.0 / 1e7 ** (np.arange(0, 16, 2) / 16)
    want = np.concatenate(
        [np.arange(6)[:, None] * inv[:2], (10 + np.arange(6))[:, None] * inv[2:5],
         (20 + np.arange(6))[:, None] * inv[5:]], axis=1)
    assert np.allclose(angle[0], want, rtol=1e-6)
    assert np.allclose(angle_i[0], np.arange(6)[:, None] / 1e7 ** (np.arange(0, 8, 2) / 8), rtol=1e-6)
    plain, _ = reference.angles(reference.positions({"inputs": jnp.zeros((1, 6), jnp.int32)}), c)
    assert np.allclose(plain[0], np.arange(6)[:, None] * inv, rtol=1e-6)


def test_the_new_readers_read_the_steps_counters_and_nothing_elsewhere():
    from benchmark import worker

    cell = cells.load_cell("keye-raw")
    readers = worker.load_metric_readers(cell, "")
    records = [
        {"spans": {}, "traced": True, "counters": {
            "dsa_kept_share": 0.2344, "dsa_tiles_run_share": 1.0, "dsa_index_kl": 0.5 + i,
            "moe_held_share": 0.125, "moe_dropped": 0.0, "moe_held_run_share": 0.26,
            "moe_held_token_run_share": 0.6875}}
        for i in range(3)
    ]
    run = {"cell": cell, "records": records, "trace": None, "traced_steps": 0}
    assert readers["dsa_kept_share"](run) == 0.2344
    assert readers["dsa_tiles_run_share"](run) == 1.0
    assert readers["dsa_index_kl"](run) == 1.5
    assert readers["keye_held_share"](run) == 0.125
    assert readers["keye_held_dropped"](run) == 0.0
    assert readers["keye_held_run_share"](run) == 0.26
    assert readers["keye_held_token_run_share"](run) == 0.6875
    assert readers["keye_held_ms"](run) is None
    assert readers["dsa_index_ms"](run) is None and readers["dsa_select_ms"](run) is None
    # a program that counts no such thing (the parent): nothing, and no error
    bare = {"cell": cell, "records": [{"spans": {}, "counters": {}}], "trace": None,
            "traced_steps": 0}
    for name in NEW_METRICS:
        assert readers[name](bare) is None, name


def test_the_trace_patterns_name_the_passes_and_keep_them_apart():
    import re

    from benchmark.metrics import dsa_index_ms, dsa_select_ms, flash_ms, gated_held_ms

    cell = cells.load_cell("keye-raw")
    d = dsa_index_ms.dims({"cell": cell})
    index = "|".join(f"(?:{p})" for p in dsa_index_ms.patterns(d))
    select = "|".join(f"(?:{p})" for p in dsa_select_ms.patterns(d))
    for name in ("fusion.12 f32[512,16,16384]{2,0,1}", "fusion.3 (f32[1,8,512,16384]{3,2,1,0}, f32[1",
                 "dsa_index_scores.4 f32[1,16384,16384]", "dsa_index_kl.7 bf16[1,16384,16384]",
                 "dsa_index_scores_bwd.2 (f32[1,16,16384,64]", "and_convert_fusion.1 pred[512,16,16384]"):
        assert re.search(index, name) and not re.search(select, name), name
    for name in ("convert_reduce_fusion.7 s32[512]{0}", "fusion.9 (pred[512,16384]{1,0}, s32[512]{0})",
                 "fusion.2960 f32[512,16384]{1,0:T(8,128)}", "copy.4 f32[1,512,16384]{2,1,0}",
                 "dsa_select.2 s32[1,16384,512]", "fusion.8 s32[1,512,512]{2,1,0}"):
        assert re.search(select, name) and not re.search(index, name), name
    for name in ("fusion.2600 f32[512]{0:T(512)}", "or_xor_fusion.12 s32[512]{0:T(512)S(1)}",
                 "log_add_fusion.14 f32[512]{0:T(512)}"):
        assert re.search(select, name), name
    # the held dispatch's row tiles are 512 rows too: its vectors, as the
    # compiled step names them, are not the selection's
    for name in ("flash_attention_selected.3 bf16[1,32,16384,128]", "fusion.1 f32[16384,2048]",
                 "ragged-dot-none.2 bf16[65536,768]", "slice_reduce_fusion.284 pred[512]{0:T(512)}",
                 "slice_reduce_fusion.283 u32[512]{0:T(512)}", "slice_reduce_fusion.282 f32[512]{0:T(512)}",
                 "multiply_reduce_fusion.95 f32[512]{0:T(512)}",
                 "dynamic-slice_convert_fusion.36 s32[512]{0:T(512)}"):
        assert not re.search(index, name) and not re.search(select, name), name
    held = "|".join(f"(?:{p})" for p in gated_held_ms.patterns({"cell": cell}))
    for name in ("ragged-dot-none.2 bf16[65536,768]", "fusion.7 f32[131072]{0:T(1024)S(1)}",
                 "convert_element_type.3 bf16[16,2048,768]", "sort.4 (f32[1,16384,128]{1,2,0}, s32[1"):
        assert re.search(held, name) and not re.search(select, name), name
    assert not re.search(held, "convert_reduce_fusion.7 s32[512]{0}")
    assert re.search(flash_ms.PATTERN, "flash_attention_selected.3 bf16[1,32,16384,128]")
