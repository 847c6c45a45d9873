"""Tier-1's view of the benchmark's own tests (the driver's command
collects ``tests/`` only): every chip-free, subprocess-free test of
``benchmark/tests/`` is collected here under its own name, no body
copied; and the table tests of the architecture this repo's
``olmoe-1b-7b-l1`` configuration brought.

Left to ``python -m pytest benchmark/tests``: ``test_v5e_compile.py``
(it loads the TPU compiler, which one process of a test run may do) and
the runs of ``run.py`` as a subprocess in ``test_run_end_to_end.py``.
"""

import importlib
import json
import os

import jax
import pytest

from benchmark import cells
from benchmark import conftest as _outgrown

MODULES = (
    "test_ar_pack_fresh_bytes", "test_arch", "test_bucket_line_metrics", "test_flops",
    "test_liveness_metrics",
    "test_reference", "test_run_end_to_end", "test_solar_reference", "test_span_metrics",
    "test_trace_reduce", "test_wait_metrics", "test_wire_fresh_bytes",
    "test_wire_schedule_metrics",
)
SUBPROCESS_RUNS = {
    "test_tiny_raw_cell_end_to_end_metrics",
    "test_tiny_two_group_cell_traced_per_layer_metrics",
    "test_a_cell_of_the_repos_table_needs_a_tpu",
}
_TESTS = [importlib.import_module(f"benchmark.tests.{m}") for m in MODULES]
# Four tests assert that the table is what it was when they were written
# (benchmark/conftest.py says which two, test_ar_pack_fresh_bytes.py and
# test_wire_schedule_metrics.py file the other two there as they are
# imported, each with the one-line edit a benchmark PR owes it); they are
# restated below for the table as it is.
OUTGROWN = set(_outgrown.OUTGROWN)
# A fifth says that the table ENDS with the entries its PR added (true of
# PR 56, whose file benchmark/tests keeps as written); the table grows at
# the ends of its lists, so it is restated below for the table as it is.
OUTGROWN.add("test_wait_the_listed_follow_everything_the_table_had")


def _is_fixture(obj):
    return type(obj).__name__ == "FixtureFunctionDefinition" or hasattr(
        obj, "_pytestfixturefunction"
    )


for _mod in _TESTS:
    for _name, _obj in vars(_mod).items():
        if _is_fixture(_obj):
            globals()[_name] = _obj
        elif _name.startswith("test_") and callable(_obj):
            if _name not in SUBPROCESS_RUNS | OUTGROWN:
                assert _name not in globals(), _name
                globals()[_name] = _obj


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    """The benchmark's tests run on one CPU device (their conftest.py);
    tier-1's conftest gives eight, and the worker takes all it sees."""
    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)


REPO_TABLE = os.path.join(cells.ROOT, "BENCHMARK.json")
DENSE_CELLS = ("mistral-raw", "mistral-ft1", "mistral-ft4", "internlm2-raw")


def test_the_default_architecture_is_found_for_the_files_that_name_none():
    for name in DENSE_CELLS:
        cell = cells.load_cell(name)
        assert "arch" not in cell.config
        assert cell.arch_dir == os.path.join(cells.HERE, "arch", "dense_decoder")
        assert cell.reference.GRAD_REL_L2_TOL == 0.04


def test_wire_fresh_bytes_step_is_an_entry_for_the_four_chip_cell_only():
    entries = {m["name"]: m for m in cells.load_json(REPO_TABLE)["per_layer"]}
    assert entries["wire_fresh_bytes_step"]["workloads"] == ["mistral-ft4"]
    assert entries["wire_fresh_bytes_step"]["source"] == "program_counter"


def test_every_span_metric_is_an_entry_of_the_table_for_its_path_only():
    from benchmark.tests import test_span_metrics

    new = "ar_pack_fresh_bytes_step"
    schedule = {"wire_start_ms", "wire_starved_ms"}  # the wire's, so ft4's alone
    line = {"ar_push_ms", "ar_early_bucket_share"}  # the fp32 line's, so ft1's alone
    # what the wire's sockets waited for: pg_collective's account, ft4's alone
    waits = {"wire_send_ms", "wire_peer_wait_ms", "wire_recv_ms", "wire_sock_cpu_ms",
             "wire_xfer_bytes_step"}
    ten = {m.__name__.rsplit(".", 1)[1] for m in test_span_metrics.ALL}
    entries = {m["name"]: m for m in cells.load_json(REPO_TABLE)["per_layer"]}
    for name in ten | {new} | schedule | line | waits:
        assert entries[name]["layer"] == "replica-axis allreduce"
        assert entries[name]["moves"] == "tok_s_chip"
    ft1 = {m["name"] for m in cells.load_cell("mistral-ft1").per_layer}
    ft4 = {m["name"] for m in cells.load_cell("mistral-ft4").per_layer}
    assert {n for n in ft1 | ft4 if n.startswith(("ar_", "wire_"))} - {
        "wire_ms", "wire_bytes_step", "wire_fresh_bytes_step", new
    } - schedule - line - waits == ten
    assert "ar_pull_ms" not in ft4 and "wire_busy_ms" not in ft1
    assert new in ft1 - ft4
    assert schedule | waits <= ft4 - ft1
    assert line <= ft1 - ft4
    for cell in ("mistral-raw", "internlm2-raw", "olmoe-raw"):
        raw = {m["name"] for m in cells.load_cell(cell).per_layer}
        assert not (ten | {new} | schedule | line | waits) & raw


def test_the_five_liveness_metrics_are_entries_for_the_ft_cells_only():
    """``test_liveness_metrics.py``'s table test, with the five looked up
    by name: entries added since follow them in the list."""
    from benchmark.tests import test_liveness_metrics

    names = list(test_liveness_metrics.NAMES)
    table = cells.load_json(REPO_TABLE)["per_layer"]
    assert [m["name"] for m in table if m["name"] in names] == names
    for e in (m for m in table if m["name"] in names):
        assert (e["source"], e["layer"], e["moves"], e["better"], e["workloads"]) == (
            "program_counter", "control plane", "tok_s_chip", "lower",
            ["mistral-ft1", "mistral-ft4"])
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert os.path.isfile(os.path.join(cells.HERE, "metrics", e["name"] + ".py"))
    for cell in ("mistral-ft1", "mistral-ft4"):
        assert set(names) <= {m["name"] for m in cells.load_cell(cell).per_layer}
    for cell in ("mistral-raw", "internlm2-raw", "olmoe-raw"):
        assert not set(names) & {m["name"] for m in cells.load_cell(cell).per_layer}


def test_the_wait_metrics_follow_everything_the_table_had_when_they_were_listed():
    """``test_wait_metrics.py``'s table test, without its claim to the
    table's end: the seven stand together, in their order, after every
    entry PR 56 found; entries added since follow them in the list."""
    from benchmark.tests import test_wait_metrics

    listed = list(test_wait_metrics.LISTED)
    names = [m["name"] for m in cells.load_json(REPO_TABLE)["per_layer"]]
    at = names.index(listed[0])
    assert names[at : at + len(listed)] == listed
    assert at > names.index("gdn_state_abs_max")


# -- the architecture `olmoe` and its configuration ----------------------


def test_the_olmoe_cell_loads_with_its_own_architecture():
    cell = cells.load_cell("olmoe-raw")
    assert cell.arch_dir == os.path.join(cells.HERE, "arch", "olmoe")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"], cell.mix["trainer"]) == (
        1, 4, 4096, "raw")
    assert set(cell.config["reduced"]) == {"num_hidden_layers"}
    assert {"assumed", "stands_for", "distortions"} <= set(cell.config)
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_ms", "moe_gmm_ms", "moe_gmm_roofline", "moe_dispatch_ms",
            "mfu_pct", "flash_ms", "flash_roofline", "host_other_ms"} <= names
    assert "step_ms" not in names and "grad_ms" not in names
    for m in cells.load_json(REPO_TABLE)["per_layer"]:
        if m["name"].startswith("moe_"):
            assert (m["layer"], m["moves"], m["source"], m["workloads"]) == (
                "expert layer", "tok_s_chip", "device_trace", ["olmoe-raw"])


def test_every_published_key_is_in_the_file_unchanged():
    """The catalog's `config` of OLMoE-1B-7B-0125-Instruct, key for key;
    the depth is the one cut."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
    }
    held = cells.load_cell("olmoe-raw").config
    differs = {k for k, v in published.items() if held.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(held["reduced"])
    assert held["reduced"]["num_hidden_layers"]["published"] == 16


def test_olmoe_counts_match_hand_worked():
    cell = cells.load_cell("olmoe-raw")
    c, f = cell.config, cell.flops
    attention = 4 * 2048 * 2048  # wq, wk, wv, wo: 16 heads x 128 = 2048
    router = 2048 * 64
    expert = 3 * 2048 * 1024  # gate, up, down
    head = 2048 * 50304
    norms = 4 * 2048 + 2048  # attention, MLP, query, key; final
    assert (attention, router, 64 * expert, head) == (
        16_777_216, 131_072, 402_653_184, 103_022_592)
    assert f.total_params(c) == attention + router + 64 * expert + 2 * head + norms
    assert f.total_params(c) == 625_616_896
    assert f.active_matmul_params(c) == attention + router + 8 * expert + head == 170_262_528
    causal = 6 * 4096 * 2048  # 6 * seq * (heads * head_dim), one layer
    assert f.model_flops_per_token(c, 4096) == 6 * 170_262_528 + causal == 1_071_906_816
    rows = 4 * 4096 * 8  # assignments a step
    # three matmuls, a multiply-add a weight, forward and two backward products
    assert f.gmm_flops_per_step(c, 4, 4096) == 3 * 2 * rows * 2048 * 1024 * 3
    assert f.gmm_flops_per_step(c, 4, 4096) / 197e12 == pytest.approx(25.1e-3, rel=2e-3)
    assert f.gmm_bytes_per_step(c, 4, 4096) == 9 * 2 * (rows * 3072 + 64 * 2048 * 1024)
    assert (f.gmm_bytes_per_step(c, 4, 4096) / 819e9
            < f.gmm_flops_per_step(c, 4, 4096) / 197e12)  # compute-bound
    assert f.flash_flops_per_step(c, 4, 4096) == causal * 4 * 4096
    # at the published depth the counts are the model's: 6.9B, 1.3B active
    full = dict(c, num_hidden_layers=16)
    assert f.total_params(full) == 6_919_161_856
    assert f.active_matmul_params(full) + head == 1_281_884_160  # with the table


def test_the_programs_parameter_tree_has_as_many_values_as_the_count():
    import jax.numpy as jnp

    from torchft_tpu.models.llama import Transformer

    cell = cells.load_cell("olmoe-raw")
    model = Transformer(cell.adapter.model_config(cell.config, 4096))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == (
        cell.flops.total_params(cell.config))


def _table_with(tmp_path, config):
    table = cells.load_json(REPO_TABLE)
    for c in table["configs"]:
        c["file"] = os.path.join(cells.ROOT, c["file"])
    table["traffic_dir"] = os.path.join(cells.HERE, "traffic")
    olmoe = next(c for c in table["configs"] if c["name"] == "olmoe-1b-7b-l1")
    olmoe["file"] = str(tmp_path / "config.json")
    (tmp_path / "config.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(table))
    return str(tmp_path / "BENCHMARK.json")


def test_each_architecture_refuses_the_others_file_by_name(tmp_path):
    olmoe = cells.load_cell("olmoe-raw").config
    dense = cells.load_cell("mistral-raw").config
    # this file under dense_decoder: keys that adapter does not read
    as_dense = {k: v for k, v in olmoe.items() if k != "arch"}
    with pytest.raises(cells.CellError, match="num_experts"):
        cells.load_cell("olmoe-raw", _table_with(tmp_path, as_dense))
    # a dense key set under this adapter: what it lacks, by name
    cell = cells.load_cell("olmoe-raw", _table_with(tmp_path, dict(dense, arch="olmoe")))
    with pytest.raises(cells.CellError, match="norm_topk_prob"):
        cell.adapter.model_config(cell.config, 4096)


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", True), ("clip_qkv", 8.0), ("attention_bias", True),
    ("rope_scaling", {"type": "linear", "factor": 2.0}), ("model_type", "mixtral"),
    ("num_experts_per_tok", 65), ("hidden_act", "gelu"),
])
def test_the_olmoe_adapter_refuses_what_the_program_does_not_compute(key, value):
    cell = cells.load_cell("olmoe-raw")
    with pytest.raises(cells.CellError):
        cell.adapter.model_config(dict(cell.config, **{key: value}), 4096)
    with pytest.raises(cells.CellError):
        cell.adapter.model_config(cell.config, 8192)  # beyond the context


def test_moe_metrics_read_none_without_a_trace_and_on_a_dense_cell():
    from benchmark.metrics import moe_dispatch_ms, moe_gmm_ms, moe_gmm_roofline, moe_ms
    from benchmark import trace_reduce

    readers = (moe_ms, moe_gmm_ms, moe_gmm_roofline, moe_dispatch_ms)
    olmoe, dense = cells.load_cell("olmoe-raw"), cells.load_cell("mistral-raw")
    base = {"device_kind": "test chip", "traced_steps": 0, "trace": None,
            "peaks": {"test chip": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}}
    for cell in (olmoe, dense):
        assert [r.read(dict(base, cell=cell)) for r in readers] == [None] * 4

    def traced(ops):
        return trace_reduce.Trace((0.0, 1.0), 1, 0.9, ops, [], {})

    # a trace without the kernels (the parent's program): nothing, no error
    other = traced({"fusion.1 f32[4,128,50304]{2,1,0} fusion(": 0.4})
    assert [r.read(dict(base, cell=olmoe, trace=other, traced_steps=4))
            for r in readers] == [None] * 4
    # names as the v5e's compiler gives them: two steps of 60 ms of matmuls
    ops = {
        "ragged-dot-none.7 bf16[131072,1024]{1,0:T(8,128)(2,1)} cust": 0.080,
        "ragged-dot-none.2 bf16[64,1024,2048]{2,1,0:T(8,128)(2,1)} c": 0.039,
        "ragged-dot-metadata (s32[65]{0:T(128)}, s32[319]{0:T(512)}": 0.001,
        "sort.42 (s32[131072]{0:T(1024)}, s32[131072]{0:T(1024)S(1)})": 0.004,
        "sort.38 (f32[4,4096,64]{1,2,0:T(8,128)S(1)}, s32[4,4096,64]": 0.002,
        "sort.58 (s32[16384]{0:T(1024)}, s32[16384]{0:T(1024)S(1)})": 0.5,  # the table's
        "fusion.2 bf16[131072,2048]{1,0:T(8,128)(2,1)} fusion(": 0.010,
        "copy.272 bf16[64,2048,1024]{2,1,0:T(8,128)(2,1)} copy(": 0.002,
        "copy.269 bf16[1,64,2048,1024]{2,3,1,0:T(8,128)(2,": 0.002,
        "fusion.66 (f32[1,64,2048,1024]{3,2,1,0:T(8,128)},": 0.5,  # AdamW
        "fusion.46 f32[]{:T(128)} fusion(bf16[64,1024,2048]": 0.5,  # the gradient's norm
        "fusion.45 (f32[16384,8]{1,0:T(8,128)S(1)}, bf16[13": 0.5,  # T rows first
        "fusion.44 bf16[16384,2048]{1,0:T(8,128)(2,1)} fusion(": 0.5,
    }
    run = dict(base, cell=olmoe, trace=traced(ops), traced_steps=2)
    assert moe_gmm_ms.read(run) == pytest.approx(60.0)
    assert moe_ms.read(run) == pytest.approx(70.0)
    assert moe_dispatch_ms.read(run) == pytest.approx(10.0)
    assert moe_gmm_roofline.read(run) == pytest.approx(100 * 25.116 / 60.0, rel=1e-3)
    assert moe_ms.read(dict(run, cell=dense)) is None
