"""A configuration names its architecture, and the harness finds the
adapter, the reference and the operation counts by that name: the lookup,
its refusals, ``dense_decoder`` against the program's configuration
written out by hand, and ``table/arch/moe_decoder``, an architecture that
exists only as files and entries of the test table."""

import dataclasses
import json
import os
import re

import jax.numpy as jnp
import pytest

from benchmark import cells, worker
from benchmark.metrics import flash_roofline, mfu_pct, quant_roofline
from torchft_tpu.models.llama import LlamaConfig

TABLE = os.path.join(cells.HERE, "tests", "table", "BENCHMARK.json")
REPO_CELLS = [
    w["name"] for w in cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["workloads"]
]


class Recording(dict):
    """A configuration that notes which of its keys are looked up."""

    def __init__(self, *a):
        super().__init__(*a)
        self.seen = set()

    def __getitem__(self, k):
        self.seen.add(k)
        return super().__getitem__(k)

    def get(self, k, default=None):
        self.seen.add(k)
        return super().get(k, default)


def _table_with(tmp_path, config):
    """A copy of the test table whose ``tiny`` configuration is ``config``."""
    table = cells.load_json(TABLE)
    here = os.path.dirname(TABLE)
    table["traffic_dir"] = os.path.join(here, "traffic")
    for c in table["configs"]:
        c["file"] = os.path.join(here, c["file"])
    table["configs"][0]["file"] = str(tmp_path / "config.json")
    (tmp_path / "config.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(table))
    return str(tmp_path / "BENCHMARK.json")


def test_the_default_architecture_is_found_for_a_file_that_names_none():
    loaded = [cells.load_cell(n) for n in REPO_CELLS] + [cells.load_cell("tiny-raw", TABLE)]
    nameless = [c for c in loaded if "arch" not in c.config]
    assert len(nameless) >= 5 and nameless[-1].name == "tiny-raw"
    for cell in nameless:
        assert cell.arch_dir == os.path.join(cells.HERE, "arch", "dense_decoder")
        assert cell.adapter.KEYS and cell.reference.GRAD_REL_L2_TOL == 0.04
        assert cell.reference.LOSS_REL_TOL == 2e-4


def test_an_architecture_beside_the_table_is_found_by_name():
    cell = cells.load_cell("tiny-moe-raw", TABLE)
    assert cell.arch_dir == os.path.join(os.path.dirname(TABLE), "arch", "moe_decoder")
    cfg = cell.adapter.model_config(cell.config, 64)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (4, 2)
    assert (cfg.expert_capacity_factor, cfg.router_aux_coef) == (0.75, 0.01)


@pytest.mark.parametrize("name,table", [(n, "") for n in REPO_CELLS]
                         + [("tiny-raw", TABLE), ("tiny-moe-raw", TABLE)])
def test_an_adapter_reads_exactly_the_keys_it_declares(name, table):
    cell = cells.load_cell(name, table)
    config = Recording(cell.config)
    cell.adapter.model_config(config, int(cell.mix["seq"]))
    assert config.seen - {"run"} == set(cell.adapter.KEYS)
    # and the operation counts read nothing else
    cell.flops.model_flops_per_token(config, int(cell.mix["seq"]))
    cell.flops.total_params(config)
    assert config.seen - {"run"} == set(cell.adapter.KEYS)


@pytest.mark.parametrize("key,value", [("num_experts", 64), ("qk_norm", True)])
def test_a_key_the_adapter_does_not_read_is_refused_by_name(tmp_path, key, value):
    tiny = cells.load_cell("tiny-raw", TABLE).config
    with pytest.raises(cells.CellError, match=key):
        cells.load_cell("tiny-raw", _table_with(tmp_path, dict(tiny, **{key: value})))
    documented = dict(tiny, assumed={}, stands_for="", distortions={}, reduced={})
    assert cells.load_cell("tiny-raw", _table_with(tmp_path, documented))


def test_a_sparse_configuration_under_the_dense_architecture_is_refused(tmp_path):
    """The silent case: without the refusal this file trains a dense
    decoder with a 128-wide MLP under the sparse model's name."""
    moe = cells.load_cell("tiny-moe-raw", TABLE).config
    as_dense = {k: v for k, v in moe.items() if k != "arch"}
    with pytest.raises(cells.CellError, match="num_experts"):
        cells.load_cell("tiny-raw", _table_with(tmp_path, as_dense))


def test_an_architecture_that_is_nowhere_is_refused(tmp_path):
    tiny = cells.load_cell("tiny-raw", TABLE).config
    with pytest.raises(cells.CellError, match="no_such_arch"):
        cells.load_cell("tiny-raw", _table_with(tmp_path, dict(tiny, arch="no_such_arch")))


def test_dense_decoder_gives_the_programs_configuration_field_for_field():
    cell = cells.load_cell("mistral-raw")
    got = cell.adapter.model_config(cell.config, 4096)
    assert got == LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=1, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=4096, rope_theta=10000.0, norm_eps=1e-5,
        tie_embeddings=False, attn_impl="flash",
        dtype=jnp.dtype("bfloat16"), param_dtype=jnp.dtype("float32"),
    )
    cell = cells.load_cell("internlm2-raw")
    got = cell.adapter.model_config(cell.config, 8192)
    assert got == LlamaConfig(
        vocab_size=92544, hidden_size=2048, intermediate_size=8192,
        num_layers=3, num_heads=16, num_kv_heads=8, head_dim=128,
        max_seq_len=8192, rope_theta=1e6, norm_eps=1e-5,
        tie_embeddings=False, attn_impl="flash",
        dtype=jnp.dtype("bfloat16"), param_dtype=jnp.dtype("float32"),
    )
    # the check's 1024-token sample takes the flash kernel, as the cell does
    sample = cell.adapter.sample_config(got, 1024)
    assert sample == dataclasses.replace(got, flash_min_seq=1024)
    assert cell.adapter.sample_config(got, 1000) == got  # not a whole tile


def _check(name, **changed):
    ctx = worker.Ctx(cells.load_cell(name, TABLE), 3, 0, False)
    ctx.config = dict(ctx.config, **changed)  # what the reference is given
    return worker.reference_check(ctx)


def test_reference_check_passes_for_the_sparse_architecture():
    got = _check("tiny-moe-raw")
    assert got["ok"] and got["tokens"] == 64
    assert got["grad_rel_l2_worst"] < 1e-5 and got["loss_rel_diff"] < 1e-6


@pytest.mark.parametrize("changed", [
    {"num_experts_per_tok": 1},  # one expert per token too few
    {"expert_capacity_factor": 0.8},  # 25 places an expert, not 24: tokens are dropped
    {"router_aux_loss_coef": 0.0},
])
def test_reference_check_fails_when_the_reference_is_another_model(changed):
    assert not _check("tiny-moe-raw", **changed)["ok"]


def test_reference_check_of_the_dense_architecture_uses_its_own_tolerances():
    got = _check("tiny-raw")  # bf16 against float32: percents, not 1e-6
    assert got["ok"] and 1e-4 < got["grad_rel_l2_worst"] < 0.04


def _run(cell, tok_s_chip=1000.0):
    return {"cell": cell, "tok_s_chip": tok_s_chip, "device_kind": "test chip",
            "peaks": {"test chip": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}},
            "trace": None, "traced_steps": 0}


def test_metrics_take_their_counts_from_the_cells_architecture():
    moe, dense = cells.load_cell("tiny-moe-raw", TABLE), cells.load_cell("tiny-raw", TABLE)
    # by hand, one block: wq + wo 2*64*64, wk + wv 2*64*32, the router 64*4,
    # one expert 3*64*128; head 64*256; causal attention 6*seq*64 a layer
    attention, router, expert, head = 12288, 256, 24576, 16384
    active = 2 * (attention + router + 2 * expert) + head
    per_token = 6 * active + 6 * 64 * 64 * 2
    assert moe.flops.model_flops_per_token(moe.config, 64) == per_token
    assert mfu_pct.read(_run(moe)) == 100.0 * 1000.0 * per_token / 1e12
    assert moe.flops.total_params(moe.config) == (
        2 * (attention + router + 4 * expert) + 2 * head + 5 * 64
    )
    dense_per_token = 6 * (2 * (attention + expert) + head) + 6 * 64 * 64 * 2
    assert mfu_pct.read(_run(dense)) == 100.0 * 1000.0 * dense_per_token / 1e12
    # the program's parameter tree has as many values as the count says
    ctx = worker.Ctx(moe, 3, 0, False)
    import jax

    shapes = jax.eval_shape(
        lambda: ctx.model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    )["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == (
        moe.flops.total_params(moe.config)
    )


def test_a_kernel_metric_reads_none_where_the_architecture_has_no_such_kernel(monkeypatch):
    moe, dense = cells.load_cell("tiny-moe-raw", TABLE), cells.load_cell("tiny-raw", TABLE)
    from benchmark.metrics import flash_ms, quant_kernel_ms

    monkeypatch.setattr(quant_kernel_ms, "read", lambda run: 2.0)
    monkeypatch.setattr(flash_ms, "read", lambda run: 2.0)
    for cell in (moe, dense):
        cell.mix["quantize_bits"] = 8
    assert not hasattr(moe.flops, "quant_bytes_per_step")
    assert quant_roofline.read(_run(moe)) is None
    n = dense.flops.total_params(dense.config)
    assert quant_roofline.read(_run(dense)) == 100.0 * (2 * (4 * n + n + 4 * n / 512) / 1e11) * 1e3 / 2.0
    assert flash_roofline.read(_run(moe)) == flash_roofline.read(_run(dense)) > 0


def test_the_harness_names_no_model_and_no_architecture():
    """The second architecture arrived as files and entries: what finds
    it knows no model class, no model key and no architecture's name."""
    for f in ("run.py", "worker.py", "cells.py"):
        with open(os.path.join(cells.HERE, f)) as fh:
            text = fh.read()
        for word in ("llama", "LlamaConfig", "intermediate_size", "num_experts",
                     "moe_decoder", "flash_min_seq"):
            assert not re.search(word, text, re.IGNORECASE), (f, word)
    with open(os.path.join(cells.HERE, "cells.py")) as fh:
        assert fh.read().count("dense_decoder") == 1  # DEFAULT_ARCH, nothing else
