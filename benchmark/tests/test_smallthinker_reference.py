"""The SmallThinker reference's own proof (arch/smallthinker/reference.py):
the band mask counts the position itself, the held experts are the row's
six top logits under a softmax over those six and restricted to the held,
the router's choice depends on the layer's input and not on its attention,
the blocked form is the whole square's, rounding the operands moves the
result, the counts of flops.py are the hand count and a brute-force count
of the kept entries, the new metrics read what they say, and the adapter
refuses at once a checkout whose program has no windowed family."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, trace_reduce

adapter = cells.arch_module("smallthinker", "adapter")
reference = cells.arch_module("smallthinker", "reference")
flops = cells.arch_module("smallthinker", "flops")
PUBLISHED = cells.load_json(
    os.path.join(cells.HERE, "configs", "smallthinker-21b-l8e8.json"))
NEW_METRICS = ("swa_ms", "swa_roofline", "swa_kept_share", "smallthinker_held_share",
               "smallthinker_held_dropped", "smallthinker_gmm_roofline")


def tiny(**overrides):
    """The published file at widths a CPU test can afford: one period,
    a window of 12, four of sixteen experts held, this chip the second
    expert rank."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, moe_ffn_hidden_size=48,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=4, rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1],
        sliding_window_size=12, moe_num_primary_experts=4, expert_parallel_chips=4,
        expert_parallel_index=1, moe_num_active_primary_experts=3,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def test_the_band_counts_the_position_itself():
    see = np.asarray(reference.visible(9, 3))
    for i in range(9):
        assert [j for j in range(9) if see[i, j]] == list(range(max(0, i - 2), i + 1))
    assert np.array_equal(np.asarray(reference.visible(9, None)), np.tril(np.ones((9, 9), bool)))
    assert np.array_equal(np.asarray(reference.visible(9, 9)), np.asarray(reference.visible(9, None)))
    assert int(reference.visible(9, 1).sum()) == 9  # a window of one: the diagonal


def test_the_held_experts_part_is_the_rows_top_logits_restricted_to_the_held():
    """One row at a time, by hand: the row's top 3 LOGITS, a softmax over
    those three, and of them only the ones this chip holds (experts 4-7 of
    16) multiply anything, through relu(gate) * up."""
    c = tiny()
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    m = jax.random.normal(k[0], (5, 64))
    logits = 2.0 * jax.random.normal(k[1], (5, 16))
    p = {
        "experts_gate": 0.1 * jax.random.normal(k[3], (4, 64, 48)),
        "experts_up": 0.1 * jax.random.normal(k[4], (4, 64, 48)),
        "experts_down": 0.1 * jax.random.normal(k[5], (4, 48, 64)),
    }
    got, balance = reference.experts(m, logits, p, c, lambda a: a)
    some_held, load = False, np.zeros(16)
    for t in range(5):
        chosen = sorted(range(16), key=lambda e: -float(logits[t, e]))[:3]
        z = np.exp([float(logits[t, e]) for e in chosen])
        want = jnp.zeros((64,))
        for e, g in zip(chosen, z / z.sum()):
            load[e] += 1
            if 4 <= e < 8:
                some_held = True
                hidden = jnp.maximum(m[t] @ p["experts_gate"][e - 4], 0.0) * (
                    m[t] @ p["experts_up"][e - 4])
                want = want + g * (hidden @ p["experts_down"][e - 4])
        assert jnp.allclose(got[t], want, rtol=1e-4, atol=1e-5)
    assert some_held
    p_e = np.asarray(jax.nn.softmax(logits, axis=-1)).mean(axis=0)
    assert float(balance) == pytest.approx(16 * float((load / 15 * p_e).sum()), rel=1e-5)


@functools.lru_cache(maxsize=None)
def _sample(seq=40, seed=0):
    """(config, seeded parameters, a batch, the reference's loss and
    gradients), once for the tests below. 40 positions under a window of
    12: most rows have lost keys to the band."""
    from torchft_tpu.parallel.train import build_model

    c = tiny()
    model = build_model(adapter.model_config(c, seq), None)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, seq + 1), 0, c["vocab_size"])
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((2, seq), jnp.int32)}
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return c, params, data, jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)


def _worst(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), got, want)
    return max(jax.tree_util.tree_leaves(errs))


def test_the_router_reads_the_layers_input_and_not_its_attention():
    """A layer's balance term is a function of its router's logits alone:
    another W_o moves the layer's output and leaves the term, bit for bit;
    another router moves both."""
    c, params, data, _ = _sample()
    x = params["embed"]["embedding"][data["inputs"]]
    attn, ffn = params["layers_2"], params["layers_3"]
    ident = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out, balance = reference._layer(x, attn, ffn, c, 1, ident, None)
        other_wo = dict(attn, attn=dict(attn["attn"], wo={"kernel": 3.0 * attn["attn"]["wo"]["kernel"]}))
        out2, balance2 = reference._layer(x, other_wo, ffn, c, 1, ident, None)
        other_router = dict(attn, router={"kernel": attn["router"]["kernel"][::-1]})
        out3, balance3 = reference._layer(x, other_router, ffn, c, 1, ident, None)
    assert float(balance) == float(balance2) and float(jnp.abs(out - out2).max()) > 1e-3
    assert float(balance) != float(balance3) and float(jnp.abs(out - out3).max()) > 1e-3


@pytest.mark.parametrize("block", [8, 20, 40])
def test_the_blocked_form_is_the_whole_squares(block):
    c, params, data, (loss, grads) = _sample()
    got, g = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c, query_block=block))(
        params, data)
    assert float(got) == pytest.approx(float(loss), rel=1e-6)
    assert _worst(g, grads) < 1e-5


@pytest.mark.parametrize("window", [11, 13, 40])
def test_a_moved_band_edge_is_another_result(window):
    c, params, data, (loss, grads) = _sample()
    moved = dict(c, sliding_window_size=window)
    got, g = jax.jit(lambda p, b: reference.loss_and_grads(p, b, moved))(params, data)
    assert _worst(g, grads) > 1e-3 and float(got) != float(loss)


def test_rounded_operands_are_another_result():
    c, params, data, (exact, g) = _sample()
    errs = {}
    for name, dtype in (("bf16", jnp.bfloat16), ("fp8", jnp.float8_e4m3fn)):
        low, g_low = jax.jit(lambda p, b, dtype=dtype: reference.loss_and_grads(
            p, b, c, operand_dtype=dtype))(params, data)
        errs[name] = _worst(g_low, g)
        assert float(low) != float(exact)
    assert errs["fp8"] > 4 * errs["bf16"] > 0.0


def test_the_counts_are_the_hand_count_and_a_brute_force_count():
    """ISSUE 60's arithmetic at the published widths of the cut file, the
    kept entries against a count over every (row, column), and the
    published model's 21.5B from the published counts."""
    c = PUBLISHED
    assert flops.attention_matmul_params(c) == 20_971_520
    assert flops.router_params(c) == 163_840
    assert flops.expert_params(c) == 5_898_240
    layer = 20_971_520 + 163_840 + 8 * 5_898_240 + 5_120
    assert layer == 68_326_400
    assert flops.total_params(c) == 8 * layer + 2 * 18_992 * 2560 + 2560 == 643_852_800
    assert flops.active_matmul_params(c) == pytest.approx(
        8 * (20_971_520 + 163_840 + 6 * 5_898_240 / 8) + 48_619_520)
    assert (flops.global_layers(c), flops.window_layers(c)) == (2, 6)
    assert flops.global_kept_entries(16384) == 134_225_920
    assert flops.window_kept_entries(c, 16384) == 58_722_304
    assert flops.window_kept_entries(c, 2048) == flops.global_kept_entries(2048)
    for seq, w in ((37, 5), (64, 16), (50, 50), (20, 64), (33, 1)):
        brute = sum(1 for i in range(seq) for j in range(seq) if j <= i and i - j < w)
        assert flops.window_kept_entries(dict(c, sliding_window_size=w), seq) == brute
        assert flops.global_kept_entries(seq) == sum(i + 1 for i in range(seq))
    per_entry = 12 * 28 * 128
    assert flops.swa_flops_per_step(c, 1, 16384) == pytest.approx(6 * per_entry * 58_722_304)
    assert flops.flash_flops_per_step(c, 1, 16384) == pytest.approx(
        per_entry * (6 * 58_722_304 + 2 * 134_225_920))
    assert flops.model_flops_per_token(c, 16384) * 16384 == pytest.approx(
        6 * flops.active_matmul_params(c) * 16384 + flops.flash_flops_per_step(c, 1, 16384))
    qkvo = 6 * 2 * 16384 * (28 + 4) * 128
    assert flops.swa_bytes_per_step(c, 1, 16384) == 6 * qkvo
    assert flops.flash_bytes_per_step(c, 1, 16384) == 8 * qkvo
    # compute-bound on a v5e by an order
    assert (flops.swa_flops_per_step(c, 1, 16384) / 197e12
            > 10 * flops.swa_bytes_per_step(c, 1, 16384) / 819e9)
    rows = 16384 * 6 * 8 / 64
    assert rows / 8 == 1536
    assert flops.gmm_flops_per_step(c, 1, 16384) == pytest.approx(6 * 5_898_240 * rows * 8)
    assert flops.gmm_flops_per_step(c, 1, 16384, 0.25) == pytest.approx(
        2 * flops.gmm_flops_per_step(c, 1, 16384))
    assert flops.gmm_bytes_per_step(c, 1, 16384) == pytest.approx(
        18 * (rows * (2560 + 768) + 8 * 2560 * 768) * 8)
    whole = dict(
        c, num_hidden_layers=52, rope_layout=[0, 1, 1, 1] * 13,
        sliding_window_layout=[0, 1, 1, 1] * 13, moe_num_primary_experts=64,
        expert_parallel_chips=1, vocab_size=151936, vocab_parallel_chips=1)
    assert adapter.pattern(whole) == "*EWEWEWE" * 13
    assert flops.total_params(whole) == 52 * (
        20_971_520 + 163_840 + 64 * 5_898_240 + 5_120) + 2560 + 2 * 151936 * 2560
    assert round(flops.total_params(whole) / 1e9, 1) == 21.5
    assert round(flops.active_matmul_params(whole) / 1e9, 1) == 3.3


def _fake_run(cell, ops, records):
    return {"cell": cell, "trace": trace_reduce.Trace((0.0, 1.0), 1, 0.9, ops, [], {}),
            "traced_steps": 2, "records": records, "device_kind": "TPU v5 lite",
            "peaks": cells.load_json(os.path.join(cells.HERE, "peaks.json"))}


def test_the_new_metrics_read_the_steps_counters_and_the_kernels_names():
    from benchmark.metrics import (
        flash_ms, flash_roofline, smallthinker_gmm_roofline, smallthinker_held_dropped,
        smallthinker_held_share, swa_kept_share, swa_ms, swa_roofline,
    )

    cell = cells.load_cell("smallthinker-raw")
    counters = lambda share: {  # noqa: E731
        "moe_held_share": share, "moe_dropped": 0.0, "swa_kept_share": 0.8}
    records = [{"traced": True, "counters": counters(0.10)},
               {"traced": True, "counters": counters(0.14)},
               {"traced": False, "counters": counters(0.0)}]
    ops = {
        "flash_attention_window.12 bf16[1,28,16384,128]": 0.30,
        "flash_attention_window.14 (bf16[1,4,16384,128], bf16[1,4,16384,128])": 0.20,
        "flash_attention.3 bf16[1,28,16384,128]": 0.25,   # a global layer's
        "ragged-dot-none.3 bf16[49152,768]{1,0:T(8,128)(2,1)} cust": 0.12,
        "fusion.7 f32[1,16384,2560]": 0.5,
    }
    run = _fake_run(cell, ops, records)
    assert swa_ms.read(run) == pytest.approx(250.0)
    assert flash_ms.read(run) == pytest.approx(375.0)  # the banded kernels are a part of it
    assert swa_kept_share.read(run) == 0.8
    assert smallthinker_held_share.read(run) == 0.10
    assert smallthinker_held_dropped.read(run) == 0.0
    least = flops.swa_flops_per_step(cell.config, 1, 16384) / 197e12 * 1e3
    assert swa_roofline.read(run) == pytest.approx(100 * least / 250.0)
    whole = flops.flash_flops_per_step(cell.config, 1, 16384) / 197e12 * 1e3
    assert flash_roofline.read(run) == pytest.approx(100 * whole / 375.0)
    gmm = flops.gmm_flops_per_step(cell.config, 1, 16384, 0.12) / 197e12 * 1e3
    assert smallthinker_gmm_roofline.read(run) == pytest.approx(100 * gmm / 60.0)
    # a program or a cell without them: nothing to read, and no error
    bare = {**run, "records": [{"traced": True, "counters": {}}],
            "trace": trace_reduce.Trace((0.0, 1.0), 1, 0.9, {"fusion.1 f32[8]": 0.1}, [], {})}
    for metric in (swa_ms, swa_roofline, swa_kept_share, smallthinker_held_share,
                   smallthinker_held_dropped, smallthinker_gmm_roofline):
        assert metric.read(bare) is None
        assert metric.read({**bare, "trace": None}) is None
    other = {**run, "cell": cells.load_cell("mistral-raw")}
    assert swa_roofline.read(other) is None  # no such kernel in that architecture's flops.py


def test_a_checkout_whose_program_has_no_windowed_family_is_refused_as_the_adapter_loads(
    tmp_path, monkeypatch
):
    """What the parent commit does under this PR's benchmark files: the
    adapter is loaded by the parent process of a run (``cells.load_cell``),
    and raises there, before JAX, the program or a chip is touched."""
    path = os.path.join(cells.HERE, "arch", "smallthinker", "adapter.py")
    assert cells.load_module(path).KEYS == adapter.KEYS
    ops = tmp_path / "torchft_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "flash_attention.py").write_text("def flash_attention(q, k, v):\n    pass\n")
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    with pytest.raises(cells.CellError, match="no sliding-window attention"):
        cells.load_module(path)
    (ops / "flash_attention.py").unlink()
    with pytest.raises(cells.CellError, match="no sliding-window attention"):
        cells.load_module(path)


def test_the_cell_is_found_by_its_arch_key_with_its_metrics():
    cell = cells.load_cell("smallthinker-raw")
    assert cell.arch_dir == os.path.join(cells.HERE, "arch", "smallthinker")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"]) == (1, 1, 16384)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"mfu_pct", "flash_ms", "flash_roofline", "hbm_reserved_gib", "host_other_ms",
            "setup_check_s"} <= names
    assert "head_loss_ms" not in names  # its list is a benchmark PR's to edit
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for entry in table["per_layer"]:
        if entry["name"] in NEW_METRICS:
            assert entry["workloads"] == ["smallthinker-raw"] and entry["moves"] == "tok_s_chip"
            assert cells.find_file("", "metrics", entry["name"] + ".py")
