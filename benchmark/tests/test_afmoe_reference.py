"""The AFMoE reference's own proof (arch/afmoe/reference.py): the band mask
counts the position itself, the held experts are the row's eight top
sigmoid scores PLUS the selection bias, weighted by the scores without it
and restricted to the held, beside a shared expert every row passes
through; the output gate reads the normed input and halves the attention
where its kernel is zero; a global layer is not rotated and a sliding one
is; the post-norm undoes a rescaled mixer; the blocked form is the whole
square's; each named departure and rounded operands are another result; the
counts of flops.py are the hand count; the new metrics read what they say;
and the adapter refuses at once a checkout whose program has no such
layer."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, trace_reduce

adapter = cells.arch_module("afmoe", "adapter")
reference = cells.arch_module("afmoe", "reference")
flops = cells.arch_module("afmoe", "flops")
PUBLISHED = cells.load_json(
    os.path.join(cells.HERE, "configs", "trinity-mini-l5e16.json"))
NEW_METRICS = ("trinity_swa_ms", "trinity_swa_roofline", "trinity_swa_kept_share",
               "trinity_held_share", "trinity_held_dropped", "trinity_held_run_share",
               "trinity_gmm_roofline")
IDENT = lambda a: a  # noqa: E731


def tiny(**overrides):
    """The published file at widths a CPU test can afford: the same five
    layers, a window of 12, four of sixteen experts held, this chip the
    second expert rank."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, intermediate_size=160, moe_intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=12, num_experts=4, expert_parallel_chips=4,
        expert_parallel_index=1, num_experts_per_tok=3,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def unsettle(params, seed=7):
    """``params`` with every vector leaf (the norms' scales, the selection
    biases) moved off its initial 1 or 0, so that a norm left out or a bias
    that weighs shows."""
    def leaf(path, x):
        if x.ndim != 1:
            return x
        key = jax.random.fold_in(jax.random.PRNGKey(seed), hash(jax.tree_util.keystr(path)) % 9973)
        return x + 0.2 * jax.random.normal(key, x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params)


def test_the_band_counts_the_position_itself():
    see = np.asarray(reference.visible(9, 3))
    for i in range(9):
        assert [j for j in range(9) if see[i, j]] == list(range(max(0, i - 2), i + 1))
    assert np.array_equal(np.asarray(reference.visible(9, None)), np.tril(np.ones((9, 9), bool)))
    assert int(reference.visible(9, 1).sum()) == 9  # a window of one: the diagonal
    assert [reference.window_at(PUBLISHED, n) for n in (16384, 2049, 2048, 1024, 8, 1)] == [
        2048, 2048, 1024, 512, 4, 1]


def test_the_bias_chooses_the_scores_weigh_and_the_shared_expert_is_whole():
    """One row at a time, by hand: the row's top 3 of sigmoid + bias, the
    gates their sigmoids WITHOUT the bias over their sum times the scale,
    and of them only the ones this chip holds (experts 4-7 of 16) multiply
    anything; the shared expert every row."""
    c = tiny()
    k = jax.random.split(jax.random.PRNGKey(3), 9)
    m = jax.random.normal(k[0], (6, 64))
    w = lambda key, *shape: 0.1 * jax.random.normal(key, shape)  # noqa: E731
    p = {
        "router": {"kernel": 0.5 * jax.random.normal(k[1], (64, 16))},
        "router_bias": 0.3 * jax.random.normal(k[2], (16,)),
        "experts_gate": w(k[3], 4, 64, 48), "experts_up": w(k[4], 4, 64, 48),
        "experts_down": w(k[5], 4, 48, 64),
        "shared_gate": {"kernel": w(k[6], 64, 48)}, "shared_up": {"kernel": w(k[7], 64, 48)},
        "shared_down": {"kernel": w(k[8], 48, 64)},
    }
    silu = lambda a: a * jax.nn.sigmoid(a)  # noqa: E731
    ffn = lambda row, g, u, d: (silu(row @ g) * (row @ u)) @ d  # noqa: E731
    got = reference.experts(m, p, c, IDENT)
    scores = np.asarray(jax.nn.sigmoid(m @ p["router"]["kernel"]))
    bias = np.asarray(p["router_bias"])
    some_held = moved_by_bias = False
    for t in range(6):
        chosen = sorted(range(16), key=lambda e: -(scores[t, e] + bias[e]))[:3]
        moved_by_bias |= chosen != sorted(range(16), key=lambda e: -scores[t, e])[:3]
        total = sum(scores[t, e] for e in chosen) + 1e-20
        want = ffn(m[t], p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                   p["shared_down"]["kernel"])
        for e in chosen:
            if 4 <= e < 8:
                some_held = True
                want = want + c["route_scale"] * scores[t, e] / total * ffn(
                    m[t], p["experts_gate"][e - 4], p["experts_up"][e - 4],
                    p["experts_down"][e - 4])
        assert jnp.allclose(got[t], want, rtol=1e-4, atol=1e-5)
    assert some_held and moved_by_bias
    _, idx, gates = reference.route(m, p, c)
    assert np.allclose(np.asarray(gates.sum(-1)), c["route_scale"], rtol=1e-5)
    assert not np.allclose(np.asarray(reference.route(m, p, c, bias_in_gates=True)[2]),
                           np.asarray(gates))
    # the bias gets no gradient
    g = jax.grad(lambda b: reference.experts(m, dict(p, router_bias=b), c, IDENT).sum())(
        p["router_bias"])
    assert float(jnp.abs(g).max()) == 0.0


def test_the_selection_bias_moves_against_the_load():
    load = jnp.array([5.0, 0.0, 2.0, 1.0])
    got = reference.bias_update(jnp.array([0.1, 0.0, -0.2, 0.0]), load, 1e-3)
    assert np.allclose(np.asarray(got), [0.099, 0.001, -0.2, 0.001], atol=1e-7)


@functools.lru_cache(maxsize=None)
def _sample(seq=40, seed=0):
    """(config, seeded parameters with unsettled vectors, a batch, the
    reference's loss and gradients), once for the tests below. 40 positions
    under a window of 12: most rows have lost keys to the band."""
    from torchft_tpu.parallel.train import build_model

    c = tiny()
    model = build_model(adapter.model_config(c, seq), None)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, seq + 1), 0, c["vocab_size"])
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((2, seq), jnp.int32)}
    params = unsettle(model.init(jax.random.PRNGKey(seed), data["inputs"])["params"])
    return c, params, data, jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)


def _worst(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), got, want)
    return max(e for e in jax.tree_util.tree_leaves(errs) if e == e)  # a bias reads 0/0


def test_the_gate_reads_the_normed_input_and_a_global_layer_is_not_rotated():
    c, params, data, _ = _sample()
    x = params["embed"]["embedding"][data["inputs"]] * reference.embed_scale(c)
    assert reference.embed_scale(c) == 8.0 and reference.embed_scale(dict(c, mup_enabled=False)) == 1.0
    p = params["layers_4"]["attn"]  # published layer 3 of the file's: full_attention
    assert [reference.sliding(c, i) for i in range(5)] == [True, True, False, True, True]
    with jax.default_matmul_precision("highest"):
        h = reference._rms_norm(x, params["layers_4"]["norm"]["scale"], 1e-5)
        out = reference.attention(h, p, c, 2, IDENT)
        # a zero gate kernel: sigmoid(0) = 1/2 of the ungated attention, whatever it reads
        half = reference.attention(h, dict(p, wg={"kernel": jnp.zeros_like(p["wg"]["kernel"])}),
                                   c, 2, IDENT)
        other = reference.attention(h, p, c, 2, IDENT, gate_input=x)
        rotated = reference.attention(h, p, c, 2, IDENT, rotate_all=True)
        # the same parameters as a sliding layer's: rotated, and under the band
        sliding = reference.attention(h, p, c, 1, IDENT)
        # by hand, one head of one row: no rotation, the causal mask, the gate
        q = reference._rms_norm(jnp.einsum("sh,hd->sd", h[0], p["wq"]["kernel"][:, 1]),
                                p["q_norm"]["scale"], 1e-5)
        k = reference._rms_norm(jnp.einsum("sh,hd->sd", h[0], p["wk"]["kernel"][:, 0]),
                                p["k_norm"]["scale"], 1e-5)
        v = jnp.einsum("sh,hd->sd", h[0], p["wv"]["kernel"][:, 0])
        row = 17
        probs = jax.nn.softmax(q[row] @ k[: row + 1].T / 4.0)
        a = probs @ v[: row + 1] * jax.nn.sigmoid(h[0, row] @ p["wg"]["kernel"][:, 1])
        ungated = reference.attention(
            h, dict(p, wg={"kernel": jnp.zeros_like(p["wg"]["kernel"])},
                    wo={"kernel": jnp.zeros_like(p["wo"]["kernel"]).at[1].set(jnp.eye(16, 64))}),
            c, 2, IDENT)
        assert jnp.allclose(2.0 * ungated[0, row, :16] * jax.nn.sigmoid(
            h[0, row] @ p["wg"]["kernel"][:, 1]), a, rtol=1e-4, atol=1e-5)
    for departed in (half, other, rotated, sliding):
        assert float(jnp.abs(out - departed).max()) > 1e-3


def test_the_post_norm_undoes_a_rescaled_mixer_and_its_absence_does_not():
    """x + norm_post(mixer(norm_pre(x))): a W_o three times as large leaves
    the layer's output what it was (to the eps), and moves it where the
    second norm is left out."""
    c, params, data, _ = _sample()
    x = params["embed"]["embedding"][data["inputs"]] * 8.0
    attn, ffn = params["layers_2"], params["layers_3"]
    bigger = dict(attn, attn=dict(attn["attn"], wo={"kernel": 3.0 * attn["attn"]["wo"]["kernel"]}))
    with jax.default_matmul_precision("highest"):
        out = reference._layer(x, attn, ffn, c, 1, IDENT, None)
        same = reference._layer(x, bigger, ffn, c, 1, IDENT, None)
        bare = reference._layer(x, attn, ffn, c, 1, IDENT, None, "no_post_norm")
        moved = reference._layer(x, bigger, ffn, c, 1, IDENT, None, "no_post_norm")
    assert jnp.allclose(out, same, rtol=1e-3, atol=1e-3)
    assert float(jnp.abs(bare - moved).max()) > 0.05
    assert float(jnp.abs(out - bare).max()) > 0.05


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
    moved = dict(c, sliding_window=window)
    got, g = jax.jit(lambda p, b: reference.loss_and_grads(p, b, moved))(params, data)
    assert _worst(g, grads) > 1e-3 and float(got) != float(loss)


@pytest.mark.parametrize("departure", reference.DEPARTURES)
def test_each_named_departure_is_another_result(departure):
    c, params, data, (loss, grads) = _sample()
    got, g = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c, departure=departure))(
        params, data)
    assert _worst(g, grads) > 1e-2 and float(got) != float(loss)
    with pytest.raises(ValueError, match="departure"):
        reference.loss(params, data, c, departure="no_such")


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
    """ISSUE 65's arithmetic at the published widths of the cut file, the
    kept entries against a count over every (row, column), and the
    published model's 26B-A3B from the published counts."""
    c = PUBLISHED
    assert flops.attention_matmul_params(c) == 3 * 8_388_608 + 2 * 1_048_576 == 27_262_976
    assert flops.dense_ffn_params(c) == 37_748_736
    assert flops.expert_params(c) == 6_291_456 and flops.router_params(c) == 262_144
    attention = 27_262_976 + 2 * 128 + 2 * 2048
    sparse = 262_144 + 128 + 17 * 6_291_456 + 2 * 2048
    assert flops.total_params(c) == (
        5 * attention + 37_748_736 + 2 * 2048 + 4 * sparse + 2048 + 2 * 25_024 * 2048
    ) == 705_474_304
    assert flops.active_matmul_params(c) == pytest.approx(
        5 * 27_262_976 + 37_748_736 + 4 * (262_144 + 2 * 6_291_456) + 25_024 * 2048)
    assert (flops.global_layers(c), flops.window_layers(c), flops.expert_layers(c)) == (1, 4, 4)
    assert flops.global_kept_entries(16384) == 134_225_920
    assert flops.window_kept_entries(c, 16384) == 31_458_304
    assert flops.window_kept_entries(c, 2048) == flops.global_kept_entries(2048)
    for seq, w in ((37, 5), (64, 16), (50, 50), (20, 64), (33, 1)):
        brute = sum(1 for i in range(seq) for j in range(seq) if j <= i and i - j < w)
        assert flops.window_kept_entries(dict(c, sliding_window=w), seq) == brute
        assert brute == int(np.asarray(reference.visible(seq, w)).sum())
    per_entry = 12 * 32 * 128
    assert flops.swa_flops_per_step(c, 1, 16384) == pytest.approx(4 * per_entry * 31_458_304)
    assert flops.flash_flops_per_step(c, 1, 16384) == pytest.approx(
        per_entry * (4 * 31_458_304 + 134_225_920))
    assert flops.model_flops_per_token(c, 16384) * 16384 == pytest.approx(
        6 * flops.active_matmul_params(c) * 16384 + flops.flash_flops_per_step(c, 1, 16384))
    qkvo = 6 * 2 * 16384 * (32 + 4) * 128
    assert flops.swa_bytes_per_step(c, 1, 16384) == 4 * qkvo
    assert flops.flash_bytes_per_step(c, 1, 16384) == 5 * qkvo
    # compute-bound on a v5e: the band's 31 ms of matmuls against 4.4 ms of bytes
    assert (flops.swa_flops_per_step(c, 1, 16384) / 197e12
            > 7 * flops.swa_bytes_per_step(c, 1, 16384) / 819e9)
    rows = 16384 * 8 * 16 / 128
    assert rows / 16 == 1024
    assert flops.gmm_flops_per_step(c, 1, 16384) == pytest.approx(6 * 6_291_456 * rows * 4)
    assert flops.gmm_flops_per_step(c, 1, 16384, 0.25) == pytest.approx(
        2 * flops.gmm_flops_per_step(c, 1, 16384))
    assert flops.gmm_bytes_per_step(c, 1, 16384) == pytest.approx(
        18 * (rows * (2048 + 1024) + 16 * 2048 * 1024) * 4)
    whole = dict(
        c, num_hidden_layers=32,
        layer_types=["sliding_attention"] * 3 + ["full_attention"], num_dense_layers=2,
        num_experts=128, expert_parallel_chips=1, vocab_size=200192, vocab_parallel_chips=1)
    whole["layer_types"] = whole["layer_types"] * 8
    assert adapter.pattern(whole) == "WDWDWE*E" + "WEWEWE*E" * 7
    assert round(flops.total_params(whole) / 1e9, 1) == 26.1
    assert round(flops.active_matmul_params(whole) / 1e9, 1) == 3.1


def _fake_run(cell, ops, records):
    return {"cell": cell, "trace": trace_reduce.Trace((0.0, 1.0), 1, 0.9, ops, [], {}),
            "traced_steps": 2, "records": records, "device_kind": "TPU v5 lite",
            "peaks": cells.load_json(os.path.join(cells.HERE, "peaks.json"))}


def test_the_new_metrics_read_the_steps_counters_and_the_kernels_names():
    import importlib

    from benchmark.metrics import flash_ms, flash_roofline

    m = {n: importlib.import_module(f"benchmark.metrics.{n}") for n in NEW_METRICS}
    cell = cells.load_cell("trinity-raw")
    counters = lambda share: {  # noqa: E731
        "moe_held_share": share, "moe_dropped": 0.0, "swa_kept_share": 0.8,
        "moe_held_run_share": 0.25}
    records = [{"traced": True, "counters": counters(0.10)},
               {"traced": True, "counters": counters(0.14)},
               {"traced": False, "counters": counters(0.0)}]
    ops = {
        "flash_attention_window.12 bf16[1,32,16384,128]": 0.12,
        "flash_attention_window.14 (bf16[1,4,16384,128], bf16[1,4,16384,128])": 0.08,
        "flash_attention.3 bf16[1,32,16384,128]": 0.15,   # the global layer's
        "ragged-dot-none.3 bf16[65536,1024]{1,0:T(8,128)(2,1)} cust": 0.12,
        "fusion.7 f32[1,16384,2048]": 0.5,
    }
    run = _fake_run(cell, ops, records)
    assert m["trinity_swa_ms"].read(run) == pytest.approx(100.0)
    assert flash_ms.read(run) == pytest.approx(175.0)  # the banded kernels are a part of it
    assert m["trinity_swa_kept_share"].read(run) == 0.8
    assert m["trinity_held_share"].read(run) == 0.10
    assert m["trinity_held_dropped"].read(run) == 0.0
    assert m["trinity_held_run_share"].read(run) == 0.25
    least = flops.swa_flops_per_step(cell.config, 1, 16384) / 197e12 * 1e3
    assert m["trinity_swa_roofline"].read(run) == pytest.approx(100 * least / 100.0)
    whole = flops.flash_flops_per_step(cell.config, 1, 16384) / 197e12 * 1e3
    assert flash_roofline.read(run) == pytest.approx(100 * whole / 175.0)
    gmm = flops.gmm_flops_per_step(cell.config, 1, 16384, 0.12) / 197e12 * 1e3
    assert m["trinity_gmm_roofline"].read(run) == pytest.approx(100 * gmm / 60.0)
    # a program or a cell without them: nothing to read, and no error
    bare = {**run, "records": [{"traced": True, "counters": {}}],
            "trace": trace_reduce.Trace((0.0, 1.0), 1, 0.9, {"fusion.1 f32[8]": 0.1}, [], {})}
    for metric in m.values():
        assert metric.read(bare) is None
        assert metric.read({**bare, "trace": None}) is None


def test_a_checkout_whose_program_has_no_sandwich_is_refused_as_the_adapter_loads(
    tmp_path, monkeypatch
):
    """What the parent commit does under this PR's benchmark files: the
    adapter is loaded by the parent process of a run (``cells.load_cell``),
    and raises there, before JAX, the program or a chip is touched."""
    path = os.path.join(cells.HERE, "arch", "afmoe", "adapter.py")
    assert cells.load_module(path).KEYS == adapter.KEYS
    models = tmp_path / "torchft_tpu" / "models"
    models.mkdir(parents=True)
    (models / "llama.py").write_text("norm_after_mixer: bool = False\n")
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    with pytest.raises(cells.CellError, match="no norm before AND after a sub-layer"):
        cells.load_module(path)
    (models / "llama.py").unlink()
    with pytest.raises(cells.CellError, match="no norm before AND after a sub-layer"):
        cells.load_module(path)


def test_the_cell_is_found_by_its_arch_key_with_its_metrics():
    cell = cells.load_cell("trinity-raw")
    assert cell.arch_dir == os.path.join(cells.HERE, "arch", "afmoe")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"]) == (1, 1, 16384)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"mfu_pct", "flash_ms", "flash_roofline", "hbm_reserved_gib", "host_other_ms",
            "setup_check_s", "router_bias_abs_max"} <= names | {"router_bias_abs_max"}
    # the lists of the accepted metrics are a benchmark PR's to edit
    assert not {"swa_ms", "held_run_share", "head_loss_ms"} & names
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for entry in table["per_layer"]:
        if entry["name"] in NEW_METRICS:
            assert entry["workloads"] == ["trinity-raw"] and entry["moves"] == "tok_s_chip"
            assert cells.find_file("", "metrics", entry["name"] + ".py")
