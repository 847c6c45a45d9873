"""The Solar-Open2 reference's own proof (arch/solar_open2/reference.py):
the position-by-position delta rule with a decay a key channel does what
such a rule does (an empty state at the first position, a write read back,
a key's value replaced and not added to, a state reflected at beta = 2,
every channel decayed by its own factor), the held experts are the row's
choices restricted to the held, each of ``DROPS`` is another result,
rounding the operands moves it, the counts of flops.py are the hand count
(the published 250B among them), and the adapter refuses at once a
checkout whose program has no such mixer."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells

adapter = cells.arch_module("solar_open2", "adapter")
reference = cells.arch_module("solar_open2", "reference")
flops = cells.arch_module("solar_open2", "flops")
PUBLISHED = cells.load_json(
    os.path.join(cells.HERE, "configs", "solar-open2-250b-l4h8e8.json"))
NEW_METRICS = ("kda_ms", "kda_roofline", "kda_state_abs_max", "kda_decay_min",
               "solar_held_share", "solar_held_dropped", "solar_gmm_roofline")


def tiny(**overrides):
    """The published file at widths a CPU test can afford: two of four
    delta heads and four query heads on two key/value heads held, four of
    sixteen experts held, this chip the second expert rank."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, moe_intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_attn_config=dict(PUBLISHED["linear_attn_config"], num_heads=2, head_dim=16),
        head_parallel_chips=2, head_parallel_index=1,
        n_routed_experts=4, expert_parallel_chips=4, expert_parallel_index=1,
        num_experts_per_tok=3,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def _unit(key, shape):
    x = jax.random.normal(key, shape)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def test_the_first_position_sees_an_empty_state_and_reads_its_own_write():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q, key = _unit(k[0], (3, 2, 8)), _unit(k[1], (3, 2, 8))
    v = jax.random.normal(k[2], (3, 2, 16))
    g, beta = -jnp.exp(jax.random.normal(k[3], (3, 2, 8))), jnp.full((3, 2), 0.7)
    o, _ = reference.delta_rule(q, key, v, g, beta)
    # S_1 = beta k v^T whatever the decays: o_1 = beta (k . q) v
    want = 0.7 * jnp.sum(key[0] * q[0], axis=-1)[:, None] * v[0]
    assert jnp.allclose(o[0], want, rtol=1e-5, atol=1e-6)


def test_a_key_written_twice_holds_the_second_value_not_the_sum():
    """The correction: at beta = 1 and no decay, writing (k, v2) over (k, v1)
    leaves k -> v2; an additive state would read v1 + v2. At beta = 2 the
    first write is reflected: k -> 2 v2 - 2 v1."""
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    key = jnp.broadcast_to(_unit(k[0], (1, 1, 8)), (2, 1, 8))
    v = jax.random.normal(k[1], (2, 1, 16))
    zero = jnp.zeros((2, 1, 8))
    o, state = reference.delta_rule(key, key, v, zero, jnp.ones((2, 1)))
    assert jnp.allclose(o[1], v[1], atol=1e-5)
    assert jnp.allclose(jnp.einsum("hde,hd->he", state, key[0]), v[1], atol=1e-5)
    o2, _ = reference.delta_rule(key, key, v, zero, jnp.full((2, 1), 2.0))
    assert jnp.allclose(o2[1], 2 * v[1] - 2 * v[0], atol=1e-5)


def test_every_key_channel_decays_by_its_own_factor():
    """The second position writes nothing (beta = 0) and decays the first
    write: ROW c of the state by exp(g_c), where one decay a head would
    scale the whole state alike."""
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    key = _unit(k[0], (2, 1, 8))
    v = jax.random.normal(k[1], (2, 1, 16))
    g = jnp.stack([jnp.zeros((1, 8)), -jnp.linspace(0.1, 3.0, 8)[None]])
    _, state = reference.delta_rule(key, key, v, g, jnp.zeros((2, 1)).at[0].set(1.0))
    want = jnp.exp(g[1])[:, :, None] * key[0][:, :, None] * v[0][:, None, :]
    assert jnp.allclose(state, want, atol=1e-6)
    ratio = state[0, :, 0] / (key[0, 0] * v[0, 0, 0])
    assert float(ratio[0] / ratio[-1]) == pytest.approx(float(jnp.exp(2.9)), rel=1e-4)


def test_the_held_experts_part_is_the_rows_choices_restricted_to_the_held():
    """One row at a time, by hand: the row's top-3 of score + bias, the
    gates the chosen scores over their sum, and of those three only the
    ones this chip holds (experts 4-7 of 16) multiply anything."""
    c = tiny()
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    m = jax.random.normal(k[0], (5, 64))
    p = {
        "router": {"kernel": jax.random.normal(k[1], (64, 16))},
        "router_bias": 0.3 * jax.random.normal(k[2], (16,)),
        "experts_gate": 0.1 * jax.random.normal(k[3], (4, 64, 48)),
        "experts_up": 0.1 * jax.random.normal(k[4], (4, 64, 48)),
        "experts_down": 0.1 * jax.random.normal(k[5], (4, 48, 64)),
    }
    got = reference.experts(m, p, c, lambda a: a, shared=False)
    some_held = False
    for t in range(5):
        s = jax.nn.sigmoid(m[t] @ p["router"]["kernel"])
        chosen = sorted(range(16), key=lambda e: -float(s[e] + p["router_bias"][e]))[:3]
        total = sum(float(s[e]) for e in chosen)
        want = jnp.zeros((64,))
        for e in chosen:
            if 4 <= e < 8:
                some_held = True
                hidden = jax.nn.silu(m[t] @ p["experts_gate"][e - 4]) * (
                    m[t] @ p["experts_up"][e - 4])
                want = want + float(s[e]) / total * (hidden @ p["experts_down"][e - 4])
        assert jnp.allclose(got[t], want, rtol=1e-4, atol=1e-5)
    assert some_held


@functools.lru_cache(maxsize=None)
def _sample(seq=48, seed=0):
    """(config, seeded parameters, a batch, the reference's loss and
    gradients), once for the tests below."""
    from torchft_tpu.parallel.train import build_model

    c = tiny()
    model = build_model(adapter.model_config(c, seq), None)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, seq + 1), 0, c["vocab_size"])
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((2, seq), jnp.int32)}
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return c, params, data, jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)


def _loss(c, params, data, **options):
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda p, b: reference.loss(p, b, c, **options))(params, data))


@pytest.mark.parametrize("drop", reference.DROPS)
def test_each_dropped_term_is_another_result(drop):
    c, params, data, (full, _) = _sample()
    cut = _loss(c, params, data, drop=drop)
    assert abs(cut - float(full)) / float(full) > reference.LOSS_REL_TOL
    if drop == "beta_doubling":  # it is the file with the flag off
        assert cut == _loss(dict(c, kda_allow_neg_eigval=False), params, data)


def test_loss_and_grads_names_what_it_can_drop():
    c, params, data, _ = _sample()
    with pytest.raises(cells.CellError, match="norm"):
        reference.loss_and_grads(params, data, c, drop="norm")


def test_rounded_operands_are_another_result():
    c, params, data, (exact, g) = _sample()
    low, g8 = jax.jit(lambda p, b: reference.loss_and_grads(
        p, b, c, operand_dtype=jnp.float8_e4m3fn))(params, data)
    err = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), g8, g)
    assert max(e for e in jax.tree_util.tree_leaves(err) if e == e) > reference.GRAD_REL_L2_TOL
    assert float(exact) != float(low)


def test_the_counts_are_the_hand_count():
    """ISSUE 58's arithmetic, at the published widths of the cut file, and
    the published model's 250B from the published counts."""
    c = PUBLISHED
    assert flops.kda_matmul_params(c) == (
        4 * 4_194_304 + 2 * (524_288 + 131_072) + 32_768) == 18_120_704
    assert flops.kda_params(c) == 18_120_704 + 12_288 + 8 + 2 * 1024 + 128 == 18_135_176
    assert flops.attention_matmul_params(c) == 3 * 4_194_304 + 2 * 524_288 == 13_631_488
    assert flops.expert_params(c) == 15_728_640
    assert flops.expert_layer_params(c) == (
        8 * 15_728_640 + 15_728_640 + 1_310_720 + 320) == 142_868_800
    assert flops.total_params(c) == (
        3 * 18_135_176 + 13_631_488 + 4 * 142_868_800 + 8 * 4096
        + 2 * 100_663_296 + 4096) == 840_875_672
    held = 8 * (8 / 320) * 15_728_640
    assert flops.active_matmul_params(c) == pytest.approx(
        3 * 18_120_704 + 13_631_488 + 4 * (1_310_720 + 15_728_640 + held) + 100_663_296)
    whole = dict(
        c, num_hidden_layers=48, gqa_layers=list(range(0, 48, 4)), vocab_size=196608,
        num_attention_heads=64, num_key_value_heads=8, n_routed_experts=320,
        linear_attn_config=dict(c["linear_attn_config"], num_heads=64),
        head_parallel_chips=1, expert_parallel_chips=1, vocab_parallel_chips=1)
    assert adapter.pattern(whole) == "*EKEKEKE" * 12
    # 36 x 137.7M + 12 x 109.1M + 48 x 5,050M + 2 x 805M: the published 250B
    assert flops.kda_params(whole) == (
        137_625_600 + 98_304 + 64 + 2 * 8192 + 128) == 137_740_480
    assert flops.attention_matmul_params(whole) == 109_051_904
    assert flops.expert_layer_params(whole) == 5_050_204_480
    assert flops.total_params(whole) == 250_288_105_216
    assert round(flops.total_params(whole) / 1e9, 1) == 250.3
    rule = 2 * 8 * (5 * 32 * 128 + 64 * 64 / 6 + 3 * 128 * 128)
    assert flops.kda_flops_per_token(c) == pytest.approx(rule)
    conv = 2 * 4 * 3072
    attention = 3 * 2 * 8192 * 1024
    assert flops.model_flops_per_token(c, 8192) == pytest.approx(
        6 * flops.active_matmul_params(c) + attention + 3 * 3 * (rule + conv))
    assert flops.kda_flops_per_step(c, 2, 8192) == pytest.approx(3 * 3 * rule * 16384)
    # q, k, v in bf16, a log-decay a channel and beta in float32 in; o out
    ins, out = 6 * 1024 + 4 * 1024 + 4 * 8, 2 * 1024
    assert flops.kda_bytes_per_step(c, 2, 8192) == (3 * ins + 2 * out) * 16384 * 3
    assert (flops.kda_bytes_per_step(c, 2, 8192) / 819e9
            > flops.kda_flops_per_step(c, 2, 8192) / 197e12)  # memory-bound on a v5e
    assert flops.flash_flops_per_step(c, 2, 8192) == pytest.approx(attention * 16384)
    rows = 16384 * 8 * 8 / 320
    assert rows / 8 == pytest.approx(409.6)
    assert flops.gmm_flops_per_step(c, 2, 8192) == pytest.approx(6 * 15_728_640 * rows * 4)
    assert flops.gmm_flops_per_step(c, 2, 8192, 0.05) == pytest.approx(
        2 * flops.gmm_flops_per_step(c, 2, 8192))
    assert flops.gmm_bytes_per_step(c, 2, 8192) == pytest.approx(
        18 * (rows * (4096 + 1280) + 8 * 4096 * 1280) * 4)


def test_a_checkout_whose_program_has_no_such_mixer_is_refused_as_the_adapter_loads(
    tmp_path, monkeypatch
):
    """What the parent commit does under this PR's benchmark files: the
    adapter is loaded by the parent process of a run (``cells.load_cell``),
    and raises there, before JAX, the program or a chip is touched."""
    path = os.path.join(cells.HERE, "arch", "solar_open2", "adapter.py")
    assert cells.load_module(path).KEYS == adapter.KEYS
    models = tmp_path / "torchft_tpu" / "models"
    models.mkdir(parents=True)
    (models / "gated_delta.py").write_text("class GatedDeltaMixer:\n    pass\n")
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    with pytest.raises(cells.CellError, match="no Kimi delta attention"):
        cells.load_module(path)
    (models / "gated_delta.py").unlink()
    with pytest.raises(cells.CellError, match="no Kimi delta attention"):
        cells.load_module(path)


def test_the_cell_is_found_by_its_arch_key_with_its_metrics():
    cell = cells.load_cell("solar-open2-raw")
    assert cell.arch_dir == os.path.join(cells.HERE, "arch", "solar_open2")
    assert (cell.chips, cell.mix["batch"], cell.mix["seq"]) == (1, 2, 8192)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"mfu_pct", "flash_ms", "flash_roofline", "hbm_reserved_gib", "host_other_ms",
            "setup_check_s"} <= names
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for entry in table["per_layer"]:
        if entry["name"] in NEW_METRICS:
            assert entry["workloads"] == ["solar-open2-raw"] and entry["moves"] == "tok_s_chip"
            assert cells.find_file("", "metrics", entry["name"] + ".py")
