"""The Olmo-Hybrid reference's own proof (arch/olmo_hybrid/reference.py):
the position-by-position delta rule does what a delta rule does (an empty
state at the first position, a write read back, a key's value replaced and
not added to, a state reflected at beta = 2, decay), each of ``DROPS`` is
another result, rounding the operands moves it, and the counts of
flops.py are the hand count."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells

adapter = cells.arch_module("olmo_hybrid", "adapter")
reference = cells.arch_module("olmo_hybrid", "reference")
flops = cells.arch_module("olmo_hybrid", "flops")
PUBLISHED = cells.load_json(
    os.path.join(cells.HERE, "configs", "olmo-hybrid-7b-l4h15.json"))


def tiny(**overrides):
    """The published file at widths a CPU test can afford: two of four
    heads held, this chip the second rank."""
    c = dict(PUBLISHED)
    c.update(
        hidden_size=64, vocab_size=256, intermediate_size=160,
        num_attention_heads=2, num_key_value_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=16,
        head_parallel_index=1,
        run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
    )
    c.update(overrides)
    return c


def _unit(key, shape):
    x = jax.random.normal(key, shape)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def test_the_first_position_sees_an_empty_state_and_reads_its_own_write():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q, key = _unit(k[0], (3, 2, 8)), _unit(k[1], (3, 2, 8))
    v = jax.random.normal(k[2], (3, 2, 16))
    g, beta = jnp.full((3, 2), -0.3), jnp.full((3, 2), 0.7)
    o, _ = reference.delta_rule(q, key, v, g, beta)
    # S_1 = beta k v^T whatever the decay: o_1 = beta (k . q) v
    want = 0.7 * jnp.sum(key[0] * q[0], axis=-1)[:, None] * v[0]
    assert jnp.allclose(o[0], want, rtol=1e-5, atol=1e-6)


def test_a_key_written_twice_holds_the_second_value_not_the_sum():
    """The correction: at beta = 1 and no decay, writing (k, v2) over (k, v1)
    leaves k -> v2; an additive state would read v1 + v2. At beta = 2 the
    first write is reflected: k -> 2 v2 - 2 v1."""
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    key = jnp.broadcast_to(_unit(k[0], (1, 1, 8)), (2, 1, 8))
    v = jax.random.normal(k[1], (2, 1, 16))
    zero = jnp.zeros((2, 1))
    o, state = reference.delta_rule(key, key, v, zero, jnp.ones((2, 1)))
    assert jnp.allclose(o[1], v[1], atol=1e-5)
    assert jnp.allclose(jnp.einsum("hde,hd->he", state, key[0]), v[1], atol=1e-5)
    o2, _ = reference.delta_rule(key, key, v, zero, jnp.full((2, 1), 2.0))
    assert jnp.allclose(o2[1], 2 * v[1] - 2 * v[0], atol=1e-5)


def test_the_decay_scales_what_was_written_before():
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    key = _unit(k[0], (2, 1, 8))
    key = key.at[1].set(jnp.roll(key[0], 1, axis=-1) * 0 + _unit(k[2], (1, 8)))
    v = jax.random.normal(k[1], (2, 1, 16))
    g = jnp.array([[0.0], [-1.5]])
    _, state = reference.delta_rule(key, key, v, g, jnp.zeros((2, 1)).at[0].set(1.0))
    # the second position writes nothing (beta = 0) and decays the first write
    want = jnp.exp(-1.5) * key[0][:, :, None] * v[0][:, None, :]
    assert jnp.allclose(state, want, atol=1e-6)


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
    assert abs(cut - float(full)) / float(full) > 5 * reference.LOSS_REL_TOL
    if drop == "beta_doubling":  # it is the file with the flag off
        assert cut == _loss(dict(c, linear_allow_neg_eigval=False), params, data)


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
    assert max(jax.tree_util.tree_leaves(err)) > reference.GRAD_REL_L2_TOL
    assert float(exact) != float(low)


def test_the_counts_are_the_hand_count():
    """ISSUE 54's arithmetic, at the published widths of the cut file, and
    the published model's 7.43B from the published counts."""
    c = PUBLISHED
    assert flops.linear_params(c) == (
        2 * 5_529_600 + 3 * 11_059_200 + 2 * 57_600 + 30 + 23_040 + 192) == 44_375_262
    assert flops.attention_params(c) == 29_491_200 + 3_840
    assert flops.mlp_params(c) == 126_812_160
    assert flops.total_params(c) == (
        3 * 171_195_102 + 156_314_880 + 2 * 48_168_960 + 3_840) == 766_241_946
    assert flops.matmul_params(c) == 3 * 44_352_000 + 29_491_200 + 4 * 126_812_160 + 48_168_960
    whole = dict(
        c, num_hidden_layers=32, layer_types=c["layer_types"] * 8, vocab_size=100352,
        head_parallel_chips=1, vocab_parallel_chips=1,
        **{k: 30 for k in ("num_attention_heads", "num_key_value_heads",
                           "linear_num_key_heads", "linear_num_value_heads")})
    assert flops.total_params(whole) == 7_430_870_688
    assert flops.linear_params(whole) + flops.mlp_params(whole) + 2 * 3840 == 215_570_172
    rule = 2 * 15 * (3 * 32 * 96 + 2 * 32 * 192 + 64 * 64 / 6 + 3 * 96 * 192)
    assert flops.gdn_flops_per_token(c) == pytest.approx(rule)
    conv = 2 * 4 * 5760
    attention = 3 * 2 * 8192 * 1920
    assert flops.model_flops_per_token(c, 8192) == pytest.approx(
        6 * flops.matmul_params(c) + attention + 3 * 3 * (rule + conv))
    assert flops.gdn_flops_per_step(c, 2, 8192) == pytest.approx(3 * 3 * rule * 16384)
    assert flops.gdn_bytes_per_step(c, 2, 8192) == (17_400 + 29_040) * 16384 * 3
    assert flops.flash_flops_per_step(c, 2, 8192) == pytest.approx(attention * 16384)
