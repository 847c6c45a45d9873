"""The Nemotron-H reference's own proof (arch/nemotron_h/reference.py): the
T x T form its loss uses gives what the position-by-position recurrence
gives, values and gradients; rounding the decays moves it."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells

reference = cells.arch_module("nemotron_h", "reference")


def _inputs(t, heads=4, width=8, n=6, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (t, heads, width))
    delta = jax.nn.softplus(jax.random.normal(k[1], (t, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(k[3], (t, heads, n))
    c = jax.random.normal(k[4], (t, heads, n))
    return x, delta, a, b, c


@pytest.mark.parametrize("t", [1, 7, 64])
def test_the_quadratic_form_is_the_recurrence(t):
    args = _inputs(t)
    with jax.default_matmul_precision("highest"):
        want = reference.ssm_recurrent(*args)
        got = reference.ssm_quadratic(*args)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)
        weigh = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        grads = lambda f: jax.grad(  # noqa: E731
            lambda *a: jnp.sum(f(*a) * weigh), argnums=(0, 1, 2, 3, 4)
        )(*args)
        for g, w in zip(grads(reference.ssm_quadratic), grads(reference.ssm_recurrent)):
            assert jnp.allclose(g, w, rtol=2e-4, atol=2e-4)


def test_the_first_position_sees_an_empty_state():
    x, delta, a, b, c = _inputs(3)
    y = reference.ssm_recurrent(x, delta, a, b, c)
    want = jnp.einsum("hp,hn,hn->hp", delta[0][:, None] * x[0], b[0], c[0])
    assert jnp.allclose(y[0], want, rtol=1e-5, atol=1e-6)


def test_rounded_decays_are_another_result():
    args = _inputs(64)
    exact = reference.ssm_quadratic(*args)
    rounded = reference.ssm_quadratic(*args, decay_dtype=jnp.bfloat16)
    err = jnp.linalg.norm(rounded - exact) / jnp.linalg.norm(exact)
    assert 1e-3 < float(err) < 0.5
