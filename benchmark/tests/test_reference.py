"""reference.py against the program's model and loss at a tiny size in
float32: the same mathematics written twice must agree to rounding, and
must stop agreeing when either side changes it."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, reference
from torchft_tpu.models.llama import LlamaConfig, Transformer
from torchft_tpu.parallel.train import _loss_fn

TINY = dict(
    hidden_size=64, intermediate_size=160, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=320, max_position_embeddings=128, sliding_window=None,
    rope_theta=1e4, rms_norm_eps=1e-5, hidden_act="silu",
    tie_word_embeddings=False, run={"attn_impl": "dense"},
)


def _both(config, seq=64, **overrides):
    cfg = LlamaConfig(**cells.model_kwargs(config, seq), dtype=jnp.float32,
                      remat=False, **overrides)
    model = Transformer(cfg)
    key = jax.random.PRNGKey(0)
    toks = jax.random.randint(key, (2, seq + 1), 0, config["vocab_size"])
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
             "mask": jnp.ones((2, seq), jnp.int32)}
    params = model.init(key, batch["inputs"])["params"]
    system = jax.value_and_grad(
        lambda p: _loss_fn(model, p, batch["inputs"], batch["targets"], batch["mask"])
    )(params)
    return system, reference.loss_and_grads(params, batch, config)


def _worst(g_sys, g_ref):
    return max(
        float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
        for a, b in zip(jax.tree_util.tree_leaves(g_sys),
                        jax.tree_util.tree_leaves(g_ref))
    )


@pytest.mark.parametrize("tied", [False, True])
def test_loss_and_gradients_agree_in_float32(tied):
    config = dict(TINY, tie_word_embeddings=tied)
    (l_sys, g_sys), (l_ref, g_ref) = _both(config)
    # float32 both sides: differences are summation order only
    assert abs(float(l_sys) - float(l_ref)) < 1e-5
    assert _worst(g_sys, g_ref) < 1e-5


def test_a_changed_rotary_base_is_seen():
    """The comparison must be able to fail: the reference at another
    rope_theta is a different model."""
    (_, g_sys), _ = _both(TINY)
    _, (_, g_other) = _both(dict(TINY, rope_theta=5e5))
    assert _worst(g_sys, g_other) > 1e-2


def test_window_shorter_than_sequence_is_refused():
    with pytest.raises(cells.CellError):
        cells.model_kwargs(dict(TINY, sliding_window=32), 64)
