"""The head and loss of the compiled step (``parallel.train._head_loss_sum``
behind ``_loss_and_metrics``): a chunk forms its own gradient while its
logits are live. Values and gradients against the full-logits ``optax``
loss, the shape of the differentiated program, and the rule that sizes
the chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models.llama import llama_debug, llama_moe_debug
from torchft_tpu.parallel import make_mesh
from torchft_tpu.parallel import train
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
)

B, S = 2, 256


def _reference_loss(model, params, batch):
    """The loss as ``_loss_and_metrics`` documents it, from the model's own
    full [B,S,V] logits."""
    cfg = model.cfg
    logits, sown = train._apply_with_aux(model, params, batch["inputs"])
    mask = batch["mask"].astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), batch["targets"]
    )
    loss = (losses * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if sown:
        loss = loss + cfg.router_aux_coef * sown["router_aux"].mean()
        loss = loss + cfg.router_z_coef * sown["router_z"].mean()
    return loss


def _batch(cfg, b=B, s=S, mask="ones", seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s + 1)), jnp.int32)
    masks = {
        "ones": np.ones((b, s)),
        "holes": rng.random((b, s)) > 0.4,
        "zeros": np.zeros((b, s)),
    }
    return {
        "inputs": tokens[:, :-1],
        "targets": tokens[:, 1:],
        "mask": jnp.asarray(masks[mask], jnp.int32),
    }


def _rel_l2(got, want):
    """The worst leaf's |got - want| / |want| (absolute where want is 0)."""
    def one(g, w):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        return np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-6)

    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(one, got, want)))


# name -> (config, batch keywords, TORCHFT_LOSS_CHUNK or 0, loss rtol, gradient rel-L2)
F32 = dict(dtype=jnp.float32, max_seq_len=S)
CASES = {
    "untied": (llama_debug(**F32), {}, 0, 1e-5, 1e-4),
    "tied": (llama_debug(tie_embeddings=True, **F32), {}, 0, 1e-5, 1e-4),
    "mask_with_zeros": (llama_debug(**F32), {"mask": "holes"}, 64, 1e-5, 1e-4),
    "all_zero_mask": (llama_debug(**F32), {"mask": "zeros"}, 64, 1e-5, 1e-4),
    "bf16_compute_f32_params": (
        llama_debug(dtype=jnp.bfloat16, max_seq_len=S), {"mask": "holes"}, 64, 2e-2, 5e-2,
    ),
    "bf16_tied": (
        llama_debug(dtype=jnp.bfloat16, tie_embeddings=True, max_seq_len=S), {}, 128,
        2e-2, 5e-2,
    ),
    "router_terms": (
        llama_moe_debug(router_z_coef=1e-3, **F32), {"mask": "holes"}, 128, 1e-5, 2e-4,
    ),
    "plain_path_s_not_a_multiple": (llama_debug(**F32), {"s": 192}, 0, 1e-5, 1e-4),
    "explicit_chunk_of_32": (llama_debug(**F32), {}, 32, 1e-5, 1e-4),
}


@pytest.mark.parametrize("name", list(CASES))
def test_loss_and_gradients_match_the_full_logits_reference(name, monkeypatch):
    """The loss, and the gradient of every parameter (the head, a tied
    table, and through ``dh`` the whole trunk)."""
    cfg, batch_kw, chunk, loss_rtol, grad_tol = CASES[name]
    monkeypatch.setattr(train, "_LOSS_CHUNK", chunk)
    model = build_model(cfg, None)
    batch = _batch(cfg, **batch_kw)
    params = model.init(jax.random.PRNGKey(0), batch["inputs"])["params"]
    assert params["embed"]["embedding"].dtype == jnp.float32

    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p: train._loss_fn(model, p, batch["inputs"], batch["targets"], batch["mask"])
    ))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _reference_loss(model, p, batch)
    ))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=loss_rtol, atol=1e-6)
    assert _rel_l2(got_grads, want_grads) < grad_tol
    assert all(
        g.dtype == p.dtype for g, p in zip(
            jax.tree_util.tree_leaves(got_grads), jax.tree_util.tree_leaves(params)
        )
    )
    if name == "all_zero_mask":
        assert float(got) == 0.0
        assert not any(np.asarray(g).any() for g in jax.tree_util.tree_leaves(got_grads))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_hidden_state_and_head_gradients_of_the_function_itself(dtype):
    """``_head_loss_sum`` alone: d/dh in the compute dtype, d/dw float32
    whatever the compute dtype, both against float32 full logits."""
    H, V, C = 64, 384, 64
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    h = jax.random.normal(k[0], (B, S, H), jnp.float32).astype(dtype)
    w = 0.1 * jax.random.normal(k[1], (H, V), jnp.float32)
    targets = jax.random.randint(k[2], (B, S), 0, V)
    mask = (jax.random.uniform(k[3], (B, S)) > 0.3).astype(jnp.float32)

    def reference(h, w):
        logits = jnp.einsum(
            "bsh,hv->bsv", h.astype(jnp.float32),
            w.astype(dtype).astype(jnp.float32), precision="highest",
        )
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return 0.5 * (losses * mask).sum()

    want, (want_dh, want_dw) = jax.value_and_grad(reference, argnums=(0, 1))(h, w)
    got, (dh, dw) = jax.value_and_grad(
        lambda h, w: 0.5 * train._head_loss_sum(h, w, targets, mask, C),
        argnums=(0, 1),
    )(h, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(float(got), float(want), rtol=tol)
    assert dh.dtype == dtype and dw.dtype == jnp.float32
    assert _rel_l2(dh, want_dh) < tol and _rel_l2(dw, want_dw) < tol
    # Not differentiated, the same value from the forward scan alone.
    alone = train._head_loss_sum(h, w, targets, mask, C)
    np.testing.assert_allclose(float(alone), 2 * float(got), rtol=1e-6)


def test_two_device_mesh_with_the_head_sharded_over_the_vocabulary(monkeypatch):
    monkeypatch.setattr(train, "_LOSS_CHUNK", 64)
    mesh = make_mesh(dp=1, fsdp=1, sp=1, tp=2)
    cfg = llama_debug(**F32)
    model = build_model(cfg, mesh)
    state, shardings = init_train_state(model, mesh, jax.random.PRNGKey(0), (B, S))
    head = state.params["lm_head"]["kernel"]
    assert head.sharding.shard_shape(head.shape) == (cfg.hidden_size, cfg.vocab_size // 2)
    batch = _batch(cfg, mask="holes")
    loss, grads = make_grad_step(model, mesh, shardings)(state.params, batch)
    host_params = jax.device_get(state.params)
    want, want_grads = jax.value_and_grad(
        lambda p: _reference_loss(model, p, batch)
    )(host_params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert _rel_l2(jax.device_get(grads), want_grads) < 1e-4
    assert grads["lm_head"]["kernel"].sharding == head.sharding


def test_accum_steps_2_means_the_microbatches_gradients(monkeypatch):
    """Plain SGD at rate 1 hands the step's gradient back as the
    parameters' change: rows k::2 each under their own mask count, then
    the mean."""
    monkeypatch.setattr(train, "_LOSS_CHUNK", 64)
    mesh = make_mesh(dp=1, fsdp=1, sp=1, tp=1)
    cfg = llama_debug(**F32)
    model = build_model(cfg, mesh)
    opt = optax.sgd(1.0)
    state, shardings = init_train_state(
        model, mesh, jax.random.PRNGKey(0), (4, S), optimizer=opt
    )
    batch = _batch(cfg, b=4, mask="holes")
    step = make_train_step(
        model, mesh, shardings, optimizer=opt, donate=False, accum_steps=2
    )
    new_state, metrics = step(state, batch)
    micro = [{k: v[i::2] for k, v in batch.items()} for i in range(2)]
    outs = [
        jax.value_and_grad(lambda p, mb=mb: _reference_loss(model, p, mb))(state.params)
        for mb in micro
    ]
    want_loss = np.mean([float(v) for v, _ in outs])
    want_grads = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, outs[0][1], outs[1][1])
    got_grads = jax.tree_util.tree_map(
        lambda old, new: old - new, state.params, new_state.params
    )
    np.testing.assert_allclose(float(metrics["loss"]), want_loss, rtol=1e-5)
    assert _rel_l2(got_grads, want_grads) < 1e-3  # a difference of parameters


def _equations(jaxpr, inside_scan=False):
    """(inside a scan?, equation) through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        yield inside_scan, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, inside_scan or eqn.primitive.name == "scan")


def _vocab_wide_dots(jaxpr, V):
    """Every ``dot_general`` with a ``V``-sized dimension among its
    operands or its result."""
    return [
        (inside, eqn) for inside, eqn in _equations(jaxpr)
        if eqn.primitive.name == "dot_general"
        and any(V in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
    ]


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_three_vocabulary_wide_matmuls_a_chunk_and_none_outside_the_scan(tied, monkeypatch):
    """The differentiated loss holds the logits, dh and dW matmuls in one
    scan body and no vocabulary-wide matmul anywhere else: none for logits
    recomputed, none in the backward pass. dW's is accumulated in float32."""
    monkeypatch.setattr(train, "_LOSS_CHUNK", 64)
    cfg = llama_debug(
        dtype=jnp.bfloat16, tie_embeddings=tied, max_seq_len=S, vocab_size=384
    )
    model = build_model(cfg, None)
    batch = _batch(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch["inputs"])["params"]
    )
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p: train._loss_and_metrics(
            model, p, batch["inputs"], batch["targets"], batch["mask"]
        ),
        has_aux=True,
    ))(params)
    dots = _vocab_wide_dots(jaxpr.jaxpr, cfg.vocab_size)
    assert [inside for inside, _ in dots] == [True] * 3, dots
    results = sorted(
        (eqn.outvars[0].aval.shape, str(eqn.outvars[0].aval.dtype)) for _, eqn in dots
    )
    assert results == sorted([
        ((B * 64, cfg.vocab_size), "float32"),  # logits, a chunk's rows as one axis
        ((B * 64, cfg.hidden_size), "float32"),  # dh, before its cast
        ((cfg.hidden_size, cfg.vocab_size), "float32"),  # dW
    ])


# (B, S, V) of the benchmark's five configurations -> tokens a row a chunk.
@pytest.mark.parametrize("shape, want", [
    ((4, 4096, 32000), 512),  # mistral-7b-l1: 2048 rows, 262 MB of logits
    ((2, 8192, 92544), 1024),  # internlm2-1.8b-l3: 2048 rows, 758 MB
    ((4, 4096, 50304), 512),  # olmoe-1b-7b-l1: 412 MB
    ((2, 8192, 16384), 1024),  # nemotron3-nano-30b-l9e8 and lfm2-8b-a1b-l5e8
    ((2, 256, 256), 256),  # the tests' own sizes: one chunk
    ((8, 1024, 32000), 256),  # chip_smoke's wider batch
    ((2, 8192, 262144), 512),  # the byte cap binds: 1024 rows, 1 GiB
    ((64, 4096, 131072), 128),  # nothing fits: the smallest there is
    ((1, 100, 256), 100),  # shorter than 128: one chunk
    ((1, 200, 256), 128),  # no multiple of 128 divides it: the plain path
    ((1, 384, 256), 384),
], ids=str)
def test_the_chunk_is_a_function_of_the_shapes(shape, want):
    assert train.loss_chunk(*shape) == want


def test_the_knob_overrides_the_rule_and_zero_means_derived(monkeypatch):
    from torchft_tpu import knobs

    assert knobs.KNOBS["TORCHFT_LOSS_CHUNK"].default == "0"
    cfg = llama_debug(**F32)
    model = build_model(cfg, None)
    batch = _batch(cfg)

    def scan_lengths():
        jaxpr = jax.make_jaxpr(
            lambda p: train._loss_fn(
                model, p, batch["inputs"], batch["targets"], batch["mask"]
            )
        )(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), batch["inputs"])["params"]
        ))
        return [
            eqn.params["length"] for _, eqn in _equations(jaxpr.jaxpr)
            if eqn.primitive.name == "scan"
        ]

    monkeypatch.setattr(train, "_LOSS_CHUNK", 0)
    assert scan_lengths()[-1] == 1  # S = 256: the rule gives one chunk
    monkeypatch.setattr(train, "_LOSS_CHUNK", 32)
    assert scan_lengths()[-1] == 8
    monkeypatch.setattr(train, "_LOSS_CHUNK", 4096)  # longer than the row
    assert scan_lengths()[-1] == 1
