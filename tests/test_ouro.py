"""Ouro's looped language model (one ``layer_pattern`` stack applied
``loop_steps`` times on one set of parameters, an exit gate over the
depths, the expected-cross-entropy-less-entropy loss) against its plain
reference at a small size on the CPU, in float32 with seeded weights: loss
and every gradient leaf, the gate's among them and its bias (the last row
of its one leaf) by itself; the loop tied to the
model (shared weights against a stack of copies, the scan against a Python
loop over one module); the exit distribution; the head's pass that hands
the rows' cross-entropies to the weights; a task where depth helps. The
step, the harness's check and the presets: tests/test_ouro_step.py."""

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark import cells
from benchmark.tests import test_ouro_reference as _reference_tests
from torchft_tpu.models.llama import (
    MixerLayer,
    RMSNorm,
    Transformer,
    ouro_debug,
    rope_table,
)
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import (
    _head_loss_rows,
    _head_loss_sum,
    _loss_and_metrics,
    build_model,
    exit_distribution,
    init_train_state,
    make_eval_step,
    make_train_step,
    state_shardings,
)
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("ouro", "adapter")
reference = cells.arch_module("ouro", "reference")
tiny = _reference_tests.tiny

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj
sound = _reference_tests.sound  # their module-scoped fixture

# The CPU comparison's limit on a gradient leaf: float32 on both sides, so
# what is left is the order of the sums (the worst leaf reads 1e-6 to 1e-5).
CPU_GRAD_TOL = 2e-4
FLASH = dict(attn_impl="flash", flash_min_seq=16, flash_block_q=16, flash_block_k=16)
# a layer: 4 projections and 2 norms, 3 matrices and 2 norms; the table,
# the head, the final norm and the gate's one
LEAVES = 2 * 11 + 3 + 1


def _setup(c, seq, batch=2, seed=0, **cfg_overrides):
    cfg = dataclasses.replace(adapter.model_config(c, seq), **{"remat": False, **cfg_overrides})
    model = build_model(cfg, None)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    data["mask"] = data["mask"].at[0, 3].set(0)
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    # a gate off its symmetric start, norms' scales off 1
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.3 if path[-1].key == "scale" else a, params)
    gate = params["exit_gate"]["kernel"]
    params["exit_gate"]["kernel"] = gate.at[-1, 0].add(0.3)  # its last row is the bias
    return model, params, data


def _loss_grads(model, params, data):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: _loss_and_metrics(
                model, p, data["inputs"], data["targets"], data["mask"]),
            has_aux=True,
        ))(params)


@pytest.fixture(scope="module")
def referred():
    """Two layers applied four times on one seeded sample of 32 tokens, and
    the reference's loss and gradients there, computed once."""
    c, seq = tiny(), 32
    _, params, data = _setup(c, seq)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    return c, seq, params, data, loss_ref, grads_ref


@pytest.mark.parametrize("overrides", [{}, dict(FLASH, remat=True)], ids=["plain", "flash"])
def test_loss_and_every_gradient_leaf_match_the_reference(referred, overrides):
    """The whole model's loss and every gradient leaf, the exit gate's
    included and not zero, its bias (the leaf's last row: one number in
    2,049 on the chip, where the leaf's norm hides it) held by itself; under per-sub-layer remat and with the flash
    kernel (interpreted, tiles of 16) inside the scanned body too."""
    c, seq, params, data, loss_ref, grads_ref = referred
    model, _, _ = _setup(c, seq, **overrides)
    (loss, metrics), grads = _loss_grads(model, params, data)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    assert len(errs) == LEAVES and max(errs.values()) < CPU_GRAD_TOL, errs
    gate, gate_ref = grads["exit_gate"]["kernel"], grads_ref["exit_gate"]["kernel"]
    assert gate.shape == (c["hidden_size"] + 1, 1)
    assert float(jnp.linalg.norm(gate[:-1])) > 1e-3 and abs(float(gate_ref[-1, 0])) > 1e-4
    assert float(gate[-1, 0]) == pytest.approx(float(gate_ref[-1, 0]), rel=CPU_GRAD_TOL)
    assert set(metrics) == {
        "loop_ce_1", "loop_ce_2", "loop_ce_3", "loop_ce_4",
        "loop_exit_step_mean", "loop_exit_entropy", "loop_p_last"}
    # the metrics are the reference's own quantities
    h, z = reference.steps(params, data["inputs"], c)
    p = reference.exit_probabilities(z)
    mask = data["mask"].astype(jnp.float32)
    over = lambda x: float((x * mask).sum() / mask.sum())  # noqa: E731
    assert float(metrics["loop_exit_entropy"]) == pytest.approx(over(reference.entropy(p)), rel=1e-4)
    assert float(metrics["loop_p_last"]) == pytest.approx(over(p[-1]), rel=1e-4)
    assert float(metrics["loop_exit_step_mean"]) == pytest.approx(
        over(sum((t + 1) * p[t] for t in range(4))), rel=1e-4)


# -- the loop tied to the model ------------------------------------------------------


def _by_hand(cfg, copies, rest, data):
    """The looped model's loss as a Python loop over the program's own
    layer modules, step t's layers reading ``copies[t]``: the stack a
    looped model is by definition, 4L layers deep, whose copies may hold
    different values. Full logits, the exit distribution from the
    program's function."""
    tokens, mask = data["inputs"], data["mask"].astype(jnp.float32)
    cos, sin = rope_table(
        jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape),
        cfg.head_dim, cfg.rope_theta, cfg.dtype)
    x = rest["embed"]["embedding"][tokens]
    hs, zs = [], []
    for step in copies:
        for i, kind in enumerate(cfg.layer_pattern):
            args = (cos, sin) if kind == "*" else ()
            x = MixerLayer(cfg, kind).apply({"params": step[f"layers_{i}"]}, x, *args)
        x = RMSNorm(cfg.norm_eps).apply({"params": rest["final_norm"]}, x)
        hs.append(x)
        gate = rest["exit_gate"]["kernel"]
        zs.append(x @ gate[:-1, 0] + gate[-1, 0])
    log_p, p = exit_distribution(jnp.stack(zs))
    ce = jnp.stack([
        optax.softmax_cross_entropy_with_integer_labels(
            h @ rest["lm_head"]["kernel"], data["targets"]) for h in hs])
    per_token = (p * ce).sum(axis=0) + cfg.loop_entropy_coef * (p * log_p).sum(axis=0)
    return (per_token * mask).sum() / mask.sum()


@pytest.fixture(scope="module")
def tied():
    """The program's loss and gradients on shared weights, and the by-hand
    stack's on four copies of them (each copy its own argument)."""
    c, seq = tiny(), 24
    model, params, data = _setup(c, seq)
    layers = {k: v for k, v in params.items() if k.startswith("layers_")}
    rest = {k: v for k, v in params.items() if not k.startswith("layers_")}
    (loss, _), grads = _loss_grads(model, params, data)
    with jax.default_matmul_precision("highest"):
        by_hand, (g_copies, g_rest) = jax.jit(jax.value_and_grad(
            lambda copies, rest: _by_hand(model.cfg, copies, rest, data), argnums=(0, 1),
        ))([layers] * 4, rest)
    return float(loss), grads, float(by_hand), g_copies, g_rest


def test_four_steps_on_shared_weights_are_the_stack_of_four_copies(tied):
    """T = 4 on shared weights equals the 4L-layer unshared stack whose
    copies hold the same values; a shared leaf's gradient is the sum of its
    four copies', which differ; every unlooped leaf's is the stack's."""
    loss, grads, by_hand, g_copies, g_rest = tied
    assert loss == pytest.approx(by_hand, rel=1e-6)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *g_copies)
    shared = {k: v for k, v in grads.items() if k.startswith("layers_")}
    errs = _leaf_errors(shared, summed)
    assert len(errs) == 22 and max(errs.values()) < 1e-5, errs
    errs = _leaf_errors({k: grads[k] for k in g_rest}, g_rest)
    assert len(errs) == 4 and max(errs.values()) < 1e-5, errs
    # no visit's gradient is the sum: each of the four is a part of it
    first = _leaf_errors(g_copies[0], summed)
    assert min(first.values()) > 0.1


def test_the_traced_program_holds_one_scan_over_the_steps():
    """One scan of four steps, and the stack's two attentions' softmaxes
    inside it alone: the layers are in the program once."""
    model, params, data = _setup(tiny(), 24)
    jaxpr = jax.make_jaxpr(
        lambda p: model.apply({"params": p}, data["inputs"]))(params)
    scans = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "scan" and e.params["length"] == 4]
    assert len(scans) == 1
    outside = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("exp", "dot_general")]
    assert len(outside) == 1, outside  # the head's matmul on step T's states


def test_one_step_without_the_entropy_term_is_the_sandwich_stack_to_the_bit():
    """``loop_steps`` 1 and beta 0 are the fields' defaults: the model is
    the unlooped sandwich stack (no exit gate in its tree, no loop metric),
    its loss and gradients equal to the bit those of the same stack
    configured without a word about loops."""
    c = tiny()
    looped = adapter.model_config(c, 24)
    one = dataclasses.replace(looped, loop_steps=1, loop_entropy_coef=0.0, remat=False)
    fields = {f.name: getattr(one, f.name) for f in dataclasses.fields(one)
              if not f.name.startswith("loop_")}
    plain = type(one)(**fields)
    assert plain == one and (plain.loop_steps, plain.loop_entropy_coef) == (1, 0.0)
    data = _data(c["vocab_size"], 2, 24)
    params = Transformer(one).init(jax.random.PRNGKey(0), data["inputs"])["params"]
    assert "exit_gate" not in params and "final_norm" in params
    (loss, metrics), grads = _loss_grads(Transformer(one), params, data)
    (want, _), want_grads = _loss_grads(Transformer(plain), params, data)
    assert metrics == {} and float(loss) == float(want)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)))
    # and it is the looped model's own first step: the scan's body is that stack
    looped_params = dict(params, exit_gate={"kernel": jnp.zeros((65, 1))})
    h, _ = Transformer(dataclasses.replace(looped, remat=False)).apply(
        {"params": looped_params}, data["inputs"], return_hidden=True)
    first = Transformer(one).apply({"params": params}, data["inputs"], return_hidden=True)
    assert jnp.allclose(h[0], first, rtol=1e-6, atol=1e-6)


# -- the exit distribution and the head's pass ------------------------------------------


def test_the_exit_distribution_from_logs_is_the_products():
    z = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 16)) * 4.0
    log_p, p = exit_distribution(z)
    assert jnp.allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert jnp.allclose(p, reference.exit_probabilities(z), rtol=1e-5, atol=1e-7)
    assert jnp.array_equal(p, exit_distribution(z.at[-1].set(9.0))[1])  # z_T is not read
    fresh = exit_distribution(jnp.zeros((4, 1)))[1][:, 0]
    assert jnp.allclose(fresh, jnp.array([0.5, 0.25, 0.125, 0.125]))
    for z0, at in ((30.0, 0), (-30.0, 3)):
        z = jnp.full((4, 1), z0)
        log_p, p = exit_distribution(z)
        assert jnp.allclose(p, reference.exit_probabilities(z), atol=1e-9)
        assert float(p[at, 0]) == pytest.approx(1.0, abs=1e-6)
        assert bool(jnp.all(jnp.isfinite(log_p)))  # a small p, never a 0 whose log is not
        grad = jax.grad(lambda z: -(exit_distribution(z)[1] * exit_distribution(z)[0]).sum())(z)
        assert bool(jnp.all(jnp.isfinite(grad)))


def test_the_heads_rows_pass_hands_the_weights_their_cross_entropies():
    """``_head_loss_rows`` against ``jax.grad`` of the plain full-logits
    form: the sum, the rows, and the cotangents of the hidden states, the
    head and the WEIGHTS (each row's cross-entropy), under a cotangent that
    is not 1; its sum and gradients of h and w are ``_head_loss_sum``'s."""
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    h = jax.random.normal(key[0], (3, 256, 32))
    w = jax.random.normal(key[1], (32, 96)) * 0.2
    targets = jax.random.randint(key[2], (3, 256), 0, 96)
    weights = jax.random.uniform(key[3], (3, 256))

    def plain(h, w, weights):
        ce = optax.softmax_cross_entropy_with_integer_labels(h @ w, targets)
        return 1.7 * (ce * weights).sum(), ce

    def chunked(h, w, weights):
        total, rows = _head_loss_rows(h, w, targets, weights, 128)
        return 1.7 * total, rows

    with jax.default_matmul_precision("highest"):
        (want, ce), want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True)(
            h, w, weights)
        (got, rows), grads = jax.jit(jax.value_and_grad(
            chunked, argnums=(0, 1, 2), has_aux=True))(h, w, weights)
        old, old_grads = jax.value_and_grad(
            lambda h, w: 1.7 * _head_loss_sum(h, w, targets, weights, 128), argnums=(0, 1))(h, w)
    assert float(got) == pytest.approx(float(want), rel=1e-5) and float(got) == float(old)
    assert jnp.allclose(rows, ce, rtol=1e-4, atol=1e-5)
    for a, b in zip(grads, want_grads):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5
    assert jnp.allclose(grads[2], 1.7 * ce, rtol=1e-4, atol=1e-5)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(grads[:2], old_grads))
    # the rows themselves carry no gradient: one pass forms gradients under the weights alone
    none = jax.grad(lambda h: _head_loss_rows(h, w, targets, weights, 128)[1].sum())(h)
    assert float(jnp.abs(none).max()) == 0.0


# -- what the gate learns, what evaluation reads -----------------------------------------


def test_on_a_task_where_depth_helps_the_gate_moves_the_exit_deeper():
    """Targets a function of the token two places back, ONE attention layer
    applied four times: the first step cannot solve it and the later ones
    can, so within a few dozen steps the steps' cross-entropies part
    (``loop_ce_spread`` off 0) and the gate, which learns from those
    differences through the weights' cotangent, moves the mean exit step up
    from a fresh gate's (about 1.9)."""
    vocab, seq, batch = 16, 24, 16
    cfg = ouro_debug(
        dtype=jnp.float32, vocab_size=vocab, hidden_size=32, intermediate_size=64,
        num_layers=1, layer_pattern="*D", num_heads=2, num_kv_heads=2, head_dim=16)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    opt = optax.adam(1e-2)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (batch, seq), opt)
    step = make_train_step(model, mesh, sh, opt, donate=False)
    mask = jnp.broadcast_to((jnp.arange(seq) >= 2).astype(jnp.int32), (batch, seq))

    def data(i):
        toks = jax.random.randint(jax.random.PRNGKey(100 + i), (batch, seq), 0, vocab)
        return {"inputs": toks, "targets": (jnp.roll(toks, 2, axis=1) + 1) % vocab, "mask": mask}

    spread = lambda m: (  # noqa: E731
        max(float(m[f"loop_ce_{t}"]) for t in range(1, 5))
        - min(float(m[f"loop_ce_{t}"]) for t in range(1, 5)))
    state, first = step(state, data(0))
    # a fresh gate: a zero bias and a lecun-normal weight on unit-variance
    # rows, so z ~ N(0, 1) a position and lambda 1/2 on average
    assert float(first["loop_exit_step_mean"]) == pytest.approx(1.875, abs=0.15)
    assert 0.9 < float(first["loop_exit_entropy"]) < 1.25
    assert spread(first) < 0.2
    for i in range(1, 80):
        state, last = step(state, data(i))
    assert spread(last) > 1.0 and float(last["loop_ce_1"]) > float(last["loop_ce_4"]) + 1.0
    assert float(last["loop_exit_step_mean"]) > float(first["loop_exit_step_mean"]) + 0.5
    assert float(last["loss"]) < 0.5 * float(first["loss"])


def test_evaluation_reads_the_last_steps_logits():
    """``early_exit_threshold`` 1: no token leaves early. Plain ``__call__``
    returns step T's logits; ``make_eval_step`` the training loss."""
    c, seq = tiny(), 16
    model, params, data = _setup(c, seq)
    logits = model.apply({"params": params}, data["inputs"])
    h, z = model.apply({"params": params}, data["inputs"], return_hidden=True)
    assert h.shape == (4, 2, seq, 64) and z.shape == (4, 2, seq) and z.dtype == jnp.float32
    assert jnp.allclose(logits, h[-1] @ params["lm_head"]["kernel"], rtol=1e-5, atol=1e-5)
    assert not jnp.allclose(logits, h[0] @ params["lm_head"]["kernel"], atol=1e-2)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(model.cfg, mesh)
    evaluated = make_eval_step(model, mesh, state_shardings(model, mesh, (2, seq)))(params, data)
    assert float(evaluated) == pytest.approx(float(_loss_grads(model, params, data)[0][0]), rel=1e-6)


def test_what_a_looped_layer_sows_gains_a_step_axis():
    """A windowed layer inside the loop sows its kept share once a step:
    [T] values where an unlooped stack's layer sows one."""
    cfg = ouro_debug(dtype=jnp.float32, layer_pattern="WD", num_layers=1, sliding_window=8)
    data = _data(cfg.vocab_size, 1, 16)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), data["inputs"])["params"]
    _, sown = model.apply({"params": params}, data["inputs"], mutable=["intermediates"])
    kept = jax.tree_util.tree_leaves(sown["intermediates"])
    assert [k.shape for k in kept] == [(4, 1)] or [k.shape for k in kept] == [(4,)]
    _, metrics = _loss_and_metrics(model, params, data["inputs"], data["targets"], data["mask"])
    assert "swa_kept_share" in metrics and "loop_p_last" in metrics


@pytest.mark.parametrize("overrides,says", [
    (dict(layer_pattern=None), "layer_pattern stack's"),
    (dict(mtp_layers=1), "without prediction modules"),
    (dict(objective="block_diffusion", block_length=4), "next-token model"),
])
def test_a_loop_is_refused_where_it_is_not_built(overrides, says):
    cfg = ouro_debug(**overrides)
    with pytest.raises(ValueError, match=says):
        Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_pipelines_copy_of_the_stack_refuses_a_loop():
    from torchft_tpu.parallel.pipeline import _check_cfg

    with pytest.raises(ValueError, match="loop over the stack"):
        _check_cfg(ouro_debug(), 2)
    _check_cfg(ouro_debug(loop_steps=1), 2)
