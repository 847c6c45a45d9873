"""OLMoE's layer in the program (models/llama.py: the sorted dropless
expert dispatch, QK-norm over the whole projection; parallel/train.py:
both router losses and the routing counters) against the plain reference
of benchmark/arch/olmoe, at a tiny size in float32 on the CPU, and each
property of the published model pinned so that the other reading of it
fails."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from torchft_tpu.models.llama import (
    LlamaConfig, MoEMLP, Transformer, llama_debug, olmoe_1b_7b,
)
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import (
    _loss_and_metrics, _loss_fn, build_model, init_train_state,
    make_grad_step, make_train_step,
)

adapter = cells.arch_module("olmoe", "adapter")
reference = cells.arch_module("olmoe", "reference")

# OLMoE's shape at a size the CPU holds: 8 experts of width 32, top-2.
TINY = dict(
    model_type="olmoe", attention_bias=False, clip_qkv=None, rope_scaling=None,
    norm_topk_prob=False, hidden_act="silu", hidden_size=64, intermediate_size=32,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    vocab_size=320, max_position_embeddings=128, rope_theta=1e4,
    rms_norm_eps=1e-5, tie_word_embeddings=False, num_experts=8,
    num_experts_per_tok=2, router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
    run={"attn_impl": "dense", "compute_dtype": "float32", "param_dtype": "float32"},
)
SEQ = 64


@functools.lru_cache(maxsize=None)
def _setup(seed=0):
    config = TINY
    cfg = dataclasses.replace(adapter.model_config(config, SEQ), remat=False)
    model = Transformer(cfg)
    key = jax.random.PRNGKey(seed)
    toks = jax.random.randint(key, (2, SEQ + 1), 0, config["vocab_size"])
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
             "mask": jnp.ones((2, SEQ), jnp.int32)}
    params = model.init(key, batch["inputs"])["params"]
    # scales away from 1 so that a norm over the wrong axis shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1.0 + 0.3 * jax.random.normal(key, a.shape))
        if "norm" in jax.tree_util.keystr(path) else a, params,
    )
    return model, params, batch


def _system(model, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: _loss_fn(model, p, batch["inputs"], batch["targets"], batch["mask"])
    ))(params)


@functools.lru_cache(maxsize=None)
def _both(seed=0):
    """(loss, gradients) of the program and of the reference on one sample."""
    model, params, batch = _setup(seed)
    return _system(model, params, batch), reference.loss_and_grads(params, batch, TINY)


def _worst(g_sys, g_ref):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())),
        g_sys, g_ref,
    )
    return max(jax.tree_util.tree_leaves(errs)), errs


@pytest.mark.parametrize("seed", [0, 1])
def test_loss_and_every_gradient_leaf_agree_with_the_reference(seed):
    params = _setup(seed)[1]
    (l_sys, g_sys), (l_ref, g_ref) = _both(seed)
    # float32 on both sides and the same top-2 choices (no near-tie at this
    # size and seed): what is left is the order of summation.
    assert abs(float(l_sys) - float(l_ref)) < 1e-5
    worst, errs = _worst(g_sys, g_ref)
    assert worst < 1e-5, errs
    assert set(params["layers"]["attn"]) >= {"q_norm", "k_norm"}
    assert set(params["layers"]["mlp"]) == {
        "router", "experts_gate", "experts_up", "experts_down"}


def test_remat_and_the_odd_length_path_give_the_same_loss():
    model, params, batch = _setup()
    l0, g0 = _both()[0]
    remat = Transformer(dataclasses.replace(model.cfg, remat=True))
    l1, g1 = _system(remat, params, batch)
    assert abs(float(l0) - float(l1)) < 1e-6 and _worst(g1, g0)[0] < 1e-5
    odd = {k: v[:, :-1] for k, v in batch.items()}  # 63 tokens: full logits
    l_ref, g_ref = reference.loss_and_grads(params, odd, TINY)
    l_odd, g_odd = _system(model, params, odd)
    assert abs(float(l_odd) - float(l_ref)) < 1e-5 and _worst(g_odd, g_ref)[0] < 1e-5


def _moe(cfg, x, seed=1, router=None):
    moe = MoEMLP(cfg)
    p = moe.init(jax.random.PRNGKey(seed), x)["params"]
    if router is not None:
        p = dict(p, router={"kernel": router})
    y, inter = moe.apply({"params": p}, x, mutable=["intermediates"])
    sown = {k: float(v[0]) for k, v in inter["intermediates"].items()}
    return p, y, sown


def test_sorted_dispatch_equals_a_loop_over_tokens_and_experts():
    cfg = adapter.model_config(TINY, SEQ)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))
    p, y, sown = _moe(cfg, x)
    xf = np.asarray(x, np.float64).reshape(32, 64)
    z = xf @ np.asarray(p["router"]["kernel"], np.float64)
    probs = np.exp(z - z.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(xf)
    for t in range(32):
        for e in np.argsort(-probs[t])[:2]:  # top-2, gates as they are
            gate = xf[t] @ np.asarray(p["experts_gate"][e], np.float64)
            up = xf[t] @ np.asarray(p["experts_up"][e], np.float64)
            hidden = gate / (1.0 + np.exp(-gate)) * up
            want[t] += probs[t, e] * (hidden @ np.asarray(p["experts_down"][e], np.float64))
    np.testing.assert_allclose(np.asarray(y).reshape(32, 64), want, atol=1e-6)
    assert sown["moe_dropped"] == 0.0


def test_nothing_is_dropped_when_every_token_chooses_the_same_experts():
    """A router forced onto experts 0 and 1: the capacity form would drop
    most assignments; here every one is computed, and the counters say
    what happened."""
    cfg = adapter.model_config(TINY, SEQ)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))) + 0.1
    forced = jnp.zeros((64, 8)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    p, y, sown = _moe(cfg, x, router=forced)
    assert sown["moe_dropped"] == 0.0
    assert sown["moe_max_load"] == pytest.approx(8 / 2)  # E / K: two experts take all
    assert bool(jnp.all(jnp.any(y != 0.0, axis=-1)))  # every token got its experts
    one = dataclasses.replace(cfg, num_experts_per_tok=1)
    _, _, sown = _moe(one, x, router=forced)
    assert sown["moe_max_load"] == pytest.approx(8.0)  # = E: one expert takes all
    # the capacity form on the same routing drops, and counts it
    capped = dataclasses.replace(one, expert_capacity_factor=1.0)  # C = 16/8 = 2
    _, y_c, sown_c = _moe(capped, x, router=forced)
    assert sown_c["moe_dropped"] == 32 - 2 * 2  # two places a sequence
    assert sown_c["moe_max_load"] == pytest.approx(8.0)
    assert int(jnp.sum(jnp.any(y_c != 0.0, axis=-1))) == 4


def test_qk_norm_is_over_the_whole_projection_not_per_head(monkeypatch):
    model, params, batch = _setup()
    attn = params["layers"]["attn"]
    assert attn["q_norm"]["scale"].shape == (2, 4 * 16)  # layers x heads*head_dim
    assert attn["k_norm"]["scale"].shape == (2, 4 * 16)

    def per_head(x, scale, eps):  # the other reading: RMS over each head's 16
        b, s, w = x.shape
        xh = x.reshape(b, s, 4, 16)
        xh = xh * jax.lax.rsqrt(jnp.mean(xh * xh, axis=-1, keepdims=True) + eps)
        return xh.reshape(b, s, w) * scale

    l_sys, l_ref = float(_both()[0][0]), float(_both()[1][0])
    attention = reference._attention

    def per_head_attention(*a):  # the only norms inside it are q's and k's
        with monkeypatch.context() as m:
            m.setattr(reference, "_rms_norm", per_head)
            return attention(*a)

    monkeypatch.setattr(reference, "_attention", per_head_attention)
    l_per_head = float(reference.loss_and_grads(params, batch, TINY)[0])
    assert abs(l_sys - l_ref) < 1e-5
    assert abs(l_sys - l_per_head) > 1e-3


def test_gates_are_not_renormalised(monkeypatch):
    """With norm_topk_prob semantics the two gates of a token sum to 1;
    OLMoE's do not, and the output is smaller by the token's top-2 mass."""
    cfg = adapter.model_config(TINY, SEQ)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    p, y, _ = _moe(cfg, x)
    probs = jax.nn.softmax(x.reshape(16, 64) @ p["router"]["kernel"], axis=-1)
    mass = jax.lax.top_k(probs, 2)[0].sum(-1)  # < 1
    assert float(mass.max()) < 0.9
    # the capacity form renormalises (and with ample room drops nothing):
    renorm = dataclasses.replace(cfg, expert_capacity_factor=8.0)
    y_r = MoEMLP(renorm).apply({"params": p}, x, mutable=["intermediates"])[0]
    np.testing.assert_allclose(
        np.asarray(y[0]), np.asarray(y_r[0] * mass[:, None]), atol=1e-6)
    # and the reference given norm_topk_prob's gates is another model
    model, params, batch = _setup()
    g_sys = _both()[0][1]
    route = reference.route

    def renormalised(m, p_, c):
        z, g, idx = route(m, p_, c)
        return z, g / g.sum(-1, keepdims=True), idx

    monkeypatch.setattr(reference, "route", renormalised)
    g_other = reference.loss_and_grads(params, batch, TINY)[1]
    assert _worst(g_sys, g_other)[0] > 0.1


def test_balance_term_is_one_at_uniform_routing_and_z_loss_by_hand():
    cfg = adapter.model_config(TINY, SEQ)
    # A router of zeros: p = 1/8 for every expert, so P_e = 1/8 and
    # sum_e f_e = 1 whatever top_k picks at the tie: E * sum f_e P_e = 1;
    # logsumexp of eight zeros is log 8.
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))
    _, _, sown = _moe(cfg, x, router=jnp.zeros((64, 8)))
    assert sown["router_aux"] == pytest.approx(1.0, abs=1e-6)
    assert sown["router_z"] == pytest.approx(np.log(8.0) ** 2, rel=1e-6)
    # 2 tokens x 3 experts, top-1, by hand: logits [[2, 0, 0], [0, 1, 0]]
    three = dataclasses.replace(
        cfg, hidden_size=3, num_experts=3, num_experts_per_tok=1, intermediate_size=4)
    _, _, sown = _moe(three, jnp.eye(3)[None, :2] * jnp.array([2.0, 1.0, 0.0]),
                      router=jnp.eye(3))
    z = np.array([[2.0, 0, 0], [0, 1.0, 0]])
    lse = np.log(np.exp(z).sum(-1))
    probs = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    f = np.array([0.5, 0.5, 0.0])  # token 0 -> expert 0, token 1 -> expert 1
    assert sown["router_z"] == pytest.approx(float((lse ** 2).mean()), rel=1e-6)
    assert sown["router_aux"] == pytest.approx(float(3 * (f * probs.mean(0)).sum()), rel=1e-6)
    assert sown["moe_max_load"] == pytest.approx(1.5)


def test_both_router_terms_are_in_the_loss_with_their_coefficients():
    model, params, batch = _setup()
    args = (params, batch["inputs"], batch["targets"], batch["mask"])
    loss, m = _loss_and_metrics(model, *args)
    assert set(m) == {"router_aux", "router_z", "moe_max_load", "moe_dropped"}
    bare = Transformer(dataclasses.replace(model.cfg, router_aux_coef=0.0, router_z_coef=0.0))
    ce = float(_loss_fn(bare, *args))
    assert float(loss) == pytest.approx(
        ce + 0.01 * float(m["router_aux"]) + 0.001 * float(m["router_z"]), abs=1e-6)
    assert float(m["router_aux"]) >= 1.0 and float(m["router_z"]) > 0.0


def test_the_steps_metrics_carry_the_four_counters():
    cfg = adapter.model_config(TINY, SEQ)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, SEQ))
    _, _, batch = _setup()
    want = {"loss", "grad_norm", "router_aux", "router_z", "moe_max_load", "moe_dropped"}
    (loss, m), grads = make_grad_step(model, mesh, sh, with_metrics=True)(state.params, batch)
    loss2, grads2 = make_grad_step(model, mesh, sh)(state.params, batch)
    assert float(loss) == float(loss2) and set(m) == want - {"loss", "grad_norm"}
    for accum in (1, 2):
        step = make_train_step(model, mesh, sh, donate=False, accum_steps=accum)
        _, metrics = step(state, batch)
        assert set(metrics) == want
        assert float(metrics["moe_dropped"]) == 0.0
        assert 1.0 <= float(metrics["moe_max_load"]) <= 8.0
    # a dense model's step reports what it always did
    dense = build_model(llama_debug(), mesh)
    dstate, dsh = init_train_state(dense, mesh, jax.random.PRNGKey(0), (2, SEQ))
    dbatch = {k: jnp.minimum(v, 255) for k, v in batch.items()}
    _, metrics = make_train_step(dense, mesh, dsh, donate=False)(dstate, dbatch)
    assert set(metrics) == {"loss", "grad_norm"}
    (_, m), _ = make_grad_step(dense, mesh, dsh, with_metrics=True)(dstate.params, dbatch)
    assert m == {}


def test_a_sharded_mesh_computes_the_same_step():
    """fsdp=2 x tp=2 on four virtual devices: GSPMD partitions the sort,
    the gathers and the grouped matmuls (train_hsdp.py --model olmoe)."""
    from torchft_tpu.parallel import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = adapter.model_config(TINY, SEQ)
    _, _, batch = _setup()
    losses = []
    for mesh in (auto_mesh(1, devices=jax.devices()[:1]), make_mesh(fsdp=2, tp=2)):
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, SEQ))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, batch)
        losses.append((float(metrics["loss"]), float(metrics["grad_norm"])))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


def _jaxpr(cfg):
    model = Transformer(cfg)
    toks = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), toks))["params"]
    return str(jax.make_jaxpr(
        lambda p: jax.value_and_grad(lambda q: _loss_fn(model, q, toks, toks, toks))(p)
    )(params))


def test_a_dense_configuration_traces_to_the_same_program_whatever_the_new_fields_say():
    """The fields this model added are read by the expert layer and by
    QK-norm only: a dense configuration built without naming them (the
    old way) and one that sets every expert field gives one jaxpr."""
    old_way = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
    )
    assert (old_way.qk_norm, old_way.router_z_coef, old_way.expert_capacity_factor) == (
        False, 0.0, 1.25)
    set_all = dataclasses.replace(
        old_way, expert_capacity_factor=None, router_z_coef=0.001,
        num_experts_per_tok=8,
    )
    assert _jaxpr(old_way) == _jaxpr(set_all)
    assert _jaxpr(old_way) != _jaxpr(dataclasses.replace(old_way, qk_norm=True))
    assert "ragged_dot" not in _jaxpr(old_way)


def test_the_preset_is_the_published_model():
    cfg = olmoe_1b_7b()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.vocab_size, cfg.max_seq_len) == (
        2048, 16, 16, 16, 128, 1024, 50304, 4096)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_capacity_factor,
            cfg.qk_norm, cfg.router_aux_coef, cfg.router_z_coef, cfg.rope_theta) == (
        64, 8, None, True, 0.01, 0.001, 1e4)
    assert olmoe_1b_7b(num_layers=1).num_layers == 1
    # the benchmark's configuration is this preset at one layer
    cell = cells.load_cell("olmoe-raw")
    assert cell.adapter.model_config(cell.config, 4096) == olmoe_1b_7b(
        num_layers=1, attn_impl="flash", dtype=jnp.dtype("bfloat16"),
        param_dtype=jnp.dtype("float32"))


def test_lower_precision_than_stated_fails_the_references_tolerance():
    """The tolerances must fail a run in the precision below the stated
    one. At this size, float32 against the reference with every matmul's
    operands rounded to bfloat16 is already outside 1e-5; against
    float8's three mantissa bits it is outside the chip's tolerance."""
    model, params, batch = _setup()
    g_ref = _both()[1][1]
    _, g_bf16 = reference.loss_and_grads(params, batch, TINY, jnp.bfloat16)
    _, g_f8 = reference.loss_and_grads(params, batch, TINY, jnp.float8_e4m3fn)
    assert _worst(g_bf16, g_ref)[0] > 1e-3
    assert _worst(g_f8, g_ref)[0] > reference.GRAD_REL_L2_TOL
