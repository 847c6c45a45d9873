"""The seam between the compiled step and the fault-tolerant loop: the split
step (``make_split_grad_step`` then ``make_apply_step``) is the fused one
(``make_train_step``) bit for bit on every small preset; ``FTStep`` under
two real Managers commits bitwise-equal parameters; a step the gate refuses
changes nothing; the four stages one by one are the call; the heal contract
in both forms. On the CPU in float32."""

import functools
import os
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tests.test_manager import make_manager  # noqa: E402
from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.ddp import DistributedDataParallel  # noqa: E402
from torchft_tpu.ft_step import FTStep  # noqa: E402
from torchft_tpu.manager import Manager  # noqa: E402
from torchft_tpu.models import (  # noqa: E402
    joyai_flash_debug,
    lfm2_moe_debug,
    llama_debug,
    llama_moe_debug,
    nemotron_h_debug,
    olmo_hybrid_debug,
    olmoe_1b_7b,
    sdar_moe_debug,
    solar_open2_debug,
)
from torchft_tpu.parallel import auto_mesh  # noqa: E402
from torchft_tpu.parallel.train import (  # noqa: E402
    build_model,
    init_train_state,
    make_apply_step,
    make_split_grad_step,
    make_train_step,
    router_bias_abs_max,
)
from torchft_tpu.process_group import ProcessGroupSocket  # noqa: E402

SMALL = {
    "llama_debug": llama_debug,
    "llama_moe_debug": llama_moe_debug,
    "nemotron_h_debug": nemotron_h_debug,
    "lfm2_moe_debug": lfm2_moe_debug,
    "sdar_moe_debug": sdar_moe_debug,
    "joyai_flash_debug": joyai_flash_debug,
    "olmo_hybrid_debug": olmo_hybrid_debug,
    "solar_open2_debug": solar_open2_debug,
    # the published preset cut to test widths, as tests/test_olmoe.py's TINY
    "olmoe_1b_7b": functools.partial(
        olmoe_1b_7b, hidden_size=64, intermediate_size=32, num_layers=2, num_heads=4,
        num_kv_heads=4, head_dim=16, vocab_size=320, max_seq_len=128, num_experts=8,
        num_experts_per_tok=2, remat=False),
}
B, S = 2, 32


def data(vocab, seed):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0, vocab)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:], "mask": jnp.ones((B, S), jnp.int32)}


def programs(preset):
    """(cfg, state, the split step's two programs, the fused step) on one device."""
    cfg = preset(dtype=jnp.float32)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (B, S))
    return (cfg, state, make_split_grad_step(model, mesh, sh), make_apply_step(model, sh),
            make_train_step(model, mesh, sh, donate=False))


def leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def same(a, b, but=None):
    """Equal bit for bit; leaves whose name is ``but`` only to the last bits."""
    a, b = jax.tree_util.tree_leaves_with_path(a), jax.tree_util.tree_leaves_with_path(b)
    return len(a) == len(b) and all(
        np.allclose(x, y, rtol=0, atol=2e-7 * np.abs(x).max())
        if but and but in jax.tree_util.keystr(path) else np.array_equal(x, y)
        for (path, x), (_, y) in zip(a, b))


@pytest.mark.parametrize("name", list(SMALL))
def test_the_split_step_is_the_fused_step_bit_for_bit(name):
    cfg, state, grad_step, apply_step, train_step = programs(SMALL[name])
    batch = data(cfg.vocab_size, seed=1)
    fused, metrics = train_step(state, batch)
    loss, router, to_reduce = grad_step(state.params, batch)
    params, opt_state = apply_step(state.params, state.opt_state, to_reduce)
    assert float(loss) == float(metrics["loss"]) and np.isfinite(float(loss))
    # XLA's CPU backend sums the Mamba mixers' convolution-kernel gradient in another order
    # inside the larger program: 1 ulp on 3-4% of those four leaves, and nowhere else.
    but = "conv_kernel" if name == "nemotron_h_debug" else None
    assert same(params, fused.params, but) and same(opt_state, fused.opt_state, but)
    assert not same(params, state.params)
    assert set(router) == set(metrics) - {"loss", "grad_norm", "router_bias_abs_max"}
    assert ("loss_mtp" in router) == bool(cfg.mtp_layers) == (name == "joyai_flash_debug")
    assert all(float(router[k]) == float(metrics[k]) for k in router)
    # the loads leave the step only beside the gradients, and only where the recipe moves biases
    assert (to_reduce[1] is not None) == bool(cfg.router_bias_update_rate) == (
        name in ("lfm2_moe_debug", "joyai_flash_debug"))
    if cfg.router_bias_update_rate:
        assert float(router_bias_abs_max(state.params)) == 0.0
        assert float(router_bias_abs_max(params)) == pytest.approx(cfg.router_bias_update_rate)


def two_replicas(preset, tag, steps=2):
    """Two replica groups as threads under real Managers on one lighthouse,
    each an ``FTStep`` over the preset's split step, both fed ONE batch a
    step: per replica (losses, parameter leaves)."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=20000, quorum_tick_ms=50)
    barrier = threading.Barrier(2)

    def replica(r):
        cfg, state, grad_step, apply_step, _ = programs(preset)
        manager = Manager(
            pg=ProcessGroupSocket(timeout=15.0), min_replica_size=2, use_async_quorum=False,
            timeout=15.0, quorum_timeout=30.0, replica_id=f"{tag}{r}",
            lighthouse_addr=lighthouse.address(), group_rank=0, group_world_size=1,
            init_sync=False,
        )
        step = FTStep(manager, DistributedDataParallel(manager), grad_step, apply_step,
                      state.params, state.opt_state)
        losses = []
        try:
            for i in range(steps):
                barrier.wait(timeout=120)
                committed, loss, _ = step(data(cfg.vocab_size, seed=i))
                assert committed and manager.current_step() == i + 1
                losses.append(float(loss))
        finally:
            manager.shutdown()
        return losses, leaves(step.params)

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            futs = [pool.submit(replica, r) for r in range(2)]
            return [f.result(timeout=240) for f in futs]
    finally:
        lighthouse.shutdown()


@pytest.mark.timeout(300)
@pytest.mark.parametrize("name", ["llama_debug", "lfm2_moe_debug"])
def test_two_replicas_commit_bitwise_equal_parameters(name):
    (losses0, leaves0), (losses1, leaves1) = two_replicas(SMALL[name], name)
    assert losses0 == losses1 and len(losses0) == 2 and losses0[0] != losses0[1]
    assert all(np.array_equal(a, b) for a, b in zip(leaves0, leaves1))
    # what two replicas fed one batch commit is what one worker's fused step computes
    cfg, state, _, _, train_step = programs(SMALL[name])
    for i in range(2):
        state, _ = train_step(state, data(cfg.vocab_size, seed=i))
    assert all(np.allclose(a, b, rtol=1e-5, atol=1e-6)
               for a, b in zip(leaves0, leaves(state.params)))


def mocked(preset=lfm2_moe_debug, **kw):
    """An ``FTStep`` under a Manager whose control plane is mocked (the gate
    echoes the local vote; the dummy group's allreduce halves), and a batch."""
    cfg, state, grad_step, apply_step, _ = programs(preset)
    manager = make_manager(use_async_quorum=False)
    step = FTStep(manager, DistributedDataParallel(manager), grad_step, apply_step,
                  state.params, state.opt_state, **kw)
    return step, data(cfg.vocab_size, seed=1)


def test_a_step_the_gate_refuses_leaves_the_state_as_it_was():
    step, batch = mocked()
    try:
        echo = step.manager._test_client.should_commit.side_effect
        step.manager._test_client.should_commit.side_effect = lambda *a, **k: False
        params, opt_state = step.params, step.opt_state
        committed, loss, _ = step(batch)
        assert not committed and np.isfinite(float(loss))
        assert step.params is params and step.opt_state is opt_state
        assert step.manager.current_step() == 0
        step.manager._test_client.should_commit.side_effect = echo
        committed, again, _ = step(batch)
        assert committed and float(again) == float(loss) and step.manager.current_step() == 1
        assert not same(step.params, params) and not same(step.opt_state, opt_state)
    finally:
        step.manager.shutdown()


def test_the_four_stages_one_by_one_are_the_call():
    whole, batch = mocked()
    staged, _ = mocked()
    try:
        committed, loss, metrics = whole(batch)
        staged.begin()
        loss_s, metrics_s, to_reduce = staged.grads(batch)
        reduced = staged.reduce(to_reduce)
        assert same(reduced, jax.tree_util.tree_map(lambda x: x / 2, to_reduce))
        assert staged.manager.current_step() == 0 and staged.commit(reduced) and committed
        assert float(loss_s) == float(loss) and same(metrics_s, metrics)
        assert same(staged.params, whole.params) and same(staged.opt_state, whole.opt_state)
        assert staged.manager.current_step() == whole.manager.current_step() == 1
    finally:
        whole.manager.shutdown()
        staged.manager.shutdown()


@pytest.mark.parametrize("sharded_heal", [False, True])
def test_the_heal_contract_in_both_forms(sharded_heal):
    """What a healthy peer hands out (host numpy, or the sharded device
    leaves) loads into a replica that fell behind, onto its own shardings,
    through the functions the Manager was given."""
    ahead, batch = mocked(sharded_heal=sharded_heal)
    behind, _ = mocked(sharded_heal=sharded_heal)
    try:
        assert ahead(batch)[0]
        sent = ahead.manager._manager_state_dict()["user"]["default"]
        assert set(sent) == {"params", "opt_state"}
        assert all(isinstance(x, jax.Array if sharded_heal else np.ndarray)
                   for x in jax.tree_util.tree_leaves(sent))
        want = jax.tree_util.tree_map(lambda x: x.sharding, (behind.params, behind.opt_state))
        behind.manager._load_state_dicts["default"](sent)
        assert same(behind.params, ahead.params) and same(behind.opt_state, ahead.opt_state)
        got = jax.tree_util.tree_map(lambda x: x.sharding, (behind.params, behind.opt_state))
        assert jax.tree_util.tree_leaves(got) == jax.tree_util.tree_leaves(want)
        assert jax.tree_util.tree_structure(behind.opt_state) == jax.tree_util.tree_structure(
            ahead.opt_state)
        assert behind(batch)[0]  # and trains on from there
    finally:
        ahead.manager.shutdown()
        behind.manager.shutdown()
