"""Trinity-Mini (AFMoE), the second half of ``tests/test_trinity.py`` (a
file of its own so that the suite's workers share the time): the expert
shares against the uncut layer, the fused step's bias move and counters,
remat, the existing presets' trees, the file and the adapter's refusals,
the harness's own check and the builder's long comparison at a small size,
the presets and ``train_hsdp.py --model trinity_debug``."""

import dataclasses
import hashlib
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.tests import test_afmoe_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, llama
from torchft_tpu.models.llama import (
    Attention,
    MixerLayer,
    MoEMLP,
    Transformer,
    trinity_debug,
    trinity_mini,
    window_attention,
    window_mask,
)
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors
from tests.test_trinity import (  # noqa: F401
    CPU_GRAD_TOL,
    FLASH,
    LEAVES,
    PUBLISHED,
    TREES,
    _equations,
    _grads,
    _tree_digest,
    adapter,
    flops,
    reference,
    tiny,
)


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of one layer's sixteen. The routed
    parts the eight compute, with the shared expert (which every chip
    computes alike) counted once, are the uncut reference layer; no chip
    drops a row and their held shares are the whole."""
    whole = tiny(num_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    full = MoEMLP(adapter.model_config(whole, 32))
    params = full.init(jax.random.PRNGKey(0), x)["params"]
    params["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    m = x.reshape(-1, 64)
    stacks = ("experts_gate", "experts_up", "experts_down")
    with jax.default_matmul_precision("highest"):
        want = reference.experts(m, params, whole, lambda a: a)
        assert jnp.allclose(full.apply({"params": params}, x).reshape(want.shape), want,
                            rtol=1e-4, atol=1e-5)
        # what every chip computes alike, by hand
        shared = (
            jax.nn.silu(m @ params["shared_gate"]["kernel"]) * (m @ params["shared_up"]["kernel"])
        ) @ params["shared_down"]["kernel"]
        total, held_share = jnp.zeros_like(want), 0.0
        for index in range(8):
            share = tiny(num_experts=2, expert_parallel_chips=8, expert_parallel_index=index)
            own = {k: v[2 * index : 2 * index + 2] if k in stacks else v
                   for k, v in params.items()}
            out, sown = MoEMLP(adapter.model_config(share, 32)).apply(
                {"params": own}, x, mutable=["intermediates"])
            sown = sown["intermediates"]
            total = total + out.reshape(want.shape) - shared
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            # the share's own reference is the share
            assert jnp.allclose(out.reshape(want.shape),
                                reference.experts(m, own, share, lambda a: a),
                                rtol=1e-4, atol=1e-5)
    assert jnp.allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0)
    assert float(jnp.linalg.norm(total)) > 0.1 and float(jnp.linalg.norm(shared)) > 0.1


def test_the_step_moves_the_bias_against_the_load_and_hands_on_the_counters(caplog):
    """The fused step: the selection bias of every expert layer moves by
    the file's 0.001 towards the experts the router under-used, from zero
    (no gradient reaches it, no weight decay touches it), and the step
    hands on the band's, the experts' and the bias's counters."""
    # float32: a rounding that flips one row's choice between the step and
    # the second forward pass below would move a load by one
    cfg = trinity_debug(dtype=jnp.float32, **FLASH)
    assert cfg.router_bias_update_rate == 1e-3
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    data = _data(cfg.vocab_size, 2, 64)
    llama._ATTN_NOTED.clear()
    with caplog.at_level(logging.INFO, logger="torchft_tpu.models.llama"):
        new, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
    assert ("attention: asked=flash/window traced=flash/window seq=64 window=16 tiles=16x16"
            in caplog.text)
    assert "attention: asked=flash traced=flash seq=64 tiles=16x16" in caplog.text
    assert "WARNING" not in [r.levelname for r in caplog.records]
    assert set(metrics) == {
        "loss", "grad_norm", "swa_kept_share", "moe_held_share", "moe_held_run_share",
        "moe_held_token_run_share",
        "moe_dropped", "moe_max_load", "router_aux", "router_bias_abs_max"}
    assert int(new.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert float(metrics["router_bias_abs_max"]) == pytest.approx(1e-3)
    assert float(metrics["swa_kept_share"]) == pytest.approx(
        (16 * 17 // 2 + 48 * 16) / (7 * 256))
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0 and float(metrics["moe_dropped"]) == 0.0
    _, sown = model.apply({"params": state.params}, data["inputs"], mutable=["intermediates"])
    for name in ("layers_3", "layers_5", "layers_7", "layers_9"):
        load = sown["intermediates"][name]["mlp"]["moe_load"][0]
        assert float(load.sum()) == 2 * 64 * 3
        bias = new.params[name]["mlp"]["router_bias"]
        assert jnp.allclose(bias, reference.bias_update(jnp.zeros(16), load, 1e-3))
        assert {round(float(v), 6) for v in np.abs(np.asarray(bias))} <= {0.0, 0.001}
    # out of the gradient: the loss's gradient at the bias is exactly zero
    _, grads = _grads(cfg, state.params, data)
    assert all(float(jnp.abs(grads[n]["mlp"]["router_bias"]).max()) == 0.0
               for n in ("layers_3", "layers_9"))
    # the published cell's schedule, from shapes alone
    cut = adapter.model_config(PUBLISHED, 16384)
    assert window_attention(cut, 16384) == ((512, 512), pytest.approx(0.800, abs=5e-4))
    assert llama.held_buffer_rows(cut, 16384) == 65536 == 4 * 16384 * 8 // 8


def test_remat_computes_the_same_step():
    data = _data(256, 2, 64)
    seen = []
    for remat in (False, True):
        cfg = trinity_debug(dtype=jnp.float32, remat=remat)
        mesh = auto_mesh(1, devices=jax.devices()[:1])
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "moe_held_share")])
    assert seen[0] == pytest.approx(seen[1], rel=1e-5)


