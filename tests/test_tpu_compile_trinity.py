"""The whole step program of ``trinity-raw`` (16,384 tokens a step) compiled
for a described TPU v5e, about a hundred seconds of the chip's compiler: a
file of its own beside ``tests/test_tpu_compile.py`` (the kernels alone,
which says how the topology is described and why every compile happens in
this process) so that ``--dist loadfile`` can spread the cells' compiles.
Nothing runs: no results, no times."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.test_tpu_compile import (  # noqa: F401
    ALLOCATOR_BYTES,
    _custom_calls,
    topo,
)


@pytest.mark.timeout(900)
def test_the_trinity_cells_step_fits_and_holds_no_square_of_the_sequence(topo, monkeypatch):
    """The fused step of ``trinity-raw`` (one sequence of 16,384 tokens
    through a windowed attention and a dense layer, then a period of
    windowed, global, windowed, windowed with an expert layer of 16 held
    experts and a shared one after each, a norm before and after every
    sub-layer): it fits the chip; the four windowed layers are banded kernel
    calls at the tiles of 512 the band's rule takes for a window of 2,048
    (forward, remat's forward and backward a layer), the global layer's the
    causal family's, all of them among what ``flash_ms`` finds; no tensor of
    the program is a square of the sequence; and the grouped matmuls run
    over the 65,536-row buffer."""
    import re

    from benchmark import cells
    from benchmark.metrics import flash_ms, moe_gmm_ms, swa_ms
    from benchmark.tests.test_v5e_compile import _programs
    from torchft_tpu.models.llama import window_attention
    from torchft_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    cell = cells.load_cell("trinity-raw")
    cfg = cell.adapter.model_config(cell.config, 16384)
    assert window_attention(cfg, 16384)[0] == (512, 512)
    programs, resident = _programs(cell, topo)
    prog, args = programs["step"]
    compiled = prog.lower(*args).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"trinity-raw/step needs {need / 2**30:.2f} GiB, resident {resident / 2**30:.2f}")
    assert resident == 12 * 705_474_304 + 8  # weights, two moments, two counters
    assert resident < need < 15.5e9, need
    text = compiled.as_text()
    calls = _custom_calls(text)
    flash = [c for c in calls if re.search(flash_ms.PATTERN, c)]
    banded = [c for c in flash if re.search(swa_ms.PATTERN, c)]
    assert len(banded) == 4 * 3 and len(flash) == 5 * 3, (len(banded), len(flash))
    assert all("16384,128]" in c for c in flash), flash
    assert not re.search(r"\[(?:\d+,)*16384,16384\]", text)
    gmm = [c for c in calls if re.search(moe_gmm_ms.PATTERN, c)]
    rows = [c for c in gmm if "ragged-dot-none" in c]
    assert len(rows) == 4 * 12 and all("[65536," in c or "[16," in c for c in rows), rows
