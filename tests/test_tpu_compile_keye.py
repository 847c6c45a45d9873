"""The whole step program of ``keye-raw`` (16,384 tokens a step) compiled
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
def test_the_keye_cells_step_fits_and_selects_once_a_layer(topo, monkeypatch):
    """The fused step of ``keye-raw`` (one sequence of 16,384 tokens through
    six layers of a selected attention and 16 held experts): it fits the
    chip; every layer's attention is the selected family's kernels (forward,
    remat's forward and the backward), all of them among what ``flash_ms``
    finds; the score pass runs ONCE a layer, so remat's second forward neither
    scores nor selects and the [S, S] float32 scores are no residual (six
    such tensors in the program, each the forward's own temporary); the
    probabilities' pass runs once forward and once backward, the transpose
    once."""
    import re

    from benchmark import cells
    from benchmark.metrics import dsa_index_ms, flash_ms
    from benchmark.tests.test_v5e_compile import _programs
    from torchft_tpu.ops import flash_attention, sparse_index

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    monkeypatch.setattr(sparse_index, "_kernels", lambda seq: seq % sparse_index.CHUNK == 0)
    cell = cells.load_cell("keye-raw")
    programs, resident = _programs(cell, topo)
    prog, args = programs["step"]
    compiled = prog.lower(*args).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"keye-raw/step needs {need / 2**30:.2f} GiB, resident {resident / 2**30:.2f}")
    assert resident == 12 * 659_190_016 + 8  # weights, two moments, two counters
    assert resident < need < 15.75 * 2**30, need
    text = compiled.as_text()
    calls = _custom_calls(text)
    flash = [c for c in calls if re.search(flash_ms.PATTERN, c)]
    assert len(flash) == 6 * 3 and all("flash_attention_selected" in c for c in flash), flash
    index = [c for c in calls if re.search(dsa_index_ms.KERNELS, c)]
    by_name = {n: sum(c.startswith(n + ".") or c.startswith(n + " ") for c in index)
               for n in ("dsa_index_scores", "dsa_index_kl", "dsa_index_scores_bwd")}
    assert by_name == {"dsa_index_scores": 6, "dsa_index_kl": 12, "dsa_index_scores_bwd": 6}, (
        by_name, index)
    assert len(re.findall(r"= f32\[1,16384,16384\]", text)) <= 2 * 6
    assert not re.search(r"f32\[32,(?:\d+,)*16384,16384\]", text)
