"""The whole step program of ``smallthinker-raw`` (16,384 tokens a step) compiled
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
def test_the_smallthinker_cells_step_fits_and_holds_no_square_of_the_sequence(
    topo, monkeypatch
):
    """The fused step of ``smallthinker-raw`` (one sequence of 16,384 tokens
    through two periods of a global and three windowed attentions and eight
    expert layers of 8 held experts): it fits the chip; the six windowed
    layers are banded kernel calls under the name ``swa_ms`` tells from the
    two global layers' causal ones by (forward, remat's forward and backward a
    layer), all of them among what ``flash_ms`` finds; no tensor of the
    program is a square of the sequence; the grouped matmuls run over the
    49,152-row buffer; and every sub-layer's router logits leave their
    attention sub-layer as float32 [1, 16384, 64]."""
    import re

    from benchmark import cells
    from benchmark.metrics import flash_ms, moe_gmm_ms, swa_ms
    from benchmark.tests.test_v5e_compile import _programs
    from torchft_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    cell = cells.load_cell("smallthinker-raw")
    programs, resident = _programs(cell, topo)
    prog, args = programs["step"]
    compiled = prog.lower(*args).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"smallthinker-raw/step needs {need / 2**30:.2f} GiB, resident {resident / 2**30:.2f}")
    assert resident == 12 * 643_852_800 + 8  # weights, two moments, two counters
    assert resident < need < ALLOCATOR_BYTES, need
    text = compiled.as_text()
    calls = _custom_calls(text)
    flash = [c for c in calls if re.search(flash_ms.PATTERN, c)]
    banded = [c for c in flash if re.search(swa_ms.PATTERN, c)]
    assert len(banded) == 6 * 3 and len(flash) == 8 * 3, (len(banded), len(flash))
    assert all("16384,128]" in c for c in flash), flash
    assert not re.search(r"\[(?:\d+,)*16384,16384\]", text)
    gmm = [c for c in calls if re.search(moe_gmm_ms.PATTERN, c)]
    rows = [c for c in gmm if "ragged-dot-none" in c]
    assert len(rows) == 8 * 12 and all("[49152," in c or "[8," in c for c in rows), rows
    assert "f32[1,16384,64]" in text
