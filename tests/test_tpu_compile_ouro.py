"""The ``ouro-raw`` cell's fused step compiled for a described TPU v5e (a
file of its own, so that ``--dist loadfile`` does not put it behind
the other cells' minute-long compiles). Nothing runs: no results, no times.

The topology is described inside a fixture, never at import, and the
compile happens in this process (``tests/test_tpu_compile.py`` says why)."""

import re

import pytest

from tests.test_tpu_compile import (  # noqa: F401 - ``topo`` is this file's fixture too
    ALLOCATOR_BYTES,
    _computations,
    _custom_calls,
    _instructions,
    topo,
)


@pytest.fixture(scope="module")
def step(topo):
    """The cell's one program, lowered and compiled once for every case."""
    from benchmark import cells
    from benchmark.tests.test_v5e_compile import _programs
    from torchft_tpu.ops import flash_attention

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(flash_attention, "_interpret", lambda: False)
        cell = cells.load_cell("ouro-raw")
        programs, resident = _programs(cell, topo)
        prog, args = programs["step"]
        lowered = prog.lower(*args)
        compiled = lowered.compile()
    return cell, resident, lowered.as_text(), compiled


def test_the_ouro_cells_step_fits_the_chip_with_its_layers_once_in_the_state(step):
    """509,661,185 parameters, each ONCE however often the loop visits it:
    12 B a parameter resident; the whole program under what the chip's
    allocator hands out."""
    cell, resident, _, compiled = step
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"ouro-raw/step needs {need / 2**30:.2f} GiB, resident {resident / 2**30:.2f}")
    assert cell.flops.total_params(cell.config) == 509_661_185
    assert resident == 12 * 509_661_185 + 8  # weights, two moments, two counters
    assert resident < need < ALLOCATOR_BYTES, need
    # what the loop keeps for its backward pass is activations in the compute
    # type: no float32 tensor of the stream's shape is stacked over the steps
    assert not re.search(r"f32\[4,2,8192,2048\]", compiled.as_text())


def test_the_program_holds_one_copy_of_the_stack(step):
    """One scan over the loop's four steps whose body is the six layers:
    six flash calls forward, six for remat's forward and six backward, all
    among what ``flash_ms`` finds, where an unrolled loop would hold 72;
    the lowered text has one loop over the steps a pass."""
    from benchmark.metrics import flash_ms

    _, _, lowered, compiled = step
    calls = _custom_calls(compiled.as_text())
    flash = [c for c in calls if re.search(flash_ms.PATTERN, c)]
    assert len(calls) == len(flash) == 6 * 3, calls
    assert all("[2,16,8192,128]" in c for c in flash), flash
    # forward and backward over the steps, and the head's chunks
    assert lowered.count("stablehlo.while") == 3


def test_the_steps_instructions_lead_with_the_shapes_the_new_metrics_match(step):
    """``ouro_head_loss_ms`` (the head's pass over 4 x 2 rows: a chunk of
    256 tokens a row, 2,048 rows a chunk under the 49,152-row vocabulary)
    and ``loop_gate_ms`` (the stacked exit logits) find instructions of the
    compiled step by the names a trace gives them."""
    from benchmark.metrics import head_loss_ms, loop_gate_ms

    cell, _, _, compiled = step
    names = [
        n for _, block in _computations(compiled.as_text()).values()
        for n in _instructions(block, running=True)
    ]
    run = {"cell": cell}
    d = head_loss_ms.dims(run)
    assert d == {"b": 2, "s": 8192, "h": 2048, "v": 49152}
    head = re.compile("|".join(
        f"(?:{p})" for p in head_loss_ms.patterns({**d, "b": 8})))
    found = [n for n in names if head.search(n)]
    assert any("f32[2048,49152]" in n for n in found), found[:5]  # dW's accumulator
    assert any("[2048,49152]" in n and n.split(" ")[1].startswith(("bf16", "(bf16")) for n in found)
    assert any("[32,2048,2048]" in n for n in found)  # the chunks' hidden-state gradient
    gate = re.compile("|".join(f"(?:{p})" for p in loop_gate_ms.patterns(loop_gate_ms.dims(run))))
    found = [n for n in names if gate.search(n)]
    assert any("f32[4,2,8192]" in n for n in found) and any("f32[3,2,8192]" in n for n in found)
    # nothing of the layers' work is taken for the gate's
    assert not any("2048]" in n.split(" ", 1)[1][:24] for n in found), found
