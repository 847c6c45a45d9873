"""``wire_fresh_bytes_step``: the per-layer metric that sums the
``fresh_bytes`` counts of the ``wire_reduce`` spans, on a journal written
out here (three steps, two buckets of three stages each; the first step
sizes the buffers, the others reuse them) and on PR 23's recorded fixture,
whose spans carry no such count."""

import importlib
import os

import pytest

from benchmark import cells
from benchmark.tests import test_span_metrics as recorded

wire_fresh_bytes_step = importlib.import_module("benchmark.metrics.wire_fresh_bytes_step")

REDUCE = "torchft::collectives::wire_reduce"


def _step(counts):
    """One ``step_spans`` event whose ``wire_reduce`` spans carry
    ``counts`` — (fresh, reused) pairs — and one span that is not of the
    stage."""
    spans = [[REDUCE, 10.0 + i, 10.5 + i, 100 + i, 99, 2,
              {"fresh_bytes": fresh, "reused_bytes": reused}]
             for i, (fresh, reused) in enumerate(counts)]
    spans.append(["torchft::collectives::wire_alltoall", 9.0, 10.0, 98, 99, 2,
                  {"fresh_bytes": 12345}])
    return {"event": "step_spans", "attrs": {"spans": spans, "dropped": 0}}


GROW = [(4096, 0), (1040, 0), (2112, 0), (0, 4096), (0, 1040), (0, 2112)]
REUSE = [(0, 4096), (0, 1040), (0, 2112)] * 2


@pytest.mark.parametrize("steps,want", [
    ([GROW, REUSE, REUSE], 0),  # medians: the sizing step does not show
    ([GROW], 4096 + 1040 + 2112),
    ([GROW, REUSE[:5] + [(2112, 0)]], (7248 + 2112) / 2),
], ids=["steady", "first-step", "a-payload-found-no-free-buffer"])
def test_sums_the_stage_and_takes_the_median_over_steps(steps, want):
    run = {"journal": [_step(counts) for counts in steps]}
    assert wire_fresh_bytes_step.read(run) == pytest.approx(want)


def test_spans_without_the_count_read_none_not_zero():
    """The parent of the commit that counts: the same spans, no attrs."""
    assert wire_fresh_bytes_step.read(recorded._run("device-path")) is None
    assert wire_fresh_bytes_step.read(recorded._run("host-path")) is None
    assert wire_fresh_bytes_step.read({"journal": []}) is None


def test_is_an_entry_of_the_table_for_the_four_chip_cell_only():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in table["per_layer"] if m["name"] == "wire_fresh_bytes_step"]
    assert entry == {
        "name": "wire_fresh_bytes_step", "unit": "bytes", "better": "lower",
        "source": "program_counter", "layer": "replica-axis allreduce",
        "moves": "tok_s_chip", "workloads": ["mistral-ft4"],
    }
    for w in table["workloads"]:
        names = {m["name"] for m in cells.load_cell(w["name"]).per_layer}
        assert ("wire_fresh_bytes_step" in names) == (w["name"] == "mistral-ft4")
