"""``ar_pack_fresh_bytes_step``: the per-layer metric that sums the
``fresh_bytes`` counts of the ``ddp::pack`` spans, on
``data/ar_pack_journal.jsonl`` and on PR 23's recorded fixture, whose
spans carry no such count.

The journal is the ``step_spans`` events of a recorded CPU run of the
program's host path (a quorum of one, a dummy process group, three
buckets of 1200, 800 and 400 B), six gates: the wrapper's first call
sizes its buffers (2400 B fresh), two steps reuse them, the fourth step
fails on a latched manager error (it still packed into the kept
buffers: 0 fresh) and retires them, the fifth sizes new ones (2400
fresh), the sixth reuses those.
"""

import importlib
import json
import os

import pytest

from benchmark import cells
from benchmark.tests import test_span_metrics as recorded

ar_pack_fresh_bytes_step = importlib.import_module(
    "benchmark.metrics.ar_pack_fresh_bytes_step")

JOURNAL = os.path.join(os.path.dirname(__file__), "data", "ar_pack_journal.jsonl")
PACK = "torchft::ddp::pack"


def _events():
    with open(JOURNAL) as f:
        return [json.loads(line) for line in f]


def _fresh(event):
    return sum(s[6]["fresh_bytes"] for s in event["attrs"]["spans"] if s[0] == PACK)


def test_the_recorded_run_is_what_the_docstring_says():
    events = _events()
    assert [_fresh(e) for e in events] == [2400, 0, 0, 0, 2400, 0]
    for e in events:
        packs = [s[6] for s in e["attrs"]["spans"] if s[0] == PACK]
        assert [p["nbytes"] for p in packs] == [1200, 800, 400]
        assert all(p["fresh_bytes"] + p["reused_bytes"] == p["nbytes"] for p in packs)
        assert e["attrs"]["dropped"] == 0


@pytest.mark.parametrize("steps,want", [
    (slice(0, 6), 0),  # medians: neither sizing step shows
    (slice(1, 4), 0),  # a window after the warm-up
    (slice(0, 1), 2400),  # the sizing step alone
    (slice(3, 5), 1200),  # the failed step and the fresh pages after it
    (slice(4, 5), 2400),
], ids=["whole-run", "steady", "first-step", "around-a-failure", "after-a-failure"])
def test_sums_the_packs_and_takes_the_median_over_steps(steps, want):
    run = {"journal": _events()[steps]}
    assert ar_pack_fresh_bytes_step.read(run) == pytest.approx(want)


def test_only_the_pack_spans_count():
    event = _events()[0]
    event["attrs"]["spans"].append(
        ["torchft::ddp::pull", 9.0, 10.0, 98, 99, 2, {"fresh_bytes": 12345}])
    assert ar_pack_fresh_bytes_step.read({"journal": [event]}) == 2400


def test_pack_spans_without_the_count_read_none_not_zero():
    """The parent of the commit that counts: the same spans, no attrs."""
    assert ar_pack_fresh_bytes_step.read(recorded._run("host-path")) is None
    assert ar_pack_fresh_bytes_step.read(recorded._run("device-path")) is None
    assert ar_pack_fresh_bytes_step.read({"journal": []}) is None


def test_is_an_entry_of_the_table_for_the_fp32_cell_only():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in table["per_layer"] if m["name"] == "ar_pack_fresh_bytes_step"]
    assert entry == {
        "name": "ar_pack_fresh_bytes_step", "unit": "bytes", "better": "lower",
        "source": "program_counter", "layer": "replica-axis allreduce",
        "moves": "tok_s_chip", "workloads": ["mistral-ft1"],
    }
    assert "ar_pack_fresh_bytes_step" in {
        m["name"] for m in cells.load_cell("mistral-ft1").per_layer}
    for cell in ("mistral-ft4", "mistral-raw", "internlm2-raw", "olmoe-raw"):
        assert "ar_pack_fresh_bytes_step" not in {
            m["name"] for m in cells.load_cell(cell).per_layer}
