"""``wire_start_ms`` and ``wire_starved_ms``: when a step's serialised
wire starts and how long it then stands still, on
``data/wire_schedule_journal.jsonl`` and on PR 23's recorded fixture.

The journal is in the shape the program journals (``step_spans`` events of
the device path, three buckets of 12288, 6144 and 1024 B, each on a thread
of its own), times in whole milliseconds, two programs of three steps:

"layout-order", the schedule before the buckets were ordered: issued 0, 1,
2 (largest first) at 0-100, 100-200, 200-300 ms, every pull starts at
issue (1300 ms for bucket 0, +100 and +300 in the second and third step)
and the wire serves issue order back to back: it starts at 1400 / 1500 /
1700 ms and never stands still.

"smallest-first", this schedule: issued 2, 1, 0 at 0-30, 30-100, 100-200
ms, pulls one at a time (`pull_turn_wait`, then `quantize_pull`). The
wire starts at 40 / 50 / 60 ms with bucket 2 until 100 ms, bucket 1
holds it 250-550 ms, bucket 0 from 650 ms (+20 and +50 in the second and
third step): it stands still 150 + 100 / 120 / 150 ms.
"""

import importlib
import json
import os

import pytest

from benchmark import cells
from benchmark.tests import test_span_metrics as recorded

wire_start_ms = importlib.import_module("benchmark.metrics.wire_start_ms")
wire_starved_ms = importlib.import_module("benchmark.metrics.wire_starved_ms")
BOTH = (wire_start_ms, wire_starved_ms)

JOURNAL = os.path.join(os.path.dirname(__file__), "data", "wire_schedule_journal.jsonl")
WIRE = "torchft::collectives::wire"
ROOT = "torchft::ddp::allreduce_grads"


def _events(replica):
    with open(JOURNAL) as f:
        events = [json.loads(line) for line in f]
    return [e for e in events if e["replica_id"] == replica]


def _wires(event):
    return sorted((s for s in event["attrs"]["spans"] if s[0] == WIRE), key=lambda s: s[1])


def test_the_recorded_runs_are_what_the_docstring_says():
    for replica, issue, starts in (("layout-order", [0, 1, 2], [1.4, 1.5, 1.7]),
                                   ("smallest-first", [2, 1, 0], [0.04, 0.05, 0.06])):
        events = _events(replica)
        assert len(events) == 3
        for e, start in zip(events, starts):
            (root,) = [s for s in e["attrs"]["spans"] if s[0] == ROOT]
            wires = _wires(e)
            assert [w[6]["bucket"] for w in wires] == issue  # wire order = issue order
            assert wires[0][1] - root[1] == pytest.approx(start)
            for a, b in zip(wires, wires[1:]):
                assert a[2] <= b[1]  # one bucket on the wire at a time
            pulls = sorted((s for s in e["attrs"]["spans"]
                            if s[0] == "torchft::collectives::quantize_pull"),
                           key=lambda s: s[1])
            waited = [s for s in e["attrs"]["spans"]
                      if s[0] == "torchft::collectives::pull_turn_wait"]
            if replica == "smallest-first":  # pulls take turns
                assert len(waited) == 3
                assert all(a[2] <= b[1] for a, b in zip(pulls, pulls[1:]))
            else:  # every pull starts at issue and they overlap
                assert not waited and pulls[0][2] > pulls[2][1]
            assert e["attrs"]["dropped"] == 0


@pytest.mark.parametrize("metric,replica,steps,want", [
    (wire_start_ms, "layout-order", slice(0, 3), 1500.0),  # 1400, 1500, 1700
    (wire_start_ms, "layout-order", slice(0, 1), 1400.0),
    (wire_start_ms, "smallest-first", slice(0, 3), 50.0),  # 40, 50, 60
    (wire_start_ms, "smallest-first", slice(1, 3), 55.0),
    (wire_starved_ms, "layout-order", slice(0, 3), 0.0),  # back to back: 0, a number
    (wire_starved_ms, "smallest-first", slice(0, 3), 270.0),  # 250, 270, 300
    (wire_starved_ms, "smallest-first", slice(0, 1), 250.0),  # 150 + 100
    (wire_starved_ms, "smallest-first", slice(0, 2), 260.0),
], ids=lambda v: getattr(v, "__name__", str(v)).rsplit(".", 1)[-1])
def test_values_on_the_recorded_runs(metric, replica, steps, want):
    run = {"journal": _events(replica)[steps]}
    assert metric.read(run) == pytest.approx(want, abs=1e-6)


def test_the_older_fixture_reads_too():
    """PR 23's device path: the wire starts 400 ms into the allreduce in
    both steps; its two buckets follow each other at once in the first and
    200 ms apart in the second."""
    assert wire_start_ms.read(recorded._run("device-path")) == pytest.approx(400.0)
    assert wire_starved_ms.read(recorded._run("device-path")) == pytest.approx(100.0)


def test_only_the_wire_spans_and_the_root_count():
    """Its children, the waits for a turn and the pulls move neither."""
    (event,) = _events("smallest-first")[:1]
    kept = [s for s in event["attrs"]["spans"] if s[0] in (WIRE, ROOT)]
    assert len(kept) == 4 < len(event["attrs"]["spans"])
    cut = dict(event, attrs=dict(event["attrs"], spans=kept))
    for metric in BOTH:
        assert metric.read({"journal": [cut]}) == metric.read({"journal": [event]})


def test_wire_spans_that_overlap_are_counted_once():
    """Two groups in one process share the span buffer (thread tests):
    overlapping `wire` spans do not make the gap negative."""
    spans = [[ROOT, 10.0, 14.0, 1, None, 1, {}],
             [WIRE, 10.5, 11.5, 2, 1, 2, {}], [WIRE, 11.0, 12.0, 3, 1, 3, {}],
             [WIRE, 12.25, 13.0, 4, 1, 4, {}]]
    run = {"journal": [{"event": "step_spans", "attrs": {"spans": spans}}]}
    assert wire_start_ms.read(run) == pytest.approx(500.0)
    assert wire_starved_ms.read(run) == pytest.approx(250.0)


@pytest.mark.parametrize("metric", BOTH, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_a_program_with_no_such_spans_reads_none_not_zero(metric):
    # the host fp32 path has a root and no wire
    assert metric.read(recorded._run("host-path")) is None
    # a program that journals no span tree: its other events, no steps
    run = recorded._run("device-path")
    run["journal"] = [e for e in run["journal"] if e["event"] != "step_spans"]
    assert run["journal"] and metric.read(run) is None
    assert metric.read({"journal": []}) is None


def test_a_step_without_a_wire_is_left_out_of_the_median():
    events = _events("smallest-first")
    bare = dict(events[0], attrs=dict(events[0]["attrs"], spans=[
        s for s in events[0]["attrs"]["spans"] if s[0] != WIRE]))
    run = {"journal": [bare] + events[1:]}
    assert wire_start_ms.read(run) == pytest.approx(55.0)
    assert wire_starved_ms.read(run) == pytest.approx(285.0)


def test_the_two_are_entries_of_the_table_for_the_four_chip_cell_only():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in table["per_layer"]}
    for name in ("wire_start_ms", "wire_starved_ms"):
        assert entries[name] == {
            "name": name, "unit": "ms", "better": "lower", "source": "program_span",
            "layer": "replica-axis allreduce", "moves": "tok_s_chip",
            "workloads": ["mistral-ft4"],
        }
        assert name in {m["name"] for m in cells.load_cell("mistral-ft4").per_layer}
        for cell in ("mistral-ft1", "mistral-raw", "internlm2-raw", "olmoe-raw"):
            assert name not in {m["name"] for m in cells.load_cell(cell).per_layer}
        assert cells.find_file(os.path.join(cells.ROOT, "BENCHMARK.json"),
                               "metrics", name + ".py")
