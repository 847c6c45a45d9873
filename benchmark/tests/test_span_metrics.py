"""The ten per-layer metrics that read the program's ``step_spans``
journal events, on ``data/step_spans_journal.jsonl``.

The fixture is in the shape the program journals (the span names, parents,
threads, attrs and event fields of recorded CPU runs of ``tests/table``'s
two-group cell and of a quorum of one), cut to two buckets and with times
in whole milliseconds so that every value below can be worked by hand:

host path (``replica_id`` "host-path"), three steps, one thread. In each:
``grads_wait`` 10 ms, then ``pull`` (2000 B) of 1200 / 1300 / 1500 ms,
``pack`` of bucket 0 (1200 B) 300 ms, ``Manager.allreduce`` 20 ms holding a
``host_copy`` of 2 ms that copied nothing, ``pack`` of bucket 1 (800 B)
200 ms, ``Manager.allreduce`` 20 ms holding a ``host_copy`` of 3 ms that
copied its 800 B, then two ``allreduce_wait`` of 500 and 300 ms holding
``allreduce_scale`` of 480 ms (1200 B) and 290 ms (800 B).

device path ("device-path"), two steps, two buckets, each on a thread of
its own. The caller issues bucket 0 in 0-100 ms and bucket 1 in 100-200 ms
and then waits 900 ms and 700 ms (900 in the second step). Bucket 0 holds
the wire 400-1000 ms at once (turn wait 0): alltoall 200, reduce 150 + 100,
allgather 150. Bucket 1 is ready at 500 ms and waits 500 ms (700 in the
second step) for its turn, then holds the wire 700 ms: alltoall 300,
reduce 150 + 50, allgather 200.
"""

import json
import os

import pytest

from benchmark import span_readers
from benchmark.metrics import (
    ar_host_bytes_step,
    ar_issue_ms,
    ar_pack_ms,
    ar_pull_ms,
    ar_scale_ms,
    ar_wait_ms,
    wire_busy_ms,
    wire_reduce_ms,
    wire_sock_ms,
    wire_turn_wait_ms,
)

JOURNAL = os.path.join(os.path.dirname(__file__), "data", "step_spans_journal.jsonl")
ALL = (ar_host_bytes_step, ar_issue_ms, ar_pack_ms, ar_pull_ms, ar_scale_ms,
       ar_wait_ms, wire_busy_ms, wire_reduce_ms, wire_sock_ms, wire_turn_wait_ms)


def _run(replica):
    with open(JOURNAL) as f:
        events = [json.loads(line) for line in f]
    return {"journal": [e for e in events if e["replica_id"] == replica]}


HOST = [
    (ar_issue_ms, 1850.0),  # 1750, 1850, 2050: root start to the last issue
    (ar_wait_ms, 800.0),  # 500 + 300
    (ar_pull_ms, 1300.0),  # 1200, 1300, 1500
    (ar_pack_ms, 505.0),  # 300 + 200 + host copies of 2 + 3
    (ar_scale_ms, 770.0),  # 480 + 290
    (ar_host_bytes_step, 6800),  # 2000 + (1200 + 800) + (0 + 800) + (1200 + 800)
]
DEVICE = [
    (ar_issue_ms, 200.0),
    (ar_wait_ms, 1700.0),  # 900 + 700 and 900 + 900
    (wire_turn_wait_ms, 250.0),  # p50 of 0, 500, 0, 700
    (wire_busy_ms, 1300.0),  # [400, 1000] and [1000, 1700]: 600 + 700
    (wire_sock_ms, 850.0),  # 200 + 150 + 300 + 200
    (wire_reduce_ms, 450.0),  # 150 + 100 + 150 + 50
]


@pytest.mark.parametrize("metric,want", HOST, ids=lambda v: getattr(v, "__name__", ""))
def test_host_path_values(metric, want):
    assert metric.read(_run("host-path")) == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("metric,want", DEVICE, ids=lambda v: getattr(v, "__name__", ""))
def test_device_path_values(metric, want):
    assert metric.read(_run("device-path")) == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("metric", ALL, ids=lambda m: m.__name__)
def test_no_step_spans_event_reads_none_not_zero(metric):
    """What every commit before the one that journals ``step_spans``
    gives: the other events, and no tree."""
    run = _run("device-path")
    run["journal"] = [e for e in run["journal"] if e["event"] != "step_spans"]
    assert run["journal"] and metric.read(run) is None
    assert metric.read({"journal": []}) is None


def test_a_path_that_did_not_run_reads_none():
    # the device path pulls, packs and scales nothing on the host ...
    for metric in (ar_pull_ms, ar_pack_ms, ar_scale_ms, ar_host_bytes_step):
        assert metric.read(_run("device-path")) is None
    # ... and the host path has no wire
    for metric in (wire_turn_wait_ms, wire_busy_ms, wire_sock_ms, wire_reduce_ms):
        assert metric.read(_run("host-path")) is None


def test_spans_are_read_whole_and_the_union_counts_overlap_once():
    steps = span_readers.steps(_run("device-path"))
    assert [len(s) for s in steps] == [26, 26]
    root = span_readers.named(steps[0], span_readers.ROOT)[0]
    assert root.parent is None and root.seconds == pytest.approx(1.85)
    wire = span_readers.named(steps[0], "torchft::collectives::wire")
    assert [w.attrs["bucket"] for w in wire] == [0, 1] and wire[0].thread != root.thread
    overlapping = {"journal": [{"event": "step_spans", "attrs": {"spans": [
        ["w", 0.0, 2.0, 1, None, 1, {}], ["w", 1.0, 3.0, 2, None, 2, {}],
    ]}}]}
    assert span_readers.union_ms(overlapping, "w") == pytest.approx(3000.0)
    assert span_readers.sum_ms(overlapping, "w") == pytest.approx(4000.0)


def test_every_new_metric_is_an_entry_of_the_table():
    """The allreduce's own entries, ``ar_*`` and ``wire_*``, as the table
    has them today (later PRs add more: nothing here counts them): each
    is of the replica-axis allreduce, moves ``tok_s_chip``, has a reader
    file, and is reported by the FT cells it lists and by no other cell."""
    from benchmark import cells

    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    own = {m["name"]: m for m in table["per_layer"]
           if m["name"].startswith(("ar_", "wire_"))}
    ten = {m.__name__.rsplit(".", 1)[1] for m in ALL}
    assert ten <= set(own)
    # the cells with a Manager in the loop: their mix asks for a lighthouse
    ft = {w["name"] for w in table["workloads"]
          if cells.load_cell(w["name"]).mix.get("min_replicas")}
    assert {"mistral-ft1", "mistral-ft4"} <= ft and "mistral-raw" not in ft
    for name, entry in own.items():
        assert entry["layer"] == "replica-axis allreduce", name
        assert entry["moves"] == "tok_s_chip", name
        assert set(entry["workloads"]) <= ft and entry["workloads"], name
        assert os.path.isfile(os.path.join(cells.HERE, "metrics", name + ".py")), name
    for w in table["workloads"]:
        reported = {m["name"] for m in cells.load_cell(w["name"]).per_layer} & set(own)
        assert reported == {n for n, e in own.items() if w["name"] in e["workloads"]}
        assert bool(reported) == (w["name"] in ft)
    # the host path's stages are the fp32 cell's, the wire's the int8 cell's
    assert own["ar_pull_ms"]["workloads"] == ["mistral-ft1"]
    assert own["wire_busy_ms"]["workloads"] == ["mistral-ft4"]
