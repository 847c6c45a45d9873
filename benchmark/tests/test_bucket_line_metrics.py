"""``ar_push_ms`` and ``ar_early_bucket_share``: what the fp32 host path
spends sending reduced buckets back to the device, and whether they go
back while later ones still arrive, on ``data/bucket_line_journal.jsonl``
and on PR 23's recorded fixture (the program before the line).

The journal is in the shape the program journals: the 32 spans of a
recorded CPU step of the line (one thread; three buckets of 268, 800 and
1200 B under their layout indices 2, 1, 0, issued in that order), times
in whole milliseconds from the root's start, three steps:

``grads_wait`` 0-10. Bucket 2: ``pull`` 10-40, ``pack`` 5,
``Manager.allreduce`` 5 (``host_copy`` 1, nothing copied); its collective
is done, so before the next pull it is waited for (``allreduce_wait`` 10
holding ``allreduce_scale`` 7), unpacked (1) and pushed: ``push`` 61-65.
Bucket 1: ``pull`` 65-165, ``pack`` 20, issue 5, wait 25 (scale 22),
unpack 1, ``push`` 216-222. Bucket 0: ``pull`` 222-372 (to 392 in the
second step), ``pack`` 30, issue 5, wait 35 (scale 32), unpack 1,
``push`` 8, then ``push_wait`` 50 (60 in the second step).

In the third step bucket 1's collective is still running when bucket 0's
pull starts (190-390): it goes back after the last pull, before bucket 0
(``push`` 451-457 and 493-501), and ``push_wait`` is 40.
"""

import importlib
import json
import os

import pytest

from benchmark import cells
from benchmark.metrics import (
    ar_host_bytes_step,
    ar_issue_ms,
    ar_pack_ms,
    ar_pull_ms,
    ar_scale_ms,
    ar_wait_ms,
)
from benchmark.tests import test_span_metrics as recorded

ar_push_ms = importlib.import_module("benchmark.metrics.ar_push_ms")
ar_early_bucket_share = importlib.import_module("benchmark.metrics.ar_early_bucket_share")
BOTH = (ar_push_ms, ar_early_bucket_share)

JOURNAL = os.path.join(os.path.dirname(__file__), "data", "bucket_line_journal.jsonl")
ROOT = "torchft::ddp::allreduce_grads"
PULL = "torchft::ddp::pull"
PUSH = "torchft::ddp::push"
PUSH_WAIT = "torchft::ddp::push_wait"


def _events():
    with open(JOURNAL) as f:
        return [json.loads(line) for line in f]


def _named(event, name):
    return sorted((s for s in event["attrs"]["spans"] if s[0] == name), key=lambda s: s[1])


def _short(metric):
    return getattr(metric, "__name__", str(metric)).rsplit(".", 1)[-1]


def test_the_recorded_steps_are_what_the_docstring_says():
    steps = [e for e in _events() if e["event"] == "step_spans"]
    assert len(steps) == 3 and len(_events()) == 6  # a commit_gate before each
    for e, last_pull_end, push_starts in zip(
            steps, (0.372, 0.392, 0.390),
            ([0.061, 0.216, 0.443], [0.061, 0.216, 0.463], [0.061, 0.451, 0.493])):
        spans = e["attrs"]["spans"]
        assert len(spans) == 32 and e["attrs"]["dropped"] == 0
        (root,) = _named(e, ROOT)
        pulls, pushes = _named(e, PULL), _named(e, PUSH)
        assert [p[6] for p in pulls] == [
            {"bucket": 2, "nbytes": 268}, {"bucket": 1, "nbytes": 800},
            {"bucket": 0, "nbytes": 1200}]
        assert [p[6] for p in pushes] == [p[6] for p in pulls]
        assert pulls[-1][2] - root[1] == pytest.approx(last_pull_end)
        assert [p[1] - root[1] for p in pushes] == pytest.approx(push_starts)
        (landed,) = _named(e, PUSH_WAIT)
        assert pushes[-1][2] == pytest.approx(landed[1]) and landed[2] <= root[2]
        for s in spans:  # one thread, every stage a child of the root
            assert s[5] == root[5]
            if s[0].startswith("torchft::ddp::") and s is not root:
                assert s[4] == root[3]


@pytest.mark.parametrize("metric,steps,want", [
    (ar_push_ms, slice(0, 3), 68.0),  # 4 + 6 + 8 + 50; 78; 58
    (ar_push_ms, slice(0, 1), 68.0),
    (ar_push_ms, slice(1, 2), 78.0),  # push_wait 60
    (ar_push_ms, slice(1, 3), 68.0),  # (78 + 58) / 2
    (ar_push_ms, slice(2, 3), 58.0),  # 4 + 6 + 8 + 40
    (ar_early_bucket_share, slice(0, 3), 2 / 3),  # 2/3, 2/3, 1/3
    (ar_early_bucket_share, slice(0, 1), 2 / 3),  # all but the last bucket
    (ar_early_bucket_share, slice(2, 3), 1 / 3),  # bucket 1 went back late
    (ar_early_bucket_share, slice(1, 3), 0.5),
], ids=_short)
def test_values_on_the_recorded_steps(metric, steps, want):
    events = [e for e in _events() if e["event"] == "step_spans"][steps]
    assert metric.read({"journal": events}) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("metric,want", [
    (ar_pull_ms, 300.0),  # 30 + 100 + 150; 300; 330: one span a bucket, summed
    (ar_host_bytes_step, 6804),  # pulled + packed + scaled 2268 each, copied 0
    (ar_pack_ms, 58.0),  # 5 + 20 + 30 and three host copies of 1
    (ar_scale_ms, 61.0),  # 7 + 22 + 32
    (ar_wait_ms, 70.0),  # 10 + 25 + 35, now between the issues
    (ar_issue_ms, 425.0),  # the last issue ends 407, 427, 425 after the root starts
], ids=_short)
def test_the_older_readers_read_the_per_bucket_pulls_as_they_read_the_one(metric, want):
    assert metric.read({"journal": _events()}) == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("metric", BOTH, ids=_short)
def test_the_parents_journal_reads_none_for_both(metric):
    """The program before the line: one ``pull`` of everything, no
    ``push`` (the host gradient goes up inside ``apply_step``). None, not
    0, so the line of a run on the parent leaves the metric out."""
    parent = recorded._run("host-path")
    assert any(s[0] == PULL for e in parent["journal"] if e["event"] == "step_spans"
               for s in e["attrs"]["spans"])
    assert metric.read(parent) is None
    # the int8 device path has neither span; nor has a journal with no tree
    assert metric.read(recorded._run("device-path")) is None
    bare = [e for e in _events() if e["event"] != "step_spans"]
    assert bare and metric.read({"journal": bare}) is None
    assert metric.read({"journal": []}) is None


def test_a_line_that_does_not_engage_reads_zero_not_none():
    """Every push after the last pull (each collective slower than the
    next pull): a share of 0, a number."""
    (event,) = [e for e in _events() if e["event"] == "step_spans"][:1]
    late = [[s[0], s[1] + 1.0, s[2] + 1.0, *s[3:]] if s[0] == PUSH else s
            for s in event["attrs"]["spans"]]
    run = {"journal": [dict(event, attrs=dict(event["attrs"], spans=late))]}
    assert ar_early_bucket_share.read(run) == 0.0
    assert ar_push_ms.read(run) == pytest.approx(68.0)


def test_a_step_without_a_push_is_left_out_of_the_median():
    """A step whose every bucket failed pushes nothing and still waits."""
    events = [e for e in _events() if e["event"] == "step_spans"]
    failed = dict(events[0], attrs=dict(events[0]["attrs"], spans=[
        s for s in events[0]["attrs"]["spans"] if s[0] != PUSH]))
    run = {"journal": [failed] + events[1:]}
    assert ar_early_bucket_share.read(run) == pytest.approx(0.5)  # 2/3 and 1/3
    assert ar_push_ms.read(run) == pytest.approx(58.0)  # 50 alone, 78, 58


def test_only_push_push_wait_and_pull_count():
    (event,) = [e for e in _events() if e["event"] == "step_spans"][2:]
    kept = [s for s in event["attrs"]["spans"] if s[0] in (PUSH, PUSH_WAIT, PULL)]
    assert len(kept) == 7 < len(event["attrs"]["spans"])
    cut = dict(event, attrs=dict(event["attrs"], spans=kept))
    for metric in BOTH:
        assert metric.read({"journal": [cut]}) == metric.read({"journal": [event]})


def test_the_two_are_entries_of_the_table_for_the_fp32_cell_only():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in table["per_layer"]}
    for name, unit, better in (("ar_push_ms", "ms", "lower"),
                               ("ar_early_bucket_share", "ratio", "higher")):
        assert entries[name] == {
            "name": name, "unit": unit, "better": better, "source": "program_span",
            "layer": "replica-axis allreduce", "moves": "tok_s_chip",
            "workloads": ["mistral-ft1"],
        }
        assert name in {m["name"] for m in cells.load_cell("mistral-ft1").per_layer}
        for w in table["workloads"]:
            if w["name"] != "mistral-ft1":
                assert name not in {m["name"] for m in cells.load_cell(w["name"]).per_layer}
        assert cells.find_file(os.path.join(cells.ROOT, "BENCHMARK.json"),
                               "metrics", name + ".py")
