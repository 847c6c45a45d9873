"""The five per-layer metrics of the control plane that read the commit
gates, on ``data/liveness_journal.jsonl`` and on PR 23's recorded
fixture, whose gates carry none of the fields.

The journal is a recorded CPU run: two Managers in lockstep under a
lighthouse with ``--min-replicas 2`` (steps of ~50 ms, heartbeats every
100 ms, so a gate counts one round or none), the 31st heartbeat of the
victim's manager server stalled 1.5 s by the chaos plane
(``stall@ctrl:match=heartbeat``). Kept: the ``commit_gate``,
``lh_evicted`` and ``failure_signal`` events of the six gates before and
after. The lighthouse evicted the victim eight times in 350 ms (signals
1-8: every quorum registration put its heartbeat entry back, the next
scan took it again) and heard its heartbeat 1,601 ms after the one
before; the sender saw a 1,500.7 ms round trip and then a 1,601.0 ms
gap. The victim's own acks showed it signals 2-8 (an ack carries the
newest eight) and the word ``evicted`` for signal 8.
"""

import importlib
import json
import os

import pytest

from benchmark import cells, gate_readers
from benchmark.tests import test_span_metrics as recorded

NAMES = ("hb_gap_max_ms", "hb_rtt_max_ms", "lh_evictions_window",
         "quorum_changes_window", "host_rss_peak_gib")
METRICS = {n: importlib.import_module(f"benchmark.metrics.{n}") for n in NAMES}
JOURNAL = os.path.join(os.path.dirname(__file__), "data", "liveness_journal.jsonl")


def _run(who):
    with open(JOURNAL) as f:
        events = [json.loads(line) for line in f]
    return {"journal": [e for e in events if e["replica_id"].startswith(who)]}


def test_the_recorded_liveness_run_is_what_the_docstring_says():
    victim, peer = _run("victim"), _run("peer")
    assert len(gate_readers.gates(victim)) == 12 and len(gate_readers.gates(peer)) == 17
    (ev,) = [e["attrs"] for e in victim["journal"] if e["event"] == "lh_evicted"]
    assert (ev["seq"], ev["gap_ms"], ev["budget_ms"], ev["erased"], ev["via"]) == (
        8, 1601, 1200, "heartbeat", "heartbeat")
    assert ev["sender_rtt_ms"] == pytest.approx(1500.734)
    lapses = [e["attrs"]["seq"] for e in peer["journal"]
              if e["event"] == "failure_signal" and e["attrs"]["source"] == "hb_lapse"]
    assert lapses == list(range(1, 9))
    assert {g["cause"] for g in gate_readers.gates(victim)} == {"ok"}
    assert set(gate_readers.field(peer, "hb_rounds")) == {0, 1}


@pytest.mark.parametrize("name,who,want", [
    ("hb_gap_max_ms", "victim", 1600.973),
    ("hb_gap_max_ms", "peer", 100.726),
    ("hb_rtt_max_ms", "victim", 1500.734),
    ("hb_rtt_max_ms", "peer", 0.565),
    ("lh_evictions_window", "victim", 7),  # signals 2-8; `evicted` is the 8th again
    ("lh_evictions_window", "peer", 0),  # the signals it saw name the victim
    ("quorum_changes_window", "victim", 0),
    ("quorum_changes_window", "peer", 0),
])
def test_values_on_the_recorded_run(name, who, want):
    assert METRICS[name].read(_run(who)) == pytest.approx(want)


def test_host_rss_peak_is_the_largest_gate_in_gib():
    run = _run("victim")
    peak = max(g["rss_peak_bytes"] for g in gate_readers.gates(run))
    assert METRICS["host_rss_peak_gib"].read(run) == pytest.approx(peak / 2**30)
    assert 0.05 < METRICS["host_rss_peak_gib"].read(run) < 64


def test_an_eviction_is_counted_once_and_only_for_the_group_itself():
    run = _run("victim")
    # The word `evicted` alone (an older lighthouse's acks show one signal).
    alone = {"journal": [e for e in run["journal"] if e["event"] != "failure_signal"]}
    assert METRICS["lh_evictions_window"].read(alone) == 1
    # A window that ends before the eviction: a count of 0, not None.
    before = {"journal": run["journal"][:5]}
    assert all(e["event"] == "commit_gate" for e in before["journal"])
    assert METRICS["lh_evictions_window"].read(before) == 0
    # The evidence watcher's own failure_signal names a peer: not counted.
    watcher = dict(run["journal"][0], event="failure_signal", attrs={
        "source": "hb_lapse", "subject": "peer:1", "site": "trainer.evidence_watch",
        "seq": 99, "reaction": "pg_abort"})
    assert METRICS["lh_evictions_window"].read(
        {"journal": before["journal"] + [watcher]}) == 0


def test_quorum_changes_are_counted_between_consecutive_gates():
    gates = [e for e in _run("peer")["journal"] if e["event"] == "commit_gate"][:5]
    for e, qid in zip(gates, (4, 4, 5, 5, 7)):
        e["attrs"]["quorum_id"] = qid
    assert METRICS["quorum_changes_window"].read({"journal": gates}) == 2
    assert METRICS["quorum_changes_window"].read({"journal": gates[:1]}) == 0


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("replica", ["host-path", "device-path"])
def test_a_program_from_before_the_fields_gives_none_not_zero(name, replica):
    old = recorded._run(replica)
    assert METRICS[name].read(old) is None
    assert METRICS[name].read({"journal": []}) is None


def test_the_old_fixture_does_have_gates_to_read():
    gates = [g for r in ("host-path", "device-path")
             for g in gate_readers.gates(recorded._run(r))]
    assert gates and all("hb_rounds" not in g and "quorum_id" not in g for g in gates)


def test_the_five_are_entries_of_the_table_with_files_for_the_ft_cells_only():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in table["per_layer"]}
    assert [m["name"] for m in table["per_layer"] if m["name"] in NAMES] == list(NAMES)
    for name in NAMES:
        e = entries[name]
        assert (e["source"], e["layer"], e["moves"], e["better"], e["workloads"]) == (
            "program_counter", "control plane", "tok_s_chip", "lower",
            ["mistral-ft1", "mistral-ft4"])
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert os.path.isfile(os.path.join(cells.HERE, "metrics", name + ".py"))
    for cell in ("mistral-ft1", "mistral-ft4"):
        assert set(NAMES) <= {m["name"] for m in cells.load_cell(cell).per_layer}
    for w in table["workloads"]:
        if w["name"] not in ("mistral-ft1", "mistral-ft4"):
            assert not set(NAMES) & {m["name"] for m in cells.load_cell(w["name"]).per_layer}
