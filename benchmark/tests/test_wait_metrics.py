"""The nine per-layer metrics that read what the host waited for: five
from the socket collectives' own accounts (``pg_collective``), four from
the ``getrusage`` of the commit gates (two of them, the counts of faults
and of preemptions, are entries of no cell while the benchmark's
machines run a kernel that counts neither). On journals written by hand in
the shape the program journals, so that every value can be worked in the
head, and on every recorded fixture of ``data/``, whose events carry
none of the fields.

The hand-written wire journal: three steps (``q1.s1`` .. ``q1.s3``) of
two alltoalls and one allgather each; step ``n``'s collectives each sent
for ``n`` ms (CPU half of it), waited ``10 n`` ms for a late peer and
``2 n`` ms while the bytes landed (reader CPU ``n`` ms), and moved
``1000 n`` bytes each way. Beside them a ring allreduce (another op), a
failed alltoall, a native allgather without the fields and a collective
outside any step, none of which count.

The hand-written gate journal: four gates one second apart (the last
two seconds after the third) with cumulative user CPU 10, 11.5, 13, 16,
system CPU 1, 1.25, 1.5, 2, ``minflt`` 100, 400, 700, 1300 and
``nivcsw`` 5, 7, 9, 20.
"""

import glob
import importlib
import json
import os

import pytest

from benchmark import cells, wait_readers

WIRE = ("wire_send_ms", "wire_peer_wait_ms", "wire_recv_ms", "wire_sock_cpu_ms",
        "wire_xfer_bytes_step")
HOST = ("host_cpu_cores", "host_sys_ms_step", "host_minflt_step", "host_nivcsw_step")
NINE = WIRE + HOST
METRICS = {n: importlib.import_module(f"benchmark.metrics.{n}") for n in NINE}
DATA = os.path.join(os.path.dirname(__file__), "data")


def _collective(trace, op, n, ok=True, account=True, backend="torchft-socket"):
    attrs = {"backend": backend, "op": op, "nbytes": 0 if op == "alltoall" else 500 * n,
             "tag": "c1", "elapsed_s": 0.02 * n, "queued_s": 0.0, "ok": ok}
    if account:
        attrs.update(tx_bytes=1000 * n, rx_bytes=1000 * n, send_s=0.001 * n,
                     send_cpu_s=0.0005 * n, peer_wait_s=0.010 * n, recv_s=0.002 * n,
                     recv_cpu_s=0.001 * n)
    e = {"ts": 100.0 + n, "replica_id": "g0", "step": n, "event": "pg_collective",
         "attrs": attrs}
    if trace:
        e["trace"] = trace
    return e


def _wire_run(steps=(1, 2, 3)):
    journal = []
    for n in steps:
        t = f"q1.s{n}"
        journal += [_collective(t, "alltoall", n), _collective(t, "alltoall", n),
                    _collective(t, "allgather", n),
                    _collective(t, "allreduce", 50),  # another op
                    _collective(t, "alltoall", 70, ok=False),  # it failed
                    _collective(t, "allgather", 90, account=False,
                                backend="torchft-native")]
    journal.append(_collective("", "alltoall", 1000))  # outside any step
    return {"journal": journal}


def _gate(ts, user, sys_, minflt, nivcsw, **more):
    attrs = {"committed": True, "rss_peak_bytes": 1 << 30, "cpu_user_s": user,
             "cpu_sys_s": sys_, "minflt": minflt, "nivcsw": nivcsw}
    attrs.update(more)
    return {"ts": ts, "replica_id": "g0", "step": int(ts), "event": "commit_gate",
            "attrs": attrs}


GATES = [_gate(10.0, 10.0, 1.0, 100, 5), _gate(11.0, 11.5, 1.25, 400, 7),
         _gate(12.0, 13.0, 1.5, 700, 9), _gate(14.0, 16.0, 2.0, 1300, 20)]


@pytest.mark.parametrize("name,steps,want", [
    # three collectives a step, step n's each n times the unit; median step 2
    ("wire_send_ms", (1, 2, 3), 3 * 2 * 1.0),
    ("wire_peer_wait_ms", (1, 2, 3), 3 * 2 * 10.0),
    ("wire_recv_ms", (1, 2, 3), 3 * 2 * 2.0),
    ("wire_sock_cpu_ms", (1, 2, 3), 3 * 2 * (0.5 + 1.0)),
    ("wire_xfer_bytes_step", (1, 2, 3), 3 * 2 * 2000),
    # an even count of steps: the mean of the middle two (steps 2 and 3)
    ("wire_peer_wait_ms", (1, 2, 3, 4), 3 * 2.5 * 10.0),
    ("wire_xfer_bytes_step", (1, 2, 3, 4), 3 * 2.5 * 2000),
    ("wire_send_ms", (5,), 3 * 5 * 1.0),
])
def test_wait_sums_by_trace_id_and_takes_the_median_over_steps(name, steps, want):
    assert METRICS[name].read(_wire_run(steps)) == pytest.approx(want)


def test_the_three_waits_tile_what_the_collectives_spent_on_their_sockets():
    run = _wire_run()
    tiled = sum(METRICS[n].read(run) for n in WIRE[:3])
    assert tiled == pytest.approx(3 * 2 * 13.0)
    # less than the collectives' own elapsed time (3 x 2 x 20 ms): the
    # rest is each rank's copy of its own chunk
    assert tiled < 3 * 2 * 20.0


@pytest.mark.parametrize("name,want", [
    # growth between consecutive gates: the first gate gives none
    ("host_cpu_cores", 1.75),  # (1.5 + .25) / 1 s twice, (3 + .5) / 2 s
    ("host_sys_ms_step", 250.0),  # 250, 250, 500
    ("host_minflt_step", 300),  # 300, 300, 600
    ("host_nivcsw_step", 2),  # 2, 2, 11
])
def test_wait_differences_between_consecutive_gates(name, want):
    assert METRICS[name].read({"journal": GATES}) == pytest.approx(want)
    # other events between the gates change nothing
    mixed = [e for g in GATES for e in (g, _collective("q1.s1", "alltoall", 1))]
    assert METRICS[name].read({"journal": mixed}) == pytest.approx(want)


@pytest.mark.parametrize("name", HOST)
def test_wait_the_first_gate_gives_none_and_two_give_one_reading(name):
    assert METRICS[name].read({"journal": GATES[:1]}) is None
    assert METRICS[name].read({"journal": GATES[:2]}) is not None


def test_wait_the_rate_is_over_the_events_own_ts():
    slow = [_gate(0.0, 0.0, 0.0, 0, 0), _gate(4.0, 2.0, 0.0, 0, 0)]
    assert METRICS["host_cpu_cores"].read({"journal": slow}) == pytest.approx(0.5)
    same_ts = [_gate(1.0, 0.0, 0.0, 0, 0), _gate(1.0, 2.0, 0.0, 0, 0)]
    assert METRICS["host_cpu_cores"].read({"journal": same_ts}) is None
    assert wait_readers.per_gate({"journal": slow}, "cpu_user_s", "cpu_sys_s") == 2.0


# What ``runsc`` journals: CPU seconds filled, the two counts 0 from the
# process's start.
UNCOUNTED = [_gate(10.0 + i, 10.0 + 5 * i, 1.0 + 0.25 * i, 0, 0) for i in range(4)]


@pytest.mark.parametrize("name,want", [
    ("host_minflt_step", None), ("host_nivcsw_step", None),
    ("host_cpu_cores", 5.25), ("host_sys_ms_step", 250.0),
])
def test_wait_a_count_that_is_0_at_every_gate_was_not_counted(name, want):
    """None, not a 0 that ``better: lower`` would read as a best; the
    fields the kernel does fill read on beside it."""
    got = METRICS[name].read({"journal": UNCOUNTED})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name", ["host_minflt_step", "host_nivcsw_step"])
def test_wait_a_count_that_stood_still_on_a_counting_kernel_reads_0(name):
    """A cumulative above 0 was counted: no growth between gates is 0."""
    still = [_gate(10.0 + i, 10.0 + i, 1.0, 700, 9) for i in range(3)]
    assert METRICS[name].read({"journal": still}) == 0
    # counted from the second gate on: the first pair's growth still reads
    late = [_gate(10.0, 1.0, 0.0, 0, 0), _gate(11.0, 2.0, 0.0, 40, 3)]
    assert METRICS[name].read({"journal": late}) in (40, 3)


def test_wait_a_gate_from_before_the_fields_costs_its_two_readings_only():
    old = _gate(11.5, 0, 0, 0, 0)
    for k in ("cpu_user_s", "cpu_sys_s", "minflt", "nivcsw"):
        del old["attrs"][k]
    run = {"journal": GATES[:2] + [old] + GATES[2:]}
    # pairs left: (g0, g1) and (g2, g3)
    assert METRICS["host_minflt_step"].read(run) == pytest.approx((300 + 600) / 2)


@pytest.mark.parametrize("name", NINE)
def test_wait_metrics_read_none_not_zero_on_every_recorded_fixture(name):
    fixtures = sorted(glob.glob(os.path.join(DATA, "*.jsonl")))
    assert len(fixtures) >= 6
    for path in fixtures:
        with open(path) as f:
            events = [json.loads(line) for line in f]
        assert METRICS[name].read({"journal": events}) is None, path
    assert METRICS[name].read({"journal": []}) is None
    # collectives and gates without the fields (the parent's program)
    bare = [_collective("q1.s1", "alltoall", 1, account=False)] + [
        {"ts": 1.0 + i, "event": "commit_gate", "attrs": {"rss_peak_bytes": 1}}
        for i in range(3)]
    assert METRICS[name].read({"journal": bare}) is None


def test_wait_the_recorded_fixtures_do_hold_collectives_and_gates():
    kinds = set()
    for path in glob.glob(os.path.join(DATA, "*.jsonl")):
        with open(path) as f:
            kinds |= {json.loads(line)["event"] for line in f}
    assert {"pg_collective", "commit_gate"} <= kinds


def test_wait_readers_sum_only_events_that_carry_every_field():
    run = _wire_run((2,))
    assert wait_readers.per_step(run, "tx_bytes") == 3 * 2000
    assert wait_readers.per_step(run, "tx_bytes", "no_such_field") is None
    assert wait_readers.per_step(run, "send_s", scale=1e3) == pytest.approx(3 * 2.0)
    only_others = {"journal": [e for e in run["journal"]
                               if e["attrs"]["op"] not in wait_readers.WIRE_OPS]}
    assert wait_readers.per_step(only_others, "tx_bytes") is None


TABLE = {
    "wire_send_ms": ("ms", "replica-axis allreduce", ["mistral-ft4"]),
    "wire_peer_wait_ms": ("ms", "replica-axis allreduce", ["mistral-ft4"]),
    "wire_recv_ms": ("ms", "replica-axis allreduce", ["mistral-ft4"]),
    "wire_sock_cpu_ms": ("ms", "replica-axis allreduce", ["mistral-ft4"]),
    "wire_xfer_bytes_step": ("bytes", "replica-axis allreduce", ["mistral-ft4"]),
    "host_cpu_cores": ("cores", "control plane", ["mistral-ft1", "mistral-ft4"]),
    "host_sys_ms_step": ("ms", "control plane", ["mistral-ft1", "mistral-ft4"]),
    # Held: their reader finds nothing on the benchmark's machines
    # (``runsc`` counts neither), and a metric lists the cells in which
    # it finds something to read.
    "host_minflt_step": None,
    "host_nivcsw_step": None,
}
LISTED = tuple(n for n in NINE if TABLE[n])


@pytest.mark.parametrize("name", NINE)
def test_wait_metric_is_the_entry_the_issue_tabled(name):
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entries = [m for m in table["per_layer"] if m["name"] == name]
    assert os.path.isfile(os.path.join(cells.HERE, "metrics", name + ".py"))
    assert METRICS[name].__doc__ and "None" in METRICS[name].__doc__
    if TABLE[name] is None:
        assert entries == [] and "No cell of BENCHMARK.json" in METRICS[name].__doc__
        workloads = []
    else:
        unit, layer, workloads = TABLE[name]
        assert entries == [{"name": name, "unit": unit, "better": "lower",
                            "source": "program_counter", "layer": layer,
                            "moves": "tok_s_chip", "workloads": workloads}]
    for w in table["workloads"]:
        reported = {m["name"] for m in cells.load_cell(w["name"]).per_layer}
        assert (name in reported) == (w["name"] in workloads)


def test_wait_the_listed_follow_everything_the_table_had():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in table["per_layer"]]
    assert names[names.index("wire_send_ms"):] == list(LISTED)
    assert names.index("wire_send_ms") > names.index("gdn_state_abs_max")
