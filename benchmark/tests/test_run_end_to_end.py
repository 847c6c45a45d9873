"""run.py end to end at a tiny size on the CPU, on two configurations (one
of an architecture of its own), two mixes, three cells and a per-layer
metric that exist only under benchmark/tests/table/: each is files plus
an entry, and neither run.py nor worker.py names any of them. And the
other side of that: an entry of the repo's BENCHMARK.json does not run
without a TPU.

Then ``join`` and ``report`` on fabricated result files: which group's
memory peak a cell of several groups reports, and what a run that lost a
step writes to standard error (``data/lost_step_journal.jsonl``: four
groups, written out by hand in the shape the program journals; at step
41 group 2's host stands still, the lighthouse evicts it, the three
peers' collectives abort and each refuses the step, group 2 is voted
down). And the step's own counters, from the raw trainer to the two
metrics of the expert layer that read them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells, gate_readers, run, worker

RUN = os.path.join(cells.HERE, "run.py")
TABLE = os.path.join(cells.HERE, "tests", "table", "BENCHMARK.json")


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, RUN, *argv], capture_output=True, text=True,
        env=env, timeout=600,
    )


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device", "compared"}
    assert list(out)[-1] == "compared"  # every number compared beside its limit, last
    assert all(set(c) == {"value", "limit"} for c in out["compared"].values())
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes",
                                  "memory_peak_bytes_by_group"}
    peaks = out["device"]["memory_peak_bytes_by_group"]
    assert peaks and max(peaks) == out["device"]["memory_peak_bytes"]  # one a group
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    return out


@pytest.mark.timeout(600)
@pytest.mark.parametrize("name", ["tiny-raw", "tiny-moe-raw"])
def test_tiny_raw_cell_end_to_end_metrics(name):
    """The second is of an architecture that exists only beside the test
    table (arch/moe_decoder): model, reference check and all."""
    out = _last_line(_run("--table", TABLE, "--workload", name,
                          "--seed", "3000000001", "--seconds", "2", "--trace", "0"))
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 3
    assert set(out["metrics"]) == {"setup_s", "tok_s_chip", "peak_hbm_gib"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1


@pytest.mark.timeout(600)
def test_tiny_two_group_cell_traced_per_layer_metrics():
    out = _last_line(_run("--table", TABLE, "--workload", "tiny-ft2",
                          "--seed", "4", "--seconds", "2", "--trace", "1"))
    # two groups in lockstep stopped on the same step with equal
    # parameters (part of `correct`), and counted both groups' steps
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] % 2 == 0 and out["device"]["count"] == 2
    got = set(out["metrics"])
    # the table's own metric, found beside the table; the benchmark's,
    # found in benchmark/metrics; flash_ms has nothing to read on a CPU
    # (no device trace) and is left out
    assert {"steps_per_s", "host_other_ms", "allreduce_ms", "wire_bytes_step",
            "commit_ms", "setup_import_s"} <= got
    assert "flash_ms" not in got
    assert out["metrics"]["wire_bytes_step"]["value"] > 0


def test_a_cell_of_the_repos_table_needs_a_tpu():
    name = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["workloads"][0]["name"]
    proc = _run("--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not proc.stdout.strip(), "a run without a TPU printed a result"
    assert "wanted platform 'tpu'" in proc.stderr


def test_table_and_files_agree():
    """Every cell loads: its configuration and mix exist, its chips are
    its mix's groups times chips per group, each of its per-layer metrics
    has a reader file, and at most a quarter of the cells take four chips."""
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for w in table["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
        worker.load_metric_readers(cell, "")
        cell.adapter.model_config(cell.config, int(cell.mix["seq"]))
        assert cell.reference.loss_and_grads and cell.flops.total_params
    four = [w for w in table["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(table["workloads"]) // 4)


def test_table_is_within_the_contracts_limits():
    """What the driver refuses before any run, checked here at no chip
    time: exact keys, names, units, lengths, the 43200 s budget at the
    full 24 cells, files under `paths`."""
    import re

    path = os.path.join(cells.ROOT, "BENCHMARK.json")
    table = cells.load_json(path)
    assert os.path.getsize(path) <= 64 * 1024
    assert set(table) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s  # noqa: E731
    assert 1 <= table["run_seconds"] <= 51
    assert (2 + 14 * 24) * (table["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for c in table["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in table["paths"]))
        assert all(name.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        held = cells.load_json(os.path.join(cells.ROOT, c["file"]))
        assert set(c["reduced"]) == set(held["reduced"])
    assert 2 <= len(table["workloads"]) <= 24
    for w in table["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in table["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {c["name"] for c in table["configs"]} == {w["config"] for w in table["workloads"]}
    e2e = {m["name"] for m in table["end_to_end"]}
    for m in table["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in e2e
    layers = set()
    for m in table["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        layers.add(m["layer"])
    for m in table["end_to_end"] + table["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in table["end_to_end"] + table["per_layer"]]
    assert len(set(names)) == len(names) and len(table["per_layer"]) <= 128
    # PERF.md §3 names the layers, letter for letter
    with open(os.path.join(cells.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(f"| {layer} |" in perf for layer in layers), layers
    for root, _, files in os.walk(cells.HERE):
        if "__pycache__" in root:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), cells.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


# -- join and report on fabricated result files ---------------------------

GIB = 2**30
LEVELS = [int(13.634 * GIB), int(13.853 * GIB), int(13.634 * GIB), int(14.315 * GIB)]
LOST = os.path.join(cells.HERE, "tests", "data", "lost_step_journal.jsonl")


def _result(group, peak, failed=0, lost=None):
    """What worker.py writes for one group, as far as join reads it."""
    r = {
        "group": group, "seed": 1, "memory_peak_bytes": peak,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "window": {"t_start": 100.0 + group, "t_end": 151.0, "attempted": 20,
                   "failed": failed, "tokens": (20 - failed) * 16384},
        "checks": {"programs_compiled_in_window": 0, "manager_step": 23,
                   "reference": {"ok": True, "loss_rel_diff": 3e-5, "loss_rel_tol": 2e-4,
                                 "grad_rel_l2_worst": 0.02, "grad_rel_l2_tol": 0.04}},
        "fingerprint": "f" * 64, "steps_done": 23,
        "losses": [10.4] * 23, "step_s": [2.5] * 20,
    }
    if lost is not None:
        r["lost_steps"] = lost
    return r


def test_a_cell_of_several_groups_reports_its_smallest_groups_peak():
    cell = cells.load_cell("mistral-ft4")
    out = run.join(cell, [_result(g, p) for g, p in enumerate(LEVELS)], 0, "tpu")
    assert out["correct"] is True and (out["attempted"], out["failed"]) == (80, 0)
    assert out["metrics"]["peak_hbm_gib"] == {"value": LEVELS[0] / GIB, "unit": "GiB"}
    assert out["device"]["memory_peak_bytes"] == LEVELS[3]  # the other end stays
    assert out["device"]["memory_peak_bytes_by_group"] == LEVELS  # group order
    assert out["device"]["count"] == 4
    # a true rise in the step's footprint raises every group, the smallest too
    up = run.join(cell, [_result(g, p + GIB // 4) for g, p in enumerate(LEVELS)], 0, "tpu")
    assert up["metrics"]["peak_hbm_gib"]["value"] == (LEVELS[0] + GIB // 4) / GIB
    # the other end-to-end metrics are what they were: all groups' tokens
    # from the first start to the last end, over the cell's chips
    assert out["metrics"]["tok_s_chip"]["value"] == pytest.approx(80 * 16384 / 51.0 / 4)
    assert set(out["metrics"]) == {"setup_s", "tok_s_chip", "peak_hbm_gib"}


@pytest.mark.parametrize("name", ["mistral-raw", "mistral-ft1", "internlm2-raw", "olmoe-raw"])
def test_in_a_cell_of_one_group_the_three_readings_agree(name):
    out = run.join(cells.load_cell(name), [_result(0, LEVELS[1])], 0, "tpu")
    assert out["correct"] is True
    assert out["metrics"]["peak_hbm_gib"]["value"] * GIB == LEVELS[1]
    assert out["device"]["memory_peak_bytes"] == LEVELS[1]
    assert out["device"]["memory_peak_bytes_by_group"] == [LEVELS[1]]


def _journal(group):
    return [e for e in worker.read_jsonl(LOST) if e["replica_id"].startswith(f"{group}:")]


def _report(tmp_path, capsys, results):
    for r in results:
        (tmp_path / f"worker_g{r['group']}.log").write_text(f"tail of group {r['group']}\n")
        (tmp_path / f"result_g{r['group']}.json").write_text(json.dumps(r))
    rc = run.report(cells.load_cell("mistral-ft4"), str(tmp_path), 0, "tpu")
    return rc, capsys.readouterr()


def test_a_run_that_lost_a_step_says_why_on_standard_error(tmp_path, capsys):
    lost = {g: gate_readers.explain(_journal(g)) for g in range(4)}
    assert [len(lost[g]) for g in range(4)] == [1, 1, 2, 1]
    rc, said = _report(tmp_path, capsys, [
        _result(g, p, failed=1, lost=lost[g]) for g, p in enumerate(LEVELS)])
    assert rc == 0
    lines = said.err.splitlines()
    refused = [x for x in lines if x.startswith("refused-gate:")]
    evicted = [x for x in lines if x.startswith("lh-evicted:")]
    assert len(refused) == 4 and len(evicted) == 1
    assert refused[0] == (
        "refused-gate: step=41 cause=local_error error_class=ProcessGroupAborted "
        "quorum_id=5 participants=[0,1,2,3] hb_gap_max_ms=108.0 hb_rtt_max_ms=7.0 group=0")
    assert "cause=peer_voted_no" in refused[2] and refused[2].endswith("group=2")
    assert "error_class" not in refused[2]  # the record has none: left out
    assert evicted[0] == (
        "lh-evicted: step=41 seq=3 gap_ms=3703 budget_ms=1200 out_ms=1717 "
        "sender_gap_ms=1987.7 sender_rtt_ms=1716.5 group=2")
    # after the workers' tails, before the compared numbers, which come last
    at = {k: min(i for i, x in enumerate(lines) if k in x)
          for k in ("tail of group 3", "refused-gate:", "compared:")}
    assert at["tail of group 3"] < at["refused-gate:"] < at["compared:"]
    assert "compared:" in lines[-1]
    # standard output's one line is what it is without the lines
    (line,) = said.out.splitlines()
    out = json.loads(line)
    assert (out["attempted"], out["failed"], out["correct"]) == (80, 4, True)
    assert "lost_steps" not in out and "refused" not in line


def test_a_run_that_lost_no_step_writes_nothing_more(tmp_path, capsys):
    rc, said = _report(tmp_path, capsys, [_result(g, p) for g, p in enumerate(LEVELS)])
    assert rc == 0 and "refused-gate:" not in said.err and "lh-evicted:" not in said.err
    assert json.loads(said.out)["failed"] == 0
    # the by-group readings are in what the driver keeps of standard error
    assert f"memory_peak_bytes by group {LEVELS}" in said.err


def test_at_most_forty_lines_a_run(tmp_path, capsys):
    storm = [f"lh-evicted: step=9 seq={i}" for i in range(30)]
    rc, said = _report(tmp_path, capsys, [
        _result(g, p, failed=1, lost=storm) for g, p in enumerate(LEVELS)])
    lines = [x for x in said.err.splitlines() if x.startswith(("refused-gate:", "lh-evicted:"))]
    assert rc == 0 and len(lines) == run.LOST_STEP_LINES == 40
    assert "80 more such lines" in said.err


def test_explain_reads_only_what_lost_a_step():
    from benchmark.tests import test_span_metrics as recorded

    assert gate_readers.explain([]) == []
    assert gate_readers.explain(_journal(0)[:1]) == []  # a committed gate
    # PR 23's recorded gates carry none of the fields: a line with the step alone
    old = [dict(e, attrs=dict(e["attrs"], committed=False))
           for e in recorded._run("host-path")["journal"] if e["event"] == "commit_gate"][:1]
    (line,) = gate_readers.explain(old)
    assert line.startswith("refused-gate: step=") and "cause" not in line


# -- the step's own counters ----------------------------------------------


def test_the_raw_trainer_hands_the_steps_counters_to_the_records():
    from benchmark.metrics import expert_dropped_step, expert_max_load
    from benchmark.trainers import raw

    seen = {}
    for name in ("tiny-moe-raw", "tiny-raw"):
        cell = cells.load_cell(name, TABLE)
        out = raw.Trainer(worker.Ctx(cell, 7, 0, False)).step()
        assert out.committed and out.tokens == cell.mix["batch"] * cell.mix["seq"]
        assert isinstance(out.loss, float) and "loss" not in out.counters
        assert all(isinstance(v, float) for v in out.counters.values())
        seen[name] = {"records": [{"counters": out.counters}] * 3}
    assert {"moe_dropped", "moe_max_load", "router_aux", "router_z"} <= set(
        seen["tiny-moe-raw"]["records"][0]["counters"])
    # four experts, two a token: the largest holds between the mean and all of it
    assert 1.0 <= expert_max_load.read(seen["tiny-moe-raw"]) <= 4.0
    assert expert_dropped_step.read(seen["tiny-moe-raw"]) >= 0.0
    # a dense step counts no such thing: nothing to read, not 0
    assert expert_max_load.read(seen["tiny-raw"]) is None
    assert expert_dropped_step.read(seen["tiny-raw"]) is None


@pytest.mark.parametrize("name,counter", [("expert_dropped_step", "moe_dropped"),
                                          ("expert_max_load", "moe_max_load")])
def test_a_counter_metric_is_the_median_over_the_steps_that_carry_it(name, counter):
    import importlib

    read = importlib.import_module(f"benchmark.metrics.{name}").read
    steps = [{"counters": {counter: v, "grad_norm": 1.0}} for v in (3.0, 0.0, 4.5, 3.5)]
    assert read({"records": steps}) == pytest.approx(3.25)
    assert read({"records": steps + [{"counters": {}}, {}]}) == pytest.approx(3.25)
    assert read({"records": [{"counters": {"grad_norm": 1.0}}]}) is None
    assert read({"records": []}) is None
    # an entry of the table for the sparse cell only, read from the program's counter
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in table["per_layer"] if m["name"] == name]
    assert (entry["source"], entry["layer"], entry["moves"], entry["workloads"]) == (
        "program_counter", "expert layer", "tok_s_chip", ["olmoe-raw"])
