"""run.py end to end at a tiny size on the CPU, on a configuration, two
mixes, two cells and a per-layer metric that exist only under
benchmark/tests/table/: each is a file plus an entry, and neither run.py
nor worker.py names any of them. And the other side of that: an entry of
the repo's BENCHMARK.json does not run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells

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
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    return out


@pytest.mark.timeout(600)
def test_tiny_raw_cell_end_to_end_metrics():
    out = _last_line(_run("--table", TABLE, "--workload", "tiny-raw",
                          "--seed", "3", "--seconds", "2", "--trace", "0"))
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
    from benchmark import worker

    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for w in table["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
        worker.load_metric_readers(cell, "")
        cells.model_kwargs(cell.config, int(cell.mix["seq"]))
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
