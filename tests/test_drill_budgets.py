"""A drill's budgets live in the drill: each of the six drills that owns
budgeted metrics states them as one ``BUDGETS`` table, computes the values
from the report it has just built (``budget_values``) and checks them with
``drills.check_budgets``. These cases hold each drill's committed record
to its table, break it both ways (a value past its bound, a value gone),
and pin the thirty names, directions and bounds the tables hold."""

import copy
import importlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from drills import check_budgets  # noqa: E402

# drill module, its committed record, where one budgeted value sits in that
# record, the factor that pushes it past its bound, the metric it feeds.
DRILLS = [
    ("lighthouse_drill", "BENCH_CONTROL.json",
     ("summary", "failover_p95_s"), 100, "control.failover_p95_s"),
    ("detect_drill", "BENCH_DETECT.json",
     ("summary", "detect", "hb_stop.hb_lapse", "p95_s"), 10,
     "detect.hb_stop.hb_lapse.p95_s"),
    ("elastic_drill", "BENCH_ELASTIC.json",
     ("summary", "goodput_retention"), 0.9, "elastic.goodput_retention"),
    ("fleet_load", "BENCH_FLEET.json",
     ("multijob", "formation_p95_ms"), 10_000,
     "fleet.multijob_formation_p95_ms.m16x4"),
    ("goodput_soak", "BENCH_GOODPUT.json",
     ("summary", "fault_badput_s"), 10, "goodput.fault_badput_s"),
    ("recovery_drill", "BENCH_RECOVERY.json",
     ("summary", "ttr_p95_s"), 10, "recovery.ttr_p95_s"),
]
IDS = [d[0] for d in DRILLS]


def _record(name):
    with open(os.path.join(REPO, name)) as f:
        return json.load(f)


def _problems(mod, record):
    if mod.__name__ == "fleet_load":
        # Its budgets bind by the run that measured them; the committed
        # record holds one run of each mode.
        return [p for section in ("fleets", "restart", "multijob")
                for p in mod.budget_problems(record, section)]
    return check_budgets(mod.budget_values(record), mod.BUDGETS)


def _parent(record, path):
    for key in path[:-1]:
        record = record[key]
    return record


@pytest.mark.parametrize("drill, record, _path, _factor, _metric", DRILLS,
                         ids=IDS)
def test_committed_record_meets_its_budgets(drill, record, _path, _factor,
                                            _metric):
    assert _problems(importlib.import_module(drill), _record(record)) == []


@pytest.mark.parametrize("drill, record, path, factor, metric", DRILLS,
                         ids=IDS)
def test_value_past_its_bound_is_a_problem(drill, record, path, factor,
                                           metric):
    doc = copy.deepcopy(_record(record))
    _parent(doc, path)[path[-1]] *= factor
    problems = _problems(importlib.import_module(drill), doc)
    assert any(p.startswith(metric + ":") and "breaks budget" in p
               for p in problems), problems


@pytest.mark.parametrize("drill, record, path, _factor, metric", DRILLS,
                         ids=IDS)
def test_missing_budgeted_value_is_a_problem(drill, record, path, _factor,
                                             metric):
    doc = copy.deepcopy(_record(record))
    del _parent(doc, path)[path[-1]]
    problems = _problems(importlib.import_module(drill), doc)
    assert any(p.startswith(metric + ":") and "not measured" in p
               for p in problems), problems


def test_check_budgets_directions_and_equality():
    table = (("a.s", "lower", 2.0, ""), ("b.frac", "higher", 0.5, ""))
    assert check_budgets({"a.s": 2.0, "b.frac": 0.5}, table) == []
    assert [p.split(":")[0] for p in
            check_budgets({"a.s": 2.1, "b.frac": 0.49}, table)] == [
                "a.s", "b.frac"]
    # An unbudgeted value is nobody's problem.
    assert check_budgets({"a.s": 1.0, "b.frac": 1.0, "c": 9e9}, table) == []


# What the pre-chip gate's baselines file held with a ``budget`` key when
# the tables took its place (4d8e932): no budget is lost, loosened or added in a move.
THIRTY = {
    "control.failover_p95_s": ("lower", 20.0),
    "control.quorum_gap_s": ("lower", 30.0),
    "control.stale_quorums_accepted": ("lower", 0.0),
    "detect.abort_piggyback.native_abort.p95_s": ("lower", 2.0),
    "detect.dead_leave.proc_death.p95_s": ("lower", 2.0),
    "detect.digest_stall.digest_anomaly.p95_s": ("lower", 2.0),
    "detect.hb_stop.hb_lapse.p95_s": ("lower", 5.0),
    "detect.p95_s": ("lower", 5.0),
    "elastic.goodput_retention": ("higher", 0.8),
    "fleet.fleet_json_p95_us.n1024": ("lower", 500000.0),
    "fleet.fleet_json_p95_us.n256": ("lower", 300000.0),
    "fleet.multijob_formation_p95_ms.m16x4": ("lower", 2000.0),
    "fleet.multijob_formation_p95_ms.m4x2": ("lower", 2000.0),
    "fleet.multijob_isolation_violations.m16x4": ("lower", 0.0),
    "fleet.multijob_isolation_violations.m4x2": ("lower", 0.0),
    "fleet.multijob_sibling_hb_p95_us.m16x4": ("lower", 400000.0),
    "fleet.multijob_sibling_hb_p95_us.m4x2": ("lower", 400000.0),
    "fleet.quorum_formation_ms.n1024": ("lower", 2000.0),
    "fleet.restart_repopulate_s.n256": ("lower", 60.0),
    "fleet.restart_reregister_s.n256": ("lower", 30.0),
    "goodput.fault_badput_s": ("lower", 12.0),
    "goodput.retention": ("higher", 0.95),
    "recovery.heal_gib_s.http": ("higher", 0.02),
    "recovery.phase_p95_s.catchup": ("lower", 60.0),
    "recovery.phase_p95_s.detect": ("lower", 12.0),
    "recovery.phase_p95_s.quorum": ("lower", 30.0),
    "recovery.phase_p95_s.rebuild": ("lower", 5.0),
    "recovery.phase_p95_s.transfer": ("lower", 5.0),
    "recovery.ttr_p50_s": ("lower", 60.0),
    "recovery.ttr_p95_s": ("lower", 60.0),
}


def test_the_six_tables_hold_the_thirty_budgets():
    rows = [row for drill in IDS
            for row in importlib.import_module(drill).BUDGETS]
    assert len(rows) == len(THIRTY) == 30
    assert {m: (d, float(b)) for m, d, b, _why in rows} == THIRTY
    assert all(why.strip() for _m, _d, _b, why in rows)
