#!/usr/bin/env bash
# The commit gate: the FULL test suite (258 tests), run as two lanes.
#
# Why two invocations instead of one `pytest tests/`: on the 1-core box
# a single combined run interleaves the heavyweight OS-process integ
# tests (each spawning 2-3 compiling children) into the long tail of
# accumulated in-process state and runs ~2x slower than the same tests
# split by tier (measured r5: combined >58 min and flaky vs 8m15s fast
# + 25m00s slow, both green). Same tests, same assertions, stable wall
# time — lane order: fast first (fails fast on logic regressions), slow
# integ second.
#
# Usage: bash tools/suite_gate.sh       # exits nonzero if EITHER lane fails
#        bash tools/suite_gate.sh obs   # observability smoke only: 2-replica
#                                       # demo with the event journal on,
#                                       # asserted through tools/obs_report.py
#        bash tools/suite_gate.sh trace # flight-recorder/trace smoke:
#                                       # 2-replica native kill+heal drill ->
#                                       # obs_trace.py Chrome trace, schema-
#                                       # checked with trace-id assertions
#        bash tools/suite_gate.sh chaos # seeded fault-injection soak:
#                                       # 2-replica DDP under the quick
#                                       # schedule -> CHAOS_SOAK.json, then a
#                                       # same-seed replay asserting the
#                                       # injection sequence is identical
#        bash tools/suite_gate.sh fleet # live fleet-health drill: 2-replica
#                                       # demo with a chaos heartbeat stall on
#                                       # one replica; /fleet.json must flag
#                                       # it straggler WHILE running, obs_top
#                                       # --once --check must render
#        bash tools/suite_gate.sh fleetload # synthetic-fleet load harness,
#                                       # quick mode: N=64 heartbeat/quorum/
#                                       # HTTP latency vs the budgets in
#                                       # fleet_load.py -> BENCH_FLEET_quick
#                                       # .json (full O(1000) ladder: run
#                                       # fleet_load.py directly)
#        bash tools/suite_gate.sh lint  # contract linter: dual-language
#                                       # invariants (golden constants, enums,
#                                       # ABI, RPC surface, event kinds, env
#                                       # knobs) proven from source; seconds,
#                                       # pure Python, no build needed
#        bash tools/suite_gate.sh san   # sanitizer lane: cpp_tests + the
#                                       # 2-replica allreduce/abort drill
#                                       # under TSan, ASan(+LSan) and UBSan
#        bash tools/suite_gate.sh perf  # perf attribution: 2-replica DDP
#                                       # drill under TORCHFT_PERF -> journal
#                                       # -> perf_report critical-path/overlap
#                                       # check
#        bash tools/suite_gate.sh recovery # recovery forensics drill:
#                                       # kill+heal with heal chaos armed ->
#                                       # BENCH_RECOVERY.json, episode report
#                                       # --check (phases must tile TTR)
#        bash tools/suite_gate.sh elastic # elastic membership drill:
#                                       # 2-replica DDP grows to 8 under
#                                       # load, seeded preemptions drain 5
#                                       # groups down to 3 -> BENCH_ELASTIC
#                                       # .json (join latency, heal GiB/s,
#                                       # goodput retention vs a static
#                                       # baseline), same-seed replay
#        bash tools/suite_gate.sh wan   # degraded-network drill: 2-region
#                                       # DiLoCo over a throttled wan link
#                                       # with mid-collective stripe tears
#                                       # -> BENCH_WAN.json, then a same-seed
#                                       # replay asserting the injection
#                                       # multiset is identical
#        bash tools/suite_gate.sh multijob # multi-tenant federation drill:
#                                       # M jobs x N replicas across two
#                                       # district lighthouses + a root,
#                                       # seeded per-job churn storm, cross-
#                                       # job isolation asserted bit-exact,
#                                       # district failover fenced at the
#                                       # root -> BENCH_FLEET.json multijob
#                                       # section
#        bash tools/suite_gate.sh detect # detection-latency drill: seeded
#                                       # ground-truth faults (hb stop,
#                                       # digest stall, dead leave, piggyback
#                                       # abort) vs the failure-evidence bus
#                                       # -> BENCH_DETECT.json, attribution
#                                       # report --check (phases tile, first
#                                       # source matches the fault kind),
#                                       # same-seed replay
#        bash tools/suite_gate.sh goodput # goodput ledger soak: 2-replica
#                                       # paced DDP with 1 kill/100 steps ->
#                                       # BENCH_GOODPUT.json, accounts must
#                                       # tile wall clock (eps 1e-6), kill
#                                       # cost attributed per fault kind,
#                                       # retention >= 0.95
#        bash tools/suite_gate.sh control # control-plane-loss drill: kill
#                                       # the active lighthouse mid-run ->
#                                       # warm-standby takeover (epoch+1),
#                                       # resurrected stale primary fenced
#                                       # out, bit-exact survivors ->
#                                       # BENCH_CONTROL.json, same-seed
#                                       # replay
#
# A drill's budgets (TTR, detection latency, goodput retention, fleet
# latencies ...) are a table in the drill's own file; the drill checks
# them against the report it has just built, so its exit code is the gate.
set -u
cd "$(dirname "$0")/.."

if [ "${1:-}" = "obs" ]; then
  echo "== obs smoke: 2-replica journaled demo -> obs_report =="
  exec timeout 300 env JAX_PLATFORMS=cpu python tools/obs_smoke.py
fi

if [ "${1:-}" = "trace" ]; then
  echo "== trace smoke: native kill+heal drill -> obs_trace Chrome trace =="
  exec timeout 600 env JAX_PLATFORMS=cpu python tools/obs_trace_smoke.py
fi

if [ "${1:-}" = "chaos" ]; then
  echo "== chaos soak: seeded 2-replica DDP drill (quick schedule) =="
  timeout 600 env JAX_PLATFORMS=cpu python tools/chaos_soak.py --quick \
    || exit 1
  echo "== chaos replay: same seed must reproduce the injection sequence =="
  exec timeout 600 env JAX_PLATFORMS=cpu python tools/chaos_soak.py \
    --replay CHAOS_SOAK.json
fi

if [ "${1:-}" = "fleet" ]; then
  echo "== fleet smoke: live straggler detection + obs_top =="
  exec timeout 600 env JAX_PLATFORMS=cpu python tools/obs_fleet_smoke.py
fi

if [ "${1:-}" = "fleetload" ]; then
  echo "== fleetload: synthetic N=64 fleet vs latency budgets =="
  exec timeout 600 env JAX_PLATFORMS=cpu python tools/fleet_load.py \
    --quick --out BENCH_FLEET_quick.json
fi

if [ "${1:-}" = "lint" ]; then
  echo "== lint: dual-language contract linter (tools/tft_lint.py) =="
  exec timeout 120 python tools/tft_lint.py --check --report LINT_REPORT.json
fi

if [ "${1:-}" = "wan" ]; then
  echo "== wan drill: 2-region DiLoCo over a degraded striped link =="
  timeout 600 env JAX_PLATFORMS=cpu python tools/wan_drill.py --quick \
    || exit 1
  echo "== wan replay: same seed must reproduce the injection multiset =="
  exec timeout 600 env JAX_PLATFORMS=cpu python tools/wan_drill.py \
    --replay BENCH_WAN.json
fi

if [ "${1:-}" = "elastic" ]; then
  echo "== elastic drill: 2->8->3 walk under seeded preemption =="
  # ~6 min wall: a static 2-replica goodput baseline leg + the elastic
  # leg (compute-dominant batch so samples/s is world-fair on 1 core).
  timeout 1700 env JAX_PLATFORMS=cpu python tools/elastic_drill.py --quick \
    || exit 1
  echo "== elastic replay: same seed must reproduce the preemption plan =="
  exec timeout 120 env JAX_PLATFORMS=cpu python tools/elastic_drill.py \
    --replay BENCH_ELASTIC.json
fi

if [ "${1:-}" = "recovery" ]; then
  echo "== recovery drill: kill+heal under heal chaos -> BENCH_RECOVERY =="
  timeout 600 env JAX_PLATFORMS=cpu python tools/recovery_drill.py --quick \
    || exit 1
  echo "== recovery report: episode phases must tile TTR exactly =="
  exec timeout 120 env JAX_PLATFORMS=cpu python tools/recovery_report.py \
    --from-bench BENCH_RECOVERY.json --check --min-episodes 1
fi

if [ "${1:-}" = "detect" ]; then
  echo "== detect drill: seeded faults vs the failure-evidence signal bus =="
  timeout 600 env JAX_PLATFORMS=cpu python tools/detect_drill.py --quick \
    || exit 1
  echo "== detect report: injection -> signal -> quorum -> react must tile =="
  timeout 120 env JAX_PLATFORMS=cpu python tools/detect_report.py \
    --from-bench BENCH_DETECT.json --check --require-detected \
    --min-injections 8 || exit 1
  echo "== detect replay: same seed must reproduce the fault plan =="
  exec timeout 120 env JAX_PLATFORMS=cpu python tools/detect_drill.py \
    --replay
fi

if [ "${1:-}" = "goodput" ]; then
  echo "== goodput soak: paced 2-replica DDP, 1 kill/100 steps =="
  timeout 900 env JAX_PLATFORMS=cpu python tools/goodput_soak.py --quick \
    || exit 1
  echo "== goodput report: accounts must tile wall clock (eps 1e-6) =="
  exec timeout 120 env JAX_PLATFORMS=cpu python tools/goodput_report.py \
    --from-bench BENCH_GOODPUT.json --check --min-windows 50
fi

if [ "${1:-}" = "control" ]; then
  echo "== control drill: lighthouse kill -> standby takeover -> fence =="
  timeout 600 env JAX_PLATFORMS=cpu python tools/lighthouse_drill.py --quick \
    || exit 1
  echo "== control replay: same seed must reproduce the kill schedule =="
  exec timeout 120 env JAX_PLATFORMS=cpu python tools/lighthouse_drill.py \
    --replay
fi

if [ "${1:-}" = "multijob" ]; then
  echo "== multijob: M jobs x N replicas, district->root federation =="
  exec timeout 600 env JAX_PLATFORMS=cpu python tools/fleet_load.py \
    --multijob --quick --out BENCH_FLEET.json
fi

if [ "${1:-}" = "san" ]; then
  echo "== san: cpp_tests + san_drill under TSan / ASan / UBSan =="
  exec timeout 3600 make -C torchft_tpu/_cpp san
fi

if [ "${1:-}" = "perf" ]; then
  echo "== perf smoke: journaled 2-replica DDP drill -> perf_report =="
  exec timeout 600 env JAX_PLATFORMS=cpu python tools/perf_smoke.py
fi

t0=$(date +%s)
echo "== lane 1/2: fast (pytest -m 'not slow') =="
timeout 1800 python -m pytest tests/ -m "not slow" -q -rf
fast_rc=$?
echo "== lane 2/2: slow integ (pytest -m slow) =="
timeout 5000 python -m pytest tests/ -m slow -q -rf
slow_rc=$?
t1=$(date +%s)
echo "== suite gate: fast_rc=$fast_rc slow_rc=$slow_rc wall=$((t1 - t0))s =="
[ "$fast_rc" = 0 ] && [ "$slow_rc" = 0 ]
