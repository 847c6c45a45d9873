// Lighthouse: the global quorum coordinator for torchft-tpu.
//
// Capability parity with the reference's src/lighthouse.rs:68-480:
// heartbeats + participants maps, a tick loop running quorum_compute every
// quorum_tick_ms, quorum_id bumps on membership change or commit failures,
// blocking Quorum requests answered via broadcast, an HTTP status dashboard
// served on the same port (sniffed by first bytes), and a kill endpoint that
// forwards a Kill message to a member's manager address.
//
// Wire protocol: length-prefixed JSON frames (see net.hpp). Requests:
//   {"type":"heartbeat","replica_id":...[,"job":J,"digest":{...},
//       "hb_interval_ms":N]}
//   {"type":"quorum","timeout_ms":N,"requester":{QuorumMember}[,"job":J]}
//   {"type":"status"}
//   {"type":"fleet"[,"job":J]}   (live fleet-health table, the framed twin of
//       GET /fleet.json: per-replica digest rows + aggregates + anomalies)
//   {"type":"kill","replica_id":...[,"job":J]}
// HTTP: GET / or /status (dashboard), GET /fleet.json[?job=J] (live health
// table), GET/POST /replica/<id>/kill.
//
// Multi-tenant namespaces: every frame may carry a "job" id; an absent or
// empty field maps to "default" (wire back-compat with pre-namespace
// clients). Each job owns a fully isolated control-plane island — its own
// participant/heartbeat/quorum tables, fleet-health table, anomaly detectors
// and ring, aggregate trackers, and /fleet.json snapshot cache — under its
// OWN mutex, so one job's churn or quorum storm cannot stall another job's
// heartbeat/quorum hot path or bump its quorum generation.
//
// Incremental quorum compute: registrations no longer trigger a full
// O(N log N) quorum_compute each (the O(N^2) registration storm that put
// quorum formation at ~4 s for N=1024). Each join/leave maintains O(1) gate
// counters (previous members re-registered; heartbeating replicas not yet
// registered); the full quorum_compute — still the single source of truth —
// only runs when the gate says a quorum CAN form, plus on the periodic tick
// as the time-driven (heartbeat expiry, join timeout) fallback. A gate bug
// can therefore only delay a formation by one tick, never form a wrong one.
//
// Federation: a lighthouse started with a root address periodically reports
// a per-job rollup upward over the SAME heartbeat frame type (piggyback
// channel), tagged with its district name and fencing epoch. The root keeps
// a per-district table with per-district epoch fencing — after a district
// failover the old primary's rollups are dropped, and a district's loss or
// failover never perturbs sibling districts or other jobs' tables.
//
// Live fleet plane: heartbeats optionally carry a StepDigest (compact
// per-replica health summary built by telemetry.StepDigest). The lighthouse
// keeps a rolling per-replica fleet table PER JOB, runs an online
// straggler/anomaly detector (relative step-rate slowdown vs the job median,
// heartbeat-gap jitter against the sender-declared cadence, commit-failure
// streaks), and serves it all at /fleet.json. Digest-driven rules evaluate
// at heartbeat ARRIVAL (same digest sequence => same anomaly sequence, so
// chaos replays reproduce alerts); only the time-based rules (open heartbeat
// gaps, staleness) live in the tick scan.
#pragma once

#include <atomic>
#include <condition_variable>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <set>
#include <string>
#include <thread>
#include <vector>

#include "conn_tracker.hpp"
#include "quorum.hpp"

namespace tft {

// Lock-free log-bucket latency histogram: the C++ twin of
// telemetry._HIST_BOUNDS / _hist_percentile. Bucket i (i in 0..27) holds
// samples with latency <= 2^i microseconds (1 us doubling up to ~134 s);
// bucket 28 is overflow. Percentiles report the UPPER bound of the bucket
// containing the quantile, so they over-estimate within one power of two —
// identical semantics to the Python side, which keeps dashboards comparable
// across both planes.
class LatencyHist {
 public:
  static constexpr int kFinite = 28;
  static constexpr int kBuckets = kFinite + 1;

  struct Snap {
    int64_t count = 0;
    int64_t sum_us = 0;
    int64_t buckets[kBuckets] = {0};
  };

  // First bucket whose upper bound (2^i us) covers the sample; matches
  // bisect.bisect_left(_HIST_BOUNDS, dt) on the Python side.
  static int bucket_of(int64_t us) {
    if (us <= 1) return 0;
    for (int i = 1; i < kFinite; i++)
      if ((int64_t{1} << i) >= us) return i;
    return kFinite;  // overflow
  }

  void observe_us(int64_t us) {
    if (us < 0) us = 0;
    buckets_[bucket_of(us)].fetch_add(1, std::memory_order_relaxed);
    count_.fetch_add(1, std::memory_order_relaxed);
    sum_us_.fetch_add(us, std::memory_order_relaxed);
  }

  Snap snapshot() const {
    Snap s;
    s.count = count_.load(std::memory_order_relaxed);
    s.sum_us = sum_us_.load(std::memory_order_relaxed);
    for (int i = 0; i < kBuckets; i++)
      s.buckets[i] = buckets_[i].load(std::memory_order_relaxed);
    return s;
  }

  // Upper-bound quantile from bucket counts (telemetry._hist_percentile):
  // 0 with no samples; an empty bucket prefix never satisfies the target;
  // the overflow bucket reports the last finite bound.
  static int64_t percentile_us(const Snap& s, double q) {
    int64_t total = 0;
    for (int i = 0; i < kBuckets; i++) total += s.buckets[i];
    if (total == 0) return 0;
    double target = q * static_cast<double>(total);
    int64_t cum = 0;
    for (int i = 0; i < kBuckets; i++) {
      if (s.buckets[i] == 0) continue;
      cum += s.buckets[i];
      if (static_cast<double>(cum) >= target)
        return int64_t{1} << (i < kFinite ? i : kFinite - 1);
    }
    return int64_t{1} << (kFinite - 1);
  }

 private:
  std::atomic<int64_t> buckets_[kBuckets] = {};
  std::atomic<int64_t> count_{0};
  std::atomic<int64_t> sum_us_{0};
};

// Exact running median over a multiset of doubles with O(log N)
// insert/erase, replacing the per-heartbeat full-table sort. Maintains the
// same "upper median" the old fleet_median(sort) returned: lo_ holds the
// smaller floor(n/2) values, hi_ the larger ceil(n/2), so
// median() == sorted[n/2] bit-for-bit (the property tests in
// tests/test_fleet.py pin this equality against a full recompute).
class MedianTracker {
 public:
  void insert(double v) {
    if (hi_.empty() || v >= *hi_.begin())
      hi_.insert(v);
    else
      lo_.insert(v);
    rebalance();
  }

  // No-op if v is not present (defensive: an aggregate drift bug should
  // surface as a wrong median in the property test, not a crash).
  void erase(double v) {
    auto it = hi_.find(v);
    if (it != hi_.end()) {
      hi_.erase(it);
    } else {
      auto lo = lo_.find(v);
      if (lo == lo_.end()) return;
      lo_.erase(lo);
    }
    rebalance();
  }

  size_t size() const { return lo_.size() + hi_.size(); }
  double median() const { return hi_.empty() ? 0.0 : *hi_.begin(); }

 private:
  void rebalance() {
    while (hi_.size() > lo_.size() + 1) {
      lo_.insert(*hi_.begin());
      hi_.erase(hi_.begin());
    }
    while (lo_.size() > hi_.size()) {
      auto it = std::prev(lo_.end());
      hi_.insert(*it);
      lo_.erase(it);
    }
  }

  std::multiset<double> lo_, hi_;
};

// Size of the closed badput classification (telemetry.BADPUT_KINDS); the names
// live in lighthouse.cc (kBadputKindNames, lint-mirrored positionally
// against the Python tuple). The digest's "acct" array is indexed by it.
constexpr int kNumBadputKinds = 10;

class Lighthouse {
 public:
  Lighthouse(const std::string& bind_host, int port, LighthouseOpts opts);
  ~Lighthouse();

  // Starts listener + tick threads. Returns false if bind failed.
  bool start();
  void stop();

  int port() const { return port_; }
  std::string address() const;

  // Exposed for tests: runs one tick synchronously (all jobs).
  void tick();

 private:
  // ---- live fleet health plane (per job) ----
  struct FleetEntry {
    Json digest;                     // last StepDigest wire dict
    bool has_digest = false;
    int64_t digest_ms = 0;           // arrival time of that digest
    int64_t last_hb_ms = 0;          // last heartbeat arrival
    int64_t hb_interval_ms = 0;      // sender-declared cadence (0 = unknown)
    double hb_gap_ewma_ms = 0.0;     // inter-arrival EWMA (old-client fallback)
    int64_t hb_count = 0;
    int64_t last_jitter_ms = 0;      // when a closed gap last blew the budget
    std::set<std::string> flags;     // active anomaly flags
    int64_t straggler_until_ms = 0;  // sticky display flag
    std::string last_signal;         // last failure-signal source (evidence)
    int64_t last_signal_ms = 0;      // when that signal was recorded
  };

  // Generation-tagged cached fleet snapshot (per job). The full /fleet.json
  // payload is only O(N)-rebuilt when the cached copy is older than
  // fleet_snap_ms; the rebuild copies raw rows under the job's hot lock
  // (cheap) and does the JSON build + dump OFF it, so heartbeats never wait
  // behind serialization. Keyed per job: one job's content change never
  // forces a rebuild of (or serves a stale gen to) another job.
  struct FleetSnapshot {
    int64_t gen = -1;       // job fleet_gen at build
    int64_t built_ms = 0;   // wall time at build (== payload ts_ms)
    Json json;              // the /fleet.json object
    std::string body;       // pre-dumped body served verbatim over HTTP
  };

  // One fully isolated control-plane island per job namespace. Everything
  // here is guarded by the island's OWN mu (snap by snap_mu, rebuilds by
  // rebuild_mu — same ordering discipline as the old instance-wide locks:
  // rebuild_mu strictly outside snap_mu and mu; snap_mu never held with
  // mu; never two jobs' mu held at once).
  struct JobState {
    std::string name;
    std::mutex mu;
    std::condition_variable cv;

    // ---- quorum plane ----
    LighthouseState state;
    std::optional<Quorum> last_quorum;  // most recently broadcast quorum
    int64_t quorum_gen = 0;             // bumped on every broadcast
    // Serialized {"ok":true,"quorum":...} built ONCE per formation and
    // shared by every waiter: with N waiters each dumping an O(N)
    // participant list the broadcast is O(N^2) — at N=1024 that was ~3.7 s
    // of lighthouse CPU per formation.
    std::shared_ptr<const std::string> quorum_payload;
    int64_t joins_total = 0;   // members added across quorum transitions
    int64_t leaves_total = 0;  // members gone across quorum transitions
    std::string last_reason;   // why no quorum yet (for status page)
    // Max quorum_id seen in this job's manager heartbeats. A takeover
    // standby resumes the job's numbering above it (strict monotonicity
    // across failover without a lighthouse-to-lighthouse channel).
    int64_t observed_quorum_id = 0;

    // ---- incremental-quorum gate counters (see quorum_gate_locked) ----
    std::set<std::string> prev_ids;  // ids of prev_quorum members
    int64_t prev_present = 0;        // prev_ids currently registered
    int64_t hb_not_joined = 0;       // heartbeating ids not registered

    // ---- fleet plane ----
    std::map<std::string, FleetEntry> fleet;
    std::deque<Json> anomalies;   // rise-edge anomaly ring (capped)
    int64_t anomaly_seq = 0;      // total anomalies ever (ring drops old)
    int64_t anomalies_dropped = 0;  // rise-edges evicted from the ring

    // ---- failure-evidence plane ----
    // Ring of failure signals (same discipline as the anomaly ring: capped,
    // overflow pops the oldest and bumps signals_dropped). Each entry:
    // {seq, ts_ms, replica_id, source, site, job, detail}. signal_seq is
    // the monotonic total ever recorded — consumers diff it as a cursor.
    std::deque<Json> signals;
    int64_t signal_seq = 0;
    int64_t signals_dropped = 0;
    std::map<std::string, int64_t> signal_counts;  // per-source totals
    // Evidence evictions not yet told to the evicted id: what the scan saw
    // and erased, and (once the id is heard from again) how long it was
    // out. The first heartbeat ack after the re-admission carries the
    // record back to the sender and drops it. Bounded: kEvictedCap.
    struct Evicted {
      int64_t seq = 0;          // the hb_lapse signal's seq
      int64_t at_ms = 0;        // when the scan evicted it
      int64_t open_gap_ms = 0;  // the open gap the scan judged
      int64_t budget_ms = 0;
      std::string erased;       // "heartbeat", "+participant", "+quorum_request"
      int64_t gap_ms = -1;      // closed gap, arrival to arrival (-1: open)
      int64_t out_ms = -1;      // eviction -> first frame from the id
      std::string via;          // that frame: "heartbeat", "quorum request"
    };
    std::map<std::string, Evicted> evicted;
    // Quorum requests parked in quorum_rpc, by requester: what an eviction
    // finds to erase besides the table entries.
    std::map<std::string, int64_t> parked;
    int64_t fleet_gen = 0;  // bumped on every fleet-table mutation
    int64_t flagged = 0;    // entries with a non-empty flag set
    int64_t n_digest = 0;   // entries with a digest
    // Incremental O(log N) aggregate state, updated at digest arrival/leave.
    MedianTracker agg_rates;        // digest rates > 0
    MedianTracker agg_steps;        // digest steps (as double, like the sort)
    MedianTracker agg_gps;          // digest goodputs
    std::multiset<int64_t> agg_cfs;  // digest commit-failure streaks

    // ---- time-accounting (goodput) plane ----
    // Running per-kind badput second sums over rows whose digest carries
    // an "acct" vector — maintained at digest swap exactly like the
    // median trackers (remove old contribution, insert new), so the job
    // goodput fraction is O(1) at read time.
    double agg_badput[kNumBadputKinds] = {};
    int64_t n_acct = 0;          // rows currently contributing to agg_badput
    int64_t first_seen_ms = 0;   // first heartbeat ever (MTBF denominator)
    int64_t hard_signals = 0;    // hard-evidence rise edges (MTBF numerator)
    // ETTR episode: opened on a hard-signal rise, closed when any digest
    // advances past the fleet max step as of the fault (forward progress
    // resumed). One open episode at a time — overlapping faults extend it.
    bool ettr_open = false;
    int64_t ettr_open_ms = 0;
    int64_t ettr_open_step = 0;
    double ettr_sum_s = 0.0;
    int64_t ettr_n = 0;
    // SLO burn-rate evaluator: rise-edge slo_burn ring (same discipline
    // as the anomaly ring — monotone seq, bounded, drops counted).
    bool slo_burning = false;
    std::deque<Json> slo_burns;
    int64_t slo_seq = 0;
    int64_t slo_dropped = 0;

    // ---- per-job snapshot cache ----
    std::mutex snap_mu;     // guards snap only
    std::mutex rebuild_mu;  // single-flight rebuild
    std::shared_ptr<const FleetSnapshot> snap;
  };

  // District table kept by a ROOT lighthouse: one row per reporting district
  // lighthouse, fed by rollup-tagged heartbeat frames. Guarded by
  // districts_mu_ (never held together with a job mu).
  struct DistrictEntry {
    int64_t last_hb_ms = 0;
    int64_t epoch = 0;          // max fencing epoch seen (per-district fence)
    int64_t hb_count = 0;
    int64_t failovers = 0;      // epoch advances observed (district failover)
    int64_t stale_dropped = 0;  // rollups fenced out (old primary)
    bool lost = false;          // no rollup within heartbeat_timeout_ms
    Json rollup;                // last accepted per-job rollup
  };

  void accept_loop();
  void tick_loop();
  void district_loop();  // district -> root rollup sender
  void handle_conn(int fd);
  void handle_http(int fd);
  // `raw` (when non-null) lets the quorum path hand back the prebuilt
  // shared response bytes instead of a Json tree the caller would re-dump
  // per connection; when *raw is set the returned Json is meaningless.
  Json handle_request(const Json& req, int64_t deadline_ms,
                      std::shared_ptr<const std::string>* raw = nullptr);
  Json quorum_rpc(const Json& req, int64_t deadline_ms,
                  std::shared_ptr<const std::string>* raw = nullptr);
  std::string render_status_html();
  std::string render_metrics();
  Json status_json();

  // Job-island resolution: creates the island on first use (seeded from the
  // durable snapshot so quorum ids stay monotone across warm restarts).
  JobState& job_state(const std::string& job);
  std::vector<JobState*> all_jobs();

  // Runs one quorum evaluation for ONE job with js.mu held by the caller;
  // broadcasts (and notifies js.cv) when a quorum forms.
  void job_tick_locked(JobState& js, int64_t now);
  // O(1) gate: can a quorum POSSIBLY form for this job right now? Only a
  // pass pays the full quorum_compute; a miss defers to the periodic tick.
  bool quorum_gate_locked(const JobState& js) const;
  // Join/implicit-heartbeat bookkeeping shared by register + re-register,
  // maintaining the gate counters (js.mu held).
  void register_participant_locked(JobState& js, const QuorumMember& me);

  // All fleet_* helpers run with js.mu held by the caller.
  void fleet_note_heartbeat(JobState& js, const std::string& replica_id,
                            const Json& req, int64_t now);
  void fleet_scan_locked(JobState& js, int64_t now);  // time-based rules
  void fleet_set_flag(JobState& js, const std::string& replica_id,
                      FleetEntry& e, const std::string& kind, int64_t now,
                      Json detail);
  // Record one failure signal in the job's signal ring (js.mu held). The
  // caller decides whether to follow up with an evidence-driven
  // job_tick_locked; this only records + stamps the fleet row.
  void signal_note_locked(JobState& js, const std::string& source,
                          const std::string& replica_id,
                          const std::string& site, Json detail, int64_t now);
  // Evidence-driven hb-lapse eviction (js.mu held): drop `replica_id` from
  // the quorum tables with leave-style gate fixups but NO tombstone (a
  // relaunch rejoins normally) and keep the fleet row as forensics. What
  // it erased goes on record (js.evicted) with the scan's numbers.
  void evidence_evict_locked(JobState& js, const std::string& replica_id,
                             int64_t now, int64_t seq, int64_t open_gap_ms,
                             int64_t budget_ms);
  // The first frame from an evicted id (js.mu held): notes how long it was
  // out and by what it came back, prints the re-admission, and wakes its
  // parked quorum request. The heartbeat handler closes the gap.
  void readmit_locked(JobState& js, const std::string& replica_id,
                      const char* via, int64_t now);
  void fleet_clear_flag(JobState& js, FleetEntry& e, const std::string& kind);
  void fleet_erase(JobState& js, const std::string& replica_id);
  void fleet_agg_remove(JobState& js, const FleetEntry& e);
  void fleet_agg_insert(JobState& js, const FleetEntry& e);
  int64_t fleet_jitter_budget_ms(const FleetEntry& e) const;
  Json fleet_summary_locked(JobState& js, int64_t now);  // status.json slice
  Json fleet_agg_locked(JobState& js, int64_t now);      // O(1)-ish agg dict
  Json hist_json() const;  // hot-path histograms for status

  // Per-job cached snapshot; empty job = the composite view (the default
  // job's payload extended with the cross-job summary + district table, so
  // pre-namespace consumers keep their top-level schema).
  std::shared_ptr<const FleetSnapshot> fleet_snapshot(const std::string& job,
                                                      int64_t now);

  // ---- federation (root side) ----
  Json district_note(const Json& req);     // absorb one rollup frame
  void district_scan(int64_t now);         // time-based district-loss rule
  Json districts_json(int64_t now);

  std::mutex jobs_mu_;  // guards the jobs_ map only (lookup/insert); job
                        // islands are never erased, so JobState* stay valid
  std::map<std::string, JobState> jobs_;

  std::mutex districts_mu_;
  std::map<std::string, DistrictEntry> districts_;
  int64_t district_losses_ = 0;  // districts that went silent (cumulative)

  // Hot-path latency histograms (lock-free, exported on /metrics and
  // status.json["hist"]).
  LatencyHist hist_heartbeat_;   // heartbeat RPC branch incl. lock wait
  LatencyHist hist_quorum_;      // quorum_compute inside tick
  LatencyHist hist_anomaly_;     // digest fold + anomaly rules per heartbeat
  LatencyHist hist_http_;        // whole HTTP request service
  LatencyHist hist_snapshot_;    // fleet snapshot rebuild (copy+build+dump)

  int64_t export_max_replicas_ = 64;  // TORCHFT_EXPORT_MAX_REPLICAS

  // SLO burn-rate knobs (TORCHFT_LH_SLO_*): goodput target, burn-rate
  // threshold that trips a slo_burn event, and the minimum accounted
  // seconds before the evaluator arms (startup/compile grace).
  double slo_goodput_ = 0.95;  // TORCHFT_LH_SLO_GOODPUT
  double slo_burn_ = 2.0;      // TORCHFT_LH_SLO_BURN
  double slo_min_s_ = 30.0;    // TORCHFT_LH_SLO_MIN_S

  std::string bind_host_;
  int port_;
  LighthouseOpts opts_;

  // ---- HA / fencing state (instance-global: there is ONE epoch owner per
  // lighthouse identity, shared by every job it serves) ----
  // Fencing epoch this instance stamps on quorums while active. Restored
  // from the durable snapshot on warm restart; bumped past observed_epoch_
  // on standby takeover. 0 only before a fresh active boot assigns 1.
  std::atomic<int64_t> epoch_{0};
  // Max epoch seen in manager heartbeats — the fleet's view of the current
  // owner. A standby uses it to fence its takeover epoch; an active
  // instance that observes a higher value has been superseded and demotes.
  std::atomic<int64_t> observed_epoch_{0};
  std::atomic<bool> active_{true};  // false = standby: absorb heartbeats only
  std::atomic<int64_t> takeovers_{0};   // standby -> active transitions
  std::atomic<int64_t> demotions_{0};   // active -> standby (fenced)
  // Serializes role transitions + durable saves; ordered strictly inside any
  // job mu (job mu -> persist_mu_, never the reverse).
  std::mutex persist_mu_;
  int64_t dur_quorum_id_ = 0;  // max quorum_id across jobs (persist_mu_)
  int64_t dur_gen_ = 0;        // max quorum_gen across jobs (persist_mu_)
  int64_t restored_quorum_id_ = 0;  // seeds for job islands created later
  int64_t restored_gen_ = 0;
  // Fold one job's freshly bumped ids into the durable maxima and fsync the
  // snapshot BEFORE the quorum publishes (ids stay monotone across crashes).
  void persist(int64_t job_qid, int64_t job_gen);
  void persist_locked(int64_t job_qid, int64_t job_gen);  // persist_mu_ held

  int listen_fd_ = -1;
  std::atomic<bool> running_{false};
  std::thread accept_thread_;
  std::thread tick_thread_;
  std::thread district_thread_;
  ConnTracker conns_;
};

}  // namespace tft
