#include "lighthouse.hpp"

#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstdarg>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <sstream>
#include <vector>

#include "chaos.hpp"
#include "net.hpp"

namespace tft {

namespace {
// Every line the lighthouse prints starts with the wall clock in
// milliseconds: the clock of ``ts_ms`` in its signal and anomaly rings and
// (times 1000) of ``ts`` in a manager's journal, so a line can be set
// beside what a replica group wrote down for the same instant. One write a
// line, so lines of concurrent handlers do not interleave.
__attribute__((format(printf, 1, 2))) void lh_log(const char* fmt, ...) {
  char buf[2048];
  int n = snprintf(buf, sizeof(buf), "%lld ", static_cast<long long>(now_ms()));
  va_list ap;
  va_start(ap, fmt);
  int m = vsnprintf(buf + n, sizeof(buf) - n, fmt, ap);
  va_end(ap);
  if (m < 0) return;
  size_t len = std::min(sizeof(buf) - 1, static_cast<size_t>(n + m));
  if (len > 0 && buf[len - 1] != '\n') buf[len - 1] = '\n';  // truncated
  fwrite(buf, 1, len, stderr);
}

// Wire back-compat: pre-namespace clients send no "job" field; an absent or
// empty value maps to the default namespace on every frame type.
std::string job_of(const Json& req) {
  std::string j = req.get("job").as_str();
  return j.empty() ? "default" : j;
}

// Closed failure-evidence source enum — positionally mirrors
// telemetry.SIGNAL_SOURCES on the Python side (lint rule signal-sources).
const char* const kSignalSourceNames[] = {
    "hb_lapse",       "lease_expiry", "digest_anomaly",
    "rpc_error",      "native_abort", "proc_death",
};

bool known_signal_source(const std::string& s) {
  for (const char* n : kSignalSourceNames)
    if (s == n) return true;
  return false;
}

// Closed badput classification — positionally mirrors telemetry.BADPUT_KINDS on
// the Python side (lint rule badput-kinds). The digest's "acct" array is
// indexed by this order; index 1 ("compute") is the goodput numerator.
const char* const kBadputKindNames[] = {
    "init_compile",   "compute",        "exposed_comm",
    "quorum_wait",    "heal",           "discarded_step",
    "replay_catchup", "straggler_idle", "drain",
    "down",
};
static_assert(sizeof(kBadputKindNames) / sizeof(kBadputKindNames[0]) ==
                  static_cast<size_t>(kNumBadputKinds),
              "kBadputKindNames must match kNumBadputKinds");
constexpr int kBadputComputeIdx = 1;

// Hard failure evidence (same set the trainer's _EvidenceWatcher acts on):
// these rise edges count as faults for MTBF and open an ETTR episode.
bool hard_signal_source(const std::string& s) {
  return s == "hb_lapse" || s == "proc_death" || s == "native_abort";
}

// A digest's acct vector, when complete: pre-namespace digests (or ones
// from a client older than the classification) simply don't contribute.
bool digest_acct(const Json& digest, double out[kNumBadputKinds]) {
  const Json& a = digest.get("acct");
  if (!a.is_array() || a.arr.size() < static_cast<size_t>(kNumBadputKinds))
    return false;
  for (int i = 0; i < kNumBadputKinds; i++)
    out[i] = a.arr[i].as_double(0.0);
  return true;
}
}  // namespace

Lighthouse::Lighthouse(const std::string& bind_host, int port,
                       LighthouseOpts opts)
    : bind_host_(bind_host), port_(port), opts_(opts) {
  // Shared with tools/obs_export.py (same knob, same default): above this
  // many replicas, per-replica /metrics series collapse to aggregates +
  // anomalous rows only, so a 1024-replica scrape stays bounded.
  const char* em = std::getenv("TORCHFT_EXPORT_MAX_REPLICAS");
  if (em != nullptr && *em != '\0') export_max_replicas_ = std::atoll(em);
  if (export_max_replicas_ < 0) export_max_replicas_ = 0;
  // SLO burn-rate knobs. A target >= 1.0 disarms the evaluator (the burn
  // denominator would be <= 0 — there is no error budget to spend).
  const char* sg = std::getenv("TORCHFT_LH_SLO_GOODPUT");
  if (sg != nullptr && *sg != '\0') slo_goodput_ = std::atof(sg);
  const char* sb = std::getenv("TORCHFT_LH_SLO_BURN");
  if (sb != nullptr && *sb != '\0') slo_burn_ = std::atof(sb);
  const char* sm = std::getenv("TORCHFT_LH_SLO_MIN_S");
  if (sm != nullptr && *sm != '\0') slo_min_s_ = std::atof(sm);
}

Lighthouse::~Lighthouse() { stop(); }

// Reserve this much generation headroom on every durable save: generations
// bump on every broadcast but are only persisted on (rare) quorum_id/epoch
// changes, so a reload must jump past anything possibly handed out since
// the last fsync to keep (epoch, generation) strictly monotone.
static constexpr int64_t kGenReserve = 1 << 20;

void Lighthouse::persist_locked(int64_t job_qid, int64_t job_gen) {
  if (opts_.state_dir.empty()) return;
  // The durable snapshot stores the MAX ids across every job island: a warm
  // restart (or takeover) must resume each job's numbering strictly above
  // anything any job ever published, and a single fsync'd file is the
  // cheapest shape that guarantees it.
  if (job_qid > dur_quorum_id_) dur_quorum_id_ = job_qid;
  if (job_gen > dur_gen_) dur_gen_ = job_gen;
  LighthouseDurable d;
  d.epoch = epoch_.load();
  d.quorum_id = dur_quorum_id_;
  d.generation = dur_gen_ + kGenReserve;
  if (!lh_state_save(opts_.state_dir, d)) {
    lh_log("[lighthouse] WARNING: failed to persist state to %s\n",
           opts_.state_dir.c_str());
  }
}

void Lighthouse::persist(int64_t job_qid, int64_t job_gen) {
  std::lock_guard<std::mutex> lk(persist_mu_);
  persist_locked(job_qid, job_gen);
}

Lighthouse::JobState& Lighthouse::job_state(const std::string& job) {
  std::lock_guard<std::mutex> lk(jobs_mu_);
  auto it = jobs_.find(job);
  if (it == jobs_.end()) {
    it = jobs_.try_emplace(job).first;
    JobState& js = it->second;
    js.name = job;
    // Seed from the restored durable maxima so a job island created after a
    // warm restart (or a job first seen post-restart) continues its quorum
    // numbering monotonically. restored_* are written once in start()
    // before any thread runs, so the unlocked read is safe.
    js.state.quorum_id = restored_quorum_id_;
    js.quorum_gen = restored_gen_;
  }
  return it->second;
}

std::vector<Lighthouse::JobState*> Lighthouse::all_jobs() {
  std::lock_guard<std::mutex> lk(jobs_mu_);
  std::vector<JobState*> out;
  out.reserve(jobs_.size());
  // std::map nodes are stable and islands are never erased, so the pointers
  // stay valid after jobs_mu_ is dropped.
  for (auto& kv : jobs_) out.push_back(&kv.second);
  return out;
}

bool Lighthouse::start() {
  listen_fd_ = tcp_listen(bind_host_, port_);
  if (listen_fd_ < 0) return false;
  port_ = bound_port(listen_fd_);
  {
    std::lock_guard<std::mutex> lk(persist_mu_);
    active_ = !opts_.standby;
    LighthouseDurable d;
    if (!opts_.state_dir.empty() && lh_state_load(opts_.state_dir, &d)) {
      // Warm restart: resume the persisted reign — same epoch (we may still
      // be the rightful owner), quorum ids continue strictly monotone, and
      // generations jump past the reserved headroom. Participant/fleet
      // tables rebuild from the live heartbeat stream.
      epoch_ = d.epoch;
      restored_quorum_id_ = dur_quorum_id_ = d.quorum_id;
      restored_gen_ = dur_gen_ = d.generation;
      lh_log(
          "[lighthouse] warm restart from %s: epoch=%lld quorum_id=%lld "
          "gen=%lld%s\n",
          opts_.state_dir.c_str(), static_cast<long long>(epoch_.load()),
          static_cast<long long>(restored_quorum_id_),
          static_cast<long long>(restored_gen_),
          active_ ? "" : " (standby)");
    }
    if (active_ && epoch_ == 0) epoch_ = 1;  // fresh active boot
    if (active_) persist_locked(dur_quorum_id_, dur_gen_);
  }
  // The default namespace island always exists (pre-namespace clients and
  // the composite /fleet.json land there).
  job_state("default");
  running_ = true;
  accept_thread_ = std::thread([this] { accept_loop(); });
  tick_thread_ = std::thread([this] { tick_loop(); });
  // Federation sender: a lighthouse configured with a district name and a
  // root address reports per-job rollups upward.
  if (!opts_.root_addr.empty() && !opts_.district.empty())
    district_thread_ = std::thread([this] { district_loop(); });
  return true;
}

void Lighthouse::stop() {
  if (!running_.exchange(false)) return;
  for (JobState* js : all_jobs()) {
    std::lock_guard<std::mutex> lk(js->mu);
    js->cv.notify_all();
  }
  conns_.shutdown_all();  // interrupt in-flight frames so handlers drain fast
  // shutdown() unblocks the accept loop; close() + reset must wait until
  // the thread is joined — accept_loop reads listen_fd_ until then.
  if (listen_fd_ >= 0) shutdown(listen_fd_, SHUT_RDWR);
  if (accept_thread_.joinable()) accept_thread_.join();
  if (tick_thread_.joinable()) tick_thread_.join();
  if (district_thread_.joinable()) district_thread_.join();
  if (listen_fd_ >= 0) {
    close(listen_fd_);
    listen_fd_ = -1;
  }
  conns_.wait_idle(10000);
}

std::string Lighthouse::address() const {
  return "127.0.0.1:" + std::to_string(port_);
}

void Lighthouse::accept_loop() {
  while (running_) {
    int fd = tcp_accept(listen_fd_, 200);
    if (fd < 0) continue;
    if (!conns_.add(fd)) {
      close(fd);
      continue;
    }
    std::thread([this, fd] {
      handle_conn(fd);
      conns_.remove(fd);
    }).detach();
  }
}

void Lighthouse::tick_loop() {
  while (running_) {
    tick();
    sleep_ms(opts_.quorum_tick_ms);
  }
}

void Lighthouse::tick() {
  // The periodic tick is the time-driven fallback of the incremental gate:
  // it catches everything only the clock can decide (heartbeat expiry,
  // join-timeout straggler cutoff, open heartbeat gaps) plus any formation
  // a conservative gate miss deferred. Jobs tick independently under their
  // own locks — one job's slow scan never blocks another's heartbeats.
  int64_t now = now_ms();
  for (JobState* js : all_jobs()) {
    std::lock_guard<std::mutex> lk(js->mu);
    fleet_scan_locked(*js, now);
    job_tick_locked(*js, now);
  }
  district_scan(now);
}

void Lighthouse::district_loop() {
  // District -> root rollup sender, piggybacking on the heartbeat frame
  // type. Only the ACTIVE instance reports: a standby stays silent, and
  // after a takeover the new primary reports with its higher epoch — the
  // root observes the epoch advance as a district failover while the fenced
  // old primary's late rollups are dropped by the per-district fence.
  int64_t interval = opts_.heartbeat_timeout_ms / 4;
  if (interval < 250) interval = 250;
  if (interval > 1000) interval = 1000;
  std::string host;
  int port = 0;
  const bool addr_ok = split_host_port(opts_.root_addr, &host, &port);
  if (!addr_ok) {
    lh_log("[lighthouse] bad root address '%s'; federation off\n",
           opts_.root_addr.c_str());
    return;
  }
  int fd = -1;
  while (running_) {
    if (active_.load()) {
      Json jobs = Json::object();
      int64_t now = now_ms();
      for (JobState* js : all_jobs()) {
        std::lock_guard<std::mutex> lk(js->mu);
        jobs[js->name] = fleet_summary_locked(*js, now);
      }
      Json rollup = Json::object();
      rollup["jobs"] = jobs;
      Json req = Json::object();
      req["type"] = Json::of(std::string("heartbeat"));
      req["replica_id"] = Json::of("district:" + opts_.district);
      req["district"] = Json::of(opts_.district);
      req["epoch"] = Json::of(epoch_.load());
      req["district_rollup"] = rollup;
      if (fd < 0) fd = tcp_connect(host, port, 2000);
      if (fd >= 0) {
        Json resp;
        if (!call_json(fd, req, &resp, 5000)) {
          close(fd);
          fd = -1;  // reconnect next round
        }
      }
    }
    sleep_ms(interval);
  }
  if (fd >= 0) close(fd);
}

void Lighthouse::handle_conn(int fd) {
  // Sniff: framed requests begin with a 4-byte big-endian length whose first
  // byte is 0 for any sane control message; HTTP begins with ASCII letters.
  char peek[4] = {0};
  int n = peek_bytes(fd, peek, 4, 30000);
  if (n <= 0) {
    close(fd);
    return;
  }
  if (n >= 3 && (memcmp(peek, "GET", 3) == 0 || memcmp(peek, "POS", 3) == 0 ||
                 memcmp(peek, "HEA", 3) == 0)) {
    handle_http(fd);
    close(fd);
    return;
  }
  // Persistent framed connection: serve requests until the peer closes.
  while (running_) {
    std::string payload;
    if (!recv_frame(fd, &payload, 3600 * 1000)) break;
    Json req;
    std::string err;
    Json resp;
    if (!Json::parse(payload, &req, &err)) {
      resp["ok"] = Json::of(false);
      resp["error"] = Json::of("bad json: " + err);
    } else {
      // Server-side chaos (rpc_delay sleeps; rpc_drop/reset tear the
      // connection without replying — the client sees a torn RPC and must
      // absorb it through its retry policy).
      if (!chaos::server_rpc(req.get("type").as_str())) break;
      int64_t timeout = req.get("timeout_ms").as_int(60000);
      std::shared_ptr<const std::string> raw;
      resp = handle_request(req, now_ms() + timeout, &raw);
      if (raw) {
        // Prebuilt shared quorum broadcast: send the bytes as-is. (No
        // trace echo on this path — the manager's quorum client reads only
        // ok/quorum and stamps its own trace on the step events.)
        if (!send_frame(fd, *raw, 30000)) break;
        continue;
      }
      // Echo the caller's trace id so both planes of a step share one id
      // (the Python Manager mints it; responses carry it for correlation).
      if (req.has("trace_id")) resp["trace_id"] = req.get("trace_id");
    }
    if (!send_frame(fd, resp.dump(), 30000)) break;
  }
  close(fd);
}

Json Lighthouse::handle_request(const Json& req, int64_t deadline_ms,
                                std::shared_ptr<const std::string>* raw) {
  const std::string type = req.get("type").as_str();
  Json resp = Json::object();
  if (type == "heartbeat") {
    // District rollups ride the heartbeat frame type (piggyback channel)
    // but are control-plane metadata, not replica liveness: divert them
    // BEFORE the job tables so a district never appears as a fleet row or
    // quorum participant.
    if (req.has("district_rollup")) return district_note(req);
    // Timed from before the lock: the histogram must show contention (the
    // wait behind a /fleet.json rebuild was exactly the bug), not just the
    // work done once inside.
    int64_t hb_t0 = now_us_steady();
    JobState& js = job_state(job_of(req));
    {
      std::lock_guard<std::mutex> lk(js.mu);
      const std::string replica_id = req.get("replica_id").as_str();
      // Managers stamp the max quorum epoch they have accepted into every
      // heartbeat: this is how a standby (or a resurrected stale primary)
      // learns the fleet's current owner without any lighthouse-to-
      // lighthouse channel. An active instance seeing a higher epoch has
      // been superseded by a takeover — it fences itself out (demotes to
      // standby) instead of competing for the fleet.
      int64_t hb_epoch = req.get("epoch").as_int(0);
      int64_t seen = observed_epoch_.load();
      while (hb_epoch > seen &&
             !observed_epoch_.compare_exchange_weak(seen, hb_epoch)) {
      }
      // Max accepted quorum_id rides the same frames, tracked PER JOB: a
      // standby resumes each job's numbering above what that job's fleet
      // accepted (a global max would inflate job B's ids from job A's).
      int64_t hb_qid = req.get("quorum_id").as_int(0);
      if (hb_qid > js.observed_quorum_id) js.observed_quorum_id = hb_qid;
      if (active_.load() && observed_epoch_.load() > epoch_.load()) {
        std::lock_guard<std::mutex> plk(persist_mu_);
        if (active_.load() && observed_epoch_.load() > epoch_.load()) {
          active_ = false;
          demotions_ += 1;
          js.last_reason = "fenced: observed epoch " +
                           std::to_string(observed_epoch_.load()) +
                           " > own epoch " + std::to_string(epoch_.load());
          lh_log(
              "[lighthouse] demoting to standby: fleet is on epoch %lld, "
              "ours is %lld (stale primary fenced out)\n",
              static_cast<long long>(observed_epoch_.load()),
              static_cast<long long>(epoch_.load()));
        }
      }
      // A drained replica's manager may have one heartbeat in flight when
      // its leave lands; the tombstone keeps it from resurrecting the entry
      // (which would stall the survivors' next quorum until heartbeat
      // expiry).
      if (!js.state.left.count(replica_id)) {
        int64_t now = now_ms();
        // Gate counter: a replica heartbeating but not (yet) registered
        // holds the "all healthy joined" condition open.
        // First heartbeat from an id the evidence plane evicted: close
        // its record against the fleet row as it stood (the closed gap),
        // and wake its parked quorum request so that it registers again.
        readmit_locked(js, replica_id, "heartbeat", now);
        auto evd = js.evicted.find(replica_id);
        if (evd != js.evicted.end() && evd->second.gap_ms < 0) {
          // The gap that got it evicted, closed: arrival to arrival.
          auto fe = js.fleet.find(replica_id);
          evd->second.gap_ms =
              fe != js.fleet.end() ? now - fe->second.last_hb_ms : -1;
          lh_log(
              "[lighthouse] heartbeat of evicted %s back (job %s): signal "
              "#%lld gap_ms=%lld budget_ms=%lld out_ms=%lld via=%s\n",
              replica_id.c_str(), js.name.c_str(),
              static_cast<long long>(evd->second.seq),
              static_cast<long long>(evd->second.gap_ms),
              static_cast<long long>(evd->second.budget_ms),
              static_cast<long long>(evd->second.out_ms),
              evd->second.via.c_str());
        }
        if (!js.state.heartbeats.count(replica_id) &&
            !js.state.participants.count(replica_id))
          js.hb_not_joined += 1;
        js.state.heartbeats[replica_id] = now;
        // Heartbeats carry the manager address so drain_all can reach a
        // replica that heartbeats but never registered a quorum.
        const std::string addr = req.get("address").as_str();
        if (!addr.empty()) js.state.heartbeat_addrs[replica_id] = addr;
        // Live fleet plane: fold the optional digest + declared cadence into
        // the fleet table and run the digest-driven anomaly rules. Old
        // clients send neither field; the row simply stays digest-less.
        fleet_note_heartbeat(js, replica_id, req, now);
      }
      // Failure-evidence ingest: manager-observed signals (rpc_error,
      // native_abort, proc_death, lease_expiry) piggyback on the heartbeat
      // frame. Old clients never send the key (wire back-compat); unknown
      // sources are dropped rather than poisoning the closed enum.
      if (req.has("signals") && req.get("signals").is_array()) {
        int64_t now = now_ms();
        bool ingested = false;
        for (const auto& sg : req.get("signals").arr) {
          const std::string src = sg.get("source").as_str();
          if (!known_signal_source(src)) continue;
          std::string subject = sg.get("replica_id").as_str();
          if (subject.empty()) subject = replica_id;
          std::string site = sg.get("site").as_str();
          if (site.empty()) site = "manager:" + replica_id;
          signal_note_locked(js, src, subject, site, sg.get("detail"), now);
          ingested = true;
        }
        // Evidence tick: fresh evidence re-evaluates the quorum NOW (the
        // periodic tick and vote-timeout landing stay as the fallback).
        if (ingested && opts_.evidence) job_tick_locked(js, now_ms());
      }
      // The ACK carries the job's signal cursor + last signal so every
      // manager's evidence_status view advances at heartbeat cadence with
      // zero extra RPCs. Old managers ignore both keys.
      resp["signal_seq"] = Json::of(js.signal_seq);
      if (!js.signals.empty()) resp["signal"] = js.signals.back();
      // A sender that says how far it has read gets every signal past
      // that (the newest eight): two groups evicted by one scan are two
      // signals, and ``signal`` alone shows the second.
      const int64_t cursor = req.get("signal_seq").as_int(-1);
      if (cursor >= 0 && cursor < js.signal_seq) {
        size_t first = js.signals.size();
        while (first > 0 && js.signals.size() - first < 8 &&
               js.signals[first - 1].get("seq").as_int(0) > cursor)
          first -= 1;
        Json arr = Json::array();
        for (size_t k = first; k < js.signals.size(); k++)
          arr.push(js.signals[k]);
        resp["signals"] = std::move(arr);
      }
      // The group that was dropped learns that it was, with the
      // lighthouse's numbers for the interval: once, in the first ack
      // after it was heard from again. Old managers ignore the key.
      auto ev = js.evicted.find(replica_id);
      if (ev != js.evicted.end() && ev->second.gap_ms >= 0) {
        Json e = Json::object();
        e["seq"] = Json::of(ev->second.seq);
        e["ts_ms"] = Json::of(ev->second.at_ms);
        e["gap_ms"] = Json::of(ev->second.gap_ms);
        e["open_gap_ms"] = Json::of(ev->second.open_gap_ms);
        e["budget_ms"] = Json::of(ev->second.budget_ms);
        e["out_ms"] = Json::of(ev->second.out_ms);
        e["erased"] = Json::of(ev->second.erased);
        e["via"] = Json::of(ev->second.via);
        resp["evicted"] = std::move(e);
        js.evicted.erase(ev);
      }
    }
    resp["ok"] = Json::of(true);
    hist_heartbeat_.observe_us(now_us_steady() - hb_t0);
    return resp;
  }
  if (type == "fleet") {
    // Served from the generation-tagged cached snapshot — the framed twin
    // of GET /fleet.json no longer rebuilds O(N) JSON under the job lock.
    // No/empty job = the composite (default + cross-job summary) view.
    auto snap = fleet_snapshot(req.get("job").as_str(), now_ms());
    resp["ok"] = Json::of(true);
    resp["fleet"] = snap->json;
    return resp;
  }
  if (type == "leave") {
    // Graceful drain (no reference analog; the reference only has Kill →
    // exit(1), so survivors always pay the heartbeat-expiry stall). Removing
    // the member's heartbeat + registration lets the very next evaluation
    // form the shrunken quorum: ~quorum_tick_ms of stall instead of
    // ~heartbeat_timeout_ms.
    const std::string replica_id = req.get("replica_id").as_str();
    const std::string reason = req.get("reason").as_str();
    JobState& js = job_state(job_of(req));
    {
      std::lock_guard<std::mutex> lk(js.mu);
      bool was_part = js.state.participants.count(replica_id) > 0;
      bool was_hb = js.state.heartbeats.count(replica_id) > 0;
      // A leave on the DEAD replica's behalf (the manager binary's
      // parent-death watchdog) is failure evidence, not a planned drain:
      // signal proc_death so peers wedged mid-collective with the corpse
      // abort at heartbeat speed instead of their collective timeout.
      if ((was_part || was_hb) && reason == "trainer died") {
        Json d = Json::object();
        d["reason"] = Json::of(reason);
        signal_note_locked(js, "proc_death", replica_id, "lighthouse.leave",
                           std::move(d), now_ms());
      }
      js.state.heartbeats.erase(replica_id);
      js.state.heartbeat_addrs.erase(replica_id);
      js.state.participants.erase(replica_id);
      js.state.left.insert(replica_id);
      if (was_hb && !was_part) js.hb_not_joined -= 1;
      if (was_part && js.prev_ids.count(replica_id)) js.prev_present -= 1;
      // A drained replica must not linger in the fleet table looking like
      // a straggler whose heartbeats stopped.
      fleet_erase(js, replica_id);
      // Proactive evaluation for THIS job only: survivors already blocked
      // in a quorum RPC see the shrunken membership now, not at the next
      // timer tick — and sibling jobs are untouched.
      job_tick_locked(js, now_ms());
    }
    lh_log("[lighthouse] replica %s left gracefully (job %s)\n",
           replica_id.c_str(), js.name.c_str());
    resp["ok"] = Json::of(true);
    return resp;
  }
  if (type == "quorum") {
    return quorum_rpc(req, deadline_ms, raw);
  }
  if (type == "status") {
    resp["ok"] = Json::of(true);
    resp["status"] = status_json();
    return resp;
  }
  if (type == "kill" || type == "drain") {
    // Forward to the member's manager address (kill: lighthouse.rs:454-479;
    // drain: no reference analog — asks the trainer to leave gracefully at
    // its next step boundary instead of exit(1)). Lookup is scoped to the
    // frame's job namespace.
    std::string replica_id = req.get("replica_id").as_str();
    JobState& js = job_state(job_of(req));
    std::string addr;
    {
      std::lock_guard<std::mutex> lk(js.mu);
      if (js.state.prev_quorum) {
        for (const auto& m : js.state.prev_quorum->participants)
          if (m.replica_id == replica_id) addr = m.address;
      }
      for (const auto& kv : js.state.participants)
        if (kv.first == replica_id) addr = kv.second.first.address;
    }
    if (addr.empty()) {
      resp["ok"] = Json::of(false);
      resp["error"] = Json::of("unknown replica " + replica_id);
      return resp;
    }
    Json fwd = Json::object();
    if (type == "kill") {
      fwd["type"] = Json::of("kill");
      fwd["msg"] = Json::of("killed via lighthouse");
    } else {
      fwd["type"] = Json::of("request_drain");
    }
    Json ignored;
    bool ok = call_json_addr(addr, fwd, &ignored, 5000);
    // A kill victim exits without replying; treat connection-level failure
    // after send as success-ish.
    resp["ok"] = Json::of(true);
    resp["sent"] = Json::of(ok);
    return resp;
  }
  if (type == "drain_all") {
    // Operator-initiated FULL drain: forward request_drain to every
    // registered member's manager. Each trainer drains at its own safe
    // boundary (with --durable-dir that includes a final durable
    // snapshot), so a whole job can be stopped cleanly and relaunched
    // later — the operator-triggered twin of a whole-pod preemption.
    // A frame with a "job" drains that namespace only; without one it
    // drains EVERY namespace (the pre-namespace whole-instance semantics).
    // Union of the last formed quorum and any currently-registering
    // members per job (registration empties into prev_quorum when a quorum
    // forms, and a drain must reach members in either place). Live
    // registrations overwrite stale prev_quorum addresses; tombstoned
    // (already-left) members are excluded; heartbeat-only replicas are
    // reached through their heartbeat-carried addresses.
    std::vector<JobState*> targets;
    if (req.has("job") && !req.get("job").as_str().empty()) {
      targets.push_back(&job_state(job_of(req)));
    } else {
      targets = all_jobs();
    }
    std::map<std::string, std::string> members;
    for (JobState* jsp : targets) {
      std::lock_guard<std::mutex> lk(jsp->mu);
      if (jsp->state.prev_quorum) {
        for (const auto& m : jsp->state.prev_quorum->participants)
          if (!jsp->state.left.count(m.replica_id))
            members[m.replica_id] = m.address;
      }
      for (const auto& kv : jsp->state.participants)
        members[kv.first] = kv.second.first.address;
      for (const auto& kv : jsp->state.heartbeat_addrs)
        if (!members.count(kv.first) && !jsp->state.left.count(kv.first))
          members[kv.first] = kv.second;
    }
    Json sent = Json::object();
    int n_sent = 0;
    for (const auto& m : members) {
      Json fwd = Json::object();
      fwd["type"] = Json::of("request_drain");
      Json ignored;
      // Bound each forward by the request's remaining deadline (capped
      // at 5 s): a job with several unreachable members (stale
      // prev_quorum addresses after crashes — exactly when an operator
      // reaches for drain ALL) must still return the per-member send
      // report to the caller instead of timing out the whole RPC.
      int64_t remaining = deadline_ms - now_ms();
      if (remaining < 200) {
        sent[m.first] = Json::of(false);
        continue;
      }
      int64_t budget = remaining < 5000 ? remaining : 5000;
      bool ok = call_json_addr(m.second, fwd, &ignored,
                               static_cast<int>(budget));
      sent[m.first] = Json::of(ok);
      if (ok) n_sent++;
    }
    resp["ok"] = Json::of(true);
    resp["sent"] = sent;
    resp["n_sent"] = Json::of(static_cast<int64_t>(n_sent));
    resp["n_members"] = Json::of(static_cast<int64_t>(members.size()));
    return resp;
  }
  resp["ok"] = Json::of(false);
  resp["error"] = Json::of("unknown request type '" + type + "'");
  return resp;
}

void Lighthouse::register_participant_locked(JobState& js,
                                             const QuorumMember& me) {
  // Joining is an implicit heartbeat (lighthouse.rs:502-512) and clears any
  // graceful-leave tombstone (a drained replica relaunching to rejoin).
  int64_t now = now_ms();
  js.state.left.erase(me.replica_id);
  const bool was_part = js.state.participants.count(me.replica_id) > 0;
  const bool was_hb = js.state.heartbeats.count(me.replica_id) > 0;
  js.state.heartbeats[me.replica_id] = now;
  js.state.participants[me.replica_id] = {me, now};
  if (!was_part) {
    if (was_hb) js.hb_not_joined -= 1;
    if (js.prev_ids.count(me.replica_id)) js.prev_present += 1;
  }
}

bool Lighthouse::quorum_gate_locked(const JobState& js) const {
  // O(1) decision: can a quorum POSSIBLY form right now? The gate is
  // deliberately one-sided — a pass pays the full quorum_compute (which
  // remains the single source of truth and can still say no); a miss defers
  // to the periodic tick. A counter bug can therefore only delay a
  // formation by one tick, never form a wrong quorum.
  if (!active_.load()) return false;
  if (js.state.participants.empty()) return false;
  // Fast-quorum certain: every member of the previous quorum has
  // re-registered (their registration doubled as a fresh heartbeat).
  if (js.state.prev_quorum && !js.prev_ids.empty() &&
      js.prev_present == static_cast<int64_t>(js.prev_ids.size()))
    return true;
  // Everyone heartbeating has registered and the floor is met: no straggler
  // the join-timeout wait would hold the door for.
  if (static_cast<int64_t>(js.state.participants.size()) >=
          opts_.min_replicas &&
      js.hb_not_joined == 0)
    return true;
  return false;
}

void Lighthouse::job_tick_locked(JobState& js, int64_t now) {
  // A standby absorbs heartbeats (keeping fleet/participant tables warm)
  // but must not form quorums — there is exactly one epoch owner, and it is
  // not us until a manager fails over and its quorum request promotes us.
  if (!active_.load()) {
    js.last_reason = "standby (not forming quorums)";
    return;
  }
  std::string reason;
  int64_t q_t0 = now_us_steady();
  auto members = quorum_compute(now, js.state, opts_, &reason);
  hist_quorum_.observe_us(now_us_steady() - q_t0);
  if (!members) {
    if (reason != js.last_reason && !js.state.participants.empty()) {
      lh_log("[lighthouse] no quorum (job %s): %s\n",
             js.name.c_str(), reason.c_str());
    }
    js.last_reason = reason;
    return;
  }
  // Bump quorum_id only when membership changed or a member reported commit
  // failures (lighthouse.rs:305-325) — a changed id forces process groups to
  // reconfigure, so we avoid it when the world is stable.
  bool bump = false;
  if (!js.state.prev_quorum) {
    bump = true;
  } else if (quorum_changed(js.state.prev_quorum->participants, *members)) {
    bump = true;
  } else {
    for (const auto& m : *members)
      if (m.commit_failures > 0) bump = true;
  }
  if (bump) {
    // Resume numbering above anything this job's fleet already accepted
    // (relevant on a takeover or a stateless warm restart).
    if (js.observed_quorum_id > js.state.quorum_id)
      js.state.quorum_id = js.observed_quorum_id;
    js.state.quorum_id += 1;
    // Fsync the new id BEFORE publishing the quorum: a crash between
    // publish and persist could otherwise let a warm restart re-issue an id
    // the fleet has already seen.
    persist(js.state.quorum_id, js.quorum_gen);
  }

  // Participant churn across quorum transitions (surfaced via status +
  // /metrics): a member present now but not in the previous quorum is a
  // join; one gone is a leave. Covers crash, kill, and graceful drain
  // uniformly at the granularity monitoring cares about.
  std::set<std::string> new_ids;
  for (const auto& m : *members) new_ids.insert(m.replica_id);
  {
    std::set<std::string> old_ids;
    if (js.state.prev_quorum)
      for (const auto& m : js.state.prev_quorum->participants)
        old_ids.insert(m.replica_id);
    for (const auto& id : new_ids)
      if (!old_ids.count(id)) js.joins_total += 1;
    for (const auto& id : old_ids)
      if (!new_ids.count(id)) js.leaves_total += 1;
  }

  Quorum q;
  q.quorum_id = js.state.quorum_id;
  q.participants = *members;
  q.created_ms = now;
  q.epoch = epoch_.load();
  q.generation = js.quorum_gen + 1;
  q.job = js.name;
  js.state.prev_quorum = q;
  js.state.participants.clear();  // next round starts fresh (lighthouse.rs:336)
  // Reset the gate counters for the next round: nobody from the new quorum
  // has re-registered yet, and with participants cleared every heartbeating
  // replica is momentarily unregistered.
  js.prev_ids = new_ids;
  js.prev_present = 0;
  js.hb_not_joined = static_cast<int64_t>(js.state.heartbeats.size());
  js.last_quorum = q;
  // Serialize the broadcast ONCE: every in-quorum waiter (and its
  // connection loop) sends these exact bytes, turning the O(N^2)
  // per-waiter to_json+dump fan-out into a single O(N) build.
  {
    Json bresp = Json::object();
    bresp["ok"] = Json::of(true);
    bresp["quorum"] = q.to_json();
    js.quorum_payload = std::make_shared<const std::string>(bresp.dump());
  }
  js.quorum_gen += 1;
  js.last_reason.clear();
  lh_log("[lighthouse] quorum %lld formed with %zu members (job %s)\n",
         static_cast<long long>(q.quorum_id), q.participants.size(),
         js.name.c_str());
  if (std::getenv("TORCHFT_LH_DEBUG") != nullptr) {
    std::string ids;
    for (const auto& m : q.participants) ids += m.replica_id + " ";
    lh_log("[lighthouse] +%lld formed gen=%lld job=%s members: %s\n",
           static_cast<long long>(now_ms() % 1000000),
           static_cast<long long>(js.quorum_gen), js.name.c_str(),
           ids.c_str());
  }
  js.cv.notify_all();
}

Json Lighthouse::quorum_rpc(const Json& req, int64_t deadline_ms,
                            std::shared_ptr<const std::string>* raw) {
  QuorumMember me = QuorumMember::from_json(req.get("requester"));
  Json resp = Json::object();
  if (me.replica_id.empty()) {
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of("quorum request missing requester.replica_id");
    return resp;
  }
  const bool debug = std::getenv("TORCHFT_LH_DEBUG") != nullptr;
  JobState& js = job_state(job_of(req));
  std::unique_lock<std::mutex> lk(js.mu);
  // Warm-standby takeover: managers only send quorum RPCs to their active
  // target, so a quorum request arriving at a standby means the fleet's
  // lease on the old primary lapsed and failover chose us. Claim the reign
  // with a strictly higher epoch than anything observed (fencing out the
  // old primary) and persist it before serving a single quorum.
  if (!active_.load()) {
    std::lock_guard<std::mutex> plk(persist_mu_);
    if (!active_.load()) {
      epoch_ = std::max(epoch_.load(), observed_epoch_.load()) + 1;
      // Resume this job's quorum numbering above anything its fleet
      // accepted from the old primary: each quorum_id must have exactly
      // one (epoch) owner.
      js.state.quorum_id =
          std::max(js.state.quorum_id, js.observed_quorum_id);
      active_ = true;
      takeovers_ += 1;
      persist_locked(js.state.quorum_id, js.quorum_gen);
      lh_log(
          "[lighthouse] standby takeover: now active with epoch %lld "
          "(first quorum request from %s, job %s)\n",
          static_cast<long long>(epoch_.load()), me.replica_id.c_str(),
          js.name.c_str());
    }
  }
  // Parked from here to every return (the guard dies before the lock).
  struct Parked {
    JobState& js;
    const std::string& id;
    Parked(JobState& j, const std::string& i) : js(j), id(i) {
      js.parked[id]++;
    }
    ~Parked() {
      if (--js.parked[id] <= 0) js.parked.erase(id);
    }
  } parked_guard(js, me.replica_id);
  readmit_locked(js, me.replica_id, "quorum request", now_ms());
  register_participant_locked(js, me);
  int64_t my_gen = js.quorum_gen;
  if (debug) {
    lh_log(
        "[lighthouse] +%lld register %s job=%s step=%lld gen=%lld "
        "pool=%zu\n",
        static_cast<long long>(now_ms() % 1000000), me.replica_id.c_str(),
        js.name.c_str(), static_cast<long long>(me.step),
        static_cast<long long>(my_gen), js.state.participants.size());
  }
  // Incremental quorum: the O(1) gate decides whether this registration
  // could complete a quorum; only then does the full quorum_compute run —
  // inline, still under the job lock, replacing the per-registration
  // unconditional full tick (the O(N^2) storm behind the 4 s formations at
  // N=1024). A gate miss is covered by the periodic tick.
  if (quorum_gate_locked(js)) job_tick_locked(js, now_ms());

  while (running_) {
    // Wait for a fresh quorum broadcast.
    while (running_ && js.quorum_gen == my_gen) {
      // Evicted on evidence while parked here, and heard from since (the
      // heartbeat that re-admitted the id woke us): the registration went
      // with the eviction and nobody else files it again before this
      // request times out, so file it. Within one generation only an
      // eviction or a leave erases a registration, and a leave leaves a
      // tombstone.
      if (!js.state.participants.count(me.replica_id) &&
          js.state.heartbeats.count(me.replica_id) &&
          !js.state.left.count(me.replica_id)) {
        register_participant_locked(js, me);
        lh_log("[lighthouse] re-registered the parked quorum request of %s "
               "(job %s)\n",
               me.replica_id.c_str(), js.name.c_str());
        if (quorum_gate_locked(js)) job_tick_locked(js, now_ms());
        continue;
      }
      if (js.cv.wait_until(lk, std::chrono::system_clock::time_point(
                                   std::chrono::milliseconds(deadline_ms))) ==
          std::cv_status::timeout) {
        if (now_ms() >= deadline_ms) {
          resp["ok"] = Json::of(false);
          resp["error"] = Json::of("timed out waiting for quorum");
          resp["timeout"] = Json::of(true);
          return resp;
        }
      }
    }
    if (!running_) break;
    my_gen = js.quorum_gen;
    if (js.last_quorum) {
      // prev_ids is exactly the broadcast quorum's member set (assigned
      // together with last_quorum at formation): O(log N) membership
      // instead of a per-waiter linear scan.
      if (js.prev_ids.count(me.replica_id)) {
        if (raw && js.quorum_payload) {
          *raw = js.quorum_payload;  // shared prebuilt bytes, no re-dump
          return resp;
        }
        resp["ok"] = Json::of(true);
        resp["quorum"] = js.last_quorum->to_json();
        return resp;
      }
      // Delivered quorum doesn't include us (we joined too late): rejoin and
      // wait for the next one (lighthouse.rs:523-544).
      register_participant_locked(js, me);
      if (quorum_gate_locked(js)) job_tick_locked(js, now_ms());
      if (js.quorum_gen != my_gen) continue;  // formed inline; re-check
    }
  }
  resp["ok"] = Json::of(false);
  resp["error"] = Json::of("lighthouse shutting down");
  return resp;
}

Json Lighthouse::status_json() {
  int64_t now = now_ms();
  Json s = Json::object();
  // Top-level keys keep the pre-namespace schema, reporting the DEFAULT
  // job's island (what old dashboards and tests read); the per-job map
  // below carries every namespace including default.
  {
    JobState& js = job_state("default");
    std::lock_guard<std::mutex> lk(js.mu);
    s["quorum_id"] = Json::of(js.state.quorum_id);
    s["quorum_generation"] = Json::of(js.quorum_gen);
    s["joins_total"] = Json::of(js.joins_total);
    s["leaves_total"] = Json::of(js.leaves_total);
    s["epoch"] = Json::of(epoch_.load());
    s["observed_epoch"] = Json::of(observed_epoch_.load());
    s["observed_quorum_id"] = Json::of(js.observed_quorum_id);
    s["role"] = Json::of(std::string(active_.load() ? "active" : "standby"));
    s["takeovers"] = Json::of(takeovers_.load());
    s["demotions"] = Json::of(demotions_.load());
    Json hb = Json::object();
    for (const auto& kv : js.state.heartbeats)
      hb[kv.first] = Json::of(now - kv.second);
    s["heartbeat_ages_ms"] = hb;
    Json parts = Json::array();
    for (const auto& kv : js.state.participants)
      parts.push(kv.second.first.to_json());
    s["participants"] = parts;
    s["prev_quorum"] =
        js.state.prev_quorum ? js.state.prev_quorum->to_json() : Json::null();
    Json left = Json::array();
    for (const auto& id : js.state.left) left.push(Json::of(id));
    s["left"] = left;
    s["reason"] = Json::of(js.last_reason);
    // Live-plane summary rides along so a status poller sees fleet health
    // without a second RPC; the full table stays on /fleet.json.
    s["fleet"] = fleet_summary_locked(js, now);
  }
  // Per-job sections: one summary per namespace island, gathered by
  // locking each island one at a time (never two job locks at once).
  Json jobs = Json::object();
  for (JobState* jsp : all_jobs()) {
    std::lock_guard<std::mutex> lk(jsp->mu);
    Json j = Json::object();
    j["quorum_id"] = Json::of(jsp->state.quorum_id);
    j["quorum_generation"] = Json::of(jsp->quorum_gen);
    j["participants"] =
        Json::of(static_cast<int64_t>(jsp->state.participants.size()));
    j["members"] = Json::of(
        jsp->state.prev_quorum
            ? static_cast<int64_t>(jsp->state.prev_quorum->participants.size())
            : int64_t{0});
    j["heartbeats"] =
        Json::of(static_cast<int64_t>(jsp->state.heartbeats.size()));
    j["joins_total"] = Json::of(jsp->joins_total);
    j["leaves_total"] = Json::of(jsp->leaves_total);
    j["reason"] = Json::of(jsp->last_reason);
    j["fleet"] = fleet_summary_locked(*jsp, now);
    jobs[jsp->name] = j;
  }
  s["jobs"] = jobs;
  s["districts"] = districts_json(now);
  // Hot-path latency histograms (p50/p95/p99 in microseconds, upper-bound
  // estimates from the log buckets — same semantics as telemetry
  // span_percentiles on the Python side).
  s["hist"] = hist_json();
  return s;
}

Json Lighthouse::hist_json() const {
  struct Named {
    const char* name;
    const LatencyHist* h;
  };
  const Named hists[] = {
      {"heartbeat", &hist_heartbeat_},   {"quorum_compute", &hist_quorum_},
      {"anomaly_eval", &hist_anomaly_},  {"http", &hist_http_},
      {"fleet_snapshot", &hist_snapshot_},
  };
  Json out = Json::object();
  for (const auto& nh : hists) {
    LatencyHist::Snap s = nh.h->snapshot();
    Json h = Json::object();
    h["count"] = Json::of(s.count);
    h["sum_us"] = Json::of(s.sum_us);
    h["p50_us"] = Json::of(LatencyHist::percentile_us(s, 0.50));
    h["p95_us"] = Json::of(LatencyHist::percentile_us(s, 0.95));
    h["p99_us"] = Json::of(LatencyHist::percentile_us(s, 0.99));
    out[nh.name] = h;
  }
  return out;
}

// ---------------------------------------------------------------------------
// Federation: root-side district table
// ---------------------------------------------------------------------------

Json Lighthouse::district_note(const Json& req) {
  const std::string name = req.get("district").as_str();
  const int64_t ep = req.get("epoch").as_int(0);
  Json resp = Json::object();
  if (name.empty()) {
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of("district rollup missing district name");
    return resp;
  }
  std::lock_guard<std::mutex> lk(districts_mu_);
  DistrictEntry& e = districts_[name];
  // Per-district fence: a rollup stamped with an epoch below the highest
  // this district has reported is the fenced old primary still talking
  // after a failover — drop it so the root's view can't flap backwards.
  if (ep < e.epoch) {
    e.stale_dropped += 1;
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of("stale district epoch");
    return resp;
  }
  if (ep > e.epoch && e.hb_count > 0) {
    // Epoch advance from a district we already knew = its lighthouse
    // failed over (standby takeover bumps the epoch). Only this district's
    // row changes; siblings and other jobs' tables are untouched.
    e.failovers += 1;
    lh_log(
        "[lighthouse] district %s failed over: epoch %lld -> %lld\n",
        name.c_str(), static_cast<long long>(e.epoch),
        static_cast<long long>(ep));
  }
  e.epoch = ep;
  e.last_hb_ms = now_ms();
  e.hb_count += 1;
  e.lost = false;
  e.rollup = req.get("district_rollup");
  resp["ok"] = Json::of(true);
  return resp;
}

void Lighthouse::district_scan(int64_t now) {
  std::lock_guard<std::mutex> lk(districts_mu_);
  for (auto& kv : districts_) {
    DistrictEntry& e = kv.second;
    if (!e.lost && now - e.last_hb_ms > opts_.heartbeat_timeout_ms) {
      e.lost = true;
      district_losses_ += 1;
      lh_log(
          "[lighthouse] district %s lost: no rollup for %lld ms\n",
          kv.first.c_str(), static_cast<long long>(now - e.last_hb_ms));
    }
  }
}

Json Lighthouse::districts_json(int64_t now) {
  std::lock_guard<std::mutex> lk(districts_mu_);
  Json out = Json::object();
  for (const auto& kv : districts_) {
    const DistrictEntry& e = kv.second;
    Json d = Json::object();
    d["age_ms"] = Json::of(now - e.last_hb_ms);
    d["epoch"] = Json::of(e.epoch);
    d["hb_count"] = Json::of(e.hb_count);
    d["failovers"] = Json::of(e.failovers);
    d["stale_dropped"] = Json::of(e.stale_dropped);
    d["lost"] = Json::of(e.lost);
    d["jobs"] = e.rollup.get("jobs");
    out[kv.first] = d;
  }
  return out;
}

// ---------------------------------------------------------------------------
// Live fleet health plane (per job)
// ---------------------------------------------------------------------------

namespace {
constexpr size_t kFleetAnomalyRing = 64;     // rise-edge records kept
constexpr int64_t kFleetStickyMs = 10000;    // straggler display hold
constexpr int64_t kFleetCommitStall = 3;     // cf streak that flags
constexpr double kFleetSlowRateFrac = 0.5;   // rate < frac*median flags
constexpr int64_t kFleetStepLag = 2;         // step < median-lag flags
constexpr int64_t kFleetJitterMult = 8;      // budget = mult * cadence
constexpr int64_t kFleetJitterFloorMs = 1000;
constexpr int64_t kFleetEwmaWarmup = 5;      // gaps before EWMA budget counts
constexpr size_t kFleetSignalRing = 64;      // failure signals kept
constexpr size_t kEvictedCap = 256;          // eviction records awaiting an ack
// (The old full-sort fleet_median lived here; the MedianTracker members in
// lighthouse.hpp maintain the identical upper median incrementally.)
}  // namespace

int64_t Lighthouse::fleet_jitter_budget_ms(const FleetEntry& e) const {
  // Deterministic when the sender declared its cadence; EWMA of observed
  // inter-arrival gaps as the old-client fallback. The floor absorbs GC /
  // scheduler hiccups that are noise at any cadence.
  int64_t base = e.hb_interval_ms > 0
                     ? e.hb_interval_ms * kFleetJitterMult
                     : static_cast<int64_t>(e.hb_gap_ewma_ms) * kFleetJitterMult;
  return base < kFleetJitterFloorMs ? kFleetJitterFloorMs : base;
}

void Lighthouse::fleet_set_flag(JobState& js, const std::string& replica_id,
                                FleetEntry& e, const std::string& kind,
                                int64_t now, Json detail) {
  e.straggler_until_ms = now + kFleetStickyMs;
  js.fleet_gen += 1;  // sticky-window extension alone changes the table view
  if (e.flags.count(kind)) return;  // only the RISE edge is an anomaly
  if (e.flags.empty()) js.flagged += 1;
  e.flags.insert(kind);
  js.anomaly_seq += 1;
  Json a = Json::object();
  a["seq"] = Json::of(js.anomaly_seq);
  a["ts_ms"] = Json::of(now);
  a["replica_id"] = Json::of(replica_id);
  a["kind"] = Json::of(kind);
  a["job"] = Json::of(js.name);
  a["detail"] = detail;
  js.anomalies.push_back(a);
  while (js.anomalies.size() > kFleetAnomalyRing) {
    // At fleet scale the ring overflows routinely; a silent pop would make
    // the anomaly feed look complete when it is not. The drop count rides
    // /fleet.json + /metrics, and obs_export journals the rise edge.
    js.anomalies.pop_front();
    js.anomalies_dropped += 1;
  }
  lh_log("[lighthouse] anomaly #%lld: %s on %s (job %s) %s\n",
         static_cast<long long>(js.anomaly_seq), kind.c_str(),
         replica_id.c_str(), js.name.c_str(), detail.dump().c_str());
  // Digest-driven anomaly rise-edges double as failure evidence (the
  // heartbeat-gap rules have their own cadence-aware hb_lapse source in
  // the scan/eviction path, so they are excluded here).
  if (kind != "hb_jitter") {
    Json d = Json::object();
    d["kind"] = Json::of(kind);
    d["anomaly_seq"] = Json::of(js.anomaly_seq);
    signal_note_locked(js, "digest_anomaly", replica_id, "lighthouse.digest",
                       d, now);
  }
}

void Lighthouse::fleet_clear_flag(JobState& js, FleetEntry& e,
                                  const std::string& kind) {
  if (e.flags.erase(kind) == 0) return;
  if (e.flags.empty()) js.flagged -= 1;
  js.fleet_gen += 1;
}

void Lighthouse::signal_note_locked(JobState& js, const std::string& source,
                                    const std::string& replica_id,
                                    const std::string& site, Json detail,
                                    int64_t now) {
  // One failure signal into the job's ring — same discipline as the anomaly
  // ring: monotonic seq (the consumers' cursor), bounded ring, overflow pops
  // the oldest and bumps the drop counter so the feed can't silently look
  // complete.
  js.signal_seq += 1;
  js.signal_counts[source] += 1;
  // Fault bookkeeping off the evidence plane: hard sources are faults for
  // MTBF, and the first one with no open episode starts the ETTR clock —
  // recovery is "done" when any digest step passes the fleet max as of
  // now (forward progress resumed; see fleet_note_heartbeat).
  if (hard_signal_source(source)) {
    js.hard_signals += 1;
    if (!js.ettr_open) {
      int64_t max_step = 0;
      for (const auto& kv : js.fleet)
        if (kv.second.has_digest) {
          int64_t st = kv.second.digest.get("step").as_int(0);
          if (st > max_step) max_step = st;
        }
      js.ettr_open = true;
      js.ettr_open_ms = now;
      js.ettr_open_step = max_step;
    }
  }
  Json sgn = Json::object();
  sgn["seq"] = Json::of(js.signal_seq);
  sgn["ts_ms"] = Json::of(now);
  sgn["replica_id"] = Json::of(replica_id);
  sgn["source"] = Json::of(source);
  sgn["site"] = Json::of(site);
  sgn["job"] = Json::of(js.name);
  sgn["detail"] = detail;
  js.signals.push_back(sgn);
  while (js.signals.size() > kFleetSignalRing) {
    js.signals.pop_front();
    js.signals_dropped += 1;
  }
  // Stamp the fleet row (never CREATE one: a signal about a replica the
  // fleet never saw must not fabricate a liveness row).
  auto it = js.fleet.find(replica_id);
  if (it != js.fleet.end()) {
    it->second.last_signal = source;
    it->second.last_signal_ms = now;
  }
  js.fleet_gen += 1;
  lh_log("[lighthouse] signal #%lld: %s on %s via %s (job %s) %s\n",
         static_cast<long long>(js.signal_seq), source.c_str(),
         replica_id.c_str(), site.c_str(), js.name.c_str(),
         js.signals.back().get("detail").dump().c_str());
}

void Lighthouse::evidence_evict_locked(JobState& js,
                                       const std::string& replica_id,
                                       int64_t now, int64_t seq,
                                       int64_t open_gap_ms,
                                       int64_t budget_ms) {
  // Evidence says this replica is dead: drop it from the quorum tables NOW
  // so the next evaluation forms the shrunken quorum, instead of waiting
  // out heartbeat_timeout_ms. Same gate fixups as a graceful leave, but NO
  // tombstone — evidence can be wrong, and the replica's next heartbeat or
  // registration re-admits it with zero ceremony. The fleet row stays
  // (flags, digest, last_signal intact) as detection forensics.
  const bool was_part = js.state.participants.count(replica_id) > 0;
  const bool was_hb = js.state.heartbeats.count(replica_id) > 0;
  if (!was_part && !was_hb) return;
  js.state.heartbeats.erase(replica_id);
  js.state.heartbeat_addrs.erase(replica_id);
  js.state.participants.erase(replica_id);
  if (was_hb && !was_part) js.hb_not_joined -= 1;
  if (was_part && js.prev_ids.count(replica_id)) js.prev_present -= 1;
  // What went, for the log and for the evicted id itself (its next ack):
  // a participant entry IS its registration for the next quorum, and with a
  // handler parked in quorum_rpc it is a request somebody is waiting on.
  auto pk = js.parked.find(replica_id);
  const bool was_parked = was_part && pk != js.parked.end() && pk->second > 0;
  JobState::Evicted ev;
  ev.seq = seq;
  ev.at_ms = now;
  ev.open_gap_ms = open_gap_ms;
  ev.budget_ms = budget_ms;
  ev.erased = was_hb ? "heartbeat" : "";
  if (was_part) ev.erased += "+participant";
  if (was_parked) ev.erased += "+quorum_request";
  if (js.evicted.size() >= kEvictedCap) js.evicted.erase(js.evicted.begin());
  js.evicted[replica_id] = ev;
  lh_log(
      "[lighthouse] evicted %s on evidence (job %s): signal #%lld gap_ms=%lld "
      "budget_ms=%lld erased=%s\n",
      replica_id.c_str(), js.name.c_str(), static_cast<long long>(seq),
      static_cast<long long>(open_gap_ms), static_cast<long long>(budget_ms),
      ev.erased.c_str());
}

void Lighthouse::readmit_locked(JobState& js, const std::string& replica_id,
                                const char* via, int64_t now) {
  auto it = js.evicted.find(replica_id);
  if (it == js.evicted.end() || it->second.out_ms >= 0) return;
  JobState::Evicted& ev = it->second;
  ev.out_ms = now - ev.at_ms;
  ev.via = via;
  lh_log(
      "[lighthouse] re-admitted %s by its %s (job %s): signal #%lld "
      "out_ms=%lld erased=%s\n",
      replica_id.c_str(), via, js.name.c_str(),
      static_cast<long long>(ev.seq), static_cast<long long>(ev.out_ms),
      ev.erased.c_str());
  // A request parked in quorum_rpc lost its registration to the eviction:
  // wake it; it registers again now that the id is known to be alive.
  if (js.parked.count(replica_id)) js.cv.notify_all();
}

// Retire / fold one entry's digest contributions. Together these keep the
// running aggregates exactly equal to a full-table recompute: every digest
// row contributes its step and goodput, its rate only when > 0 (matching
// the old scan's filter), and its commit-failure streak to the max-tracker.
void Lighthouse::fleet_agg_remove(JobState& js, const FleetEntry& e) {
  if (!e.has_digest) return;
  double r = e.digest.get("rate").as_double(0.0);
  if (r > 0.0) js.agg_rates.erase(r);
  js.agg_steps.erase(static_cast<double>(e.digest.get("step").as_int(0)));
  js.agg_gps.erase(e.digest.get("gp").as_double(0.0));
  auto it = js.agg_cfs.find(e.digest.get("cf").as_int(0));
  if (it != js.agg_cfs.end()) js.agg_cfs.erase(it);
  js.n_digest -= 1;
  double acct[kNumBadputKinds];
  if (digest_acct(e.digest, acct)) {
    for (int i = 0; i < kNumBadputKinds; i++) js.agg_badput[i] -= acct[i];
    js.n_acct -= 1;
  }
}

void Lighthouse::fleet_agg_insert(JobState& js, const FleetEntry& e) {
  if (!e.has_digest) return;
  double r = e.digest.get("rate").as_double(0.0);
  if (r > 0.0) js.agg_rates.insert(r);
  js.agg_steps.insert(static_cast<double>(e.digest.get("step").as_int(0)));
  js.agg_gps.insert(e.digest.get("gp").as_double(0.0));
  js.agg_cfs.insert(e.digest.get("cf").as_int(0));
  js.n_digest += 1;
  double acct[kNumBadputKinds];
  if (digest_acct(e.digest, acct)) {
    for (int i = 0; i < kNumBadputKinds; i++) js.agg_badput[i] += acct[i];
    js.n_acct += 1;
  }
}

void Lighthouse::fleet_erase(JobState& js, const std::string& replica_id) {
  auto it = js.fleet.find(replica_id);
  if (it == js.fleet.end()) return;
  fleet_agg_remove(js, it->second);
  if (!it->second.flags.empty()) js.flagged -= 1;
  js.fleet.erase(it);
  js.fleet_gen += 1;
}

void Lighthouse::fleet_note_heartbeat(JobState& js,
                                      const std::string& replica_id,
                                      const Json& req, int64_t now) {
  FleetEntry& e = js.fleet[replica_id];
  if (e.hb_count > 0) {
    int64_t gap = now - e.last_hb_ms;
    // Judge the gap against the budget BEFORE folding it into the EWMA —
    // a jittered gap must not raise its own threshold.
    bool budget_valid =
        e.hb_interval_ms > 0 || e.hb_count >= kFleetEwmaWarmup;
    if (budget_valid && gap > fleet_jitter_budget_ms(e)) {
      Json d = Json::object();
      d["gap_ms"] = Json::of(gap);
      d["budget_ms"] = Json::of(fleet_jitter_budget_ms(e));
      fleet_set_flag(js, replica_id, e, "hb_jitter", now, d);
      e.last_jitter_ms = now;
    }
    e.hb_gap_ewma_ms = e.hb_gap_ewma_ms == 0.0
                           ? static_cast<double>(gap)
                           : 0.8 * e.hb_gap_ewma_ms + 0.2 * gap;
  }
  e.last_hb_ms = now;
  e.hb_count += 1;
  js.fleet_gen += 1;
  if (js.first_seen_ms == 0) js.first_seen_ms = now;
  int64_t declared = req.get("hb_interval_ms").as_int(0);
  if (declared > 0) e.hb_interval_ms = declared;
  if (!req.has("digest") || !req.get("digest").is_object()) return;

  // Digest-driven rules run at ARRIVAL, against the job's fleet table as of
  // this heartbeat: given the same per-job digest sequence the flag/anomaly
  // sequence is identical, so a chaos replay reproduces its alerts — and a
  // sibling job's digests can never perturb it.
  // Bounded-cost contract: everything below is O(log N) — the medians the
  // rules compare against come from the running trackers, never from a
  // full-table rescan (tests/test_fleet.py pins tracker == recompute).
  int64_t an_t0 = now_us_steady();
  fleet_agg_remove(js, e);  // retire the previous digest's contributions
  e.digest = req.get("digest");
  e.has_digest = true;
  e.digest_ms = now;
  fleet_agg_insert(js, e);

  int64_t cf = e.digest.get("cf").as_int(0);
  if (cf >= kFleetCommitStall) {
    Json d = Json::object();
    d["cf"] = Json::of(cf);
    fleet_set_flag(js, replica_id, e, "commit_stall", now, d);
  } else {
    fleet_clear_flag(js, e, "commit_stall");
  }

  double own_rate = e.digest.get("rate").as_double(0.0);
  if (js.agg_rates.size() >= 2) {
    double med = js.agg_rates.median();
    if (own_rate < kFleetSlowRateFrac * med) {
      Json d = Json::object();
      d["rate"] = Json::of(own_rate);
      d["median_rate"] = Json::of(med);
      fleet_set_flag(js, replica_id, e, "slow_rate", now, d);
    } else {
      fleet_clear_flag(js, e, "slow_rate");
    }
  }
  int64_t own_step = e.digest.get("step").as_int(0);
  if (js.agg_steps.size() >= 2) {
    int64_t med = static_cast<int64_t>(js.agg_steps.median());
    if (own_step < med - kFleetStepLag) {
      Json d = Json::object();
      d["step"] = Json::of(own_step);
      d["median_step"] = Json::of(med);
      fleet_set_flag(js, replica_id, e, "step_lag", now, d);
    } else {
      fleet_clear_flag(js, e, "step_lag");
    }
  }

  // ETTR close: training moved past the fleet max step recorded when the
  // fault's hard evidence arrived — the job has recovered.
  if (js.ettr_open && own_step > js.ettr_open_step) {
    js.ettr_sum_s += static_cast<double>(now - js.ettr_open_ms) / 1000.0;
    js.ettr_n += 1;
    js.ettr_open = false;
  }

  // SLO burn-rate evaluator: burn = (1 - goodput) / (1 - target) — how
  // many times faster than allotted the job spends its error budget.
  // Rise-edge only (the ring is the pager feed), armed after slo_min_s_
  // accounted seconds so compile/startup can't page, disarmed entirely
  // when target >= 1 (no budget to spend).
  if (js.n_acct > 0 && slo_goodput_ < 1.0) {
    double acct_total = 0.0;
    for (int i = 0; i < kNumBadputKinds; i++)
      acct_total += js.agg_badput[i] > 0.0 ? js.agg_badput[i] : 0.0;
    if (acct_total >= slo_min_s_) {
      double gp = std::max(js.agg_badput[kBadputComputeIdx], 0.0) / acct_total;
      double burn = (1.0 - gp) / (1.0 - slo_goodput_);
      if (burn >= slo_burn_) {
        if (!js.slo_burning) {
          js.slo_burning = true;
          js.slo_seq += 1;
          Json b = Json::object();
          b["seq"] = Json::of(js.slo_seq);
          b["ts_ms"] = Json::of(now);
          b["job"] = Json::of(js.name);
          b["goodput"] = Json::of(gp);
          b["target"] = Json::of(slo_goodput_);
          b["burn"] = Json::of(burn);
          js.slo_burns.push_back(b);
          while (js.slo_burns.size() > kFleetAnomalyRing) {
            js.slo_burns.pop_front();
            js.slo_dropped += 1;
          }
          js.fleet_gen += 1;
          lh_log(
              "[lighthouse] slo_burn #%lld: job %s goodput %.4f vs "
              "target %.4f (burn %.2fx)\n",
              static_cast<long long>(js.slo_seq), js.name.c_str(), gp,
              slo_goodput_, burn);
        }
      } else if (js.slo_burning) {
        js.slo_burning = false;  // fall edge: budget spend back in bounds
        js.fleet_gen += 1;
      }
    }
  }
  hist_anomaly_.observe_us(now_us_steady() - an_t0);
}

void Lighthouse::fleet_scan_locked(JobState& js, int64_t now) {
  // Time-based rules only: an OPEN heartbeat gap (the replica is wedged
  // RIGHT NOW — arrival-side checks can't see it because nothing arrives)
  // plus expiry of a jitter flag whose evidence has aged out.
  for (auto& kv : js.fleet) {
    FleetEntry& e = kv.second;
    bool budget_valid =
        e.hb_interval_ms > 0 || e.hb_count >= kFleetEwmaWarmup;
    int64_t open_gap = now - e.last_hb_ms;
    if (budget_valid && open_gap > fleet_jitter_budget_ms(e)) {
      Json d = Json::object();
      d["gap_ms"] = Json::of(open_gap);
      d["budget_ms"] = Json::of(fleet_jitter_budget_ms(e));
      d["open"] = Json::of(true);
      fleet_set_flag(js, kv.first, e, "hb_jitter", now, d);
      e.last_jitter_ms = now;
    } else if (e.flags.count("hb_jitter") &&
               now - e.last_jitter_ms > kFleetStickyMs) {
      fleet_clear_flag(js, e, "hb_jitter");
    }
  }
  // Evidence-driven hb-lapse eviction: a replica whose OPEN gap blew the
  // cadence-aware budget is dead on evidence — signal it and drop it from
  // the quorum tables immediately, so the shrunken quorum forms at tick
  // speed instead of heartbeat_timeout_ms. Only replicas that DECLARED a
  // cadence qualify (old clients keep the timeout path: wire back-compat),
  // and only while they still hold a quorum-plane heartbeat entry — which
  // also makes the signal naturally rise-edge-only.
  if (opts_.evidence) {
    std::vector<std::string> evict;
    for (const auto& kv : js.fleet) {
      const FleetEntry& e = kv.second;
      if (e.hb_interval_ms <= 0) continue;
      int64_t budget = e.hb_interval_ms * opts_.evict_mult;
      if (budget < opts_.evict_floor_ms) budget = opts_.evict_floor_ms;
      if (now - e.last_hb_ms <= budget) continue;
      if (!js.state.heartbeats.count(kv.first)) continue;
      evict.push_back(kv.first);
    }
    for (const auto& id : evict) {
      const FleetEntry& e = js.fleet[id];
      Json d = Json::object();
      d["gap_ms"] = Json::of(now - e.last_hb_ms);
      d["budget_ms"] =
          Json::of(std::max(e.hb_interval_ms * opts_.evict_mult,
                            opts_.evict_floor_ms));
      const int64_t gap = d.get("gap_ms").as_int();
      const int64_t budget = d.get("budget_ms").as_int();
      signal_note_locked(js, "hb_lapse", id, "lighthouse.fleet_scan", d, now);
      evidence_evict_locked(js, id, now, js.signal_seq, gap, budget);
    }
    // Evidence tick: fresh evidence re-evaluates the quorum NOW; the
    // periodic tick and the timeout landing stay as the fallback.
    if (!evict.empty()) job_tick_locked(js, now);
  }
}

// Aggregate dict straight from the running trackers — O(1) medians/max plus
// one allocation-free pass for the time-dependent straggler count. This is
// the "agg" the property tests compare against a full recompute from the
// row dicts in the same payload.
Json Lighthouse::fleet_agg_locked(JobState& js, int64_t now) {
  int64_t n_straggler = 0;
  for (const auto& kv : js.fleet)
    if (!kv.second.flags.empty() || now < kv.second.straggler_until_ms)
      n_straggler += 1;
  Json agg = Json::object();
  agg["n"] = Json::of(static_cast<int64_t>(js.fleet.size()));
  agg["n_digest"] = Json::of(js.n_digest);
  agg["stragglers"] = Json::of(n_straggler);
  agg["median_rate"] = js.agg_rates.size() == 0
                           ? Json::null()
                           : Json::of(js.agg_rates.median());
  agg["median_step"] =
      js.agg_steps.size() == 0
          ? Json::null()
          : Json::of(static_cast<int64_t>(js.agg_steps.median()));
  agg["median_goodput"] =
      js.agg_gps.size() == 0 ? Json::null() : Json::of(js.agg_gps.median());
  agg["max_commit_failures"] =
      Json::of(js.agg_cfs.empty() ? int64_t{0} : *js.agg_cfs.rbegin());
  agg["anomalies_dropped"] = Json::of(js.anomalies_dropped);
  agg["signals_dropped"] = Json::of(js.signals_dropped);
  // Elastic-membership view: current quorum size plus cumulative
  // join/leave churn, so obs_top's WORLD column tracks capacity changes
  // (deliberate scale-up/down AND crash churn) from the same counters
  // /metrics exports.
  agg["quorum_world"] = Json::of(
      js.last_quorum ? static_cast<int64_t>(js.last_quorum->participants.size())
                     : int64_t{0});
  agg["joins_total"] = Json::of(js.joins_total);
  agg["leaves_total"] = Json::of(js.leaves_total);
  // Control-plane ownership view: the fencing epoch this instance stamps on
  // quorums (obs_top's EPOCH column). A jump means a standby takeover; a
  // reader comparing two lighthouses can tell owner from fenced stale
  // primary by it.
  agg["epoch"] = Json::of(epoch_.load());
  // Time-accounting rollup: per-kind badput seconds summed over every row
  // whose digest carries an acct vector (clamped at 0 — the running sums
  // can drift a few ulps negative), the job goodput fraction (compute
  // share of all accounted seconds), and the fault metrics derived from
  // the evidence plane. Null until any acct digest / fault arrives.
  double acct_total = 0.0;
  for (int i = 0; i < kNumBadputKinds; i++)
    acct_total += js.agg_badput[i] > 0.0 ? js.agg_badput[i] : 0.0;
  if (js.n_acct > 0 && acct_total > 0.0) {
    Json bp = Json::object();
    for (int i = 0; i < kNumBadputKinds; i++)
      bp[kBadputKindNames[i]] = Json::of(std::max(js.agg_badput[i], 0.0));
    agg["badput_s"] = bp;
    agg["goodput_frac"] =
        Json::of(std::max(js.agg_badput[kBadputComputeIdx], 0.0) / acct_total);
  } else {
    agg["badput_s"] = Json::null();
    agg["goodput_frac"] = Json::null();
  }
  agg["mtbf_s"] =
      js.hard_signals > 0 && js.first_seen_ms > 0
          ? Json::of(static_cast<double>(now - js.first_seen_ms) / 1000.0 /
                     static_cast<double>(js.hard_signals))
          : Json::null();
  agg["ettr_s"] = js.ettr_n > 0 ? Json::of(js.ettr_sum_s /
                                           static_cast<double>(js.ettr_n))
                                : Json::null();
  agg["slo_burning"] = Json::of(js.slo_burning);
  agg["slo_dropped"] = Json::of(js.slo_dropped);
  return agg;
}

std::shared_ptr<const Lighthouse::FleetSnapshot> Lighthouse::fleet_snapshot(
    const std::string& job, int64_t now) {
  // Empty job = the composite view: served FROM the default island's cache
  // slot (its payload extended with the cross-job summary + districts), so
  // pre-namespace consumers keep the old top-level schema while each job's
  // full table stays per-job. Keyed per island: one job's content change
  // never rebuilds, or serves a stale gen to, another job.
  const std::string jname = job.empty() ? "default" : job;
  const bool composite = jname == "default";
  JobState& js = job_state(jname);
  // Bounded staleness: any cached payload younger than fleet_snap_ms is
  // served as-is (fleet_snap_ms == 0 disables caching — the "before" mode
  // the fleet_load harness benchmarks against).
  if (opts_.fleet_snap_ms > 0) {
    std::lock_guard<std::mutex> lk(js.snap_mu);
    if (js.snap && now >= js.snap->built_ms &&
        now - js.snap->built_ms <= opts_.fleet_snap_ms)
      return js.snap;
  }
  // Single-flight rebuild: concurrent readers that all see a stale (or
  // absent) snapshot would otherwise each pay the O(N) rebuild at once —
  // a thundering herd that turns the cache off exactly when load peaks.
  // One caller rebuilds; the rest block here, then re-check and serve the
  // winner's result.
  std::lock_guard<std::mutex> rebuild_lk(js.rebuild_mu);
  if (opts_.fleet_snap_ms > 0) {
    std::lock_guard<std::mutex> lk(js.snap_mu);
    if (js.snap && now >= js.snap->built_ms &&
        now - js.snap->built_ms <= opts_.fleet_snap_ms)
      return js.snap;
  }
  int64_t t0 = now_us_steady();
  // Copy raw state under the hot lock; build + dump the JSON off it. The
  // copy is the cheap part (row structs + small digest dicts); the O(N)
  // string formatting that used to stall heartbeats happens unlocked.
  std::vector<std::pair<std::string, FleetEntry>> rows;
  std::deque<Json> anomalies;
  std::deque<Json> signals;
  std::deque<Json> slo_burns;
  std::map<std::string, int64_t> signal_counts;
  Json agg;
  int64_t gen, aseq, sseq, slseq;
  {
    std::lock_guard<std::mutex> lk(js.mu);
    rows.assign(js.fleet.begin(), js.fleet.end());
    anomalies = js.anomalies;
    signals = js.signals;
    slo_burns = js.slo_burns;
    signal_counts = js.signal_counts;
    agg = fleet_agg_locked(js, now);
    gen = js.fleet_gen;
    aseq = js.anomaly_seq;
    sseq = js.signal_seq;
    slseq = js.slo_seq;
  }
  auto snap = std::make_shared<FleetSnapshot>();
  snap->gen = gen;
  snap->built_ms = now;
  Json f = Json::object();
  f["ts_ms"] = Json::of(now);
  f["gen"] = Json::of(gen);
  f["snap_ms"] = Json::of(opts_.fleet_snap_ms);
  f["job"] = Json::of(jname);
  Json reps = Json::object();
  for (const auto& kv : rows) {
    const FleetEntry& e = kv.second;
    Json r = Json::object();
    r["last_hb_age_ms"] = Json::of(now - e.last_hb_ms);
    r["hb_interval_ms"] = Json::of(e.hb_interval_ms);
    // Old client (no digest ever): fields render as null, row stays —
    // the forward-compat contract the tests pin.
    r["digest"] = e.has_digest ? e.digest : Json::null();
    r["digest_age_ms"] =
        e.has_digest ? Json::of(now - e.digest_ms) : Json::null();
    Json fl = Json::array();
    for (const auto& k : e.flags) fl.push(Json::of(k));
    if (now - e.last_hb_ms > opts_.heartbeat_timeout_ms)
      fl.push(Json::of("stale"));  // view-only: presence, not an anomaly
    r["flags"] = fl;
    r["straggler"] =
        Json::of(!e.flags.empty() || now < e.straggler_until_ms);
    // Failure-evidence view: last signal source recorded about this
    // replica and its age (null until any evidence arrives).
    r["signal"] =
        e.last_signal.empty() ? Json::null() : Json::of(e.last_signal);
    r["signal_age_ms"] = e.last_signal.empty()
                             ? Json::null()
                             : Json::of(now - e.last_signal_ms);
    reps[kv.first] = r;
  }
  f["replicas"] = reps;
  f["agg"] = agg;
  Json an = Json::array();
  for (const auto& a : anomalies) an.push(a);
  f["anomalies"] = an;
  f["anomaly_seq"] = Json::of(aseq);
  Json sg = Json::array();
  for (const auto& s : signals) sg.push(s);
  f["signals"] = sg;
  f["signal_seq"] = Json::of(sseq);
  Json sb = Json::array();
  for (const auto& b : slo_burns) sb.push(b);
  f["slo_burns"] = sb;
  f["slo_seq"] = Json::of(slseq);
  Json scnt = Json::object();
  for (const auto& kv : signal_counts) scnt[kv.first] = Json::of(kv.second);
  f["signal_counts"] = scnt;
  if (composite) {
    // Cross-job summary map + district table ride the composite payload
    // only — SUMMARIES, not full tables, so the default payload stays O(N
    // of default) + O(jobs) and per-job readers use ?job=<id>. Each
    // sibling island is locked one at a time, off this island's hot path.
    Json jobs = Json::object();
    for (JobState* oj : all_jobs()) {
      std::lock_guard<std::mutex> olk(oj->mu);
      jobs[oj->name] = fleet_summary_locked(*oj, now);
    }
    f["jobs"] = jobs;
    f["districts"] = districts_json(now);
  }
  snap->json = f;
  snap->body = f.dump();
  hist_snapshot_.observe_us(now_us_steady() - t0);
  std::lock_guard<std::mutex> lk(js.snap_mu);
  js.snap = snap;
  return js.snap;
}

Json Lighthouse::fleet_summary_locked(JobState& js, int64_t now) {
  Json s = fleet_agg_locked(js, now);
  s["anomaly_seq"] = Json::of(js.anomaly_seq);
  s["signal_seq"] = Json::of(js.signal_seq);
  s["slo_seq"] = Json::of(js.slo_seq);
  s["gen"] = Json::of(js.fleet_gen);
  return s;
}

std::string Lighthouse::render_status_html() {
  Json s = status_json();
  std::ostringstream html;
  html << "<!doctype html><html><head><title>torchft-tpu lighthouse</title>"
       << "<style>body{font-family:monospace;margin:2em}table{border-collapse:"
          "collapse}td,th{border:1px solid #999;padding:4px 8px}</style>"
       << "</head><body><h1>torchft-tpu lighthouse</h1>"
       << "<p>quorum_id: " << s.get("quorum_id").as_int() << "</p>";
  html << "<h2>heartbeats</h2><table><tr><th>replica</th><th>age (ms)</th>"
       << "<th></th></tr>";
  for (const auto& kv : s.get("heartbeat_ages_ms").obj) {
    html << "<tr><td>" << kv.first << "</td><td>" << kv.second.as_int()
         << "</td><td><form method=post action=\"/replica/" << kv.first
         << "/kill\" style=\"display:inline\"><button>kill</button></form> "
         << "<form method=post action=\"/replica/" << kv.first
         << "/drain\" style=\"display:inline\"><button>drain</button></form>"
         << "</td></tr>";
  }
  html << "</table><p><form method=post action=\"/drain_all\" "
          "style=\"display:inline\"><button>drain ALL (stop job "
          "cleanly)</button></form></p>";
  // Namespace overview: one row per job island (quorum + fleet summary).
  html << "<h2>jobs</h2><table><tr><th>job</th><th>quorum_id</th>"
       << "<th>members</th><th>participants</th><th>heartbeats</th></tr>";
  for (const auto& kv : s.get("jobs").obj) {
    html << "<tr><td>" << kv.first << "</td><td>"
         << kv.second.get("quorum_id").as_int() << "</td><td>"
         << kv.second.get("members").as_int() << "</td><td>"
         << kv.second.get("participants").as_int() << "</td><td>"
         << kv.second.get("heartbeats").as_int() << "</td></tr>";
  }
  html << "</table>";
  if (!s.get("districts").obj.empty()) {
    html << "<h2>districts</h2><table><tr><th>district</th><th>epoch</th>"
         << "<th>age (ms)</th><th>failovers</th><th>lost</th></tr>";
    for (const auto& kv : s.get("districts").obj) {
      html << "<tr><td>" << kv.first << "</td><td>"
           << kv.second.get("epoch").as_int() << "</td><td>"
           << kv.second.get("age_ms").as_int() << "</td><td>"
           << kv.second.get("failovers").as_int() << "</td><td>"
           << (kv.second.get("lost").as_bool() ? "LOST" : "up")
           << "</td></tr>";
    }
    html << "</table>";
  }
  html << "<h2>previous quorum</h2><table><tr><th>replica</th>"
       << "<th>address</th><th>step</th><th>world</th></tr>";
  if (s.get("prev_quorum").is_object()) {
    for (const auto& p : s.get("prev_quorum").get("participants").arr) {
      html << "<tr><td>" << p.get("replica_id").as_str() << "</td><td>"
           << p.get("address").as_str() << "</td><td>"
           << p.get("step").as_int() << "</td><td>"
           << p.get("world_size").as_int() << "</td></tr>";
    }
  }
  html << "</table>";
  if (!s.get("reason").as_str().empty())
    html << "<p>waiting: " << s.get("reason").as_str() << "</p>";
  html << "</body></html>";
  return html.str();
}

static std::string prom_escape(const std::string& s) {
  // Prometheus label values must escape backslash, double-quote, and
  // newline — replica ids are client-supplied strings.
  std::string out;
  out.reserve(s.size());
  for (char c : s) {
    if (c == '\\') out += "\\\\";
    else if (c == '"') out += "\\\"";
    else if (c == '\n') out += "\\n";
    else out += c;
  }
  return out;
}

std::string Lighthouse::render_metrics() {
  // Prometheus text exposition (the reference lighthouse has only an HTML
  // dashboard; a scrapeable endpoint is what production monitoring needs).
  // Unlabeled gauges keep the pre-namespace series names and report the
  // DEFAULT job (existing alert rules keep firing); job-labeled gauges
  // cover every namespace. Scalars and minimal per-replica tuples are
  // copied under each job's lock one island at a time; all string
  // formatting happens off the hot locks, so a scrape never stalls the
  // heartbeat path behind O(N) text building.
  struct FleetRow {
    std::string id;
    bool straggler = false;
    bool has_rate = false;
    double rate = 0.0;
  };
  struct JobRow {
    std::string name;
    int64_t quorum_id = 0, quorum_gen = 0, joins = 0, leaves = 0;
    int64_t aseq = 0, adropped = 0, gen = 0;
    int64_t sseq = 0, sdropped = 0;
    size_t n_participants = 0, n_members = 0, n_fleet = 0;
    int64_t n_straggler = 0;
    // Time-accounting plane (valid when has_acct).
    bool has_acct = false;
    double badput[kNumBadputKinds] = {};
    double goodput = 0.0;
    int64_t slo_seq = 0;
    bool slo_burning = false;
    double mtbf_s = -1.0, ettr_s = -1.0;  // <0 = no fault observed yet
  };
  int64_t now = now_ms();
  const int64_t epoch = epoch_.load();
  const int64_t takeovers = takeovers_.load();
  const int64_t demotions = demotions_.load();
  const bool is_active = active_.load();
  std::vector<std::pair<std::string, int64_t>> hb_ages;
  std::vector<std::pair<std::string, int64_t>> member_steps;
  std::vector<FleetRow> rows;
  bool have_median = false;
  double median_rate = 0.0;
  std::vector<JobRow> job_rows;
  std::map<std::string, int64_t> def_signal_counts;
  JobRow def;
  for (JobState* jsp : all_jobs()) {
    std::lock_guard<std::mutex> lk(jsp->mu);
    JobRow j;
    j.name = jsp->name;
    j.quorum_id = jsp->state.quorum_id;
    j.quorum_gen = jsp->quorum_gen;
    j.joins = jsp->joins_total;
    j.leaves = jsp->leaves_total;
    j.aseq = jsp->anomaly_seq;
    j.adropped = jsp->anomalies_dropped;
    j.sseq = jsp->signal_seq;
    j.sdropped = jsp->signals_dropped;
    j.gen = jsp->fleet_gen;
    j.n_participants = jsp->state.participants.size();
    j.n_members = jsp->state.prev_quorum
                      ? jsp->state.prev_quorum->participants.size()
                      : 0;
    j.n_fleet = jsp->fleet.size();
    for (const auto& kv : jsp->fleet)
      if (!kv.second.flags.empty() || now < kv.second.straggler_until_ms)
        j.n_straggler += 1;
    double acct_total = 0.0;
    for (int i = 0; i < kNumBadputKinds; i++) {
      j.badput[i] = std::max(jsp->agg_badput[i], 0.0);
      acct_total += j.badput[i];
    }
    if (jsp->n_acct > 0 && acct_total > 0.0) {
      j.has_acct = true;
      j.goodput = j.badput[kBadputComputeIdx] / acct_total;
    }
    j.slo_seq = jsp->slo_seq;
    j.slo_burning = jsp->slo_burning;
    if (jsp->hard_signals > 0 && jsp->first_seen_ms > 0)
      j.mtbf_s = static_cast<double>(now - jsp->first_seen_ms) / 1000.0 /
                 static_cast<double>(jsp->hard_signals);
    if (jsp->ettr_n > 0)
      j.ettr_s = jsp->ettr_sum_s / static_cast<double>(jsp->ettr_n);
    if (jsp->name == "default") {
      def = j;
      hb_ages.reserve(jsp->state.heartbeats.size());
      for (const auto& kv : jsp->state.heartbeats)
        hb_ages.emplace_back(kv.first, now - kv.second);
      if (jsp->state.prev_quorum)
        for (const auto& mem : jsp->state.prev_quorum->participants)
          member_steps.emplace_back(mem.replica_id, mem.step);
      rows.reserve(jsp->fleet.size());
      for (const auto& kv : jsp->fleet) {
        FleetRow r;
        r.id = kv.first;
        r.straggler =
            !kv.second.flags.empty() || now < kv.second.straggler_until_ms;
        if (kv.second.has_digest) {
          r.rate = kv.second.digest.get("rate").as_double(0.0);
          r.has_rate = true;
        }
        rows.push_back(std::move(r));
      }
      if (jsp->agg_rates.size() > 0) {
        have_median = true;
        median_rate = jsp->agg_rates.median();
      }
      def_signal_counts = jsp->signal_counts;
    }
    job_rows.push_back(std::move(j));
  }
  struct DistrictRow {
    std::string name;
    int64_t epoch = 0, failovers = 0, stale_dropped = 0;
    bool lost = false;
  };
  std::vector<DistrictRow> dist_rows;
  int64_t district_losses;
  {
    std::lock_guard<std::mutex> lk(districts_mu_);
    district_losses = district_losses_;
    for (const auto& kv : districts_) {
      DistrictRow d;
      d.name = kv.first;
      d.epoch = kv.second.epoch;
      d.failovers = kv.second.failovers;
      d.stale_dropped = kv.second.stale_dropped;
      d.lost = kv.second.lost;
      dist_rows.push_back(std::move(d));
    }
  }
  // Label-cardinality bound (TORCHFT_EXPORT_MAX_REPLICAS, shared with
  // obs_export): above the cap, per-replica series are emitted only for
  // anomalous/straggler replicas; healthy rows collapse into the aggregate
  // gauges plus a suppressed-count so the scrape stays O(cap), not O(N).
  const size_t cap = static_cast<size_t>(export_max_replicas_);
  const bool capped = rows.size() > cap;
  int64_t suppressed = 0;
  std::ostringstream m;
  m << "# HELP torchft_lighthouse_quorum_id Current quorum id.\n"
    << "# TYPE torchft_lighthouse_quorum_id gauge\n"
    << "torchft_lighthouse_quorum_id " << def.quorum_id << "\n";
  m << "# HELP torchft_lighthouse_quorum_generation Quorum broadcasts since "
       "boot.\n"
    << "# TYPE torchft_lighthouse_quorum_generation counter\n"
    << "torchft_lighthouse_quorum_generation " << def.quorum_gen << "\n";
  m << "# HELP torchft_lighthouse_epoch Fencing epoch stamped on quorums.\n"
    << "# TYPE torchft_lighthouse_epoch gauge\n"
    << "torchft_lighthouse_epoch " << epoch << "\n";
  m << "# HELP torchft_lighthouse_active 1 when this instance owns the "
       "fleet (forms quorums); 0 when standby/fenced.\n"
    << "# TYPE torchft_lighthouse_active gauge\n"
    << "torchft_lighthouse_active " << (is_active ? 1 : 0) << "\n";
  m << "# HELP torchft_lighthouse_takeovers_total Standby->active "
       "transitions.\n"
    << "# TYPE torchft_lighthouse_takeovers_total counter\n"
    << "torchft_lighthouse_takeovers_total " << takeovers << "\n";
  m << "# HELP torchft_lighthouse_demotions_total Active->standby fences "
       "(superseded by a higher epoch).\n"
    << "# TYPE torchft_lighthouse_demotions_total counter\n"
    << "torchft_lighthouse_demotions_total " << demotions << "\n";
  m << "# HELP torchft_lighthouse_joins_total Members added across quorum "
       "transitions.\n"
    << "# TYPE torchft_lighthouse_joins_total counter\n"
    << "torchft_lighthouse_joins_total " << def.joins << "\n";
  m << "# HELP torchft_lighthouse_leaves_total Members gone across quorum "
       "transitions.\n"
    << "# TYPE torchft_lighthouse_leaves_total counter\n"
    << "torchft_lighthouse_leaves_total " << def.leaves << "\n";
  m << "# HELP torchft_lighthouse_participants Replicas currently waiting in "
       "the next quorum.\n"
    << "# TYPE torchft_lighthouse_participants gauge\n"
    << "torchft_lighthouse_participants " << def.n_participants << "\n";
  m << "# HELP torchft_lighthouse_quorum_members Members of the last "
       "delivered quorum.\n"
    << "# TYPE torchft_lighthouse_quorum_members gauge\n"
    << "torchft_lighthouse_quorum_members " << def.n_members << "\n";
  int64_t max_hb_age = 0;
  for (const auto& kv : hb_ages)
    if (kv.second > max_hb_age) max_hb_age = kv.second;
  m << "# HELP torchft_lighthouse_heartbeat_age_max_ms Oldest replica "
       "heartbeat age.\n"
    << "# TYPE torchft_lighthouse_heartbeat_age_max_ms gauge\n"
    << "torchft_lighthouse_heartbeat_age_max_ms " << max_hb_age << "\n";
  if (!capped) {
    m << "# HELP torchft_lighthouse_heartbeat_age_ms Milliseconds since "
         "each replica's last heartbeat.\n"
      << "# TYPE torchft_lighthouse_heartbeat_age_ms gauge\n";
    for (const auto& kv : hb_ages)
      m << "torchft_lighthouse_heartbeat_age_ms{replica=\""
        << prom_escape(kv.first) << "\"} " << kv.second << "\n";
  }
  if (!member_steps.empty() && !capped) {
    m << "# HELP torchft_lighthouse_member_step Training step each quorum "
         "member reported.\n"
      << "# TYPE torchft_lighthouse_member_step gauge\n";
    for (const auto& kv : member_steps)
      m << "torchft_lighthouse_member_step{replica=\""
        << prom_escape(kv.first) << "\"} " << kv.second << "\n";
  }
  // Live-plane alert gauges: straggler flags + the anomaly counter are
  // what a pager rule fires on; per-replica step rate + the fleet median
  // give the rule its denominator.
  m << "# HELP torchft_lighthouse_anomalies_total Anomaly rise-edges "
       "detected since boot.\n"
    << "# TYPE torchft_lighthouse_anomalies_total counter\n"
    << "torchft_lighthouse_anomalies_total " << def.aseq << "\n";
  m << "# HELP torchft_lighthouse_anomalies_dropped Anomaly records evicted "
       "from the bounded ring (feed incomplete when > 0).\n"
    << "# TYPE torchft_lighthouse_anomalies_dropped counter\n"
    << "torchft_lighthouse_anomalies_dropped " << def.adropped << "\n";
  // Failure-evidence counters: per-source totals (bounded: the source enum
  // is closed at SIGNAL_SOURCES size, never per-replica) plus the ring-drop
  // counter — the same incompleteness alarm the anomaly ring has.
  m << "# HELP torchft_lighthouse_signals_total Failure signals recorded "
       "since boot, by evidence source.\n"
    << "# TYPE torchft_lighthouse_signals_total counter\n"
    << "torchft_lighthouse_signals_total " << def.sseq << "\n";
  for (const auto& kv : def_signal_counts)
    m << "torchft_lighthouse_signals_total{source=\"" << prom_escape(kv.first)
      << "\"} " << kv.second << "\n";
  m << "# HELP torchft_lighthouse_signals_dropped Failure-signal records "
       "evicted from the bounded ring (feed incomplete when > 0).\n"
    << "# TYPE torchft_lighthouse_signals_dropped counter\n"
    << "torchft_lighthouse_signals_dropped " << def.sdropped << "\n";
  m << "# HELP torchft_lighthouse_fleet_gen Fleet-table content generation "
       "(bumped on every mutation; tags /fleet.json snapshots).\n"
    << "# TYPE torchft_lighthouse_fleet_gen counter\n"
    << "torchft_lighthouse_fleet_gen " << def.gen << "\n";
  m << "# HELP torchft_lighthouse_fleet_replicas Replicas in the fleet "
       "table.\n"
    << "# TYPE torchft_lighthouse_fleet_replicas gauge\n"
    << "torchft_lighthouse_fleet_replicas " << rows.size() << "\n";
  m << "# HELP torchft_lighthouse_fleet_stragglers Replicas currently "
       "flagged or inside the sticky straggler window.\n"
    << "# TYPE torchft_lighthouse_fleet_stragglers gauge\n"
    << "torchft_lighthouse_fleet_stragglers " << def.n_straggler << "\n";
  if (!rows.empty()) {
    std::ostringstream strag, per_replica;
    for (const auto& r : rows) {
      if (capped && !r.straggler) {
        suppressed += 1;
        continue;
      }
      strag << "torchft_lighthouse_straggler{replica=\""
            << prom_escape(r.id) << "\"} " << (r.straggler ? 1 : 0) << "\n";
      if (r.has_rate)
        per_replica << "torchft_lighthouse_replica_step_rate{replica=\""
                    << prom_escape(r.id) << "\"} " << r.rate << "\n";
    }
    std::string st = strag.str();
    if (!st.empty()) {
      m << "# HELP torchft_lighthouse_straggler Replica currently flagged "
           "as a straggler (1) or healthy (0).\n"
        << "# TYPE torchft_lighthouse_straggler gauge\n"
        << st;
    }
    std::string per = per_replica.str();
    if (!per.empty()) {
      m << "# HELP torchft_lighthouse_replica_step_rate Committed steps "
           "per second each replica reported in its digest.\n"
        << "# TYPE torchft_lighthouse_replica_step_rate gauge\n"
        << per;
    }
    if (have_median) {
      m << "# HELP torchft_lighthouse_fleet_median_step_rate Fleet median "
           "of reported step rates.\n"
        << "# TYPE torchft_lighthouse_fleet_median_step_rate gauge\n"
        << "torchft_lighthouse_fleet_median_step_rate " << median_rate
        << "\n";
    }
  }
  m << "# HELP torchft_lighthouse_replicas_suppressed Healthy replicas "
       "whose per-replica series were collapsed into aggregates "
       "(TORCHFT_EXPORT_MAX_REPLICAS).\n"
    << "# TYPE torchft_lighthouse_replicas_suppressed gauge\n"
    << "torchft_lighthouse_replicas_suppressed " << suppressed << "\n";
  // Per-job series: every namespace island, keyed by the job label. The
  // cardinality here is O(jobs), not O(replicas) — bounded by how many
  // jobs the fleet actually runs.
  m << "# HELP torchft_lighthouse_job_quorum_id Current quorum id per job "
       "namespace.\n"
    << "# TYPE torchft_lighthouse_job_quorum_id gauge\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_quorum_id{job=\"" << prom_escape(j.name)
      << "\"} " << j.quorum_id << "\n";
  m << "# HELP torchft_lighthouse_job_quorum_generation Quorum broadcasts "
       "per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_quorum_generation counter\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_quorum_generation{job=\""
      << prom_escape(j.name) << "\"} " << j.quorum_gen << "\n";
  m << "# HELP torchft_lighthouse_job_participants Replicas waiting in the "
       "next quorum per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_participants gauge\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_participants{job=\"" << prom_escape(j.name)
      << "\"} " << j.n_participants << "\n";
  m << "# HELP torchft_lighthouse_job_fleet_replicas Fleet-table rows per "
       "job namespace.\n"
    << "# TYPE torchft_lighthouse_job_fleet_replicas gauge\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_fleet_replicas{job=\"" << prom_escape(j.name)
      << "\"} " << j.n_fleet << "\n";
  m << "# HELP torchft_lighthouse_job_stragglers Flagged/sticky replicas "
       "per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_stragglers gauge\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_stragglers{job=\"" << prom_escape(j.name)
      << "\"} " << j.n_straggler << "\n";
  m << "# HELP torchft_lighthouse_job_anomalies_total Anomaly rise-edges "
       "per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_anomalies_total counter\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_anomalies_total{job=\"" << prom_escape(j.name)
      << "\"} " << j.aseq << "\n";
  // Time-accounting series. Cardinality stays bounded by construction:
  // goodput/SLO gauges are O(jobs); the badput family is O(jobs x the
  // CLOSED kind enum), never per-replica.
  m << "# HELP torchft_lighthouse_job_goodput_fraction Compute share of "
       "all accounted replica-seconds per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_goodput_fraction gauge\n";
  for (const auto& j : job_rows)
    if (j.has_acct)
      m << "torchft_lighthouse_job_goodput_fraction{job=\""
        << prom_escape(j.name) << "\"} " << j.goodput << "\n";
  m << "# HELP torchft_lighthouse_job_badput_seconds Accounted "
       "replica-seconds per badput kind per job namespace (closed enum).\n"
    << "# TYPE torchft_lighthouse_job_badput_seconds gauge\n";
  for (const auto& j : job_rows)
    if (j.has_acct)
      for (int i = 0; i < kNumBadputKinds; i++)
        m << "torchft_lighthouse_job_badput_seconds{job=\""
          << prom_escape(j.name) << "\",kind=\"" << kBadputKindNames[i]
          << "\"} " << j.badput[i] << "\n";
  m << "# HELP torchft_lighthouse_job_slo_burns_total SLO burn-rate rise "
       "edges per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_slo_burns_total counter\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_slo_burns_total{job=\"" << prom_escape(j.name)
      << "\"} " << j.slo_seq << "\n";
  m << "# HELP torchft_lighthouse_job_slo_burning Job currently burning "
       "its goodput error budget faster than the threshold (1) or not (0).\n"
    << "# TYPE torchft_lighthouse_job_slo_burning gauge\n";
  for (const auto& j : job_rows)
    m << "torchft_lighthouse_job_slo_burning{job=\"" << prom_escape(j.name)
      << "\"} " << (j.slo_burning ? 1 : 0) << "\n";
  m << "# HELP torchft_lighthouse_job_mtbf_seconds Mean time between "
       "hard-evidence faults per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_mtbf_seconds gauge\n";
  for (const auto& j : job_rows)
    if (j.mtbf_s >= 0.0)
      m << "torchft_lighthouse_job_mtbf_seconds{job=\"" << prom_escape(j.name)
        << "\"} " << j.mtbf_s << "\n";
  m << "# HELP torchft_lighthouse_job_ettr_seconds Mean evidence-to-"
       "training-resumption time per job namespace.\n"
    << "# TYPE torchft_lighthouse_job_ettr_seconds gauge\n";
  for (const auto& j : job_rows)
    if (j.ettr_s >= 0.0)
      m << "torchft_lighthouse_job_ettr_seconds{job=\"" << prom_escape(j.name)
        << "\"} " << j.ettr_s << "\n";
  // District (federation) series, present on a root lighthouse.
  m << "# HELP torchft_lighthouse_districts Districts reporting rollups.\n"
    << "# TYPE torchft_lighthouse_districts gauge\n"
    << "torchft_lighthouse_districts " << dist_rows.size() << "\n";
  m << "# HELP torchft_lighthouse_district_losses_total Districts that "
       "went silent past the heartbeat timeout (cumulative).\n"
    << "# TYPE torchft_lighthouse_district_losses_total counter\n"
    << "torchft_lighthouse_district_losses_total " << district_losses << "\n";
  if (!dist_rows.empty()) {
    m << "# HELP torchft_lighthouse_district_up District currently "
         "reporting (1) or lost (0).\n"
      << "# TYPE torchft_lighthouse_district_up gauge\n";
    for (const auto& d : dist_rows)
      m << "torchft_lighthouse_district_up{district=\"" << prom_escape(d.name)
        << "\"} " << (d.lost ? 0 : 1) << "\n";
    m << "# HELP torchft_lighthouse_district_epoch Max fencing epoch seen "
         "from each district.\n"
      << "# TYPE torchft_lighthouse_district_epoch gauge\n";
    for (const auto& d : dist_rows)
      m << "torchft_lighthouse_district_epoch{district=\""
        << prom_escape(d.name) << "\"} " << d.epoch << "\n";
    m << "# HELP torchft_lighthouse_district_failovers_total Epoch advances "
         "observed per district (its lighthouse failed over).\n"
      << "# TYPE torchft_lighthouse_district_failovers_total counter\n";
    for (const auto& d : dist_rows)
      m << "torchft_lighthouse_district_failovers_total{district=\""
        << prom_escape(d.name) << "\"} " << d.failovers << "\n";
    m << "# HELP torchft_lighthouse_district_stale_dropped_total Rollups "
         "fenced out per district (old primary after failover).\n"
      << "# TYPE torchft_lighthouse_district_stale_dropped_total counter\n";
    for (const auto& d : dist_rows)
      m << "torchft_lighthouse_district_stale_dropped_total{district=\""
        << prom_escape(d.name) << "\"} " << d.stale_dropped << "\n";
  }
  // Hot-path latency histograms: upper-bound percentile gauges per path
  // (log buckets, telemetry._hist_percentile semantics).
  struct Named {
    const char* name;
    const LatencyHist* h;
  };
  const Named hists[] = {
      {"heartbeat", &hist_heartbeat_},   {"quorum_compute", &hist_quorum_},
      {"anomaly_eval", &hist_anomaly_},  {"http", &hist_http_},
      {"fleet_snapshot", &hist_snapshot_},
  };
  m << "# HELP torchft_lighthouse_hotpath_p50_us Hot-path latency p50 "
       "(upper-bound log-bucket estimate, microseconds).\n"
    << "# TYPE torchft_lighthouse_hotpath_p50_us gauge\n"
    << "# HELP torchft_lighthouse_hotpath_p95_us Hot-path latency p95.\n"
    << "# TYPE torchft_lighthouse_hotpath_p95_us gauge\n"
    << "# HELP torchft_lighthouse_hotpath_count Hot-path samples observed.\n"
    << "# TYPE torchft_lighthouse_hotpath_count counter\n";
  for (const auto& nh : hists) {
    LatencyHist::Snap s = nh.h->snapshot();
    m << "torchft_lighthouse_hotpath_p50_us{path=\"" << nh.name << "\"} "
      << LatencyHist::percentile_us(s, 0.50) << "\n"
      << "torchft_lighthouse_hotpath_p95_us{path=\"" << nh.name << "\"} "
      << LatencyHist::percentile_us(s, 0.95) << "\n"
      << "torchft_lighthouse_hotpath_count{path=\"" << nh.name << "\"} "
      << s.count << "\n";
  }
  return m.str();
}

void Lighthouse::handle_http(int fd) {
  int64_t t0 = now_us_steady();
  std::string req = read_http_request(fd, 10000);
  std::string path = "/";
  std::string method;
  {
    size_t sp1 = req.find(' ');
    size_t sp2 = req.find(' ', sp1 + 1);
    if (sp1 != std::string::npos && sp2 != std::string::npos) {
      method = req.substr(0, sp1);
      path = req.substr(sp1 + 1, sp2 - sp1 - 1);
    }
  }
  // Query-string split: /fleet.json?job=<id> selects one namespace island
  // (only the "job" key is recognized; anything else is ignored).
  std::string query;
  {
    size_t qpos = path.find('?');
    if (qpos != std::string::npos) {
      query = path.substr(qpos + 1);
      path = path.substr(0, qpos);
    }
  }
  std::string q_job;
  {
    size_t pos = 0;
    while (pos < query.size()) {
      size_t amp = query.find('&', pos);
      std::string kv = query.substr(
          pos, amp == std::string::npos ? std::string::npos : amp - pos);
      if (kv.rfind("job=", 0) == 0) q_job = kv.substr(4);
      if (amp == std::string::npos) break;
      pos = amp + 1;
    }
  }
  // Side-effecting endpoints (kill / drain / drain_all) are POST-only:
  // a GET must never stop a replica — browsers prefetch URLs and
  // monitoring scrapers walk dashboard paths. The dashboard forms
  // declare method=post already.
  const bool side_effecting =
      path == "/drain_all" || path.rfind("/replica/", 0) == 0;
  if (side_effecting && method != "POST") {
    std::string body405 = "method not allowed (POST required)";
    std::ostringstream hdr;
    hdr << "HTTP/1.1 405 Method Not Allowed\r\nContent-Type: text/plain"
        << "\r\nAllow: POST\r\nContent-Length: " << body405.size()
        << "\r\nConnection: close\r\n\r\n";
    std::string out405 = hdr.str() + body405;
    write_all(fd, out405.data(), out405.size(), 10000);
    hist_http_.observe_us(now_us_steady() - t0);
    return;
  }
  std::string body;
  std::string ctype = "text/html";
  int code = 200;
  if (path == "/" || path == "/status") {
    body = render_status_html();
  } else if (path == "/status.json") {
    body = status_json().dump();
    ctype = "application/json";
  } else if (path == "/fleet.json") {
    // Pre-dumped cached snapshot: serving is a string copy, not an O(N)
    // JSON build under the job lock (the contention the fleet_load harness
    // measures). ?job=<id> selects that namespace; bare = composite.
    body = fleet_snapshot(q_job, now_ms())->body;
    ctype = "application/json";
  } else if (path == "/metrics") {
    body = render_metrics();
    ctype = "text/plain; version=0.0.4";
  } else if (path.rfind("/replica/", 0) == 0 && path.size() > 14 &&
             (path.compare(path.size() - 5, 5, "/kill") == 0 ||
              path.compare(path.size() - 6, 6, "/drain") == 0)) {
    bool is_kill = path.compare(path.size() - 5, 5, "/kill") == 0;
    size_t suffix = is_kill ? 5 : 6;
    std::string replica_id = path.substr(9, path.size() - 9 - suffix);
    Json kreq = Json::object();
    kreq["type"] = Json::of(is_kill ? "kill" : "drain");
    kreq["replica_id"] = Json::of(replica_id);
    if (!q_job.empty()) kreq["job"] = Json::of(q_job);
    Json kresp = handle_request(kreq, now_ms() + 5000);
    body = kresp.dump();
    ctype = "application/json";
    if (!kresp.get("ok").as_bool()) code = 404;
  } else if (path == "/drain_all") {
    Json dreq = Json::object();
    dreq["type"] = Json::of("drain_all");
    if (!q_job.empty()) dreq["job"] = Json::of(q_job);
    Json dresp = handle_request(dreq, now_ms() + 15000);
    body = dresp.dump();
    ctype = "application/json";
  } else {
    code = 404;
    body = "not found";
    ctype = "text/plain";
  }
  std::ostringstream hdr;
  hdr << "HTTP/1.1 " << code << (code == 200 ? " OK" : " Not Found")
      << "\r\nContent-Type: " << ctype
      << "\r\nContent-Length: " << body.size()
      << "\r\nConnection: close\r\n\r\n";
  std::string out = hdr.str() + body;
  write_all(fd, out.data(), out.size(), 10000);
  hist_http_.observe_us(now_us_steady() - t0);
}

}  // namespace tft
