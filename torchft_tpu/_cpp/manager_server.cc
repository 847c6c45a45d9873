#include "manager_server.hpp"

#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cstdio>
#include <cstdlib>

#include "chaos.hpp"
#include "net.hpp"

namespace tft {

ManagerServer::ManagerServer(ManagerOpts opts) : opts_(std::move(opts)) {
  if (opts_.bind_host.empty()) opts_.bind_host = "0.0.0.0";
  if (opts_.advertise_host.empty()) opts_.advertise_host = "127.0.0.1";
  // Parse the ordered lighthouse list once; the vector is read-only after
  // construction so both the heartbeat thread and quorum path can index it
  // with only the atomic active index.
  std::string rest = opts_.lighthouse_addr;
  while (!rest.empty()) {
    size_t comma = rest.find(',');
    std::string one = rest.substr(0, comma);
    size_t b = one.find_first_not_of(" \t");
    size_t e = one.find_last_not_of(" \t");
    if (b != std::string::npos) lh_addrs_.push_back(one.substr(b, e - b + 1));
    if (comma == std::string::npos) break;
    rest = rest.substr(comma + 1);
  }
  if (lh_addrs_.empty()) lh_addrs_.push_back(opts_.lighthouse_addr);
}

ManagerServer::~ManagerServer() { stop(); }

bool ManagerServer::start() {
  listen_fd_ = tcp_listen(opts_.bind_host, opts_.port);
  if (listen_fd_ < 0) return false;
  port_ = bound_port(listen_fd_);
  running_ = true;
  accept_thread_ = std::thread([this] { accept_loop(); });
  heartbeat_thread_ = std::thread([this] { heartbeat_loop(); });
  return true;
}

void ManagerServer::stop() {
  if (!running_.exchange(false)) return;
  cv_.notify_all();
  conns_.shutdown_all();  // interrupt in-flight frames so handlers drain fast
  // shutdown() unblocks the accept loop; close() + reset must wait until
  // the thread is joined — accept_loop reads listen_fd_ until then.
  if (listen_fd_ >= 0) shutdown(listen_fd_, SHUT_RDWR);
  if (accept_thread_.joinable()) accept_thread_.join();
  if (heartbeat_thread_.joinable()) heartbeat_thread_.join();
  if (listen_fd_ >= 0) {
    close(listen_fd_);
    listen_fd_ = -1;
  }
  conns_.wait_idle(10000);
}

void ManagerServer::accept_loop() {
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

void ManagerServer::heartbeat_loop() {
  // Pings EVERY lighthouse in the ordered list each round over persistent
  // connections (manager.rs:194-216, extended for HA): the active entry's
  // ack renews its lease; standbys receive the same heartbeats read-only so
  // their fleet/participant tables stay warm for takeover. When the active
  // entry's lease lapses (no ack for lighthouse_lease_ms) we fail over
  // deterministically to the next address down the list, with the shared
  // seeded-jitter backoff so a fleet of managers doesn't storm the standby
  // in lockstep.
  const size_t n = lh_addrs_.size();
  std::vector<std::string> hosts(n);
  std::vector<int> ports(n, -1);
  size_t n_ok = 0;
  for (size_t i = 0; i < n; i++) {
    if (split_host_port(lh_addrs_[i], &hosts[i], &ports[i])) {
      n_ok++;
    } else {
      ports[i] = -1;
      fprintf(stderr, "[manager %s] bad lighthouse addr '%s' (entry %zu)\n",
              opts_.replica_id.c_str(), lh_addrs_[i].c_str(), i);
    }
  }
  if (n_ok == 0) return;
  std::vector<int> fds(n, -1);
  // Per-address reconnect backoff: a dead standby must not stall every
  // round behind its connect timeout, and the active entry's connect budget
  // must stay well inside the lease so a down primary is detected in time.
  std::vector<int64_t> next_try_ms(n, 0);
  std::vector<uint64_t> fail_streak(n, 0);
  int64_t last_active_ok_ms = now_ms();
  uint64_t failover_streak = 0;  // consecutive failovers without any ack
  // Consecutive TRANSPORT failures (connect refused/reset — not a live
  // lighthouse saying no) on the active entry: hard evidence the process is
  // gone, consumed by the evidence failover below.
  uint64_t active_fail_streak = 0;
  // When the last heartbeat to the active entry was sent (0: none yet).
  int64_t last_send_us = 0;
  while (running_) {
    if (draining_) {
      // Graceful drain in progress: no more heartbeats (a fresh heartbeat
      // would make the lighthouse wait for us after we announced our leave).
      sleep_ms(opts_.heartbeat_interval_ms);
      continue;
    }
    const int active = lh_active_.load() % static_cast<int>(n);
    // Shared failover tail for both triggers (lease lapse / hard evidence):
    // advance down the list, record detection attribution for lh_failover
    // journaling, and queue a failure signal for the NEW active lighthouse.
    auto fail_over = [&](int kind, const char* label, int64_t detect_ms) {
      failover_streak += 1;
      int next = (active + 1) % static_cast<int>(n);
      lh_active_.store(next);
      lh_failovers_.fetch_add(1);
      lh_detect_ms_.store(detect_ms);
      lh_failover_kind_.store(kind);
      Json d = Json::object();
      d["detect_ms"] = Json::of(detect_ms);
      d["failed_addr"] = Json::of(lh_addrs_[active]);
      d["next_addr"] = Json::of(lh_addrs_[next]);
      queue_signal(kind == 2 ? "rpc_error" : "lease_expiry",
                   "lighthouse:" + lh_addrs_[active],
                   "manager:" + opts_.replica_id + ":hb_loop", std::move(d));
      last_active_ok_ms = now_ms();
      active_fail_streak = 0;
      fprintf(stderr,
              "[manager %s] lighthouse %s on %s (detect %lld ms): failing "
              "over to %s (failover #%lld)\n",
              opts_.replica_id.c_str(), label, lh_addrs_[active].c_str(),
              static_cast<long long>(detect_ms), lh_addrs_[next].c_str(),
              static_cast<long long>(lh_failovers_.load()));
      // Seeded full-jitter pause (shared PR-7 backoff) so the whole fleet
      // doesn't re-register against the standby in the same instant.
      double unit = chaos::backoff_unit(opts_.replica_id + "|lh_failover",
                                        failover_streak);
      sleep_ms(static_cast<int64_t>(unit * 500.0));
    };
    for (size_t i = 0; i < n && running_ && !draining_; i++) {
      if (ports[i] < 0) continue;
      const bool is_active = static_cast<int>(i) == active;
      int64_t now = now_ms();
      if (!is_active && fds[i] < 0 && now < next_try_ms[i]) continue;
      // Attribute heartbeat I/O to (ctrl, lighthouse-host, "heartbeat") for
      // the chaos plane: a stall@ctrl:match=heartbeat spec can delay THIS
      // replica's heartbeats (the fleet lane's straggler signal) without
      // touching quorum or data traffic.
      chaos::ScopedCtx chaos_ctx("ctrl", hosts[i], "heartbeat");
      if (fds[i] < 0) {
        // Connect budget: a third of the lease for the active entry (a dead
        // primary must be detected within the lease, not behind a 10 s
        // connect), a short probe for standbys.
        int64_t budget = is_active
                             ? std::max<int64_t>(
                                   50, std::min(opts_.lighthouse_lease_ms / 3,
                                                opts_.connect_timeout_ms))
                             : 250;
        fds[i] = tcp_connect(hosts[i], ports[i], budget);
      }
      bool acked = false;
      if (fds[i] >= 0) {
        Json req = Json::object();
        req["type"] = Json::of("heartbeat");
        req["replica_id"] = Json::of(opts_.replica_id);
        // Job namespace: routes this heartbeat to our job's isolated island
        // on a namespaced lighthouse; an old lighthouse ignores the key.
        req["job"] = Json::of(opts_.job);
        // Carry our address: lets the lighthouse drain_all reach us even if
        // we never managed to register a quorum (drain_all blind spot).
        req["address"] = Json::of(address());
        // Our nominal cadence: lets the lighthouse derive a deterministic
        // jitter threshold instead of guessing from arrival statistics.
        req["hb_interval_ms"] = Json::of(opts_.heartbeat_interval_ms);
        // The max quorum epoch we have accepted: the heartbeat stream is how
        // standbys learn the fleet's current owner (for a fenced takeover
        // epoch) and how a resurrected stale primary learns it has been
        // superseded (self-demotes).
        req["epoch"] = Json::of(lh_epoch_.load());
        // Max accepted quorum_id rides along so a takeover standby can
        // resume numbering strictly above the old primary's quorums.
        req["quorum_id"] = Json::of(lh_quorum_id_.load());
        req["lh_index"] = Json::of(static_cast<int64_t>(active));
        {
          // Piggyback the latest health digest (if the trainer pushed one).
          // Old lighthouses read only the keys they know, so this is free
          // to send unconditionally.
          std::lock_guard<std::mutex> lk(digest_mu_);
          if (has_digest_) req["digest"] = digest_;
        }
        // Piggyback queued failure signals on the ACTIVE entry (the island
        // that forms quorums is the one that must ingest evidence). The
        // outbox is only drained on ack, so a torn send re-delivers — the
        // lighthouse ring tolerates duplicates, losing evidence is worse.
        size_t attached = 0;
        if (is_active) {
          // How far we have read the island's signals: the ack then shows
          // every one past it, not the last alone.
          req["signal_seq"] = Json::of(lh_signal_seq_.load());
          std::lock_guard<std::mutex> lk(signal_mu_);
          if (!signal_outbox_.empty()) {
            Json arr = Json::array();
            for (const auto& s : signal_outbox_) arr.push(s);
            attached = signal_outbox_.size();
            req["signals"] = std::move(arr);
          }
        }
        // The sender's view of the liveness path: the gap from the
        // previous send to this one, and this round trip. A heartbeat the
        // lighthouse finds late is late by one or the other.
        const int64_t send_us = now_us_steady();
        int64_t gap_us = 0;
        if (is_active) {
          if (last_send_us > 0) gap_us = send_us - last_send_us;
          last_send_us = send_us;
        }
        Json resp;
        const bool answered = call_json(fds[i], req, &resp, 5000);
        const int64_t rtt_us = now_us_steady() - send_us;
        if (is_active) {
          std::lock_guard<std::mutex> lk(hb_mu_);
          hb_.rounds += 1;
          hb_.gap_max_us = std::max(hb_.gap_max_us, gap_us);
          hb_.rtt_max_us = std::max(hb_.rtt_max_us, rtt_us);
          if (gap_us > 3 * opts_.heartbeat_interval_ms * 1000) hb_.late += 1;
        }
        if (answered) {
          acked = resp.get("ok").as_bool();
          if (acked && is_active) {
            // Evidence cursor: the ack carries the island's failure-signal
            // seq + last signal; the trainer's watcher polls these via the
            // "evidence_status" RPC to react to peer death in ~one
            // heartbeat instead of a full collective timeout.
            int64_t sseq = resp.get("signal_seq").as_int(-1);
            if (sseq >= 0) {
              int64_t cur = lh_signal_seq_.load();
              while (sseq > cur &&
                     !lh_signal_seq_.compare_exchange_weak(cur, sseq)) {
              }
              std::lock_guard<std::mutex> lk(signal_mu_);
              if (resp.has("signal")) last_signal_ = resp.get("signal");
            }
            {
              // Every signal an ack shows, once, for the gate's journal
              // (``signals``: all past our cursor; an older lighthouse
              // sends the last one alone), and the lighthouse's word that
              // it had evicted this very group, beside this sender's
              // numbers for the heartbeat that brought it back.
              std::lock_guard<std::mutex> lk(hb_mu_);
              auto note = [&](const Json& sg) {
                int64_t q = sg.get("seq").as_int(0);
                if (!sg.is_object() || q <= seen_signal_seq_) return;
                seen_signal_seq_ = q;
                seen_signals_.push_back(sg);
                while (seen_signals_.size() > kAckRing)
                  seen_signals_.pop_front();
              };
              if (resp.get("signals").is_array()) {
                for (const auto& sg : resp.get("signals").arr) note(sg);
              } else if (resp.has("signal")) {
                note(resp.get("signal"));
              }
              if (resp.get("evicted").is_object()) {
                Json e = resp.get("evicted");
                e["sender_gap_ms"] = Json::of(gap_us / 1000.0);
                e["sender_rtt_ms"] = Json::of(rtt_us / 1000.0);
                evicted_.push_back(std::move(e));
                while (evicted_.size() > kAckRing) evicted_.pop_front();
              }
            }
            if (attached > 0) {
              std::lock_guard<std::mutex> lk(signal_mu_);
              for (size_t k = 0; k < attached && !signal_outbox_.empty(); k++)
                signal_outbox_.pop_front();
            }
          }
        } else {
          close(fds[i]);
          fds[i] = -1;
        }
      }
      if (acked) {
        fail_streak[i] = 0;
        next_try_ms[i] = 0;
        if (is_active) {
          last_active_ok_ms = now_ms();
          failover_streak = 0;
          active_fail_streak = 0;
        }
      } else if (fds[i] < 0) {
        if (is_active) active_fail_streak += 1;
        fail_streak[i] += 1;
        double unit = chaos::backoff_unit(
            opts_.replica_id + "|hb|" + lh_addrs_[i], fail_streak[i]);
        next_try_ms[i] =
            now_ms() + static_cast<int64_t>(unit * 2000.0);  // cap 2 s
      }
    }
    if (!draining_ && n > 1 && opts_.evidence_streak > 0 &&
        active_fail_streak >= static_cast<uint64_t>(opts_.evidence_streak)) {
      // Hard-evidence failover: N consecutive transport failures against
      // the active entry (connect refused/reset — the process is GONE, not
      // merely slow) fail over at heartbeat-cadence speed instead of
      // waiting out the rest of the lease.
      fail_over(2, "transport-dead (hard evidence)",
                now_ms() - last_active_ok_ms);
    } else if (!draining_ &&
               now_ms() - last_active_ok_ms > opts_.lighthouse_lease_ms) {
      // Lease lapsed: deterministic failover down the list (wrapping, so a
      // resurrected earlier entry can be re-adopted if everything later
      // also dies — it will take over with a freshly fenced epoch). The
      // soft-evidence fallback: covers hangs/partitions where connects
      // still land but acks never do.
      fail_over(1, "lease lapsed", now_ms() - last_active_ok_ms);
    }
    sleep_ms(opts_.heartbeat_interval_ms);
  }
  for (size_t i = 0; i < n; i++)
    if (fds[i] >= 0) close(fds[i]);
}

void ManagerServer::handle_conn(int fd) {
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
      // Server-side chaos: delay or drop this RPC (see lighthouse.cc).
      if (!chaos::server_rpc(req.get("type").as_str())) break;
      int64_t timeout = req.get("timeout_ms").as_int(60000);
      resp = handle_request(req, now_ms() + timeout);
      // Echo the caller's trace id so both planes of a step share one id
      // (the Python Manager mints it; responses carry it for correlation).
      if (req.has("trace_id")) resp["trace_id"] = req.get("trace_id");
    }
    if (!send_frame(fd, resp.dump(), 30000)) break;
  }
  close(fd);
}

Json ManagerServer::handle_request(const Json& req, int64_t deadline_ms) {
  const std::string type = req.get("type").as_str();
  Json resp = Json::object();
  if (type == "quorum") return quorum_rpc(req, deadline_ms);
  if (type == "should_commit") return should_commit_rpc(req, deadline_ms);
  if (type == "checkpoint_metadata") {
    int64_t rank = req.get("rank").as_int();
    std::lock_guard<std::mutex> lk(mu_);
    auto it = checkpoint_metadata_.find(rank);
    if (it == checkpoint_metadata_.end()) {
      resp["ok"] = Json::of(false);
      resp["error"] =
          Json::of("no checkpoint metadata for rank " + std::to_string(rank));
    } else {
      resp["ok"] = Json::of(true);
      resp["checkpoint_metadata"] = Json::of(it->second);
    }
    return resp;
  }
  if (type == "kill") {
    fprintf(stderr, "[manager %s] kill requested: %s\n",
            opts_.replica_id.c_str(), req.get("msg").as_str().c_str());
    fflush(stderr);
    // _exit, not exit: static destructors would try to join live server
    // threads and delay the death the caller is counting on
    // (reference kills the whole process too, manager.rs:481-486).
    _exit(1);
  }
  if (type == "leave") {
    bool sent = leave("graceful drain",
                      std::max<int64_t>(500, deadline_ms - now_ms()));
    resp["ok"] = Json::of(true);
    resp["sent"] = Json::of(sent);
    return resp;
  }
  if (type == "request_drain") {
    // Only sets the flag — the trainer sees it on its next quorum
    // response and drains at a step boundary it knows is safe.
    drain_requested_ = true;
    fprintf(stderr, "[manager %s] drain requested (operator)\n",
            opts_.replica_id.c_str());
    resp["ok"] = Json::of(true);
    return resp;
  }
  if (type == "drain_status") {
    // Out-of-band read of the flag: the piggyback on quorum responses
    // only delivers on quorum SUCCESS, so a trainer whose peers drained
    // a beat earlier (its quorums now fail) polls this after a failed
    // step instead of retrying quorums it can never win.
    resp["ok"] = Json::of(true);
    resp["drain_requested"] = Json::of(drain_requested_.load());
    return resp;
  }
  if (type == "set_digest") {
    // Cache the trainer's latest health digest; the heartbeat loop
    // attaches it to every lighthouse ping until replaced. Advisory
    // telemetry only — no validation beyond "is an object" (the
    // lighthouse tolerates anything), and dropping it is never an error.
    {
      std::lock_guard<std::mutex> lk(digest_mu_);
      digest_ = req.get("digest");
      has_digest_ = digest_.is_object();
    }
    resp["ok"] = Json::of(true);
    return resp;
  }
  if (type == "signal") {
    // Trainer/runner-observed failure evidence: queue for heartbeat
    // piggyback to the active lighthouse. Source must be one of
    // telemetry.SIGNAL_SOURCES; the lighthouse drops unknown sources, so
    // here we only refuse the obviously malformed (empty) case.
    const std::string source = req.get("source").as_str();
    if (source.empty()) {
      resp["ok"] = Json::of(false);
      resp["error"] = Json::of("signal requires a non-empty 'source'");
      return resp;
    }
    queue_signal(source, req.get("replica_id").as_str(opts_.replica_id),
                 req.get("site").as_str(""), req.get("detail"));
    resp["ok"] = Json::of(true);
    return resp;
  }
  if (type == "evidence_status") {
    // Lock-cheap poll for the trainer's evidence watcher: the island-wide
    // failure-signal cursor plus the last signal seen in an active ack. A
    // seq rise with a hard source on a PEER is grounds to abort a wedged
    // collective now instead of waiting out its timeout.
    resp["ok"] = Json::of(true);
    resp["signal_seq"] = Json::of(lh_signal_seq_.load());
    {
      std::lock_guard<std::mutex> lk(signal_mu_);
      resp["signal"] = last_signal_;
      resp["outbox"] = Json::of(static_cast<int64_t>(signal_outbox_.size()));
      resp["outbox_dropped"] = Json::of(signal_outbox_dropped_);
    }
    resp["lh"] = lh_info_json();
    {
      // The sender's view of its own heartbeats since the last read that
      // reset it (the commit gate's, once a step), and what the acks said
      // since: evictions of this group, signals about any.
      const bool reset = req.get("reset").as_bool();
      std::lock_guard<std::mutex> lk(hb_mu_);
      Json hb = Json::object();
      hb["rounds"] = Json::of(hb_.rounds);
      hb["gap_max_ms"] = Json::of(hb_.gap_max_us / 1000.0);
      hb["rtt_max_ms"] = Json::of(hb_.rtt_max_us / 1000.0);
      hb["late"] = Json::of(hb_.late);
      hb["interval_ms"] = Json::of(opts_.heartbeat_interval_ms);
      resp["hb"] = std::move(hb);
      Json ev = Json::array();
      for (const auto& e : evicted_) ev.push(e);
      resp["evicted"] = std::move(ev);
      Json sg = Json::array();
      for (const auto& g : seen_signals_) sg.push(g);
      resp["signals"] = std::move(sg);
      if (reset) {
        hb_ = HbStats();
        evicted_.clear();
        seen_signals_.clear();
      }
    }
    return resp;
  }
  if (type == "info") {
    resp["ok"] = Json::of(true);
    resp["replica_id"] = Json::of(opts_.replica_id);
    resp["address"] = Json::of(address());
    resp["world_size"] = Json::of(opts_.world_size);
    resp["lh"] = lh_info_json();
    return resp;
  }
  resp["ok"] = Json::of(false);
  resp["error"] = Json::of("unknown request type '" + type + "'");
  return resp;
}

Json ManagerServer::lh_info_json() const {
  Json lh = Json::object();
  int idx = lh_active_.load() % static_cast<int>(lh_addrs_.size());
  lh["active"] = Json::of(static_cast<int64_t>(idx));
  lh["addr"] = Json::of(lh_addrs_[idx]);
  lh["failovers"] = Json::of(lh_failovers_.load());
  lh["epoch"] = Json::of(lh_epoch_.load());
  lh["stale_rejected"] = Json::of(lh_stale_rejected_.load());
  lh["unreachable_retries"] = Json::of(lh_unreachable_retries_.load());
  lh["job"] = Json::of(opts_.job);
  // Detection attribution of the LAST failover: how long the dead active
  // entry went unacked before we moved ("detect_ms"), and which trigger won
  // — hard transport evidence or the lease timeout fallback.
  lh["detect_ms"] = Json::of(lh_detect_ms_.load());
  int k = lh_failover_kind_.load();
  lh["evidence"] = Json::of(k == 2 ? "evidence" : (k == 1 ? "lease" : ""));
  lh["signal_seq"] = Json::of(lh_signal_seq_.load());
  return lh;
}

void ManagerServer::queue_signal(const std::string& source,
                                 const std::string& subject,
                                 const std::string& site, Json detail) {
  Json s = Json::object();
  s["source"] = Json::of(source);
  s["replica_id"] = Json::of(subject.empty() ? opts_.replica_id : subject);
  s["site"] =
      Json::of(site.empty() ? "manager:" + opts_.replica_id : site);
  s["ts_ms"] = Json::of(now_ms());
  if (!detail.is_null()) s["detail"] = std::move(detail);
  std::lock_guard<std::mutex> lk(signal_mu_);
  signal_outbox_.push_back(std::move(s));
  // Bounded like the lighthouse rings: drop the OLDEST — fresh evidence is
  // what unblocks survivors.
  while (signal_outbox_.size() > 16) {
    signal_outbox_.pop_front();
    signal_outbox_dropped_ += 1;
  }
}

std::optional<Quorum> ManagerServer::lighthouse_quorum(
    const QuorumMember& me, int64_t deadline_ms, const std::string& trace_id,
    std::string* error) {
  // Retry with per-attempt deadline slices (manager.rs:250-306): each attempt
  // gets total/(retries+1). A connect-level failure (lighthouse unreachable —
  // a transient blip or a dead primary mid-failover) is absorbed with the
  // shared seeded full-jitter backoff rather than failing the step; a live
  // lighthouse's explicit refusal is a different error. The active target is
  // re-read every attempt: the heartbeat thread's lease may fail over
  // mid-retry and the next attempt must follow it down the list.
  int64_t attempts = std::max<int64_t>(1, opts_.quorum_retries + 1);
  int64_t total = std::max<int64_t>(1, deadline_ms - now_ms());
  int64_t slice = std::max<int64_t>(100, total / attempts);
  int64_t unreachable = 0;
  std::string last_addr;
  std::string denied;
  // Follow-the-failover retries: when the heartbeat thread fails over WHILE
  // an attempt is burning its connect budget against the dead target, the
  // next try against the new active is free (not counted against the
  // budgeted attempts, no backoff). Bounded so a flapping list can't loop.
  int64_t free_retries = static_cast<int64_t>(lh_addrs_.size()) * 2;

  for (int64_t a = 0; a < attempts && running_; a++) {
    const int active_at_start = lh_active_.load();
    const std::string addr =
        lh_addrs_[active_at_start % static_cast<int>(lh_addrs_.size())];
    last_addr = addr;
    std::string host;
    int port = 0;
    int fd = -1;
    bool transport_fail = false;
    int64_t attempt_deadline = std::min(deadline_ms, now_ms() + slice);
    if (split_host_port(addr, &host, &port)) {
      // Per-attempt connect budget. With standbys configured, cap it near
      // the lease: a SIGKILLed primary must not eat the whole slice (the
      // full quorum timeout when quorum_retries=0) when the heartbeat
      // thread will have failed over at evidence speed long before — the
      // free retry below follows it. Single-lighthouse deployments keep
      // the full budget (nowhere else to go).
      int64_t cbudget = std::min<int64_t>(slice, opts_.connect_timeout_ms);
      if (lh_addrs_.size() > 1)
        cbudget = std::min(
            cbudget, std::max<int64_t>(250, opts_.lighthouse_lease_ms));
      fd = tcp_connect_retry(host, port, cbudget);
    }
    if (fd < 0) {
      transport_fail = true;
      unreachable += 1;
      lh_unreachable_retries_.fetch_add(1);
    } else {
      Json req = Json::object();
      req["type"] = Json::of("quorum");
      req["job"] = Json::of(opts_.job);
      req["timeout_ms"] = Json::of(attempt_deadline - now_ms());
      req["requester"] = me.to_json();
      if (!trace_id.empty()) req["trace_id"] = Json::of(trace_id);
      Json resp;
      bool ok = call_json(fd, req, &resp, attempt_deadline - now_ms());
      close(fd);
      if (!ok) {
        // Torn mid-RPC (connection reset / partition): same bucket as
        // unreachable — retry, don't latch.
        transport_fail = true;
        unreachable += 1;
        lh_unreachable_retries_.fetch_add(1);
      } else if (!resp.get("ok").as_bool()) {
        denied = resp.get("error").as_str("quorum denied");
      } else {
        Quorum q = Quorum::from_json(resp.get("quorum"));
        int64_t fence = lh_epoch_.load();
        if (q.epoch < fence) {
          // Split-brain fence: a resurrected stale primary can answer
          // quorums, but its epoch is below what the fleet has already
          // accepted from the takeover. Never deliver it to the trainer.
          lh_stale_rejected_.fetch_add(1);
          denied = "stale quorum fenced: epoch " + std::to_string(q.epoch) +
                   " < " + std::to_string(fence) + " (from " + addr + ")";
          fprintf(stderr, "[manager %s] %s\n", opts_.replica_id.c_str(),
                  denied.c_str());
        } else {
          while (q.epoch > fence &&
                 !lh_epoch_.compare_exchange_weak(fence, q.epoch)) {
          }
          int64_t qid = lh_quorum_id_.load();
          while (q.quorum_id > qid &&
                 !lh_quorum_id_.compare_exchange_weak(qid, q.quorum_id)) {
          }
          return q;
        }
      }
    }
    if (now_ms() >= deadline_ms) break;
    if (transport_fail && free_retries > 0 &&
        lh_active_.load() != active_at_start) {
      // The heartbeat thread failed over mid-attempt: follow it now.
      free_retries -= 1;
      a -= 1;
      continue;
    }
    if (a + 1 < attempts) {
      // Seeded full-jitter between attempts (chaos.backoff_jitter's C++
      // twin, keyed per replica so retries across the fleet decorrelate).
      double unit = chaos::backoff_unit(
          opts_.replica_id + "|lh_quorum|" + addr, static_cast<uint64_t>(a + 1));
      int64_t cap = std::min<int64_t>(1000, deadline_ms - now_ms());
      sleep_ms(std::max<int64_t>(10, static_cast<int64_t>(unit * cap)));
    }
  }
  if (error) {
    if (!denied.empty()) {
      *error = "lighthouse quorum denied: " + denied;
    } else {
      *error = "lighthouse unreachable after " + std::to_string(unreachable) +
               " attempts (last: " + last_addr + ")";
    }
  }
  return std::nullopt;
}

bool ManagerServer::leave(const std::string& reason, int64_t budget_ms) {
  // Stop our lighthouse heartbeats FIRST so a racing ping can't resurrect
  // the entry, then tell the lighthouse to drop us (its tombstone covers
  // the one heartbeat that may already be in flight). A repeat call (e.g.
  // a second local rank's leave RPC, or the RPC racing the parent-death
  // watchdog) short-circuits only once the lighthouse has CONFIRMED —
  // otherwise it retries the send, so a transient connect failure on the
  // first attempt can't latch a false "sent" while survivors stall out
  // the heartbeat expiry. Concurrent duplicate sends are harmless (the
  // lighthouse leave is idempotent).
  draining_ = true;
  if (left_sent_) return true;
  bool sent = false;
  // One budget for the WHOLE attempt (connect + RPC, across however many
  // list entries we manage to try): the parent-death watchdog passes a
  // small budget so an unreachable lighthouse (whole-machine / partition
  // loss, where the leave is moot anyway) can't hold the orphaned binary
  // alive — a slow connect must not let the RPC wait spend the full budget
  // again on top. Starting at the ACTIVE entry (and walking down the list
  // on failure) covers a drain racing a failover: the leave must land on
  // whichever lighthouse will form the survivors' next quorum.
  int64_t deadline = now_ms() + budget_ms;
  const size_t n = lh_addrs_.size();
  const int start = lh_active_.load() % static_cast<int>(n);
  for (size_t k = 0; k < n && !sent; k++) {
    const std::string& addr = lh_addrs_[(start + k) % n];
    std::string host;
    int port = 0;
    if (!split_host_port(addr, &host, &port)) continue;
    int64_t remaining = deadline - now_ms();
    if (remaining < 100 && k > 0) break;
    int fd = tcp_connect(
        host, port,
        std::max<int64_t>(100, std::min<int64_t>(
                                   remaining, opts_.connect_timeout_ms)));
    if (fd >= 0) {
      remaining = std::max<int64_t>(200, deadline - now_ms());
      Json lv = Json::object();
      lv["type"] = Json::of("leave");
      lv["replica_id"] = Json::of(opts_.replica_id);
      lv["job"] = Json::of(opts_.job);
      // Why we left: "trainer died" (the parent-death watchdog leaving on
      // the corpse's behalf) is failure evidence the lighthouse turns into
      // a proc_death signal; planned drains stay signal-free.
      lv["reason"] = Json::of(reason);
      Json lresp;
      sent = call_json(fd, lv, &lresp, remaining) && lresp.get("ok").as_bool();
      close(fd);
    }
  }
  if (sent) left_sent_ = true;
  fprintf(stderr, "[manager %s] leaving quorum (%s, sent=%d)\n",
          opts_.replica_id.c_str(), reason.c_str(), sent ? 1 : 0);
  return sent;
}

Json ManagerServer::quorum_rpc(const Json& req, int64_t deadline_ms) {
  int64_t rank = req.get("group_rank").as_int();
  bool init_sync = req.get("init_sync").as_bool(true);
  const std::string trace_id = req.get("trace_id").as_str();
  Json resp = Json::object();
  if (draining_) {
    // A post-leave quorum registration would clear our lighthouse tombstone
    // while our heartbeats stay stopped — recreating the heartbeat-expiry
    // stall the drain exists to remove. All ranks and clients share this
    // layer, so the refusal is enforced here, not just in the Python
    // Manager's _drained flag (which is per-object).
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of(
        "manager is draining (leave() called); relaunch the process to rejoin");
    return resp;
  }
  if (rank < 0 || rank >= opts_.world_size) {
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of("group_rank " + std::to_string(rank) +
                             " out of range [0, " +
                             std::to_string(opts_.world_size) + ")");
    return resp;
  }

  std::unique_lock<std::mutex> lk(mu_);
  RankInfo info;
  info.step = req.get("step").as_int();
  info.shrink_only = req.get("shrink_only").as_bool();
  info.commit_failures = req.get("commit_failures").as_int();
  participants_[rank] = info;
  checkpoint_metadata_[rank] = req.get("checkpoint_metadata").as_str();
  int64_t my_round = quorum_round_;

  if (static_cast<int64_t>(participants_.size()) >= opts_.world_size &&
      !quorum_inflight_) {
    // Last local rank in: this thread performs the lighthouse round
    // (manager.rs:332-402).
    quorum_inflight_ = true;
    QuorumMember me;
    me.replica_id = opts_.replica_id;
    me.address = address();
    me.store_address = opts_.store_address;
    me.world_size = opts_.world_size;
    for (const auto& kv : participants_) {
      me.step = std::max(me.step, kv.second.step);
      me.shrink_only = me.shrink_only || kv.second.shrink_only;
      me.commit_failures = std::max(me.commit_failures, kv.second.commit_failures);
    }
    lk.unlock();
    std::string lherr;
    auto q = lighthouse_quorum(me, deadline_ms, trace_id, &lherr);
    lk.lock();
    if (q) {
      current_quorum_ = q;
      quorum_error_.clear();
    } else {
      current_quorum_.reset();
      quorum_error_ = lherr.empty()
                          ? "lighthouse quorum failed (timeout or unreachable)"
                          : lherr;
    }
    quorum_round_ += 1;
    participants_.clear();
    quorum_inflight_ = false;
    lk.unlock();
    cv_.notify_all();
    lk.lock();
  } else {
    while (running_ && quorum_round_ == my_round) {
      if (cv_.wait_until(lk, std::chrono::system_clock::time_point(
                                 std::chrono::milliseconds(deadline_ms))) ==
              std::cv_status::timeout &&
          now_ms() >= deadline_ms) {
        participants_.erase(rank);
        resp["ok"] = Json::of(false);
        resp["error"] = Json::of("timed out waiting for local ranks / quorum");
        resp["timeout"] = Json::of(true);
        return resp;
      }
    }
  }

  if (!current_quorum_) {
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of(
        quorum_error_.empty() ? "no quorum delivered" : quorum_error_);
    resp["lh"] = lh_info_json();
    return resp;
  }
  std::string err;
  auto result = compute_quorum_results(rank, opts_.replica_id, *current_quorum_,
                                       init_sync, &err);
  if (!result) {
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of(err);
    return resp;
  }
  resp["ok"] = Json::of(true);
  resp["result"] = result->to_json();
  resp["quorum"] = current_quorum_->to_json();
  resp["drain_requested"] = Json::of(drain_requested_.load());
  // HA telemetry: epoch/failover/retry counters so the Python Manager can
  // journal lh_epoch / lh_failover / rpc_retry transitions per step.
  resp["lh"] = lh_info_json();
  return resp;
}

Json ManagerServer::should_commit_rpc(const Json& req, int64_t deadline_ms) {
  int64_t rank = req.get("group_rank").as_int();
  bool vote = req.get("should_commit").as_bool();
  Json resp = Json::object();
  if (rank < 0 || rank >= opts_.world_size) {
    resp["ok"] = Json::of(false);
    resp["error"] = Json::of("group_rank " + std::to_string(rank) +
                             " out of range [0, " +
                             std::to_string(opts_.world_size) + ")");
    return resp;
  }

  std::unique_lock<std::mutex> lk(mu_);
  commit_votes_[rank] = vote;
  int64_t my_round = commit_round_;
  if (static_cast<int64_t>(commit_votes_.size()) >= opts_.world_size) {
    // Barrier complete: commit iff no rank voted false (manager.rs:423-479).
    bool all = true;
    for (const auto& kv : commit_votes_) all = all && kv.second;
    commit_result_ = all;
    commit_votes_.clear();
    commit_round_ += 1;
    lk.unlock();
    cv_.notify_all();
    lk.lock();
  } else {
    while (running_ && commit_round_ == my_round) {
      if (cv_.wait_until(lk, std::chrono::system_clock::time_point(
                                 std::chrono::milliseconds(deadline_ms))) ==
              std::cv_status::timeout &&
          now_ms() >= deadline_ms) {
        commit_votes_.erase(rank);
        resp["ok"] = Json::of(false);
        resp["error"] = Json::of("timed out waiting for should_commit barrier");
        resp["timeout"] = Json::of(true);
        return resp;
      }
    }
  }
  resp["ok"] = Json::of(true);
  resp["should_commit"] = Json::of(commit_result_);
  return resp;
}

}  // namespace tft
