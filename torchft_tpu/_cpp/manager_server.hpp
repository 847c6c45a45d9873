// ManagerServer: per-replica-group coordinator for torchft-tpu.
//
// Capability parity with the reference's src/manager.rs:68-487: local ranks
// of one replica group check in via a Quorum request; when the last of
// `world_size` ranks arrives the server forwards a single QuorumMember to the
// Lighthouse (with retry/reconnect, manager.rs:250-306), broadcasts the
// delivered quorum to all waiting ranks, and each rank's reply carries its
// recovery plan from compute_quorum_results. Also: a ShouldCommit barrier
// (commit iff zero ranks voted false, manager.rs:423-479), CheckpointMetadata
// lookup for recovering peers (manager.rs:404-421), a Kill request that exits
// the process (manager.rs:481-486), and a heartbeat loop pinging the
// Lighthouse (manager.rs:194-216).
//
// Requests (length-prefixed JSON frames):
//   {"type":"quorum","group_rank":r,"step":s,"checkpoint_metadata":m,
//    "shrink_only":b,"init_sync":b,"commit_failures":n,"timeout_ms":N}
//   {"type":"should_commit","group_rank":r,"step":s,"should_commit":b,
//    "timeout_ms":N}
//   {"type":"checkpoint_metadata","rank":r}
//   {"type":"kill","msg":...}
//   {"type":"leave"}   (graceful drain: stop heartbeats, tell the lighthouse)
//   {"type":"request_drain"}   (operator asks the TRAINER to drain: sets a
//       flag piggybacked on every quorum response as "drain_requested";
//       the trainer drains at its next step boundary via "leave")
//   {"type":"set_digest","digest":{...}}   (trainer hands over its latest
//       StepDigest wire dict; the heartbeat loop attaches it to every
//       lighthouse heartbeat until replaced — the live fleet-health feed)
//   {"type":"info"}
#pragma once

#include <atomic>
#include <condition_variable>
#include <deque>
#include <map>
#include <mutex>
#include <optional>
#include <string>
#include <thread>
#include <vector>

#include "conn_tracker.hpp"
#include "quorum.hpp"

namespace tft {

struct ManagerOpts {
  std::string replica_id;
  // Ordered comma list "host:port[,host:port...]": first entry is the
  // primary lighthouse, the rest are warm standbys. Managers heartbeat every
  // entry (standbys stay warm, read-only) and fail over down the list when
  // the active entry's lease lapses.
  std::string lighthouse_addr;
  std::string advertise_host;      // host other processes can reach us at
  int port = 0;                    // 0 = ephemeral
  std::string bind_host;           // default 0.0.0.0
  std::string store_address;       // rendezvous store this group advertises
  int64_t world_size = 1;          // local ranks in this replica group
  int64_t heartbeat_interval_ms = 100;
  int64_t connect_timeout_ms = 10000;
  int64_t quorum_retries = 0;
  // Lease on the active lighthouse: no successful heartbeat ack for this
  // long => deterministically advance to the next address in the list
  // (TORCHFT_LH_LEASE_MS / --lh-lease-ms).
  int64_t lighthouse_lease_ms = 3000;
  // Job namespace this replica group belongs to (TORCHFT_JOB / --job).
  // Stamped on every heartbeat/quorum/leave frame; the lighthouse keeps a
  // fully isolated control-plane island per job. "default" matches the
  // pre-namespace wire behavior (the key is still sent; an old lighthouse
  // ignores unknown keys).
  std::string job = "default";
  // Failure-evidence failover: this many CONSECUTIVE transport failures on
  // the ACTIVE lighthouse entry (connect refused/reset — hard evidence the
  // process is gone) fail over immediately instead of waiting out the full
  // lease. 0 disables: lease lapse stays the only failover trigger
  // (TORCHFT_MGR_EVIDENCE_STREAK / --evidence-streak).
  int64_t evidence_streak = 3;
};

class ManagerServer {
 public:
  explicit ManagerServer(ManagerOpts opts);
  ~ManagerServer();

  bool start();
  void stop();

  int port() const { return port_; }
  std::string address() const {
    return opts_.advertise_host + ":" + std::to_string(port_);
  }

  // Graceful drain: stop heartbeating, tell the lighthouse to drop this
  // replica. Idempotent; returns whether the lighthouse confirmed. Called
  // by the "leave" RPC (trainer-initiated drain) and by the parent-death
  // watchdog (trainer crashed — leave on its behalf so survivors shrink at
  // watchdog-poll speed instead of heartbeat expiry).
  bool leave(const std::string& reason, int64_t budget_ms = 5000);

 private:
  void accept_loop();
  void heartbeat_loop();
  void handle_conn(int fd);
  Json handle_request(const Json& req, int64_t deadline_ms);
  Json quorum_rpc(const Json& req, int64_t deadline_ms);
  Json should_commit_rpc(const Json& req, int64_t deadline_ms);
  // Calls the lighthouse Quorum RPC with retries; returns nullopt on failure
  // with a human-readable reason in *error that distinguishes "lighthouse
  // unreachable" (connect-level, retried with the shared seeded-jitter
  // backoff) from "quorum denied" (a live lighthouse said no) from "stale
  // quorum fenced" (epoch below the fence). `trace_id` (may be empty) is
  // forwarded so the lighthouse leg of the step's control-plane path carries
  // the same correlation id.
  std::optional<Quorum> lighthouse_quorum(const QuorumMember& me,
                                          int64_t deadline_ms,
                                          const std::string& trace_id,
                                          std::string* error);
  // HA counters snapshot attached to quorum/info responses so the Python
  // Manager can journal lh_failover / lh_epoch / rpc_retry events.
  Json lh_info_json() const;
  // Enqueue a failure signal for heartbeat piggyback (bounded outbox; oldest
  // dropped). Used by the "signal" RPC and by manager-side evidence (lease
  // lapse / transport-fail failover observations).
  void queue_signal(const std::string& source, const std::string& subject,
                    const std::string& site, Json detail);

  ManagerOpts opts_;
  // ---- lighthouse HA state ----
  // Parsed ordered address list (set in the constructor, then read-only).
  std::vector<std::string> lh_addrs_;
  std::atomic<int> lh_active_{0};       // index of the current active target
  std::atomic<int64_t> lh_failovers_{0};
  // Max quorum epoch ever accepted: the split-brain fence. Any delivered
  // quorum with a lower epoch (a resurrected stale primary) is rejected.
  std::atomic<int64_t> lh_epoch_{0};
  // Max quorum_id ever accepted; heartbeat-carried so a takeover standby
  // resumes numbering above it (strict quorum-id monotonicity w/o a
  // lighthouse-to-lighthouse channel).
  std::atomic<int64_t> lh_quorum_id_{0};
  std::atomic<int64_t> lh_stale_rejected_{0};
  // Connect-level quorum retries absorbed before latching quorum_error_.
  std::atomic<int64_t> lh_unreachable_retries_{0};
  // ---- failure-evidence state ----
  // Max failure-signal seq seen in ACTIVE-entry heartbeat ACKs: the local
  // evidence cursor the trainer's watcher polls via "evidence_status".
  std::atomic<int64_t> lh_signal_seq_{0};
  // Detection latency of the last failover: ms from the last successful
  // active ack to the failover decision (-1 before any failover), plus
  // which trigger won the race (0 none, 1 lease lapse, 2 hard evidence).
  std::atomic<int64_t> lh_detect_ms_{-1};
  std::atomic<int> lh_failover_kind_{0};
  // Last signal object from an active ACK (signal_mu_), and the bounded
  // outbox of trainer-emitted signals awaiting heartbeat piggyback.
  std::mutex signal_mu_;
  Json last_signal_ = Json::null();
  std::deque<Json> signal_outbox_;
  int64_t signal_outbox_dropped_ = 0;
  // ---- the liveness path as this sender saw it (hb_mu_) ----
  // Since the last evidence_status read that asked to reset: heartbeat
  // rounds to the active lighthouse, the largest gap between two sends and
  // the largest round trip of one (steady clock), and the gaps over three
  // intervals. With them, what the acks said of this group (an eviction
  // the lighthouse has just taken back) and every signal seen in an ack.
  struct HbStats {
    int64_t rounds = 0;
    int64_t gap_max_us = 0;
    int64_t rtt_max_us = 0;
    int64_t late = 0;
  };
  std::mutex hb_mu_;
  HbStats hb_;
  std::deque<Json> evicted_;       // capped at kAckRing
  std::deque<Json> seen_signals_;  // capped at kAckRing, distinct by seq
  int64_t seen_signal_seq_ = 0;
  static constexpr size_t kAckRing = 16;
  int port_ = 0;
  int listen_fd_ = -1;
  std::atomic<bool> running_{false};
  // Set by a "leave" request: the heartbeat loop stops pinging the lighthouse
  // so the drained replica ages out instead of looking healthy forever.
  std::atomic<bool> draining_{false};
  // Whether the lighthouse actually confirmed our leave: a repeat leave()
  // call retries the send if the first attempt failed (a false "sent" would
  // hide that survivors are stuck waiting out the heartbeat expiry).
  std::atomic<bool> left_sent_{false};
  // Operator-requested drain (dashboard/RPC): surfaced to the trainer on
  // every quorum response; the trainer owns the actual drain (finish the
  // step, leave, exit) because only it knows a safe boundary.
  std::atomic<bool> drain_requested_{false};
  std::thread accept_thread_;
  std::thread heartbeat_thread_;
  ConnTracker conns_;

  // Latest StepDigest handed over via set_digest, attached verbatim to every
  // heartbeat frame. Own mutex: the heartbeat loop must never contend with a
  // quorum round holding mu_ across a lighthouse RPC.
  std::mutex digest_mu_;
  Json digest_ = Json::null();
  bool has_digest_ = false;

  std::mutex mu_;
  std::condition_variable cv_;

  // Quorum round state (reset after each broadcast).
  struct RankInfo {
    int64_t step = 0;
    bool shrink_only = false;
    int64_t commit_failures = 0;
  };
  std::map<int64_t, RankInfo> participants_;
  std::map<int64_t, std::string> checkpoint_metadata_;  // persists across rounds
  std::optional<Quorum> current_quorum_;
  int64_t quorum_round_ = 0;
  bool quorum_inflight_ = false;
  std::string quorum_error_;

  // should_commit round state.
  std::map<int64_t, bool> commit_votes_;
  bool commit_result_ = false;
  int64_t commit_round_ = 0;
};

}  // namespace tft
