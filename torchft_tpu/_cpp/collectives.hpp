// Native DCN data plane: a C++ pipelined collective engine.
//
// The fault-tolerant replica axis moves every gradient byte host-side over
// DCN TCP. ProcessGroupSocket drives that ring from Python — one connection
// per peer, one chunk in flight, the interpreter on the copy path — which
// caps throughput far below the NIC. This engine is the native data plane
// behind ProcessGroupNative (process_group.py): the same framed-TCP net
// layer underneath (net.hpp), but
//
//  - multi-connection striping: n_streams sockets per peer, each carrying a
//    contiguous slice of every transfer, so one TCP window / one core never
//    bounds a transfer;
//  - chunked ring allreduce with pipelined receive-reduce: each stripe
//    reader consumes the wire in pipeline_bytes sub-blocks and reduces
//    sub-block k into the destination while k+1 is still in flight (the
//    kernel socket buffer is the second half of the double buffer);
//  - optional int8 blockwise wire compression (allreduce_q8) that
//    round-trips through the exact quantize_blockwise layout of
//    torchft_tpu/collectives.py + ops/quantization.py: BLOCK=512 values per
//    float32 scale, scale = absmax/127 (1.0 for all-zero blocks),
//    round-half-even, clip to ±127 — quantize once, alltoall owner chunks,
//    fp32 local reduce, requantize, allgather, so every rank decodes the
//    same bytes and results stay cross-replica bitwise identical;
//  - ragged allgather / broadcast carrying an opaque metadata string per
//    payload (the Python side stores dtype/shape there; the engine only
//    relays it).
//
// Numerics: the fp32/f64/i32/i64 ring uses np.array_split chunking and the
// same per-element accumulation (dst = dst OP incoming, left-neighbor
// contributions in ring order) as ProcessGroupSocket._ring_allreduce_flat,
// so uncompressed results are bitwise identical to the socket backend.
//
// Exposed to Python through the C ABI at the bottom (ctypes over
// libtftcollectives.so, see torchft_tpu/_native.py). One collective at a
// time per engine (the Python PG already serializes ops on one executor
// thread); abort() may be called concurrently from any thread and shuts
// down every socket so blocked calls fail fast instead of timing out.
//
// Degraded-network survival (per-peer link policy + stripe failover):
//
//  - Every peer link carries a LinkPolicy (class local|dcn|wan, per-attempt
//    connect clamp, optional per-leg I/O budget, stripe count, wire
//    preference), pushed from TORCHFT_LINKS before connect_mesh. Policies
//    must be configured symmetrically: rank A's policy for B and B's for A
//    agree on stripe count, or the mesh handshake fails.
//  - A striped transfer no longer aborts the collective on one socket
//    error: the stripes of one (peer, direction) leg group report into the
//    group, and the last leg to finish re-assigns every failed stripe's
//    byte range to the lowest-indexed surviving stripe (both ends compute
//    the identical handoff from the shared alive mask + split logic, so no
//    extra control round-trip is needed). Dead stripes are excluded from
//    later transfers via a per-peer alive bitmask; only when ALL stripes to
//    a peer are dead (or the deadline is already spent) does the engine
//    fall back to the abort/poison path. Failovers are recorded in a ring
//    exposed by fr_snapshot ("failovers") and journaled by the Python PG as
//    stripe_failover events.
//  - Failover relies on SYMMETRIC detection (a reset/shutdown propagates to
//    the peer mid-leg, so both ends fail the same stripe in the same leg
//    group). An asymmetric failure — receiver errors while the sender's
//    bytes all fit in the kernel socket buffer — leaves the ends with
//    different masks and falls back to deadline -> abort -> heal.
//  - A background janitor reconnects dead stripes (seeded jittered backoff,
//    original connect direction) and stages the new socket on both ends
//    with an activation collective number negotiated in the rejoin
//    handshake, so both ends swap the fd in before the same collective.
#pragma once

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <deque>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <queue>
#include <string>
#include <thread>
#include <vector>

namespace tft {

// Codes shared with the ctypes bindings (_native.py). Keep in sync.
enum : int32_t {
  TFT_DT_F32 = 0,
  TFT_DT_F64 = 1,
  TFT_DT_I32 = 2,
  TFT_DT_I64 = 3,
};
enum : int32_t {
  TFT_OP_SUM = 0,
  TFT_OP_MAX = 1,
  TFT_OP_MIN = 2,
};

// ---------------------------------------------------------------------------
// Flight recorder: a fixed-size ring of per-collective records written on the
// hot path with no allocation and no locks. One collective runs at a time per
// engine, so a record has a single writer for its scalar fields; the striped
// transfer jobs claim disjoint lane slots via one fetch_add each. Snapshots
// (fr_snapshot) read the ring concurrently: records whose seq no longer
// matches their slot (wrapped mid-read) are skipped, in-flight records are
// reported as such — a torn lane costs a garbage number in a diagnostic
// record, never memory unsafety.
// ---------------------------------------------------------------------------

constexpr int kFrTagLen = 64;
constexpr int kFrCauseLen = 96;
constexpr int kFrMaxLanes = 32;  // (peer, stripe, direction) legs per record
constexpr int kFrMaxSteps = 16;  // ring-step completion stamps per record

// One striped transfer leg. Written by exactly one pool job.
struct FlightLane {
  int16_t peer = -1;
  int8_t stripe = 0;
  int8_t dir = 0;          // 0 = send, 1 = recv, 2 = recv-reduce
  uint32_t spins = 0;      // MSG_DONTWAIT misses (EAGAIN -> poll) in this leg
  uint64_t bytes = 0;
  uint64_t t0_ns = 0;      // CLOCK_REALTIME, aligns with journal time.time()
  uint64_t t1_ns = 0;
  uint64_t reduce_ns = 0;  // recv-reduce only: ns folding blocks into dst
};

struct FlightRec {
  std::atomic<uint64_t> seq{0};    // 1-based; 0 = slot never written
  int32_t op = 0;                  // 0 allreduce 1 q8 2 allgather 3 broadcast
  int32_t dtype = -1;
  int32_t red_op = -1;
  std::atomic<int32_t> status{0};  // 0 in-flight 1 ok 2 error 3 timeout 4 abort
  uint64_t bytes = 0;
  uint64_t t_start_ns = 0;
  uint64_t t_end_ns = 0;
  char tag[kFrTagLen] = {0};       // trace tag in force when the op started
  char cause[kFrCauseLen] = {0};   // abort/poison/error cause on failure
  std::atomic<uint32_t> nsteps{0};
  uint64_t step_ns[kFrMaxSteps] = {0};  // per-chunk ring-step completion
  std::atomic<uint32_t> lane_n{0};      // lanes claimed (may exceed kFrMaxLanes)
  FlightLane lanes[kFrMaxLanes];
};

// Cumulative per-peer link counters, always on (plain atomic adds): feed the
// Prometheus exporter's per-peer bandwidth gauges even when the ring is off.
struct PeerCounters {
  std::atomic<uint64_t> tx_bytes{0};
  std::atomic<uint64_t> rx_bytes{0};
  std::atomic<uint64_t> tx_busy_ns{0};  // summed over stripe jobs (overlapping)
  std::atomic<uint64_t> rx_busy_ns{0};
  std::atomic<uint64_t> spins{0};
};

// Per-peer link policy, pushed from TORCHFT_LINKS (knobs.py) before
// connect_mesh. Both ends of a link must agree on n_streams (the mesh
// handshake validates stripe indices against the local policy). `q8` is
// consumed by the Python wire-format selection, not the engine; it rides
// here so one registry owns the whole policy.
struct LinkPolicy {
  std::string cls = "dcn";     // local | dcn | wan (chaos link:<class> scope)
  int64_t connect_ms = 5000;   // per-attempt clamp inside tcp_connect_retry
  int64_t io_ms = 0;           // per-leg I/O budget; 0 = collective deadline.
                               // A stripe stalled past this fails early enough
                               // for the leg group to hand its range over.
  int n_streams = 0;           // stripes on this link; 0 = engine default
  bool q8 = false;             // prefer int8 wire compression on this link
};

// Fixed-size worker pool for concurrent striped send/recv jobs. Sized so
// every stripe to and from every peer can progress at once — a smaller pool
// could fill up with blocked senders and deadlock the mesh.
class TaskPool {
 public:
  explicit TaskPool(int n_threads);
  ~TaskPool();
  void submit(std::function<void()> fn);

 private:
  void worker();
  std::vector<std::thread> threads_;
  std::queue<std::function<void()>> queue_;
  std::mutex mu_;
  std::condition_variable cv_;
  bool stop_ = false;
};

class CollectiveEngine {
 public:
  // fr_capacity: flight-recorder ring slots; 0 disables recording (the
  // per-peer counters stay on either way).
  CollectiveEngine(int n_streams, int64_t pipeline_bytes, int fr_capacity = 0);
  ~CollectiveEngine();

  // Binds the data-plane listener. Returns the port, or -1 (last_error set).
  int listen(const std::string& host);
  // Full-mesh rendezvous: connect n_streams sockets to every lower rank,
  // accept n_streams from every higher rank. peers[i] is rank i's
  // "host:port" (peers[rank] ignored). False on failure.
  bool connect_mesh(int rank, int world, const std::vector<std::string>& peers,
                    int64_t timeout_ms);
  // Shuts down every socket (listener included). Safe from any thread while
  // a collective is blocked; that collective returns an error promptly.
  void abort(const std::string& why);

  // Installs the link policy for `peer` (-1 = default for unlisted peers).
  // Must be called before connect_mesh; ignored afterwards (the janitor
  // reads policies without a lock once the mesh is up).
  void set_link_policy(int peer, const LinkPolicy& pol);

  // In-place ring allreduce over `count` elements of `dtype`. AVG is the
  // caller's job (SUM then divide), matching ProcessGroupSocket.
  bool allreduce(void* data, uint64_t count, int32_t dtype, int32_t op,
                 int64_t timeout_ms);
  // In-place int8-compressed fp32 SUM allreduce (blockwise layout above).
  bool allreduce_q8(float* data, uint64_t count, int64_t timeout_ms);
  // Ragged allgather of (meta, payload); results land in slots [0, world).
  bool allgather(const std::string& meta, const void* data, uint64_t nbytes,
                 int64_t timeout_ms);
  // Broadcast from root; non-root ranks find (meta, payload) in slot `root`.
  bool broadcast(const std::string& meta, const void* data, uint64_t nbytes,
                 int root, int64_t timeout_ms);

  const std::string& result_meta(int slot) const { return results_[slot].first; }
  const std::string& result_payload(int slot) const {
    return results_[slot].second;
  }
  int world() const { return world_; }
  int port() const { return port_; }
  uint64_t bytes_tx() const { return bytes_tx_.load(); }
  uint64_t bytes_rx() const { return bytes_rx_.load(); }
  std::string last_error() const;

  // -- flight recorder ----------------------------------------------------
  // Tag stamped onto every subsequent record (trace id + collective tag,
  // e.g. "q3.s17|c4"). Callable between collectives from any thread.
  void set_trace(const std::string& tag);
  // Highest record seq allocated so far (0 if recording is off/idle).
  uint64_t fr_seq() const { return fr_seq_.load(); }
  // Records evicted by ring wrap since creation.
  uint64_t fr_dropped() const { return fr_dropped_.load(); }
  // JSON snapshot of records with seq > since_seq plus cumulative counters.
  // Safe to call from any thread while a collective is in flight.
  std::string fr_snapshot(uint64_t since_seq) const;

 private:
  struct Waiter;
  struct LegGroup;

  void set_error(const std::string& msg);
  bool fail(const std::string& msg);  // set_error + return false
  void close_all();

  // Effective policy / stripe count for a peer (clamped to the 32-bit alive
  // mask; both ends must agree — symmetric TORCHFT_LINKS configuration).
  LinkPolicy link_policy(int peer) const;
  int stripes_for(int peer) const;
  // Lowest-indexed live stripe to `peer` (header/metadata traffic), or -1.
  int first_alive(int peer) const;

  // Enqueue striped transfer jobs against `peer`; the stripes of one call
  // form a leg group that reports ONE completion into *w — individual
  // stripe failures are handled inside the group (handoff to a surviving
  // stripe) before the group resolves. `esize` keeps stripe boundaries on
  // element boundaries (both ends must pass the same esize or the slices
  // would interleave mid-element). `rec` (nullable) collects per-stripe
  // flight-recorder lanes.
  void send_stripes(int peer, const char* data, uint64_t nbytes,
                    uint64_t esize, int64_t deadline_ms, Waiter* w,
                    FlightRec* rec = nullptr);
  void recv_stripes(int peer, char* data, uint64_t nbytes, uint64_t esize,
                    int64_t deadline_ms, Waiter* w, FlightRec* rec = nullptr);
  // Striped receive that reduces into dst in pipeline_bytes sub-blocks
  // (dst[i] = dst[i] OP incoming[i]) instead of storing raw bytes.
  void recv_reduce_stripes(int peer, void* dst, uint64_t count, int32_t dtype,
                           int32_t op, int64_t deadline_ms, Waiter* w,
                           FlightRec* rec = nullptr);

  // Partitions [0, units) over the live stripes of g->peer and submits one
  // pool job per leg; the group resolves g->w exactly once (leg_epilogue).
  void launch_group(std::shared_ptr<LegGroup> g, uint64_t units);
  // One stripe leg: transfer, flight-recorder lane, group bookkeeping.
  void run_leg(std::shared_ptr<LegGroup> g, size_t li);
  // Runs on the pool thread of the LAST stripe job of a group to finish:
  // re-assigns every failed stripe's byte range to survivors (or fails the
  // group), then resolves the group's Waiter slot exactly once.
  void leg_epilogue(std::shared_ptr<LegGroup> g);
  // Re-runs failed leg `li` in full over surviving stripe `to` (16-byte
  // {magic, stripe, ulen} header so both ends can detect disagreement).
  bool handoff_leg(LegGroup& g, size_t li, int to);
  // One rejoin dial for a dead stripe (janitor). Stages the socket with the
  // activation number the acceptor picked. False = retry next sweep.
  bool try_rejoin(int peer, int stripe);
  // Records one handoff in the failover ring (fr_snapshot "failovers").
  void record_failover(int peer, int stripe, int to_stripe, int dir,
                       uint64_t moved_bytes, const char* tag);

  // Collective entry: bumps op_seq_ and installs janitor-staged rejoin
  // sockets whose negotiated activation number has arrived (both ends
  // install before the same collective, so stripe partitions agree).
  void begin_op();
  void janitor_loop();   // connector side: redial dead stripes to lower ranks
  void acceptor_loop();  // acceptor side: absorb rejoin dials from higher ranks

  template <typename T>
  bool ring_allreduce_t(T* data, uint64_t count, int32_t dtype, int32_t op,
                        int64_t deadline_ms, FlightRec* rec);

  bool allreduce_q8_inner(float* data, uint64_t count, int64_t timeout_ms,
                          FlightRec* rec);
  bool allgather_inner(const std::string& meta, const void* data,
                       uint64_t nbytes, int64_t timeout_ms, FlightRec* rec);
  bool broadcast_inner(const std::string& meta, const void* data,
                       uint64_t nbytes, int root, int64_t timeout_ms,
                       FlightRec* rec);

  // Flight-recorder plumbing (all no-ops when recording is off / rec null).
  FlightRec* fr_begin(int32_t op_code, int32_t dtype, int32_t red_op,
                      uint64_t bytes);
  void fr_end(FlightRec* rec, bool ok);
  void fr_step(FlightRec* rec);  // stamp the next ring-step completion
  // Completion of one stripe job: updates the per-peer counters and, when
  // recording, claims a lane on `rec`.
  void fr_job(FlightRec* rec, int peer, int stripe, int dir, uint64_t bytes,
              uint64_t t0_ns, uint64_t spins_before, uint64_t reduce_ns);

  int n_streams_;
  int64_t pipeline_bytes_;
  int rank_ = -1;
  int world_ = 0;
  int listen_fd_ = -1;
  int port_ = -1;
  std::vector<std::vector<int>> peer_fds_;  // [peer][stripe]; self empty
  std::unique_ptr<TaskPool> pool_;

  // -- link policy / stripe health ----------------------------------------
  LinkPolicy default_policy_;
  std::map<int, LinkPolicy> link_policies_;  // frozen once connect_mesh runs
  std::vector<std::string> peer_addrs_;      // "host:port" per rank (janitor)
  // Bit s set = stripe s to that peer is usable. Cleared by leg groups on
  // symmetric failure detection, restored by the rejoin janitor. 32 bits
  // bounds stripes per link at 32 (ctor clamps).
  std::unique_ptr<std::atomic<uint32_t>[]> alive_mask_;
  // alive_mask_ snapshot frozen at begin_op: the partition mask every group
  // launched during one collective uses, so mid-op leg deaths (observed at
  // different times on the two ends) cannot desynchronize the byte ranges.
  // Written in begin_op (under reconn_mu_) and read by launch_group /
  // first_alive on the same caller thread that ran begin_op.
  std::vector<uint32_t> op_mask_;
  // Per-(peer, stripe) throughput EWMA in GiB/s, updated per leg (fr_job).
  mutable std::mutex health_mu_;
  std::vector<std::vector<double>> stripe_gibs_;

  // -- failover ring (fr_snapshot "failovers") ----------------------------
  struct FailoverEvent {
    int64_t seq;
    int16_t peer;
    int8_t stripe;     // stripe whose range moved (or rejoined)
    int8_t to_stripe;  // surviving carrier; -1 for a rejoin event
    int8_t dir;        // 0 send 1 recv 2 recv-reduce 3 rejoin
    uint64_t bytes;
    uint64_t t_ns;
    char tag[kFrTagLen];
  };
  mutable std::mutex fo_mu_;
  std::deque<FailoverEvent> failovers_;  // capped; Python drains by seq
  int64_t fo_seq_ = 0;

  // -- rejoin janitor -----------------------------------------------------
  // Lock order: reconn_mu_ is a leaf (never held across I/O or other locks).
  std::mutex reconn_mu_;
  uint64_t op_seq_ = 0;  // collectives started; rejoin activation unit
  struct Staged {
    int peer;
    int stripe;
    int fd;
    uint64_t activate_at;  // install when op_seq_ reaches this
  };
  std::vector<Staged> staged_;
  // fds replaced by a rejoin: already shut down, kept open until the
  // destructor so a stripe job blocked on one fails instead of touching a
  // recycled descriptor (same lifetime rule as peer_fds_).
  std::vector<int> retired_fds_;
  std::thread janitor_;
  std::thread acceptor_;
  std::atomic<bool> stopping_{false};
  std::vector<std::pair<std::string, std::string>> results_;  // meta, payload
  std::atomic<bool> aborted_{false};
  std::atomic<uint64_t> bytes_tx_{0};
  std::atomic<uint64_t> bytes_rx_{0};
  mutable std::mutex err_mu_;
  std::string last_error_;

  // Flight recorder state. The ring is a raw array (not std::vector) because
  // FlightRec holds atomics and is neither copyable nor movable.
  int fr_cap_ = 0;
  std::unique_ptr<FlightRec[]> fr_ring_;
  std::atomic<uint64_t> fr_seq_{0};
  std::atomic<uint64_t> fr_dropped_{0};
  std::atomic<uint64_t> spin_total_{0};
  std::unique_ptr<PeerCounters[]> peer_counters_;  // sized world_ at connect
  // Serializes ring-record field mutation (fr_begin/end/step/job) against
  // fr_snapshot. The per-record seq/status/nsteps/lane_n atomics stay for
  // wrap detection and slot claiming; the mutex covers the plain fields a
  // snapshot would otherwise read torn. Held for ns — collective jobs spend
  // their time in socket I/O, not here.
  mutable std::mutex fr_mu_;
  mutable std::mutex trace_mu_;
  char trace_tag_[kFrTagLen] = {0};
};

}  // namespace tft

// ---------------------------------------------------------------------------
// C ABI for the ctypes bindings (torchft_tpu/_native.py). Return codes:
// 0 = ok, 1 = error (see tft_coll_last_error), 2 = timeout.
// ---------------------------------------------------------------------------
extern "C" {
// fr_capacity: flight-recorder ring slots (0 = recording off).
void* tft_coll_create(int32_t n_streams, int64_t pipeline_bytes,
                      int32_t fr_capacity);
void tft_coll_destroy(void* h);
int32_t tft_coll_listen(void* h, const char* host);  // port or -1
// peers_json: JSON array of "host:port", one per rank (self ignored).
int32_t tft_coll_connect(void* h, int32_t rank, int32_t world,
                         const char* peers_json, int64_t timeout_ms);
void tft_coll_abort(void* h, const char* why);
// Link policy for `peer` (-1 = default). cls: "local"|"dcn"|"wan".
// n_streams 0 = engine default; q8 nonzero = prefer int8 wire. Call before
// tft_coll_connect; ignored afterwards.
void tft_coll_set_link(void* h, int32_t peer, const char* cls,
                       int64_t connect_ms, int64_t io_ms, int32_t n_streams,
                       int32_t q8);
int32_t tft_coll_allreduce(void* h, void* data, uint64_t count, int32_t dtype,
                           int32_t op, int64_t timeout_ms);
int32_t tft_coll_allreduce_q8(void* h, float* data, uint64_t count,
                              int64_t timeout_ms);
int32_t tft_coll_allgather(void* h, const char* meta, const void* data,
                           uint64_t nbytes, int64_t timeout_ms);
int32_t tft_coll_broadcast(void* h, const char* meta, const void* data,
                           uint64_t nbytes, int32_t root, int64_t timeout_ms);
int64_t tft_coll_result_meta_len(void* h, int32_t slot);
int32_t tft_coll_result_meta(void* h, int32_t slot, char* out, int64_t cap);
int64_t tft_coll_result_size(void* h, int32_t slot);
int32_t tft_coll_result_copy(void* h, int32_t slot, void* out, int64_t cap);
uint64_t tft_coll_bytes_tx(void* h);
uint64_t tft_coll_bytes_rx(void* h);
// Copies the last error into out (NUL-terminated, truncated to cap).
void tft_coll_last_error(void* h, char* out, int64_t cap);
// Tag stamped onto subsequent flight records (trace id + collective tag).
void tft_coll_set_trace(void* h, const char* tag);
// Highest flight-record seq allocated so far.
uint64_t tft_coll_fr_seq(void* h);
// JSON snapshot of flight records with seq > since_seq plus engine counters.
// Returns the full serialized length (excluding NUL); writes up to cap-1
// bytes plus a NUL when cap > 0 — callers re-call with a larger buffer when
// the return value >= cap. Safe concurrently with an in-flight collective.
int64_t tft_coll_fr_snapshot(void* h, uint64_t since_seq, char* out,
                             int64_t cap);
// The int8 blockwise codec alone, no engine: blocks [b0, b1) of the Python
// wire turn's chunk, one pass a block. qs[p] / ss[p] are peer p's payload and
// scales of the WHOLE chunk (b1 blocks or more each); the fp32 sum over the
// peers, in that order, is written to acc (whole chunk, fp32) where acc is
// given, and requantized into q_out / s_out (whole chunk) where q_out is
// given. Bit for bit what collectives.py computes in numpy.
void tft_q8_reduce_blocks(const int8_t* const* qs, const float* const* ss,
                          int32_t n_peers, uint64_t b0, uint64_t b1,
                          float* acc, int8_t* q_out, float* s_out);
}
