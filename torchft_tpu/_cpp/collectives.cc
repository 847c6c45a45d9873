#include "collectives.hpp"

#include <math.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cstdio>

#include "chaos.hpp"
#include "net.hpp"

namespace tft {

namespace {

// Matches _net.set_buffer_sizes (Python side): 4 MiB socket buffers so a
// single DCN stream can keep a large window in flight.
constexpr int kSockBuf = 16 * 1024 * 1024;

void set_data_plane_opts(int fd) {
  setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &kSockBuf, sizeof(kSockBuf));
  setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &kSockBuf, sizeof(kSockBuf));
}

// ---------------------------------------------------------------------------
// Blockwise int8 quantization, numerically identical to
// torchft_tpu/collectives.py quantize_blockwise / dequantize_blockwise
// (bits=8): BLOCK=512 values per float32 scale, scale = absmax/127 (1.0 for
// all-zero blocks), round-half-even, clip to ±127, zero-padded tail block.
// All arithmetic stays in fp32 with the same operation order as the numpy
// path, so quantized wire bytes and reduced results agree bit-for-bit with
// the Python codec.
// ---------------------------------------------------------------------------

constexpr uint64_t kQBlock = 512;

void q8_quantize(const float* x, uint64_t n, uint64_t blocks, int8_t* q,
                 float* scales) {
  for (uint64_t b = 0; b < blocks; ++b) {
    const uint64_t lo = b * kQBlock;
    float absmax = 0.f;
    for (uint64_t j = 0; j < kQBlock; ++j) {
      const uint64_t idx = lo + j;
      const float v = idx < n ? x[idx] : 0.f;
      const float a = fabsf(v);
      if (a > absmax) absmax = a;
    }
    float s = absmax / 127.0f;
    if (absmax == 0.f) s = 1.0f;
    scales[b] = s;
    for (uint64_t j = 0; j < kQBlock; ++j) {
      const uint64_t idx = lo + j;
      const float v = idx < n ? x[idx] : 0.f;
      float t = nearbyintf(v / s);  // FE_TONEAREST = ties-to-even = np.rint
      if (t > 127.f) t = 127.f;
      if (t < -127.f) t = -127.f;
      q[lo + j] = static_cast<int8_t>(t);
    }
  }
}

// acc[i] += (float)q[i] * scale[block], same two fp32 roundings as the numpy
// dequantize-then-accumulate (mat *= scales; acc += mat).
void q8_accumulate(float* acc, const int8_t* q, const float* scales,
                   uint64_t blocks) {
  for (uint64_t b = 0; b < blocks; ++b) {
    const float s = scales[b];
    const uint64_t lo = b * kQBlock;
    for (uint64_t j = 0; j < kQBlock; ++j) {
      const float t = static_cast<float>(q[lo + j]) * s;
      acc[lo + j] += t;
    }
  }
}

// One pass a block of the Python wire turn's host arithmetic
// (collectives.py `_dequantize_sum` then `_quantize_into`, bits=8): the fp32
// sum over `n_peers` of float(q) * scale in the order given, the first peer
// written and not added, then (where `q_out` is given) absmax, scale,
// divide, round-half-even, clip, store. The sum lives in `sum`, which is a
// block of the caller's `acc` where the caller wants the sum and a block on
// the stack where it wants the requantized bytes only.
//
// Every operation is the numpy path's, in its order and in fp32, so payload
// bytes, scales and sums agree with it bit for bit (the Makefile keeps the
// compiler from contracting a multiply and an add into an FMA). Written so
// that the loops vectorise under strict IEEE semantics, since 484 MB of fp32
// a rank and step pass through here in mistral-ft4:
// * absmax over the values' bit patterns with the sign cleared, as integers:
//   for floats that is the order of their magnitudes, a NaN is the largest
//   (np.max hands a NaN on, too), and an integer max may be reordered where
//   a float max may not;
// * round by adding and taking away 1.5 * 2^23: |t| is 127 and a rounding
//   at most (the scale is the block's own absmax / 127), so the sum lies in
//   [2^23, 2^24), whose floats are the integers: the add rounds to the
//   nearest even one, as np.rint does, and the subtraction is exact. -0.3
//   comes out +0.0 where rint gives -0.0: the same byte. The clip is on the
//   integer, which float compares under trapping math would keep scalar.
// * scale == 0 -> 1 is tested on the QUOTIENT, as numpy does: a denormal
//   absmax whose 127th underflows takes scale 1 as well.
void q8_reduce_block(const int8_t* const* qs, const float* const* ss,
                     int32_t n_peers, uint64_t b, float* __restrict sum,
                     int8_t* __restrict q_out, float* s_out) {
  const uint64_t lo = b * kQBlock;
  {
    const int8_t* __restrict q = qs[0] + lo;
    const float s = ss[0][b];
    for (uint64_t j = 0; j < kQBlock; ++j)
      sum[j] = static_cast<float>(q[j]) * s;
  }
  for (int32_t p = 1; p < n_peers; ++p) {
    const int8_t* __restrict q = qs[p] + lo;
    const float s = ss[p][b];
    for (uint64_t j = 0; j < kQBlock; ++j) {
      const float t = static_cast<float>(q[j]) * s;
      sum[j] += t;
    }
  }
  if (q_out == nullptr) return;
  uint32_t top = 0;
  for (uint64_t j = 0; j < kQBlock; ++j) {
    uint32_t bits;
    memcpy(&bits, &sum[j], sizeof(bits));
    bits &= 0x7fffffffu;
    top = bits > top ? bits : top;
  }
  float absmax;
  memcpy(&absmax, &top, sizeof(absmax));
  float s = absmax / 127.0f;
  if (s == 0.f) s = 1.0f;
  s_out[b] = s;
  constexpr float kRound = 12582912.0f;  // 1.5 * 2^23
  int8_t* __restrict q = q_out + lo;
  for (uint64_t j = 0; j < kQBlock; ++j) {
    const float t = (sum[j] / s + kRound) - kRound;
    int32_t i = static_cast<int32_t>(t);
    i = i > 127 ? 127 : i;
    i = i < -127 ? -127 : i;
    q[j] = static_cast<int8_t>(i);
  }
}

template <typename T>
void reduce_into(T* dst, const T* src, uint64_t n, int32_t op) {
  if (op == TFT_OP_SUM) {
    for (uint64_t i = 0; i < n; ++i) dst[i] += src[i];
  } else if (op == TFT_OP_MAX) {
    for (uint64_t i = 0; i < n; ++i)
      dst[i] = dst[i] > src[i] ? dst[i] : src[i];
  } else {
    for (uint64_t i = 0; i < n; ++i)
      dst[i] = dst[i] < src[i] ? dst[i] : src[i];
  }
}

uint64_t dtype_size(int32_t dtype) {
  switch (dtype) {
    case TFT_DT_F32:
    case TFT_DT_I32:
      return 4;
    case TFT_DT_F64:
    case TFT_DT_I64:
      return 8;
  }
  return 0;
}

// np.array_split semantics over `n` units across `parts`: the first n%parts
// chunks get one extra unit. Identical to ProcessGroupSocket's chunking, so
// the uncompressed ring reduces the exact same slices.
uint64_t split_size(uint64_t n, int parts, int i) {
  return n / parts + (static_cast<uint64_t>(i) < n % parts ? 1 : 0);
}
uint64_t split_off(uint64_t n, int parts, int i) {
  const uint64_t base = n / parts;
  const uint64_t rem = n % parts;
  const uint64_t extra =
      std::min<uint64_t>(static_cast<uint64_t>(i), rem);
  return base * static_cast<uint64_t>(i) + extra;
}

}  // namespace

// ---------------------------------------------------------------------------
// TaskPool
// ---------------------------------------------------------------------------

TaskPool::TaskPool(int n_threads) {
  threads_.reserve(n_threads);
  for (int i = 0; i < n_threads; ++i)
    threads_.emplace_back([this] { worker(); });
}

TaskPool::~TaskPool() {
  {
    std::lock_guard<std::mutex> lk(mu_);
    stop_ = true;
  }
  cv_.notify_all();
  for (auto& t : threads_) t.join();
}

void TaskPool::submit(std::function<void()> fn) {
  {
    std::lock_guard<std::mutex> lk(mu_);
    queue_.push(std::move(fn));
  }
  cv_.notify_one();
}

void TaskPool::worker() {
  while (true) {
    std::function<void()> fn;
    {
      std::unique_lock<std::mutex> lk(mu_);
      cv_.wait(lk, [this] { return stop_ || !queue_.empty(); });
      // Drain remaining jobs even when stopping: queued jobs carry Waiter
      // pointers someone may still be blocked on; with the sockets shut
      // down they fail fast rather than hang.
      if (queue_.empty()) return;
      fn = std::move(queue_.front());
      queue_.pop();
    }
    fn();
  }
}

// ---------------------------------------------------------------------------
// Waiter: completion barrier for a batch of striped transfer jobs.
// ---------------------------------------------------------------------------

struct CollectiveEngine::Waiter {
  std::mutex mu;
  std::condition_variable cv;
  int pending = 0;
  bool ok = true;
  bool timed_out = false;
  std::string err;

  void add(int n) {
    std::lock_guard<std::mutex> lk(mu);
    pending += n;
  }
  void done(bool job_ok, bool job_timeout, const char* what) {
    std::lock_guard<std::mutex> lk(mu);
    if (!job_ok && ok) {
      ok = false;
      timed_out = job_timeout;
      err = what;
    }
    if (--pending == 0) cv.notify_all();
  }
  bool wait_all() {
    std::unique_lock<std::mutex> lk(mu);
    cv.wait(lk, [this] { return pending == 0; });
    return ok;
  }
};

// ---------------------------------------------------------------------------
// CollectiveEngine
// ---------------------------------------------------------------------------

CollectiveEngine::CollectiveEngine(int n_streams, int64_t pipeline_bytes,
                                   int fr_capacity)
    // 32 stripes bounds the per-peer alive bitmask (failover bookkeeping).
    : n_streams_(std::min(32, std::max(1, n_streams))),
      pipeline_bytes_(std::max<int64_t>(64 * 1024, pipeline_bytes)),
      fr_cap_(std::max(0, fr_capacity)) {
  if (fr_cap_ > 0) fr_ring_ = std::make_unique<FlightRec[]>(fr_cap_);
}

CollectiveEngine::~CollectiveEngine() {
  stopping_.store(true);
  abort("engine destroyed");
  if (janitor_.joinable()) janitor_.join();
  if (acceptor_.joinable()) acceptor_.join();
  pool_.reset();  // joins workers; queued jobs fail fast on shut-down fds
  close_all();
}

void CollectiveEngine::set_link_policy(int peer, const LinkPolicy& pol) {
  // Frozen once connect_mesh ran: the janitor and leg jobs read policies
  // without a lock.
  if (world_ != 0) return;
  LinkPolicy p = pol;
  if (p.n_streams > 32) p.n_streams = 32;
  if (p.connect_ms <= 0) p.connect_ms = 5000;
  if (peer < 0)
    default_policy_ = p;
  else
    link_policies_[peer] = p;
}

LinkPolicy CollectiveEngine::link_policy(int peer) const {
  auto it = link_policies_.find(peer);
  return it != link_policies_.end() ? it->second : default_policy_;
}

int CollectiveEngine::stripes_for(int peer) const {
  const int n = link_policy(peer).n_streams;
  return n > 0 ? std::min(n, 32) : n_streams_;
}

int CollectiveEngine::first_alive(int peer) const {
  // Header frames must ride a stripe both ends agree on, so this consults
  // the per-op frozen mask (see begin_op), like launch_group's partition.
  if (peer < 0 || peer >= static_cast<int>(op_mask_.size())) return 0;
  const uint32_t mask = op_mask_[peer];
  if (mask == 0) return -1;
  return __builtin_ctz(mask);
}

void CollectiveEngine::set_error(const std::string& msg) {
  std::lock_guard<std::mutex> lk(err_mu_);
  last_error_ = msg;
}

bool CollectiveEngine::fail(const std::string& msg) {
  // An abort reason beats the downstream I/O error it caused.
  if (!aborted_.load()) set_error(msg);
  return false;
}

std::string CollectiveEngine::last_error() const {
  std::lock_guard<std::mutex> lk(err_mu_);
  return last_error_;
}

int CollectiveEngine::listen(const std::string& host) {
  listen_fd_ = tcp_listen(host, 0, 256);
  if (listen_fd_ < 0) {
    set_error("data plane listen failed");
    return -1;
  }
  // Accepted sockets inherit the buffer sizes; must precede accept.
  set_data_plane_opts(listen_fd_);
  port_ = bound_port(listen_fd_);
  return port_;
}

bool CollectiveEngine::connect_mesh(int rank, int world,
                                    const std::vector<std::string>& peers,
                                    int64_t timeout_ms) {
  rank_ = rank;
  world_ = world;
  results_.assign(world, {});
  peer_fds_.assign(world, {});
  peer_addrs_ = peers;
  peer_counters_ = std::make_unique<PeerCounters[]>(world);
  alive_mask_ = std::make_unique<std::atomic<uint32_t>[]>(world);
  op_mask_.assign(world, 0);
  stripe_gibs_.assign(world, {});
  for (int p = 0; p < world; ++p) {
    const int ns = p == rank ? 0 : stripes_for(p);
    alive_mask_[p].store(ns >= 32 ? ~0u : ((1u << ns) - 1));
    op_mask_[p] = alive_mask_[p].load();
    stripe_gibs_[p].assign(ns, 0.0);
  }
  if (world <= 1) {
    pool_ = std::make_unique<TaskPool>(1);
    return true;
  }
  if (static_cast<int>(peers.size()) != world)
    return fail("connect_mesh: need one address per rank");
  const int64_t deadline = now_ms() + timeout_ms;
  // Deterministic full mesh (same shape as ProcessGroupSocket.configure):
  // connect the link's stripe count to every lower rank, accept from higher
  // ranks. Per-peer counts come from the link policy; both ends must be
  // configured symmetrically (the acceptor validates against ITS policy).
  for (int p = 0; p < rank; ++p) {
    std::string host;
    int port = 0;
    if (!split_host_port(peers[p], &host, &port))
      return fail("connect_mesh: bad peer address " + peers[p]);
    const LinkPolicy pol = link_policy(p);
    const int ns = stripes_for(p);
    peer_fds_[p].assign(ns, -1);
    for (int s = 0; s < ns; ++s) {
      const int64_t remaining = deadline - now_ms();
      if (remaining <= 0 || aborted_.load())
        return fail("timeout: data plane connect to rank " +
                    std::to_string(p));
      chaos::ScopedCtx cctx("data", std::to_string(p), "configure");
      int fd = tcp_connect_retry(host, port, remaining, pol.connect_ms);
      if (fd < 0)
        return fail("timeout: data plane connect to rank " +
                    std::to_string(p));
      set_data_plane_opts(fd);
      Json hello = Json::object();
      hello["rank"] = Json::of(static_cast<int64_t>(rank));
      hello["stripe"] = Json::of(static_cast<int64_t>(s));
      if (!send_frame(fd, hello.dump(), deadline - now_ms())) {
        close(fd);
        return fail("connect_mesh: hello to rank " + std::to_string(p) +
                    " failed");
      }
      peer_fds_[p][s] = fd;
    }
  }
  int expected = 0;
  for (int p = rank + 1; p < world; ++p) expected += stripes_for(p);
  for (int i = 0; i < expected; ++i) {
    const int64_t remaining = deadline - now_ms();
    if (remaining <= 0 || aborted_.load())
      return fail("timeout: data plane accept (" + std::to_string(i) + "/" +
                  std::to_string(expected) + ")");
    int fd = tcp_accept(listen_fd_, static_cast<int>(remaining));
    if (fd < 0)
      return fail("timeout: data plane accept (" + std::to_string(i) + "/" +
                  std::to_string(expected) + ")");
    set_data_plane_opts(fd);
    std::string raw;
    Json hello;
    if (!recv_frame(fd, &raw, std::max<int64_t>(1, deadline - now_ms())) ||
        !Json::parse(raw, &hello)) {
      close(fd);
      return fail("connect_mesh: bad hello frame");
    }
    // A janitor of an already-meshed higher rank can dial while we are
    // still collecting mesh sockets; don't let its rejoin hello consume a
    // mesh slot (the dial self-heals: no reply arrives, it retries later).
    if (hello.get("rejoin").as_int(0) != 0) {
      close(fd);
      --i;
      continue;
    }
    const int p = static_cast<int>(hello.get("rank").as_int(-1));
    const int s = static_cast<int>(hello.get("stripe").as_int(-1));
    if (p <= rank || p >= world || s < 0 || s >= stripes_for(p)) {
      close(fd);
      return fail("connect_mesh: hello from unexpected rank/stripe");
    }
    if (peer_fds_[p].empty()) peer_fds_[p].assign(stripes_for(p), -1);
    peer_fds_[p][s] = fd;
  }
  // Worst concurrent job count: the compressed alltoall runs two striped
  // sends + two striped recvs per peer at once. Undersizing the pool could
  // fill every worker with blocked senders and deadlock the mesh.
  int total_stripes = 0;
  for (int p = 0; p < world; ++p)
    if (p != rank) total_stripes += stripes_for(p);
  const int n_threads = std::min(64, std::max(2, 4 * total_stripes));
  pool_ = std::make_unique<TaskPool>(n_threads);
  // Stripe-rejoin plumbing: the connector side redials dead stripes, the
  // acceptor side absorbs those dials after the mesh is up.
  janitor_ = std::thread([this] { janitor_loop(); });
  acceptor_ = std::thread([this] { acceptor_loop(); });
  return true;
}

void CollectiveEngine::abort(const std::string& why) {
  if (aborted_.exchange(true)) return;
  set_error("aborted: " + why);
  // Shut down (not close) every socket: blocked reads/writes in pool jobs
  // and any caller mid-collective fail immediately; fds stay valid until
  // the destructor so no job can race a close/reuse.
  if (listen_fd_ >= 0) ::shutdown(listen_fd_, SHUT_RDWR);
  // reconn_mu_ also orders this against begin_op's fd installs so the scan
  // below never reads a peer_fds_ slot mid-write.
  std::lock_guard<std::mutex> lk(reconn_mu_);
  for (auto& fds : peer_fds_)
    for (int fd : fds)
      if (fd >= 0) ::shutdown(fd, SHUT_RDWR);
  for (const Staged& st : staged_) ::shutdown(st.fd, SHUT_RDWR);
  for (int fd : retired_fds_) ::shutdown(fd, SHUT_RDWR);
}

void CollectiveEngine::close_all() {
  if (listen_fd_ >= 0) close(listen_fd_);
  listen_fd_ = -1;
  for (auto& fds : peer_fds_)
    for (int fd : fds)
      if (fd >= 0) close(fd);
  peer_fds_.clear();
  for (const Staged& st : staged_) close(st.fd);
  staged_.clear();
  for (int fd : retired_fds_) close(fd);
  retired_fds_.clear();
}

// ---------------------------------------------------------------------------
// Stripe rejoin: janitor (connector side), acceptor, and activation
// ---------------------------------------------------------------------------

void CollectiveEngine::begin_op() {
  std::lock_guard<std::mutex> lk(reconn_mu_);
  ++op_seq_;
  for (auto it = staged_.begin(); it != staged_.end();) {
    if (it->activate_at > op_seq_) {
      ++it;
      continue;
    }
    // Both ends negotiated the same activation number, so (barring a dial
    // racing >8 collectives ahead — then the masks diverge and the next
    // transfer fails back into the abort/heal path) they swap the fd in
    // before the same collective and the stripe partitions agree again.
    const int old = peer_fds_[it->peer][it->stripe];
    if (old >= 0) {
      ::shutdown(old, SHUT_RDWR);
      retired_fds_.push_back(old);
    }
    peer_fds_[it->peer][it->stripe] = it->fd;
    alive_mask_[it->peer].fetch_or(1u << it->stripe);
    record_failover(it->peer, it->stripe, -1, /*dir=*/3, 0, "rejoin");
    it = staged_.erase(it);
  }
  // Freeze the partition mask for this collective. Groups launched during
  // the op must NOT re-read alive_mask_: a leg death observed by one
  // direction's epilogue mid-collective would repartition the other
  // direction's (or the next step's) launch on this end only, while the
  // peer — which observes the death on its own schedule — still partitions
  // over the old stripe set, desynchronizing the byte ranges. With a frozen
  // mask both ends keep launching legs on the dead stripe for the rest of
  // the op; those fail instantly (the fd is shut down) and the handoff
  // protocol re-routes them — identically on both ends.
  for (int p = 0; p < world_; ++p)
    op_mask_[p] = alive_mask_[p].load(std::memory_order_acquire);
}

bool CollectiveEngine::try_rejoin(int peer, int stripe) {
  if (peer < 0 || peer >= static_cast<int>(peer_addrs_.size())) return false;
  std::string host;
  int port = 0;
  if (!split_host_port(peer_addrs_[peer], &host, &port)) return false;
  const LinkPolicy pol = link_policy(peer);
  chaos::ScopedCtx cctx("data", std::to_string(peer), "rejoin");
  int fd = tcp_connect(host, port, std::max<int64_t>(1, pol.connect_ms));
  if (fd < 0) return false;
  set_data_plane_opts(fd);
  uint64_t my_seq;
  {
    std::lock_guard<std::mutex> lk(reconn_mu_);
    my_seq = op_seq_;
  }
  Json hello = Json::object();
  hello["rank"] = Json::of(static_cast<int64_t>(rank_));
  hello["stripe"] = Json::of(static_cast<int64_t>(stripe));
  hello["rejoin"] = Json::of(static_cast<int64_t>(1));
  hello["op_seq"] = Json::of(static_cast<int64_t>(my_seq));
  std::string raw;
  Json reply;
  if (!send_frame(fd, hello.dump(), 2000) || !recv_frame(fd, &raw, 5000) ||
      !Json::parse(raw, &reply)) {
    close(fd);  // never shared: safe to close directly
    return false;
  }
  const int64_t act = reply.get("op_seq").as_int(-1);
  if (act < 0) {
    close(fd);
    return false;
  }
  std::lock_guard<std::mutex> lk(reconn_mu_);
  staged_.push_back({peer, stripe, fd, static_cast<uint64_t>(act)});
  return true;
}

void CollectiveEngine::janitor_loop() {
  uint64_t attempt = 0;
  const std::string key = "stripe_rejoin:" + std::to_string(rank_);
  while (!stopping_.load() && !aborted_.load()) {
    // Seeded full-jitter backoff (~50ms..2s): deterministic under a chaos
    // seed, desynchronized across ranks by the key.
    const int64_t cap =
        std::min<int64_t>(2000, 200 << std::min<uint64_t>(attempt, 4));
    int64_t pause =
        50 + static_cast<int64_t>(chaos::backoff_unit(key, attempt) *
                                  static_cast<double>(cap));
    while (pause > 0 && !stopping_.load() && !aborted_.load()) {
      const int64_t step = std::min<int64_t>(50, pause);
      sleep_ms(step);
      pause -= step;
    }
    bool any_dead = false;
    for (int p = 0; p < rank_ && !stopping_.load() && !aborted_.load(); ++p) {
      const int ns = stripes_for(p);
      const uint32_t full = ns >= 32 ? ~0u : ((1u << ns) - 1);
      uint32_t dead = full & ~alive_mask_[p].load(std::memory_order_acquire);
      {
        std::lock_guard<std::mutex> lk(reconn_mu_);
        for (const Staged& st : staged_)
          if (st.peer == p) dead &= ~(1u << st.stripe);
      }
      while (dead != 0 && !stopping_.load() && !aborted_.load()) {
        const int s = __builtin_ctz(dead);
        dead &= ~(1u << s);
        any_dead = true;
        try_rejoin(p, s);
      }
    }
    attempt = any_dead ? attempt + 1 : 0;
  }
}

void CollectiveEngine::acceptor_loop() {
  while (!stopping_.load() && !aborted_.load()) {
    int fd = tcp_accept(listen_fd_, 250);
    if (fd < 0) continue;
    set_data_plane_opts(fd);
    std::string raw;
    Json hello;
    if (!recv_frame(fd, &raw, 2000) || !Json::parse(raw, &hello)) {
      close(fd);
      continue;
    }
    const int p = static_cast<int>(hello.get("rank").as_int(-1));
    const int s = static_cast<int>(hello.get("stripe").as_int(-1));
    if (hello.get("rejoin").as_int(0) != 1 || p <= rank_ || p >= world_ ||
        s < 0 || s >= stripes_for(p) ||
        (alive_mask_[p].load(std::memory_order_acquire) & (1u << s)) != 0) {
      close(fd);
      continue;
    }
    bool staged_ok = false;
    uint64_t act = 0;
    {
      std::lock_guard<std::mutex> lk(reconn_mu_);
      bool dup = false;
      for (const Staged& st : staged_)
        if (st.peer == p && st.stripe == s) {
          dup = true;
          break;
        }
      if (!dup) {
        const uint64_t theirs = static_cast<uint64_t>(
            std::max<int64_t>(0, hello.get("op_seq").as_int(0)));
        // +8 gives the reply a few collectives of headroom to cross the
        // wire before either end reaches the activation number.
        act = std::max(theirs, op_seq_) + 8;
        staged_.push_back({p, s, fd, act});
        staged_ok = true;
      }
    }
    if (!staged_ok) {
      close(fd);
      continue;
    }
    Json reply = Json::object();
    reply["op_seq"] = Json::of(static_cast<int64_t>(act));
    // A lost reply self-heals: the stripe activates here, comes up dead on
    // the next transfer, and fails over again.
    send_frame(fd, reply.dump(), 2000);
  }
}

void CollectiveEngine::record_failover(int peer, int stripe, int to_stripe,
                                       int dir, uint64_t moved_bytes,
                                       const char* tag) {
  std::lock_guard<std::mutex> lk(fo_mu_);
  FailoverEvent ev{};
  ev.seq = ++fo_seq_;
  ev.peer = static_cast<int16_t>(peer);
  ev.stripe = static_cast<int8_t>(stripe);
  ev.to_stripe = static_cast<int8_t>(to_stripe);
  ev.dir = static_cast<int8_t>(dir);
  ev.bytes = moved_bytes;
  ev.t_ns = now_realtime_ns();
  const size_t n = std::min(strlen(tag), sizeof(ev.tag) - 1);
  memcpy(ev.tag, tag, n);
  ev.tag[n] = '\0';
  failovers_.push_back(ev);
  if (failovers_.size() > 256) failovers_.pop_front();
}

// ---------------------------------------------------------------------------
// Flight recorder
// ---------------------------------------------------------------------------

void CollectiveEngine::set_trace(const std::string& tag) {
  std::lock_guard<std::mutex> lk(trace_mu_);
  const size_t n = std::min(tag.size(), sizeof(trace_tag_) - 1);
  memcpy(trace_tag_, tag.data(), n);
  trace_tag_[n] = '\0';
}

FlightRec* CollectiveEngine::fr_begin(int32_t op_code, int32_t dtype,
                                      int32_t red_op, uint64_t bytes) {
  if (fr_cap_ <= 0) return nullptr;
  const uint64_t seq = fr_seq_.fetch_add(1, std::memory_order_relaxed) + 1;
  if (seq > static_cast<uint64_t>(fr_cap_))
    fr_dropped_.fetch_add(1, std::memory_order_relaxed);
  FlightRec* rec = &fr_ring_[(seq - 1) % fr_cap_];
  std::lock_guard<std::mutex> fr_lk(fr_mu_);
  // seq=0 marks the slot torn while we reset it; a concurrent snapshot
  // skips it instead of reporting a half-old half-new record.
  rec->seq.store(0, std::memory_order_release);
  rec->op = op_code;
  rec->dtype = dtype;
  rec->red_op = red_op;
  rec->bytes = bytes;
  rec->t_start_ns = now_realtime_ns();
  rec->t_end_ns = 0;
  rec->cause[0] = '\0';
  {
    std::lock_guard<std::mutex> lk(trace_mu_);
    memcpy(rec->tag, trace_tag_, sizeof(rec->tag));
  }
  memset(rec->step_ns, 0, sizeof(rec->step_ns));
  rec->nsteps.store(0, std::memory_order_relaxed);
  rec->lane_n.store(0, std::memory_order_relaxed);
  rec->status.store(0, std::memory_order_relaxed);
  rec->seq.store(seq, std::memory_order_release);
  return rec;
}

void CollectiveEngine::fr_end(FlightRec* rec, bool ok) {
  if (rec == nullptr) return;
  const std::string err = ok ? std::string() : last_error();
  std::lock_guard<std::mutex> fr_lk(fr_mu_);
  rec->t_end_ns = now_realtime_ns();
  int32_t st = 1;
  if (!ok) {
    const size_t n = std::min(err.size(), sizeof(rec->cause) - 1);
    memcpy(rec->cause, err.data(), n);
    rec->cause[n] = '\0';
    if (aborted_.load())
      st = 4;
    else if (err.rfind("timeout", 0) == 0)
      st = 3;
    else
      st = 2;
  }
  rec->status.store(st, std::memory_order_release);
}

void CollectiveEngine::fr_step(FlightRec* rec) {
  if (rec == nullptr) return;
  const uint32_t i = rec->nsteps.fetch_add(1, std::memory_order_relaxed);
  if (i < kFrMaxSteps) {
    std::lock_guard<std::mutex> fr_lk(fr_mu_);
    rec->step_ns[i] = now_realtime_ns();
  }
}

void CollectiveEngine::fr_job(FlightRec* rec, int peer, int stripe, int dir,
                              uint64_t bytes, uint64_t t0_ns,
                              uint64_t spins_before, uint64_t reduce_ns) {
  const uint64_t t1 = now_realtime_ns();
  const uint64_t spins = net_spin_count() - spins_before;
  spin_total_.fetch_add(spins, std::memory_order_relaxed);
  if (peer_counters_ && peer >= 0 && peer < world_) {
    PeerCounters& pc = peer_counters_[peer];
    if (dir == 0) {
      pc.tx_bytes.fetch_add(bytes, std::memory_order_relaxed);
      pc.tx_busy_ns.fetch_add(t1 - t0_ns, std::memory_order_relaxed);
    } else {
      pc.rx_bytes.fetch_add(bytes, std::memory_order_relaxed);
      pc.rx_busy_ns.fetch_add(t1 - t0_ns, std::memory_order_relaxed);
    }
    pc.spins.fetch_add(spins, std::memory_order_relaxed);
  }
  // Per-stripe throughput EWMA (fr_snapshot "stripes"): slow-decaying so a
  // WAN drill can read steady-state per-link-class GiB/s off one snapshot.
  if (bytes > 0 && t1 > t0_ns && peer >= 0 &&
      peer < static_cast<int>(stripe_gibs_.size())) {
    const double gibs = static_cast<double>(bytes) /
                        (static_cast<double>(t1 - t0_ns) / 1e9) /
                        static_cast<double>(1ull << 30);
    std::lock_guard<std::mutex> lk(health_mu_);
    if (stripe >= 0 && stripe < static_cast<int>(stripe_gibs_[peer].size())) {
      double& e = stripe_gibs_[peer][stripe];
      e = e == 0.0 ? gibs : 0.8 * e + 0.2 * gibs;
    }
  }
  if (rec == nullptr) return;
  const uint32_t li = rec->lane_n.fetch_add(1, std::memory_order_relaxed);
  if (li >= static_cast<uint32_t>(kFrMaxLanes)) return;
  std::lock_guard<std::mutex> fr_lk(fr_mu_);
  FlightLane& L = rec->lanes[li];
  L.peer = static_cast<int16_t>(peer);
  L.stripe = static_cast<int8_t>(stripe);
  L.dir = static_cast<int8_t>(dir);
  L.spins = static_cast<uint32_t>(spins);
  L.bytes = bytes;
  L.t0_ns = t0_ns;
  L.t1_ns = t1;
  L.reduce_ns = reduce_ns;
}

namespace {

// Snapshot reads are serialized with writers by fr_mu_, but the strings are
// still caller-supplied byte buffers: keep only printable ASCII so the
// emitted JSON always parses.
std::string fr_sanitize(const char* s, size_t cap) {
  std::string out;
  for (size_t i = 0; i < cap && s[i] != '\0'; ++i) {
    const unsigned char c = static_cast<unsigned char>(s[i]);
    out += (c >= 0x20 && c < 0x7f) ? static_cast<char>(c) : '?';
  }
  return out;
}

const char* fr_op_name(int32_t op) {
  switch (op) {
    case 0:
      return "allreduce";
    case 1:
      return "allreduce_q8";
    case 2:
      return "allgather";
    case 3:
      return "broadcast";
  }
  return "unknown";
}

const char* fr_status_name(int32_t st) {
  switch (st) {
    case 0:
      return "in_flight";
    case 1:
      return "ok";
    case 2:
      return "error";
    case 3:
      return "timeout";
    case 4:
      return "aborted";
  }
  return "unknown";
}

const char* fr_dir_name(int8_t dir) {
  return dir == 0 ? "send" : (dir == 1 ? "recv" : "recv_reduce");
}

Json fr_u64(uint64_t v) { return Json::of(static_cast<int64_t>(v)); }

}  // namespace

std::string CollectiveEngine::fr_snapshot(uint64_t since_seq) const {
  Json root = Json::object();
  const uint64_t hi = fr_seq_.load(std::memory_order_acquire);
  root["seq"] = fr_u64(hi);
  root["capacity"] = Json::of(fr_cap_);
  root["dropped"] = fr_u64(fr_dropped_.load(std::memory_order_relaxed));
  root["spin_total"] = fr_u64(spin_total_.load(std::memory_order_relaxed));
  root["bytes_tx"] = fr_u64(bytes_tx_.load());
  root["bytes_rx"] = fr_u64(bytes_rx_.load());
  root["world"] = Json::of(world_);
  root["n_streams"] = Json::of(n_streams_);
  Json peers = Json::array();
  if (peer_counters_) {
    for (int p = 0; p < world_; ++p) {
      if (p == rank_) continue;
      const PeerCounters& pc = peer_counters_[p];
      Json jp = Json::object();
      jp["peer"] = Json::of(p);
      jp["tx_bytes"] = fr_u64(pc.tx_bytes.load(std::memory_order_relaxed));
      jp["rx_bytes"] = fr_u64(pc.rx_bytes.load(std::memory_order_relaxed));
      jp["tx_busy_ns"] = fr_u64(pc.tx_busy_ns.load(std::memory_order_relaxed));
      jp["rx_busy_ns"] = fr_u64(pc.rx_busy_ns.load(std::memory_order_relaxed));
      jp["spins"] = fr_u64(pc.spins.load(std::memory_order_relaxed));
      jp["link"] = Json::of(link_policy(p).cls);
      if (alive_mask_) {
        const uint32_t mask =
            alive_mask_[p].load(std::memory_order_relaxed);
        jp["alive_mask"] = Json::of(static_cast<int64_t>(mask));
        Json stripes = Json::array();
        const int ns = p < static_cast<int>(stripe_gibs_.size())
                           ? static_cast<int>(stripe_gibs_[p].size())
                           : 0;
        std::lock_guard<std::mutex> hl(health_mu_);
        for (int s = 0; s < ns; ++s) {
          Json js = Json::object();
          js["stripe"] = Json::of(s);
          js["alive"] = Json::of(static_cast<int64_t>((mask >> s) & 1));
          js["gibs"] = Json::of(stripe_gibs_[p][s]);
          stripes.push(std::move(js));
        }
        jp["stripes"] = std::move(stripes);
      }
      peers.push(std::move(jp));
    }
  }
  root["peers"] = std::move(peers);
  // Failover ring: every in-collective stripe handoff plus janitor rejoins.
  // Python drains by the monotonic per-event seq (journal stripe_failover).
  Json fos = Json::array();
  {
    std::lock_guard<std::mutex> fo_lk(fo_mu_);
    for (const auto& ev : failovers_) {
      Json je = Json::object();
      je["seq"] = Json::of(ev.seq);
      je["peer"] = Json::of(static_cast<int>(ev.peer));
      je["stripe"] = Json::of(static_cast<int>(ev.stripe));
      je["to_stripe"] = Json::of(static_cast<int>(ev.to_stripe));
      je["dir"] = Json::of(ev.dir == 3 ? "rejoin" : fr_dir_name(ev.dir));
      je["bytes"] = fr_u64(ev.bytes);
      je["t_ns"] = fr_u64(ev.t_ns);
      je["tag"] = Json::of(fr_sanitize(ev.tag, sizeof(ev.tag)));
      fos.push(std::move(je));
    }
  }
  root["failovers"] = std::move(fos);
  Json recs = Json::array();
  std::lock_guard<std::mutex> fr_lk(fr_mu_);
  if (fr_cap_ > 0 && hi > 0) {
    const uint64_t lo0 = hi > static_cast<uint64_t>(fr_cap_)
                             ? hi - static_cast<uint64_t>(fr_cap_)
                             : 0;
    for (uint64_t s = std::max(since_seq, lo0) + 1; s <= hi; ++s) {
      const FlightRec& r = fr_ring_[(s - 1) % fr_cap_];
      if (r.seq.load(std::memory_order_acquire) != s) continue;  // wrapped
      Json jr = Json::object();
      jr["seq"] = fr_u64(s);
      jr["op"] = Json::of(fr_op_name(r.op));
      jr["dtype"] = Json::of(r.dtype);
      jr["red_op"] = Json::of(r.red_op);
      jr["status"] =
          Json::of(fr_status_name(r.status.load(std::memory_order_acquire)));
      jr["bytes"] = fr_u64(r.bytes);
      jr["t_start_ns"] = fr_u64(r.t_start_ns);
      jr["t_end_ns"] = fr_u64(r.t_end_ns);
      jr["tag"] = Json::of(fr_sanitize(r.tag, sizeof(r.tag)));
      jr["cause"] = Json::of(fr_sanitize(r.cause, sizeof(r.cause)));
      const uint32_t nsteps = std::min<uint32_t>(
          r.nsteps.load(std::memory_order_relaxed), kFrMaxSteps);
      Json steps = Json::array();
      for (uint32_t i = 0; i < nsteps; ++i) steps.push(fr_u64(r.step_ns[i]));
      jr["step_ns"] = std::move(steps);
      const uint32_t claimed = r.lane_n.load(std::memory_order_relaxed);
      const uint32_t nlanes = std::min<uint32_t>(claimed, kFrMaxLanes);
      jr["lanes_dropped"] = Json::of(static_cast<int64_t>(claimed - nlanes));
      Json lanes = Json::array();
      for (uint32_t i = 0; i < nlanes; ++i) {
        const FlightLane& L = r.lanes[i];
        Json jl = Json::object();
        jl["peer"] = Json::of(static_cast<int>(L.peer));
        jl["stripe"] = Json::of(static_cast<int>(L.stripe));
        jl["dir"] = Json::of(fr_dir_name(L.dir));
        jl["spins"] = Json::of(static_cast<int64_t>(L.spins));
        jl["bytes"] = fr_u64(L.bytes);
        jl["t0_ns"] = fr_u64(L.t0_ns);
        jl["t1_ns"] = fr_u64(L.t1_ns);
        jl["reduce_ns"] = fr_u64(L.reduce_ns);
        lanes.push(std::move(jl));
      }
      jr["lanes"] = std::move(lanes);
      recs.push(std::move(jr));
    }
  }
  root["records"] = std::move(recs);
  return root.dump();
}

// ---------------------------------------------------------------------------
// Leg groups: striped transfer with in-collective failover
// ---------------------------------------------------------------------------

// All stripes of one (peer, direction) transfer. The group resolves its
// Waiter slot exactly once, from whichever pool thread finishes last; that
// thread also runs the failover epilogue inline (its group-mates are done,
// so the survivor sockets are quiescent and handoff bytes follow the
// normal stripe bytes in order).
struct CollectiveEngine::LegGroup {
  int peer = -1;
  int dir = 0;  // 0 send, 1 recv, 2 recv-reduce
  uint64_t esize = 1;
  int64_t deadline_ms = 0;
  Waiter* w = nullptr;
  FlightRec* rec = nullptr;
  // Transfer base. Send legs only read through it (the const_cast at
  // construction is confined to this struct).
  char* base = nullptr;
  // recv-reduce only.
  int32_t dtype = -1;
  int32_t op = -1;
  uint64_t block_elems = 0;
  uint32_t mask0 = 0;  // alive-mask snapshot the partition was built on
  std::mutex mu;
  int remaining = 0;
  struct Leg {
    int stripe = -1;
    int fd = -1;
    uint64_t uoff = 0;
    uint64_t ulen = 0;
    uint64_t done_units = 0;  // recv-reduce: units already folded into dst
    bool ok = false;
  };
  std::vector<Leg> legs;  // ascending stripe order (failover determinism)
};

namespace {

// Handoff frame: {magic u32, original stripe u32, ulen u64}. Lets the
// receiving end detect asymmetric failure detection (the ends disagreeing
// about which stripe died) instead of misparsing payload bytes.
constexpr uint32_t kHandoffMagic = 0x46414F56;  // "VOAF"

// Pipelined receive-reduce over one contiguous element span: consume the
// wire in sub-blocks and fold each into dst while the peer (and the kernel
// socket buffer) keeps the next sub-block in flight. `skip_elems` consumes
// but does not reduce the leading elements (handoff resends a failed
// stripe's FULL range; the live end must not re-reduce what it already
// folded). `done_out` reports consumed-and-folded progress even on failure
// so a later handoff knows where to resume reducing.
template <typename T>
bool recv_reduce_span(int fd, T* dst, uint64_t elems, int32_t op,
                      uint64_t block_elems, int64_t deadline_ms,
                      std::atomic<uint64_t>* bytes_rx, uint64_t skip_elems,
                      uint64_t* done_out, uint64_t* reduce_ns_out) {
  std::vector<T> scratch(std::min(elems, block_elems));
  uint64_t done = 0;
  uint64_t reduce_ns = 0;
  bool ok = true;
  while (done < elems) {
    const uint64_t m = std::min(block_elems, elems - done);
    const int64_t remaining = deadline_ms - now_ms();
    if (remaining <= 0 ||
        !read_exact(fd, reinterpret_cast<char*>(scratch.data()),
                    m * sizeof(T), remaining)) {
      ok = false;
      break;
    }
    *bytes_rx += m * sizeof(T);
    const uint64_t lo = std::max(done, skip_elems);
    if (lo < done + m) {
      // Per-chunk wire-vs-reduce split for the flight recorder: the lane's
      // total minus reduce_ns is time blocked on the wire.
      const uint64_t r0 = now_realtime_ns();
      reduce_into<T>(dst + lo, scratch.data() + (lo - done), done + m - lo,
                     op);
      reduce_ns += now_realtime_ns() - r0;
    }
    done += m;
  }
  if (done_out != nullptr) *done_out = done;
  if (reduce_ns_out != nullptr) *reduce_ns_out = reduce_ns;
  return ok;
}

bool recv_reduce_dispatch(int32_t dtype, int fd, char* base, uint64_t uoff,
                          uint64_t ulen, int32_t op, uint64_t block_elems,
                          int64_t deadline_ms,
                          std::atomic<uint64_t>* bytes_rx, uint64_t skip,
                          uint64_t* done_out, uint64_t* reduce_ns_out) {
  switch (dtype) {
    case TFT_DT_F32:
      return recv_reduce_span<float>(fd, reinterpret_cast<float*>(base) + uoff,
                                     ulen, op, block_elems, deadline_ms,
                                     bytes_rx, skip, done_out, reduce_ns_out);
    case TFT_DT_F64:
      return recv_reduce_span<double>(
          fd, reinterpret_cast<double*>(base) + uoff, ulen, op, block_elems,
          deadline_ms, bytes_rx, skip, done_out, reduce_ns_out);
    case TFT_DT_I32:
      return recv_reduce_span<int32_t>(
          fd, reinterpret_cast<int32_t*>(base) + uoff, ulen, op, block_elems,
          deadline_ms, bytes_rx, skip, done_out, reduce_ns_out);
    case TFT_DT_I64:
      return recv_reduce_span<int64_t>(
          fd, reinterpret_cast<int64_t*>(base) + uoff, ulen, op, block_elems,
          deadline_ms, bytes_rx, skip, done_out, reduce_ns_out);
  }
  return false;
}

}  // namespace

void CollectiveEngine::launch_group(std::shared_ptr<LegGroup> g,
                                    uint64_t units) {
  const int peer = g->peer;
  const int ns = stripes_for(peer);
  // Partition over the mask FROZEN at begin_op, not the live alive_mask_ —
  // see begin_op for why (mid-op repartitioning desyncs the two ends).
  const uint32_t mask = peer < static_cast<int>(op_mask_.size())
                            ? op_mask_[peer]
                            : (ns >= 32 ? ~0u : ((1u << ns) - 1));
  if (mask == 0) {
    g->w->add(1);
    g->w->done(false, false, "all stripes to peer dead");
    return;
  }
  g->mask0 = mask;
  // Partition over the LIVE stripes only (np.array_split semantics over the
  // survivor count). Both ends hold the same mask after a symmetric
  // failure, so their partitions agree without a control round-trip.
  std::vector<int> alive;
  alive.reserve(ns);
  for (int s = 0; s < ns; ++s)
    if (mask & (1u << s)) alive.push_back(s);
  const int parts = static_cast<int>(alive.size());
  for (int i = 0; i < parts; ++i) {
    const uint64_t ulen = split_size(units, parts, i);
    if (ulen == 0) continue;
    LegGroup::Leg leg;
    leg.stripe = alive[i];
    leg.fd = peer_fds_[peer][alive[i]];
    leg.uoff = split_off(units, parts, i);
    leg.ulen = ulen;
    g->legs.push_back(leg);
  }
  if (g->legs.empty()) return;
  g->remaining = static_cast<int>(g->legs.size());
  g->w->add(1);
  for (size_t i = 0; i < g->legs.size(); ++i)
    pool_->submit([this, g, i] { run_leg(g, i); });
}

void CollectiveEngine::run_leg(std::shared_ptr<LegGroup> g, size_t li) {
  LegGroup::Leg& leg = g->legs[li];
  const uint64_t t0 = now_realtime_ns();
  const uint64_t sp0 = net_spin_count();
  // Chaos scope: stall/partial_write/reset/throttle rules fire inside
  // write_all/read_exact, attributed to (peer rank, collective tag). The
  // "|s<stripe>" suffix lets a rule pin one stripe (match=|s2).
  chaos::ScopedCtx cctx(
      "data", std::to_string(g->peer),
      (g->rec != nullptr ? std::string(g->rec->tag) : std::string()) + "|s" +
          std::to_string(leg.stripe));
  // An io_ms budget fails a stalled stripe early enough for the group to
  // hand its range over; without one a stall rides to the collective
  // deadline and can only abort.
  const LinkPolicy pol = link_policy(g->peer);
  int64_t leg_deadline = g->deadline_ms;
  if (pol.io_ms > 0)
    leg_deadline = std::min(leg_deadline, now_ms() + pol.io_ms);
  const uint64_t len = leg.ulen * g->esize;
  uint64_t reduce_ns = 0;
  uint64_t done_units = 0;
  bool ok = false;
  const int64_t remaining = leg_deadline - now_ms();
  if (remaining > 0 && !aborted_.load()) {
    switch (g->dir) {
      case 0:
        ok = write_all(leg.fd, g->base + leg.uoff * g->esize, len, remaining);
        if (ok) bytes_tx_ += len;
        break;
      case 1:
        ok = read_exact(leg.fd, g->base + leg.uoff * g->esize, len,
                        remaining);
        if (ok) bytes_rx_ += len;
        break;
      default:
        ok = recv_reduce_dispatch(g->dtype, leg.fd, g->base, leg.uoff,
                                  leg.ulen, g->op, g->block_elems,
                                  leg_deadline, &bytes_rx_, /*skip=*/0,
                                  &done_units, &reduce_ns);
        break;
    }
  }
  fr_job(g->rec, g->peer, leg.stripe, g->dir, ok ? len : 0, t0, sp0,
         reduce_ns);
  bool last;
  {
    std::lock_guard<std::mutex> lk(g->mu);
    leg.ok = ok;
    leg.done_units = done_units;
    last = --g->remaining == 0;
  }
  if (last) leg_epilogue(std::move(g));
}

bool CollectiveEngine::handoff_leg(LegGroup& g, size_t li, int to) {
  LegGroup::Leg& leg = g.legs[li];
  const int fd = peer_fds_[g.peer][to];
  int64_t remaining = g.deadline_ms - now_ms();
  if (fd < 0 || remaining <= 0) return false;
  chaos::ScopedCtx cctx(
      "data", std::to_string(g.peer),
      (g.rec != nullptr ? std::string(g.rec->tag) : std::string()) +
          "|handoff");
  const uint64_t t0 = now_realtime_ns();
  const uint64_t sp0 = net_spin_count();
  const uint64_t len = leg.ulen * g.esize;
  char hdr[16];
  const uint32_t magic = kHandoffMagic;
  uint64_t reduce_ns = 0;
  bool ok = false;
  if (g.dir == 0) {
    const uint32_t s32 = static_cast<uint32_t>(leg.stripe);
    memcpy(hdr, &magic, 4);
    memcpy(hdr + 4, &s32, 4);
    memcpy(hdr + 8, &leg.ulen, 8);
    ok = write_all(fd, hdr, 16, remaining);
    remaining = g.deadline_ms - now_ms();
    ok = ok && remaining > 0 &&
         write_all(fd, g.base + leg.uoff * g.esize, len, remaining);
    if (ok) bytes_tx_ += len;
  } else {
    ok = read_exact(fd, hdr, 16, remaining);
    if (ok) {
      uint32_t m2 = 0, s2 = 0;
      uint64_t ul = 0;
      memcpy(&m2, hdr, 4);
      memcpy(&s2, hdr + 4, 4);
      memcpy(&ul, hdr + 8, 8);
      ok = m2 == magic && s2 == static_cast<uint32_t>(leg.stripe) &&
           ul == leg.ulen;
    }
    remaining = g.deadline_ms - now_ms();
    ok = ok && remaining > 0;
    if (ok) {
      if (g.dir == 1) {
        ok = read_exact(fd, g.base + leg.uoff * g.esize, len, remaining);
        if (ok) bytes_rx_ += len;
      } else {
        uint64_t done2 = 0;
        ok = recv_reduce_dispatch(g.dtype, fd, g.base, leg.uoff, leg.ulen,
                                  g.op, g.block_elems, g.deadline_ms,
                                  &bytes_rx_, /*skip=*/leg.done_units, &done2,
                                  &reduce_ns);
      }
    }
  }
  // The handoff shows up as a lane on the carrier stripe, so obs_trace
  // recovery lanes render it next to the leg it replaced.
  fr_job(g.rec, g.peer, to, g.dir, ok ? len : 0, t0, sp0, reduce_ns);
  return ok;
}

void CollectiveEngine::leg_epilogue(std::shared_ptr<LegGroup> g) {
  // No lock needed: remaining hit 0 under g->mu, publishing every leg.
  std::vector<size_t> failed;
  for (size_t i = 0; i < g->legs.size(); ++i)
    if (!g->legs[i].ok) failed.push_back(i);
  if (failed.empty()) {
    g->w->done(true, false, "");
    return;
  }
  uint32_t mask = g->mask0;
  for (size_t i : failed) {
    mask &= ~(1u << g->legs[i].stripe);
    alive_mask_[g->peer].fetch_and(~(1u << g->legs[i].stripe));
  }
  auto give_up = [&](const char* what) {
    g->w->done(false, now_ms() >= g->deadline_ms && !aborted_.load(), what);
  };
  if (aborted_.load()) {
    give_up("stripe transfer aborted");
    return;
  }
  if (mask == 0) {
    give_up("all stripes to peer dead");
    return;
  }
  if (g->deadline_ms - now_ms() <= 0) {
    give_up("timeout: stripe failover budget spent");
    return;
  }
  // Hand each failed leg's FULL range to the lowest live stripe. Both ends
  // walk their failed legs in ascending stripe order over the same mask, so
  // the carrier choice needs no control round-trip; if a carrier dies too,
  // both ends see it (symmetric detection) and cascade identically.
  const char* tag = g->rec != nullptr ? g->rec->tag : "";
  for (size_t i : failed) {
    bool moved = false;
    while (mask != 0) {
      const int to = __builtin_ctz(mask);
      if (handoff_leg(*g, i, to)) {
        record_failover(g->peer, g->legs[i].stripe, to, g->dir,
                        g->legs[i].ulen * g->esize, tag);
        moved = true;
        break;
      }
      mask &= ~(1u << to);
      alive_mask_[g->peer].fetch_and(~(1u << to));
    }
    if (!moved) {
      give_up(mask == 0 ? "all stripes to peer dead"
                        : "stripe handoff failed");
      return;
    }
  }
  g->w->done(true, false, "");
}

void CollectiveEngine::send_stripes(int peer, const char* data,
                                    uint64_t nbytes, uint64_t esize,
                                    int64_t deadline_ms, Waiter* w,
                                    FlightRec* rec) {
  if (nbytes == 0) return;
  auto g = std::make_shared<LegGroup>();
  g->peer = peer;
  g->dir = 0;
  g->esize = esize;
  g->deadline_ms = deadline_ms;
  g->w = w;
  g->rec = rec;
  g->base = const_cast<char*>(data);  // send legs never write through base
  launch_group(std::move(g), nbytes / esize);
}

void CollectiveEngine::recv_stripes(int peer, char* data, uint64_t nbytes,
                                    uint64_t esize, int64_t deadline_ms,
                                    Waiter* w, FlightRec* rec) {
  if (nbytes == 0) return;
  auto g = std::make_shared<LegGroup>();
  g->peer = peer;
  g->dir = 1;
  g->esize = esize;
  g->deadline_ms = deadline_ms;
  g->w = w;
  g->rec = rec;
  g->base = data;
  launch_group(std::move(g), nbytes / esize);
}

void CollectiveEngine::recv_reduce_stripes(int peer, void* dst, uint64_t count,
                                           int32_t dtype, int32_t op,
                                           int64_t deadline_ms, Waiter* w,
                                           FlightRec* rec) {
  if (count == 0) return;
  const uint64_t esize = dtype_size(dtype);
  auto g = std::make_shared<LegGroup>();
  g->peer = peer;
  g->dir = 2;
  g->esize = esize;
  g->deadline_ms = deadline_ms;
  g->w = w;
  g->rec = rec;
  g->base = static_cast<char*>(dst);
  g->dtype = dtype;
  g->op = op;
  g->block_elems =
      std::max<uint64_t>(1, static_cast<uint64_t>(pipeline_bytes_) / esize);
  launch_group(std::move(g), count);
}

template <typename T>
bool CollectiveEngine::ring_allreduce_t(T* data, uint64_t count, int32_t dtype,
                                        int32_t op, int64_t deadline_ms,
                                        FlightRec* rec) {
  const int ws = world_, r = rank_;
  const int right = (r + 1) % ws;
  const int left = (r - 1 + ws) % ws;
  auto coff = [&](int i) { return split_off(count, ws, i); };
  auto clen = [&](int i) { return split_size(count, ws, i); };
  auto ring_idx = [&](int i) { return ((i % ws) + ws) % ws; };
  // Reduce-scatter: after step k, chunk (r - k - 1) holds the partial
  // reduction of k+2 ranks; after ws-1 steps rank r owns the full reduction
  // of chunk (r + 1) % ws. Same schedule (and therefore the same
  // per-element accumulation order) as _ring_allreduce_flat.
  for (int step = 0; step < ws - 1; ++step) {
    const int si = ring_idx(r - step);
    const int ri = ring_idx(r - step - 1);
    Waiter w;
    send_stripes(right, reinterpret_cast<const char*>(data + coff(si)),
                 clen(si) * sizeof(T), sizeof(T), deadline_ms, &w, rec);
    recv_reduce_stripes(left, data + coff(ri), clen(ri), dtype, op,
                        deadline_ms, &w, rec);
    if (!w.wait_all())
      return fail((w.timed_out ? "timeout: " : "") + std::string(
                      "allreduce reduce-scatter step ") +
                  std::to_string(step) + ": " + w.err);
    fr_step(rec);
  }
  // Allgather: circulate the fully reduced chunks.
  for (int step = 0; step < ws - 1; ++step) {
    const int si = ring_idx(r - step + 1);
    const int ri = ring_idx(r - step);
    Waiter w;
    send_stripes(right, reinterpret_cast<const char*>(data + coff(si)),
                 clen(si) * sizeof(T), sizeof(T), deadline_ms, &w, rec);
    recv_stripes(left, reinterpret_cast<char*>(data + coff(ri)),
                 clen(ri) * sizeof(T), sizeof(T), deadline_ms, &w, rec);
    if (!w.wait_all())
      return fail((w.timed_out ? "timeout: " : "") +
                  std::string("allreduce allgather step ") +
                  std::to_string(step) + ": " + w.err);
    fr_step(rec);
  }
  return true;
}

bool CollectiveEngine::allreduce(void* data, uint64_t count, int32_t dtype,
                                 int32_t op, int64_t timeout_ms) {
  if (world_ <= 1) return true;
  if (aborted_.load()) return false;
  if (pool_ == nullptr) return fail("engine not connected");
  begin_op();
  const int64_t deadline = now_ms() + timeout_ms;
  FlightRec* rec = fr_begin(0, dtype, op, count * dtype_size(dtype));
  bool ok = false;
  switch (dtype) {
    case TFT_DT_F32:
      ok = ring_allreduce_t<float>(static_cast<float*>(data), count, dtype,
                                   op, deadline, rec);
      break;
    case TFT_DT_F64:
      ok = ring_allreduce_t<double>(static_cast<double*>(data), count, dtype,
                                    op, deadline, rec);
      break;
    case TFT_DT_I32:
      ok = ring_allreduce_t<int32_t>(static_cast<int32_t*>(data), count,
                                     dtype, op, deadline, rec);
      break;
    case TFT_DT_I64:
      ok = ring_allreduce_t<int64_t>(static_cast<int64_t*>(data), count,
                                     dtype, op, deadline, rec);
      break;
    default:
      ok = fail("allreduce: unsupported dtype code " + std::to_string(dtype));
      break;
  }
  fr_end(rec, ok);
  return ok;
}

bool CollectiveEngine::allreduce_q8(float* data, uint64_t count,
                                    int64_t timeout_ms) {
  if (world_ <= 1) return true;
  if (aborted_.load()) return false;
  if (pool_ == nullptr) return fail("engine not connected");
  begin_op();
  FlightRec* rec = fr_begin(1, TFT_DT_F32, TFT_OP_SUM, count * sizeof(float));
  const bool ok = allreduce_q8_inner(data, count, timeout_ms, rec);
  fr_end(rec, ok);
  return ok;
}

bool CollectiveEngine::allreduce_q8_inner(float* data, uint64_t count,
                                          int64_t timeout_ms, FlightRec* rec) {
  const int64_t deadline = now_ms() + timeout_ms;
  const int ws = world_, me = rank_;
  const uint64_t blocks = (count + kQBlock - 1) / kQBlock;

  // Quantize the full payload exactly once (collectives.py:586).
  std::vector<int8_t> q(blocks * kQBlock);
  std::vector<float> scales(blocks);
  q8_quantize(data, count, blocks, q.data(), scales.data());

  if (blocks < static_cast<uint64_t>(ws)) {
    // Tiny payload (fewer blocks than ranks): allgather-all fallback, no
    // chunking — mirrors _quantized_wire_pipeline's blocks < ws branch.
    std::string payload(reinterpret_cast<const char*>(scales.data()),
                        blocks * sizeof(float));
    payload.append(reinterpret_cast<const char*>(q.data()), q.size());
    if (!allgather("", payload.data(), payload.size(), timeout_ms))
      return false;
    std::vector<float> acc(blocks * kQBlock, 0.f);
    for (int p = 0; p < ws; ++p) {
      const char* src = p == me ? payload.data() : results_[p].second.data();
      q8_accumulate(acc.data(),
                    reinterpret_cast<const int8_t*>(src +
                                                    blocks * sizeof(float)),
                    reinterpret_cast<const float*>(src), blocks);
    }
    memcpy(data, acc.data(), count * sizeof(float));
    return true;
  }

  // Owner chunks: contiguous block-aligned np.array_split over blocks, so
  // each chunk owns whole scales (collectives.py:543).
  auto boff = [&](int i) { return split_off(blocks, ws, i); };
  auto blen = [&](int i) { return split_size(blocks, ws, i); };
  const uint64_t my_blocks = blen(me);

  // Each direction of each peer exchange must be one contiguous transfer:
  // two concurrent send_stripes to the same peer would race on the shared
  // per-stripe fds and interleave bytes. Wire layout per chunk of b blocks:
  // [b fp32 scales][b * kQBlock int8 codes].
  auto pack = [](const float* s, const int8_t* qv, uint64_t nb) {
    std::vector<char> buf(nb * (sizeof(float) + kQBlock));
    memcpy(buf.data(), s, nb * sizeof(float));
    memcpy(buf.data() + nb * sizeof(float), qv, nb * kQBlock);
    return buf;
  };
  auto unpack_s = [](const std::vector<char>& buf) {
    return reinterpret_cast<const float*>(buf.data());
  };
  auto unpack_q = [](const std::vector<char>& buf, uint64_t nb) {
    return reinterpret_cast<const int8_t*>(buf.data() + nb * sizeof(float));
  };

  // Phase 1: alltoall — send rank p its chunk of my quantized payload,
  // receive every peer's slice of MY chunk.
  std::vector<std::vector<char>> out(ws), in(ws);
  {
    Waiter w;
    for (int p = 0; p < ws; ++p) {
      if (p == me) continue;
      out[p] = pack(scales.data() + boff(p), q.data() + boff(p) * kQBlock,
                    blen(p));
      send_stripes(p, out[p].data(), out[p].size(), 1, deadline, &w, rec);
      in[p].resize(my_blocks * (sizeof(float) + kQBlock));
      recv_stripes(p, in[p].data(), in[p].size(), 1, deadline, &w, rec);
    }
    if (!w.wait_all())
      return fail((w.timed_out ? "timeout: " : "") +
                  std::string("q8 alltoall: ") + w.err);
    fr_step(rec);
  }

  // Local fp32 reduce of my chunk, rank order 0..ws-1 (alltoall output
  // order in _alltoall_chunk_reduce) — cross-replica bitwise identical.
  std::vector<float> acc(my_blocks * kQBlock, 0.f);
  for (int p = 0; p < ws; ++p) {
    const int8_t* src_q = p == me ? q.data() + boff(me) * kQBlock
                                  : unpack_q(in[p], my_blocks);
    const float* src_s = p == me ? scales.data() + boff(me) : unpack_s(in[p]);
    q8_accumulate(acc.data(), src_q, src_s, my_blocks);
  }

  // Requantize my reduced chunk (the second and final lossy step), then
  // allgather every rank's chunk.
  std::vector<int8_t> q2(my_blocks * kQBlock);
  std::vector<float> s2(my_blocks);
  q8_quantize(acc.data(), acc.size(), my_blocks, q2.data(), s2.data());
  const std::vector<char> mine = pack(s2.data(), q2.data(), my_blocks);
  std::vector<std::vector<char>> gathered(ws);
  {
    Waiter w;
    for (int p = 0; p < ws; ++p) {
      if (p == me) continue;
      send_stripes(p, mine.data(), mine.size(), 1, deadline, &w, rec);
      gathered[p].resize(blen(p) * (sizeof(float) + kQBlock));
      recv_stripes(p, gathered[p].data(), gathered[p].size(), 1, deadline, &w,
                   rec);
    }
    if (!w.wait_all())
      return fail((w.timed_out ? "timeout: " : "") +
                  std::string("q8 allgather: ") + w.err);
    fr_step(rec);
  }

  // Decode the assembled (q_final, s_final) straight into the caller's
  // buffer: data[i] = (float)q * scale, trimmed to count.
  for (int p = 0; p < ws; ++p) {
    const uint64_t nb = blen(p);
    const int8_t* fq = p == me ? q2.data() : unpack_q(gathered[p], nb);
    const float* fs = p == me ? s2.data() : unpack_s(gathered[p]);
    const uint64_t lo = boff(p) * kQBlock;
    for (uint64_t b = 0; b < nb; ++b) {
      const float s = fs[b];
      for (uint64_t j = 0; j < kQBlock; ++j) {
        const uint64_t idx = lo + b * kQBlock + j;
        if (idx >= count) break;
        data[idx] = static_cast<float>(fq[b * kQBlock + j]) * s;
      }
    }
  }
  return true;
}

bool CollectiveEngine::allgather(const std::string& meta, const void* data,
                                 uint64_t nbytes, int64_t timeout_ms) {
  for (auto& r : results_) r = {};
  if (world_ <= 1) return true;
  if (aborted_.load()) return false;
  if (pool_ == nullptr) return fail("engine not connected");
  begin_op();
  FlightRec* rec = fr_begin(2, -1, -1, nbytes);
  const bool ok = allgather_inner(meta, data, nbytes, timeout_ms, rec);
  fr_end(rec, ok);
  return ok;
}

bool CollectiveEngine::allgather_inner(const std::string& meta,
                                       const void* data, uint64_t nbytes,
                                       int64_t timeout_ms, FlightRec* rec) {
  const int64_t deadline = now_ms() + timeout_ms;
  // Phase A: fixed-size headers + meta on the first LIVE stripe of every
  // peer link (both ends agree on the alive mask, so they pick the same
  // one). The barrier before phase B guarantees the header precedes that
  // stripe's payload bytes on the same socket, and that every receive
  // buffer is sized.
  char hdr[12];
  const uint32_t mlen = static_cast<uint32_t>(meta.size());
  memcpy(hdr, &mlen, 4);
  memcpy(hdr + 4, &nbytes, 8);
  std::string hdr_full(hdr, 12);
  hdr_full += meta;
  {
    Waiter w;
    for (int p = 0; p < world_; ++p) {
      if (p == rank_) continue;
      const int fa = first_alive(p);
      const int fd0 = peer_fds_[p][fa < 0 ? 0 : fa];
      w.add(2);
      pool_->submit([this, fd0, &hdr_full, deadline, w_ptr = &w] {
        const int64_t remaining = deadline - now_ms();
        const bool ok = remaining > 0 && !aborted_.load() &&
                        write_all(fd0, hdr_full.data(), hdr_full.size(),
                                  remaining);
        if (ok) bytes_tx_ += hdr_full.size();
        w_ptr->done(ok, !ok && now_ms() >= deadline && !aborted_.load(),
                    "allgather header send failed");
      });
      pool_->submit([this, p, fd0, deadline, w_ptr = &w] {
        char h[12];
        int64_t remaining = deadline - now_ms();
        bool ok = remaining > 0 && !aborted_.load() &&
                  read_exact(fd0, h, 12, remaining);
        uint32_t peer_mlen = 0;
        uint64_t peer_nbytes = 0;
        if (ok) {
          memcpy(&peer_mlen, h, 4);
          memcpy(&peer_nbytes, h + 4, 8);
          ok = peer_mlen <= (64u << 20) && peer_nbytes <= (1ull << 40);
        }
        if (ok && peer_mlen > 0) {
          results_[p].first.resize(peer_mlen);
          remaining = deadline - now_ms();
          ok = remaining > 0 &&
               read_exact(fd0, &results_[p].first[0], peer_mlen, remaining);
        }
        if (ok) {
          results_[p].second.resize(peer_nbytes);
          bytes_rx_ += 12 + peer_mlen;
        }
        w_ptr->done(ok, !ok && now_ms() >= deadline && !aborted_.load(),
                    "allgather header recv failed");
      });
    }
    if (!w.wait_all())
      return fail((w.timed_out ? "timeout: " : "") +
                  std::string("allgather headers: ") + w.err);
    fr_step(rec);
  }
  // Phase B: striped payloads, all peers in full flight.
  {
    Waiter w;
    for (int p = 0; p < world_; ++p) {
      if (p == rank_) continue;
      send_stripes(p, static_cast<const char*>(data), nbytes, 1, deadline,
                   &w, rec);
      recv_stripes(p, results_[p].second.empty() ? nullptr
                                                 : &results_[p].second[0],
                   results_[p].second.size(), 1, deadline, &w, rec);
    }
    if (!w.wait_all())
      return fail((w.timed_out ? "timeout: " : "") +
                  std::string("allgather payloads: ") + w.err);
    fr_step(rec);
  }
  return true;
}

bool CollectiveEngine::broadcast(const std::string& meta, const void* data,
                                 uint64_t nbytes, int root,
                                 int64_t timeout_ms) {
  for (auto& r : results_) r = {};
  if (world_ <= 1) return true;
  if (aborted_.load()) return false;
  if (pool_ == nullptr) return fail("engine not connected");
  if (root < 0 || root >= world_)
    return fail("broadcast: bad root " + std::to_string(root));
  begin_op();
  FlightRec* rec = fr_begin(3, -1, -1, nbytes);
  const bool ok = broadcast_inner(meta, data, nbytes, root, timeout_ms, rec);
  fr_end(rec, ok);
  return ok;
}

bool CollectiveEngine::broadcast_inner(const std::string& meta,
                                       const void* data, uint64_t nbytes,
                                       int root, int64_t timeout_ms,
                                       FlightRec* rec) {
  const int64_t deadline = now_ms() + timeout_ms;
  if (rank_ == root) {
    char hdr[12];
    const uint32_t mlen = static_cast<uint32_t>(meta.size());
    const uint64_t pn = nbytes;
    memcpy(hdr, &mlen, 4);
    memcpy(hdr + 4, &pn, 8);
    std::string hdr_full(hdr, 12);
    hdr_full += meta;
    {
      // Headers first (barrier keeps them ahead of stripe-0 payload).
      Waiter w;
      for (int p = 0; p < world_; ++p) {
        if (p == rank_) continue;
        const int fa = first_alive(p);
        const int fd0 = peer_fds_[p][fa < 0 ? 0 : fa];
        w.add(1);
        pool_->submit([this, fd0, &hdr_full, deadline, w_ptr = &w] {
          const int64_t remaining = deadline - now_ms();
          const bool ok = remaining > 0 && !aborted_.load() &&
                          write_all(fd0, hdr_full.data(), hdr_full.size(),
                                    remaining);
          if (ok) bytes_tx_ += hdr_full.size();
          w_ptr->done(ok, !ok && now_ms() >= deadline && !aborted_.load(),
                      "broadcast header send failed");
        });
      }
      if (!w.wait_all())
        return fail((w.timed_out ? "timeout: " : "") +
                    std::string("broadcast headers: ") + w.err);
    }
    Waiter w;
    for (int p = 0; p < world_; ++p) {
      if (p == rank_) continue;
      send_stripes(p, static_cast<const char*>(data), nbytes, 1, deadline,
                   &w, rec);
    }
    if (!w.wait_all())
      return fail((w.timed_out ? "timeout: " : "") +
                  std::string("broadcast payload: ") + w.err);
    return true;
  }
  // Non-root: header from root on its first live stripe (caller thread),
  // then striped payload into the result slot.
  const int fa = first_alive(root);
  const int fd0 = peer_fds_[root][fa < 0 ? 0 : fa];
  char h[12];
  int64_t remaining = deadline - now_ms();
  if (remaining <= 0 || !read_exact(fd0, h, 12, remaining))
    return fail(now_ms() >= deadline && !aborted_.load()
                    ? "timeout: broadcast header"
                    : "broadcast header recv failed");
  uint32_t peer_mlen = 0;
  uint64_t peer_nbytes = 0;
  memcpy(&peer_mlen, h, 4);
  memcpy(&peer_nbytes, h + 4, 8);
  if (peer_mlen > (64u << 20) || peer_nbytes > (1ull << 40))
    return fail("broadcast: implausible header");
  if (peer_mlen > 0) {
    results_[root].first.resize(peer_mlen);
    remaining = deadline - now_ms();
    if (remaining <= 0 ||
        !read_exact(fd0, &results_[root].first[0], peer_mlen, remaining))
      return fail("broadcast meta recv failed");
  }
  bytes_rx_ += 12 + peer_mlen;
  results_[root].second.resize(peer_nbytes);
  Waiter w;
  recv_stripes(root,
               results_[root].second.empty() ? nullptr
                                             : &results_[root].second[0],
               peer_nbytes, 1, deadline, &w, rec);
  if (!w.wait_all())
    return fail((w.timed_out ? "timeout: " : "") +
                std::string("broadcast payload: ") + w.err);
  return true;
}

}  // namespace tft

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

namespace {

tft::CollectiveEngine* eng(void* h) {
  return static_cast<tft::CollectiveEngine*>(h);
}

int32_t rc_for(tft::CollectiveEngine* e, bool ok) {
  if (ok) return 0;
  return e->last_error().rfind("timeout", 0) == 0 ? 2 : 1;
}

}  // namespace

extern "C" {

void* tft_coll_create(int32_t n_streams, int64_t pipeline_bytes,
                      int32_t fr_capacity) {
  return new tft::CollectiveEngine(n_streams, pipeline_bytes, fr_capacity);
}

void tft_coll_destroy(void* h) { delete eng(h); }

int32_t tft_coll_listen(void* h, const char* host) {
  return eng(h)->listen(host ? host : "");
}

int32_t tft_coll_connect(void* h, int32_t rank, int32_t world,
                         const char* peers_json, int64_t timeout_ms) {
  tft::Json peers;
  std::vector<std::string> addrs;
  if (peers_json && tft::Json::parse(peers_json, &peers) &&
      peers.is_array()) {
    for (const auto& p : peers.arr) addrs.push_back(p.as_str());
  }
  return rc_for(eng(h),
                eng(h)->connect_mesh(rank, world, addrs, timeout_ms));
}

void tft_coll_abort(void* h, const char* why) {
  eng(h)->abort(why ? why : "abort");
}

void tft_coll_set_link(void* h, int32_t peer, const char* cls,
                       int64_t connect_ms, int64_t io_ms, int32_t n_streams,
                       int32_t q8) {
  tft::LinkPolicy pol;
  if (cls != nullptr && cls[0] != '\0') pol.cls = cls;
  pol.connect_ms = connect_ms;
  pol.io_ms = io_ms;
  pol.n_streams = n_streams;
  pol.q8 = q8 != 0;
  eng(h)->set_link_policy(peer, pol);
}

int32_t tft_coll_allreduce(void* h, void* data, uint64_t count, int32_t dtype,
                           int32_t op, int64_t timeout_ms) {
  return rc_for(eng(h), eng(h)->allreduce(data, count, dtype, op, timeout_ms));
}

int32_t tft_coll_allreduce_q8(void* h, float* data, uint64_t count,
                              int64_t timeout_ms) {
  return rc_for(eng(h), eng(h)->allreduce_q8(data, count, timeout_ms));
}

int32_t tft_coll_allgather(void* h, const char* meta, const void* data,
                           uint64_t nbytes, int64_t timeout_ms) {
  return rc_for(eng(h), eng(h)->allgather(meta ? meta : "", data, nbytes,
                                          timeout_ms));
}

int32_t tft_coll_broadcast(void* h, const char* meta, const void* data,
                           uint64_t nbytes, int32_t root, int64_t timeout_ms) {
  return rc_for(eng(h), eng(h)->broadcast(meta ? meta : "", data, nbytes,
                                          root, timeout_ms));
}

int64_t tft_coll_result_meta_len(void* h, int32_t slot) {
  auto* e = eng(h);
  if (slot < 0 || slot >= e->world()) return -1;
  return static_cast<int64_t>(e->result_meta(slot).size());
}

int32_t tft_coll_result_meta(void* h, int32_t slot, char* out, int64_t cap) {
  auto* e = eng(h);
  if (slot < 0 || slot >= e->world()) return 1;
  const std::string& m = e->result_meta(slot);
  if (static_cast<int64_t>(m.size()) > cap) return 1;
  memcpy(out, m.data(), m.size());
  return 0;
}

int64_t tft_coll_result_size(void* h, int32_t slot) {
  auto* e = eng(h);
  if (slot < 0 || slot >= e->world()) return -1;
  return static_cast<int64_t>(e->result_payload(slot).size());
}

int32_t tft_coll_result_copy(void* h, int32_t slot, void* out, int64_t cap) {
  auto* e = eng(h);
  if (slot < 0 || slot >= e->world()) return 1;
  const std::string& p = e->result_payload(slot);
  if (static_cast<int64_t>(p.size()) > cap) return 1;
  memcpy(out, p.data(), p.size());
  return 0;
}

uint64_t tft_coll_bytes_tx(void* h) { return eng(h)->bytes_tx(); }
uint64_t tft_coll_bytes_rx(void* h) { return eng(h)->bytes_rx(); }

void tft_coll_last_error(void* h, char* out, int64_t cap) {
  if (cap <= 0) return;
  const std::string e = eng(h)->last_error();
  const int64_t n = std::min<int64_t>(cap - 1, e.size());
  memcpy(out, e.data(), n);
  out[n] = '\0';
}

void tft_coll_set_trace(void* h, const char* tag) {
  eng(h)->set_trace(tag ? tag : "");
}

uint64_t tft_coll_fr_seq(void* h) { return eng(h)->fr_seq(); }

int64_t tft_coll_fr_snapshot(void* h, uint64_t since_seq, char* out,
                             int64_t cap) {
  const std::string snap = eng(h)->fr_snapshot(since_seq);
  if (out != nullptr && cap > 0) {
    const int64_t n = std::min<int64_t>(cap - 1, snap.size());
    memcpy(out, snap.data(), n);
    out[n] = '\0';
  }
  return static_cast<int64_t>(snap.size());
}

void tft_q8_reduce_blocks(const int8_t* const* qs, const float* const* ss,
                          int32_t n_peers, uint64_t b0, uint64_t b1,
                          float* acc, int8_t* q_out, float* s_out) {
  alignas(64) float local[tft::kQBlock];
  for (uint64_t b = b0; b < b1; ++b) {
    float* sum = acc != nullptr ? acc + b * tft::kQBlock : local;
    tft::q8_reduce_block(qs, ss, n_peers, b, sum, q_out, s_out);
  }
}


}  // extern "C"
