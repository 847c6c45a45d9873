// Implementation of the C++ half of the deterministic chaos plane.
// See chaos.hpp for the contract and torchft_tpu/chaos.py for the Python
// twin — the grammar, the decision hash, and the visit-counter semantics
// here MUST stay bit-identical to the Python implementation (the parity is
// regression-tested from tests/test_chaos.py via ctypes).

#include "chaos.hpp"

#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include <atomic>
#include <deque>
#include <map>
#include <mutex>
#include <stdexcept>
#include <unordered_map>
#include <vector>

#include "json.hpp"
#include "net.hpp"

namespace tft {
namespace chaos {

namespace {

constexpr int64_t kStepMax = int64_t(1) << 62;
constexpr size_t kEventRing = 1024;

const char* kKindNames[] = {
    "connect_refuse", "reset",    "stall",      "partial_write", "rpc_delay",
    "rpc_drop",       "abort_heal", "ckpt_truncate", "throttle", "preempt",
};
constexpr int32_t kNumKinds = 10;

struct Rule {
  int32_t kind = -1;
  std::string plane;  // ctrl | data | heal | srv | any
  int32_t index = 0;
  bool has_peer = false, has_match = false, has_link = false;
  std::string peer, match, link;
  int64_t step_lo = -1, step_hi = kStepMax;
  double p = 1.0;
  int64_t after = 0, every = 1, count = -1;  // count -1 = unlimited
  int64_t ms = 100;
  double frac = 0.5;
  int64_t rate = int64_t(1) << 20;    // throttle: bytes/second sustained
  int64_t bucket = int64_t(1) << 16;  // throttle: burst bytes
  int64_t grace = 0;  // preempt: drain window ms (0 = TORCHFT_DRAIN_GRACE_S)
};

struct Event {
  int64_t seq = 0;
  int32_t kind = -1;
  std::string plane, site;
  int32_t rule = 0;
  int64_t visit = 0, step = -1, ms = 0;
  double frac = 0.0;
  int64_t rate = 0, bucket = 0, grace = 0;
  uint64_t ts_ns = 0;
};

// Wall-clock token bucket pacing an activated throttle site. Which visit
// activates it is the seeded pick(); the pacing itself (like a stall's
// sleep) is not part of the replayed decision sequence.
struct Bucket {
  int64_t rate = 0;  // 0 == not yet configured
  int64_t cap = 1;
  double tokens = 0.0;
  int64_t t_last_ms = 0;
};

struct State {
  uint64_t seed = 0;
  std::vector<Rule> rules;
  std::mutex mu;
  std::map<std::pair<int32_t, std::string>, uint64_t> visits;
  std::map<int32_t, int64_t> fired;
  int64_t seq = 0;
  std::deque<Event> events;
  std::unordered_map<std::string, uint64_t> site_hash;
  std::map<std::string, Bucket> buckets;  // site -> active throttle
  bool has_throttle = false;              // any kThrottle rule in `rules`
};

// Never freed once armed: hooks on detached threads may outlive main.
State* g_state = nullptr;
std::atomic<bool> g_armed{false};
std::atomic<int64_t> g_step{-1};
std::mutex g_init_mu;

// Peer -> link class, fed from TORCHFT_LINKS via tft_chaos_set_link.
// Own mutex: written at configure time, read in pick() only for rules that
// carry a link filter.
std::map<std::string, std::string>* g_links = nullptr;  // never freed
std::mutex g_links_mu;

// Serializes throttle activation (bucket check + pick + create) so
// concurrent stripe threads at one site produce a deterministic number of
// activation visits; also guards State::buckets and pacing math.
std::mutex g_throttle_mu;

// True when the rule's link filter matches the current thread's peer.
bool link_matches(const Rule& r, const std::string& peer) {
  if (!r.has_link) return true;
  std::lock_guard<std::mutex> lk(g_links_mu);
  if (g_links == nullptr) return false;
  auto it = g_links->find(peer);
  return it != g_links->end() && it->second == r.link;
}

struct Ctx {
  bool set = false;
  std::string plane, peer, match;
  // Cached "could any armed rule ever match this ctx" verdict, valid while
  // gen matches g_gen (bumped on every re-arm/disarm).
  uint64_t gen = 0;
  bool maybe = false;
};
thread_local Ctx t_ctx;

// Schedule generation: starts at 1 so a fresh ctx (gen 0) always
// recomputes; install()/disarm bump it so cached verdicts expire.
std::atomic<uint64_t> g_gen{1};

// Rules are immutable once armed and a ctx's (plane, peer, match) are
// fixed for its lifetime, so the filter scan runs once per
// (ctx, generation) instead of on every I/O call — the armed-but-inert
// fast path is then two relaxed loads and a TLS read. Step windows are
// treated as always matchable here (the step can change mid-ctx); the
// per-visit scan in pick() still applies them.
bool ctx_maybe(const State& st) {
  const uint64_t gen = g_gen.load(std::memory_order_relaxed);
  if (t_ctx.gen != gen) {
    bool m = false;
    for (const Rule& r : st.rules) {
      if (r.plane != "any" && r.plane != t_ctx.plane) continue;
      if (r.has_peer && t_ctx.peer.find(r.peer) == std::string::npos)
        continue;
      if (r.has_match && t_ctx.match.find(r.match) == std::string::npos)
        continue;
      m = true;
      break;
    }
    t_ctx.maybe = m;
    t_ctx.gen = gen;
  }
  return t_ctx.maybe;
}

int32_t kind_code(const std::string& name) {
  for (int32_t i = 0; i < kNumKinds; ++i)
    if (name == kKindNames[i]) return i;
  return -1;
}

bool valid_plane(const std::string& p) {
  return p == "ctrl" || p == "data" || p == "heal" || p == "srv" ||
         p == "any";
}

bool parse_rule(const std::string& text, int32_t index, Rule* out,
                std::string* err) {
  std::vector<std::string> parts;
  size_t start = 0;
  while (start <= text.size()) {
    size_t colon = text.find(':', start);
    if (colon == std::string::npos) colon = text.size();
    std::string piece = text.substr(start, colon - start);
    if (!piece.empty()) parts.push_back(piece);
    start = colon + 1;
  }
  if (parts.empty()) {
    *err = "empty rule";
    return false;
  }
  size_t at = parts[0].find('@');
  if (at == std::string::npos) {
    *err = "rule '" + text + "': expected <kind>@<plane>";
    return false;
  }
  Rule r;
  r.index = index;
  std::string kind = parts[0].substr(0, at);
  r.plane = parts[0].substr(at + 1);
  r.kind = kind_code(kind);
  if (r.kind < 0) {
    *err = "rule '" + text + "': unknown kind '" + kind + "'";
    return false;
  }
  if (!valid_plane(r.plane)) {
    *err = "rule '" + text + "': unknown plane '" + r.plane + "'";
    return false;
  }
  for (size_t i = 1; i < parts.size(); ++i) {
    size_t eq = parts[i].find('=');
    if (eq == std::string::npos) {
      *err = "rule '" + text + "': bad param '" + parts[i] + "'";
      return false;
    }
    std::string k = parts[i].substr(0, eq);
    std::string v = parts[i].substr(eq + 1);
    try {
      if (k == "peer") {
        r.has_peer = true;
        r.peer = v;
      } else if (k == "match") {
        r.has_match = true;
        r.match = v;
      } else if (k == "link") {
        r.has_link = true;
        r.link = v;
      } else if (k == "step") {
        size_t dash = v.find('-');
        std::string lo = dash == std::string::npos ? v : v.substr(0, dash);
        std::string hi =
            dash == std::string::npos ? "" : v.substr(dash + 1);
        r.step_lo = lo.empty() ? 0 : std::stoll(lo);
        r.step_hi = hi.empty() ? kStepMax : std::stoll(hi);
      } else if (k == "p") {
        r.p = std::stod(v);
        if (r.p < 0.0 || r.p > 1.0) throw std::runtime_error("p");
      } else if (k == "after") {
        r.after = std::stoll(v);
      } else if (k == "every") {
        r.every = std::max<int64_t>(1, std::stoll(v));
      } else if (k == "count") {
        r.count = std::stoll(v);
      } else if (k == "ms") {
        r.ms = std::stoll(v);
      } else if (k == "frac") {
        r.frac = std::stod(v);
        if (r.frac < 0.0 || r.frac > 1.0) throw std::runtime_error("frac");
      } else if (k == "rate") {
        r.rate = std::stoll(v);
        if (r.rate <= 0) throw std::runtime_error("rate");
      } else if (k == "bucket") {
        r.bucket = std::stoll(v);
        if (r.bucket <= 0) throw std::runtime_error("bucket");
      } else if (k == "grace") {
        r.grace = std::stoll(v);
        if (r.grace < 0) throw std::runtime_error("grace");
      } else {
        *err = "rule '" + text + "': unknown param '" + k + "'";
        return false;
      }
    } catch (const std::exception&) {
      *err = "rule '" + text + "': bad value in '" + parts[i] + "'";
      return false;
    }
  }
  *out = r;
  return true;
}

void log_event(const Event& ev) {
  fprintf(stderr,
          "[chaos] inject seq=%lld kind=%s plane=%s site=%s rule=%d "
          "visit=%lld step=%lld\n",
          static_cast<long long>(ev.seq), kKindNames[ev.kind],
          ev.plane.c_str(), ev.site.c_str(), ev.rule,
          static_cast<long long>(ev.visit),
          static_cast<long long>(ev.step));
}

}  // namespace

uint64_t fnv1a64(const std::string& s) {
  uint64_t h = 0xcbf29ce484222325ull;
  for (unsigned char c : s) {
    h ^= c;
    h *= 0x100000001b3ull;
  }
  return h;
}

uint64_t splitmix64(uint64_t x) {
  uint64_t z = x + 0x9E3779B97F4A7C15ull;
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ull;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBull;
  return z ^ (z >> 31);
}

uint64_t decision_hash(uint64_t seed, uint64_t rule_idx, uint64_t site_hash,
                       uint64_t visit) {
  uint64_t x = seed ^ site_hash ^ (rule_idx * 0x9E3779B97F4A7C15ull) ^
               (visit * 0xBF58476D1CE4E5B9ull);
  return splitmix64(x);
}

bool init_from_spec(const std::string& spec, std::string* err) {
  std::string trimmed = spec;
  while (!trimmed.empty() && (trimmed.back() == ' ' || trimmed.back() == '\n'))
    trimmed.pop_back();
  if (trimmed.empty()) {
    g_armed.store(false, std::memory_order_relaxed);
    g_gen.fetch_add(1, std::memory_order_relaxed);
    return true;
  }
  if (trimmed.rfind("seed:", 0) != 0) {
    *err = "TORCHFT_CHAOS must start with 'seed:<int>,spec:'";
    return false;
  }
  std::string rest = trimmed.substr(5);
  size_t comma = rest.find(',');
  if (comma == std::string::npos || rest.compare(comma + 1, 5, "spec:") != 0) {
    *err = "TORCHFT_CHAOS must be 'seed:<int>,spec:<rules>'";
    return false;
  }
  uint64_t seed = 0;
  try {
    seed = static_cast<uint64_t>(std::stoull(rest.substr(0, comma)));
  } catch (const std::exception&) {
    *err = "bad seed '" + rest.substr(0, comma) + "'";
    return false;
  }
  std::string body = rest.substr(comma + 6);
  auto st = new State();
  st->seed = seed;
  size_t start = 0;
  int32_t index = 0;
  while (start <= body.size()) {
    size_t semi = body.find(';', start);
    if (semi == std::string::npos) semi = body.size();
    std::string rtext = body.substr(start, semi - start);
    start = semi + 1;
    // Trim spaces.
    while (!rtext.empty() && rtext.front() == ' ') rtext.erase(0, 1);
    while (!rtext.empty() && rtext.back() == ' ') rtext.pop_back();
    if (rtext.empty()) continue;
    Rule r;
    if (!parse_rule(rtext, index, &r, err)) {
      delete st;
      return false;
    }
    if (r.kind == kThrottle) st->has_throttle = true;
    st->rules.push_back(r);
    ++index;
  }
  if (st->rules.empty()) {
    delete st;
    *err = "TORCHFT_CHAOS spec has no rules";
    return false;
  }
  std::lock_guard<std::mutex> lk(g_init_mu);
  delete g_state;  // safe: callers only hold g_state under armed checks
  g_state = st;
  g_armed.store(true, std::memory_order_release);
  g_gen.fetch_add(1, std::memory_order_release);
  return true;
}

void init_from_env() {
  const char* v = getenv("TORCHFT_CHAOS");
  if (v == nullptr || v[0] == '\0') return;
  std::string err;
  if (!init_from_spec(v, &err))
    fprintf(stderr, "[chaos] bad TORCHFT_CHAOS (ignored): %s\n", err.c_str());
}

bool armed() { return g_armed.load(std::memory_order_relaxed); }

void set_step(int64_t step) {
  g_step.store(step, std::memory_order_relaxed);
}

ScopedCtx::ScopedCtx(const char* plane, const std::string& peer,
                     const std::string& match)
    : prev_plane_(t_ctx.plane),
      prev_peer_(t_ctx.peer),
      prev_match_(t_ctx.match),
      prev_set_(t_ctx.set),
      prev_gen_(t_ctx.gen),
      prev_maybe_(t_ctx.maybe) {
  t_ctx.set = true;
  t_ctx.plane = plane;
  t_ctx.peer = peer;
  t_ctx.match = match;
  t_ctx.gen = 0;  // new filters: force ctx_maybe to recompute
}

ScopedCtx::~ScopedCtx() {
  t_ctx.set = prev_set_;
  t_ctx.plane = prev_plane_;
  t_ctx.peer = prev_peer_;
  t_ctx.match = prev_match_;
  t_ctx.gen = prev_gen_;
  t_ctx.maybe = prev_maybe_;
}

Decision pick(int32_t kind, const std::string& site) {
  Decision d;
  if (!g_armed.load(std::memory_order_acquire) || !t_ctx.set) return d;
  State& st = *g_state;
  if (!ctx_maybe(st)) return d;
  const int64_t step = g_step.load(std::memory_order_relaxed);
  // Lock-free pre-scan over the (immutable once armed) rule filters: if
  // nothing can match this visit, no counter moves — so skip the schedule
  // mutex entirely. Keeps an armed-but-narrowly-scoped schedule from
  // serializing every unrelated stripe thread on one global lock.
  bool any = false;
  for (const Rule& r : st.rules) {
    if (r.kind != kind) continue;
    if (r.plane != "any" && r.plane != t_ctx.plane) continue;
    if (r.has_peer && t_ctx.peer.find(r.peer) == std::string::npos) continue;
    if (r.has_match && t_ctx.match.find(r.match) == std::string::npos)
      continue;
    if (!link_matches(r, t_ctx.peer)) continue;
    if (r.step_lo >= 0 &&
        (step < 0 || step < r.step_lo || step > r.step_hi))
      continue;
    any = true;
    break;
  }
  if (!any) return d;
  Event ev;
  {
    std::lock_guard<std::mutex> lk(st.mu);
    for (const Rule& r : st.rules) {
      if (r.kind != kind) continue;
      if (r.plane != "any" && r.plane != t_ctx.plane) continue;
      if (r.has_peer && t_ctx.peer.find(r.peer) == std::string::npos)
        continue;
      if (r.has_match && t_ctx.match.find(r.match) == std::string::npos)
        continue;
      if (!link_matches(r, t_ctx.peer)) continue;
      if (r.step_lo >= 0) {  // windowed rule: needs a known step
        if (step < 0 || step < r.step_lo || step > r.step_hi) continue;
      }
      // Bump the visit counter of EVERY matching rule (mirrors chaos.py):
      // rule order must not change later rules' counters.
      auto key = std::make_pair(r.index, site);
      uint64_t visit = st.visits[key]++;
      if (d.kind >= 0) continue;  // already fired this visit
      if (static_cast<int64_t>(visit) < r.after) continue;
      uint64_t k = visit - static_cast<uint64_t>(r.after);
      if (k % static_cast<uint64_t>(r.every) != 0) continue;
      if (r.count >= 0 && st.fired[r.index] >= r.count) continue;
      if (r.p < 1.0) {
        auto it = st.site_hash.find(site);
        uint64_t sh;
        if (it != st.site_hash.end()) {
          sh = it->second;
        } else {
          sh = fnv1a64(site);
          st.site_hash.emplace(site, sh);
        }
        uint64_t h = decision_hash(st.seed, r.index, sh, visit);
        // Top 53 bits as a unit float, same as chaos.py _hash_unit.
        double unit = static_cast<double>(h >> 11) / 9007199254740992.0;
        if (unit >= r.p) continue;
      }
      st.fired[r.index]++;
      st.seq++;
      d.kind = kind;
      d.ms = r.ms;
      d.frac = r.frac;
      if (kind == kThrottle) {
        d.rate = r.rate;
        d.bucket = r.bucket;
      }
      if (kind == kPreempt) d.grace = r.grace;
      ev.seq = st.seq;
      ev.kind = kind;
      ev.plane = t_ctx.plane;
      ev.site = site;
      ev.rule = r.index;
      ev.visit = static_cast<int64_t>(visit);
      ev.step = step;
      ev.ms = r.ms;
      ev.frac = r.frac;
      ev.rate = d.rate;
      ev.bucket = d.bucket;
      ev.grace = d.grace;
      ev.ts_ns = now_realtime_ns();
      st.events.push_back(ev);
      if (st.events.size() > kEventRing) st.events.pop_front();
    }
  }
  if (d.kind >= 0) log_event(ev);
  return d;
}

namespace {

// Milliseconds a paced I/O of `len` bytes must sleep under the bucket.
int64_t bucket_consume(Bucket& b, size_t len) {
  const int64_t now = now_ms();
  b.tokens = std::min(static_cast<double>(b.cap),
                      b.tokens + static_cast<double>(now - b.t_last_ms) *
                                     static_cast<double>(b.rate) / 1000.0);
  b.t_last_ms = now;
  b.tokens -= static_cast<double>(len);
  if (b.tokens >= 0.0) return 0;
  // Cap per-call sleeps so one huge buffered write can't wedge a
  // deadline-driven transfer longer than a stall rule could.
  return std::min<int64_t>(
      static_cast<int64_t>(-b.tokens * 1000.0 / b.rate), 2000);
}

// Throttle hook body: once a seeded throttle pick fires for `site`, a token
// bucket paces every later I/O there without further picks (one journaled
// activation, visit-deterministic because activation is serialized under
// g_throttle_mu).
int64_t throttle_ms(State& st, const std::string& site, size_t len) {
  if (!st.has_throttle) return 0;  // schedules without throttle: lock-free
  std::lock_guard<std::mutex> lk(g_throttle_mu);
  auto it = st.buckets.find(site);
  if (it == st.buckets.end()) {
    Decision t = pick(kThrottle, site);
    if (t.kind < 0) return 0;
    Bucket b;
    b.rate = std::max<int64_t>(1, t.rate);
    b.cap = std::max<int64_t>(1, t.bucket);
    b.tokens = static_cast<double>(b.cap);
    b.t_last_ms = now_ms();
    it = st.buckets.emplace(site, b).first;
  }
  return bucket_consume(it->second, len);
}

}  // namespace

Decision on_write(int fd, size_t len) {
  (void)fd;
  Decision none;
  if (!g_armed.load(std::memory_order_acquire) || !t_ctx.set) return none;
  // Skip the site-string allocation and the pick() scans when the armed
  // schedule cannot touch this ctx.
  if (!ctx_maybe(*g_state)) return none;
  const std::string site =
      "send:" + (t_ctx.peer.empty() ? std::string("?") : t_ctx.peer);
  int64_t tms = throttle_ms(*g_state, site, len);
  if (tms > 0) sleep_ms(tms);
  Decision s = pick(kStall, site);
  if (s.kind == kStall) sleep_ms(s.ms);
  Decision pw = pick(kPartialWrite, site);
  if (pw.kind >= 0) return pw;
  return pick(kReset, site);
}

Decision on_read(int fd, size_t len) {
  (void)fd;
  Decision none;
  if (!g_armed.load(std::memory_order_acquire) || !t_ctx.set) return none;
  if (!ctx_maybe(*g_state)) return none;
  const std::string site =
      "recv:" + (t_ctx.peer.empty() ? std::string("?") : t_ctx.peer);
  int64_t tms = throttle_ms(*g_state, site, len);
  if (tms > 0) sleep_ms(tms);
  Decision s = pick(kStall, site);
  if (s.kind == kStall) sleep_ms(s.ms);
  return pick(kReset, site);
}

bool on_connect(const std::string& host, int port) {
  if (!g_armed.load(std::memory_order_relaxed) || !t_ctx.set) return false;
  std::string peer = t_ctx.peer.empty()
                         ? host + ":" + std::to_string(port)
                         : t_ctx.peer;
  const std::string site = "connect:" + peer;
  return pick(kConnectRefuse, site).kind >= 0;
}

void set_link_class(const std::string& peer, const std::string& cls) {
  std::lock_guard<std::mutex> lk(g_links_mu);
  if (g_links == nullptr) g_links = new std::map<std::string, std::string>();
  (*g_links)[peer] = cls;
}

double backoff_unit(const std::string& key, uint64_t attempt) {
  uint64_t seed = 0;
  if (g_armed.load(std::memory_order_acquire)) seed = g_state->seed;
  uint64_t h =
      splitmix64(seed ^ fnv1a64(key) ^ (attempt * 0x9E3779B97F4A7C15ull));
  // Top 53 bits as a unit float, same as chaos.py _hash_unit.
  return static_cast<double>(h >> 11) / 9007199254740992.0;
}

bool server_rpc(const std::string& rpc_type) {
  if (!g_armed.load(std::memory_order_relaxed)) return true;
  ScopedCtx ctx("srv", "", rpc_type);
  const std::string site = "srv:" + rpc_type;
  Decision d = pick(kRpcDelay, site);
  if (d.kind == kRpcDelay) sleep_ms(d.ms);
  if (pick(kRpcDrop, site).kind >= 0) return false;
  if (pick(kReset, site).kind >= 0) return false;
  return true;
}

}  // namespace chaos
}  // namespace tft

extern "C" {

int32_t tft_chaos_init(const char* spec) {
  std::string err;
  std::string s = spec == nullptr ? "" : spec;
  if (s.empty()) {
    tft::chaos::init_from_env();
    return tft::chaos::armed() ? 0 : 0;
  }
  if (!tft::chaos::init_from_spec(s, &err)) {
    fprintf(stderr, "[chaos] bad spec: %s\n", err.c_str());
    return -1;
  }
  return 0;
}

int32_t tft_chaos_armed() { return tft::chaos::armed() ? 1 : 0; }

void tft_chaos_set_step(int64_t step) { tft::chaos::set_step(step); }

void tft_chaos_set_link(const char* peer, const char* cls) {
  if (peer == nullptr || cls == nullptr) return;
  tft::chaos::set_link_class(peer, cls);
}

int64_t tft_chaos_seq() {
  using namespace tft::chaos;
  if (!armed()) return 0;
  // g_state is stable once armed (re-init replaces the pointer under
  // g_init_mu; hooks read the old or the new — both valid objects).
  State* st = g_state;
  std::lock_guard<std::mutex> lk(st->mu);
  return st->seq;
}

int64_t tft_chaos_snapshot(int64_t since_seq, char* buf, int64_t cap) {
  using namespace tft;
  using namespace tft::chaos;
  Json root;
  Json events = Json::array();
  int64_t seq = 0;
  if (armed()) {
    State* st = g_state;
    std::lock_guard<std::mutex> lk(st->mu);
    seq = st->seq;
    for (const Event& ev : st->events) {
      if (ev.seq <= since_seq) continue;
      Json je;
      je["seq"] = Json::of(ev.seq);
      je["kind"] = Json::of(kKindNames[ev.kind]);
      je["plane"] = Json::of(ev.plane);
      je["site"] = Json::of(ev.site);
      je["rule"] = Json::of(static_cast<int64_t>(ev.rule));
      je["visit"] = Json::of(ev.visit);
      je["step"] = Json::of(ev.step);
      je["ms"] = Json::of(ev.ms);
      je["frac"] = Json::of(ev.frac);
      je["rate"] = Json::of(ev.rate);
      je["bucket"] = Json::of(ev.bucket);
      je["grace"] = Json::of(ev.grace);
      je["ts_ns"] = Json::of(static_cast<int64_t>(ev.ts_ns));
      events.push(std::move(je));
    }
  }
  root["seq"] = Json::of(seq);
  root["events"] = std::move(events);
  std::string out = root.dump();
  int64_t need = static_cast<int64_t>(out.size()) + 1;
  if (need > cap) return -need;
  memcpy(buf, out.data(), out.size());
  buf[out.size()] = '\0';
  return static_cast<int64_t>(out.size());
}

}  // extern "C"
