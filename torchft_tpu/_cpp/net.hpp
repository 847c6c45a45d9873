// TCP helpers for the torchft-tpu control plane: listen/connect with timeouts,
// length-prefixed JSON frames, and exponential-backoff connect retry.
//
// Capability parity with the reference's src/net.rs:10-36 (keep-alive connect
// with exponential backoff 100ms -> 10s x1.5) and src/retry.rs, minus gRPC:
// the wire format here is [u32 big-endian length][JSON payload].
#pragma once

#include <cstdint>
#include <functional>
#include <string>

#include "json.hpp"

namespace tft {

// Returns ms since epoch (steady for intervals where it matters we use the
// same clock consistently).
int64_t now_ms();
// Steady-clock microseconds: for a latency, a gap or a round trip, which
// must not jump when the wall clock steps.
int64_t now_us_steady();

// Wall-clock nanoseconds (CLOCK_REALTIME), chosen over CLOCK_MONOTONIC so
// timestamps recorded in the data plane align with the Python journal's
// time.time() records for cross-plane trace assembly.
uint64_t now_realtime_ns();

// Count of MSG_DONTWAIT misses (EAGAIN -> poll waits) taken by the calling
// thread inside write_all/read_exact since thread start. Thread-local so a
// transfer job can delta it around one stripe without synchronization.
uint64_t net_spin_count();

// Starts a detached watchdog thread that _exit(2)s this process as soon as
// getppid() != parent_pid (poll every 500 ms). Used by the control-plane
// binaries (--parent-pid): a server orphaned by `kill -9` of its trainer
// would keep heartbeating and wedge the lighthouse's split-brain majority
// guard. Polling the ppid is immune to the PR_SET_PDEATHSIG pitfalls
// (fires on spawning-*thread* exit; exec-window race under subreapers) —
// if the parent died before this call, getppid() already differs and the
// first poll exits. `on_death` (optional) runs before the exit — the
// manager binary uses it to send a lighthouse leave on behalf of its dead
// trainer, cutting the survivors' stall from heartbeat expiry (~5 s) to
// one watchdog poll (~0.5 s).
void watch_parent(int64_t parent_pid, std::function<void()> on_death = nullptr);

// Sleep helper.
void sleep_ms(int64_t ms);

// Creates a listening socket bound to `host` (empty or "0.0.0.0" = any) and
// `port` (0 = ephemeral). Returns fd >= 0 or -1 on error (errno set).
int tcp_listen(const std::string& host, int port, int backlog = 128);

// Port a listening fd is bound to, or -1.
int bound_port(int fd);

// Accept with timeout. Returns client fd, -1 on timeout/error.
int tcp_accept(int listen_fd, int timeout_ms);

// Connect to host:port with a timeout. Returns fd or -1.
int tcp_connect(const std::string& host, int port, int64_t timeout_ms);

// Connect with exponential backoff retries until deadline, mirroring the
// reference's net.rs connect(): 100ms initial, x1.5, max 10s interval —
// with seeded full jitter on each sleep (chaos::backoff_unit) so mass
// reconnects after a partition heal don't stampede in lockstep.
// `attempt_ms` clamps each individual connect attempt (link-policy budget:
// WAN links legitimately need more than the old hardcoded 5000, local
// links much less).
int tcp_connect_retry(const std::string& host, int port, int64_t timeout_ms,
                      int64_t attempt_ms = 5000);

// Splits "host:port" (also accepts "[v6]:port"). Returns false on parse error.
bool split_host_port(const std::string& addr, std::string* host, int* port);

// Sends a length-prefixed frame. Returns false on error/timeout.
bool send_frame(int fd, const std::string& payload, int64_t timeout_ms);

// Receives a length-prefixed frame into *out. Returns false on error/timeout.
bool recv_frame(int fd, std::string* out, int64_t timeout_ms);

// Convenience: send `req` JSON, receive one JSON reply. False on any failure.
bool call_json(int fd, const Json& req, Json* resp, int64_t timeout_ms);

// One-shot: connect, call, close. False on any failure.
bool call_json_addr(const std::string& addr, const Json& req, Json* resp,
                    int64_t timeout_ms);

// Peeks at up to n bytes without consuming (for HTTP-vs-frame sniffing).
// Returns number of bytes peeked, or -1.
int peek_bytes(int fd, char* buf, int n, int timeout_ms);

// Reads until the socket closes or `max` bytes (for HTTP requests).
std::string read_http_request(int fd, int timeout_ms);

// Writes all bytes. Returns false on error.
bool write_all(int fd, const char* data, size_t len, int64_t timeout_ms);

// Reads exactly `len` bytes (raw, no framing). Returns false on
// error/timeout/peer close. The bulk-transfer twin of write_all, used by the
// collective engine for striped tensor payloads whose sizes both sides
// already know (no per-chunk frame header on the hot path).
bool read_exact(int fd, char* data, size_t len, int64_t timeout_ms);

}  // namespace tft
