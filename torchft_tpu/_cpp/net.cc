#include "net.hpp"

#include <arpa/inet.h>
#include "chaos.hpp"
#include <errno.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <string.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <chrono>
#include <thread>

namespace tft {

int64_t now_ms() {
  return std::chrono::duration_cast<std::chrono::milliseconds>(
             std::chrono::system_clock::now().time_since_epoch())
      .count();
}

int64_t now_us_steady() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

uint64_t now_realtime_ns() {
  timespec ts{};
  clock_gettime(CLOCK_REALTIME, &ts);
  return static_cast<uint64_t>(ts.tv_sec) * 1000000000ull +
         static_cast<uint64_t>(ts.tv_nsec);
}

// Thread-local: a stripe job deltas this around one transfer; no other
// thread's misses can leak into the reading.
static thread_local uint64_t g_spin_count = 0;

uint64_t net_spin_count() { return g_spin_count; }

void sleep_ms(int64_t ms) {
  std::this_thread::sleep_for(std::chrono::milliseconds(ms));
}

static void set_nonblocking(int fd, bool nb) {
  int flags = fcntl(fd, F_GETFL, 0);
  if (nb)
    fcntl(fd, F_SETFL, flags | O_NONBLOCK);
  else
    fcntl(fd, F_SETFL, flags & ~O_NONBLOCK);
}

static void set_common_opts(int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  // TCP keep-alives stand in for the reference's HTTP2 keep-alives
  // (net.rs:13-18: 60s interval / 20s timeout).
  setsockopt(fd, SOL_SOCKET, SO_KEEPALIVE, &one, sizeof(one));
#ifdef TCP_KEEPIDLE
  int idle = 60, intvl = 20, cnt = 3;
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPIDLE, &idle, sizeof(idle));
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPINTVL, &intvl, sizeof(intvl));
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPCNT, &cnt, sizeof(cnt));
#endif
}

int tcp_listen(const std::string& host, int port, int backlog) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (host.empty() || host == "0.0.0.0" || host == "::") {
    addr.sin_addr.s_addr = INADDR_ANY;
  } else if (inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
    // Resolve hostname.
    addrinfo hints{}, *res = nullptr;
    hints.ai_family = AF_INET;
    hints.ai_socktype = SOCK_STREAM;
    if (getaddrinfo(host.c_str(), nullptr, &hints, &res) != 0 || !res) {
      close(fd);
      return -1;
    }
    addr.sin_addr = reinterpret_cast<sockaddr_in*>(res->ai_addr)->sin_addr;
    freeaddrinfo(res);
  }
  if (bind(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 ||
      listen(fd, backlog) != 0) {
    close(fd);
    return -1;
  }
  return fd;
}

int bound_port(int fd) {
  sockaddr_in addr{};
  socklen_t len = sizeof(addr);
  if (getsockname(fd, reinterpret_cast<sockaddr*>(&addr), &len) != 0) return -1;
  return ntohs(addr.sin_port);
}

int tcp_accept(int listen_fd, int timeout_ms) {
  pollfd pfd{listen_fd, POLLIN, 0};
  int rc = poll(&pfd, 1, timeout_ms);
  if (rc <= 0) return -1;
  int fd = accept(listen_fd, nullptr, nullptr);
  if (fd >= 0) set_common_opts(fd);
  return fd;
}

int tcp_connect(const std::string& host, int port, int64_t timeout_ms) {
  if (chaos::armed() && chaos::on_connect(host, port)) return -1;
  addrinfo hints{}, *res = nullptr;
  hints.ai_family = AF_INET;
  hints.ai_socktype = SOCK_STREAM;
  std::string h = host.empty() ? "127.0.0.1" : host;
  if (h == "0.0.0.0" || h == "::") h = "127.0.0.1";
  if (getaddrinfo(h.c_str(), std::to_string(port).c_str(), &hints, &res) != 0 ||
      !res)
    return -1;
  int fd = socket(res->ai_family, res->ai_socktype, res->ai_protocol);
  if (fd < 0) {
    freeaddrinfo(res);
    return -1;
  }
  set_nonblocking(fd, true);
  int rc = connect(fd, res->ai_addr, res->ai_addrlen);
  freeaddrinfo(res);
  if (rc != 0 && errno != EINPROGRESS) {
    close(fd);
    return -1;
  }
  if (rc != 0) {
    pollfd pfd{fd, POLLOUT, 0};
    rc = poll(&pfd, 1, static_cast<int>(timeout_ms));
    if (rc <= 0) {
      close(fd);
      return -1;
    }
    int err = 0;
    socklen_t len = sizeof(err);
    getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &len);
    if (err != 0) {
      close(fd);
      return -1;
    }
  }
  set_nonblocking(fd, false);
  set_common_opts(fd);
  return fd;
}

int tcp_connect_retry(const std::string& host, int port, int64_t timeout_ms,
                      int64_t attempt_ms) {
  // Exponential backoff mirroring reference net.rs/retry.rs:
  // 100ms initial, x1.5 multiplier, 10s max interval, until deadline.
  // Full jitter (seeded, deterministic per (host:port, attempt) — see
  // chaos::backoff_unit) keeps a fleet of reconnecting peers from retrying
  // in lockstep after a partition heals.
  int64_t deadline = now_ms() + timeout_ms;
  int64_t backoff = 100;
  if (attempt_ms <= 0) attempt_ms = 5000;
  const std::string key = host + ":" + std::to_string(port);
  uint64_t attempt = 0;
  while (true) {
    int64_t remaining = deadline - now_ms();
    if (remaining <= 0) return -1;
    int fd = tcp_connect(host, port, std::min<int64_t>(remaining, attempt_ms));
    if (fd >= 0) return fd;
    remaining = deadline - now_ms();
    if (remaining <= 0) return -1;
    const int64_t cap = std::min(backoff, remaining);
    const int64_t jittered = std::max<int64_t>(
        10, static_cast<int64_t>(chaos::backoff_unit(key, attempt) *
                                 static_cast<double>(cap)));
    sleep_ms(std::min(jittered, remaining));
    backoff = std::min<int64_t>(static_cast<int64_t>(backoff * 1.5), 10000);
    ++attempt;
  }
}

bool split_host_port(const std::string& addr_in, std::string* host, int* port) {
  // Accept scheme-prefixed URLs (the reference's TORCHFT_LIGHTHOUSE is
  // e.g. http://host:29510) and trailing slashes.
  std::string addr = addr_in;
  size_t scheme = addr.find("://");
  if (scheme != std::string::npos) addr = addr.substr(scheme + 3);
  if (!addr.empty() && addr[0] != '[') {  // keep [v6] brackets intact
    size_t slash = addr.find('/');
    if (slash != std::string::npos) addr = addr.substr(0, slash);
  }
  while (!addr.empty() && addr.back() == '/') addr.pop_back();
  if (addr.empty()) return false;
  size_t colon;
  if (addr[0] == '[') {  // [v6]:port
    size_t close_b = addr.find(']');
    if (close_b == std::string::npos || close_b + 1 >= addr.size() ||
        addr[close_b + 1] != ':')
      return false;
    *host = addr.substr(1, close_b - 1);
    colon = close_b + 1;
  } else {
    colon = addr.rfind(':');
    if (colon == std::string::npos) return false;
    *host = addr.substr(0, colon);
  }
  try {
    *port = std::stoi(addr.substr(colon + 1));
  } catch (...) {
    return false;
  }
  if (*host == "::" || host->empty()) *host = "127.0.0.1";
  return true;
}

static bool wait_fd(int fd, short events, int64_t deadline) {
  int64_t remaining = deadline - now_ms();
  if (remaining < 0) remaining = 0;
  pollfd pfd{fd, events, 0};
  int rc = poll(&pfd, 1, static_cast<int>(remaining));
  return rc > 0 && (pfd.revents & (events | POLLHUP | POLLERR));
}

static bool write_all_inner(int fd, const char* data, size_t len,
                            int64_t deadline) {
  size_t off = 0;
  while (off < len) {
    // Optimistic fast path: MSG_DONTWAIT keeps the call non-blocking on a
    // blocking fd, so we only pay a poll() when the socket buffer is full.
    ssize_t n = send(fd, data + off, len - off, MSG_NOSIGNAL | MSG_DONTWAIT);
    if (n < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        ++g_spin_count;
        if (!wait_fd(fd, POLLOUT, deadline)) return false;
        continue;
      }
      return false;
    }
    off += static_cast<size_t>(n);
  }
  return true;
}

bool write_all(int fd, const char* data, size_t len, int64_t timeout_ms) {
  int64_t deadline = now_ms() + timeout_ms;
  if (chaos::armed()) {
    chaos::Decision d = chaos::on_write(fd, len);
    if (d.kind == chaos::kPartialWrite) {
      // Write a prefix through the REAL path, then tear the connection:
      // the peer sees a torn transfer, this side reports failure.
      size_t cut = static_cast<size_t>(static_cast<double>(len) * d.frac);
      if (cut > 0) write_all_inner(fd, data, cut, deadline);
      shutdown(fd, SHUT_RDWR);
      return false;
    }
    if (d.kind == chaos::kReset) {
      shutdown(fd, SHUT_RDWR);
      return false;
    }
  }
  return write_all_inner(fd, data, len, deadline);
}

static bool read_all(int fd, char* data, size_t len, int64_t deadline) {
  size_t off = 0;
  while (off < len) {
    ssize_t n = recv(fd, data + off, len - off, MSG_DONTWAIT);
    if (n < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        ++g_spin_count;
        if (!wait_fd(fd, POLLIN, deadline)) return false;
        continue;
      }
      return false;
    }
    if (n == 0) return false;  // peer closed
    off += static_cast<size_t>(n);
  }
  return true;
}

bool read_exact(int fd, char* data, size_t len, int64_t timeout_ms) {
  int64_t deadline = now_ms() + timeout_ms;
  if (chaos::armed()) {
    chaos::Decision d = chaos::on_read(fd, len);
    if (d.kind == chaos::kReset) {
      shutdown(fd, SHUT_RDWR);
      return false;
    }
  }
  return read_all(fd, data, len, deadline);
}

bool send_frame(int fd, const std::string& payload, int64_t timeout_ms) {
  uint32_t len = htonl(static_cast<uint32_t>(payload.size()));
  std::string buf(reinterpret_cast<char*>(&len), 4);
  buf += payload;
  return write_all(fd, buf.data(), buf.size(), timeout_ms);
}

bool recv_frame(int fd, std::string* out, int64_t timeout_ms) {
  int64_t deadline = now_ms() + timeout_ms;
  uint32_t len_be = 0;
  if (!read_all(fd, reinterpret_cast<char*>(&len_be), 4, deadline)) return false;
  uint32_t len = ntohl(len_be);
  if (len > (1u << 30)) return false;  // 1 GiB sanity cap
  out->resize(len);
  return read_all(fd, out->data(), len, deadline);
}

bool call_json(int fd, const Json& req, Json* resp, int64_t timeout_ms) {
  int64_t deadline = now_ms() + timeout_ms;
  if (!send_frame(fd, req.dump(), timeout_ms)) return false;
  std::string raw;
  int64_t remaining = deadline - now_ms();
  if (remaining < 1) remaining = 1;
  if (!recv_frame(fd, &raw, remaining)) return false;
  return Json::parse(raw, resp);
}

bool call_json_addr(const std::string& addr, const Json& req, Json* resp,
                    int64_t timeout_ms) {
  std::string host;
  int port = 0;
  if (!split_host_port(addr, &host, &port)) return false;
  int fd = tcp_connect(host, port, timeout_ms);
  if (fd < 0) return false;
  bool ok = call_json(fd, req, resp, timeout_ms);
  close(fd);
  return ok;
}

int peek_bytes(int fd, char* buf, int n, int timeout_ms) {
  pollfd pfd{fd, POLLIN, 0};
  if (poll(&pfd, 1, timeout_ms) <= 0) return -1;
  return static_cast<int>(recv(fd, buf, n, MSG_PEEK));
}

void watch_parent(int64_t parent_pid, std::function<void()> on_death) {
  std::thread([parent_pid, on_death = std::move(on_death)] {
    while (true) {
      if (static_cast<int64_t>(getppid()) != parent_pid) {
        fprintf(stderr, "parent %lld died; exiting\n",
                static_cast<long long>(parent_pid));
        if (on_death) on_death();
        _exit(2);
      }
      sleep_ms(500);
    }
  }).detach();
}

std::string read_http_request(int fd, int timeout_ms) {
  // Reads headers up to the blank line (control-plane GET/POSTs carry no body
  // we care about).
  int64_t deadline = now_ms() + timeout_ms;
  std::string req;
  char c;
  while (req.size() < 65536) {
    if (!wait_fd(fd, POLLIN, deadline)) break;
    ssize_t n = recv(fd, &c, 1, 0);
    if (n <= 0) break;
    req += c;
    if (req.size() >= 4 && req.compare(req.size() - 4, 4, "\r\n\r\n") == 0)
      break;
    if (req.size() >= 2 && req.compare(req.size() - 2, 2, "\n\n") == 0) break;
  }
  return req;
}

}  // namespace tft
