#include "rpc.h"

#include <arpa/inet.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <chrono>
#include <cstdio>
#include <cstring>
#include <random>

#include "blackbox.h"     // crash-durable rpc.serve breadcrumbs
#include "faultinject.h"  // env-gated injection points (torn frames, delays)
#include "lathist.h"      // rpc.serve latency histogram
#include "profiler.h"     // always-on sampling (rpc serve / quorum fan-out)

namespace tft {

int64_t now_ms() {
  using namespace std::chrono;
  return duration_cast<milliseconds>(steady_clock::now().time_since_epoch())
      .count();
}

std::string errno_str(int e) {
  char buf[128];
  // GNU strerror_r: fills buf OR returns a pointer to an immutable
  // static string — either way no shared mutable state (see rpc.h)
  return std::string(strerror_r(e, buf, sizeof(buf)));
}

static void set_keepalive(int fd) {
  int on = 1;
  setsockopt(fd, SOL_SOCKET, SO_KEEPALIVE, &on, sizeof(on));
  // Mirror the reference's HTTP2 keepalive cadence (60s interval / 20s
  // timeout, src/net.rs:11-16) at the TCP level.
  int idle = 60, intvl = 20, cnt = 3;
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPIDLE, &idle, sizeof(idle));
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPINTVL, &intvl, sizeof(intvl));
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPCNT, &cnt, sizeof(cnt));
  int nodelay = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &nodelay, sizeof(nodelay));
}

bool parse_addr(const std::string& addr, std::string* host, int* port) {
  std::string a = addr;
  for (const char* scheme : {"http://", "tft://", "tcp://"}) {
    if (a.rfind(scheme, 0) == 0) {
      a = a.substr(strlen(scheme));
      break;
    }
  }
  // strip any trailing path
  auto slash = a.find('/');
  if (slash != std::string::npos) a = a.substr(0, slash);
  // [v6]:port or host:port
  if (!a.empty() && a[0] == '[') {
    auto close = a.find(']');
    if (close == std::string::npos) return false;
    *host = a.substr(1, close - 1);
    if (close + 1 >= a.size() || a[close + 1] != ':') return false;
    *port = atoi(a.c_str() + close + 2);
    return true;
  }
  auto colon = a.rfind(':');
  if (colon == std::string::npos) return false;
  *host = a.substr(0, colon);
  *port = atoi(a.c_str() + colon + 1);
  return *port > 0 || a.substr(colon + 1) == "0";
}

int tcp_listen(const std::string& bind_addr, std::string* err) {
  std::string host;
  int port = 0;
  if (!parse_addr(bind_addr, &host, &port)) {
    if (err) *err = "bad bind address: " + bind_addr;
    return -1;
  }
  // Prefer IPv6 dual-stack like the reference's default [::] bind.
  bool v6 = host.empty() || host == "::" || host.find(':') != std::string::npos;
  int fd = socket(v6 ? AF_INET6 : AF_INET, SOCK_STREAM, 0);
  if (fd < 0) {
    // e.g. "[::]:0" on a host without IPv6: name the address asked for
    if (err) *err = "socket for " + bind_addr + ": " + errno_str(errno);
    return -1;
  }
  int on = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &on, sizeof(on));
  int rc;
  if (v6) {
    int off = 0;
    setsockopt(fd, IPPROTO_IPV6, IPV6_V6ONLY, &off, sizeof(off));
    sockaddr_in6 sa{};
    sa.sin6_family = AF_INET6;
    sa.sin6_port = htons((uint16_t)port);
    if (host.empty() || host == "::")
      sa.sin6_addr = in6addr_any;
    else if (inet_pton(AF_INET6, host.c_str(), &sa.sin6_addr) != 1) {
      if (err) *err = "bad v6 address: " + host;
      close(fd);
      return -1;
    }
    rc = bind(fd, (sockaddr*)&sa, sizeof(sa));
  } else {
    sockaddr_in sa{};
    sa.sin_family = AF_INET;
    sa.sin_port = htons((uint16_t)port);
    if (host.empty() || host == "0.0.0.0")
      sa.sin_addr.s_addr = INADDR_ANY;
    else if (inet_pton(AF_INET, host.c_str(), &sa.sin_addr) != 1) {
      // resolve hostname
      addrinfo hints{}, *res = nullptr;
      hints.ai_family = AF_INET;
      hints.ai_socktype = SOCK_STREAM;
      if (getaddrinfo(host.c_str(), nullptr, &hints, &res) != 0 || !res) {
        if (err) *err = "cannot resolve: " + host;
        close(fd);
        return -1;
      }
      sa.sin_addr = ((sockaddr_in*)res->ai_addr)->sin_addr;
      freeaddrinfo(res);
    }
    rc = bind(fd, (sockaddr*)&sa, sizeof(sa));
  }
  if (rc != 0 || listen(fd, 1024) != 0) {
    if (err) *err = "bind/listen " + bind_addr + ": " + errno_str(errno);
    close(fd);
    return -1;
  }
  return fd;
}

int listen_port(int fd) {
  sockaddr_storage ss{};
  socklen_t len = sizeof(ss);
  if (getsockname(fd, (sockaddr*)&ss, &len) != 0) return 0;
  if (ss.ss_family == AF_INET6) return ntohs(((sockaddr_in6*)&ss)->sin6_port);
  return ntohs(((sockaddr_in*)&ss)->sin_port);
}

int tcp_connect(const std::string& host, int port, int64_t timeout_ms,
                std::string* err) {
  addrinfo hints{}, *res = nullptr;
  hints.ai_family = AF_UNSPEC;
  hints.ai_socktype = SOCK_STREAM;
  char portbuf[16];
  snprintf(portbuf, sizeof(portbuf), "%d", port);
  std::string h = host.empty() ? "localhost" : host;
  int rc = getaddrinfo(h.c_str(), portbuf, &hints, &res);
  if (rc != 0 || !res) {
    if (err) *err = "resolve " + h + ": " + gai_strerror(rc);
    return -1;
  }
  int fd = -1;
  for (addrinfo* ai = res; ai; ai = ai->ai_next) {
    fd = socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
    if (fd < 0) continue;
    // non-blocking connect with timeout
    int flags = fcntl(fd, F_GETFL, 0);
    fcntl(fd, F_SETFL, flags | O_NONBLOCK);
    rc = connect(fd, ai->ai_addr, ai->ai_addrlen);
    if (rc != 0 && errno == EINPROGRESS) {
      pollfd pfd{fd, POLLOUT, 0};
      rc = poll(&pfd, 1, (int)timeout_ms);
      if (rc == 1) {
        int soerr = 0;
        socklen_t slen = sizeof(soerr);
        getsockopt(fd, SOL_SOCKET, SO_ERROR, &soerr, &slen);
        rc = soerr == 0 ? 0 : -1;
        if (soerr != 0 && err) *err = errno_str(soerr);
      } else {
        rc = -1;
        if (err) *err = "connect timeout";
      }
    } else if (rc != 0 && err) {
      *err = errno_str(errno);
    }
    if (rc == 0) {
      fcntl(fd, F_SETFL, flags);  // back to blocking
      set_keepalive(fd);
      break;
    }
    close(fd);
    fd = -1;
  }
  freeaddrinfo(res);
  if (fd < 0 && err && err->empty()) *err = "connect failed";
  return fd;
}

bool read_exact(int fd, void* buf, size_t n, int64_t deadline_abs_ms) {
  char* p = (char*)buf;
  while (n > 0) {
    if (deadline_abs_ms > 0) {
      int64_t left = deadline_abs_ms - now_ms();
      if (left <= 0) return false;
      pollfd pfd{fd, POLLIN, 0};
      int rc = poll(&pfd, 1, (int)std::min<int64_t>(left, 60000));
      if (rc == 0) continue;  // re-check deadline
      if (rc < 0) {
        if (errno == EINTR) continue;
        return false;
      }
    }
    ssize_t k = recv(fd, p, n, 0);
    if (k == 0) return false;
    if (k < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    p += k;
    n -= (size_t)k;
  }
  return true;
}

bool write_all(int fd, const void* buf, size_t n) {
  const char* p = (const char*)buf;
  while (n > 0) {
    ssize_t k = send(fd, p, n, MSG_NOSIGNAL);
    if (k < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    p += k;
    n -= (size_t)k;
  }
  return true;
}

// ---- server --------------------------------------------------------------

bool RpcServer::start(const std::string& bind_addr, RpcHandler handler,
                      HttpHandler http_handler, std::string* err) {
  listen_fd_ = tcp_listen(bind_addr, err);
  if (listen_fd_ < 0) return false;
  port_ = listen_port(listen_fd_);
  handler_ = std::move(handler);
  http_handler_ = std::move(http_handler);
  running_.store(true);
  accept_thread_ = std::thread([this] { accept_loop(); });
  return true;
}

void RpcServer::shutdown() {
  if (!running_.exchange(false)) return;
  ::shutdown(listen_fd_, SHUT_RDWR);
  close(listen_fd_);
  {
    std::lock_guard<std::mutex> g(conns_mu_);
    for (int fd : conns_) ::shutdown(fd, SHUT_RDWR);
  }
  if (accept_thread_.joinable()) accept_thread_.join();
  // Join all connection threads. Owners must cancel any in-handler blocking
  // waits (cv broadcasts, client aborts) *before* calling this so the join
  // completes promptly; once it returns, no thread touches handler state.
  std::map<uint64_t, std::thread> threads;
  {
    std::lock_guard<std::mutex> g(conns_mu_);
    threads.swap(conn_threads_);
  }
  for (auto& [id, t] : threads)
    if (t.joinable()) t.join();
}

void RpcServer::accept_loop() {
  while (running_.load()) {
    int fd = accept(listen_fd_, nullptr, nullptr);
    if (fd < 0) {
      if (errno == EINTR) continue;
      break;  // listener closed
    }
    set_keepalive(fd);
    std::vector<std::thread> reaped;
    {
      std::lock_guard<std::mutex> g(conns_mu_);
      // Reap threads for connections that already finished (join is
      // instant once a thread has announced itself in finished_threads_).
      for (uint64_t id : finished_threads_) {
        auto it = conn_threads_.find(id);
        if (it != conn_threads_.end()) {
          reaped.push_back(std::move(it->second));
          conn_threads_.erase(it);
        }
      }
      finished_threads_.clear();
      conns_.insert(fd);
      uint64_t id = next_thread_id_++;
      conn_threads_.emplace(id, std::thread([this, fd, id] {
        // one guard covers the whole connection: rpc dispatch AND the
        // ManagerSrv quorum fan-out both run on these threads, so their
        // stacks land in the "rpc.serve" collapsed-stack bucket
        prof::ThreadGuard prof_guard("rpc.serve");
        serve_conn(fd);
        std::lock_guard<std::mutex> g2(conns_mu_);
        conns_.erase(fd);
        close(fd);
        finished_threads_.push_back(id);
      }));
    }
    for (auto& t : reaped)
      if (t.joinable()) t.join();
  }
}

static std::string http_error(int code, const std::string& msg) {
  char head[128];
  snprintf(head, sizeof(head),
           "HTTP/1.1 %d Error\r\nContent-Type: text/plain\r\nContent-Length: "
           "%zu\r\nConnection: close\r\n\r\n",
           code, msg.size());
  return std::string(head) + msg;
}

void RpcServer::serve_conn(int fd) {
  char magic[4];
  if (!read_exact(fd, magic, 4, 0)) return;
  if (memcmp(magic, "TFT1", 4) != 0) {
    // Plain HTTP (dashboard / status) on the same port, like the
    // reference's accept_http1 tonic server (src/lighthouse.rs:349-355).
    std::string req(magic, 4);
    char c;
    // read until end of headers (or 64KB cap)
    while (req.size() < 65536 &&
           req.find("\r\n\r\n") == std::string::npos) {
      ssize_t k = recv(fd, &c, 1, 0);
      if (k <= 0) return;
      req.push_back(c);
    }
    auto sp1 = req.find(' ');
    auto sp2 = req.find(' ', sp1 + 1);
    if (sp1 == std::string::npos || sp2 == std::string::npos) {
      std::string resp = http_error(400, "bad request");
      write_all(fd, resp.data(), resp.size());
      return;
    }
    std::string method = req.substr(0, sp1);
    std::string path = req.substr(sp1 + 1, sp2 - sp1 - 1);
    std::string resp;
    if (http_handler_) {
      try {
        resp = http_handler_(method, path);
      } catch (const std::exception& e) {
        resp = http_error(500, std::string("Something went wrong: ") + e.what());
      }
    } else {
      resp = http_error(404, "not found");
    }
    write_all(fd, resp.data(), resp.size());
    return;
  }
  // Frame loop.
  while (running_.load()) {
    uint8_t lenbuf[4];
    if (!read_exact(fd, lenbuf, 4, 0)) return;
    uint32_t len = (uint32_t)lenbuf[0] | ((uint32_t)lenbuf[1] << 8) |
                   ((uint32_t)lenbuf[2] << 16) | ((uint32_t)lenbuf[3] << 24);
    if (len > (1u << 30)) return;  // 1GB sanity cap
    std::string payload(len, '\0');
    if (!read_exact(fd, payload.data(), len, 0)) return;

    Value resp = Value::M();
    // rpc.serve distribution: dispatch + handler time, error paths
    // included (socket reads excluded; a long-poll quorum wait is part
    // of the handler by design and shows up here — the serve tail IS
    // the control plane's latency story)
    int64_t serve_t0 = lathist::now_ns();
    try {
      Value req = decode(payload);
      std::string method = req.gets("_m");
      int64_t timeout_ms = req.geti("_d", 60000);
      int64_t deadline = now_ms() + timeout_ms;
      // env-gated injection: stretch this method's server-side handling
      // (e.g. TORCHFT_FI_SRV_DELAY=mgr.should_commit:200 is a 200 ms
      // commit-vote RTT)
      static const fi::MethodSpec fi_dly =
          fi::parse_method("TORCHFT_FI_SRV_DELAY");
      if (fi_dly.n > 0 && method == fi_dly.method) fi::sleep_ms(fi_dly.n);
      resp = handler_(method, req, deadline);
      if (resp.type != Value::Type::MAP) resp = Value::M();
      resp.set("_s", Value::I(OK));
    } catch (const RpcError& e) {
      resp = Value::M();
      resp.set("_s", Value::I(e.code));
      resp.set("_e", Value::S(e.what()));
    } catch (const std::exception& e) {
      resp = Value::M();
      resp.set("_s", Value::I(INTERNAL));
      resp.set("_e", Value::S(e.what()));
    }
    int64_t serve_ns = lathist::now_ns() - serve_t0;
    lathist::observe(lathist::kRpcServe, (double)serve_ns / 1e9);
    // crash-durable breadcrumb: the last RPCs a dying server handled
    // (a = status code, b = serve ns) survive a SIGKILL mid-serve
    bb::record(bb::kRpcServe, -1, -1, resp.geti("_s", OK), serve_ns);
    std::string body = encode(resp);
    uint8_t out[4] = {(uint8_t)(body.size() & 0xff),
                      (uint8_t)((body.size() >> 8) & 0xff),
                      (uint8_t)((body.size() >> 16) & 0xff),
                      (uint8_t)((body.size() >> 24) & 0xff)};
    if (!write_all(fd, out, 4) || !write_all(fd, body.data(), body.size()))
      return;
  }
}

// ---- client --------------------------------------------------------------

RpcClient::RpcClient(const std::string& addr, int64_t connect_timeout_ms)
    : addr_(addr), connect_timeout_ms_(connect_timeout_ms) {
  if (!parse_addr(addr, &host_, &port_))
    throw RpcError(INVALID_ARGUMENT, "bad address: " + addr);
  std::lock_guard<std::mutex> g(mu_);
  ensure_connected(connect_timeout_ms);
}

RpcClient::~RpcClient() { disconnect(); }

void RpcClient::disconnect() {
  // close only ever happens under fd_mu_ — see abort()
  std::lock_guard<std::mutex> g(fd_mu_);
  int fd = fd_.exchange(-1);
  if (fd >= 0) close(fd);
}

void RpcClient::abort() {
  // Intentionally does not take mu_ (a blocked call() holds it — making
  // that call fail fast is the whole point). shutdown() on the fd makes
  // the blocked recv/send fail; the call() path then disconnects and
  // reconnects on next use. fd_mu_ serializes us against disconnect()'s
  // close: without it, the fd NUMBER could be closed and recycled by an
  // unrelated subsystem (stripe socket, checkpoint HTTP) between our
  // load and the shutdown, tearing down someone else's live connection.
  std::lock_guard<std::mutex> g(fd_mu_);
  int fd = fd_.load();
  if (fd >= 0) ::shutdown(fd, SHUT_RDWR);
}

void RpcClient::ensure_connected(int64_t timeout_ms) {
  if (fd_ >= 0) return;
  // Exponential backoff retry, parity with src/retry.rs:6-41
  // (initial 10ms per lib.rs usage, factor 2, max 3s, jitter).
  int64_t deadline = now_ms() + timeout_ms;
  int64_t backoff = 10;
  std::mt19937_64 rng(std::random_device{}());
  std::string err;
  while (true) {
    int64_t left = deadline - now_ms();
    if (left <= 0)
      throw RpcError(UNAVAILABLE,
                     "connect to " + addr_ + " timed out: " + err);
    int fd = tcp_connect(host_, port_, std::min<int64_t>(left, 5000), &err);
    if (fd >= 0) {
      if (!write_all(fd, "TFT1", 4)) {
        close(fd);
        err = "handshake write failed";
      } else {
        fd_ = fd;
        return;
      }
    }
    int64_t jitter = (int64_t)(rng() % (backoff / 2 + 1));
    int64_t sleep_ms = std::min<int64_t>(backoff + jitter, deadline - now_ms());
    if (sleep_ms > 0)
      std::this_thread::sleep_for(std::chrono::milliseconds(sleep_ms));
    backoff = std::min<int64_t>(backoff * 2, 3000);
  }
}

Value RpcClient::call(const std::string& method, Value req, int64_t timeout_ms) {
  std::lock_guard<std::mutex> g(mu_);
  ensure_connected(connect_timeout_ms_);
  req.set("_m", Value::S(method));
  req.set("_d", Value::I(timeout_ms));
  std::string body = encode(req);
  uint8_t lenbuf[4] = {(uint8_t)(body.size() & 0xff),
                       (uint8_t)((body.size() >> 8) & 0xff),
                       (uint8_t)((body.size() >> 16) & 0xff),
                       (uint8_t)((body.size() >> 24) & 0xff)};
  // env-gated injection: cut the nth call to <method> mid-body — a torn
  // control-plane frame (the server must drop the desynced stream, the
  // caller sees UNAVAILABLE and retries on a fresh connection)
  static const fi::MethodSpec fi_cut = fi::parse_method("TORCHFT_FI_RPC_CUT");
  if (fi_cut.n > 0 && method == fi_cut.method) {
    static std::atomic<long> fi_calls{0};
    long c = ++fi_calls;
    if (c == fi_cut.n) {
      fi::write_evidence("rpc.send", c, "torn");
      write_all(fd_, lenbuf, 4);
      write_all(fd_, body.data(), body.size() / 2);
      ::shutdown(fd_, SHUT_RDWR);
      disconnect();
      throw RpcError(UNAVAILABLE,
                     "fault injection: torn rpc frame for " + method);
    }
  }
  if (!write_all(fd_, lenbuf, 4) || !write_all(fd_, body.data(), body.size())) {
    disconnect();
    throw RpcError(UNAVAILABLE, "send to " + addr_ + " failed");
  }
  // Client-side deadline = request deadline + grace so the server-side
  // DEADLINE_EXCEEDED normally wins; a dead server trips this instead.
  int64_t deadline = now_ms() + timeout_ms + 2000;
  uint8_t rlen[4];
  if (!read_exact(fd_, rlen, 4, deadline)) {
    disconnect();
    throw RpcError(DEADLINE_EXCEEDED, method + " to " + addr_ + " timed out");
  }
  uint32_t len = (uint32_t)rlen[0] | ((uint32_t)rlen[1] << 8) |
                 ((uint32_t)rlen[2] << 16) | ((uint32_t)rlen[3] << 24);
  if (len > (1u << 30)) {
    disconnect();
    throw RpcError(INTERNAL, "oversized response");
  }
  std::string payload(len, '\0');
  if (!read_exact(fd_, payload.data(), len, deadline)) {
    disconnect();
    throw RpcError(DEADLINE_EXCEEDED, method + " response truncated/timed out");
  }
  Value resp = decode(payload);
  int64_t status = resp.geti("_s", INTERNAL);
  if (status != OK)
    throw RpcError((Status)status, resp.gets("_e", "unknown error"));
  return resp;
}

}  // namespace tft
