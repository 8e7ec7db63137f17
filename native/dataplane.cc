// torchft_tpu native core — striped cross-process gradient data plane.
// See dataplane.h for the design rationale.

#include "dataplane.h"

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cmath>
#include <cstring>
#include <limits>
#include <stdexcept>

#include "blackbox.h"     // crash-durable dp.hop / dp.stripe breadcrumbs
#include "faultinject.h"  // env-gated injection points (torn hops, kills)
#include "lathist.h"      // dp.hop / dp.stripe latency histograms
#include "profiler.h"     // always-on sampling (dp pump thread stacks)
#include "rpc.h"  // tcp_listen / tcp_connect / listen_port / now_ms
#include "stripe.h"  // shared stripe framing/partition (also used by blob.cc)

namespace tft {

// the shared stripe layer owns the framing/socket plumbing both striped
// planes (allreduce + checkpoint blob) speak — see stripe.h
using stripeio::err_wouldblock;
using stripeio::HopHdr;
using stripeio::set_nonblock;
using stripeio::tune_socket;

namespace {

constexpr uint32_t kHelloMagic = 0x7F7A0D01;  // distinct from control hello

// What a cma hop offers its right neighbour to pull: `len` bytes of this
// process, lying in `npieces` >= 1 pieces whose {addr, len} follow the
// descriptor on the socket (one piece: a range of the op's buffer; several:
// an op's source where its segments' bounds fall inside the chunk).
struct CmaDesc {
  uint32_t tag;
  uint32_t len;
  uint32_t npieces;
  uint32_t reserved;
};
struct CmaPiece {
  uint64_t addr;
  uint64_t len;
};
constexpr uint32_t kCmaMaxPieces = 1u << 16;  // a garbage count fails, not allocates

// bf16 round-to-nearest-even, matching numpy/ml_dtypes astype semantics
// for the values gradients take (the Python wire codec this plane must be
// bitwise-consistent with — collectives.py pack()/round-trip).
inline uint16_t f32_to_bf16(float f) {
  uint32_t x;
  std::memcpy(&x, &f, 4);
  if ((x & 0x7FFFFFFFu) > 0x7F800000u) {  // NaN: quiet, keep payload bit
    return (uint16_t)((x >> 16) | 0x0040);
  }
  uint32_t lsb = (x >> 16) & 1u;
  x += 0x7FFFu + lsb;
  return (uint16_t)(x >> 16);
}

inline float bf16_to_f32(uint16_t h) {
  uint32_t x = ((uint32_t)h) << 16;
  float f;
  std::memcpy(&f, &x, 4);
  return f;
}

void encode_bf16(const float* src, uint16_t* dst, size_t n) {
  for (size_t i = 0; i < n; ++i) dst[i] = f32_to_bf16(src[i]);
}

// int8 wire format (must match wire_codec.Int8Codec byte for byte): a
// 4-byte LE f32 scale header (max|x|/127; NaN when the chunk holds any
// non-finite value, so NaN propagates loudly through the decode instead
// of being laundered into a finite average) followed by one int8 per
// element, round-to-nearest-even like np.rint.
size_t wire_nbytes(DpCodec codec, size_t nelems) {
  switch (codec) {
    case DpCodec::kBf16:
      return nelems * 2;
    case DpCodec::kInt8:
      return 4 + nelems;
    case DpCodec::kF32:
    default:
      return nelems * 4;
  }
}

// round-half-even without a libm call: adding/subtracting 1.5*2^23
// rounds any |v| < 2^22 to the nearest even integer in the default FP
// mode, and the expression vectorizes to two adds (baseline x86-64 has
// no roundss, so nearbyintf would be a per-element function call — it
// measured as the whole int8 row's bottleneck on a 2-core box). Inputs
// here satisfy |v| <= 127(1+eps) by construction (scale = amax/127).
inline float round_half_even_small(float v) {
  const float magic = 12582912.0f;  // 1.5 * 2^23
  return (v + magic) - magic;
}

void encode_int8(const float* src, uint8_t* dst, size_t n) {
  float amax = 0.0f;
  bool finite = true;
  for (size_t i = 0; i < n; ++i) {
    float a = std::fabs(src[i]);
    if (!std::isfinite(a)) finite = false;
    if (a > amax) amax = a;
  }
  float scale;
  if (!finite) {
    scale = std::numeric_limits<float>::quiet_NaN();
  } else {
    scale = amax > 0.0f ? amax / 127.0f : 0.0f;
  }
  std::memcpy(dst, &scale, 4);
  int8_t* q = (int8_t*)(dst + 4);
  if (!finite || scale == 0.0f) {
    std::memset(q, 0, n);
    return;
  }
  for (size_t i = 0; i < n; ++i) {
    float v = round_half_even_small(src[i] / scale);
    if (v > 127.0f) v = 127.0f;
    if (v < -127.0f) v = -127.0f;
    q[i] = (int8_t)v;
  }
}

// The average joins the ring: whoever writes an element's FINAL f32 value
// divides it by `div` there (the allreduce's divisor as a float; 1 = a
// plain reduce) — the owner's last reduce-scatter step on the exact
// planes, the decode of the owner's wire bytes on the lossy ones — so no
// pass of its own ever runs over the buffer. A true f32 division, never a
// multiply by the reciprocal: bit for bit np.divide(x, n, out=x) for
// every n (x * (1/3.f) is not x / 3.f), which needs the build to stay
// free of -ffast-math.
void decode_bf16(const uint16_t* in, float* dst, size_t n, float div) {
  if (div == 1.0f) {
    for (size_t i = 0; i < n; ++i) dst[i] = bf16_to_f32(in[i]);
  } else {
    for (size_t i = 0; i < n; ++i) dst[i] = bf16_to_f32(in[i]) / div;
  }
}

void decode_int8(const uint8_t* wire, float* dst, size_t n, float div) {
  float scale;
  std::memcpy(&scale, wire, 4);
  const int8_t* q = (const int8_t*)(wire + 4);
  if (div == 1.0f) {
    for (size_t i = 0; i < n; ++i) dst[i] = (float)q[i] * scale;
  } else {
    for (size_t i = 0; i < n; ++i) dst[i] = ((float)q[i] * scale) / div;
  }
}

void divide_f32(float* x, size_t n, float div) {
  for (size_t i = 0; i < n; ++i) x[i] = x[i] / div;
}

// the exact planes' last reduce-scatter step: sum and divide in the one
// loop the owner of the chunk runs anyway
void reduce_sum_div_f32(float* acc, const float* in, size_t n, float div) {
  for (size_t i = 0; i < n; ++i) acc[i] = (acc[i] + in[i]) / div;
}

// NaN-propagating max/min, matching np.maximum/np.minimum (the Python
// ring's semantics): a NaN in either operand wins — allreduce-MAX is used
// as a grad-norm overflow tripwire and must not launder NaN away.
inline float nan_max(float a, float b) {
  if (std::isnan(a) || std::isnan(b)) return std::numeric_limits<float>::quiet_NaN();
  return a > b ? a : b;
}
inline float nan_min(float a, float b) {
  if (std::isnan(a) || std::isnan(b)) return std::numeric_limits<float>::quiet_NaN();
  return a < b ? a : b;
}

void reduce_f32(float* acc, const float* in, size_t n, DpOp op) {
  switch (op) {
    case DpOp::kSum:
    case DpOp::kAvg:  // resolved to kSum + divisor in allreduce()
      for (size_t i = 0; i < n; ++i) acc[i] += in[i];
      break;
    case DpOp::kMax:
      for (size_t i = 0; i < n; ++i) acc[i] = nan_max(acc[i], in[i]);
      break;
    case DpOp::kMin:
      for (size_t i = 0; i < n; ++i) acc[i] = nan_min(acc[i], in[i]);
      break;
  }
}

// The three-operand forms an allreduce with a source reduces with: dst = a
// (+) in, where the in-place ones compute acc (+)= in — the same operation
// on the same operands in the same order, so the same bits. dst never
// overlaps a or in (the destination, a caller's source, the stripe's scratch).
void reduce3_f32(float* __restrict dst, const float* __restrict a,
                 const float* __restrict in, size_t n, DpOp op) {
  switch (op) {
    case DpOp::kSum:
    case DpOp::kAvg:  // resolved to kSum + divisor in allreduce()
      for (size_t i = 0; i < n; ++i) dst[i] = a[i] + in[i];
      break;
    case DpOp::kMax:
      for (size_t i = 0; i < n; ++i) dst[i] = nan_max(a[i], in[i]);
      break;
    case DpOp::kMin:
      for (size_t i = 0; i < n; ++i) dst[i] = nan_min(a[i], in[i]);
      break;
  }
}

void reduce3_sum_div_f32(float* __restrict dst, const float* __restrict a,
                         const float* __restrict in, size_t n, float div) {
  for (size_t i = 0; i < n; ++i) dst[i] = (a[i] + in[i]) / div;
}

void reduce_from_bf16(float* acc, const uint16_t* in, size_t n, DpOp op) {
  switch (op) {
    case DpOp::kSum:
    case DpOp::kAvg:  // resolved to kSum + divisor in allreduce()
      for (size_t i = 0; i < n; ++i) acc[i] += bf16_to_f32(in[i]);
      break;
    case DpOp::kMax:
      for (size_t i = 0; i < n; ++i) acc[i] = nan_max(acc[i], bf16_to_f32(in[i]));
      break;
    case DpOp::kMin:
      for (size_t i = 0; i < n; ++i) acc[i] = nan_min(acc[i], bf16_to_f32(in[i]));
      break;
  }
}

void reduce_from_int8(float* acc, const uint8_t* wire, size_t n, DpOp op) {
  float scale;
  std::memcpy(&scale, wire, 4);
  const int8_t* q = (const int8_t*)(wire + 4);
  switch (op) {
    case DpOp::kSum:
    case DpOp::kAvg:  // resolved to kSum + divisor in allreduce()
      for (size_t i = 0; i < n; ++i) acc[i] += (float)q[i] * scale;
      break;
    case DpOp::kMax:
      for (size_t i = 0; i < n; ++i) acc[i] = nan_max(acc[i], (float)q[i] * scale);
      break;
    case DpOp::kMin:
      for (size_t i = 0; i < n; ++i) acc[i] = nan_min(acc[i], (float)q[i] * scale);
      break;
  }
}

// Calls fn(src, off, len) for every run of the bucket's elements [first,
// first + n) that lies inside one segment of `source` (a DataPlane::Source):
// `src` points at the run's first element, `off` counts from `first`.
template <class SourceT, class Fn>
void walk_source(const SourceT& source, int64_t first, int64_t n, Fn&& fn) {
  // the last segment that starts at or before `first` (none is empty)
  size_t k = (size_t)(std::upper_bound(source.start.begin(),
                                       source.start.end(), first) -
                      source.start.begin()) - 1;
  for (int64_t off = 0; off < n; ++k) {
    int64_t at = first + off;
    int64_t len = std::min(n - off, source.start[k + 1] - at);
    fn(source.ptr[k] + (at - source.start[k]), off, len);
    off += len;
  }
}

// the bytes of a copy dst <- src that 4K aliasing slows: see DpAccount
inline bool copy_aliases(const void* dst, const void* src) {
  uintptr_t ahead = ((uintptr_t)dst - (uintptr_t)src) % 4096;
  return ahead > 0 && ahead < 1024;
}

// poll-bounded small-message helpers now live in the shared stripe layer
// (stripe.h send_all/recv_all); these aliases keep the CMA control-message
// call sites reading as before
constexpr auto send_small = stripeio::send_all;
constexpr auto recv_small = stripeio::recv_all;

// process-wide hop counters for the env-gated injection points: the
// schedule coordinate is "the nth hop this PROCESS runs", stable across
// plane re-rendezvous (a per-plane counter would reset on every quorum)
std::atomic<long> g_fi_hops{0};
std::atomic<long> g_fi_cma_hops{0};

}  // namespace

DataPlane::DataPlane(int rank, int world, int nstripes)
    : rank_(rank), world_(world), nstripes_(nstripes) {
  std::string err;
  listen_fd_ = tcp_listen("[::]:0", &err);
  if (listen_fd_ < 0) {
    throw std::runtime_error("dataplane listen failed: " + err);
  }
  port_ = listen_port(listen_fd_);
  for (int s = 0; s < nstripes_; ++s) {
    stripes_.push_back(std::make_unique<Stripe>());
  }
  for (int s = 0; s < nstripes_; ++s) {
    stripes_[s]->worker = std::thread([this, s] { worker_loop(s); });
  }
  acceptor_ = std::thread([this] { accept_loop(); });
}

DataPlane::~DataPlane() { shutdown(); }

void DataPlane::shutdown() {
  bool was = closed_.exchange(true);
  if (was) return;
  // wake the acceptor
  if (listen_fd_ >= 0) {
    ::shutdown(listen_fd_, SHUT_RDWR);
    ::close(listen_fd_);
  }
  // unblock any in-flight hop
  {
    std::lock_guard<std::mutex> g(socks_mu_);
    for (auto& kv : socks_) {
      for (int fd : kv.second) {
        if (fd >= 0) ::shutdown(fd, SHUT_RDWR);
      }
    }
    socks_cv_.notify_all();
  }
  // wake + join workers
  for (auto& st : stripes_) {
    {
      std::lock_guard<std::mutex> g(st->mu);
      st->cv.notify_all();
    }
    if (st->worker.joinable()) st->worker.join();
  }
  if (acceptor_.joinable()) acceptor_.join();
  {
    // in-flight hellos: shut their fds so the reads fail fast, then join
    std::lock_guard<std::mutex> g(hello_mu_);
    for (int fd : hello_fds_) ::shutdown(fd, SHUT_RDWR);
  }
  for (;;) {
    std::thread t;
    {
      std::lock_guard<std::mutex> g(hello_mu_);
      if (hello_threads_.empty()) break;
      auto it = hello_threads_.begin();
      t = std::move(it->second);
      hello_threads_.erase(it);
    }
    if (t.joinable()) t.join();
  }
  {
    std::lock_guard<std::mutex> g(socks_mu_);
    for (auto& kv : socks_) {
      for (int& fd : kv.second) {
        if (fd >= 0) ::close(fd);
        fd = -1;
      }
    }
  }
  listen_fd_ = -1;
}

void DataPlane::accept_loop() {
  while (!closed_.load()) {
    int fd = ::accept(listen_fd_, nullptr, nullptr);
    if (fd < 0) {
      if (closed_.load()) return;
      if (errno == EINTR) continue;
      return;  // listener closed
    }
    // hello runs on its own short-lived thread: one stalled or garbage
    // connection must not starve the other world*nstripes dials of the
    // rendezvous window. Finished threads are reaped here so a long-lived
    // plane poked by scanners/redials doesn't grow thread objects forever.
    std::vector<std::thread> reap;
    {
      std::lock_guard<std::mutex> g(hello_mu_);
      if (closed_.load()) {
        ::close(fd);
        return;
      }
      for (uint64_t id : hello_finished_) {
        auto it = hello_threads_.find(id);
        if (it != hello_threads_.end()) {
          reap.push_back(std::move(it->second));
          hello_threads_.erase(it);
        }
      }
      hello_finished_.clear();
      uint64_t id = next_hello_id_++;
      hello_fds_.insert(fd);
      hello_threads_.emplace(
          id, std::thread([this, fd, id] { hello_handshake(fd, id); }));
    }
    for (auto& t : reap) {
      if (t.joinable()) t.join();
    }
  }
}

void DataPlane::hello_handshake(int fd, uint64_t id) {
  // hello: {magic, rank, stripe} — bounded read
  uint32_t hello[3];
  bool ok = read_exact(fd, hello, sizeof(hello), now_ms() + 10000) &&
            hello[0] == kHelloMagic;
  int peer = ok ? (int)hello[1] : -1;
  int stripe = ok ? (int)hello[2] : -1;
  {
    std::lock_guard<std::mutex> g(hello_mu_);
    hello_fds_.erase(fd);
    hello_finished_.push_back(id);
  }
  if (!ok || peer < 0 || peer >= world_ || stripe < 0 ||
      stripe >= nstripes_) {
    ::close(fd);
    return;
  }
  tune_socket(fd);
  set_nonblock(fd);
  std::lock_guard<std::mutex> g(socks_mu_);
  if (closed_.load()) {
    ::close(fd);
    return;
  }
  auto& v = socks_[peer];
  if (v.empty()) v.assign(nstripes_, -1);
  if (v[stripe] >= 0) ::close(v[stripe]);
  v[stripe] = fd;
  socks_cv_.notify_all();
}

bool DataPlane::connect_peer(int peer, const std::string& host, int port,
                             int64_t timeout_ms, std::string* err) {
  // ONE deadline across all stripes — an unreachable peer must cost one
  // timeout budget, not nstripes of them
  int64_t deadline = now_ms() + timeout_ms;
  for (int s = 0; s < nstripes_; ++s) {
    int64_t left = deadline - now_ms();
    if (left <= 0) {
      *err = "connect deadline exceeded";
      return false;
    }
    int fd = tcp_connect(host, port, left, err);
    if (fd < 0) return false;
    uint32_t hello[3] = {kHelloMagic, (uint32_t)rank_, (uint32_t)s};
    if (!write_all(fd, hello, sizeof(hello))) {
      ::close(fd);
      *err = "hello write failed";
      return false;
    }
    tune_socket(fd);
    set_nonblock(fd);
    std::lock_guard<std::mutex> g(socks_mu_);
    auto& v = socks_[peer];
    if (v.empty()) v.assign(nstripes_, -1);
    if (v[s] >= 0) ::close(v[s]);
    v[s] = fd;
  }
  return true;
}

bool DataPlane::wait_ready(int64_t timeout_ms, std::string* err) {
  int64_t deadline = now_ms() + timeout_ms;
  std::unique_lock<std::mutex> g(socks_mu_);
  for (;;) {
    bool ready = true;
    for (int p = 0; p < world_ && ready; ++p) {
      if (p == rank_) continue;
      auto it = socks_.find(p);
      if (it == socks_.end()) {
        ready = false;
        break;
      }
      for (int fd : it->second) {
        if (fd < 0) {
          ready = false;
          break;
        }
      }
    }
    if (ready) return true;
    if (closed_.load()) {
      *err = "dataplane shut down";
      return false;
    }
    int64_t left = deadline - now_ms();
    if (left <= 0) {
      *err = "timeout waiting for stripe peers";
      return false;
    }
    cv_wait_deadline(socks_cv_, g, now_ms() + (left > 100 ? 100 : left));
  }
}

int DataPlane::fd_for(int peer, int stripe) {
  std::lock_guard<std::mutex> g(socks_mu_);
  auto it = socks_.find(peer);
  if (it == socks_.end() || it->second[stripe] < 0) return -1;
  return it->second[stripe];
}

// Full-duplex pump: send sn bytes (header+payload already framed by the
// caller into sbuf layout via two-phase state) while receiving rn bytes.
// Uses poll() on both fds so a full send buffer can't deadlock against a
// peer doing the same (the reason the Python path burned a thread per hop).
bool DataPlane::hop(int send_fd, int recv_fd, const uint8_t* sbuf, size_t sn,
                    uint8_t* rbuf, size_t rn, uint32_t tag,
                    int64_t deadline_ms, bool* send_failed, bool* timed_out,
                    std::string* err) {
  // env-gated injection points (see faultinject.h): torn write / kill /
  // delay on the nth hop this process runs. Zero-cost when disarmed.
  static const fi::NthSpec fi_cut = fi::parse_nth("TORCHFT_FI_DP_CUT");
  static const long fi_kill = fi::parse_long("TORCHFT_FI_DP_KILL");
  static const long fi_delay = fi::parse_long("TORCHFT_FI_DP_DELAY_MS");
  if (fi_cut.nth > 0 || fi_kill > 0 || fi_delay > 0) {
    long h = ++g_fi_hops;
    if (fi_delay > 0) fi::sleep_ms(fi_delay);
    if (fi_kill > 0 && h == fi_kill) fi::kill_self("dp.hop", h);
    if (fi_cut.nth > 0 && h == fi_cut.nth) {
      // torn stripe write: full-length header, a fraction of the
      // payload, then a hard cut — the peer must see a mid-frame EOF
      // (its recv errors), never a short frame it could mistake for data
      HopHdr thdr{tag, (uint32_t)sn};
      bool to = false;
      std::string e2;
      size_t kbytes = (size_t)((double)sn * fi_cut.frac);
      fi::write_evidence("dp.hop", h, "torn");
      if (send_small(send_fd, &thdr, sizeof(thdr), deadline_ms, &to, &e2) &&
          kbytes > 0) {
        send_small(send_fd, sbuf, kbytes, deadline_ms, &to, &e2);
      }
      ::shutdown(send_fd, SHUT_RDWR);
      *send_failed = true;
      *timed_out = false;
      *err = "fault injection: torn stripe write (hop " + std::to_string(h) +
             ", " + std::to_string(kbytes) + "/" + std::to_string(sn) +
             " bytes)";
      return false;
    }
  }

  HopHdr shdr{tag, (uint32_t)sn};
  HopHdr rhdr{0, 0};
  size_t s_off = 0, r_off = 0;
  size_t sh_off = 0, rh_off = 0;  // header progress
  *send_failed = false;

  while (sh_off < sizeof(shdr) || s_off < sn || rh_off < sizeof(rhdr) ||
         r_off < rn) {
    struct pollfd pfd[2];
    int n = 0;
    int send_i = -1, recv_i = -1;
    if (sh_off < sizeof(shdr) || s_off < sn) {
      pfd[n].fd = send_fd;
      pfd[n].events = POLLOUT;
      pfd[n].revents = 0;
      send_i = n++;
    }
    if (rh_off < sizeof(rhdr) || r_off < rn) {
      pfd[n].fd = recv_fd;
      pfd[n].events = POLLIN;
      pfd[n].revents = 0;
      recv_i = n++;
    }
    int64_t left = deadline_ms - now_ms();
    if (left <= 0) {
      *timed_out = true;
      *err = "hop deadline exceeded";
      return false;
    }
    int pr = ::poll(pfd, n, (int)(left > 200 ? 200 : left));
    if (closed_.load()) {
      *err = "dataplane shut down";
      return false;
    }
    if (pr < 0) {
      if (errno == EINTR) continue;
      *err = std::string("poll: ") + errno_str(errno);
      return false;
    }
    if (send_i >= 0 && (pfd[send_i].revents & (POLLOUT | POLLERR | POLLHUP))) {
      // scatter-gather: header + payload leave in ONE sendmsg from their
      // own buffers — no coalescing copy, and the common case is a
      // single syscall per pump instead of two
      while (sh_off < sizeof(shdr) || s_off < sn) {
        iovec iov[2];
        int cnt = 0;
        if (sh_off < sizeof(shdr)) {
          iov[cnt].iov_base = (uint8_t*)&shdr + sh_off;
          iov[cnt].iov_len = sizeof(shdr) - sh_off;
          ++cnt;
        }
        if (s_off < sn) {
          iov[cnt].iov_base = (void*)(sbuf + s_off);
          iov[cnt].iov_len = sn - s_off;
          ++cnt;
        }
        msghdr mh{};
        mh.msg_iov = iov;
        mh.msg_iovlen = cnt;
        ssize_t k = ::sendmsg(send_fd, &mh, MSG_NOSIGNAL);
        if (k > 0) {
          size_t adv = (size_t)k;
          if (sh_off < sizeof(shdr)) {
            size_t h = sizeof(shdr) - sh_off;
            size_t hh = adv < h ? adv : h;
            sh_off += hh;
            adv -= hh;
          }
          s_off += adv;
        } else if (k < 0 && err_wouldblock(errno)) {
          break;
        } else {
          *send_failed = true;
          *err = std::string("send: ") + (k == 0 ? "closed" : errno_str(errno));
          return false;
        }
      }
    }
    if (recv_i >= 0 && (pfd[recv_i].revents & (POLLIN | POLLERR | POLLHUP))) {
      while (rh_off < sizeof(rhdr)) {
        ssize_t k = ::recv(recv_fd, (uint8_t*)&rhdr + rh_off,
                           sizeof(rhdr) - rh_off, 0);
        if (k > 0) {
          rh_off += (size_t)k;
          if (rh_off == sizeof(rhdr)) {
            if (rhdr.tag != tag || rhdr.len != rn) {
              *err = "stripe frame mismatch: tag " + std::to_string(rhdr.tag) +
                     "/" + std::to_string(tag) + " len " +
                     std::to_string(rhdr.len) + "/" + std::to_string(rn);
              return false;
            }
          }
        } else if (k < 0 && err_wouldblock(errno)) {
          break;
        } else {
          *err = std::string("recv: ") + (k == 0 ? "closed" : errno_str(errno));
          return false;
        }
      }
      while (rh_off == sizeof(rhdr) && r_off < rn) {
        ssize_t k = ::recv(recv_fd, rbuf + r_off, rn - r_off, 0);
        if (k > 0) {
          r_off += (size_t)k;
        } else if (k < 0 && err_wouldblock(errno)) {
          break;
        } else {
          *err = std::string("recv: ") + (k == 0 ? "closed" : errno_str(errno));
          return false;
        }
      }
    }
  }
  return true;
}

void DataPlane::enable_cma(const std::vector<int64_t>& pids) {
  peer_pids_ = pids;
  // release-order: the store publishes peer_pids_ to the already-
  // running stripe workers (acquire-load in run_stripe); see the
  // member comment
  cma_.store(true, std::memory_order_release);
}

// CMA hop: descriptors and acks ride the stripe socket; the payload is
// pulled straight from the left neighbor's address space — from the pieces
// its descriptor names (`offer`: one range of the op's buffer, or the
// pieces of an op's source), in as few process_vm_readv calls as their
// number allows: a call costs ~0.2 ms on the benchmark's host whatever it
// moves (PERF.md §6, PR 39: pulling 256 KB at a time tripled the pulls'
// seconds). Message flow per
// socket direction is clean: descs flow rank→right, acks flow reader→owner
// (so on my left socket I read descs and write acks; on my right socket I
// write descs and read acks) — with world=2 both are the same fd and the
// peer's desc→ack send order keeps the stream unambiguous.
bool DataPlane::cma_hop(int send_fd, int recv_fd, const DpSegment* offer,
                        int noffer, uint8_t* rbuf, size_t rn, uint32_t tag,
                        int64_t deadline_ms, bool* send_failed,
                        bool* timed_out, std::string* err, DpAccount* acct) {
  const int left = (rank_ - 1 + world_) % world_;
  *send_failed = false;
  // the account's laps: each returns the nanoseconds since the last one
  // (a failed hop books the piece it was in before it returns)
  int64_t lap_t = 0;
  auto lap = [&lap_t]() {
    int64_t now = lathist::now_ns();
    int64_t d = now - lap_t;
    lap_t = now;
    return d;
  };
  // env-gated injection points: die with a published pull descriptor
  // outstanding (the torn-read window the ROADMAP divergence hypothesis
  // names), or tear this hop's own pull partway.
  static const long fi_cma_kill = fi::parse_long("TORCHFT_FI_CMA_KILL");
  static const fi::NthSpec fi_cma_torn =
      fi::parse_nth("TORCHFT_FI_CMA_TORN");
  long fi_h = 0;
  if (fi_cma_kill > 0 || fi_cma_torn.nth > 0) fi_h = ++g_fi_cma_hops;
  // one message: the descriptor and its pieces (a stripe's own thread runs
  // its hops, so the buffers are the thread's and allocate once)
  thread_local std::vector<uint8_t> mine;
  thread_local std::vector<CmaPiece> theirs;
  mine.resize(sizeof(CmaDesc) + (size_t)noffer * sizeof(CmaPiece));
  CmaDesc desc{tag, 0, (uint32_t)noffer, 0};
  CmaPiece* out = (CmaPiece*)(mine.data() + sizeof(CmaDesc));
  for (int i = 0; i < noffer; ++i) {
    out[i] = {(uint64_t)(uintptr_t)offer[i].addr, (uint64_t)offer[i].bytes};
    desc.len += (uint32_t)offer[i].bytes;
  }
  std::memcpy(mine.data(), &desc, sizeof(desc));
  if (!send_small(send_fd, mine.data(), mine.size(), deadline_ms, timed_out,
                  err)) {
    *send_failed = true;
    return false;
  }
  if (fi_cma_kill > 0 && fi_h == fi_cma_kill) {
    // the right neighbor now holds {addr, len} into THIS address space;
    // dying here is exactly "peer death mid-op with a dangling pull"
    fi::kill_self("cma.desc", fi_h);
  }
  lap();  // own descriptor sent
  CmaDesc got{};
  bool got_desc = recv_small(recv_fd, &got, sizeof(got), deadline_ms,
                             timed_out, err);
  if (got_desc && (got.tag != tag || got.len != rn || got.npieces == 0 ||
                   got.npieces > kCmaMaxPieces)) {
    acct->desc_wait_ns += lap();
    *err = "cma desc mismatch: tag " + std::to_string(got.tag) + "/" +
           std::to_string(tag) + " len " + std::to_string(got.len) + "/" +
           std::to_string(rn) + " pieces " + std::to_string(got.npieces);
    return false;
  }
  if (got_desc) {
    theirs.resize(got.npieces);
    got_desc = recv_small(recv_fd, theirs.data(),
                          theirs.size() * sizeof(CmaPiece), deadline_ms,
                          timed_out, err);
  }
  acct->desc_wait_ns += lap();
  if (!got_desc) return false;
  theirs.erase(std::remove_if(theirs.begin(), theirs.end(),
                              [](const CmaPiece& p) { return p.len == 0; }),
               theirs.end());
  uint64_t offered = 0;
  for (const CmaPiece& p : theirs) offered += p.len;
  if (offered != rn) {
    *err = "cma desc mismatch: its pieces hold " + std::to_string(offered) +
           " of " + std::to_string(rn) + " bytes";
    return false;
  }
  size_t goal = rn;
  if (fi_cma_torn.nth > 0 && fi_h == fi_cma_torn.nth) {
    // torn CMA read: stop the pull partway and fail the hop — the
    // partially-filled buffer must latch the step, never average in
    goal = (size_t)((double)rn * fi_cma_torn.frac);
    fi::write_evidence("cma.pull", fi_h, "torn");
  }
  // the cursor (piece, offset in it) walks the neighbour's pieces
  size_t piece = 0;
  uint64_t piece_off = 0;
  size_t off = 0;
  while (off < goal) {
    iovec lv{rbuf + off, goal - off};
    iovec rv[16];
    int nrv = 0;
    size_t need = goal - off;
    for (size_t i = piece, o = piece_off; need > 0 && nrv < 16; ++i, o = 0) {
      size_t take = std::min<uint64_t>(need, theirs[i].len - o);
      rv[nrv++] = {(void*)(uintptr_t)(theirs[i].addr + o), take};
      need -= take;
    }
    ssize_t k = ::process_vm_readv((pid_t)peer_pids_[left], &lv, 1, rv,
                                   (unsigned long)nrv, 0);
    if (k <= 0) {
      *err = std::string("process_vm_readv: ") +
             (k == 0 ? "zero read" : errno_str(errno));
      break;
    }
    off += (size_t)k;
    for (uint64_t moved = (uint64_t)k; moved > 0;) {  // advance the cursor
      uint64_t step = std::min(moved, theirs[piece].len - piece_off);
      piece_off += step;
      moved -= step;
      if (piece_off == theirs[piece].len) {
        ++piece;
        piece_off = 0;
      }
    }
  }
  acct->pull_ns += lap();
  acct->pull_bytes += (int64_t)off;
  if (off < goal) return false;
  if (goal < rn) {
    *err = "fault injection: torn CMA pull (" + std::to_string(goal) + "/" +
           std::to_string(rn) + " bytes)";
    return false;
  }
  uint32_t ack = tag;
  if (!send_small(recv_fd, &ack, sizeof(ack), deadline_ms, timed_out, err)) {
    return false;
  }
  lap();  // own ack sent
  uint32_t rack = 0;
  bool got_ack =
      recv_small(send_fd, &rack, sizeof(rack), deadline_ms, timed_out, err);
  acct->ack_wait_ns += lap();
  if (!got_ack) {
    *send_failed = true;
    return false;
  }
  if (rack != tag) {
    *err = "cma ack mismatch";
    *send_failed = true;
    return false;
  }
  return true;
}

int DataPlane::run_stripe(int stripe_idx, Job& job, int* bad_peer,
                          std::string* err) {
  const int right = (rank_ + 1) % world_;
  const int left = (rank_ - 1 + world_) % world_;
  int send_fd = fd_for(right, stripe_idx);
  int recv_fd = fd_for(left, stripe_idx);
  if (send_fd < 0 || recv_fd < 0) {
    *bad_peer = send_fd < 0 ? right : left;
    *err = "stripe socket missing";
    return -1;
  }

  // CMA pulls exact f32 out of the peer's memory — the wire codec is
  // moot (and the exactness is deterministic: the owner's bytes are
  // distributed verbatim in the allgather phase)
  // release-order: one acquire-load per job pairs with enable_cma's
  // release-store so peer_pids_ is fully visible before the first CMA
  // hop of this job
  const bool use_cma = cma_.load(std::memory_order_acquire);
  if (use_cma) job.codec = DpCodec::kF32;
  const DpCodec codec = job.codec;
  const float div = (float)job.divisor;

  float* flat = (float*)job.base;
  int64_t n = job.nelems;
  std::vector<int64_t> bounds(world_ + 1);
  for (int i = 0; i <= world_; ++i) bounds[i] = n * i / world_;
  auto chunk_ptr = [&](int i) { return flat + bounds[i]; };
  auto chunk_n = [&](int i) { return (size_t)(bounds[i + 1] - bounds[i]); };

  size_t max_chunk = 0;
  for (int i = 0; i < world_; ++i) {
    if (chunk_n(i) > max_chunk) max_chunk = chunk_n(i);
  }
  const size_t max_wire = wire_nbytes(codec, max_chunk);
  auto& st = *stripes_[stripe_idx];
  st.scratch_send.resize(max_wire);
  st.scratch_recv.resize(max_wire);
  if (codec != DpCodec::kF32) st.scratch_fwd.resize(max_wire);
  DpAccount& acct = st.acct;  // see DpAccount (dataplane.h)

  // With a source (see allreduce) nothing of this rank's contribution is
  // in `flat` yet: the stripe copies what the schedule sends before it
  // reduces it, and reads the rest where it lies.
  const Source* source = job.source;
  auto copy_from_source = [&](int64_t at, int64_t cnt) {
    int64_t t0 = lathist::now_ns();
    walk_source(*source, job.first + at, cnt,
                [&](const float* src, int64_t off, int64_t len) {
                  float* dst = flat + at + off;
                  std::memcpy(dst, src, (size_t)len * 4);
                  acct.copy_bytes += len * 4;
                  if (copy_aliases(dst, src)) acct.copy_aliased_bytes += len * 4;
                });
    acct.copy_ns += lathist::now_ns() - t0;
  };
  // step 0 sends this rank's own chunk raw, the one chunk no reduce step
  // ever writes: plane cma offers the source's pieces of it as they lie
  std::vector<DpSegment> own_pieces;
  if (source != nullptr && codec != DpCodec::kF32) {
    // a lossy codec encodes the partial sums it reads back from `flat`
    // hop after hop: all of the stripe first, then in place as ever
    copy_from_source(0, n);
    source = nullptr;
  } else if (source != nullptr && !use_cma) {
    // the pump sends from `flat`
    copy_from_source(bounds[rank_], (int64_t)chunk_n(rank_));
  } else if (source != nullptr) {
    walk_source(*source, job.first + bounds[rank_], (int64_t)chunk_n(rank_),
                [&](const float* src, int64_t, int64_t len) {
                  own_pieces.push_back({src, len * 4});
                });
  }

  auto prep_send = [&](int idx) -> std::pair<const uint8_t*, size_t> {
    size_t cn = chunk_n(idx);
    if (codec == DpCodec::kF32) {
      // zero-copy: the chunk's own bytes are the wire form
      return {(const uint8_t*)chunk_ptr(idx), cn * 4};
    }
    int64_t t0 = lathist::now_ns();
    size_t wn;
    if (codec == DpCodec::kBf16) {
      encode_bf16(chunk_ptr(idx), (uint16_t*)st.scratch_send.data(), cn);
      wn = cn * 2;
    } else {
      encode_int8(chunk_ptr(idx), st.scratch_send.data(), cn);
      wn = 4 + cn;
    }
    acct.codec_ns += lathist::now_ns() - t0;
    return {st.scratch_send.data(), wn};
  };

  bool send_failed = false;
  bool timed_out = false;
  auto do_hop = [&](const uint8_t* sb, size_t sn, uint8_t* rb, size_t rn,
                    const std::vector<DpSegment>* pieces = nullptr) {
    // what the right neighbour is offered: sb[0:sn], or (plane cma, step
    // 0 of an op with a source) the pieces of the source that hold it
    DpSegment whole{sb, (int64_t)sn};
    const bool in_pieces = pieces != nullptr && !pieces->empty();
    // per-hop latency histogram (full-duplex send+recv pump — the wait
    // for a slow left neighbor lands here, which is what makes the
    // distribution a straggler lens); failed hops record too: a
    // deadline'd hop's duration is exactly the evidence wanted
    int64_t t0 = lathist::now_ns();
    bool ok = use_cma ? cma_hop(send_fd, recv_fd,
                                in_pieces ? pieces->data() : &whole,
                                in_pieces ? (int)pieces->size() : 1, rb, rn,
                                job.tag, job.deadline_ms, &send_failed,
                                &timed_out, err, &acct)
                      : hop(send_fd, recv_fd, sb, sn, rb, rn, job.tag,
                            job.deadline_ms, &send_failed, &timed_out, err);
    int64_t hop_ns = lathist::now_ns() - t0;
    lathist::observe(lathist::kDpHop, (double)hop_ns / 1e9);
    if (!use_cma) {
      // the pump's whole hop, waiting and moving alike; bytes of the
      // hops that ended (a failed one's progress is not known here)
      acct.pump_ns += hop_ns;
      if (ok) acct.pump_bytes += (int64_t)rn;
    }
    // crash-durable breadcrumb: a worker SIGKILLed mid-allreduce leaves
    // its last hops (a = op tag, b = ok flag) in the black box — the
    // postmortem's "what was in flight" answer for the native plane
    bb::record(bb::kDpHop, -1, -1, (int64_t)job.tag, ok ? 1 : 0);
    return ok;
  };
  // a deadline or LOCAL shutdown names NO peer: slow-but-alive (or our
  // own teardown) must surface as retryable, not as an eviction-worthy
  // accusation against an innocent neighbor
  auto fail = [&]() {
    if (timed_out) {
      *bad_peer = -1;
      return -2;
    }
    if (closed_.load()) {
      *bad_peer = -1;
      return -1;
    }
    *bad_peer = send_failed ? right : left;
    return -1;
  };
  // reduce-scatter phase: every hop ships a freshly encoded partial sum
  // (re-quantized at its own magnitude); accumulation stays f32
  for (int step = 0; step < world_ - 1; ++step) {
    int send_idx = ((rank_ - step) % world_ + world_) % world_;
    int recv_idx = ((rank_ - step - 1) % world_ + world_) % world_;
    const bool from_pieces = step == 0 && !own_pieces.empty();
    auto [sb, sn] = from_pieces
                        ? std::pair<const uint8_t*, size_t>{nullptr,
                                                            chunk_n(send_idx) * 4}
                        : prep_send(send_idx);
    size_t rn = wire_nbytes(codec, chunk_n(recv_idx));
    if (!do_hop(sb, sn, st.scratch_recv.data(), rn,
                from_pieces ? &own_pieces : nullptr)) {
      return fail();
    }
    int64_t reduce_t0 = lathist::now_ns();
    switch (codec) {
      case DpCodec::kBf16:
        reduce_from_bf16(chunk_ptr(recv_idx),
                         (const uint16_t*)st.scratch_recv.data(),
                         chunk_n(recv_idx), job.op);
        break;
      case DpCodec::kInt8:
        reduce_from_int8(chunk_ptr(recv_idx), st.scratch_recv.data(),
                         chunk_n(recv_idx), job.op);
        break;
      case DpCodec::kF32:
      default: {
        // recv_idx of the last step is the chunk this rank owns: its
        // next write is the final value, so the divisor goes in here
        const bool final_value = div != 1.0f && step == world_ - 2;
        const float* pulled = (const float*)st.scratch_recv.data();
        if (source != nullptr) {
          // the chunk's first (and only) reduce: own operand from the source
          walk_source(*source, job.first + bounds[recv_idx],
                      (int64_t)chunk_n(recv_idx),
                      [&](const float* own, int64_t off, int64_t len) {
                        float* dst = chunk_ptr(recv_idx) + off;
                        if (final_value) {
                          reduce3_sum_div_f32(dst, own, pulled + off,
                                              (size_t)len, div);
                        } else {
                          reduce3_f32(dst, own, pulled + off, (size_t)len,
                                      job.op);
                        }
                      });
        } else if (final_value) {
          reduce_sum_div_f32(chunk_ptr(recv_idx), pulled, chunk_n(recv_idx),
                             div);
        } else {
          reduce_f32(chunk_ptr(recv_idx), pulled, chunk_n(recv_idx), job.op);
        }
        break;
      }
    }
    acct.reduce_ns += lathist::now_ns() - reduce_t0;
    acct.reduce_bytes += (int64_t)chunk_n(recv_idx) * 4;
  }
  if (codec == DpCodec::kF32) {
    // raw allgather: f32 lands straight in the target chunk and the
    // forwarded bytes are the owner's bytes by nature
    for (int step = 0; step < world_ - 1; ++step) {
      int send_idx = ((rank_ + 1 - step) % world_ + world_) % world_;
      int recv_idx = ((rank_ - step) % world_ + world_) % world_;
      auto [sb, sn] = prep_send(send_idx);
      float* dst = chunk_ptr(recv_idx);
      size_t cn = chunk_n(recv_idx);
      if (!do_hop(sb, sn, (uint8_t*)dst, cn * 4)) {
        return fail();
      }
    }
  } else if (world_ > 1) {
    // lossy allgather: the owner of each fully reduced chunk encodes it
    // ONCE; its wire bytes then circulate VERBATIM (intermediate ranks
    // forward what they received, zero re-encode work) and the owner
    // keeps the decode of its own bytes — every rank lands on the
    // identical f32 image by construction, not by fp-rounding luck
    // (collectives.py's _ring_allreduce_codec is the same schedule).
    // The divisor is applied by each decode of those bytes: encode the
    // sum, decode, divide — the order a trailing np.divide gave
    int owned = (rank_ + 1) % world_;
    size_t own_wire = wire_nbytes(codec, chunk_n(owned));
    int64_t codec_t0 = lathist::now_ns();
    switch (codec) {
      case DpCodec::kBf16:
        encode_bf16(chunk_ptr(owned), (uint16_t*)st.scratch_fwd.data(),
                    chunk_n(owned));
        decode_bf16((const uint16_t*)st.scratch_fwd.data(), chunk_ptr(owned),
                    chunk_n(owned), div);
        break;
      case DpCodec::kInt8:
        encode_int8(chunk_ptr(owned), st.scratch_fwd.data(), chunk_n(owned));
        decode_int8(st.scratch_fwd.data(), chunk_ptr(owned), chunk_n(owned),
                    div);
        break;
      default:
        break;
    }
    acct.codec_ns += lathist::now_ns() - codec_t0;
    uint8_t* cur = st.scratch_fwd.data();
    size_t cur_n = own_wire;
    uint8_t* spare = st.scratch_recv.data();
    for (int step = 0; step < world_ - 1; ++step) {
      int recv_idx = ((rank_ - step) % world_ + world_) % world_;
      size_t cn = chunk_n(recv_idx);
      size_t rn = wire_nbytes(codec, cn);
      if (!do_hop(cur, cur_n, spare, rn)) {
        return fail();
      }
      codec_t0 = lathist::now_ns();
      if (codec == DpCodec::kBf16) {
        decode_bf16((const uint16_t*)spare, chunk_ptr(recv_idx), cn, div);
      } else {
        decode_int8(spare, chunk_ptr(recv_idx), cn, div);
      }
      acct.codec_ns += lathist::now_ns() - codec_t0;
      uint8_t* t = cur;
      cur = spare;
      spare = t;
      cur_n = rn;
    }
  }
  return 0;
}

void DataPlane::worker_loop(int stripe_idx) {
  // samples name this thread "dp.pump" in the collapsed stacks; the
  // per-hop path itself gains zero instructions (registration happens
  // once, here — see profiler.h)
  prof::ThreadGuard prof_guard("dp.pump");
  auto& st = *stripes_[stripe_idx];
  for (;;) {
    Job job;
    {
      std::unique_lock<std::mutex> g(st.mu);
      st.cv.wait(g, [&] { return st.has_job || closed_.load(); });
      if (closed_.load()) return;
      job = st.job;
      st.has_job = false;
    }
    int bad_peer = -1;
    std::string err;
    int rc = 0;
    if (job.nelems > 0) {
      int64_t t0 = lathist::now_ns();
      rc = run_stripe(stripe_idx, job, &bad_peer, &err);
      lathist::observe(lathist::kDpStripe,
                       (double)(lathist::now_ns() - t0) / 1e9);
      // stripe-level breadcrumb (a = op tag, b = rc): pairs with the
      // per-hop records to name the exact stripe a death interrupted
      bb::record(bb::kDpStripe, -1, -1, (int64_t)job.tag, rc);
    }
    {
      std::lock_guard<std::mutex> g(st.mu);
      st.rc = rc;
      st.bad_peer = bad_peer;
      st.err = err;
      st.done = true;
      st.cv.notify_all();
    }
  }
}

int DataPlane::allreduce(void* data, int64_t nelems, const DpSegment* source,
                         int nsegments, DpDtype dtype, DpOp op, int divisor,
                         DpCodec codec, uint32_t tag, int64_t timeout_ms,
                         int* bad_peer, std::string* err) {
  *bad_peer = -1;
  last_account_ = DpAccount{};
  // AVG is SUM with the divisor `world`: one code path
  if (op == DpOp::kAvg && divisor == 1) {
    op = DpOp::kSum;
    divisor = world_;
  }
  if (divisor < 1 || (divisor != 1 && op != DpOp::kSum)) {
    *err = "a divisor goes with SUM only";
    return -1;
  }
  if (dtype != DpDtype::kF32) {
    *err = "unsupported dtype";
    return -1;
  }
  if (codec != DpCodec::kF32 && codec != DpCodec::kBf16 &&
      codec != DpCodec::kInt8) {
    *err = "unsupported wire codec";
    return -1;
  }
  source_.ptr.clear();
  source_.start.assign(1, 0);
  for (int i = 0; i < nsegments; ++i) {
    if (source[i].bytes < 0 || source[i].bytes % 4 != 0) {
      *err = "a source segment is whole f32s";
      return -1;
    }
    if (source[i].bytes == 0) continue;
    source_.ptr.push_back((const float*)source[i].addr);
    source_.start.push_back(source_.start.back() + source[i].bytes / 4);
  }
  const bool from_source = nsegments > 0;
  if (from_source && source_.start.back() != nelems) {
    *err = "the source's segments hold " + std::to_string(source_.start.back()) +
           " elements, the destination " + std::to_string(nelems);
    return -1;
  }
  last_account_.from_source = from_source;
  if (world_ <= 1) {
    if (from_source && nelems > 0) {
      walk_source(source_, 0, nelems,
                  [&](const float* src, int64_t off, int64_t len) {
                    std::memcpy((float*)data + off, src, (size_t)len * 4);
                  });
    }
    if (divisor != 1) divide_f32((float*)data, (size_t)nelems, (float)divisor);
    return 0;
  }
  if (nelems == 0) return 0;
  int64_t deadline = now_ms() + timeout_ms;
  // stripe partition: contiguous, 16-element aligned so reduce loops stay
  // vectorizable and no stripe's chunk is pathologically small
  int ns = nstripes_;
  if (nelems < ns * 64) ns = 1;
  std::vector<int64_t> sb = stripeio::stripe_bounds(nelems, ns, 16);
  for (int s = 0; s < ns; ++s) {
    auto& st = *stripes_[s];
    std::lock_guard<std::mutex> g(st.mu);
    st.job.base = (uint8_t*)((float*)data + sb[s]);
    st.job.nelems = sb[s + 1] - sb[s];
    st.job.source = from_source ? &source_ : nullptr;
    st.job.first = sb[s];
    st.job.op = op;
    st.job.divisor = divisor;
    st.job.codec = codec;
    st.job.tag = tag + (uint32_t)s;
    st.job.deadline_ms = deadline;
    st.acct = DpAccount{};
    st.has_job = true;
    st.done = false;
    st.cv.notify_all();
  }
  // aggregate: a concrete socket failure (-1, names a peer) outranks a
  // bare deadline (-2) from another stripe
  int rc = 0;
  for (int s = 0; s < ns; ++s) {
    auto& st = *stripes_[s];
    std::unique_lock<std::mutex> g(st.mu);
    st.cv.wait(g, [&] { return st.done || closed_.load(); });
    if (!st.done) {
      // Shutdown raced the op. A worker may still be inside run_stripe
      // writing into the CALLER's buffer; returning -1 now would let
      // Python free/reuse that memory under the worker's pen (shutdown's
      // join runs on a different thread and doesn't gate this return).
      // has_job still set means the worker exited at the top of its loop
      // WITHOUT taking the job — nobody will touch the buffer; otherwise
      // the worker is mid-job and, with the sockets now closed, will
      // promptly fail the next hop and set done.
      st.cv.wait(g, [&] { return st.done || st.has_job; });
      if (rc == 0) {
        *err = "dataplane shut down";
        rc = -1;
        *bad_peer = -1;
      }
      continue;
    }
    if (st.rc != 0 && (rc == 0 || (rc == -2 && st.rc == -1))) {
      rc = st.rc;
      *bad_peer = st.bad_peer;
      *err = st.err;
    }
  }
  // the op's account: see DpAccount. A stripe whose worker never took the
  // job (shutdown) adds the zeros it was handed
  static constexpr int64_t DpAccount::*kTimes[] = {
      &DpAccount::desc_wait_ns, &DpAccount::pull_ns,   &DpAccount::ack_wait_ns,
      &DpAccount::pump_ns,      &DpAccount::reduce_ns, &DpAccount::codec_ns,
      &DpAccount::copy_ns};
  static constexpr int64_t DpAccount::*kBytes[] = {
      &DpAccount::pull_bytes, &DpAccount::pump_bytes, &DpAccount::reduce_bytes,
      &DpAccount::copy_bytes, &DpAccount::copy_aliased_bytes};
  DpAccount& a = last_account_;
  a.stripes = ns;
  for (int s = 0; s < ns; ++s) {
    auto& st = *stripes_[s];
    std::lock_guard<std::mutex> g(st.mu);
    int64_t total = 0;
    for (auto t : kTimes) {
      a.*t += st.acct.*t;
      total += st.acct.*t;
    }
    for (auto c : kBytes) a.*c += st.acct.*c;
    if (total > a.slowest_stripe_ns) a.slowest_stripe_ns = total;
  }
  for (auto t : kTimes) a.*t /= ns;
  return rc;
}

}  // namespace tft

// ---- C ABI for ctypes ------------------------------------------------------

namespace {

std::mutex g_dp_mu;
int64_t g_dp_next = 1;
std::map<int64_t, std::shared_ptr<tft::DataPlane>> g_dps;

std::shared_ptr<tft::DataPlane> dp_get(int64_t h) {
  std::lock_guard<std::mutex> g(g_dp_mu);
  auto it = g_dps.find(h);
  return it == g_dps.end() ? nullptr : it->second;
}

void dp_set_err(char* err, int errlen, const std::string& msg) {
  if (err && errlen > 0) {
    strncpy(err, msg.c_str(), (size_t)errlen - 1);
    err[errlen - 1] = '\0';
  }
}

}  // namespace

extern "C" {

// Bumped whenever the ctypes-visible surface changes SHAPE or MEANING
// (v2: tft_dp_allreduce's `wire_bf16` int became the DpCodec enum — a
// stale library would silently reinterpret codec=2 as wire_bf16=true;
// v3: tft_lathist_snapshot/tft_lathist_reset added — a stale build would
// fail the loader's symbol lookup at import;
// v4: tft_blob_* striped checkpoint blob plane added (blob.cc)).
// The Python loader (_native/__init__.py) refuses to run a mismatched
// build and rebuilds in place.
// v5: mgr.should_commit carries divergence-sentinel digests, lh.digest
// RPC added, native blackbox breadcrumbs (blackbox.h) compiled in.
// v6: fixed-retention time-series store (tsdb.h): tft_tsdb_snapshot/
// tft_tsdb_reset + lighthouse /timeseries.json ingest.
// v7: always-on sampling profiler (profiler.h): tft_prof_set_hz/hz/
// snapshot/reset/samples_total — a stale build would fail the loader's
// symbol lookup at import.
// v8: tft_dp_allreduce takes the divisor after `op` (the average is taken
// inside the ring) — a stale library would read it as the codec.
// v9: tft_dp_last_account added (the ring's account of its last allreduce:
// DpAccount, dataplane.h) — a stale build would fail the loader's symbol
// lookup at import.
// v10: tft_dp_allreduce_from added (an allreduce that reads this rank's
// contribution from read-only segments), and DpAccount grew copy_ns,
// copy_bytes, copy_aliased_bytes and from_source — a stale build would fail
// the symbol lookup, or hand tft_dp_last_account's caller a short account.
int tft_abi_version() { return 10; }

int64_t tft_dp_create(int rank, int world, int nstripes, char* err,
                      int errlen) {
  try {
    auto dp = std::make_shared<tft::DataPlane>(rank, world, nstripes);
    std::lock_guard<std::mutex> g(g_dp_mu);
    int64_t h = g_dp_next++;
    g_dps[h] = std::move(dp);
    return h;
  } catch (const std::exception& e) {
    dp_set_err(err, errlen, e.what());
    return 0;
  }
}

int tft_dp_port(int64_t h) {
  auto dp = dp_get(h);
  return dp ? dp->port() : -1;
}

int tft_dp_connect(int64_t h, int peer, const char* host, int port,
                   int64_t timeout_ms, char* err, int errlen) {
  auto dp = dp_get(h);
  if (!dp) {
    dp_set_err(err, errlen, "bad handle");
    return -1;
  }
  std::string e;
  if (!dp->connect_peer(peer, host, port, timeout_ms, &e)) {
    dp_set_err(err, errlen, e);
    return -1;
  }
  return 0;
}

int tft_dp_wait_ready(int64_t h, int64_t timeout_ms, char* err, int errlen) {
  auto dp = dp_get(h);
  if (!dp) {
    dp_set_err(err, errlen, "bad handle");
    return -1;
  }
  std::string e;
  if (!dp->wait_ready(timeout_ms, &e)) {
    dp_set_err(err, errlen, e);
    return -1;
  }
  return 0;
}

int tft_dp_enable_cma(int64_t h, const int64_t* pids, int n, char* err,
                      int errlen) {
  auto dp = dp_get(h);
  if (!dp) {
    dp_set_err(err, errlen, "bad handle");
    return -1;
  }
  dp->enable_cma(std::vector<int64_t>(pids, pids + n));
  return 0;
}

// `data` ends as the allreduce of what the `nsegments` read-only segments
// hold (`seg_addrs[i]`, `seg_bytes[i]`: their concatenation is this rank's
// nelems f32); nsegments 0 is the in-place op on `data`. The segments are
// never written and must outlive the call (DataPlane::allreduce).
int tft_dp_allreduce_from(int64_t h, void* data, int64_t nelems,
                          const uint64_t* seg_addrs, const int64_t* seg_bytes,
                          int nsegments, int dtype, int op, int divisor,
                          int codec, uint32_t tag, int64_t timeout_ms,
                          int* bad_peer, char* err, int errlen) {
  auto dp = dp_get(h);
  if (!dp) {
    dp_set_err(err, errlen, "bad handle");
    return -1;
  }
  std::vector<tft::DpSegment> source((size_t)(nsegments > 0 ? nsegments : 0));
  for (size_t i = 0; i < source.size(); ++i) {
    source[i] = {(const void*)(uintptr_t)seg_addrs[i], seg_bytes[i]};
  }
  std::string e;
  int bp = -1;
  int rc = dp->allreduce(data, nelems, source.data(), (int)source.size(),
                         (tft::DpDtype)dtype, (tft::DpOp)op, divisor,
                         (tft::DpCodec)codec, tag, timeout_ms, &bp, &e);
  if (bad_peer) *bad_peer = bp;
  if (rc != 0) dp_set_err(err, errlen, e);
  return rc;
}

int tft_dp_allreduce(int64_t h, void* data, int64_t nelems, int dtype, int op,
                     int divisor, int codec, uint32_t tag, int64_t timeout_ms,
                     int* bad_peer, char* err, int errlen) {
  return tft_dp_allreduce_from(h, data, nelems, nullptr, nullptr, 0, dtype, op,
                               divisor, codec, tag, timeout_ms, bad_peer, err,
                               errlen);
}

// The account of the plane's last allreduce as int64s in DpAccount's order;
// writes min(n, kDpAccountFields) of them and returns that count (-1: bad
// handle). Call it from the thread that called tft_dp_allreduce.
int tft_dp_last_account(int64_t h, int64_t* out, int n) {
  auto dp = dp_get(h);
  if (!dp) return -1;
  const tft::DpAccount a = dp->last_account();
  int k = n < 0 ? 0 : n < tft::kDpAccountFields ? n : tft::kDpAccountFields;
  memcpy(out, &a, (size_t)k * sizeof(int64_t));
  return k;
}

void tft_dp_free(int64_t h) {
  std::shared_ptr<tft::DataPlane> dp;
  {
    std::lock_guard<std::mutex> g(g_dp_mu);
    auto it = g_dps.find(h);
    if (it == g_dps.end()) return;
    dp = std::move(it->second);
    g_dps.erase(it);
  }
  dp->shutdown();
}

}  // extern "C"
