// torchft_tpu native core — striped cross-process gradient data plane.
//
// The role NCCL plays for the reference's cross-replica-group gradient
// averaging (/root/reference/torchft/process_group.py:431-447): a
// line-rate, GIL-free allreduce between OS processes. Python's TCP ring
// (torchft_tpu/collectives.py) tops out well under loopback line rate —
// every hop pays Python thread creation, GIL handoffs, and interpreted
// framing — so the HOT DATA PATH lives here: persistent per-stripe worker
// threads drive a ring allreduce over N parallel sockets per peer with
// nonblocking full-duplex pumps, f32 accumulate, and optional bf16 wire
// encoding, all without touching the interpreter. Rendezvous, epochs,
// tags and fallback ops stay in Python (collectives.py) — this plane is
// reconfigured by constructing a fresh instance per quorum epoch.
#pragma once

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <map>
#include <memory>
#include <mutex>
#include <set>
#include <string>
#include <thread>
#include <vector>

namespace tft {

// element dtypes on the local buffer
enum class DpDtype : int { kF32 = 0 };
// reduce ops (AVG is SUM with the divisor `world`; see allreduce)
enum class DpOp : int { kSum = 0, kAvg = 1, kMax = 2, kMin = 3 };
// wire codecs (torchft_tpu/wire_codec.py mirrors these formats byte for
// byte; values must match the ctypes binding's NativeDataPlane.CODEC):
//   kF32  — raw 4 bytes/elem
//   kBf16 — round-to-nearest-even truncation, 2 bytes/elem
//   kInt8 — per-chunk symmetric quantization: a 4-byte LE f32 scale
//           header (max|x|/127; NaN when the chunk holds non-finite
//           values so NaN propagates loudly) + one int8 per element
enum class DpCodec : int { kF32 = 0, kBf16 = 1, kInt8 = 2 };

// Where one allreduce's hops spent their time: nanoseconds of the clock
// lathist::now_ns reads, summed over a stripe's hops. Always kept — a
// handful of clock reads a hop. Plane cma (cma_hop) splits a hop into
// desc_wait (own descriptor sent -> the left neighbour's received), pull
// (the process_vm_readv loop) and ack_wait (own ack sent -> the right
// neighbour's received); plane tcp (hop) is a full-duplex pump that cannot
// tell waiting from moving, so it has the one number pump. Both: reduce
// (the reduce_* passes over scratch after a hop) and codec (encode / decode
// passes; 0 on cma, whose payload stays f32). Bytes are what was pulled,
// received by the pump, and reduced (f32 bytes written). An allreduce
// with a source (see allreduce) adds copy: the stripe's memcpy of own
// elements from the source into the destination (plane tcp and the lossy
// codecs; plane cma copies nothing), its bytes, and those of them whose
// destination lies 1 to 1023 B ahead of their source modulo 4096 (the window
// of 4K aliasing, ddp.py's _ALIAS_WINDOW_B); from_source is 1 for such an op.
// The op's account (last_account) is the MEAN over its stripes of each time
// — stripes run in parallel, so the mean compares with the caller's wall
// seconds — the SUM over stripes of each byte count, the number of stripes,
// and the slowest stripe's summed times. The order of the fields is the
// C ABI's (tft_dp_last_account; _native.NativeDataPlane.ACCOUNT mirrors it).
struct DpAccount {
  int64_t desc_wait_ns = 0;
  int64_t pull_ns = 0;
  int64_t ack_wait_ns = 0;
  int64_t pump_ns = 0;
  int64_t reduce_ns = 0;
  int64_t codec_ns = 0;
  int64_t pull_bytes = 0;
  int64_t pump_bytes = 0;
  int64_t reduce_bytes = 0;
  int64_t stripes = 0;
  int64_t slowest_stripe_ns = 0;
  int64_t copy_ns = 0;
  int64_t copy_bytes = 0;
  int64_t copy_aliased_bytes = 0;
  int64_t from_source = 0;
};
constexpr int kDpAccountFields = 15;
static_assert(sizeof(DpAccount) == kDpAccountFields * sizeof(int64_t),
              "DpAccount is kDpAccountFields int64s, in the ABI's order");

// One read-only piece of an allreduce's source (see DataPlane::allreduce).
struct DpSegment {
  const void* addr;
  int64_t bytes;
};

class DataPlane {
 public:
  // Listens on an ephemeral port and starts the acceptor + stripe workers.
  // Throws std::runtime_error on bind failure.
  DataPlane(int rank, int world, int nstripes);
  ~DataPlane();

  DataPlane(const DataPlane&) = delete;
  DataPlane& operator=(const DataPlane&) = delete;

  int port() const { return port_; }

  // Dial all stripe sockets to a lower-ranked peer (higher ranks dial
  // lower, mirroring the Python plane's convention). Returns false + err.
  bool connect_peer(int peer, const std::string& host, int port,
                    int64_t timeout_ms, std::string* err);

  // Block until every peer has all nstripes sockets established.
  bool wait_ready(int64_t timeout_ms, std::string* err);

  // Switch payload transport to cross-memory attach (process_vm_readv):
  // ring hops exchange tiny {tag,len,pieces} descriptors + acks over the
  // stripe sockets and pull the payload straight out of the left
  // neighbor's address space — one copy at memcpy speed, no loopback-TCP
  // syscall tax. Caller (Python rendezvous) must have verified every rank
  // is same-host and CMA-capable (token-checked probe); pids is indexed
  // by ring rank. The wire codec is bypassed (payloads stay exact f32 —
  // deterministic since the chunk owner's bytes are distributed verbatim).
  void enable_cma(const std::vector<int64_t>& pids);

  // In-place ring allreduce of nelems f32 starting at data. Blocking;
  // returns 0 on success, -1 on socket failure with *bad_peer set to the
  // ring rank whose socket failed (or -1 if indeterminate), or -2 on
  // DEADLINE with *bad_peer = -1 — a slow-but-alive peer must surface as
  // a retryable timeout, never as an eviction-worthy accusation (the
  // Python mesh draws the same line). With a lossy codec the wire
  // carries encoded bytes while accumulation stays f32; the allgather
  // phase forwards the chunk owner's wire bytes VERBATIM, so the decoded
  // average is bit-identical on every rank by construction.
  // `divisor` (SUM only; >= 1) turns the sum into an average WITHOUT a
  // pass of its own: the rank that owns a chunk divides in its last
  // reduce-scatter step (exact planes) or every rank divides as it
  // decodes the owner's bytes (lossy codecs) — a true f32 division by
  // (float)divisor, bit for bit np.divide(sum, divisor). The caller
  // names the divisor because it is not always `world`: the Manager
  // counts participants, and a healing group's zeros are not one.
  // With a source (`nsegments` > 0: read-only segments whose concatenation
  // is the nelems f32 this rank contributes) the op is no longer in place:
  // `data` is a destination whose content on entry is ignored, and the ring
  // reads this rank's contribution from the segments — each element exactly
  // once: every reduce step writes data = source (+) pulled, and the one
  // chunk a stripe sends raw, at step 0, goes out as it lies — plane cma
  // offers the right neighbour the segments' own pieces of it to pull
  // (nothing is copied: the allgather writes that chunk of `data` last),
  // plane tcp copies it into `data` first, by the stripe's own thread
  // (1/world of the stripe), so that its pump sends from `data` as ever.
  // The same additions in the same order on the same values: `data` ends
  // bit for bit as the in-place op on a packed copy would leave it. A lossy
  // codec re-reads partial sums it wrote, so there the stripe copies its
  // whole range first and runs in place (job.codec decides). The segments
  // are never written and must stay alive until allreduce() returns.
  int allreduce(void* data, int64_t nelems, const DpSegment* source,
                int nsegments, DpDtype dtype, DpOp op, int divisor,
                DpCodec codec, uint32_t tag, int64_t timeout_ms,
                int* bad_peer, std::string* err);

  // The account of the last allreduce() on this plane, failed ones too (an
  // op that failed reports what it had). Call it from the thread that
  // called allreduce(): the collectives' one op thread.
  DpAccount last_account() const { return last_account_; }

  void shutdown();

 private:
  // An op's source as the stripes read it: each segment's first element
  // and its element offset in the bucket (`start` holds one more: the end).
  struct Source {
    std::vector<const float*> ptr;
    std::vector<int64_t> start;
  };
  struct Job {
    uint8_t* base = nullptr;   // stripe start
    int64_t nelems = 0;        // stripe elements
    const Source* source = nullptr;  // null: in place
    int64_t first = 0;         // the stripe's first element in the bucket
    DpOp op = DpOp::kSum;
    int divisor = 1;           // see allreduce()
    DpCodec codec = DpCodec::kF32;
    uint32_t tag = 0;
    int64_t deadline_ms = 0;  // absolute, now_ms() clock
  };
  struct Stripe {
    std::thread worker;
    std::mutex mu;
    std::condition_variable cv;
    bool has_job = false;
    bool done = false;
    Job job;
    int rc = 0;
    int bad_peer = -1;
    std::string err;
    // per-epoch wire scratch (vectors keep their capacity across jobs,
    // so the hot path never allocates after the first round)
    std::vector<uint8_t> scratch_send;  // wire-encoded outgoing chunk
    std::vector<uint8_t> scratch_recv;  // wire-encoded incoming chunk
    std::vector<uint8_t> scratch_fwd;   // verbatim-forward double buffer
    // this job's account: zeroed under mu when the job is handed over,
    // written by the worker alone while it runs, read under mu once done
    DpAccount acct;
  };

  void accept_loop();
  void hello_handshake(int fd, uint64_t id);
  void worker_loop(int stripe_idx);
  int run_stripe(int stripe_idx, Job& job, int* bad_peer, std::string* err);
  bool hop(int send_fd, int recv_fd, const uint8_t* sbuf, size_t sn,
           uint8_t* rbuf, size_t rn, uint32_t tag, int64_t deadline_ms,
           bool* send_failed, bool* timed_out, std::string* err);
  bool cma_hop(int send_fd, int recv_fd, const DpSegment* offer, int noffer,
               uint8_t* rbuf, size_t rn, uint32_t tag, int64_t deadline_ms,
               bool* send_failed, bool* timed_out, std::string* err,
               DpAccount* acct);
  int fd_for(int peer, int stripe);

  int rank_;
  int world_;
  int nstripes_;
  int listen_fd_ = -1;
  int port_ = 0;
  std::thread acceptor_;
  std::atomic<bool> closed_{false};

  std::mutex socks_mu_;
  std::condition_variable socks_cv_;
  // socks_[peer][stripe] = fd (or -1)
  std::map<int, std::vector<int>> socks_;

  std::vector<std::unique_ptr<Stripe>> stripes_;
  DpAccount last_account_;  // allreduce()'s caller thread only
  // the running op's source: written by allreduce() before it hands the
  // jobs over, read by the stripes until it has seen them all done
  Source source_;

  // atomic publication flag: enable_cma() runs on the Python control
  // thread AFTER the stripe workers (started in the constructor) are
  // already live — peer_pids_ is written first, then cma_ is
  // store(release)d, and the workers' load(acquire) in run_stripe/
  // cma_hop makes the pids visible. A plain bool here is a data race
  // (the publication relied on the job-queue mutex by accident).
  std::atomic<bool> cma_{false};
  std::vector<int64_t> peer_pids_;  // published by cma_ release-store

  // hello handshakes run off the accept thread so one stalled dial can't
  // starve every other peer's stripe connections during rendezvous;
  // finished threads announce their id and the accept loop reaps them
  std::mutex hello_mu_;
  std::map<uint64_t, std::thread> hello_threads_;
  std::vector<uint64_t> hello_finished_;
  uint64_t next_hello_id_ = 0;
  std::set<int> hello_fds_;  // in-flight, shut down on close
};

}  // namespace tft
