// accl-tpu native runtime: the SESSION translation unit.
//
// One instance per rank: an eager rx-buffer ring with (src, tag, seqn)
// seek matching (reference rxbuf_offload/*), rendezvous address/
// completion matching with pending queues (reference
// ccl_offload_control.c:142-408), the reliability sublayer's
// retransmit/ack policy, and a single sequencer thread running the
// call + retry queues round-robin with current_step resumption
// (reference run(), ccl_offload_control.c:2308-2483).
//
// The wire itself lives BELOW the POE seam (src/transport.h): this TU
// builds frames and hands them to a Poe (TCP mesh / UDP datagrams /
// in-process registry) as scatter-gather views, and receives inbound
// frames via PoeSink::on_frame. It never touches a socket.
//
// Collective algorithms mirror the firmware's selections exactly —
// eager/rendezvous split, ring vs flat vs binary tree by tuning register —
// the same rules accl_tpu/sequencer/plan.py encodes for the XLA path.

#include "../include/acclrt.h"

#include <atomic>
#include <cassert>
#include <chrono>
#include <condition_variable>
#include <cstddef>
#include <cstring>
#include <deque>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <set>
#include <string>
#include <cstdio>
#include <cstdlib>
#include <thread>
#include <unordered_map>
#include <vector>

#include "reliability.h"
#include "transport.h"
#include "wire.h"

using namespace acclw;

namespace {

// ---------------------------------------------------------------------------
// Error codes (mirror accl_tpu.constants.ErrorCode / constants.hpp:341-376)
// ---------------------------------------------------------------------------
enum Err : uint32_t {
  NO_ERROR = 0,
  DMA_DECODE_ERROR = 1u << 2,
  RECEIVE_TIMEOUT_ERROR = 1u << 11,
  COLLECTIVE_NOT_IMPLEMENTED = 1u << 14,
  DMA_SIZE_ERROR = 1u << 18,
  ARITH_ERROR = 1u << 19,
  PACK_SEQ_NUMBER_ERROR = 1u << 21,
  COMPRESSION_ERROR = 1u << 22,
  DMA_TAG_MISMATCH_ERROR = 1u << 26,
  NOT_READY = 0x80000000u,  // internal: requeue with current_step saved
};

// Exchange-memory register offsets (constants.hpp:139-154).
enum Addr : uint32_t {
  RETCODE = 0x1FFC,
  IDCODE = 0x1FF8,
  CFGRDY = 0x1FF4,
  PERFCNT = 0x1FF0,
  // repurposed spare: allreduce payloads <= this (and > max_eager) run
  // the reference's rendezvous reduce+bcast composition (.c:1878-1887);
  // 0 = streamed ring at every size (measured default, emu_bench.csv)
  ALLREDUCE_COMPOSITION_MAX_COUNT = 0x1FD8,
  REDUCE_FLAT_TREE_MAX_COUNT = 0x1FD4,
  REDUCE_FLAT_TREE_MAX_RANKS = 0x1FD0,
  BCAST_FLAT_TREE_MAX_RANKS = 0x1FCC,
  GATHER_FLAT_TREE_MAX_COUNT = 0x1FC8,
  GATHER_FLAT_TREE_MAX_FANIN = 0x1FC4,
};

constexpr uint32_t TAG_ANY = 0xFFFFFFFFu;
constexpr uint32_t EXCHMEM_BYTES = 8192;


// Scenario ids (constants.hpp:190-216).
enum Scenario : uint32_t {
  SC_CONFIG = 0, SC_COPY = 1, SC_COMBINE = 2, SC_SEND = 3, SC_RECV = 4,
  SC_BCAST = 5, SC_SCATTER = 6, SC_GATHER = 7, SC_REDUCE = 8,
  SC_ALLGATHER = 9, SC_ALLREDUCE = 10, SC_REDUCE_SCATTER = 11,
  SC_BARRIER = 12, SC_ALLTOALL = 13, SC_NOP = 255,
};

// Wire format (MsgType/MsgHeader/MSG_MAGIC) lives in wire.h — shared
// with the transport side of the POE seam.

// ---------------------------------------------------------------------------
// Timed condition waits: gcc-10's libtsan has no pthread_cond_clockwait
// interceptor, and libstdc++ routes steady-clock wait_for/wait_until
// through clockwait — the wait's internal unlock/reacquire becomes
// invisible to TSan, so every lock pairing after a timed wait reports
// as a false race or double lock. In TSan builds route timed waits
// through the system clock, which takes the intercepted
// pthread_cond_timedwait path. These timeouts are heartbeat ticks and
// lost-wakeup backstops, not correctness deadlines, so wall-clock
// sensitivity is acceptable in the sanitizer lane.
// ---------------------------------------------------------------------------
template <class Rep, class Period>
static std::cv_status cv_wait_for(std::condition_variable &cv,
                                  std::unique_lock<std::mutex> &lk,
                                  std::chrono::duration<Rep, Period> d) {
#if defined(__SANITIZE_THREAD__)
  return cv.wait_until(lk, std::chrono::system_clock::now() + d);
#else
  return cv.wait_for(lk, d);
#endif
}

template <class Rep, class Period, class Pred>
static bool cv_wait_for(std::condition_variable &cv,
                        std::unique_lock<std::mutex> &lk,
                        std::chrono::duration<Rep, Period> d, Pred p) {
#if defined(__SANITIZE_THREAD__)
  return cv.wait_until(lk, std::chrono::system_clock::now() + d,
                       std::move(p));
#else
  return cv.wait_for(lk, d, std::move(p));
#endif
}

// ---------------------------------------------------------------------------
// dtype helpers: elementwise SUM/MAX incl. fp16/bf16 via uint16 conversion
// (reduce_ops plugin analog, here over host memory)
// ---------------------------------------------------------------------------

static inline float half_to_float(uint16_t h) {
  uint32_t sign = (h & 0x8000u) << 16;
  uint32_t exp = (h >> 10) & 0x1F;
  uint32_t man = h & 0x3FF;
  uint32_t bits;
  if (exp == 0) {
    if (man == 0) {
      bits = sign;
    } else {  // subnormal
      int e = -1;
      do { man <<= 1; e++; } while (!(man & 0x400));
      bits = sign | ((127 - 15 - e) << 23) | ((man & 0x3FF) << 13);
    }
  } else if (exp == 31) {
    bits = sign | 0x7F800000u | (man << 13);
  } else {
    bits = sign | ((exp + 127 - 15) << 23) | (man << 13);
  }
  float f;
  std::memcpy(&f, &bits, 4);
  return f;
}

static inline uint16_t float_to_half(float f) {
  uint32_t bits;
  std::memcpy(&bits, &f, 4);
  uint32_t sign = (bits >> 16) & 0x8000u;
  uint32_t exp8 = (bits >> 23) & 0xFF;
  uint32_t man = bits & 0x7FFFFF;
  if (exp8 == 0xFF)  // inf / NaN propagate
    return (uint16_t)(sign | 0x7C00 | (man ? 0x200 : 0));
  int32_t exp = (int32_t)exp8 - 127 + 15;
  if (exp <= 0) {
    // subnormal fp16 (matches IEEE/ml_dtypes/XLA, not flush-to-zero):
    // shift the full 24-bit significand right with round-to-nearest-even
    if (exp < -10) return (uint16_t)sign;  // underflows even subnormals
    uint32_t sig = man | 0x800000;         // implicit leading 1
    uint32_t shift = (uint32_t)(14 - exp); // 14..24
    uint32_t kept = sig >> shift;
    uint32_t rem = sig & ((1u << shift) - 1);
    uint32_t half_pt = 1u << (shift - 1);
    if (rem > half_pt || (rem == half_pt && (kept & 1)))
      kept++;  // may carry into the normal range (exp field 1) — still valid
    return (uint16_t)(sign | kept);
  }
  if (exp >= 31) return (uint16_t)(sign | 0x7C00); // overflow to inf
  // round to nearest even: add 0xFFF + the lsb of the kept mantissa
  uint32_t rounded = man + 0xFFF + ((man >> 13) & 1);
  if (rounded & 0x800000) {
    rounded = 0;
    exp++;
    if (exp >= 31) return (uint16_t)(sign | 0x7C00);
  }
  return (uint16_t)(sign | (exp << 10) | (rounded >> 13));
}

static inline float bf16_to_float(uint16_t h) {
  uint32_t bits = ((uint32_t)h) << 16;
  float f;
  std::memcpy(&f, &bits, 4);
  return f;
}

static inline uint16_t float_to_bf16(float f) {
  uint32_t bits;
  std::memcpy(&bits, &f, 4);
  uint32_t lsb = (bits >> 16) & 1;
  bits += 0x7FFF + lsb;  // round to nearest even
  return (uint16_t)(bits >> 16);
}

static uint32_t dtype_bytes(uint32_t dt) {
  switch (dt) {
    case ACCL_DT_INT8: return 1;
    case ACCL_DT_FLOAT16: case ACCL_DT_BFLOAT16: return 2;
    case ACCL_DT_FLOAT32: case ACCL_DT_INT32: return 4;
    case ACCL_DT_FLOAT64: case ACCL_DT_INT64: return 8;
    default: return 0;
  }
}

template <typename T, typename Op>
static void combine_typed(void *a, const void *b, size_t n, Op op) {
  T *pa = (T *)a;
  const T *pb = (const T *)b;
  for (size_t i = 0; i < n; i++) pa[i] = op(pa[i], pb[i]);
}

// a := op(a, b), elementwise over n elements. func: 0=SUM, 1=MAX.
static uint32_t combine_buffers(uint32_t dt, uint32_t func, void *a,
                                const void *b, size_t n) {
  auto do16 = [&](auto to_f, auto from_f) {
    uint16_t *pa = (uint16_t *)a;
    const uint16_t *pb = (const uint16_t *)b;
    for (size_t i = 0; i < n; i++) {
      float x = to_f(pa[i]), y = to_f(pb[i]);
      pa[i] = from_f(func == 0 ? x + y : (x > y ? x : y));
    }
  };
  switch (dt) {
    case ACCL_DT_FLOAT32:
      func == 0 ? combine_typed<float>(a, b, n, [](float x, float y) { return x + y; })
                : combine_typed<float>(a, b, n, [](float x, float y) { return x > y ? x : y; });
      return NO_ERROR;
    case ACCL_DT_FLOAT64:
      func == 0 ? combine_typed<double>(a, b, n, [](double x, double y) { return x + y; })
                : combine_typed<double>(a, b, n, [](double x, double y) { return x > y ? x : y; });
      return NO_ERROR;
    case ACCL_DT_INT32:
      func == 0 ? combine_typed<int32_t>(a, b, n, [](int32_t x, int32_t y) { return x + y; })
                : combine_typed<int32_t>(a, b, n, [](int32_t x, int32_t y) { return x > y ? x : y; });
      return NO_ERROR;
    case ACCL_DT_INT64:
      func == 0 ? combine_typed<int64_t>(a, b, n, [](int64_t x, int64_t y) { return x + y; })
                : combine_typed<int64_t>(a, b, n, [](int64_t x, int64_t y) { return x > y ? x : y; });
      return NO_ERROR;
    case ACCL_DT_FLOAT16: do16(half_to_float, float_to_half); return NO_ERROR;
    case ACCL_DT_BFLOAT16: do16(bf16_to_float, float_to_bf16); return NO_ERROR;
    default: return ARITH_ERROR;
  }
}

// CRC32C + frame_crc live in reliability.{h,cpp} (session-side; the
// transport never computes integrity).

// ---------------------------------------------------------------------------
// runtime
// ---------------------------------------------------------------------------

// Hop-shape constants SINGLE-SOURCED with the Python timing model
// (accl_tpu/constants.py LOGP_ALLREDUCE_HOP_BYTES /
// LOGP_ALLGATHER_HOP_BYTES / STREAM_SEG_BYTES); tests/test_timing.py
// pins the two definitions together so the model cannot silently drift
// from this executor.
static constexpr uint64_t LOGP_ALLREDUCE_HOP_BYTES = 32 * 1024;
static constexpr uint64_t LOGP_ALLGATHER_HOP_BYTES = 128 * 1024;
static constexpr uint64_t STREAM_SEG_BYTES = 1ull << 20;

struct RxSlot {
  enum { IDLE, VALID } status = IDLE;
  uint32_t src = 0, tag = 0, seqn = 0;
  uint32_t lane = 0;  // the (src, lane) seqn stream this segment rides
  uint64_t msg_bytes = 0;  // total length of the message this segment is of
  uint64_t msg_off = 0;    // this segment's byte offset inside that message
  // landing time: a strict recv meeting a MISMATCHED head defers while
  // the head is young (another consumer's traffic interleaved on the
  // link) and only fails fast once it has provably gone unclaimed
  std::chrono::steady_clock::time_point t_land{};
  std::vector<uint8_t> data;
};

struct RndzvAddr {
  uint32_t src;
  uint64_t vaddr;
  uint64_t bytes;
  uint32_t tag;
  uint32_t host;
  // one-sided writes land DIRECTLY at vaddr (no staging copy): in_use
  // pins the target across the rx thread's poll-bounded read; abort is
  // the revoker's bounded-wait handshake (same protocol as
  // accl_rt::EagerLanding). Only meaningful inside posted_addrs.
  bool in_use = false;
  bool abort = false;
};

struct RndzvDone {
  uint32_t src;
  uint64_t vaddr;
  uint64_t bytes;
  uint32_t tag;
};

// Resolved communicator view: group size, this rank's position in the
// group, and the group-rank -> global-rank map (empty = identity over the
// transport world). The firmware equivalent caches the communicator
// addressed by the descriptor's comm_addr per call
// (ccl_offload_control.c:2317-2372).
struct CommView {
  uint32_t world = 0;
  uint32_t rank = 0;
  std::vector<uint32_t> map;
  uint32_t g(uint32_t r) const { return map.empty() ? r : map[r]; }
};

// Per-call persistent collective state across NOT_READY requeues: every
// do_* below is a step-indexed state machine riding Call.current_step (the
// firmware requeues ANY NOT_READY collective with current_step,
// ccl_offload_control.c:2308-2483), and this carries the data a resumed
// pass needs that does not live in caller memory.
struct CollState {
  uint64_t off = 0;  // current op's partial progress: eager bytes landed,
                     // or the rendezvous posted-address marker
  // SC_RECV posted-order FIFO ticket (see the recv op): assigned on the
  // call's first eager pass, dropped with the registry entry on terminal
  uint64_t ticket = 0;
  bool ticketed = false;
  // direct-placement landing registered for the CURRENT recv op (see
  // accl_rt::EagerLanding); cleared when the op completes
  bool landing = false;
  // Config/tuning SNAPSHOT taken on the call's first pass: the replayed
  // op sequence must be deterministic, and a config call (or tuning
  // register write) executing between requeue passes of a parked
  // collective must not flip its protocol/algorithm branches mid-flight.
  bool cfg = false;
  uint32_t max_eager = 0;
  uint64_t max_rndzv = 0;
  uint32_t tun_bcast_ranks = 0, tun_gather_fanin = 0, tun_gather_count = 0,
           tun_reduce_ranks = 0, tun_reduce_count = 0;
  uint64_t tun_allred_comp = 0;
  int wire_bf16 = -1;  // compressed wire dtype, snapshotted like the rest
  // algorithm scratch that must survive requeues (reduce accumulators,
  // ring relay buffers, rendezvous landing slots, the reduce_scatter
  // composition's full-width intermediate)
  std::vector<uint8_t> acc, tmp, full;
  // addresses THIS call posted and has not yet seen complete: revoked on
  // timeout so a late write cannot land in memory the caller reuses
  std::deque<RndzvAddr> posted;
  void unpost(uint64_t vaddr) {
    for (auto it = posted.begin(); it != posted.end(); ++it)
      if (it->vaddr == vaddr) { posted.erase(it); return; }
  }
};

struct Call {
  int64_t handle;
  uint32_t desc[15];
  uint32_t dtype;
  void *op0, *op1, *res;
  bool started = false;  // has executed at least one pass (holds its
                         // communicator's in-flight serialization slot)
  // counted once against the ACCL_RT_FAULT_KILL_AFTER budget (a
  // NOT_READY requeue must not burn the countdown twice)
  bool started_counted = false;
  uint32_t current_step = 0;  // resumption point across NOT_READY requeues
  // resolved communicator persists across requeues like current_step
  bool comm_resolved = false;
  CommView comm;
  bool deadline_set = false;
  std::chrono::steady_clock::time_point deadline;
  std::chrono::steady_clock::time_point t_start;
  // compressed-domain scratch: persists across retry requeues so partial
  // progress (already-landed segments) survives re-execution
  std::shared_ptr<std::vector<uint16_t>> c16_op0, c16_op1, c16_res;
  // step-machine scratch (shared with the compressed-domain inner Call)
  std::shared_ptr<CollState> cstate;
  // trace-ring bookkeeping (ACCL_RT_TRACE=1): sequencer-counter snapshot
  // at enqueue (the span's per-call delta base) and the deferred-head-
  // mismatch fault code behind an eventual RECEIVE_TIMEOUT
  uint64_t ctr0[4] = {0, 0, 0, 0};
  uint32_t trace_detail = 0;
  // last_defer.count at this call's first pass: the timeout detail may
  // only report mismatches recorded DURING the call — a stale note from
  // an earlier (resolved) deferral must not masquerade as this
  // timeout's root cause
  uint64_t defer0 = 0;
};

struct Completion {
  std::atomic<int> done{0};
  uint32_t retcode = 0;
  uint64_t duration_ns = 0;
};

}  // namespace

struct accl_rt : public acclw::PoeSink {
  uint32_t world, rank;  // ACCL_INIT_CONST
  uint32_t rx_buf_bytes;  // ACCL_INIT_CONST
  uint32_t max_eager;  // ACCL_ROLE_ONLY(seq); SC_CONFIG-mutable
  uint64_t max_rndzv;  // ACCL_ROLE_ONLY(seq); SC_CONFIG-mutable
  std::vector<uint8_t> exchmem = std::vector<uint8_t>(EXCHMEM_BYTES, 0);  // ACCL_GUARDED_BY(exch_mu)
  std::mutex exch_mu;

  // The Protocol Offload Engine behind the seam (src/transport.h) — TCP
  // full mesh (session-based, the EasyNet-class POE), one shared
  // datagram socket (sessionless, the VNX-UDP POE analog: every segment
  // a standalone packet carrying the full 64 B header, reassembled
  // purely by (src, tag, seqn) — the udp_depacketizer role), or the
  // intra-process registry (direct-call delivery, the intra-node
  // fast-path role NCCL fills with SHM/P2P transports). The session
  // builds frames and hands the Poe scatter-gather views; inbound
  // frames arrive via on_frame (the PoeSink side of this struct).
  std::unique_ptr<acclw::Poe> poe;  // ACCL_INIT_CONST
  bool udp_mode = false;  // ACCL_INIT_CONST
  // Per-peer LANES (TCP only, ACCL_RT_LANES, clamped [1, 2]): each
  // (peer, lane) pair is an independent ordered link carrying its own
  // seqn stream, so a jumbo eager message on the bulk lane (lane 1,
  // messages >= lane_bulk_bytes) cannot head-of-line-block a small
  // message on the default lane. All per-peer stream state below is
  // indexed by sid = rank * n_lanes + lane. Default 1 lane — the
  // single-stream wire, bit-identical to the pre-lane protocol.
  uint32_t n_lanes = 1;  // ACCL_INIT_CONST
  uint64_t lane_bulk_bytes = 64ull << 10;  // ACCL_INIT_CONST; ACCL_RT_LANE_BULK_BYTES
  bool legacy_wire = false;  // ACCL_INIT_CONST; ACCL_RT_WIRE_LEGACY: per-frame-syscall
                             // cost model, batching off (the A/B baseline)
  bool tx_batch_on = false;  // ACCL_INIT_CONST; computed at create: vectored batching
                             // armed (off under chaos/WAN/legacy/local
                             // — those paths need per-frame emission)
  uint32_t sid(uint32_t r, uint32_t lane) const { return r * n_lanes + lane; }
  uint32_t lane_of(uint64_t msg_bytes) const {
    return (n_lanes > 1 && msg_bytes >= lane_bulk_bytes) ? 1u : 0u;
  }
  std::vector<bool> hello_seen;      // ACCL_GUARDED_BY(hello_mu); bring-up handshake state
  std::mutex hello_mu;
  std::condition_variable hello_cv;
  std::atomic<bool> stop{false};

  // eager rx ring + notifications (rxbuf_offload analog). idle_q is the
  // IDLE free-list (indices into rx_slots) so landing a segment is O(1)
  // even when the datagram transport grows the ring into the thousands.
  std::vector<RxSlot> rx_slots;  // ACCL_GUARDED_BY(rx_mu)
  std::vector<size_t> idle_q;  // ACCL_GUARDED_BY(rx_mu)
  size_t base_rx_slots = 0;  // ACCL_INIT_CONST; configured ring size; growth beyond it is
                             // burst absorption and compacts when drained
  // (sid, seqn) -> slot index: seeks are O(1) even when a datagram burst
  // grows the ring to 2^20 slots (a linear scan made draining a large
  // burst quadratic). src_valid_count keeps stray-seqn detection O(1).
  // All stream-indexed maps below key on sid = src * n_lanes + lane —
  // each lane is its own ordered seqn stream.
  std::unordered_map<uint64_t, size_t> rx_index;  // ACCL_GUARDED_BY(rx_mu)
  std::vector<uint32_t> src_valid_count;  // ACCL_GUARDED_BY(rx_mu)
  // sid -> the call (CollState address) that has consumed part of a
  // multi-segment eager message from that src and owns the remainder of
  // its stream: segments of one message share tag and consecutive seqns,
  // so a DIFFERENT call matching the next head by tag would interleave
  // payload mid-message (two concurrent TAG_ANY recvs, or a recv racing
  // a collective on the same src link). Guarded by rx_mu; released on
  // message completion or call termination (release_rx_ownership).
  std::unordered_map<uint32_t, const void *> rx_stream_owner;  // ACCL_GUARDED_BY(rx_mu)
  static uint64_t rx_key(uint32_t sid, uint32_t seqn) {
    return ((uint64_t)sid << 32) | seqn;
  }

  // Outstanding SC_RECV registry for posted-order FIFO pairing (see the
  // recv op). Guarded by rx_mu, like the stream-owner map.
  struct OutstandingRecv {
    uint32_t src, tag;
    uint64_t bytes, ticket;
    const void *tok;
  };
  std::vector<OutstandingRecv> outstanding_recvs;  // ACCL_GUARDED_BY(rx_mu)
  uint64_t recv_ticket_next = 0;  // ACCL_GUARDED_BY(rx_mu)

  // Last strict-recv head mismatch that DEFERRED instead of erroring
  // (the head_is_claimable softening in seek_locked): a deferred
  // protocol fault that never resolves surfaces as a plain
  // RECEIVE_TIMEOUT, so the mismatch is recorded here and echoed in the
  // eventual timeout detail. Guarded by rx_mu like the rx state it
  // describes.
  struct DeferNote {
    uint64_t count = 0;  // defers recorded since bring-up
    uint32_t src = 0;
    uint32_t head_tag = 0, want_tag = 0, head_seqn = 0;
    uint64_t head_msg = 0, head_off = 0, want_msg = 0;
    // the fault code the mismatch WOULD have raised had the head been
    // provably stray (DMA_TAG_MISMATCH_ERROR / DMA_SIZE_ERROR): the
    // NOT_READY softening must not hide which protocol check tripped
    uint32_t code = 0;
  } last_defer;  // ACCL_GUARDED_BY(rx_mu)
  // ACCL_REQUIRES(rx_mu)
  void note_defer_locked(const RxSlot &s, uint32_t want_tag,
                         uint64_t want_msg, uint32_t code) {
    last_defer.count++;
    last_defer.src = s.src;
    last_defer.head_tag = s.tag;
    last_defer.want_tag = want_tag;
    last_defer.head_seqn = s.seqn;
    last_defer.head_msg = s.msg_bytes;
    last_defer.head_off = s.msg_off;
    last_defer.want_msg = want_msg;
    last_defer.code = code;
  }

  // Direct-placement eager landing (rxbuf bypass): a parked strict recv
  // registers its destination so the rx thread reads subsequent
  // segments of ITS message straight into the final buffer — no slot
  // allocation, no staging copy. The eager-path analog of the
  // reference's zero-copy rendezvous write (rendezvous lands at the
  // posted vaddr), sized for the streamed whole-chunk collectives where
  // the bytes are. TCP only: the ordered link guarantees the next
  // segments are the message's continuation; datagram reordering keeps
  // the slot path. Guarded by rx_mu; `in_use` pins the buffer while the
  // rx thread is mid-read (revocation waits on it).
  struct EagerLanding {
    uint8_t *base = nullptr;
    uint64_t want = 0, landed = 0;
    uint32_t tag = 0;
    bool in_use = false;  // rx thread mid-read into base
    bool abort = false;   // revoker asked the rx thread to let go
    const void *tok = nullptr;
  };
  std::unordered_map<uint32_t, EagerLanding> eager_landings;  // ACCL_GUARDED_BY(rx_mu); by sid

  // Remove a call's landings (rx_mu held via lk). An in-flight direct
  // read is asked to let go via `abort`; the rx thread's read loop is
  // poll-bounded (it re-checks under rx_mu at least every 100 ms even
  // against a frozen peer), acknowledges by clearing in_use and
  // diverting the rest of the segment to scratch, so this wait is
  // BOUNDED — the sequencer cannot wedge behind a dead link the way an
  // unbounded recv_all wait would. A partially-landed message arms the
  // orphan drain for its tail. The cv wait releases the lock, so the
  // scan restarts after every wakeup (iterators don't survive the gap).
  // ACCL_REQUIRES(rx_mu)
  void drop_landings_locked(std::unique_lock<std::mutex> &lk,
                            const void *tok) {
    for (;;) {
      auto it = eager_landings.begin();
      for (; it != eager_landings.end(); ++it)
        if (it->second.tok == tok) break;
      if (it == eager_landings.end()) return;
      if (it->second.in_use) {
        it->second.abort = true;
        cv_wait_for(rx_cv, lk, std::chrono::milliseconds(250));
        continue;
      }
      if (it->second.landed > 0 && it->second.landed < it->second.want)
        rx_drain_srcs.insert(it->first);
      eager_landings.erase(it);
    }
  }
  // sids whose seqn head may hold orphaned continuation segments of a
  // message whose recv died mid-consumption: seek discards segments with
  // msg_off != 0 until the next message head surfaces. Guarded by rx_mu.
  std::set<uint32_t> rx_drain_srcs;  // ACCL_GUARDED_BY(rx_mu)

  // Drop every rx-side claim a terminating call holds: its stream
  // ownership AND its outstanding-recv ticket (a dead elder must not
  // defer younger recvs forever). An ownership entry still present here
  // means the call died mid-message — arm the orphan drain for that src.
  void release_rx_ownership(const void *tok) {
    std::unique_lock<std::mutex> lk(rx_mu);
    drop_landings_locked(lk, tok);
    for (auto it = rx_stream_owner.begin(); it != rx_stream_owner.end();) {
      if (it->second == tok) {
        rx_drain_srcs.insert(it->first);
        it = rx_stream_owner.erase(it);
      } else {
        ++it;
      }
    }
    for (auto it = outstanding_recvs.begin(); it != outstanding_recvs.end();)
      it = (it->tok == tok) ? outstanding_recvs.erase(it) : std::next(it);
    rx_cv.notify_all();
  }
  std::mutex rx_mu;
  std::condition_variable rx_cv;

  // rendezvous pending queues (CMD/STS_RNDZV(_PENDING) analog)
  std::deque<RndzvAddr> addr_q;  // ACCL_GUARDED_BY(rndzv_mu)
  std::deque<RndzvDone> done_q;  // ACCL_GUARDED_BY(rndzv_mu)
  // addresses this rank has posted via rendezvous_send_addr, keyed by
  // vaddr with the peer allowed to write them: the ONLY targets a
  // MSG_RNDZV_WRITE may land on (anything else is an arbitrary-write
  // attempt and is dropped)
  std::deque<RndzvAddr> posted_addrs;  // ACCL_GUARDED_BY(rndzv_mu); src = the peer we posted to
  std::mutex rndzv_mu;
  std::condition_variable rndzv_cv;

  // per-(peer, lane) sequence numbers (ccl_offload_control.h:297-310),
  // indexed by sid — each lane is an independent ordered stream
  std::vector<uint32_t> inbound_seq;   // ACCL_GUARDED_BY(rx_mu)
  std::vector<uint32_t> outbound_seq;  // ACCL_ROLE_ONLY(seq)

  // call + retry queues and sequencer thread (run() analog). Calls on the
  // SAME communicator execute FIFO, one in flight at a time: the eager
  // wire carries no call identity (per-src seqn streams only), so letting
  // a second same-comm collective start while the first is parked would
  // let it consume the first's segments. Different comm_addrs interleave
  // freely — that is the disjoint-communicator concurrency the retry
  // queue exists for; OVERLAPPING groups at different table addresses
  // need distinct tags, the documented eager-wire contract.
  std::map<uint32_t, uint32_t> inflight_comms;  // ACCL_GUARDED_BY(call_mu); comm_addr -> started calls
  std::deque<Call> call_q, retry_q;  // ACCL_GUARDED_BY(call_mu)
  std::mutex call_mu;
  std::condition_variable call_cv;
  std::thread seq_thread;
  std::map<int64_t, std::shared_ptr<Completion>> completions;  // ACCL_GUARDED_BY(comp_mu)
  std::mutex comp_mu;
  std::condition_variable comp_cv;
  int64_t next_handle = 1;  // ACCL_GUARDED_BY(comp_mu)

  uint64_t timeout_ms = 5000;  // ACCL_ROLE_ONLY(seq); SC_CONFIG-mutable

  // ACCL_RT_STATS=1 diagnostics: sequencer behavior counters
  std::atomic<uint64_t> stat_passes{0}, stat_parks{0}, stat_park_ns{0},
      stat_seek_miss{0}, stat_seek_hit{0};

  // Device-resident trace ring (ACCL_RT_TRACE=1): one accl_rt_span_t
  // per completed call, fixed capacity (ACCL_RT_TRACE_CAP, default
  // 4096). Overflow drops the OLDEST span and counts it — tracing can
  // degrade under load but never blocks or crashes the data plane. The
  // perf-counter-next-to-the-data-plane posture of the CCLO's duration
  // registers, with the host draining after the fact
  // (accl_rt_trace_read -> emu_device.EmuRank.trace_read).
  bool trace_on = false;  // ACCL_INIT_CONST
  size_t trace_cap = 4096;  // ACCL_INIT_CONST
  std::deque<accl_rt_span_t> trace_q;  // ACCL_GUARDED_BY(trace_mu)
  uint64_t trace_dropped = 0;  // ACCL_GUARDED_BY(trace_mu)
  std::mutex trace_mu;
  std::chrono::steady_clock::time_point t_create =  // ACCL_INIT_CONST
      std::chrono::steady_clock::now();

  void record_span(const Call &c, uint32_t rc) {
    accl_rt_span_t s{};
    s.opcode = c.desc[0];
    s.retcode = rc;
    s.detail = c.trace_detail;
    s.count = c.desc[1];
    s.bytes = (uint64_t)c.desc[1] * dtype_bytes(c.dtype);
    auto ns_since = [&](std::chrono::steady_clock::time_point t) {
      return (uint64_t)std::chrono::duration_cast<std::chrono::nanoseconds>(
                 t - t_create)
          .count();
    };
    s.start_ns = ns_since(c.t_start);
    s.end_ns = ns_since(std::chrono::steady_clock::now());
    s.d_passes = stat_passes.load() - c.ctr0[0];
    s.d_parks = stat_parks.load() - c.ctr0[1];
    s.d_seek_hit = stat_seek_hit.load() - c.ctr0[2];
    s.d_seek_miss = stat_seek_miss.load() - c.ctr0[3];
    std::lock_guard<std::mutex> g(trace_mu);
    if (trace_q.size() >= trace_cap) {
      trace_q.pop_front();  // oldest spans yield to fresh ones
      trace_dropped++;
    }
    trace_q.push_back(s);
  }

  // ACCL_RT_SHAPE=ring|logp overrides the hop-shape auto rule for
  // allreduce/allgather (0 auto, 1 ring, 2 recursive halving/doubling):
  // the benchmark harness sweeps both to calibrate the crossover
  // (tools/rt_stats_sweep.py --shape).
  int shape_override = 0;  // ACCL_INIT_CONST

  // BFM-style wire-fault injection (the reference test strategy drives
  // its DUT through a bus-functional model that can corrupt/delay
  // streams — SURVEY.md §4; tests/test_fault_injection.py):
  //   ACCL_RT_FAULT_DELAY_TAIL_MS=N  the FIRST multi-segment eager
  //     message sent delays its final segment by N ms (a slow tail: the
  //     consumer's recv dies mid-message and must orphan-drain);
  //   ACCL_RT_FAULT_DROP_TAIL=1      the FIRST multi-segment eager
  //     message loses its final segment outright (datagram-transport
  //     loss semantics: the seqn gap must surface as a clean timeout).
  // One-shot by design: the fault arms once per runtime.
  int fault_delay_tail_ms = 0;  // ACCL_INIT_CONST
  bool fault_drop_tail = false;  // ACCL_INIT_CONST
  //   ACCL_RT_FAULT_KILL_RANK=R       rank R wedges PERMANENTLY (not the
  //     one-shot tail levers above): after ACCL_RT_FAULT_KILL_AFTER=N
  //     completed data-plane calls (default 0 — the very next call dies)
  //     every in-flight and future call on the rank completes with a
  //     sticky RECEIVE_TIMEOUT retcode — recorded as a FINAL trace-ring
  //     span, so the host flight recorder fires on the death — and the
  //     wire goes dark in both directions: outbound frames are dropped
  //     before the transport, inbound frames are read off the socket
  //     (framing preserved for the peer's tx path) and discarded. Peers
  //     observe exactly what a dead host produces: silence, surfacing as
  //     their own recv deadlines. accl_rt_kill() is the programmatic
  //     form (the resilience tests kill a rank mid-stream).
  std::atomic<bool> killed{false};
  int kill_after_calls = -1;  // ACCL_ROLE_ONLY(seq); sequencer-thread only; -1 = unarmed

  void wedge() {
    killed.store(true, std::memory_order_release);
    // wake everything that could be parked so in-flight calls reach
    // the kill check (and die with their sticky span) promptly
    rx_event();
    call_cv.notify_all();
    rndzv_cv.notify_all();
  }
  // ACCL_RT_WAN_ALPHA_US / ACCL_RT_WAN_GBPS: WAN shaper for the socket
  // transports — every outbound frame pays alpha + bytes/beta on its
  // per-destination link (inside tx_mu, so frames to one peer
  // serialize like a real wire) before entering the kernel, turning
  // loopback sockets into an emulated slow cross-slice (DCN) tier.
  // Read at create, so one process can hold differently-shaped worlds:
  // an emulated 2-tier world is unshaped local-POE pods
  // (fast ICI tier) beside shaped TCP groups (slow DCN tier). The
  // local POE is never shaped — it IS the fast tier.
  uint32_t wan_alpha_us = 0;  // ACCL_INIT_CONST
  double wan_bytes_per_us = 0.0;  // ACCL_INIT_CONST

  void wan_charge(size_t payload_len) {
    if (!wan_alpha_us && wan_bytes_per_us <= 0) return;
    double us = (double)wan_alpha_us;
    if (wan_bytes_per_us > 0)
      us += (double)(sizeof(MsgHeader) + payload_len) / wan_bytes_per_us;
    if (us >= 1.0)
      std::this_thread::sleep_for(
          std::chrono::microseconds((long long)us));
  }
  std::atomic<bool> fault_armed{false};
  std::vector<std::thread> fault_threads;
  std::mutex fault_mu;

  // ----- reliability sublayer (ACCL_RT_RELY, default on) ------------------
  // CRC32C frame integrity + per-(peer, seqn) selective retransmit: the
  // delivery guarantees the reference offload engine owns below the
  // host (README.md:6 — the host never sees a lost segment), rebuilt at
  // this wire. Sender side: every MSG_EGR_DATA frame is serialized and
  // kept in a per-destination bounded retransmit buffer until the
  // peer's cumulative MSG_ACK releases it; a MSG_NACK resends the raw
  // frame bytes. Receiver side: a seek miss records the wanted (src,
  // seqn) and the health thread NACKs it with bounded exponential
  // backoff (short first delay when stray seqns prove a gap, a longer
  // one for a possibly-not-yet-sent head); repaired frames re-land
  // idempotently on the existing dedup path (late/duplicate seqns
  // drop). The budget is bounded on BOTH axes — nack attempts and
  // retransmit-buffer bytes — so an unrecoverable frame degrades to
  // the existing RECEIVE_TIMEOUT escalation, never an unbounded stall.
  // World-uniform: every rank of a world must run the same rely mode
  // (a rely-off sender's crc=0 frames fail a rely-on receiver's check).
  bool rely_on = true;  // ACCL_INIT_CONST
  // the EFFECTIVE wire flag: rely_on, except on the in-process local
  // POE with no fault model armed — that "wire" is a synchronous
  // function call that cannot lose or corrupt frames, so CRC + retx
  // retention there is pure overhead protecting against nothing (both
  // sides of a local world share the process env, so the mode is
  // world-uniform by construction)
  bool rely_wire = true;  // ACCL_INIT_CONST
  bool debug_on = false;  // ACCL_INIT_CONST; ACCL_RT_DEBUG, read once at create: wire
                          // drop/tx prints are gated on this AND counted
                          // in stats, so a chaos soak never spams stderr
  uint64_t retx_budget_bytes = 16ull << 20;  // ACCL_INIT_CONST; per dst, oldest evicted
  uint32_t nack_max = 24;                    // ACCL_INIT_CONST; per-seqn attempt budget
  // RetxFrame/RetxBuf/HeldFrame/WantState are the shared reliability
  // types (reliability.h); retention is BY REFERENCE — the FramePtr in
  // the retx buffer is the same serialized frame the wire shipped.
  std::vector<RetxBuf> retx;  // ACCL_GUARDED_BY(rely_mu); per (dst, lane) sid; rely_mu
  // retransmits requested by peers, drained by the HEALTH thread: the
  // rx thread must never perform a blocking data-frame send itself —
  // two peers simultaneously retransmitting jumbo frames to each other
  // from their rx loops would stop draining their sockets while
  // blocked in send_all and mutually wedge both links (a liveness
  // hazard the pre-rely rx thread never had). rely_mu.
  std::deque<FramePtr> retx_pending;  // ACCL_GUARDED_BY(rely_mu); dst + lane ride the header
  std::unordered_map<uint32_t, HeldFrame> reorder_held;  // ACCL_GUARDED_BY(rely_mu); by sid; rely_mu
  std::mutex rely_mu;
  std::thread rely_thread;
  // receiver-side per-src want/ack state (rx_mu, like the rx state it
  // describes). want = the head seqn a consumer is provably waiting on
  // (recorded at seek miss); acked_upto = the last cumulative ack sent.
  std::vector<WantState> want;  // ACCL_GUARDED_BY(rx_mu); per (src, lane) sid
  std::vector<uint32_t> acked_upto;  // ACCL_GUARDED_BY(rx_mu)
  std::vector<std::chrono::steady_clock::time_point> last_ack_t;  // ACCL_GUARDED_BY(rx_mu)

  // Seeded bus-functional fault model (generalizes the one-shot
  // DROP_TAIL/DELAY_TAIL levers; the reference drives its DUT through a
  // BFM that can corrupt/delay streams, SURVEY.md §4):
  //   ACCL_RT_FAULT_LOSS_PCT     frame vanishes before the transport
  //   ACCL_RT_FAULT_CORRUPT_PCT  one payload bit flips (zero-payload
  //                              frames flip a crc-field bit) AFTER the
  //                              CRC is computed — framing stays intact,
  //                              the receiver's check must catch it
  //   ACCL_RT_FAULT_DUP_PCT      frame delivered twice
  //   ACCL_RT_FAULT_REORDER_PCT  frame held and swapped with the next
  //                              frame to the same dst (health thread
  //                              releases a tail hold after ~2 ms)
  //   ACCL_RT_FAULT_SEED         deterministic per-rank PRNG seed
  // Applied to freshly-sent MSG_EGR_DATA frames only (control frames
  // and retransmits ride clean, so repair always converges); drawn from
  // a per-runtime splitmix64 stream, so a given (seed, rank, frame
  // order) chaos run is reproducible.
  double fault_loss_pct = 0, fault_corrupt_pct = 0;  // ACCL_INIT_CONST
  double fault_dup_pct = 0, fault_reorder_pct = 0;  // ACCL_INIT_CONST
  bool fault_pct_armed = false;  // ACCL_INIT_CONST
  uint64_t rng_state = 0;  // ACCL_GUARDED_BY(rng_mu)
  std::mutex rng_mu;
  double rng_u01() {  // splitmix64 -> [0, 1)
    std::lock_guard<std::mutex> g(rng_mu);
    rng_state += 0x9E3779B97F4A7C15ull;
    uint64_t z = rng_state;
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ull;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBull;
    z ^= z >> 31;
    return (double)(z >> 11) / (double)(1ull << 53);
  }

  // wire-health counters (the accl_rt_get_stats2 surface)
  std::atomic<uint64_t> stat_tx_frames{0}, stat_rx_frames{0},
      stat_crc_drops{0}, stat_dup_drops{0}, stat_retx_sent{0},
      stat_retx_miss{0}, stat_nack_sent{0}, stat_nack_rx{0},
      stat_ack_sent{0}, stat_ack_rx{0}, stat_rndzv_drops{0},
      stat_inj_loss{0}, stat_inj_corrupt{0}, stat_inj_dup{0},
      stat_inj_reorder{0}, stat_rely_ns{0};
  // A delayed tail still in flight to fault_tail_dst: new egr traffic to
  // that dst before it lands would break wire order (the lever's one
  // precondition) — detected race-free at the SENDER, which owns
  // outbound_seq, instead of peeking the counter from the delay thread.
  std::atomic<bool> fault_tail_pending{false};
  std::atomic<uint32_t> fault_tail_dst{0};

  // intra-process POE (registry + pinning live in the LocalPoe)
  bool local_mode = false;  // ACCL_INIT_CONST

  // Generation counter of rx-side progress events (eager landings,
  // rendezvous addresses/completions): the sequencer snapshots it before
  // an execute pass and parks a NOT_READY call ONLY if no event arrived
  // since — otherwise an event landing in the gap between the failing
  // poll and the park would cost the full park timeout (a missed-wakeup
  // race the 200 us cap used to paper over, one whole cap per chunk).
  std::atomic<uint64_t> rx_events{0};
  void rx_event() {
    rx_events.fetch_add(1, std::memory_order_release);
    rx_cv.notify_all();
  }

  // ----- exchmem -----
  uint32_t rd(uint32_t addr) {
    std::lock_guard<std::mutex> g(exch_mu);
    uint32_t v;
    std::memcpy(&v, exchmem.data() + addr, 4);
    return v;
  }
  void wr(uint32_t addr, uint32_t v) {
    std::lock_guard<std::mutex> g(exch_mu);
    std::memcpy(exchmem.data() + addr, &v, 4);
  }
  uint32_t tuning(uint32_t addr, uint32_t dflt) {
    uint32_t v = rd(addr);
    return v ? v : dflt;
  }

  // Parse the communicator table at comm_addr out of exchange memory
  // (layout: size, local_rank, then per rank 7 words of which word 6 is
  // the device index == global transport rank; communicator.py
  // exchmem_words). comm_addr 0 means the full transport world.
  // Membership is derived from the device-index column so each rank's
  // exchmem copy needs no rank-specific local_rank word.
  //
  // Wire-format note: like the reference 64 B header (eth_intf.h:94-151),
  // eager frames carry (src, tag, seqn) but no communicator id, so
  // OVERLAPPING communicators must use distinct tags for concurrent
  // traffic on a shared link — the same discipline the reference
  // firmware's rxbuf seek (tag, src, seqn) matching requires. Disjoint
  // groups never share links and need no care.
  bool resolve_comm(uint32_t comm_addr, CommView &cm) {
    cm.map.clear();
    if (comm_addr == 0) {
      cm.world = world;
      cm.rank = rank;
      return true;
    }
    if (comm_addr % 4 != 0 || (uint64_t)comm_addr + 4 > EXCHMEM_BYTES)
      return false;
    uint32_t size = rd(comm_addr);
    if (size == 0 || size > world) return false;
    if ((uint64_t)comm_addr + 4ull * (2 + 7ull * size) > EXCHMEM_BYTES)
      return false;
    cm.map.resize(size);
    cm.rank = UINT32_MAX;
    bool ident = (size == world);
    uint64_t seen = 0;  // duplicate-member bitmap (world <= 64 in practice;
                        // larger worlds fall back to the O(n^2) scan)
    for (uint32_t i = 0; i < size; i++) {
      uint32_t dev = rd(comm_addr + 4 * (2 + 7 * i + 6));
      if (dev >= world) return false;
      if (dev < 64) {
        if (seen & (1ull << dev)) return false;  // duplicate member
        seen |= 1ull << dev;
      } else {
        for (uint32_t j = 0; j < i; j++)
          if (cm.map[j] == dev) return false;
      }
      cm.map[i] = dev;
      if (dev == rank) cm.rank = i;
      if (dev != i) ident = false;
    }
    if (cm.rank == UINT32_MAX) return false;  // caller not a member
    cm.world = size;
    if (ident) cm.map.clear();
    return true;
  }

  // ----- transport -----

  // Local-POE ingress: the SENDER's thread runs this against the
  // receiving runtime (no rx threads exist in local mode). The caller
  // holds none of ITS OWN locks (every frame_out site releases first),
  // so taking this runtime's rx/rndzv locks cannot deadlock.
  // ----- PoeSink: inbound frames from the transport seam ------------------

  // One inbound frame. Mem-backed bodies (datagram / in-process POEs)
  // arrive whole; stream bodies (TCP) expose the link so payloads land
  // directly at their destination.
  bool on_frame(uint32_t lane, const MsgHeader &h,
                acclw::PayloadSource &body) override {
    if (body.data()) return on_frame_mem(lane, h, body.data(), body.remaining());
    return on_frame_stream(lane, h, body);
  }

  // Memory-resident frame (the whole payload arrived with the header):
  // the merged landing path of the in-process and datagram POEs. The
  // stream POE never produces mem-backed bodies (on_frame dispatches on
  // body.data()), so tcp rx roles cannot enter.  // ACCL_POE(udp,local)
  bool on_frame_mem(uint32_t lane, const MsgHeader &h, const uint8_t *payload,
                    size_t plen) {
    if (stop.load()) return false;
    uint32_t s = sid(h.src, lane);
    // rx volume counts PRE-CRC on every transport (the acclrt.h
    // contract: a lossy link shows frames ARRIVING, damaged or not)
    if (h.msg_type == MSG_EGR_DATA) stat_rx_frames++;
    // dead host semantics for the in-process POE: frames into a wedged
    // rank are swallowed (never landed, never blocking the sender)
    if (local_mode && killed.load(std::memory_order_relaxed)) return true;
    if (rely_wire) {
      auto t0 = std::chrono::steady_clock::now();
      bool okc = h.crc == frame_crc(h, payload, plen);
      stat_rely_ns += (uint64_t)std::chrono::duration_cast<
          std::chrono::nanoseconds>(std::chrono::steady_clock::now() - t0)
          .count();
      if (!okc) {
        // corrupt frame: counted and DROPPED before any state is
        // touched — never landed. An eager drop leaves a seqn gap the
        // nack path repairs like a loss.
        stat_crc_drops++;
        if (h.msg_type == MSG_EGR_DATA &&
            !killed.load(std::memory_order_relaxed)) {
          std::lock_guard<std::mutex> g(rx_mu);
          note_want_locked(s, /*proven=*/true);
        }
        return true;
      }
    }
    switch (h.msg_type) {
      case MSG_HELLO:
        // datagram bring-up solicit (hello traffic has no meaning
        // in-process — the local POE's registry IS its bring-up)
        if (udp_mode) frame_out(h.src, MSG_HELLO_ACK, 0, 0, 0, 0, nullptr, 0);
        [[fallthrough]];
      case MSG_HELLO_ACK:
        if (udp_mode) {
          std::lock_guard<std::mutex> g(hello_mu);
          hello_seen[h.src] = true;
          hello_cv.notify_all();
        }
        return true;
      case MSG_ACK:
        if (!killed.load(std::memory_order_relaxed))
          handle_ack(h.src, lane, h.seqn);
        return true;
      case MSG_NACK:
        if (!killed.load(std::memory_order_relaxed))
          handle_nack(h.src, lane, h.seqn);
        return true;
      case MSG_EGR_DATA: {
        if (killed.load(std::memory_order_relaxed)) return true;  // dead host
        {
          // direct landing (zero-copy for the consumer): same
          // eligibility as the stream POE's rx path, but the copy
          // happens right here under rx_mu — in-process memcpy, no
          // staging. (Landings register only on ordered links, so the
          // datagram POE never matches one.)
          std::lock_guard<std::mutex> lk(rx_mu);
          auto lnd = eager_landings.find(s);
          if (lnd != eager_landings.end() && !lnd->second.in_use &&
              !lnd->second.abort && h.seqn == inbound_seq[s] &&
              src_valid_count[s] == 0 && !rx_drain_srcs.count(s) &&
              (lnd->second.tag == TAG_ANY || h.tag == TAG_ANY ||
               lnd->second.tag == h.tag) &&
              h.msg_bytes == lnd->second.want &&
              h.msg_off == lnd->second.landed &&
              h.bytes <= lnd->second.want - lnd->second.landed) {
            if (plen)
              std::memcpy(lnd->second.base + lnd->second.landed, payload,
                          plen);
            lnd->second.landed += plen;
            inbound_seq[s] = h.seqn + 1;
            rx_event();
            return true;
          }
        }
        std::vector<uint8_t> copy(payload, payload + plen);
        if (!land_eager(h, lane, std::move(copy), /*allow_grow=*/true))
          return false;
        return true;
      }
      case MSG_RNDZV_ADDR: {
        if (udp_mode) break;  // rendezvous not offered on the datagram POE
        {
          std::lock_guard<std::mutex> g(rndzv_mu);
          addr_q.push_back({h.src, h.vaddr, h.bytes, h.tag,
                            wire_host(h.host)});
          rndzv_cv.notify_all();
        }
        rx_event();
        return true;
      }
      case MSG_RNDZV_WRITE: {
        if (udp_mode) break;
        // validate + land + complete in one critical section (the
        // staged stream path's semantics; in-process the copy IS direct)
        bool posted = false;
        {
          std::lock_guard<std::mutex> g(rndzv_mu);
          for (auto it = posted_addrs.begin(); it != posted_addrs.end();
               ++it) {
            if (it->vaddr == h.vaddr && it->src == h.src &&
                it->bytes == h.bytes && !it->in_use && !it->abort) {
              if (plen)
                std::memcpy((void *)(uintptr_t)h.vaddr, payload, plen);
              posted_addrs.erase(it);
              done_q.push_back({h.src, h.vaddr, h.bytes, h.tag});
              rndzv_cv.notify_all();
              posted = true;
              break;
            }
          }
        }
        if (posted) rx_event();
        // unposted/revoked: dropped (late-write semantics), counted
        if (!posted) stat_rndzv_drops++;
        return true;
      }
      default:
        return true;
    }
    // rendezvous message on the sessionless POE: one-sided writes need
    // a session transport (reference: RDMA-only message types) — drop
    if (debug_on)
      fprintf(stderr, "[r%u] drop mt=%u on datagram transport\n", rank,
              h.msg_type);
    return true;
  }

  // Raw-frame emit: POE delivery of ONE serialized frame (header +
  // payload contiguous, CRC already set; dst and lane ride the header).
  // The retransmit path, the reorder-hold release, and the duplicate
  // injection all ride this, so a resent frame is byte-identical to
  // the original.
  bool wire_emit(const FrameBuf &f) {
    FrameView v = frame_view(f);
    return poe_send(v.h.dst, wire_lane(v.h), &v, 1);
  }

  // Every outbound frame funnels here. Debug-build invariant of the
  // vectored wire (the no-double-copy contract): the transport ships
  // borrowed scatter-gather views — payload_copies() counts
  // transport-side coalescing and stays zero except under the
  // ACCL_RT_WIRE_LEGACY cost model.
  bool poe_send(uint32_t dst, uint32_t lane, const FrameView *fv, size_t n) {
    if (stop.load()) return false;
    bool ok = poe->send_frames(dst, lane, fv, n);
    assert(legacy_wire || poe->payload_copies() == 0);
    return ok;
  }

  // Cumulative ack from a peer: everything below `upto` landed there —
  // release the retained frames of that (peer, lane) stream.
  void handle_ack(uint32_t src, uint32_t lane, uint32_t upto) {
    stat_ack_rx++;
    std::lock_guard<std::mutex> g(rely_mu);
    uint32_t s = sid(src, lane);
    if (s >= retx.size()) return;
    RetxBuf &rb = retx[s];
    while (!rb.q.empty() && (int32_t)(rb.q.front().seqn - upto) < 0) {
      rb.bytes -= rb.q.front().bytes->size();
      rb.q.pop_front();
    }
  }

  // Selective-retransmit request: queue the retained frame for the
  // HEALTH thread to resend verbatim (never a blocking data-frame send
  // on the rx thread that received the nack — see retx_pending). A seqn
  // already evicted from the bounded buffer is unrecoverable at this
  // layer (counted; the receiver's deadline owns it); a seqn the sender
  // has not produced yet is a benign receiver probe (a parked recv
  // nacking a head the sender is still computing) and is ignored.
  void handle_nack(uint32_t src, uint32_t lane, uint32_t seqn) {
    stat_nack_rx++;
    if (killed.load(std::memory_order_relaxed)) return;
    FramePtr f;
    bool evicted = false;
    {
      std::lock_guard<std::mutex> g(rely_mu);
      uint32_t s = sid(src, lane);
      if (s >= retx.size()) return;
      RetxBuf &rb = retx[s];
      for (auto &rf : rb.q)
        if (rf.seqn == seqn) {
          f = rf.bytes;
          break;
        }
      if (!f && !rb.q.empty() && (int32_t)(seqn - rb.q.front().seqn) < 0)
        evicted = true;
      if (f) {
        // dedup: a re-nack arriving before the pending resend went out
        // must not queue the same frame twice
        for (auto &p : retx_pending)
          if (p == f) {
            f = nullptr;
            break;
          }
        if (f) retx_pending.push_back(f);
      }
    }
    if (evicted) {
      stat_retx_miss++;
      if (debug_on)
        fprintf(stderr, "[r%u] NACK miss peer=%u seqn=%u (evicted)\n",
                rank, src, seqn);
    }
  }

  // Record that a consumer is provably waiting on (stream sid, inbound
  // head): the health thread turns a persistent want into
  // bounded-backoff NACKs. `proven` (a CRC drop, or stray seqns queued
  // behind the gap) nacks after ~1 ms; a bare miss may just be a
  // not-yet-sent head (or a frame mid-flight behind a scheduler stall)
  // and waits ~8 ms first — the sender ignores a nack for a seqn it
  // has not produced, but a nack for one already in flight costs a
  // spurious retransmit+dup, so the bare-miss delay is deliberately
  // above ordinary host jitter. rx_mu held by the caller.
  // ACCL_REQUIRES(rx_mu)
  void note_want_locked(uint32_t s, bool proven = false) {
    if (!rely_wire || s >= want.size()) return;
    WantState &w = want[s];
    uint32_t sq = inbound_seq[s];
    if (w.active && w.seqn == sq) return;
    w.active = true;
    w.seqn = sq;
    w.attempts = 0;
    bool fast = proven || src_valid_count[s] > 0;
    w.next_nack = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(fast ? 1 : 8);
  }

  // Reliability health thread (1 ms tick): sends the pending cumulative
  // acks and bounded-backoff nacks the rx state asks for, and releases
  // reorder-held tail frames. All sends happen with no rx/rely lock
  // held. A wedged rank's health thread goes silent with the rest of
  // its wire.
  void rely_loop() {
    while (!stop.load()) {
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
      if (stop.load()) return;
      if (killed.load(std::memory_order_relaxed)) continue;
      // NOTE: the tick's own scan is NOT charged to rely_ns — it runs
      // on this background thread, off every dispatch's critical path;
      // the control frames it emits still pay their timed CRC in
      // frame_out, which is the cost the chaos gate budgets.
      auto t0 = std::chrono::steady_clock::now();
      struct Ctl {
        uint32_t s;  // stream sid — dst = s / n_lanes, lane = s % n_lanes
        MsgType mt;
        uint32_t seqn;
      };
      std::vector<Ctl> ctl;
      {
        std::lock_guard<std::mutex> g(rx_mu);
        for (uint32_t s = 0; s < world * n_lanes; s++) {
          if (s / n_lanes == rank) continue;
          WantState &w = want[s];
          if (w.active && inbound_seq[s] != w.seqn)
            w.active = false;  // repaired (or advanced past)
          if (w.active && t0 >= w.next_nack) {
            if (w.attempts >= nack_max) {
              // nack budget exhausted: the frame is unrecoverable at
              // this layer — deactivate and let the call deadline
              // surface it (a later seek miss re-arms a fresh cycle,
              // so the chatter stays bounded by the backoff sum)
              w.active = false;
            } else {
              ctl.push_back({s, MSG_NACK, w.seqn});
              w.attempts++;
              uint64_t ms = std::min<uint64_t>(
                  1ull << std::min(w.attempts, 6u), 50);
              w.next_nack = t0 + std::chrono::milliseconds(ms);
            }
          }
          uint32_t in = inbound_seq[s];
          if (in != acked_upto[s] &&
              (in - acked_upto[s] >= 32 ||
               t0 - last_ack_t[s] >= std::chrono::milliseconds(5))) {
            ctl.push_back({s, MSG_ACK, in});
            acked_upto[s] = in;
            last_ack_t[s] = t0;
          }
        }
      }
      // release reorder holds older than ~2 ms (a held TAIL frame has
      // no follower to swap with — the nack path would recover it, but
      // releasing here keeps the common case one round trip cheaper)
      // and drain the peers' queued retransmit requests
      std::vector<FramePtr> rel;
      {
        std::lock_guard<std::mutex> g(rely_mu);
        for (auto it = reorder_held.begin(); it != reorder_held.end();) {
          if (t0 - it->second.since >= std::chrono::milliseconds(2)) {
            rel.push_back(it->second.bytes);
            it = reorder_held.erase(it);
          } else {
            ++it;
          }
        }
        while (!retx_pending.empty()) {
          rel.push_back(retx_pending.front());
          retx_pending.pop_front();
          stat_retx_sent++;
        }
      }
      for (auto &c : ctl)
        frame_out(c.s / n_lanes, c.mt, 0, c.seqn, 0, 0, nullptr, 0,
                  /*host=*/0, /*msg_bytes=*/0, /*msg_off=*/0,
                  /*lane=*/c.s % n_lanes);
      for (auto &r : rel) wire_emit(*r);
    }
  }

  // A sender-side frame batch to one (dst, lane): views accumulate and
  // flush as ONE scatter-gather send_frames call — many small frames
  // per writev/sendmmsg, the syscall-floor cut for the tiny-message
  // regime. `keep` pins serialized rely frames until the flush ships
  // them (retx-budget eviction must not free a frame the batch still
  // references); non-rely views borrow the caller's payload, which
  // egr_send keeps alive through its final flush.
  struct TxBatch {
    uint32_t dst = 0, lane = 0;
    std::vector<FrameView> views;
    std::vector<FramePtr> keep;
    size_t bytes = 0;
  };
  static constexpr size_t TX_BATCH_FRAMES = 256;     // one writev's worth
  static constexpr size_t TX_BATCH_BYTES = 4u << 20;
  bool flush_batch(TxBatch &b) {
    if (b.views.empty()) return true;
    bool ok = poe_send(b.dst, b.lane, b.views.data(), b.views.size());
    b.views.clear();
    b.keep.clear();
    b.bytes = 0;
    return ok;
  }

  bool frame_out(uint32_t dst, MsgType mt, uint32_t tag, uint32_t seqn,
                 uint64_t bytes, uint64_t vaddr, const void *payload,
                 size_t payload_len, uint32_t host = 0,
                 uint64_t msg_bytes = 0, uint64_t msg_off = 0,
                 uint32_t lane = 0, TxBatch *batch = nullptr) {
    // a wedged rank's wire is dark: outbound frames vanish before the
    // transport (bring-up hellos stay exempt so a pre-armed kill can
    // never wedge a PEER's creation barrier)
    if (killed.load(std::memory_order_relaxed) && mt != MSG_HELLO &&
        mt != MSG_HELLO_ACK)
      return true;
    MsgHeader h{};
    h.magic = MSG_MAGIC;
    h.msg_type = mt;
    h.src = rank;
    h.dst = dst;
    h.tag = tag;
    h.seqn = seqn;
    h.host = wire_pack_host(host, lane);
    h.bytes = bytes;
    h.vaddr = vaddr;
    h.msg_bytes = msg_bytes;
    h.msg_off = msg_off;
    if (rely_wire) {
      auto t0 = std::chrono::steady_clock::now();
      h.crc = frame_crc(h, payload, payload_len);
      stat_rely_ns += (uint64_t)std::chrono::duration_cast<
          std::chrono::nanoseconds>(std::chrono::steady_clock::now() - t0)
          .count();
      if (mt == MSG_ACK) stat_ack_sent++;
      if (mt == MSG_NACK) stat_nack_sent++;
    }
    if (mt == MSG_EGR_DATA) stat_tx_frames++;
    if (rely_wire && mt == MSG_EGR_DATA) {
      // serialize once: the same bytes feed the retransmit buffer, the
      // TX batch, and the wire, so a NACK replays the frame verbatim —
      // retention is BY REFERENCE, never a second payload copy
      auto f = std::make_shared<FrameBuf>(sizeof h + payload_len);
      std::memcpy(f->data(), &h, sizeof h);
      if (payload_len)
        std::memcpy(f->data() + sizeof h, payload, payload_len);
      {
        std::lock_guard<std::mutex> g(rely_mu);
        RetxBuf &rb = retx[sid(dst, lane)];
        rb.q.push_back({seqn, f});
        rb.bytes += f->size();
        while (rb.bytes > retx_budget_bytes && rb.q.size() > 1) {
          rb.bytes -= rb.q.front().bytes->size();
          rb.q.pop_front();  // a nack for it will count retx_miss
        }
      }
      if (batch && tx_batch_on) {
        batch->views.push_back(frame_view(*f));
        batch->keep.push_back(f);
        batch->bytes += f->size();
        if (batch->views.size() >= TX_BATCH_FRAMES ||
            batch->bytes >= TX_BATCH_BYTES)
          return flush_batch(*batch);
        return true;
      }
      FramePtr wire = f;
      bool dup = false, hold = false;
      if (fault_pct_armed) {
        if (rng_u01() * 100.0 < fault_loss_pct) {
          stat_inj_loss++;
          return true;  // vanished on the wire (retx buffer keeps it)
        }
        if (rng_u01() * 100.0 < fault_corrupt_pct) {
          // flip one bit AFTER the CRC was computed, in a copy so the
          // retransmit buffer keeps the clean bytes. Payload bits when
          // there are any; the crc field itself on header-only frames —
          // framing fields stay intact either way, so the stream
          // survives and only the integrity check can catch it.
          auto bad = std::make_shared<FrameBuf>(*f);
          size_t off = payload_len
                           ? sizeof h + (size_t)(rng_u01() * payload_len)
                           : offsetof(MsgHeader, crc);
          if (off >= bad->size()) off = bad->size() - 1;
          (*bad)[off] ^= (uint8_t)(1u << (int)(rng_u01() * 8));
          wire = bad;
          stat_inj_corrupt++;
        }
        dup = rng_u01() * 100.0 < fault_dup_pct;
        hold = rng_u01() * 100.0 < fault_reorder_pct;
      }
      // REORDER: emit any previously-held frame AFTER this one (the
      // swap), or hold this one for the next frame to the same
      // (dst, lane) stream
      FramePtr released;
      {
        std::lock_guard<std::mutex> g(rely_mu);
        auto it = reorder_held.find(sid(dst, lane));
        if (it != reorder_held.end()) {
          released = it->second.bytes;
          reorder_held.erase(it);
        } else if (hold) {
          reorder_held[sid(dst, lane)] =
              HeldFrame{wire, std::chrono::steady_clock::now()};
          stat_inj_reorder++;
          wire = nullptr;
        }
      }
      bool ok = true;
      if (wire) {
        ok = wire_emit(*wire);
        if (ok && dup) {
          stat_inj_dup++;
          ok = wire_emit(*wire);
        }
      }
      if (released && ok) ok = wire_emit(*released);
      return ok;
    }
    if (batch && tx_batch_on && mt == MSG_EGR_DATA) {
      FrameView v;
      v.h = h;
      v.payload = (const uint8_t *)payload;
      v.payload_len = payload_len;
      batch->views.push_back(v);
      batch->bytes += sizeof h + payload_len;
      if (batch->views.size() >= TX_BATCH_FRAMES ||
          batch->bytes >= TX_BATCH_BYTES)
        return flush_batch(*batch);
      return true;
    }
    FrameView v;
    v.h = h;
    v.payload = (const uint8_t *)payload;
    v.payload_len = payload_len;
    return poe_send(dst, lane, &v, 1);
  }

  // depacketizer -> rxbuf enqueue/dequeue: land a segment in an IDLE slot
  // and publish the notification. Returns false on shutdown.
  //
  // allow_grow (datagram transport): the single rx thread must NEVER
  // block — a full ring would overflow the kernel socket buffer (silent
  // datagram loss surfacing as timeouts) and would starve bring-up
  // hello processing. The ring grows on demand up to a generous bound,
  // past which the blocking backpressure applies as a last resort.
  bool land_eager(const MsgHeader &h, uint32_t lane,
                  std::vector<uint8_t> payload, bool allow_grow = false) {
    uint32_t s = sid(h.src, lane);
    std::unique_lock<std::mutex> lk(rx_mu);
    size_t idx;
    if (!idle_q.empty()) {
      idx = idle_q.back();
      idle_q.pop_back();
    } else if (allow_grow && rx_slots.size() < (1u << 20)) {
      rx_slots.emplace_back();
      idx = rx_slots.size() - 1;
    } else {
      // last-resort backpressure past 2^20 slots: park the rx thread
      // until the sequencer frees a slot; stop wakes it, so teardown
      // cannot wedge (the alternative is dropping frames).
      // ACCL_ALLOW(ACCLN101: rx backpressure park past the 2^20-slot ring cap; woken by stop)
      rx_cv.wait(lk, [&] { return stop.load() || !idle_q.empty(); });
      if (stop.load()) return false;
      idx = idle_q.back();
      idle_q.pop_back();
    }
    if ((int32_t)(h.seqn - inbound_seq[s]) < 0) {
      // seqn already consumed: a LATE duplicate (datagram dup, or a
      // retransmit that crossed its own repair). Landing it would
      // leave a VALID slot no seek ever requests (leaked slot,
      // compaction disabled forever) — drop it, idempotently, and
      // COUNT it (the chaos soak reads the counter; stderr prints are
      // debug-gated so injected-dup storms never spam the console).
      stat_dup_drops++;
      if (debug_on)
        fprintf(stderr, "[r%u] land DROP late src=%u seqn=%u want=%u\n", rank,
                h.src, h.seqn, inbound_seq[s]);
      idle_q.push_back(idx);
      return true;
    }
    if (!rx_index.emplace(rx_key(s, h.seqn), idx).second) {
      // duplicate (sid, seqn): idempotent drop (a datagram duplicate,
      // an injected dup, or a retransmit racing the original) — the
      // first arrival wins
      stat_dup_drops++;
      idle_q.push_back(idx);
      return true;
    }
    RxSlot &slot = rx_slots[idx];
    slot.status = RxSlot::VALID;
    slot.src = h.src;
    slot.tag = h.tag;
    slot.seqn = h.seqn;
    slot.lane = lane;
    slot.msg_bytes = h.msg_bytes;
    slot.msg_off = h.msg_off;
    slot.t_land = std::chrono::steady_clock::now();
    slot.data = std::move(payload);
    src_valid_count[s]++;
    rx_event();
    return true;
  }

  // Poll-bounded pinned read shared by BOTH zero-copy landing paths
  // (eager landings and rendezvous one-sided writes): read `plen` bytes
  // from the stream body into `dest`, consulting `still_pinned()`
  // between 100 ms slices — when it reports the pin is gone
  // (revocation), the remainder diverts to scratch (the byte stream
  // must stay framed) and `ack_divert()` runs exactly once to release
  // the buffer and wake the bounded-waiting revoker. Returns false on
  // link death / stop; `*diverted_out` reports whether the payload was
  // consumed-to-void.
  bool pinned_read(PayloadSource &body, uint8_t *dest, size_t plen,
                   const std::function<bool()> &still_pinned,
                   const std::function<void()> &ack_divert,
                   bool *diverted_out) {
    std::vector<uint8_t> scratch;
    bool diverted = false;
    size_t off = 0;
    while (off < plen && !stop.load()) {
      int pr = body.poll_in(100);
      if (!diverted && !still_pinned()) {
        scratch.resize(plen);
        diverted = true;
        ack_divert();
      }
      if (pr <= 0) continue;
      uint8_t *tgt = diverted ? scratch.data() : dest;
      ssize_t r = body.read_avail(tgt + off, plen - off);
      if (r <= 0) {
        *diverted_out = diverted;
        return false;
      }
      off += (size_t)r;
    }
    *diverted_out = diverted;
    return off >= plen;
  }

  // One inbound frame from an ordered stream-POE link. The transport
  // already validated magic, src (the link's peer), and lane (the
  // link's lane); payload bytes are still ON THE WIRE behind `body`, so
  // the zero-copy landings read them straight into their destination.
  // Returning false drops the link (the transport's rx loop exits).
  bool on_frame_stream(uint32_t lane, const MsgHeader &h,
                       PayloadSource &body) {
    thread_local std::vector<uint8_t> payload;
    uint32_t s = sid(h.src, lane);
    // reliability control frames: header-only, verified and handled
    // inline (they never enter the seqn stream or the rx ring)
    if (h.msg_type == MSG_ACK || h.msg_type == MSG_NACK) {
      if (rely_wire && h.crc != frame_crc(h, nullptr, 0)) {
        stat_crc_drops++;
        return true;  // acks are cumulative, nacks retried: both survive
      }
      if (killed.load(std::memory_order_relaxed)) return true;
      if (h.msg_type == MSG_ACK)
        handle_ack(h.src, lane, h.seqn);
      else
        handle_nack(h.src, lane, h.seqn);
      return true;
    }
    if (h.msg_type == MSG_EGR_DATA) stat_rx_frames++;
    size_t plen = body.remaining();
    if (killed.load(std::memory_order_relaxed)) {
      // wedged rank: payload bytes are read off the link (the peer's
      // tx framing must not block on a dead consumer) and discarded —
      // nothing lands, nothing completes
      payload.resize(plen);
      if (plen && !body.read_exact(payload.data(), plen)) return false;
      return true;
    }
    // Direct placement: a registered landing whose message this
    // segment continues takes the payload straight off the wire
    // into the final buffer — no slot, no staging copy. Eligible only
    // when this segment is the next seqn with nothing queued before
    // it (the ordered link makes that exact). `in_use` pins the
    // destination across the unlocked read; revocation waits on it.
    if (h.msg_type == MSG_EGR_DATA && plen) {
      uint8_t *dest = nullptr;
      std::unique_lock<std::mutex> lk(rx_mu);
      auto lnd = eager_landings.find(s);
      if (lnd != eager_landings.end() && !lnd->second.in_use &&
          !lnd->second.abort &&
          h.seqn == inbound_seq[s] && src_valid_count[s] == 0 &&
          !rx_drain_srcs.count(s) &&
          (lnd->second.tag == TAG_ANY || h.tag == TAG_ANY ||
           lnd->second.tag == h.tag) &&
          h.msg_bytes == lnd->second.want &&
          h.msg_off == lnd->second.landed &&
          h.bytes <= lnd->second.want - lnd->second.landed) {
        lnd->second.in_use = true;
        dest = lnd->second.base + lnd->second.landed;
      }
      if (dest) {
        lk.unlock();
        bool diverted = false;
        bool ok = pinned_read(
            body, dest, plen,
            [&] {
              std::lock_guard<std::mutex> g(rx_mu);
              auto it2 = eager_landings.find(s);
              return it2 != eager_landings.end() && !it2->second.abort;
            },
            [&] {
              std::lock_guard<std::mutex> g(rx_mu);
              auto it2 = eager_landings.find(s);
              if (it2 != eager_landings.end()) it2->second.in_use = false;
              rx_cv.notify_all();
            },
            &diverted);
        // integrity check BEFORE the landing is published: the frame
        // was read straight into the consumer's buffer (in_use still
        // pins it), so a corrupt frame must not advance `landed` or
        // the inbound seqn — the bytes sit unobservable until the
        // retransmitted clean frame overwrites them, and the recv can
        // only ever complete with verified data ("never landed").
        bool crc_ok = true;
        if (ok && !diverted && rely_wire) {
          auto t0 = std::chrono::steady_clock::now();
          crc_ok = h.crc == frame_crc(h, dest, plen);
          stat_rely_ns += (uint64_t)std::chrono::duration_cast<
              std::chrono::nanoseconds>(std::chrono::steady_clock::now() -
                                        t0)
              .count();
        }
        lk.lock();
        lnd = eager_landings.find(s);  // may have been erased
        if (!diverted && lnd != eager_landings.end())
          lnd->second.in_use = false;
        if (!ok || stop.load()) {
          rx_cv.notify_all();
          return false;
        }
        if (!crc_ok) {
          stat_crc_drops++;
          note_want_locked(s, /*proven=*/true);
          rx_cv.notify_all();
          return true;
        }
        if (!diverted && lnd != eager_landings.end()) {
          lnd->second.landed += plen;
        } else if (diverted && h.msg_off + plen < h.msg_bytes) {
          // consumed-to-void mid-message: the rest of the dying
          // message is orphan tail whatever the revoker saw (it may
          // have observed landed == 0 and skipped arming)
          rx_drain_srcs.insert(s);
        }
        inbound_seq[s] = h.seqn + 1;
        rx_event();
        return true;
      }
    }
    // One-sided writes land DIRECTLY at the posted vaddr — the
    // zero-copy semantics the rendezvous protocol promises (the old
    // path staged through `payload` then memcpy'd). Same poll-bounded
    // pin/abort protocol as the eager landings: in_use pins the
    // target, revocation flips abort and the read diverts to scratch
    // within one 100 ms slice, so a timed-out caller's buffer is
    // never written after revocation returns.
    if (h.msg_type == MSG_RNDZV_WRITE && plen) {
      uint8_t *dest = nullptr;
      {
        std::lock_guard<std::mutex> g(rndzv_mu);
        for (auto &pa : posted_addrs) {
          if (pa.vaddr == h.vaddr && pa.src == h.src &&
              pa.bytes == h.bytes && !pa.in_use && !pa.abort) {
            pa.in_use = true;
            dest = (uint8_t *)(uintptr_t)h.vaddr;
            break;
          }
        }
      }
      if (dest) {
        // only ever invoked under rndzv_mu (pin-check / unpin /
        // completion scopes below)  // ACCL_REQUIRES(rndzv_mu)
        auto find_mine = [&]() -> RndzvAddr * {
          for (auto &pa : posted_addrs)
            if (pa.vaddr == h.vaddr && pa.src == h.src &&
                pa.bytes == h.bytes && pa.in_use)
              return &pa;
          return nullptr;
        };
        bool diverted = false;
        bool ok = pinned_read(
            body, dest, plen,
            [&] {
              std::lock_guard<std::mutex> g(rndzv_mu);
              RndzvAddr *pa = find_mine();
              return pa != nullptr && !pa->abort;
            },
            [&] {
              std::lock_guard<std::mutex> g(rndzv_mu);
              RndzvAddr *pa = find_mine();
              if (pa) pa->in_use = false;
              rndzv_cv.notify_all();
            },
            &diverted);
        // integrity check before the completion is published: a
        // corrupt one-sided write must not complete the recv (the
        // posting stays live, so a clean re-post/retry can still
        // land; rendezvous rides the session transport, so this is
        // the wire-corruption backstop, not a retransmit seam)
        bool crc_ok = true;
        if (ok && !diverted && rely_wire) {
          auto t0 = std::chrono::steady_clock::now();
          crc_ok = h.crc == frame_crc(h, dest, plen);
          stat_rely_ns += (uint64_t)std::chrono::duration_cast<
              std::chrono::nanoseconds>(std::chrono::steady_clock::now() -
                                        t0)
              .count();
          if (!crc_ok) stat_crc_drops++;
        }
        {
          std::lock_guard<std::mutex> g(rndzv_mu);
          RndzvAddr *pa = find_mine();
          if (pa) pa->in_use = false;
          if (!ok || stop.load()) {
            rndzv_cv.notify_all();
          } else if (!diverted && crc_ok && pa) {
            // completed write: consume the posting, publish completion
            for (auto it = posted_addrs.begin(); it != posted_addrs.end();
                 ++it) {
              if (&*it == pa) {
                posted_addrs.erase(it);
                break;
              }
            }
            done_q.push_back({h.src, h.vaddr, h.bytes, h.tag});
            rndzv_cv.notify_all();
          }
          // diverted: revoked mid-write — consumed-to-void, no
          // completion (the reference's late-write drop semantics)
        }
        if (!ok || stop.load()) return false;
        rx_event();
        return true;
      }
    }
    payload.resize(plen);
    if (plen && !body.read_exact(payload.data(), plen)) return false;
    if (rely_wire) {
      auto t0 = std::chrono::steady_clock::now();
      bool okc = h.crc == frame_crc(h, payload.data(), plen);
      stat_rely_ns += (uint64_t)std::chrono::duration_cast<
          std::chrono::nanoseconds>(std::chrono::steady_clock::now() -
                                    t0)
          .count();
      if (!okc) {
        // counted and dropped, never landed; an eager gap arms the
        // nack repair path
        stat_crc_drops++;
        if (h.msg_type == MSG_EGR_DATA) {
          std::lock_guard<std::mutex> g(rx_mu);
          note_want_locked(s, /*proven=*/true);
        }
        return true;
      }
    }
    switch (h.msg_type) {
      case MSG_EGR_DATA: {
        // allow_grow on the session transport too: the ring collectives
        // stream whole chunks as multi-segment messages, and a blocked
        // rx thread (ring full, sequencer mid-send) would stall the
        // socket into a ring-wide write deadlock. Growth is burst
        // absorption — the ring compacts once drained.
        if (!land_eager(h, lane, std::move(payload), /*allow_grow=*/true))
          return false;
        break;
      }
      case MSG_RNDZV_ADDR: {
        {
          std::lock_guard<std::mutex> g(rndzv_mu);
          addr_q.push_back({h.src, h.vaddr, h.bytes, h.tag,
                            wire_host(h.host)});
          rndzv_cv.notify_all();
        }
        rx_event();  // wake a parked sequencer waiting on the address
        break;
      }
      case MSG_RNDZV_WRITE: {
        // one-sided write: valid ONLY into an address this rank posted
        // to exactly this peer with exactly this size — otherwise any
        // connected peer would hold an arbitrary-write primitive into
        // the process. Unposted writes are dropped (and logged).
        // validate + land + complete in ONE critical section: a
        // completion timeout cannot slip between the posted-check and
        // the memcpy and free the target buffer underneath the write
        bool posted = false;
        {
          std::lock_guard<std::mutex> g(rndzv_mu);
          for (auto it = posted_addrs.begin(); it != posted_addrs.end();
               ++it) {
            if (it->vaddr == h.vaddr && it->src == h.src &&
                it->bytes == h.bytes) {
              posted_addrs.erase(it);
              posted = true;
              break;
            }
          }
          if (posted) {
            std::memcpy((void *)(uintptr_t)h.vaddr, payload.data(), plen);
            done_q.push_back({h.src, h.vaddr, h.bytes, h.tag});
            rndzv_cv.notify_all();
          }
        }
        if (posted) rx_event();  // wake a parked completion poll
        if (!posted) {
          // counted (stats2 rndzv_drops), printed only under
          // ACCL_RT_DEBUG: wire-drop logging must never spam stderr
          // on a revocation-heavy or chaos run
          stat_rndzv_drops++;
          if (debug_on)
            fprintf(stderr,
                    "[r%u] DROP unposted RNDZV_WRITE from r%u vaddr=%llx "
                    "bytes=%llu\n",
                    rank, h.src, (unsigned long long)h.vaddr,
                    (unsigned long long)h.bytes);
        }
        break;
      }
    }
    return true;
  }

  // ----- eager protocol (send .c:611-648 / recv .c:687-704) -----

  // seg_bytes 0 segments at the configured rx-buf size (the reference's
  // fixed rx-buffer geometry); the ring collectives pass a jumbo segment
  // for their streamed whole-chunk messages — receiver slots are growable
  // vectors, and on a CPU-bound host the per-segment syscall+header
  // overhead at 4 KB dominates the wire cost of a large chunk. Datagram
  // transport always respects the 64 KB packet ceiling.
  uint32_t egr_send(uint32_t dst, const uint8_t *ptr, uint64_t bytes,
                    uint32_t tag, uint64_t seg_bytes = 0) {
    // the datagram POE has no rendezvous path, so the configured message
    // ceiling applies to eager transfers there (without it, a huge send
    // would overflow the receiver's datagram buffer and surface as a
    // misleading sequencing error)
    if (udp_mode && bytes > max_rndzv) return DMA_SIZE_ERROR;
    if (fault_tail_pending.load(std::memory_order_acquire) &&
        fault_tail_dst.load(std::memory_order_relaxed) == dst) {
      // ACCL_RT_FAULT_DELAY_TAIL_MS precondition violated: delivering
      // more traffic to dst now would reorder the wire behind the
      // delayed tail — fail loudly at the source instead of producing a
      // baffling downstream sequencing error
      fprintf(stderr,
              "[r%u] FATAL: ACCL_RT_FAULT_DELAY_TAIL_MS wire-order "
              "violation: new eager traffic to r%u while its delayed "
              "tail is still in flight\n",
              rank, dst);
      abort();
    }
    uint64_t seg_max = seg_bytes ? seg_bytes : rx_buf_bytes;
    if (udp_mode) seg_max = std::min<uint64_t>(seg_max, rx_buf_bytes);
    // lane selection is per MESSAGE (every segment rides the same seqn
    // stream): bulk messages take the bulk lane so a jumbo in flight
    // cannot head-of-line-block a small message on lane 0
    uint32_t lane = lane_of(bytes);
    // one-shot fault arming: this message's final segment is delayed or
    // lost (see the fault-injection block above)
    bool fault_this = false;
    if ((fault_delay_tail_ms > 0 || fault_drop_tail) && bytes > seg_max &&
        !fault_armed.exchange(true))
      fault_this = true;
    TxBatch batch;
    batch.dst = dst;
    batch.lane = lane;
    uint64_t off = 0;
    while (off < bytes || bytes == 0) {
      uint64_t seg = std::min<uint64_t>(seg_max, bytes - off);
      uint32_t seqn = outbound_seq[sid(dst, lane)]++;
      bool last = (off + seg >= bytes);
      if (fault_this && last) {
        // tail levers run with batching off (tx_batch_on), but never
        // leave queued frames stranded behind the delayed/dropped tail
        if (!flush_batch(batch)) return RECEIVE_TIMEOUT_ERROR;
        if (fault_drop_tail) return NO_ERROR;  // lost on the wire
        // slow tail: deliver from a helper thread after the delay (the
        // caller must not send MORE traffic to dst before it lands, or
        // wire order breaks — acceptable for a test lever)
        std::vector<uint8_t> payload(ptr + off, ptr + off + seg);
        fault_tail_dst.store(dst, std::memory_order_relaxed);
        fault_tail_pending.store(true, std::memory_order_release);
        std::lock_guard<std::mutex> g(fault_mu);
        fault_threads.emplace_back([this, dst, tag, seqn, seg, bytes, off,
                                    lane,
                                    payload = std::move(payload)] {
          for (int waited = 0; waited < fault_delay_tail_ms && !stop.load();
               waited += 10)
            std::this_thread::sleep_for(std::chrono::milliseconds(10));
          if (!stop.load()) {
            // delivery-time wire-order assert: the arming contract says
            // nothing else advances this link while the tail is in
            // flight (egr_send aborts new eager traffic to dst above).
            // outbound_seq[dst] past seqn+1 here means a misconfigured
            // fault test already reordered the wire — fail fast and
            // loudly instead of delivering a tail that silently
            // corrupts the stream. The read is ordered by the
            // fault_tail_pending release/acquire pair: any egr_send
            // that could advance the counter observes pending==true
            // first and aborts, so a racing write cannot exist.
            // ACCL_ALLOW(ACCLN103: fault-thread read ordered by the fault_tail_pending release/acquire pair)
            if (outbound_seq[sid(dst, lane)] != seqn + 1) {
              fprintf(stderr,
                      "[r%u] FATAL: ACCL_RT_FAULT_DELAY_TAIL_MS wire-order "
                      "violation at delivery: outbound_seq[r%u]=%u advanced "
                      "past the delayed tail seqn=%u before the helper "
                      "thread delivered it\n",
                      // ACCL_ALLOW(ACCLN103: same release/acquire-ordered read, echoed in the abort message)
                      rank, dst, outbound_seq[sid(dst, lane)], seqn);
              abort();
            }
            frame_out(dst, MSG_EGR_DATA, tag, seqn, seg, 0, payload.data(),
                      seg, /*host=*/0, /*msg_bytes=*/bytes,
                      /*msg_off=*/off, lane);
          }
          fault_tail_pending.store(false, std::memory_order_release);
        });
        return NO_ERROR;
      }
      if (!frame_out(dst, MSG_EGR_DATA, tag, seqn, seg, 0, ptr + off, seg,
                     /*host=*/0, /*msg_bytes=*/bytes, /*msg_off=*/off, lane,
                     &batch))
        return RECEIVE_TIMEOUT_ERROR;
      off += seg;
      if (bytes == 0) break;  // zero-length notification (barrier)
    }
    // the caller's `ptr` (borrowed by non-rely batched views) stays
    // alive across this flush — the batch never outlives the call
    if (!flush_batch(batch)) return RECEIVE_TIMEOUT_ERROR;
    return NO_ERROR;
  }

  // Seek the segment matching (src, tag, expected seqn) with rx_mu HELD;
  // copy out (clamped to `cap`) + release (rxbuf_seek semantics). O(1)
  // via the (src, seqn) index. Returns NOT_READY when absent,
  // DMA_SIZE_ERROR on an oversized segment.
  //
  // Ordering faults are detected instead of wedging the link (reference
  // seqn-mismatch detection, dma_mover.cpp:342-352):
  //  - slots from src exist but the expected head seqn is absent: on the
  //    ordered per-link TCP transport this can never legally occur ->
  //    PACK_SEQ_NUMBER_ERROR; on the sessionless datagram POE the kernel
  //    may reorder under buffer pressure, so the expected datagram may
  //    still be in flight -> NOT_READY until the call deadline (loss
  //    surfaces as RECEIVE_TIMEOUT, not a misleading sequencing error);
  //  - `strict_tag`: an exact-tag mismatch AT the expected seqn is a
  //    protocol violation inside a collective (the head segment can never
  //    match) -> DMA_TAG_MISMATCH_ERROR. The non-strict SC_RECV retry
  //    path keeps NOT_READY there, because another parked recv with the
  //    matching tag may legally consume the head first.
  // ACCL_REQUIRES(rx_mu)
  uint32_t seek_locked(uint32_t src, uint32_t lane, uint32_t tag,
                       uint8_t *ptr, uint64_t cap, uint64_t *got,
                       bool strict_tag = false, bool msg_start = false,
                       uint64_t want_msg = 0) {
    uint32_t s_id = sid(src, lane);
    drain_orphans_locked(s_id);
    uint32_t want_seqn = inbound_seq[s_id];
    auto it = rx_index.find(rx_key(s_id, want_seqn));
    if (it == rx_index.end()) {
      // stray seqns with a missing head: on the bare ordered link this
      // can never legally occur (PACK_SEQ_NUMBER_ERROR); with the
      // reliability sublayer on it is exactly what a lost/corrupt/
      // reordered frame looks like MID-REPAIR — defer and let the nack
      // path fill the gap (note_want_locked arms it).
      if (src_valid_count[s_id] > 0 && !udp_mode && !rely_wire)
        return PACK_SEQ_NUMBER_ERROR;  // stray seqn on an ordered link
      stat_seek_miss++;
      note_want_locked(s_id);
      return NOT_READY;
    }
    stat_seek_hit++;
    size_t i = it->second;
    RxSlot &s = rx_slots[i];
    // Strict (collective) recvs meeting a MISMATCHED head (wrong tag or
    // message length) defer instead of erroring while the head may
    // legitimately belong to OTHER traffic interleaved on the link —
    // p2p messages share links with collective chunks, and the parked
    // p2p recv will consume its head and unblock ours (the reference's
    // rx pool matches out of order by (tag, src), so interleaved
    // traffic never faults there at all). Fail-fast is preserved for
    // provably stray heads: no outstanding recv pairs with it AND it
    // has sat unclaimed past the grace window.
    auto head_is_claimable = [&]() -> bool {
      auto age = std::chrono::steady_clock::now() - s.t_land;
      if (age < std::chrono::milliseconds(250)) return true;
      for (const auto &r : outstanding_recvs)
        if (r.src == src &&
            (r.tag == TAG_ANY || s.tag == TAG_ANY || r.tag == s.tag) &&
            r.bytes == s.msg_bytes)
          return true;
      return false;
    };
    if (!(tag == TAG_ANY || s.tag == tag || s.tag == TAG_ANY)) {
      if (strict_tag) {
        if (!head_is_claimable()) return DMA_TAG_MISMATCH_ERROR;
        note_defer_locked(s, tag, want_msg, DMA_TAG_MISMATCH_ERROR);
        return NOT_READY;
      }
      return NOT_READY;
    }
    // Message-boundary match at the head of a NEW message (msg_start):
    // the head segment must BE a message head (msg_off == 0) and its
    // total-message length must equal what this recv expects. Consuming a
    // shorter head message as "partial fill" of a larger recv would
    // concatenate two messages into one buffer; on the SC_RECV retry
    // path another parked recv with the matching length may legally
    // consume this head first, so defer with NOT_READY and let the
    // deadline turn an unmatched recv into RECEIVE_TIMEOUT; strict
    // recvs apply the claimable-head rule above.
    if (msg_start && (s.msg_bytes != want_msg || s.msg_off != 0)) {
      if (strict_tag) {
        if (!head_is_claimable()) return DMA_SIZE_ERROR;
        note_defer_locked(s, tag, want_msg, DMA_SIZE_ERROR);
        return NOT_READY;
      }
      return NOT_READY;
    }
    // Mid-message continuation must line up exactly with the progress the
    // resuming recv has already landed — anything else is a framing fault.
    if (!msg_start && (s.msg_bytes != want_msg || s.msg_off != want_msg - cap))
      return DMA_SIZE_ERROR;
    // A segment overflowing the remaining capacity after the message-level
    // match is a sender protocol fault (segments of one message must sum
    // to its msg_bytes) — an error in both modes.
    if (s.data.size() > cap) return DMA_SIZE_ERROR;
    *got = s.data.size();
    // empty vector's data() is null, and memcpy declares both pointers
    // nonnull even for zero sizes (UBSan: zero-length eager segments,
    // e.g. a world-strided chunk of a sub-world-sized buffer)
    if (ptr && !s.data.empty())
      std::memcpy(ptr, s.data.data(), s.data.size());
    release_slot_locked(i);
    rx_index.erase(it);
    src_valid_count[s_id]--;
    inbound_seq[s_id] = want_seqn + 1;
    rx_cv.notify_all();
    return NO_ERROR;
  }

  // Drain orphaned continuation segments (rx_mu held): when a recv dies
  // mid-message (deadline), the unconsumed tail of its message still
  // occupies the head seqns — discard segments until the next message
  // head (msg_off == 0) surfaces, then resume normal matching. Runs at
  // the top of seek AND before the SC_RECV elder-pairing check, so FIFO
  // eligibility is always judged against the true next message head.
  // ACCL_REQUIRES(rx_mu)
  void drain_orphans_locked(uint32_t s_id) {
    while (rx_drain_srcs.count(s_id)) {
      auto dit = rx_index.find(rx_key(s_id, inbound_seq[s_id]));
      if (dit == rx_index.end()) return;  // tail not yet arrived: stay armed
      RxSlot &ds = rx_slots[dit->second];
      if (ds.msg_off == 0) {
        rx_drain_srcs.erase(s_id);  // a fresh head: drain complete
        return;
      }
      release_slot_locked(dit->second);
      rx_index.erase(dit);
      src_valid_count[s_id]--;
      inbound_seq[s_id]++;
    }
  }

  // Return one slot to the IDLE free-list (rx_mu held), compacting a
  // grown ring back to the configured size once fully drained so one
  // burst does not permanently retain payload memory (all slots idle
  // implies the index is empty).
  // Reconfiguration fence (accl_rt_flush_rx): drop every landed-but-
  // unconsumed eager frame and advance the per-peer inbound seqn past
  // it, then clear the stale rendezvous queues. After a membership
  // change, frames of the OLD world's aborted collectives may sit in
  // the ring (per-op progress re-arms deadlines, so one survivor's
  // wedged call can outlive another's final send) — and the seqn-
  // ordered streamed matching would deliver them into the NEW world's
  // first recv as data. Caller contract: quiescent — no live calls on
  // this rank and peers' in-flight deliveries settled (the recovery
  // driver joins/barriers its survivors first); an in-flight frame
  // arriving after the fence carries a seqn below the advanced
  // inbound_seq and is dropped by land_eager's late-duplicate check.
  void flush_rx() {
    {
      std::lock_guard<std::mutex> g(rx_mu);
      for (size_t i = 0; i < rx_slots.size(); i++) {
        RxSlot &s = rx_slots[i];
        if (s.status != RxSlot::VALID) continue;
        uint32_t ss = sid(s.src, s.lane);
        if ((int32_t)(s.seqn + 1 - inbound_seq[ss]) > 0)
          inbound_seq[ss] = s.seqn + 1;
        rx_index.erase(rx_key(ss, s.seqn));
        src_valid_count[ss]--;
        release_slot_locked(i);  // may compact: the loop bound re-reads
      }
      rx_drain_srcs.clear();
      // reliability state is per-membership: a want armed for an
      // old-world gap must not nack into the new world, and the acked
      // watermark follows the advanced seqns so no ack ever regresses
      for (auto &w : want) w = WantState{};
      for (uint32_t s = 0; s < acked_upto.size(); s++)
        acked_upto[s] = inbound_seq[s];
      rx_cv.notify_all();
    }
    {
      // sender-side reliability state: retained frames and reorder
      // holds of the aborted old-world collectives are stale — a
      // post-fence nack can only reference post-fence traffic
      std::lock_guard<std::mutex> g(rely_mu);
      for (auto &rb : retx) {
        rb.q.clear();
        rb.bytes = 0;
      }
      retx_pending.clear();
      reorder_held.clear();
    }
    {
      std::lock_guard<std::mutex> g(rndzv_mu);
      addr_q.clear();
      done_q.clear();
      rndzv_cv.notify_all();
    }
  }

  // ACCL_REQUIRES(rx_mu)
  void release_slot_locked(size_t i) {
    RxSlot &s = rx_slots[i];
    s.status = RxSlot::IDLE;
    if (i >= base_rx_slots)
      std::vector<uint8_t>().swap(s.data);  // free burst capacity
    else
      s.data.clear();
    idle_q.push_back(i);
    if (rx_slots.size() > base_rx_slots &&
        idle_q.size() == rx_slots.size()) {
      rx_slots.resize(base_rx_slots);
      idle_q.clear();
      for (size_t j = 0; j < base_rx_slots; j++) idle_q.push_back(j);
    }
  }

  // ----- rendezvous protocol (.c:142-408) -----

  void rendezvous_send_addr(uint32_t dst, uint64_t vaddr, uint64_t bytes,
                            uint32_t tag, uint32_t host = 0) {
    {
      // register the posting BEFORE the peer can possibly write it
      std::lock_guard<std::mutex> g(rndzv_mu);
      posted_addrs.push_back({dst, vaddr, bytes, tag, host});
    }
    frame_out(dst, MSG_RNDZV_ADDR, tag, 0, bytes, vaddr, nullptr, 0, host);
  }

  // Non-blocking: waiting for a peer's address happens by NOT_READY
  // requeue in the sequencer, never inside this call.
  uint32_t rendezvous_get_addr(uint32_t src, uint64_t bytes, uint32_t tag,
                               uint64_t *vaddr) {
    std::lock_guard<std::mutex> lk(rndzv_mu);
    for (auto it = addr_q.begin(); it != addr_q.end(); ++it) {
      // wildcard on EITHER side matches, mirroring the eager seek's
      // (tag==ANY || slot==ANY || equal) rule: a TAG_ANY recv's posted
      // address must accept a tagged send (asymmetric wildcard — the
      // eager path always allowed it; the rendezvous matchers used to
      // honor the wildcard only on the send side)
      if (it->src == src && it->bytes == bytes &&
          (tag == TAG_ANY || it->tag == TAG_ANY || it->tag == tag)) {
        *vaddr = it->vaddr;
        addr_q.erase(it);
        return NO_ERROR;
      }
    }
    return NOT_READY;
  }

  uint32_t rendezvous_write(uint32_t dst, uint64_t remote_vaddr,
                            const uint8_t *ptr, uint64_t bytes, uint32_t tag) {
    return frame_out(dst, MSG_RNDZV_WRITE, tag, 0, bytes, remote_vaddr, ptr,
                     bytes)
               ? NO_ERROR
               : RECEIVE_TIMEOUT_ERROR;
  }

  // Drop the posting matching (src, vaddr, bytes, tag) — called with
  // rndzv_mu HELD on timeout/error revocation, so a late write cannot
  // land in a buffer the caller is about to free. Erases at most one
  // entry so other in-flight recvs keep their postings.
  // rndzv_mu held via lk. An in-flight direct write is asked to let go
  // (abort) and the wait is BOUNDED: the rx thread's read loop
  // re-checks the posting at least every 100 ms and acknowledges by
  // clearing in_use, diverting the rest of the payload to scratch — the
  // target buffer is never written after this returns. The cv wait
  // drops the lock, so the scan restarts after each wakeup.
  // ACCL_REQUIRES(rndzv_mu)
  void revoke_posted_locked(std::unique_lock<std::mutex> &lk, uint32_t src,
                            uint64_t vaddr, uint64_t bytes, uint32_t tag) {
    for (;;) {
      auto it = posted_addrs.begin();
      for (; it != posted_addrs.end(); ++it)
        if (it->src == src && it->vaddr == vaddr && it->bytes == bytes &&
            (tag == TAG_ANY || it->tag == tag))
          break;
      if (it == posted_addrs.end()) return;
      if (it->in_use) {
        it->abort = true;
        cv_wait_for(rndzv_cv, lk, std::chrono::milliseconds(250));
        continue;
      }
      posted_addrs.erase(it);
      return;
    }
  }

  // Non-blocking completion checks (the blocking variants are gone: every
  // receive dependency in the sequencer is NOT_READY-resumable, so waiting
  // happens by requeue, never inside a collective).
  uint32_t rndzv_completion_nb(uint32_t src, uint64_t vaddr, uint64_t bytes,
                               uint32_t tag) {
    std::lock_guard<std::mutex> lk(rndzv_mu);
    for (auto it = done_q.begin(); it != done_q.end(); ++it) {
      if (it->src == src && it->vaddr == vaddr && it->bytes == bytes &&
          (tag == TAG_ANY || it->tag == TAG_ANY || it->tag == tag)) {
        done_q.erase(it);
        return NO_ERROR;
      }
    }
    return NOT_READY;
  }

  // "Any" matching is scoped to the addresses THIS call posted: with
  // resumable state machines, two rendezvous collectives on disjoint
  // communicators can be in flight on one rank at once, and an unscoped
  // (bytes, tag) match would let one call consume the other's completion
  // and combine foreign data.
  uint32_t rndzv_any_posted_completion_nb(const std::deque<RndzvAddr> &posted,
                                          uint64_t bytes, uint32_t tag,
                                          uint32_t *src, uint64_t *vaddr) {
    std::lock_guard<std::mutex> lk(rndzv_mu);
    for (auto it = done_q.begin(); it != done_q.end(); ++it) {
      if (it->bytes != bytes ||
          !(tag == TAG_ANY || it->tag == TAG_ANY || it->tag == tag))
        continue;
      for (const auto &pa : posted) {
        if (pa.vaddr == it->vaddr && pa.src == it->src) {
          *src = it->src;
          *vaddr = it->vaddr;
          done_q.erase(it);
          return NO_ERROR;
        }
      }
    }
    return NOT_READY;
  }

  // (The eager/rendezvous split itself lives on Ops::rndzv, evaluated
  // against the per-call config snapshot. The datagram POE is eager-only:
  // rendezvous message types exist only on the RDMA stack in the
  // reference, eth_intf.h:42-45.)

  // ----- resumable op layer ----------------------------------------------
  // Every do_* below is a DETERMINISTIC sequence of ops (sends, receives,
  // rendezvous posts/completions, local mutations). Ops replays the
  // sequence on each (re-)entry: ops with index < current_step are skipped
  // (their side effects persist in caller memory or CollState), the op AT
  // current_step executes, and the first NOT_READY aborts the pass so the
  // sequencer requeues the call with current_step saved — the firmware
  // retry contract (ccl_offload_control.c:2308-2483) for EVERY collective,
  // not just SC_RECV. No receive dependency ever blocks the sequencer
  // thread; eager sends can still backpressure on a full TCP socket, as
  // the reference firmware does on a full TX FIFO.
  struct Ops {
    accl_rt &rt;
    Call &c;
    CollState &st;
    uint32_t tag;
    uint32_t idx = 0;

    template <class F> uint32_t op(F f) {
      uint32_t i = idx++;
      if (i < c.current_step) return NO_ERROR;  // replayed: already done
      uint32_t rc = f();
      if (rc == NO_ERROR) c.current_step = i + 1;
      return rc;
    }
    // protocol split from the per-call SNAPSHOT, not live config: a
    // config call between requeue passes must not shift the op sequence
    bool rndzv(uint64_t n) const { return !rt.udp_mode && n > st.max_eager; }
    // one-shot local mutation (scratch init, result memcpy): gated so a
    // resumed pass cannot clobber accumulated progress
    template <class F> void local(F f) {
      op([&] { f(); return (uint32_t)NO_ERROR; });
    }
    // eager or rendezvous send; the rendezvous address wait is NOT_READY
    // instead of blocking
    uint32_t send(uint32_t gdst, const uint8_t *p, uint64_t n) {
      return op([&]() -> uint32_t {
        if (rndzv(n)) {
          if (n > st.max_rndzv) return DMA_SIZE_ERROR;  // configured ceiling
          uint64_t va;
          uint32_t rc = rt.rendezvous_get_addr(gdst, n, tag, &va);
          if (rc != NO_ERROR) return rc;
          return rt.rendezvous_write(gdst, va, p, n, tag);
        }
        return rt.egr_send(gdst, p, n, tag);
      });
    }
    // post this rank's landing address (one-shot; tracked for timeout
    // revocation)
    uint32_t post(uint32_t gsrc, uint8_t *p, uint64_t n) {
      return op([&]() -> uint32_t {
        rt.rendezvous_send_addr(gsrc, (uint64_t)(uintptr_t)p, n, tag);
        st.posted.push_back({gsrc, (uint64_t)(uintptr_t)p, n, tag, 0});
        return NO_ERROR;
      });
    }
    uint32_t completion(uint32_t gsrc, uint8_t *p, uint64_t n) {
      return op([&]() -> uint32_t {
        uint32_t rc =
            rt.rndzv_completion_nb(gsrc, (uint64_t)(uintptr_t)p, n, tag);
        if (rc == NO_ERROR) st.unpost((uint64_t)(uintptr_t)p);
        return rc;
      });
    }
    // consume ANY completion landing in one of THIS call's postings, then
    // run fn(src, vaddr) inside the same op (reduce-root combines ride
    // here)
    template <class F> uint32_t any_completion_then(uint64_t n, F fn) {
      return op([&]() -> uint32_t {
        uint32_t s;
        uint64_t va;
        uint32_t rc =
            rt.rndzv_any_posted_completion_nb(st.posted, n, tag, &s, &va);
        if (rc != NO_ERROR) return rc;
        st.unpost(va);
        return fn(s, va);
      });
    }
    // eager or rendezvous receive. Eager lands segment-by-segment with
    // st.off tracking partial progress within the op; rendezvous posts
    // once (st.off as the posted marker) then polls the completion.
    // strict=false is the SC_RECV contract: a head-tag mismatch stays
    // NOT_READY because another parked recv may legally consume it.
    // force_eager: consume a message the peer is known to stream eagerly
    // regardless of size (the ring collectives' whole-chunk messages) —
    // the protocol split would otherwise post a rendezvous address for a
    // write that never comes.
    // ----- streamed whole-chunk helpers (the ring/tree internal hops) --
    // One logical chunk as eagerly-streamed jumbo-segment message(s):
    // on the session transport a single message (egr_send pipelines its
    // segments without waiting; the receiver drains incrementally inside
    // one resumable recv op); on the datagram POE the chunk splits into
    // messages <= max_rndzv — the configured datagram-mode message
    // ceiling — so large collectives no longer DMA_SIZE_ERROR there
    // (both sides compute the identical split from the snapshotted
    // config). Always paired: recv_stream on the peer, never a plain
    // recv/rendezvous post.
    uint64_t stream_cap(uint64_t n) const {
      return rt.udp_mode ? std::min<uint64_t>(st.max_rndzv, n ? n : 1) : n;
    }
    uint32_t send_stream(uint32_t gdst, const uint8_t *p, uint64_t n) {
      uint64_t cap = stream_cap(n);
      uint64_t off = 0;
      do {
        uint64_t m = n ? std::min<uint64_t>(cap, n - off) : 0;
        uint32_t rc = op([&, off = off, m = m] {
          return rt.egr_send(gdst, p + off, m, tag,
                             /*seg_bytes=*/STREAM_SEG_BYTES);
        });
        if (rc != NO_ERROR) return rc;
        off += m;
      } while (off < n);
      return NO_ERROR;
    }
    uint32_t recv_stream(uint32_t gsrc, uint8_t *p, uint64_t n) {
      uint64_t cap = stream_cap(n);
      uint64_t off = 0;
      do {
        uint64_t m = n ? std::min<uint64_t>(cap, n - off) : 0;
        uint32_t rc = recv(gsrc, p ? p + off : nullptr, m, /*strict=*/true,
                           /*force_eager=*/true);
        if (rc != NO_ERROR) return rc;
        off += m;
      } while (off < n);
      return NO_ERROR;
    }
    // protocol-aware pair: rendezvous keeps its one-sided write; the
    // eager side (any size in udp_mode, <= max_eager on sessions) rides
    // the streamed helpers so large datagram-transport collectives split
    // under the message ceiling instead of failing DMA_SIZE_ERROR
    uint32_t send_x(uint32_t gdst, const uint8_t *p, uint64_t n) {
      return rndzv(n) ? send(gdst, p, n) : send_stream(gdst, p, n);
    }
    uint32_t recv_x(uint32_t gsrc, uint8_t *p, uint64_t n) {
      return rndzv(n) ? recv(gsrc, p, n) : recv_stream(gsrc, p, n);
    }
    uint32_t recv(uint32_t gsrc, uint8_t *p, uint64_t n, bool strict = true,
                  bool force_eager = false) {
      return op([&]() -> uint32_t {
        if (!force_eager && rndzv(n)) {
          if (n > st.max_rndzv) return DMA_SIZE_ERROR;
          uint64_t va = (uint64_t)(uintptr_t)p;
          if (st.off == 0) {
            rt.rendezvous_send_addr(gsrc, va, n, tag);
            st.posted.push_back({gsrc, va, n, tag, 0});
            st.off = 1;  // posted marker
          }
          uint32_t rc = rt.rndzv_completion_nb(gsrc, va, n, tag);
          if (rc == NO_ERROR) {
            st.off = 0;
            st.unpost(va);
          }
          return rc;
        }
        if (rt.udp_mode && n > st.max_rndzv) return DMA_SIZE_ERROR;
        std::lock_guard<std::mutex> lk(rt.rx_mu);
        const void *tok = (const void *)&st;
        // the lane this message rides is a pure function of its size —
        // both ends compute it from the message length, so the receiver
        // watches exactly the seqn stream the sender feeds
        const uint32_t lane = rt.lane_of(n);
        const uint32_t lsid = rt.sid(gsrc, lane);
        // SC_RECV posted-order FIFO: outstanding p2p recvs register a
        // ticket (first execution follows run() order — the sequencer
        // starts fresh calls in queue order), and a recv may take a new
        // head message only when no EARLIER-posted outstanding recv
        // also pairs with it (tag match + exact message length). This
        // is the parked-notification FIFO contract: without it two
        // TAG_ANY recvs race through the retry queue and the head goes
        // to whichever retries first, not to the first posted. Register
        // BEFORE any defer below, or a pass bounced off the stream-owner
        // check would leave this call unticketed and a younger recv
        // could out-rank it.
        if (!strict && !st.ticketed) {
          st.ticket = rt.recv_ticket_next++;
          rt.outstanding_recvs.push_back({gsrc, tag, n, st.ticket, tok});
          st.ticketed = true;
        }
        // stream ownership: a call that consumed part of a multi-segment
        // message from gsrc owns the remainder — any other call defers,
        // or it would interleave payload mid-message
        auto ow = rt.rx_stream_owner.find(lsid);
        if (ow != rt.rx_stream_owner.end() && ow->second != tok)
          return NOT_READY;
        if (!strict) {
          if (st.off == 0) {
            // judge FIFO eligibility against the true next message head,
            // not an orphaned continuation segment awaiting drain
            rt.drain_orphans_locked(lsid);
            auto hit =
                rt.rx_index.find(rx_key(lsid, rt.inbound_seq[lsid]));
            if (hit != rt.rx_index.end()) {
              const RxSlot &hs = rt.rx_slots[hit->second];
              for (const auto &r : rt.outstanding_recvs)
                if (r.tok != tok && r.src == gsrc && r.ticket < st.ticket &&
                    (r.tag == TAG_ANY || hs.tag == TAG_ANY ||
                     r.tag == hs.tag) &&
                    r.bytes == hs.msg_bytes)
                  return NOT_READY;  // the elder recv pairs with this head
            }
          }
        }
        // Direct-placement sync: a registered landing accumulates
        // progress from the rx thread; fold it into st.off (which also
        // re-arms the call deadline) before falling through to the
        // slot path — segments that landed in slots while the landing
        // was ineligible (other traffic queued ahead) still merge here.
        auto itl = st.landing ? rt.eager_landings.find(lsid)
                              : rt.eager_landings.end();
        if (itl != rt.eager_landings.end() && itl->second.tok == tok)
          st.off = itl->second.landed;
        for (;;) {
          if (st.off >= n && n > 0) break;
          uint64_t got = 0;
          uint32_t rc = rt.seek_locked(gsrc, lane, tag,
                                       p ? p + st.off : nullptr, n - st.off,
                                       &got, strict,
                                       /*msg_start=*/st.off == 0,
                                       /*want_msg=*/n);
          if (rc != NO_ERROR) {  // NOT_READY keeps st.off progress
            if (rc == NOT_READY && st.off > 0 && st.off < n)
              rt.rx_stream_owner[lsid] = tok;  // mid-message: claim
            if (rc == NOT_READY && strict && !rt.udp_mode && p && n > 0 &&
                !st.landing &&
                rt.eager_landings.find(lsid) == rt.eager_landings.end() &&
                n >= (64ull << 10)) {
              // threshold: only chunks big enough that the saved
              // staging copy + slot allocation outweigh the
              // registration round trips (small logp hops measured
              // slower with landings at 2*rx_buf)
              // register direct placement for the remainder: the rx
              // thread reads our message's further segments straight
              // into p (rxbuf bypass; see EagerLanding)
              rt.eager_landings[lsid] =
                  EagerLanding{p, n, st.off, tag, /*in_use=*/false,
                               /*abort=*/false, tok};
              st.landing = true;
            }
            return rc;
          }
          st.off += got;
          if (itl != rt.eager_landings.end() && itl->second.tok == tok)
            itl->second.landed = st.off;  // keep the rx thread's
                                          // msg_off expectation exact
          if (st.off >= n) break;  // n == 0: one zero-length segment
        }
        if (st.landing) {
          auto drop = rt.eager_landings.find(lsid);
          if (drop != rt.eager_landings.end() && drop->second.tok == tok)
            rt.eager_landings.erase(drop);
          st.landing = false;
        }
        st.off = 0;
        auto own = rt.rx_stream_owner.find(lsid);
        if (own != rt.rx_stream_owner.end() && own->second == tok)
          rt.rx_stream_owner.erase(own);
        return NO_ERROR;
      });
    }
  };

  // ----- collective algorithms (firmware ports; cites in each) -----
  // All are replayed op sequences over Ops (see above): any nonzero return
  // aborts the pass — NOT_READY requeues with progress saved, real errors
  // complete the call.

  uint32_t do_bcast(Ops &o, const CommView &cm, uint8_t *buf, uint64_t bytes,
                    uint32_t root) {
    if (cm.world == 1) return NO_ERROR;
    uint32_t rc;
    if (o.rndzv(bytes) && cm.world > o.st.tun_bcast_ranks) {
      // binary distance-doubling tree (.c:814-867). `sender` flips on a
      // completed-or-replayed recv, so resumed passes recompute it.
      uint32_t l = (cm.rank + cm.world - root) % cm.world;
      bool sender = (cm.rank == root);
      uint32_t d = 1;
      while ((d << 1) <= cm.world - 1) d <<= 1;
      while (d > 0) {
        if (sender && l % (2 * d) == 0 && l + d < cm.world) {
          uint32_t peer = (l + d + root) % cm.world;
          if ((rc = o.send(cm.g(peer), buf, bytes))) return rc;
        } else if (!sender && l % d == 0 && l >= d && (l - d) % (2 * d) == 0) {
          uint32_t peer = (l - d + root) % cm.world;
          if ((rc = o.recv(cm.g(peer), buf, bytes))) return rc;
          sender = true;
        }
        d >>= 1;
      }
      return NO_ERROR;
    }
    // flat fan-out, eager or rendezvous (.c:868-988)
    if (cm.rank == root) {
      for (uint32_t i = 0; i < cm.world; i++)
        if (i != root && (rc = o.send_x(cm.g(i), buf, bytes))) return rc;
    } else {
      if ((rc = o.recv_x(cm.g(root), buf, bytes))) return rc;
    }
    return NO_ERROR;
  }

  uint32_t do_scatter(Ops &o, const CommView &cm, const uint8_t *src,
                      uint8_t *dst, uint64_t bytes, uint32_t root) {
    uint32_t rc;
    if (cm.rank == root) {
      for (uint32_t i = 0; i < cm.world; i++) {
        if (i == root) continue;
        if ((rc = o.send_x(cm.g(i), src + (uint64_t)i * bytes, bytes)))
          return rc;
      }
      o.local([&] { std::memcpy(dst, src + (uint64_t)root * bytes, bytes); });
    } else {
      if ((rc = o.recv_x(cm.g(root), dst, bytes))) return rc;
    }
    return NO_ERROR;
  }

  uint32_t do_gather(Ops &o, const CommView &cm, const uint8_t *src,
                     uint8_t *dst, uint64_t bytes, uint32_t root) {
    // eager: ring daisy-chain (.c:1206-1293); rendezvous: flat to root
    // (.c:1142-1204). The ring keeps per-link traffic constant.
    uint32_t rc;
    CollState &st = o.st;
    if (!o.rndzv(bytes)) {
      uint32_t nxt = cm.g((cm.rank + 1) % cm.world);
      uint32_t prv = cm.g((cm.rank + cm.world - 1) % cm.world);
      st.tmp.resize(bytes + 1);  // relay buffer survives requeues
      if (cm.rank == root) {
        o.local([&] { std::memcpy(dst + (uint64_t)root * bytes, src, bytes); });
        for (uint32_t s = 0; s < cm.world - 1; s++) {
          if ((rc = o.recv_stream(prv, st.tmp.data(), bytes))) return rc;
          uint32_t origin = (root + cm.world - 1 - s) % cm.world;
          o.local([&] {
            std::memcpy(dst + (uint64_t)origin * bytes, st.tmp.data(), bytes);
          });
        }
      } else {
        // relay: own data first, then forward everything originating
        // farther from root than us — world-1-dist(rank) messages, where
        // dist is the +1-direction hop count to root.
        if ((rc = o.send_stream(nxt, src, bytes))) return rc;
        uint32_t dist = (root + cm.world - cm.rank) % cm.world;
        for (uint32_t s = 0; s + 1 + dist < cm.world; s++) {
          if ((rc = o.recv_stream(prv, st.tmp.data(), bytes))) return rc;
          if ((rc = o.send_stream(nxt, st.tmp.data(), bytes))) return rc;
        }
      }
      return NO_ERROR;
    }
    // fan-in cap (accl.cpp:1200-1201 via the tuning registers, same rule
    // as plan.py gather selection): above the count threshold the flat
    // tree becomes a binomial combining tree. Any cap value below
    // world-1 selects the radix-2 binomial on BOTH executors (the XLA
    // gather_flat_schedule makes the identical binary choice), so the
    // register is a threshold switch, not a radix.
    uint32_t fanin = bytes > st.tun_gather_count
                         ? std::max(st.tun_gather_fanin, 1u)
                         : cm.world - 1;
    if (fanin < cm.world - 1) {
      // binomial: normalized rank l accumulates subtree chunks
      // [l, l + 2^k); children with l % 2d == d relay their block to
      // l - d chunk-by-chunk, so per-message size never exceeds what the
      // flat tree would send (the rendezvous ceiling applies per chunk).
      // The accumulation buffer holds only this rank's maximum subtree
      // (lowest set bit of l), not the full world, indexed relative to l.
      // `have` is recomputed by the replay as recv ops report done.
      uint32_t l = (cm.rank + cm.world - root) % cm.world;
      uint32_t max_have =
          l == 0 ? cm.world : std::min(l & (~l + 1), cm.world - l);
      st.acc.resize((uint64_t)max_have * bytes);
      o.local([&] { std::memcpy(st.acc.data(), src, bytes); });
      uint32_t have = 1;  // chunks accumulated at [l, l + have)
      for (uint32_t d = 1; d < cm.world; d <<= 1) {
        if (l % (2 * d) == d) {
          uint32_t parent = (l - d + root) % cm.world;
          for (uint32_t ci = 0; ci < have; ci++)
            if ((rc = o.send(cm.g(parent),
                             st.acc.data() + (uint64_t)ci * bytes, bytes)))
              return rc;
          return NO_ERROR;  // subtree delivered
        }
        if (l % (2 * d) == 0 && l + d < cm.world) {
          uint32_t child = (l + d + root) % cm.world;
          uint32_t n_ch = std::min(d, cm.world - (l + d));
          for (uint32_t ci = 0; ci < n_ch; ci++)
            if ((rc = o.recv(cm.g(child),
                             st.acc.data() + (uint64_t)(d + ci) * bytes,
                             bytes)))
              return rc;
          have += n_ch;
        }
      }
      // root (l == 0) de-normalizes chunk order into dst
      o.local([&] {
        for (uint32_t ln = 0; ln < cm.world; ln++) {
          uint32_t g = (ln + root) % cm.world;
          std::memcpy(dst + (uint64_t)g * bytes,
                      st.acc.data() + (uint64_t)ln * bytes, bytes);
        }
      });
      return NO_ERROR;
    }
    if (cm.rank == root) {
      o.local([&] { std::memcpy(dst + (uint64_t)root * bytes, src, bytes); });
      for (uint32_t i = 0; i < cm.world; i++) {
        if (i == root) continue;
        if ((rc = o.post(cm.g(i), dst + (uint64_t)i * bytes, bytes)))
          return rc;
      }
      for (uint32_t i = 0; i + 1 < cm.world; i++)
        if ((rc = o.any_completion_then(
                 bytes, [](uint32_t, uint64_t) { return (uint32_t)NO_ERROR; })))
          return rc;
    } else {
      if ((rc = o.send(cm.g(root), src, bytes))) return rc;
    }
    return NO_ERROR;
  }

  uint32_t do_allgather(Ops &o, const CommView &cm, const uint8_t *src,
                        uint8_t *dst, uint64_t bytes) {
    // Streamed-eager allgather at EVERY size (.c:1297-1499's role). The
    // former per-hop rendezvous handshake paid two extra wire round
    // trips per hop and measured SLOWER than the allreduce that moves
    // twice its bytes (emu_bench.csv r4: 0.023 vs 0.083 GB/s at
    // 1 MB / 8w); whole-chunk jumbo-segment streaming replaces it.
    //  - power-of-two worlds: recursive doubling — block sizes double
    //    every step, log2(P) latency steps instead of P-1. Before step
    //    d each rank holds the contiguous d-chunk block of its aligned
    //    group; partners' blocks are adjacent and merge.
    //  - other worlds: the ring, hop payloads streamed whole.
    uint32_t rc;
    o.local([&] { std::memcpy(dst + (uint64_t)cm.rank * bytes, src, bytes); });
    if ((cm.world & (cm.world - 1)) == 0 &&
        (shape_override == 2 ||
         (shape_override == 0 &&
          bytes * cm.world <= logp_ag_max_bytes(cm.world)))) {
      for (uint32_t d = 1; d < cm.world; d <<= 1) {
        uint32_t peer = cm.g(cm.rank ^ d);
        uint64_t mine = (uint64_t)(cm.rank & ~(d - 1)) * bytes;
        uint64_t theirs = (uint64_t)((cm.rank ^ d) & ~(d - 1)) * bytes;
        if ((rc = o.send_stream(peer, dst + mine, (uint64_t)d * bytes)))
          return rc;
        if ((rc = o.recv_stream(peer, dst + theirs, (uint64_t)d * bytes)))
          return rc;
      }
      return NO_ERROR;
    }
    uint32_t nxt = cm.g((cm.rank + 1) % cm.world);
    uint32_t prv = cm.g((cm.rank + cm.world - 1) % cm.world);
    const uint8_t *send_ptr = dst + (uint64_t)cm.rank * bytes;
    for (uint32_t s = 0; s < cm.world - 1; s++) {
      uint32_t origin = (cm.rank + cm.world - 1 - s) % cm.world;
      uint8_t *recv_ptr = dst + (uint64_t)origin * bytes;
      // eager sends before receives, socket buffering absorbing the
      // send so the ring cannot deadlock
      if ((rc = o.send_stream(nxt, send_ptr, bytes))) return rc;
      if ((rc = o.recv_stream(prv, recv_ptr, bytes))) return rc;
      send_ptr = recv_ptr;
    }
    return NO_ERROR;
  }

  uint32_t do_reduce(Ops &o, const CommView &cm, uint32_t dt, uint32_t func,
                     const uint8_t *src, uint8_t *dst, uint64_t count,
                     uint32_t root) {
    uint64_t bytes = count * dtype_bytes(dt);
    uint32_t rc;
    CollState &st = o.st;
    if (cm.world == 1) {
      o.local([&] { std::memcpy(dst, src, bytes); });
      return NO_ERROR;
    }
    if (!o.rndzv(bytes)) {
      // eager ring relay with fused recv-reduce-send (.c:1730-1743)
      uint32_t prv = cm.g((cm.rank + cm.world - 1) % cm.world);
      uint32_t nxt = cm.g((cm.rank + 1) % cm.world);
      uint32_t l = (cm.rank + cm.world - root) % cm.world;  // root at 0
      st.acc.resize(bytes + 1);
      o.local([&] { std::memcpy(st.acc.data(), src, bytes); });
      if (l != 1) {  // everyone except the chain head receives a partial
        if ((rc = o.recv_stream(prv, st.acc.data(), bytes))) return rc;
        if ((rc = o.op([&] {
               return combine_buffers(dt, func, st.acc.data(), src, count);
             })))
          return rc;
      }
      if (cm.rank != root) {
        if ((rc = o.send_stream(nxt, st.acc.data(), bytes))) return rc;
      } else {
        o.local([&] { std::memcpy(dst, st.acc.data(), bytes); });
      }
      return NO_ERROR;
    }
    // rendezvous: flat tree when small world/message, else binomial
    // (.c:1531-1727)
    bool flat = cm.world <= st.tun_reduce_ranks ||
                bytes <= st.tun_reduce_count;
    uint32_t l = (cm.rank + cm.world - root) % cm.world;
    if (flat) {
      if (cm.rank == root) {
        // landing slots must stay allocated (and un-moved) until every
        // posted write completes: st.acc persists across requeues
        st.acc.resize((uint64_t)(cm.world - 1) * bytes);
        for (uint32_t i = 0, j = 0; i < cm.world; i++) {
          if (i == root) continue;
          if ((rc = o.post(cm.g(i), st.acc.data() + (uint64_t)j * bytes,
                           bytes)))
            return rc;
          j++;
        }
        o.local([&] { std::memcpy(dst, src, bytes); });
        for (uint32_t i = 0; i + 1 < cm.world; i++)
          if ((rc = o.any_completion_then(bytes, [&](uint32_t, uint64_t va) {
                 return combine_buffers(dt, func, dst,
                                        (void *)(uintptr_t)va, count);
               })))
            return rc;
      } else {
        if ((rc = o.send(cm.g(root), src, bytes))) return rc;
      }
      return NO_ERROR;
    }
    // binomial combining tree: children l%2d==d send to parent l-d
    st.acc.resize(bytes);
    st.tmp.resize(bytes);
    o.local([&] { std::memcpy(st.acc.data(), src, bytes); });
    for (uint32_t d = 1; d < cm.world; d <<= 1) {
      if (l % (2 * d) == d) {
        uint32_t peer = (l - d + root) % cm.world;
        return o.send(cm.g(peer), st.acc.data(), bytes);  // subtree done
      }
      if (l % (2 * d) == 0 && l + d < cm.world) {
        uint32_t peer = (l + d + root) % cm.world;
        if ((rc = o.recv(cm.g(peer), st.tmp.data(), bytes))) return rc;
        if ((rc = o.op([&] {
               return combine_buffers(dt, func, st.acc.data(), st.tmp.data(),
                                      count);
             })))
          return rc;
      }
    }
    if (cm.rank == root)
      o.local([&] { std::memcpy(dst, st.acc.data(), bytes); });
    return NO_ERROR;
  }

  // Auto crossover between the log2(P)-hop recursive halving/doubling
  // shapes and the 2(P-1)/(P-1)-hop rings: the log shape saves
  // (hops_ring - hops_log) scheduling latencies but its larger per-hop
  // messages overlap worse on a contended host, so it wins only while
  // payloads are latency-dominated. Calibrated from the forced-shape
  // sweep (accl_log/rt_stats_shape_*.csv, tools/rt_stats_sweep.py
  // --shape): measured tie points sit at ~32 KB of payload per hop
  // saved (w8: tie ~256 KB with 8 hops saved; w16: tie ~512-700 KB
  // with 22 saved; allgather tie ~512 KB total with 4 saved).
  static uint32_t log2_floor(uint32_t world) {
    uint32_t r = 0;
    while ((1u << (r + 1)) <= world) r++;
    return r;
  }
  // allreduce: ring 2(P-1) hops vs halving-doubling 2*log2(P)
  uint64_t logp_max_bytes(uint32_t world) const {
    uint32_t hops_saved = 2 * (world - 1) - 2 * log2_floor(world);
    return (uint64_t)hops_saved * LOGP_ALLREDUCE_HOP_BYTES;
  }
  // allgather: ring P-1 hops vs doubling log2(P); threshold compares
  // against the TOTAL gathered payload (world * chunk)
  uint64_t logp_ag_max_bytes(uint32_t world) const {
    uint32_t hops_saved = (world - 1) - log2_floor(world);
    return (uint64_t)hops_saved * LOGP_ALLGATHER_HOP_BYTES;
  }

  uint32_t do_allreduce(Ops &o, const CommView &cm, uint32_t dt,
                        uint32_t func, const uint8_t *src, uint8_t *dst,
                        uint64_t count) {
    uint64_t eb = dtype_bytes(dt);
    uint64_t bytes = count * eb;
    uint32_t rc;
    CollState &st = o.st;
    if (cm.world == 1) {
      o.local([&] { std::memcpy(dst, src, bytes); });
      return NO_ERROR;
    }
    // Tuning-register escape hatch: rendezvous-size payloads up to the
    // ALLREDUCE_COMPOSITION register run the reference's reduce+bcast
    // composition (.c:1878-1887) — kept runtime-selectable (the
    // accl.cpp:1198-1208 posture) so the timing model can arbitrate
    // ring-vs-composition per (size, world); register 0 (default) keeps
    // the measured ring below.
    if (o.rndzv(bytes) && bytes <= st.tun_allred_comp) {
      if ((rc = do_reduce(o, cm, dt, func, src, dst, count, 0))) return rc;
      return do_bcast(o, cm, dst, bytes, 0);
    }
    // Two streamed-eager shapes, both moving the bandwidth-optimal
    // ~2*bytes*(P-1)/P per link (hop payloads are whole chunks as
    // jumbo-segment messages — egr_send pipelines rx-buf/jumbo segments
    // without waiting and the receiver drains them incrementally inside
    // one resumable recv op, the reference's >2-moves-in-flight posture
    // (.c:626-647) without a per-segment op explosion):
    //
    //  - power-of-two worlds: recursive vector halving-doubling
    //    (Rabenseifner) — the same volume in 2*log2(P) latency steps
    //    instead of the ring's 2(P-1). The emulator is scheduling-
    //    latency-bound (single-core CI hosts: each serialized hop pays a
    //    thread wakeup, ~0.5 ms measured — accl_log/rt_stats_*.csv), so
    //    critical-path hop count is what the wall clock sees; on real
    //    wires the same structure is the standard latency-optimal
    //    midsize allreduce.
    //  - other worlds: ring reduce-scatter + ring allgather
    //    (.c:1888-2071's shape).
    //
    // The rendezvous reduce+bcast composition (.c:1878-1887) measured 4x
    // slower than bcast alone at 1 MB / 8 ranks (emu_bench.csv), so it
    // rides the tuning register above instead of a size rule.
    bool pow2 = (cm.world & (cm.world - 1)) == 0;
    bool logp = pow2 && (shape_override == 2 ||
                         (shape_override == 0 &&
                          bytes <= logp_max_bytes(cm.world)));
    if (logp) {
      o.local([&] { std::memcpy(dst, src, bytes); });
      // phase 1: reduce-scatter by recursive halving. Pair (r, r^d)
      // splits the shared window; the rank with bit d clear keeps the
      // lower half. Windows are identical within every pair because
      // they depend only on decisions at higher bits.
      uint64_t lo = 0, hi = count;
      for (uint32_t d = cm.world >> 1; d >= 1; d >>= 1) {
        uint32_t peer = cm.g(cm.rank ^ d);
        uint64_t mid = lo + (hi - lo) / 2;
        uint64_t klo, khi, slo, shi;
        if ((cm.rank & d) == 0) {
          klo = lo; khi = mid; slo = mid; shi = hi;
        } else {
          klo = mid; khi = hi; slo = lo; shi = mid;
        }
        if ((rc = o.send_stream(peer, dst + slo * eb, (shi - slo) * eb)))
          return rc;
        st.tmp.resize((khi - klo) * eb + 1);  // +1: never moves for n=0
        if ((rc = o.recv_stream(peer, st.tmp.data(), (khi - klo) * eb)))
          return rc;
        if ((rc = o.op([&, klo = klo, khi = khi] {
               return combine_buffers(dt, func, dst + klo * eb,
                                      st.tmp.data(), khi - klo);
             })))
          return rc;
        lo = klo; hi = khi;
      }
      // phase 2: allgather by recursive doubling, merging sibling
      // windows in reverse split order. window_at(r, d) replays r's
      // halving decisions down to distance d — the pair's windows are
      // complementary halves of their common parent.
      auto window_at = [&](uint32_t r, uint32_t dstop) {
        uint64_t wlo = 0, whi = count;
        for (uint32_t d = cm.world >> 1; d >= dstop; d >>= 1) {
          uint64_t mid = wlo + (whi - wlo) / 2;
          if ((r & d) == 0) whi = mid; else wlo = mid;
        }
        return std::pair<uint64_t, uint64_t>(wlo, whi);
      };
      for (uint32_t d = 1; d < cm.world; d <<= 1) {
        uint32_t peer = cm.g(cm.rank ^ d);
        auto [plo, phi] = window_at(cm.rank ^ d, d);
        if ((rc = o.send_stream(peer, dst + lo * eb, (hi - lo) * eb)))
          return rc;
        if ((rc = o.recv_stream(peer, dst + plo * eb, (phi - plo) * eb)))
          return rc;
        lo = std::min(lo, plo);
        hi = std::max(hi, phi);
      }
      return NO_ERROR;
    }
    uint64_t bulk = (count + cm.world - 1) / cm.world;
    auto chunk = [&](uint32_t idx) {
      uint64_t lo = std::min<uint64_t>((uint64_t)idx * bulk, count);
      uint64_t hi = std::min<uint64_t>(lo + bulk, count);
      return std::pair<uint64_t, uint64_t>(lo, hi - lo);
    };
    o.local([&] { std::memcpy(dst, src, bytes); });
    uint32_t nxt = cm.g((cm.rank + 1) % cm.world);
    uint32_t prv = cm.g((cm.rank + cm.world - 1) % cm.world);
    st.tmp.resize(bulk * eb + 1);
    // reduce-scatter: hop s sends chunk (rank-1-s) — combined locally at
    // hop s-1 — and combines arriving chunk (rank-2-s), the same
    // derivation as schedules.reduce_scatter_ring
    for (uint32_t s = 0; s + 1 < cm.world; s++) {
      uint32_t sidx = (cm.rank + cm.world - 1 - s) % cm.world;
      uint32_t ridx = (cm.rank + 2 * cm.world - 2 - s) % cm.world;
      auto [slo, sn] = chunk(sidx);
      if ((rc = o.send_stream(nxt, dst + slo * eb, sn * eb))) return rc;
      auto [rlo, rn] = chunk(ridx);
      if ((rc = o.recv_stream(prv, st.tmp.data(), rn * eb))) return rc;
      if ((rc = o.op([&, rlo = rlo, rn = rn] {
             return combine_buffers(dt, func, dst + rlo * eb, st.tmp.data(),
                                    rn);
           })))
        return rc;
    }
    // ring allgather of reduced chunks: hop s relays chunk (rank-s),
    // receiving chunk (rank-1-s) directly into place
    for (uint32_t s = 0; s + 1 < cm.world; s++) {
      uint32_t sidx = (cm.rank + cm.world - s) % cm.world;
      uint32_t ridx = (cm.rank + cm.world - 1 - s) % cm.world;
      auto [slo, sn] = chunk(sidx);
      if ((rc = o.send_stream(nxt, dst + slo * eb, sn * eb))) return rc;
      auto [rlo, rn] = chunk(ridx);
      if ((rc = o.recv_stream(prv, dst + rlo * eb, rn * eb))) return rc;
    }
    return NO_ERROR;
  }

  uint32_t do_reduce_scatter(Ops &o, const CommView &cm, uint32_t dt,
                             uint32_t func, const uint8_t *src, uint8_t *dst,
                             uint64_t count) {
    // count = per-rank output elements; input holds world*count.
    uint64_t eb = dtype_bytes(dt);
    uint64_t bytes = count * eb;
    uint32_t rc;
    CollState &st = o.st;
    if (cm.world == 1) {
      o.local([&] { std::memcpy(dst, src, bytes); });
      return NO_ERROR;
    }
    if (o.rndzv(bytes)) {
      // reduce(count*world) to 0 then scatter (.c:1768-1781); st.full is
      // the composition's intermediate (do_reduce owns st.acc/st.tmp)
      st.full.resize((uint64_t)cm.world * bytes);
      if ((rc = do_reduce(o, cm, dt, func, src, st.full.data(),
                          (uint64_t)count * cm.world, 0)))
        return rc;
      return do_scatter(o, cm, st.full.data(), dst, bytes, 0);
    }
    // eager ring (.c:1782-1850), hop payloads streamed whole
    uint32_t nxt = cm.g((cm.rank + 1) % cm.world);
    uint32_t prv = cm.g((cm.rank + cm.world - 1) % cm.world);
    st.tmp.resize(bytes + 1);
    uint32_t cidx = (cm.rank + cm.world - 1) % cm.world;
    if ((rc = o.send_stream(nxt, src + (uint64_t)cidx * bytes, bytes)))
      return rc;
    for (uint32_t s = 0; s < cm.world - 1; s++) {
      uint32_t idx = (cm.rank + 2 * cm.world - 2 - s) % cm.world;
      if ((rc = o.recv_stream(prv, st.tmp.data(), bytes))) return rc;
      if ((rc = o.op([&] {
             return combine_buffers(dt, func, st.tmp.data(),
                                    src + (uint64_t)idx * bytes, count);
           })))
        return rc;
      if (s + 1 < cm.world - 1 &&
          (rc = o.send_stream(nxt, st.tmp.data(), bytes)))
        return rc;
    }
    o.local([&] { std::memcpy(dst, st.tmp.data(), bytes); });
    return NO_ERROR;
  }

  uint32_t do_alltoall(Ops &o, const CommView &cm, const uint8_t *src,
                       uint8_t *dst, uint64_t bytes) {
    // pairwise rotation exchange (.c:2140-2211)
    uint32_t rc;
    o.local([&] {
      std::memcpy(dst + (uint64_t)cm.rank * bytes,
                  src + (uint64_t)cm.rank * bytes, bytes);
    });
    bool rv = o.rndzv(bytes);
    for (uint32_t k = 1; k < cm.world; k++) {
      uint32_t to = (cm.rank + k) % cm.world;
      uint32_t from = (cm.rank + cm.world - k) % cm.world;
      uint8_t *rptr = dst + (uint64_t)from * bytes;
      if (rv) {
        // post our landing address before sending: every rank's step-k
        // target posted its own at step k, so no addr-wait cycle forms
        if ((rc = o.post(cm.g(from), rptr, bytes))) return rc;
        if ((rc = o.send(cm.g(to), src + (uint64_t)to * bytes, bytes)))
          return rc;
        if ((rc = o.completion(cm.g(from), rptr, bytes))) return rc;
      } else {
        if ((rc = o.send_stream(cm.g(to), src + (uint64_t)to * bytes,
                                bytes)))
          return rc;
        if ((rc = o.recv_stream(cm.g(from), rptr, bytes))) return rc;
      }
    }
    return NO_ERROR;
  }

  uint32_t do_barrier(Ops &o, const CommView &cm) {
    // zero-payload notification gather to 0 + fan-out (.c:2078-2120)
    uint32_t rc;
    if (cm.rank == 0) {
      for (uint32_t i = 1; i < cm.world; i++)
        if ((rc = o.recv(cm.g(i), nullptr, 0))) return rc;
      for (uint32_t i = 1; i < cm.world; i++)
        if ((rc = o.send(cm.g(i), nullptr, 0))) return rc;
    } else {
      if ((rc = o.send(cm.g(0), nullptr, 0))) return rc;
      if ((rc = o.recv(cm.g(0), nullptr, 0))) return rc;
    }
    return NO_ERROR;
  }

  // ----- sequencer main loop (run(), .c:2308-2483) -----

  // Compressed-domain execution (ETH_COMPRESSED on fp32 operands, the
  // default (float32,float16) arithconfig with arith_is_compressed=true,
  // arithconfig.hpp:102-119): cast operands to fp16 scratch, run the
  // whole collective at half wire width, cast the result back.
  uint32_t execute(Call &c) {
    // A wedged rank (accl_rt_kill / ACCL_RT_FAULT_KILL_RANK): every
    // call — in-flight retries included — terminates NOW with the
    // sticky RECEIVE_TIMEOUT word. The terminal path below records the
    // span, so the death leaves a final sticky-retcode record in the
    // trace ring for the host flight recorder to fire on.
    if (killed.load(std::memory_order_acquire)) {
      if (c.cstate) revoke_call_postings(c);
      return RECEIVE_TIMEOUT_ERROR;
    }
    // The firmware caches the communicator addressed by desc word 2 per
    // call (ccl_offload_control.c:2317-2372); malformed tables or calls
    // from a non-member rank fail descriptor decode. The resolved view
    // rides the Call so NOT_READY requeues skip the re-parse.
    if (!c.comm_resolved) {
      if (!resolve_comm(c.desc[2], c.comm)) return DMA_DECODE_ERROR;
      c.comm_resolved = true;
    }
    if (!c.cstate) c.cstate = std::make_shared<CollState>();
    if (!c.cstate->cfg) {
      CollState &st = *c.cstate;
      st.cfg = true;
      st.max_eager = max_eager;
      st.max_rndzv = max_rndzv;
      st.tun_bcast_ranks = tuning(BCAST_FLAT_TREE_MAX_RANKS, 3);
      st.tun_gather_fanin = tuning(GATHER_FLAT_TREE_MAX_FANIN, 2);
      st.tun_gather_count = tuning(GATHER_FLAT_TREE_MAX_COUNT, 32 * 1024);
      st.tun_reduce_ranks = tuning(REDUCE_FLAT_TREE_MAX_RANKS, 4);
      st.tun_reduce_count = tuning(REDUCE_FLAT_TREE_MAX_COUNT, 32 * 1024);
      st.tun_allred_comp = tuning(ALLREDUCE_COMPOSITION_MAX_COUNT, 0);
    }
    if (!c.deadline_set) {
      c.deadline = std::chrono::steady_clock::now() +
                   std::chrono::milliseconds(timeout_ms);
      c.deadline_set = true;
      std::lock_guard<std::mutex> g(rx_mu);
      c.defer0 = last_defer.count;
    }
    uint32_t step_before = c.current_step;
    uint64_t off_before = c.cstate->off;
    uint32_t rc = execute_guts(c);
    if (rc == NOT_READY) {
      // per-op timeout semantics (each blocking primitive used to get a
      // fresh timeout_ms budget): any progress re-arms the deadline, so
      // only a genuinely stalled op times the call out
      if (c.current_step != step_before || c.cstate->off != off_before) {
        c.deadline = std::chrono::steady_clock::now() +
                     std::chrono::milliseconds(timeout_ms);
        return rc;
      }
      if (std::chrono::steady_clock::now() > c.deadline) {
        if (debug_on)
          fprintf(stderr, "[r%u] call timeout scenario=%u step=%u\n", rank,
                  c.desc[0], c.current_step);
        {
          // a strict-recv head mismatch softened into a defer
          // (head_is_claimable) is the likeliest cause of an otherwise
          // bare timeout: echo the recorded mismatch so the protocol
          // fault stays diagnosable. Gated on defers recorded DURING
          // this call (> defer0) — an earlier call's resolved deferral
          // must not be reported as this timeout's cause.
          std::lock_guard<std::mutex> g(rx_mu);
          if (last_defer.count > c.defer0) {
            fprintf(stderr,
                    "[r%u] RECEIVE_TIMEOUT detail scenario=%u step=%u: "
                    "%llu deferred head mismatch(es); last from r%u "
                    "head(tag=%u seqn=%u msg=%llu off=%llu) vs "
                    "wanted(tag=%u msg=%llu) fault=%s(0x%x)\n",
                    rank, c.desc[0], c.current_step,
                    (unsigned long long)last_defer.count, last_defer.src,
                    last_defer.head_tag, last_defer.head_seqn,
                    (unsigned long long)last_defer.head_msg,
                    (unsigned long long)last_defer.head_off,
                    last_defer.want_tag,
                    (unsigned long long)last_defer.want_msg,
                    last_defer.code == DMA_TAG_MISMATCH_ERROR
                        ? "DMA_TAG_MISMATCH"
                        : last_defer.code == DMA_SIZE_ERROR
                              ? "DMA_SIZE_ERROR"
                              : "NONE",
                    last_defer.code);
            // the span drained through accl_rt_trace_read carries the
            // original fault code alongside the RECEIVE_TIMEOUT retcode
            c.trace_detail = last_defer.code;
          }
        }
        revoke_call_postings(c);
        return RECEIVE_TIMEOUT_ERROR;
      }
    } else if (rc != NO_ERROR) {
      // terminal error mid-collective: also drop outstanding postings
      revoke_call_postings(c);
    }
    return rc;
  }

  // Revoke the addresses THIS call posted and never saw complete, so a
  // late write cannot land in memory the caller is about to reuse. A
  // write that landed at the deadline edge (between the failing poll and
  // this revocation) already consumed the posting: purge its completion
  // too, or a future recv posting the same (src, vaddr, bytes, tag)
  // would be falsely satisfied by stale data.
  void revoke_call_postings(Call &c) {
    std::unique_lock<std::mutex> g(rndzv_mu);
    for (auto &pa : c.cstate->posted) {
      revoke_posted_locked(g, pa.src, pa.vaddr, pa.bytes, pa.tag);
      for (auto it = done_q.begin(); it != done_q.end();) {
        // either-side wildcard, matching the completion seekers: a
        // TAG_ANY write completing at the deadline edge of a tagged
        // posting must be purged too, or a future recv reusing the
        // buffer would be falsely satisfied by stale data
        if (it->src == pa.src && it->vaddr == pa.vaddr &&
            it->bytes == pa.bytes &&
            (pa.tag == TAG_ANY || it->tag == TAG_ANY ||
             it->tag == pa.tag))
          it = done_q.erase(it);
        else
          ++it;
      }
    }
    c.cstate->posted.clear();
  }

  uint32_t execute_guts(Call &c) {
    const CommView &cm = c.comm;
    constexpr uint32_t ETH_COMPRESSED = 8;
    uint32_t comp_flags = c.desc[7];
    if ((comp_flags & ETH_COMPRESSED) && c.dtype == ACCL_DT_FLOAT32) {
      uint32_t scenario = c.desc[0];
      uint64_t count = c.desc[1];
      uint64_t in_elems = count, out_elems = count;
      switch (scenario) {
        case SC_SCATTER: in_elems = count * cm.world; break;
        case SC_REDUCE_SCATTER: in_elems = count * cm.world; break;
        case SC_ALLTOALL: in_elems = count * cm.world; out_elems = count * cm.world; break;
        case SC_GATHER: out_elems = count * cm.world; break;
        case SC_ALLGATHER: out_elems = count * cm.world; break;
        default: break;
      }
      // The wire dtype comes from the descriptor's arithconfig row (word
      // 6; exchmem layout arithconfig.py: [unc_bytes, cmp_bytes,
      // ratio_log, compressor, decompressor, is_compressed, lanes...]):
      // compressor 2 = fp32->bf16 (TPU-native extension row), anything
      // else the default fp16 pair — the dtype-pair-generic contract of
      // the reference arithconfig (arithconfig.hpp:102-119).
      if (c.cstate->wire_bf16 < 0) {
        // snapshot on first pass, like the protocol/tuning snapshot: a
        // row rewrite between requeue passes must not flip the wire
        // dtype of a partially-executed call
        uint32_t arcfg_addr = c.desc[6];
        // compressor lanes > 3 are the blockwise-quantized wire
        // (arithconfig.py lanes 4/5: int8 codes + per-block scales);
        // this data plane has no quantized kernel — degrading to a
        // cast would silently put 2 B/elem on a wire the caller sized
        // at ~1 B, so the call is rejected, not reinterpreted
        if (arcfg_addr != 0 && arcfg_addr + 16 < EXCHMEM_BYTES &&
            rd(arcfg_addr + 4 * 3) > 3)
          return COMPRESSION_ERROR;
        c.cstate->wire_bf16 =
            (arcfg_addr != 0 && arcfg_addr + 16 < EXCHMEM_BYTES &&
             rd(arcfg_addr + 4 * 3) == 2)
                ? 1
                : 0;
      }
      bool bf16_wire = c.cstate->wire_bf16 == 1;
      uint16_t (*cast_to)(float) = bf16_wire ? float_to_bf16 : float_to_half;
      float (*cast_from)(uint16_t) =
          bf16_wire ? bf16_to_float : half_to_float;
      auto to_h = [&](const float *src, std::vector<uint16_t> &dst,
                      uint64_t n) {
        dst.resize(n);
        for (uint64_t i = 0; i < n; i++) dst[i] = cast_to(src[i]);
      };
      if (c.op0 && !c.c16_op0) {
        c.c16_op0 = std::make_shared<std::vector<uint16_t>>();
        to_h((const float *)c.op0, *c.c16_op0, in_elems);
      }
      if (c.op1 && !c.c16_op1) {
        c.c16_op1 = std::make_shared<std::vector<uint16_t>>();
        to_h((const float *)c.op1, *c.c16_op1, in_elems);
      }
      if (c.res && !c.c16_res) {
        c.c16_res = std::make_shared<std::vector<uint16_t>>(
            std::max(in_elems, out_elems));
      }
      Call inner = c;  // shares the scratch shared_ptrs
      inner.dtype = bf16_wire ? ACCL_DT_BFLOAT16 : ACCL_DT_FLOAT16;
      inner.desc[7] = comp_flags & ~ETH_COMPRESSED;
      if (c.c16_op0) inner.op0 = c.c16_op0->data();
      if (c.c16_op1) inner.op1 = c.c16_op1->data();
      if (c.c16_res) inner.res = c.c16_res->data();
      uint32_t rc = execute_inner(inner, cm);
      // preserve ALL resumption state (current_step AND the armed
      // deadline) across NOT_READY requeues
      c.current_step = inner.current_step;
      c.deadline = inner.deadline;
      c.deadline_set = inner.deadline_set;
      if (rc == NOT_READY) return NOT_READY;
      // only ranks that own the output write it back: gather/reduce
      // deliver to root alone (non-root recvbufs stay untouched, matching
      // the uncompressed path)
      uint32_t root = c.desc[3];
      bool owns_res =
          !(scenario == SC_GATHER || scenario == SC_REDUCE) || root == cm.rank;
      if (c.res && rc == NO_ERROR && owns_res) {
        float *dst = (float *)c.res;
        for (uint64_t i = 0; i < out_elems; i++)
          dst[i] = cast_from((*c.c16_res)[i]);
      }
      // bcast mutates op0 on receivers only: compression is wire-only, so
      // the root's full-precision source stays untouched (reference
      // semantics)
      if (scenario == SC_BCAST && c.op0 && rc == NO_ERROR && root != cm.rank) {
        float *dst = (float *)c.op0;
        for (uint64_t i = 0; i < in_elems; i++)
          dst[i] = cast_from((*c.c16_op0)[i]);
      }
      return rc;
    }
    return execute_inner(c, cm);
  }

  uint32_t execute_inner(Call &c, const CommView &cm) {
    uint32_t scenario = c.desc[0];
    uint64_t count = c.desc[1];
    uint32_t root = c.desc[3];
    uint32_t func = c.desc[4];
    uint32_t tag = c.desc[5];
    uint64_t eb = dtype_bytes(c.dtype);
    uint64_t bytes = count * eb;
    auto *op0 = (const uint8_t *)c.op0;
    auto *op1 = (const uint8_t *)c.op1;
    auto *res = (uint8_t *)c.res;
    // rooted collectives: the root is communicator-relative and must
    // exist, or the group hangs waiting on a root nobody is
    switch (scenario) {
      case SC_BCAST: case SC_SCATTER: case SC_GATHER: case SC_REDUCE:
        if (root >= cm.world) return DMA_DECODE_ERROR;
        break;
      default:
        break;
    }
    switch (scenario) {
      case SC_NOP:
        return NO_ERROR;
      case SC_CONFIG:
        switch (func) {
          case 2: timeout_ms = count; return NO_ERROR;      // set_timeout
          case 3: max_eager = (uint32_t)count; return NO_ERROR;
          case 4: max_rndzv = count; return NO_ERROR;
          default: return NO_ERROR;  // reset/enable_pkt are no-ops here
        }
      case SC_COPY:
        std::memcpy(res, op0, bytes);
        return NO_ERROR;
      case SC_COMBINE: {
        std::memcpy(res, op0, bytes);
        return combine_buffers(c.dtype, func, res, op1, count);
      }
      default:
        break;
    }
    // Everything below is a resumable op sequence over the call's state
    // machine (the firmware retry contract for every scenario,
    // ccl_offload_control.c:2308-2483).
    Ops o{*this, c, *c.cstate, tag};
    switch (scenario) {
      case SC_SEND:
        // root_src_dst is the destination rank, communicator-relative
        // (reference send semantics)
        if (root >= cm.world) return DMA_DECODE_ERROR;
        return o.send(cm.g(root), op0, bytes);
      case SC_RECV:
        // root_src_dst is the source rank. Non-strict tag matching: a
        // head-tag mismatch stays NOT_READY because another parked recv
        // may legally consume the head segment first.
        if (root >= cm.world) return DMA_DECODE_ERROR;
        return o.recv(cm.g(root), res, bytes, /*strict=*/false);
      case SC_BCAST:
        return do_bcast(o, cm, (uint8_t *)op0, bytes, root);
      case SC_SCATTER:
        return do_scatter(o, cm, op0, res, bytes, root);
      case SC_GATHER:
        return do_gather(o, cm, op0, res, bytes, root);
      case SC_ALLGATHER:
        return do_allgather(o, cm, op0, res, bytes);
      case SC_REDUCE:
        return do_reduce(o, cm, c.dtype, func, op0, res, count, root);
      case SC_ALLREDUCE:
        return do_allreduce(o, cm, c.dtype, func, op0, res, count);
      case SC_REDUCE_SCATTER:
        return do_reduce_scatter(o, cm, c.dtype, func, op0, res, count);
      case SC_ALLTOALL:
        return do_alltoall(o, cm, op0, res, bytes);
      case SC_BARRIER:
        return do_barrier(o, cm);
      default:
        return COLLECTIVE_NOT_IMPLEMENTED;
    }
  }

  // Collectives serialize per communicator (see inflight_comms); p2p and
  // local scenarios have call identity (tags / no wire) and stay freely
  // concurrent — the round-2 parked-recv semantics.
  static bool comm_serialized(uint32_t scenario) {
    switch (scenario) {
      case SC_BCAST: case SC_SCATTER: case SC_GATHER: case SC_REDUCE:
      case SC_ALLGATHER: case SC_ALLREDUCE: case SC_REDUCE_SCATTER:
      case SC_BARRIER: case SC_ALLTOALL:
        return true;
      default:
        return false;
    }
  }

  void sequencer() {
    while (!stop.load()) {
      Call c;
      {
        std::unique_lock<std::mutex> lk(call_mu);
        auto pick = [&]() -> bool {
          // prefer fresh calls (run() order), skipping collectives whose
          // communicator already has one in flight; then parked retries
          for (auto it = call_q.begin(); it != call_q.end(); ++it) {
            if (comm_serialized(it->desc[0])) {
              auto f = inflight_comms.find(it->desc[2]);
              if (f != inflight_comms.end() && f->second > 0) continue;
            }
            c = std::move(*it);
            call_q.erase(it);
            return true;
          }
          if (!retry_q.empty()) {
            c = std::move(retry_q.front());
            retry_q.pop_front();
            return true;
          }
          return false;
        };
        call_cv.wait(lk, [&] { return stop.load() || pick(); });
        if (stop.load()) return;
        if (!c.started) {
          c.started = true;
          if (comm_serialized(c.desc[0])) inflight_comms[c.desc[2]]++;
        }
      }
      if (debug_on && c.desc[0] != SC_RECV)
        fprintf(stderr, "[r%u] exec scenario=%u count=%u\n", rank, c.desc[0], c.desc[1]);
      // ACCL_RT_FAULT_KILL_RANK countdown: after N completed data-plane
      // calls the rank wedges permanently (config/nop are host plumbing
      // and never count — the soak kills mid data stream)
      if (kill_after_calls >= 0 && !killed.load(std::memory_order_relaxed) &&
          c.desc[0] != SC_CONFIG && c.desc[0] != SC_NOP && !c.started_counted) {
        c.started_counted = true;
        if (kill_after_calls == 0)
          wedge();
        else
          kill_after_calls--;
      }
      uint64_t ev0 = rx_events.load(std::memory_order_acquire);
      stat_passes++;
      uint32_t rc = execute(c);
      if (debug_on && c.desc[0] != SC_RECV)
        fprintf(stderr, "[r%u] done scenario=%u rc=%u\n", rank, c.desc[0], rc);
      if (rc == NOT_READY) {
        {
          std::lock_guard<std::mutex> lk(call_mu);
          retry_q.push_back(std::move(c));
        }
        // park until a NEW rx event (progress needs a segment/address/
        // completion, not a re-poll) — but only if none arrived since
        // this pass started, or the arrival gap costs a full timeout
        std::unique_lock<std::mutex> lk(rx_mu);
        if (rx_events.load(std::memory_order_acquire) == ev0) {
          stat_parks++;
          auto t0 = std::chrono::steady_clock::now();
          // The event-counter predicate makes this wait race-free (any
          // rx progress notifies rx_cv and bumps rx_events), so the cap
          // is a pure lost-wakeup backstop. 200 us proved far too eager
          // on single-core CI hosts: with P sequencers parked, 5k
          // spurious wakeups/s stole the core from the threads moving
          // data (rt_stats parks ~= seek_miss signature); 2 ms keeps
          // the backstop while the predicate does the real waking.
          cv_wait_for(rx_cv, lk, std::chrono::milliseconds(2), [&] {
            return stop.load() ||
                   rx_events.load(std::memory_order_acquire) != ev0;
          });
          stat_park_ns += std::chrono::duration_cast<std::chrono::nanoseconds>(
                              std::chrono::steady_clock::now() - t0)
                              .count();
        }
        continue;
      }
      // terminal (success OR error): any stream ownership this call holds
      // must not outlive it — its CollState is about to be destroyed
      if (c.cstate) release_rx_ownership(c.cstate.get());
      if (trace_on) record_span(c, rc);
      auto dur = std::chrono::steady_clock::now() - c.t_start;
      if (comm_serialized(c.desc[0])) {
        // release the communicator's serialization slot: a deferred
        // same-comm call becomes runnable on the next pick()
        std::lock_guard<std::mutex> lk(call_mu);
        auto f = inflight_comms.find(c.desc[2]);
        if (f != inflight_comms.end() && --f->second == 0)
          inflight_comms.erase(f);
      }
      {
        std::lock_guard<std::mutex> lk(comp_mu);
        auto &comp = completions[c.handle];
        comp->retcode = rc;
        comp->duration_ns =
            std::chrono::duration_cast<std::chrono::nanoseconds>(dur).count();
        comp->done.store(1);
      }
      comp_cv.notify_all();
      wr(RETCODE, rc);
    }
  }
};

// glibc's std::mutex is zero-initialized — no pthread_mutex_init call —
// so ThreadSanitizer never observes a mutex's construction. If the heap
// block previously hosted a pthread mutex that WAS destroyed (the Python
// host destroys them constantly), the stale "destroyed" sync state
// suppresses lock-based happens-before and every guarded access pair
// reports as a false race. Announce each runtime mutex's birth.
#if defined(__SANITIZE_THREAD__)
extern "C" void __tsan_mutex_create(void *addr, unsigned flags);
static void tsan_announce_mutexes(accl_rt *rt) {
  for (std::mutex *m :
       {&rt->exch_mu, &rt->hello_mu, &rt->rx_mu, &rt->rndzv_mu, &rt->call_mu,
        &rt->comp_mu, &rt->trace_mu, &rt->fault_mu, &rt->rely_mu,
        &rt->rng_mu})
    __tsan_mutex_create(m, 0);
}
#else
static void tsan_announce_mutexes(accl_rt *) {}
#endif

// ---------------------------------------------------------------------------
// C API
// ---------------------------------------------------------------------------

extern "C" {

accl_rt_t *accl_rt_create_ex(uint32_t world, uint32_t rank,
                             const uint16_t *ports, uint32_t n_rx_bufs,
                             uint32_t rx_buf_bytes, uint32_t max_eager_bytes,
                             uint64_t max_rndzv_bytes, uint32_t transport) {
  auto *rt = new accl_rt();
  tsan_announce_mutexes(rt);
  rt->world = world;
  rt->rank = rank;
  rt->rx_buf_bytes = rx_buf_bytes;
  rt->max_eager = max_eager_bytes;
  rt->max_rndzv = max_rndzv_bytes;
  rt->rx_slots.resize(n_rx_bufs);
  rt->base_rx_slots = n_rx_bufs;
  for (size_t i = 0; i < rt->rx_slots.size(); i++) rt->idle_q.push_back(i);
  rt->wr(IDCODE, 0xACC17B00u);
  if (const char *s = getenv("ACCL_RT_SHAPE")) {
    if (!strcmp(s, "ring")) rt->shape_override = 1;
    else if (!strcmp(s, "logp")) rt->shape_override = 2;
  }
  if (const char *s = getenv("ACCL_RT_FAULT_DELAY_TAIL_MS"))
    rt->fault_delay_tail_ms = atoi(s);
  if (const char *s = getenv("ACCL_RT_FAULT_DROP_TAIL"))
    rt->fault_drop_tail = atoi(s) != 0;
  if (const char *s = getenv("ACCL_RT_FAULT_KILL_RANK")) {
    if ((uint32_t)atoi(s) == rank) {
      rt->kill_after_calls = 0;
      if (const char *a = getenv("ACCL_RT_FAULT_KILL_AFTER"))
        rt->kill_after_calls = atoi(a) < 0 ? 0 : atoi(a);
    }
  }
  if (const char *s = getenv("ACCL_RT_WAN_ALPHA_US"))
    rt->wan_alpha_us = (uint32_t)atoi(s);
  if (const char *s = getenv("ACCL_RT_WAN_GBPS")) {
    double g = atof(s);
    if (g > 0) rt->wan_bytes_per_us = g * 1000.0;  // 1 GB/s = 1000 B/us
  }
  if (const char *s = getenv("ACCL_RT_TRACE"))
    rt->trace_on = atoi(s) != 0;
  if (const char *s = getenv("ACCL_RT_TRACE_CAP")) {
    long cap = atol(s);
    if (cap > 0) rt->trace_cap = (size_t)cap;
  }
  // reliability sublayer + seeded chaos fault model (see the struct's
  // rely block). ACCL_RT_RELY is world-uniform by contract.
  rt->debug_on = getenv("ACCL_RT_DEBUG") != nullptr;
  if (const char *s = getenv("ACCL_RT_RELY")) rt->rely_on = atoi(s) != 0;
  if (const char *s = getenv("ACCL_RT_RELY_NACK_MAX")) {
    int v = atoi(s);
    if (v > 0) rt->nack_max = (uint32_t)v;
  }
  if (const char *s = getenv("ACCL_RT_RELY_BUF_BYTES")) {
    long long v = atoll(s);
    if (v > 0) rt->retx_budget_bytes = (uint64_t)v;
  }
  {
    auto pct = [](const char *name) {
      const char *s = getenv(name);
      double v = s ? atof(s) : 0.0;
      return v > 0 ? v : 0.0;
    };
    rt->fault_loss_pct = pct("ACCL_RT_FAULT_LOSS_PCT");
    rt->fault_corrupt_pct = pct("ACCL_RT_FAULT_CORRUPT_PCT");
    rt->fault_dup_pct = pct("ACCL_RT_FAULT_DUP_PCT");
    rt->fault_reorder_pct = pct("ACCL_RT_FAULT_REORDER_PCT");
    rt->fault_pct_armed = rt->fault_loss_pct + rt->fault_corrupt_pct +
                              rt->fault_dup_pct + rt->fault_reorder_pct >
                          0;
    uint64_t seed = 1;
    if (const char *s = getenv("ACCL_RT_FAULT_SEED"))
      seed = strtoull(s, nullptr, 10);
    // distinct deterministic stream per (seed, rank)
    rt->rng_state =
        (seed + 0x9E3779B97F4A7C15ull) * (rank + 0x632BE59BD9B4E019ull);
  }
  rt->rely_wire = rt->rely_on &&
                  (transport != ACCL_RT_TRANSPORT_LOCAL ||
                   rt->fault_pct_armed);
  // ----- wire shape: legacy cost model, lanes, TX batching ----------------
  // ACCL_RT_WIRE_LEGACY=1: pre-vectored transmit (per-frame syscalls,
  // coalescing copies) — the A/B baseline for the scatter-gather path.
  // Legacy implies the single-lane
  // bit-identical wire.
  if (const char *s = getenv("ACCL_RT_WIRE_LEGACY"))
    rt->legacy_wire = atoi(s) != 0;
  // ACCL_RT_LANES (session transport only): per-peer lanes. Lane 0
  // carries small messages and all control traffic; lane 1 carries bulk
  // messages >= ACCL_RT_LANE_BULK_BYTES, so a jumbo frame in flight
  // cannot head-of-line-block a small message. Default 1 = the legacy
  // single-stream wire, bit-identical framing.
  if (transport == ACCL_RT_TRANSPORT_TCP && !rt->legacy_wire) {
    if (const char *s = getenv("ACCL_RT_LANES")) {
      int v = atoi(s);
      if (v > (int)WIRE_MAX_LANES) v = WIRE_MAX_LANES;
      if (v >= 1) rt->n_lanes = (uint32_t)v;
    }
  }
  if (const char *s = getenv("ACCL_RT_LANE_BULK_BYTES")) {
    long long v = atoll(s);
    if (v > 0) rt->lane_bulk_bytes = (uint64_t)v;
  }
  // TX batching (many frames -> one vectored syscall) stays OFF where
  // per-frame emission is part of the contract: the legacy cost model,
  // the seeded chaos stream (frame-order determinism), the WAN shaper
  // (per-frame charges), the in-process POE (delivery IS the call), and
  // the one-shot tail levers (their wire-order asserts reason about
  // single frames).
  rt->tx_batch_on = !rt->legacy_wire && !rt->fault_pct_armed &&
                    transport != ACCL_RT_TRANSPORT_LOCAL &&
                    rt->wan_alpha_us == 0 && rt->wan_bytes_per_us <= 0 &&
                    rt->fault_delay_tail_ms == 0 && !rt->fault_drop_tail;
  // per-stream state: one seqn stream per (peer, lane) sid
  const uint32_t n_streams = world * rt->n_lanes;
  rt->inbound_seq.assign(n_streams, 0);
  rt->outbound_seq.assign(n_streams, 0);
  rt->src_valid_count.assign(n_streams, 0);
  rt->retx.resize(n_streams);
  rt->want.assign(n_streams, WantState{});
  rt->acked_upto.assign(n_streams, 0);
  rt->last_ack_t.assign(n_streams, std::chrono::steady_clock::now());
  auto start_rely = [](accl_rt *r) {
    if (r->rely_wire) r->rely_thread = std::thread([r] { r->rely_loop(); });
  };
  acclw::PoeConfig pc;
  pc.world = world;
  pc.rank = rank;
  pc.ports = ports;
  pc.lanes = rt->n_lanes;
  pc.legacy_wire = rt->legacy_wire;
  pc.debug = rt->debug_on;
  if ((rt->wan_alpha_us > 0 || rt->wan_bytes_per_us > 0) &&
      transport != ACCL_RT_TRANSPORT_LOCAL)
    pc.shaper = [rt](size_t payload_len) { rt->wan_charge(payload_len); };

  if (transport == ACCL_RT_TRANSPORT_LOCAL) {
    // intra-process POE: no sockets, no rx threads — the sender's
    // thread delivers straight into the peer runtime's sink.
    // Bring-up IS the registry: send_frames waits for a peer's entry.
    rt->local_mode = true;
    rt->poe = acclw::make_local_poe(pc);
    if (!rt->poe->connect(rt)) {
      delete rt;  // port collision: refuse rather than misroute
      return nullptr;
    }
    rt->seq_thread = std::thread([rt] { rt->sequencer(); });
    start_rely(rt);
    return rt;
  }

  if (transport == ACCL_RT_TRANSPORT_UDP) {
    // sessionless datagram POE: one SOCK_DGRAM socket, no connections.
    // Segment must fit one datagram with its header.
    if (rt->rx_buf_bytes > 60000) rt->rx_buf_bytes = 60000;
    rt->udp_mode = true;
    // hello state must exist BEFORE connect: the rx thread it spawns
    // can deliver a peer's hello immediately
    rt->hello_seen.assign(world, false);
    rt->hello_seen[rank] = true;
    rt->poe = acclw::make_udp_poe(pc);
    if (!rt->poe->connect(rt)) {
      delete rt;  // bind failure
      return nullptr;
    }
    // bring-up handshake: solicit hellos until every peer answered
    // (datagrams sent before a peer binds are simply lost, so re-solicit)
    auto deadline = std::chrono::steady_clock::now() + std::chrono::seconds(30);
    for (;;) {
      std::vector<uint32_t> missing;
      {
        std::lock_guard<std::mutex> g(rt->hello_mu);
        for (uint32_t i = 0; i < world; i++)
          if (!rt->hello_seen[i]) missing.push_back(i);
      }
      if (missing.empty()) break;
      if (std::chrono::steady_clock::now() > deadline) {
        accl_rt_destroy(rt);
        return nullptr;
      }
      for (uint32_t i : missing)
        rt->frame_out(i, MSG_HELLO, 0, 0, 0, 0, nullptr, 0);
      std::unique_lock<std::mutex> lk(rt->hello_mu);
      cv_wait_for(rt->hello_cv, lk, std::chrono::milliseconds(50));
    }
    rt->seq_thread = std::thread([rt] { rt->sequencer(); });
    start_rely(rt);
    return rt;
  }

  // session POE: full TCP mesh, one ordered stream per (peer, lane)
  rt->poe = acclw::make_tcp_poe(pc);
  if (!rt->poe->connect(rt)) {
    accl_rt_destroy(rt);
    return nullptr;
  }
  rt->seq_thread = std::thread([rt] { rt->sequencer(); });
  start_rely(rt);
  return rt;
}

accl_rt_t *accl_rt_create(uint32_t world, uint32_t rank,
                          const uint16_t *ports, uint32_t n_rx_bufs,
                          uint32_t rx_buf_bytes, uint32_t max_eager_bytes,
                          uint64_t max_rndzv_bytes) {
  return accl_rt_create_ex(world, rank, ports, n_rx_bufs, rx_buf_bytes,
                           max_eager_bytes, max_rndzv_bytes,
                           ACCL_RT_TRANSPORT_TCP);
}

void accl_rt_destroy(accl_rt_t *rt) {
  rt->stop.store(true);
  rt->call_cv.notify_all();
  rt->rx_cv.notify_all();
  rt->rndzv_cv.notify_all();
  rt->hello_cv.notify_all();
  // tear the wire down first: begin_shutdown revokes the sockets and
  // unblocks the POE's rx loops (shutdown()/self-poke/registry
  // deregistration) — senders see the revoked fds and fail fast
  if (rt->poe) rt->poe->begin_shutdown();
  // reap the runtime's own sender threads BEFORE Poe::join closes the
  // revoked fds: the rely/sequencer threads may still be inside a
  // send syscall on an fd they loaded before revocation, and closing
  // under them would hand the descriptor number to a concurrent open
  if (rt->seq_thread.joinable()) rt->seq_thread.join();
  if (rt->rely_thread.joinable()) rt->rely_thread.join();
  {
    std::lock_guard<std::mutex> g(rt->fault_mu);
    for (auto &t : rt->fault_threads)
      if (t.joinable()) t.join();
  }
  // now reap the rx loops and close the deferred fds — after this no
  // sink call is in flight
  if (rt->poe) rt->poe->join();
  if (getenv("ACCL_RT_STATS"))
    fprintf(stderr,
            "[r%u] stats: passes=%llu parks=%llu park_ms=%.1f "
            "seek_hit=%llu seek_miss=%llu\n",
            rt->rank, (unsigned long long)rt->stat_passes.load(),
            (unsigned long long)rt->stat_parks.load(),
            rt->stat_park_ns.load() / 1e6,
            (unsigned long long)rt->stat_seek_hit.load(),
            (unsigned long long)rt->stat_seek_miss.load());
  delete rt;
}

int64_t accl_rt_start(accl_rt_t *rt, const uint32_t desc[15],
                      uint32_t data_type, void *op0, void *op1, void *res) {
  Call c;
  std::memcpy(c.desc, desc, 15 * 4);
  c.dtype = data_type;
  c.op0 = op0;
  c.op1 = op1;
  c.res = res;
  c.t_start = std::chrono::steady_clock::now();
  if (rt->trace_on) {
    // counter-snapshot base for the span's per-call deltas (global
    // sequencer activity over this call's lifetime)
    c.ctr0[0] = rt->stat_passes.load();
    c.ctr0[1] = rt->stat_parks.load();
    c.ctr0[2] = rt->stat_seek_hit.load();
    c.ctr0[3] = rt->stat_seek_miss.load();
  }
  int64_t h;
  {
    std::lock_guard<std::mutex> lk(rt->comp_mu);
    h = rt->next_handle++;
    rt->completions[h] = std::make_shared<Completion>();
  }
  c.handle = h;
  {
    std::lock_guard<std::mutex> lk(rt->call_mu);
    rt->call_q.push_back(std::move(c));
  }
  rt->call_cv.notify_all();
  return h;
}

int accl_rt_test(accl_rt_t *rt, int64_t handle) {
  std::lock_guard<std::mutex> lk(rt->comp_mu);
  auto it = rt->completions.find(handle);
  return it != rt->completions.end() && it->second->done.load();
}

int accl_rt_wait(accl_rt_t *rt, int64_t handle, uint64_t timeout_ms) {
  std::unique_lock<std::mutex> lk(rt->comp_mu);
  auto it = rt->completions.find(handle);
  if (it == rt->completions.end()) return 0;
  auto comp = it->second;
  auto pred = [&] { return comp->done.load() != 0; };
  if (timeout_ms == 0) {
    rt->comp_cv.wait(lk, pred);
    return 1;
  }
  return cv_wait_for(rt->comp_cv, lk, std::chrono::milliseconds(timeout_ms),
                     pred)
             ? 1
             : 0;
}

uint32_t accl_rt_retcode(accl_rt_t *rt, int64_t handle) {
  std::lock_guard<std::mutex> lk(rt->comp_mu);
  auto it = rt->completions.find(handle);
  return it == rt->completions.end() ? 0 : it->second->retcode;
}

uint64_t accl_rt_duration_ns(accl_rt_t *rt, int64_t handle) {
  std::lock_guard<std::mutex> lk(rt->comp_mu);
  auto it = rt->completions.find(handle);
  return it == rt->completions.end() ? 0 : it->second->duration_ns;
}

/* Drop a completed call's bookkeeping (call after reading retcode and
 * duration) so long-lived ranks do not accumulate completion records. */
void accl_rt_release(accl_rt_t *rt, int64_t handle) {
  std::lock_guard<std::mutex> lk(rt->comp_mu);
  rt->completions.erase(handle);
}

uint32_t accl_rt_read(accl_rt_t *rt, uint32_t addr) { return rt->rd(addr); }

// Permanently wedge a rank (see the ACCL_RT_FAULT_KILL_RANK lever): the
// programmatic kill the resilience tests fire mid-stream. Idempotent.
void accl_rt_kill(accl_rt_t *rt) { rt->wedge(); }

// Reconfiguration fence (see accl_rt::flush_rx): drain stale frames of
// the old membership's aborted collectives before the recovery
// communicator's first call. Quiescent caller contract.
void accl_rt_flush_rx(accl_rt_t *rt) { rt->flush_rx(); }

// Cumulative sequencer counters (execute passes, event-counter parks,
// nanoseconds parked, rx-seek hits/misses): the always-on form of the
// ACCL_RT_STATS destroy-time dump, so callers can profile phases of a
// live run — the observability sibling of the per-call PERFCNT word.
void accl_rt_get_stats(accl_rt_t *rt, uint64_t out[5]) {
  out[0] = rt->stat_passes.load();
  out[1] = rt->stat_parks.load();
  out[2] = rt->stat_park_ns.load();
  out[3] = rt->stat_seek_hit.load();
  out[4] = rt->stat_seek_miss.load();
}

// Versioned counter surface (acclrt.h ACCL_RT_STAT2_*): the old 5-word
// accl_rt_get_stats stays ABI-stable above; this one carries the wire-
// health counters too and returns the total count available, so a
// caller built against an older header reads the prefix it knows.
size_t accl_rt_get_stats2(accl_rt_t *rt, uint64_t *out, size_t cap) {
  const uint64_t vals[ACCL_RT_STATS2_COUNT] = {
      rt->stat_passes.load(),      rt->stat_parks.load(),
      rt->stat_park_ns.load(),     rt->stat_seek_hit.load(),
      rt->stat_seek_miss.load(),   rt->stat_tx_frames.load(),
      rt->stat_rx_frames.load(),   rt->stat_crc_drops.load(),
      rt->stat_dup_drops.load(),   rt->stat_retx_sent.load(),
      rt->stat_retx_miss.load(),   rt->stat_nack_sent.load(),
      rt->stat_nack_rx.load(),     rt->stat_ack_sent.load(),
      rt->stat_ack_rx.load(),      rt->stat_rndzv_drops.load(),
      rt->stat_inj_loss.load(),    rt->stat_inj_corrupt.load(),
      rt->stat_inj_dup.load(),     rt->stat_inj_reorder.load(),
      rt->stat_rely_ns.load(),
      rt->poe ? rt->poe->tx_syscalls() : 0,
      rt->poe ? rt->poe->tx_batched() : 0,
  };
  size_t n = cap < ACCL_RT_STATS2_COUNT ? cap : (size_t)ACCL_RT_STATS2_COUNT;
  for (size_t i = 0; i < n; i++) out[i] = vals[i];
  return ACCL_RT_STATS2_COUNT;
}

void accl_rt_write(accl_rt_t *rt, uint32_t addr, uint32_t value) {
  rt->wr(addr, value);
}

// Drain the device-resident trace ring, oldest first (see acclrt.h).
size_t accl_rt_trace_read(accl_rt_t *rt, accl_rt_span_t *out, size_t cap,
                          uint64_t *dropped) {
  std::lock_guard<std::mutex> g(rt->trace_mu);
  if (dropped) *dropped = rt->trace_dropped;
  size_t n = 0;
  while (n < cap && !rt->trace_q.empty()) {
    out[n++] = rt->trace_q.front();
    rt->trace_q.pop_front();
  }
  return n;
}

// Snapshot of the eager rx ring (the reference's dump_eager_rx_buffers,
// accl.cpp:964-1012: one line per spare-buffer descriptor with status and
// the last-landed header fields). Writes a NUL-terminated report into out
// (truncated at cap); returns the untruncated length a la snprintf.
size_t accl_rt_dump_rxbufs(accl_rt_t *rt, char *out, size_t cap) {
  std::string s;
  {
    std::lock_guard<std::mutex> g(rt->rx_mu);
    s += "eager rx ring: " + std::to_string(rt->rx_slots.size()) +
         " slots (configured " + std::to_string(rt->base_rx_slots) +
         "), " + std::to_string(rt->idle_q.size()) + " idle\n";
    for (size_t i = 0; i < rt->rx_slots.size(); i++) {
      const RxSlot &sl = rt->rx_slots[i];
      s += "slot " + std::to_string(i) + ": " +
           (sl.status == RxSlot::VALID ? "VALID" : "IDLE");
      if (sl.status == RxSlot::VALID)
        s += " src " + std::to_string(sl.src) + " tag " +
             std::to_string(sl.tag) + " seqn " + std::to_string(sl.seqn) +
             " len " + std::to_string(sl.data.size());
      s += "\n";
    }
  }
  if (cap) {
    size_t n = std::min(cap - 1, s.size());
    std::memcpy(out, s.data(), n);
    out[n] = '\0';
  }
  return s.size();
}

}  // extern "C"
