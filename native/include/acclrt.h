/* accl-tpu native runtime: C API.
 *
 * The CPU-resident realization of the collective sequencer + transport —
 * the role the CCLO emulator plays in the reference (test/model/emulator/
 * cclo_emu.cpp: the full block design as free-running software), rebuilt
 * idiomatically: one runtime instance per rank, a sequencer thread
 * consuming a call queue + retry queue (ccl_offload_control.c:2308-2483's
 * run() loop), a TCP full-mesh transport carrying 64-byte ACCL message
 * headers (eth_intf.h:94-151), an eager rx-buffer ring with
 * (src, tag, seqn) seek matching (rxbuf_offload/rxbuf_seek.cpp:20-79),
 * and a rendezvous address/completion handshake with one-sided writes
 * (ccl_offload_control.c:142-408, rdma_sq_handler.cpp).
 *
 * The Python driver binds this via ctypes (accl_tpu/device/emu_device.py).
 */

#ifndef ACCLRT_H
#define ACCLRT_H

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef struct accl_rt accl_rt_t;

/* Transport selection: the reference ships interchangeable POEs selected
 * at build time (kernels/cclo/Makefile:20) — session-based TCP
 * (EasyNet-class) and sessionless UDP (VNX). The datagram transport is
 * eager-only (rendezvous message types exist only on the RDMA stack) and
 * reassembles purely by (src, tag, seqn) — each segment is a standalone
 * packet with a full header, the udp_depacketizer posture. */
enum accl_rt_transport {
  ACCL_RT_TRANSPORT_TCP = 0,
  ACCL_RT_TRANSPORT_UDP = 1,
  /* intra-process POE: same-process ranks deliver frames by direct
     call (no sockets) — the intra-node fast-path transport */
  ACCL_RT_TRANSPORT_LOCAL = 2,
};

/* Create a rank runtime. ports[world] lists each rank's port on
 * 127.0.0.1. Establishes the full mesh / datagram handshake (blocking)
 * before returning. */
accl_rt_t *accl_rt_create(uint32_t world, uint32_t rank,
                          const uint16_t *ports, uint32_t n_rx_bufs,
                          uint32_t rx_buf_bytes, uint32_t max_eager_bytes,
                          uint64_t max_rndzv_bytes);

/* accl_rt_create with an explicit transport (accl_rt_transport). */
accl_rt_t *accl_rt_create_ex(uint32_t world, uint32_t rank,
                             const uint16_t *ports, uint32_t n_rx_bufs,
                             uint32_t rx_buf_bytes, uint32_t max_eager_bytes,
                             uint64_t max_rndzv_bytes, uint32_t transport);

void accl_rt_destroy(accl_rt_t *rt);

/* Queue a 15-word call descriptor (driver/hls/accl_hls.h:134-198 layout;
 * word 8 carries stream|host<<8, and dtype is passed out-of-band since the
 * hardware encodes it via the arithcfg pointer). op0/op1/res are host
 * buffers owned by the caller, valid until the call completes.
 * Returns a handle. */
int64_t accl_rt_start(accl_rt_t *rt, const uint32_t desc[15],
                      uint32_t data_type, void *op0, void *op1, void *res);

/* 1 when the handle's call has finished, 0 otherwise. */
int accl_rt_test(accl_rt_t *rt, int64_t handle);

/* Block until the call finishes or timeout_ms elapses (0 = forever).
 * Returns 1 on completion, 0 on timeout. */
int accl_rt_wait(accl_rt_t *rt, int64_t handle, uint64_t timeout_ms);

/* Sticky error word of a completed call (errorCode bits). */
uint32_t accl_rt_retcode(accl_rt_t *rt, int64_t handle);

/* Wall-clock duration of a completed call, ns (perf-counter analog). */
uint64_t accl_rt_duration_ns(accl_rt_t *rt, int64_t handle);

/* Drop a completed call's bookkeeping (after reading retcode/duration). */
void accl_rt_release(accl_rt_t *rt, int64_t handle);

/* Permanently wedge the rank — the programmatic form of
 * ACCL_RT_FAULT_KILL_RANK (fault injection for the self-healing soak):
 * every in-flight and future call completes with a sticky
 * RECEIVE_TIMEOUT retcode (recorded as a final trace-ring span when
 * tracing is armed) and the wire goes dark in both directions; peers
 * observe a dead host's silence. Irreversible for the runtime's
 * lifetime; idempotent. */
void accl_rt_kill(accl_rt_t *rt);

/* Reconfiguration fence: drop every landed-but-unconsumed eager frame
 * (advancing the per-peer inbound seqn past it) and clear the stale
 * rendezvous queues. Call on every survivor, QUIESCENT (no live calls,
 * peer deliveries settled), between excluding a dead rank and the
 * recovery communicator's first call: the seqn-ordered streamed
 * matching would otherwise deliver the old membership's aborted-
 * collective frames into the new membership's first recv as data. */
void accl_rt_flush_rx(accl_rt_t *rt);

/* Exchange-memory MMIO (byte-addressed words, 8 KB). */
uint32_t accl_rt_read(accl_rt_t *rt, uint32_t addr);
void accl_rt_write(accl_rt_t *rt, uint32_t addr, uint32_t value);
/* cumulative sequencer counters: {passes, parks, park_ns, seek_hit,
   seek_miss} — live profiling access to the ACCL_RT_STATS counters */
void accl_rt_get_stats(accl_rt_t *rt, uint64_t out[5]);

/* Versioned counter surface: indices into accl_rt_get_stats2's output.
 * The first five mirror accl_rt_get_stats (kept ABI-stable); the rest
 * are the reliability sublayer's wire-health counters — frame volumes,
 * integrity/duplicate drops, the selective-retransmit ack/nack
 * traffic, and the seeded chaos fault model's injection tallies
 * (ACCL_RT_FAULT_{LOSS,CORRUPT,DUP,REORDER}_PCT + ACCL_RT_FAULT_SEED).
 * rely_ns is the cumulative nanoseconds spent computing/verifying
 * frame CRC32C on the DATA-PATH threads (sender frame_out, the rx
 * landing paths). It deliberately excludes the background health
 * tick's own scan (off every dispatch's critical path) and the
 * retransmit-buffer serialize copy; the chaos gate divides rely_ns by
 * dispatches for its <3% per-dispatch CRC budget and reports the
 * all-in rely-on vs rely-off wall delta alongside, unvarnished. */
enum accl_rt_stat2 {
  ACCL_RT_STAT2_PASSES = 0,
  ACCL_RT_STAT2_PARKS = 1,
  ACCL_RT_STAT2_PARK_NS = 2,
  ACCL_RT_STAT2_SEEK_HIT = 3,
  ACCL_RT_STAT2_SEEK_MISS = 4,
  ACCL_RT_STAT2_TX_FRAMES = 5,   /* eager data frames sent */
  ACCL_RT_STAT2_RX_FRAMES = 6,   /* eager data frames received (pre-CRC) */
  ACCL_RT_STAT2_CRC_DROPS = 7,   /* corrupt frames counted + dropped */
  ACCL_RT_STAT2_DUP_DROPS = 8,   /* late/duplicate seqns dropped */
  ACCL_RT_STAT2_RETX_SENT = 9,   /* frames resent on a peer's NACK */
  ACCL_RT_STAT2_RETX_MISS = 10,  /* NACKed frames already evicted */
  ACCL_RT_STAT2_NACK_SENT = 11,
  ACCL_RT_STAT2_NACK_RX = 12,
  ACCL_RT_STAT2_ACK_SENT = 13,
  ACCL_RT_STAT2_ACK_RX = 14,
  ACCL_RT_STAT2_RNDZV_DROPS = 15, /* unposted/revoked one-sided writes */
  ACCL_RT_STAT2_INJ_LOSS = 16,
  ACCL_RT_STAT2_INJ_CORRUPT = 17,
  ACCL_RT_STAT2_INJ_DUP = 18,
  ACCL_RT_STAT2_INJ_REORDER = 19,
  ACCL_RT_STAT2_RELY_NS = 20,
  /* vectored-wire transmit shape (the zero-copy scatter-gather path):
   * syscalls issued for frame transmit, and frames that shipped inside
   * a multi-frame writev/sendmmsg batch. syscalls/tx_frames is the
   * per-frame syscall ratio of the vectored wire; both stay 0
   * on the in-process POE (no syscalls to count). */
  ACCL_RT_STAT2_TX_SYSCALLS = 21,
  ACCL_RT_STAT2_TX_BATCHED = 22,
  ACCL_RT_STATS2_COUNT = 23,
};

/* Fill out[0..min(cap, ACCL_RT_STATS2_COUNT)) and return the total
 * number of counters this build exposes (callers detect growth by the
 * return value; accl_rt_get_stats keeps the old 5-word ABI). */
size_t accl_rt_get_stats2(accl_rt_t *rt, uint64_t *out, size_t cap);

/* Eager-rx-ring snapshot (dump_eager_rx_buffers analog): NUL-terminated
 * report into out (truncated at cap); returns the untruncated length. */
size_t accl_rt_dump_rxbufs(accl_rt_t *rt, char *out, size_t cap);

/* Device-resident trace ring (ACCL_RT_TRACE=1; ACCL_RT_TRACE_CAP sizes
 * the ring, default 4096). One record per COMPLETED call: opcode,
 * element count, payload bytes, start/end ns since runtime creation
 * (steady clock), the sticky retcode, the deferred-head-mismatch fault
 * code the timeout detail surfaced (0 when none), and the per-call
 * delta of the sequencer counters (passes/parks/seek hit/miss) over the
 * call's lifetime. Zero-cost when tracing is off: the recording path is
 * a single branch on a bool set at create. */
typedef struct accl_rt_span {
  uint32_t opcode;    /* call scenario (desc word 0) */
  uint32_t retcode;   /* sticky error word of the completed call */
  uint32_t detail;    /* deferred-mismatch fault code behind a
                         RECEIVE_TIMEOUT (DMA_TAG_MISMATCH / DMA_SIZE),
                         0 = none */
  uint32_t count;     /* element count (desc word 1) */
  uint64_t bytes;     /* payload bytes (count * dtype width) */
  uint64_t start_ns;  /* call enqueue, ns since runtime creation */
  uint64_t end_ns;    /* call completion, ns since runtime creation */
  uint64_t d_passes, d_parks, d_seek_hit, d_seek_miss; /* counter deltas */
} accl_rt_span_t;

/* Drain up to cap span records (oldest first) into out; returns the
 * number copied and removes them from the ring. *dropped (optional)
 * receives the cumulative count of spans lost to ring overflow (oldest
 * dropped first; the ring itself never blocks or crashes the data
 * plane). Returns 0 when tracing is disabled. */
size_t accl_rt_trace_read(accl_rt_t *rt, accl_rt_span_t *out, size_t cap,
                          uint64_t *dropped);

/* Data types, matching accl_tpu.constants.DataType. */
enum accl_rt_dtype {
  ACCL_DT_NONE = 0,
  ACCL_DT_INT8 = 1,
  ACCL_DT_FLOAT16 = 2,
  ACCL_DT_FLOAT32 = 3,
  ACCL_DT_FLOAT64 = 4,
  ACCL_DT_INT32 = 5,
  ACCL_DT_INT64 = 6,
  ACCL_DT_BFLOAT16 = 7,
};

#ifdef __cplusplus
}
#endif

#endif /* ACCLRT_H */
