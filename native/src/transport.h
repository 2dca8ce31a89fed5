// accl-tpu native runtime: the POE seam. One small vtable every
// Protocol Offload Engine implements — connect / send_frames / (rx
// loops feeding a sink) / stats — with three engines behind it:
//
//   TcpPoe    session full mesh, one ordered byte stream per
//             (peer, lane); scatter-gather writev transmit, many frames
//             per syscall (the EasyNet-class POE)
//   UdpPoe    one shared datagram socket, every frame a standalone
//             packet; sendmmsg batching (the VNX-UDP POE analog)
//   LocalPoe  intra-process registry, frames delivered by direct call
//
// The seam carries ALREADY-BUILT frames only: the transport never
// computes a CRC, never retains a frame for retransmit, never looks at
// seqn streams — that is all session/reliability policy above the seam
// (transport.cpp must not include reliability.h; `make seamcheck`).

#ifndef ACCLRT_TRANSPORT_H
#define ACCLRT_TRANSPORT_H

#include <cstddef>
#include <cstdint>
#include <functional>
#include <memory>
#include <sys/types.h>

#include "wire.h"

namespace acclw {

// Incremental access to one inbound frame's payload bytes. Datagram /
// in-process POEs hand the whole payload resident (data() non-null);
// the stream POE exposes the socket so the session can land bytes
// DIRECTLY at their destination (the zero-copy eager/rendezvous
// landings) with poll-bounded reads (poll_in + read_avail — the pin
// re-check between slices is the revocation protocol's liveness bound).
class PayloadSource {
 public:
  virtual ~PayloadSource() = default;
  // whole payload resident in memory (spans remaining() bytes from the
  // CURRENT read position); nullptr for stream sources
  virtual const uint8_t *data() const { return nullptr; }
  virtual size_t remaining() const = 0;
  // read exactly n bytes; false = link dead / shutdown
  virtual bool read_exact(void *dst, size_t n) = 0;
  // wait up to timeout_ms for readability: >0 ready, 0 timeout, <0 error
  // (mem-backed sources are always ready)
  virtual int poll_in(int timeout_ms) = 0;
  // single bounded read of up to n bytes (no waiting beyond one recv);
  // >0 bytes consumed, <=0 link dead
  virtual ssize_t read_avail(void *dst, size_t n) = 0;
};

// The session side of the seam: one call per inbound frame, invoked on
// the POE's rx thread (or the sender's thread, for the in-process POE).
// The sink must consume the payload via `body`; any unconsumed
// remainder is drained by the stream POE to preserve framing. Returning
// false tears the link down (fatal decode error / shutdown).
class PoeSink {
 public:
  virtual ~PoeSink() = default;
  virtual bool on_frame(uint32_t lane, const MsgHeader &h,
                        PayloadSource &body) = 0;
};

struct PoeConfig {
  uint32_t world = 0;
  uint32_t rank = 0;
  const uint16_t *ports = nullptr;  // per-rank port map (127.0.0.1)
  uint32_t lanes = 1;               // per-peer lanes (TCP only, <= WIRE_MAX_LANES)
  // ACCL_RT_WIRE_LEGACY=1: the pre-vectored cost model — per-frame
  // syscalls, payload coalescing copies, no batching. Kept as the A/B
  // baseline for the vectored path (tests/test_fault_injection.py).
  bool legacy_wire = false;
  // Optional per-frame WAN charge (the emulated slow-tier shaper): when
  // set, the POE charges it per frame under the same per-(dst, lane)
  // serialization the wire itself has. Never set for the local POE.
  std::function<void(size_t payload_len)> shaper;
  bool debug = false;  // gate bring-up/teardown stderr prints
};

class Poe {
 public:
  virtual ~Poe() = default;
  // Blocking bring-up (mesh handshake / datagram bind / registry
  // registration) and rx-thread spawn; frames flow into `sink` from the
  // moment this returns true. False = bring-up failure (caller owns
  // cleanup via destructor).
  virtual bool connect(PoeSink *sink) = 0;
  // Ship n frames to (dst, lane), in order, scatter-gather. The views'
  // payload pointers must stay valid for the duration of the call (the
  // caller's batch holds FramePtr pins / caller buffers). Returns false
  // when the link is down or shutdown began.
  virtual bool send_frames(uint32_t dst, uint32_t lane, const FrameView *fv,
                           size_t n) = 0;
  // Unblock rx loops and refuse new sends (idempotent)...
  virtual void begin_shutdown() = 0;
  // ...then reap them (destructor does both if the caller didn't).
  virtual void join() = 0;
  virtual uint32_t lanes() const = 0;
  // wire-health counters (accl_rt_get_stats2 TX_SYSCALLS / TX_BATCHED):
  // transmit syscalls issued, and frames that shipped inside a
  // multi-frame batch (the syscalls-per-frame ratio the batching win
  // shows up in).
  virtual uint64_t tx_syscalls() const = 0;
  virtual uint64_t tx_batched() const = 0;
  // Debug accounting for the no-double-copy invariant: payload bytes
  // coalesced into a transmit staging buffer. Stays ZERO on the
  // vectored path (scatter-gather ships borrowed pointers); only the
  // legacy cost model copies. The session asserts this after each send.
  virtual uint64_t payload_copies() const = 0;
};

std::unique_ptr<Poe> make_tcp_poe(const PoeConfig &cfg);
std::unique_ptr<Poe> make_udp_poe(const PoeConfig &cfg);
std::unique_ptr<Poe> make_local_poe(const PoeConfig &cfg);

}  // namespace acclw

#endif  // ACCLRT_TRANSPORT_H
