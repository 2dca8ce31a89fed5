// accl-tpu native runtime: the three Protocol Offload Engines behind
// the transport seam (transport.h) — session TCP full mesh, sessionless
// UDP datagrams, and the intra-process registry POE.
//
// The hot path is scatter-gather: a batch of frames to one (dst, lane)
// ships as ONE writev/sendmmsg with the header and payload iovecs
// borrowed in place — no coalescing copy anywhere on the vectored
// path (the session asserts payload_copies() == 0). The pre-vectored
// cost model (per-frame syscalls, datagram staging copies) survives
// behind ACCL_RT_WIRE_LEGACY as the A/B baseline.
//
// SEAM RULE: this file must not include reliability.h — the transport
// carries already-built frames and knows nothing about CRC, retransmit
// retention, or seqn streams (`make -C native seamcheck`).

#include "transport.h"

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdio>
#include <cstring>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

namespace acclw {
namespace {

// ---------------------------------------------------------------------------
// socket helpers
// ---------------------------------------------------------------------------

bool send_all(int fd, const void *buf, size_t n) {
  const char *p = (const char *)buf;
  while (n) {
    ssize_t w = ::send(fd, p, n, MSG_NOSIGNAL);
    if (w <= 0) return false;
    p += w;
    n -= (size_t)w;
  }
  return true;
}

bool recv_all(int fd, void *buf, size_t n) {
  char *p = (char *)buf;
  while (n) {
    ssize_t r = ::recv(fd, p, n, 0);
    if (r <= 0) return false;
    p += r;
    n -= (size_t)r;
  }
  return true;
}

// gathered write of a prepared iovec array, resuming after partial
// writes (writev may stop mid-payload under socket-buffer pressure)
bool writev_all(int fd, struct iovec *iov, int cnt) {
  size_t total = 0;
  for (int i = 0; i < cnt; i++) total += iov[i].iov_len;
  while (total) {
    ssize_t w = ::writev(fd, iov, cnt);
    if (w <= 0) return false;
    total -= (size_t)w;
    while (w) {
      if ((size_t)w >= iov->iov_len) {
        w -= (ssize_t)iov->iov_len;
        ++iov;
        --cnt;
      } else {
        iov->iov_base = (char *)iov->iov_base + w;
        iov->iov_len -= (size_t)w;
        w = 0;
      }
    }
  }
  return true;
}

// ---------------------------------------------------------------------------
// payload sources
// ---------------------------------------------------------------------------

class MemSource final : public PayloadSource {
 public:
  MemSource(const uint8_t *p, size_t n) : p_(p), left_(n) {}
  const uint8_t *data() const override { return p_; }
  size_t remaining() const override { return left_; }
  bool read_exact(void *dst, size_t n) override {
    if (n > left_) return false;
    if (n) std::memcpy(dst, p_, n);
    p_ += n;
    left_ -= n;
    return true;
  }
  int poll_in(int) override { return 1; }
  ssize_t read_avail(void *dst, size_t n) override {
    size_t k = n < left_ ? n : left_;
    if (!k) return -1;
    std::memcpy(dst, p_, k);
    p_ += k;
    left_ -= k;
    return (ssize_t)k;
  }

 private:
  const uint8_t *p_;
  size_t left_;
};

class StreamSource final : public PayloadSource {
 public:
  StreamSource(int fd, size_t n) : fd_(fd), left_(n) {}
  size_t remaining() const override { return left_; }
  bool read_exact(void *dst, size_t n) override {
    if (n > left_ || !recv_all(fd_, dst, n)) return false;
    left_ -= n;
    return true;
  }
  int poll_in(int timeout_ms) override {
    struct pollfd pf{fd_, POLLIN, 0};
    return poll(&pf, 1, timeout_ms);
  }
  ssize_t read_avail(void *dst, size_t n) override {
    size_t k = n < left_ ? n : left_;
    ssize_t r = ::recv(fd_, dst, k, 0);
    if (r > 0) left_ -= (size_t)r;
    return r;
  }

 private:
  int fd_;
  size_t left_;
};

// Vectored-path receive buffer, one per (peer, lane) rx thread: a
// single large recv pulls MANY back-to-back frames off the stream at
// once (the rx mirror of the writev batch on the tx side — without it
// the per-frame recv syscalls dominate and the transmit win pipelines
// away). Sources serve buffered bytes first, then fall through to the
// socket, so byte order is preserved and payloads larger than the
// buffer still land with a DIRECT read into their destination (the
// zero-copy eager/rendezvous landings keep working unchanged).
class RxBuf {
 public:
  explicit RxBuf(size_t cap) : buf_(cap) {}
  size_t avail() const { return end_ - start_; }
  const uint8_t *head() const { return buf_.data() + start_; }
  void consume(size_t n) { start_ += n; }
  // one blocking recv into the tail; false = link down / shutdown
  bool refill(int fd) {
    if (start_ == end_) {
      start_ = end_ = 0;
    } else if (end_ == buf_.size()) {
      std::memmove(buf_.data(), buf_.data() + start_, end_ - start_);
      end_ -= start_;
      start_ = 0;
    }
    ssize_t r = ::recv(fd, buf_.data() + end_, buf_.size() - end_, 0);
    if (r <= 0) return false;
    end_ += (size_t)r;
    return true;
  }

 private:
  std::vector<uint8_t> buf_;
  size_t start_ = 0, end_ = 0;
};

constexpr size_t RX_BUF_CAP = 256 * 1024;

class BufferedStreamSource final : public PayloadSource {
 public:
  BufferedStreamSource(int fd, RxBuf &rb, size_t n)
      : fd_(fd), rb_(rb), left_(n) {}
  size_t remaining() const override { return left_; }
  bool read_exact(void *dst, size_t n) override {
    if (n > left_) return false;
    uint8_t *p = (uint8_t *)dst;
    size_t from_buf = n < rb_.avail() ? n : rb_.avail();
    if (from_buf) {
      std::memcpy(p, rb_.head(), from_buf);
      rb_.consume(from_buf);
      p += from_buf;
    }
    if (n > from_buf && !recv_all(fd_, p, n - from_buf)) return false;
    left_ -= n;
    return true;
  }
  int poll_in(int timeout_ms) override {
    if (rb_.avail()) return 1;
    struct pollfd pf{fd_, POLLIN, 0};
    return poll(&pf, 1, timeout_ms);
  }
  ssize_t read_avail(void *dst, size_t n) override {
    size_t k = n < left_ ? n : left_;
    if (!k) return -1;
    if (rb_.avail()) {
      size_t m = k < rb_.avail() ? k : rb_.avail();
      std::memcpy(dst, rb_.head(), m);
      rb_.consume(m);
      left_ -= m;
      return (ssize_t)m;
    }
    ssize_t r = ::recv(fd_, dst, k, 0);
    if (r > 0) left_ -= (size_t)r;
    return r;
  }

 private:
  int fd_;
  RxBuf &rb_;
  size_t left_;
};

// common counter block
struct PoeStats {
  std::atomic<uint64_t> tx_syscalls{0}, tx_batched{0}, payload_copies{0};
};

// scatter-gather ceiling per writev/sendmmsg call (well under the
// kernel's IOV_MAX/UIO_MAXIOV of 1024)
constexpr size_t MAX_IOV = 512;

// glibc's std::mutex never calls pthread_mutex_init, so ThreadSanitizer
// misses mutex construction; heap reuse over a previously-destroyed
// pthread mutex then poisons happens-before tracking (see the twin note
// in runtime.cpp). Announce heap-allocated transport mutexes explicitly.
#if defined(__SANITIZE_THREAD__)
extern "C" void __tsan_mutex_create(void *addr, unsigned flags);
static void tsan_fresh_mutex(std::mutex &m) { __tsan_mutex_create(&m, 0); }
#else
static void tsan_fresh_mutex(std::mutex &) {}
#endif

// Steady-clock cv.wait_until routes through pthread_cond_clockwait,
// which gcc-10's libtsan does not intercept — the wait's internal
// unlock/reacquire is invisible and poisons lock happens-before (see
// the twin note on cv_wait_for in runtime.cpp). TSan builds convert
// the remaining budget to a system-clock deadline, taking the
// intercepted pthread_cond_timedwait path.
static std::cv_status cv_wait_deadline(
    std::condition_variable &cv, std::unique_lock<std::mutex> &lk,
    std::chrono::steady_clock::time_point deadline) {
#if defined(__SANITIZE_THREAD__)
  return cv.wait_until(lk, std::chrono::system_clock::now() +
                               (deadline - std::chrono::steady_clock::now()));
#else
  return cv.wait_until(lk, deadline);
#endif
}

// ---------------------------------------------------------------------------
// TCP POE: session full mesh, one ordered byte stream per (peer, lane)
// ---------------------------------------------------------------------------

class TcpPoe final : public Poe {
 public:
  explicit TcpPoe(const PoeConfig &cfg)
      : cfg_(cfg),
        ports_(cfg.ports, cfg.ports + cfg.world),
        fds_(cfg.world * cfg.lanes),
        tx_mu_(cfg.world * cfg.lanes) {
    for (auto &f : fds_) f.store(-1, std::memory_order_relaxed);
    for (auto &m : tx_mu_) tsan_fresh_mutex(m);
  }
  ~TcpPoe() override {
    begin_shutdown();
    join();
  }

  bool connect(PoeSink *sink) override {
    sink_ = sink;
    const uint32_t world = cfg_.world, rank = cfg_.rank, lanes = cfg_.lanes;
    listen_fd_ = socket(AF_INET, SOCK_STREAM, 0);
    int one = 1;
    setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
    sockaddr_in sa{};
    sa.sin_family = AF_INET;
    sa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    sa.sin_port = htons(ports_[rank]);
    if (bind(listen_fd_, (sockaddr *)&sa, sizeof sa) != 0 ||
        listen(listen_fd_, (int)(world * lanes)) != 0)
      return false;
    // accept from lower ranks in a helper thread while connecting to
    // higher; a periodic accept timeout + overall deadline prevents a
    // missing peer from wedging bring-up forever.
    std::atomic<bool> accept_ok{true};
    struct timeval tv{0, 200 * 1000};
    setsockopt(listen_fd_, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof tv);
    std::thread acceptor([&] {
      auto deadline =
          std::chrono::steady_clock::now() + std::chrono::seconds(30);
      uint32_t accepted = 0;
      while (accepted < rank * lanes) {
        int fd = accept(listen_fd_, nullptr, nullptr);
        if (fd < 0) {
          if (std::chrono::steady_clock::now() > deadline) {
            accept_ok.store(false);
            return;
          }
          continue;  // EAGAIN from the periodic timeout
        }
        // accepted fds inherit the listener's SO_RCVTIMEO on Linux.
        // Keep a BOUNDED timeout for the 8-byte {rank, lane} hello (a
        // connector that established but never identifies itself —
        // observed on sandboxed loopback stacks — must not wedge
        // bring-up forever), then clear it so idle links don't die
        // with EAGAIN later.
        struct timeval hello_tv{5, 0};
        setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &hello_tv, sizeof hello_tv);
        uint32_t hello[2];
        if (!recv_all(fd, hello, sizeof hello) || hello[0] >= world ||
            hello[1] >= lanes ||
            fds_[hello[0] * lanes + hello[1]].load() >= 0) {
          close(fd);
          continue;
        }
        struct timeval never{0, 0};
        setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &never, sizeof never);
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
        fds_[hello[0] * lanes + hello[1]].store(fd);
        accepted++;
      }
    });
    bool ok = true;
    for (uint32_t i = rank + 1; i < world && ok; i++) {
      for (uint32_t lane = 0; lane < lanes && ok; lane++) {
        sockaddr_in pa{};
        pa.sin_family = AF_INET;
        pa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
        pa.sin_port = htons(ports_[i]);
        // retry: peers come up in any order. Each attempt gets a FRESH
        // socket — POSIX leaves a socket unspecified after a failed
        // connect, and some loopback stacks wedge a re-connected fd
        // forever (observed as a bring-up hang on sandboxed kernels).
        int fd = -1;
        int tries = 0;
        for (;;) {
          fd = socket(AF_INET, SOCK_STREAM, 0);
          if (::connect(fd, (sockaddr *)&pa, sizeof pa) == 0) break;
          close(fd);
          fd = -1;
          if (++tries > 2000) {
            ok = false;
            break;
          }
          std::this_thread::sleep_for(std::chrono::milliseconds(5));
        }
        if (!ok) break;
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
        uint32_t hello[2] = {rank, lane};
        send_all(fd, hello, sizeof hello);
        fds_[i * lanes + lane].store(fd);
      }
    }
    acceptor.join();
    if (!ok || !accept_ok.load()) return false;
    for (uint32_t i = 0; i < world; i++) {
      if (i == rank) continue;
      for (uint32_t lane = 0; lane < lanes; lane++)
        rx_threads_.emplace_back([this, i, lane] { rx_loop(i, lane); });
    }
    return true;
  }

  bool send_frames(uint32_t dst, uint32_t lane, const FrameView *fv,
                   size_t n) override {
    if (stop_.load()) return false;
    std::lock_guard<std::mutex> g(tx_mu_[dst * cfg_.lanes + lane]);
    int fd = fds_[dst * cfg_.lanes + lane].load();
    if (fd < 0) return false;
    if (cfg_.debug)
      for (size_t i = 0; i < n; i++)
        fprintf(stderr, "[r%u] tx mt=%u dst=%u fd=%d bytes=%llu\n", cfg_.rank,
                (unsigned)fv[i].h.msg_type, dst, fd,
                (unsigned long long)fv[i].h.bytes);
    if (cfg_.legacy_wire) {
      // pre-vectored cost model: one syscall per contiguous serialized
      // frame, two (header, then payload) when the payload is borrowed
      for (size_t i = 0; i < n; i++) {
        if (cfg_.shaper) cfg_.shaper(fv[i].payload_len);
        if (fv[i].contiguous) {
          stats_.tx_syscalls++;
          if (!send_all(fd, (const uint8_t *)fv[i].payload - sizeof(MsgHeader),
                        sizeof(MsgHeader) + fv[i].payload_len))
            return false;
        } else {
          stats_.tx_syscalls++;
          if (!send_all(fd, &fv[i].h, sizeof(MsgHeader))) {
            if (cfg_.debug)
              fprintf(stderr, "[r%u] TX FAIL hdr dst=%u\n", cfg_.rank, dst);
            return false;
          }
          if (fv[i].payload_len) {
            stats_.tx_syscalls++;
            if (!send_all(fd, fv[i].payload, fv[i].payload_len)) return false;
          }
        }
      }
      return true;
    }
    // vectored path: header + payload iovecs borrowed in place, many
    // frames per writev — zero coalescing copies, one syscall per
    // MAX_IOV-entry gather
    if (cfg_.shaper)
      for (size_t i = 0; i < n; i++) cfg_.shaper(fv[i].payload_len);
    struct iovec iov[MAX_IOV];
    size_t i = 0;
    while (i < n) {
      int cnt = 0;
      size_t first = i;
      while (i < n && cnt + 2 <= (int)MAX_IOV) {
        iov[cnt].iov_base = (void *)&fv[i].h;
        iov[cnt].iov_len = sizeof(MsgHeader);
        cnt++;
        if (fv[i].payload_len) {
          iov[cnt].iov_base = (void *)fv[i].payload;
          iov[cnt].iov_len = fv[i].payload_len;
          cnt++;
        }
        i++;
      }
      stats_.tx_syscalls++;
      if (i - first > 1) stats_.tx_batched += i - first;
      if (!writev_all(fd, iov, cnt)) {
        if (cfg_.debug)
          fprintf(stderr, "[r%u] TX FAIL dst=%u lane=%u\n", cfg_.rank, dst,
                  lane);
        return false;
      }
    }
    return true;
  }

  void begin_shutdown() override {
    if (stop_.exchange(true)) return;
    // revoke + shutdown() only: the half-close unblocks rx loops parked
    // in recv (they see EOF and exit). close() is deferred to join() so
    // the descriptor number cannot be recycled by another thread's
    // open while an rx loop is still blocked on it.
    for (auto &f : fds_) {
      int fd = f.exchange(-1);
      if (fd >= 0) {
        shutdown(fd, SHUT_RDWR);
        doomed_.push_back(fd);
      }
    }
    if (listen_fd_ >= 0) {
      shutdown(listen_fd_, SHUT_RDWR);
      doomed_.push_back(listen_fd_);
      listen_fd_ = -1;
    }
  }

  void join() override {
    for (auto &t : rx_threads_)
      if (t.joinable()) t.join();
    for (int fd : doomed_) close(fd);
    doomed_.clear();
  }

  uint32_t lanes() const override { return cfg_.lanes; }
  uint64_t tx_syscalls() const override { return stats_.tx_syscalls.load(); }
  uint64_t tx_batched() const override { return stats_.tx_batched.load(); }
  uint64_t payload_copies() const override {
    return stats_.payload_copies.load();
  }

 private:
  void rx_loop(uint32_t peer, uint32_t lane) {
    int fd = fds_[peer * cfg_.lanes + lane].load();
    // legacy cost model: one recv per header, one per payload; the
    // vectored path batches — a single large recv drains many frames
    // into the per-link buffer (the rx half of the syscalls-per-frame
    // saving)
    RxBuf rb(cfg_.legacy_wire ? 0 : RX_BUF_CAP);
    while (!stop_.load()) {
      MsgHeader h;
      if (cfg_.legacy_wire) {
        if (!recv_all(fd, &h, sizeof h)) {
          if (cfg_.debug && !stop_.load())
            fprintf(stderr, "[r%u] RX LINK DOWN peer=%u lane=%u\n", cfg_.rank,
                    peer, lane);
          return;
        }
      } else {
        while (rb.avail() < sizeof h)
          if (!rb.refill(fd)) {
            if (cfg_.debug && !stop_.load())
              fprintf(stderr, "[r%u] RX LINK DOWN peer=%u lane=%u\n",
                      cfg_.rank, peer, lane);
            return;
          }
        std::memcpy(&h, rb.head(), sizeof h);
        rb.consume(sizeof h);
      }
      if (h.magic != MSG_MAGIC) {
        if (cfg_.debug)
          fprintf(stderr, "[r%u] RX BAD MAGIC peer=%u\n", cfg_.rank, peer);
        return;
      }
      // this is (PEER, LANE)'s session socket: a frame claiming any
      // other src or lane is forged or corrupt — drop the link before
      // any stream-indexed session state is touched
      if (h.src != peer || wire_lane(h) != lane) {
        if (cfg_.debug)
          fprintf(stderr, "[r%u] RX BAD SRC %u/lane %u on link peer=%u/%u\n",
                  cfg_.rank, h.src, wire_lane(h), peer, lane);
        return;
      }
      if (cfg_.debug)
        fprintf(stderr, "[r%u] rx mt=%u from=%u\n", cfg_.rank, h.msg_type,
                h.src);
      size_t plen = wire_payload_len(h);
      if (cfg_.legacy_wire) {
        StreamSource body(fd, plen);
        if (!sink_->on_frame(lane, h, body)) return;
        // preserve framing if the sink bailed early on the payload
        if (!drain(body)) return;
      } else {
        BufferedStreamSource body(fd, rb, plen);
        if (!sink_->on_frame(lane, h, body)) return;
        if (!drain(body)) return;
      }
    }
  }

  static bool drain(PayloadSource &body) {
    uint8_t waste[4096];
    while (body.remaining())
      if (!body.read_exact(waste, body.remaining() < sizeof waste
                                      ? body.remaining()
                                      : sizeof waste))
        return false;
    return true;
  }

  PoeConfig cfg_;                 // ACCL_INIT_CONST
  std::vector<uint16_t> ports_;   // ACCL_INIT_CONST
  // per (peer, lane); self = -1. Atomic: begin_shutdown revokes fds
  // (-1 + close) while rx loops and senders read them.
  std::vector<std::atomic<int>> fds_;
  std::vector<std::mutex> tx_mu_; // serialize frames per (peer, lane) link
  std::vector<std::thread> rx_threads_;
  int listen_fd_ = -1;            // ACCL_ROLE_ONLY(acceptor)
  // fds revoked by begin_shutdown, closed by join() once the rx
  // threads are reaped (shutdown-then-deferred-close teardown)
  std::vector<int> doomed_;       // ACCL_ROLE_ONLY(fini)
  std::atomic<bool> stop_{false};
  PoeSink *sink_ = nullptr;       // ACCL_INIT_CONST
  PoeStats stats_;
};

// ---------------------------------------------------------------------------
// UDP POE: one shared datagram socket, every frame a standalone packet
// (the udp_packetizer/depacketizer analog — segment == packet)
// ---------------------------------------------------------------------------

class UdpPoe final : public Poe {
 public:
  explicit UdpPoe(const PoeConfig &cfg)
      : cfg_(cfg), ports_(cfg.ports, cfg.ports + cfg.world) {}
  ~UdpPoe() override {
    begin_shutdown();
    join();
  }

  bool connect(PoeSink *sink) override {
    sink_ = sink;
    fd_.store(socket(AF_INET, SOCK_DGRAM, 0));
    int fd = fd_.load();
    int buf = 64 * 1024 * 1024;  // absorb bursts: the POE has no sessions
    // FORCE ignores net.core.rmem_max when privileged; fall back otherwise
    if (setsockopt(fd, SOL_SOCKET, SO_RCVBUFFORCE, &buf, sizeof buf))
      setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &buf, sizeof buf);
    setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &buf, sizeof buf);
    sockaddr_in sa{};
    sa.sin_family = AF_INET;
    sa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    sa.sin_port = htons(ports_[cfg_.rank]);
    if (bind(fd, (sockaddr *)&sa, sizeof sa) != 0) {
      close(fd);
      fd_.store(-1);
      return false;
    }
    peer_sa_.resize(cfg_.world);
    for (uint32_t i = 0; i < cfg_.world; i++) {
      peer_sa_[i] = sockaddr_in{};
      peer_sa_[i].sin_family = AF_INET;
      peer_sa_[i].sin_addr.s_addr = htonl(INADDR_LOOPBACK);
      peer_sa_[i].sin_port = htons(ports_[i]);
    }
    rx_thread_ = std::thread([this] { rx_loop(); });
    return true;
  }

  bool send_frames(uint32_t dst, uint32_t, const FrameView *fv,
                   size_t n) override {
    if (stop_.load()) return false;
    const int fd = fd_.load();
    const sockaddr *to = (const sockaddr *)&peer_sa_[dst];
    if (cfg_.legacy_wire) {
      // pre-vectored cost model: stage header+payload into one packet
      // buffer per frame (the coalescing copy the vectored path
      // removed), one sendto each
      for (size_t i = 0; i < n; i++) {
        if (cfg_.shaper) cfg_.shaper(fv[i].payload_len);
        std::vector<uint8_t> pkt(sizeof(MsgHeader) + fv[i].payload_len);
        std::memcpy(pkt.data(), &fv[i].h, sizeof(MsgHeader));
        if (fv[i].payload_len) {
          std::memcpy(pkt.data() + sizeof(MsgHeader), fv[i].payload,
                      fv[i].payload_len);
          stats_.payload_copies += fv[i].payload_len;
        }
        stats_.tx_syscalls++;
        ssize_t w = sendto(fd, pkt.data(), pkt.size(), 0, to,
                           sizeof(sockaddr_in));
        if (w != (ssize_t)pkt.size()) return false;
      }
      return true;
    }
    if (cfg_.shaper)
      for (size_t i = 0; i < n; i++) cfg_.shaper(fv[i].payload_len);
    if (n == 1) {
      // single frame: scatter-gather sendmsg, no staging copy
      struct iovec iov[2];
      iov[0] = {(void *)&fv[0].h, sizeof(MsgHeader)};
      iov[1] = {(void *)fv[0].payload, fv[0].payload_len};
      struct msghdr mh{};
      mh.msg_name = (void *)to;
      mh.msg_namelen = sizeof(sockaddr_in);
      mh.msg_iov = iov;
      mh.msg_iovlen = fv[0].payload_len ? 2 : 1;
      stats_.tx_syscalls++;
      return sendmsg(fd, &mh, 0) ==
             (ssize_t)(sizeof(MsgHeader) + fv[0].payload_len);
    }
    // batch: many datagrams per syscall via sendmmsg, each message its
    // own header+payload gather
    std::vector<struct iovec> iov(2 * n);
    std::vector<struct mmsghdr> mm(n);
    for (size_t i = 0; i < n; i++) {
      iov[2 * i] = {(void *)&fv[i].h, sizeof(MsgHeader)};
      iov[2 * i + 1] = {(void *)fv[i].payload, fv[i].payload_len};
      mm[i] = mmsghdr{};
      mm[i].msg_hdr.msg_name = (void *)to;
      mm[i].msg_hdr.msg_namelen = sizeof(sockaddr_in);
      mm[i].msg_hdr.msg_iov = &iov[2 * i];
      mm[i].msg_hdr.msg_iovlen = fv[i].payload_len ? 2 : 1;
    }
    stats_.tx_batched += n;
    size_t sent = 0;
    while (sent < n) {
      stats_.tx_syscalls++;
      int w = sendmmsg(fd, mm.data() + sent, (unsigned)(n - sent), 0);
      if (w <= 0) return false;
      sent += (size_t)w;
    }
    return true;
  }

  void begin_shutdown() override {
    if (stop_.exchange(true)) return;
    int fd = fd_.exchange(-1);
    if (fd >= 0) {
      // wake the datagram rx thread: shutdown() is a no-op on
      // unconnected UDP sockets, so poke ourselves with a runt datagram
      // (the rx loop re-checks `stop` on any short read). close() is
      // deferred to join() so the descriptor cannot be recycled while
      // the rx thread is still blocked in recvfrom on it.
      sendto(fd, "", 0, 0, (const sockaddr *)&peer_sa_[cfg_.rank],
             sizeof(sockaddr_in));
      doomed_ = fd;
    }
  }

  void join() override {
    if (rx_thread_.joinable()) rx_thread_.join();
    if (doomed_ >= 0) {
      close(doomed_);
      doomed_ = -1;
    }
  }

  uint32_t lanes() const override { return 1; }
  uint64_t tx_syscalls() const override { return stats_.tx_syscalls.load(); }
  uint64_t tx_batched() const override { return stats_.tx_batched.load(); }
  uint64_t payload_copies() const override {
    return stats_.payload_copies.load();
  }

 private:
  void rx_loop() {
    std::vector<uint8_t> pkt(sizeof(MsgHeader) + 65536);
    while (!stop_.load()) {
      ssize_t n =
          recvfrom(fd_.load(), pkt.data(), pkt.size(), 0, nullptr, nullptr);
      if (n < (ssize_t)sizeof(MsgHeader)) {
        if (stop_.load()) return;
        continue;  // runt/interrupted
      }
      MsgHeader h;
      std::memcpy(&h, pkt.data(), sizeof h);
      if (h.magic != MSG_MAGIC || h.src >= cfg_.world || wire_lane(h) != 0)
        continue;
      size_t plen = wire_payload_len(h);
      if ((ssize_t)(sizeof h + plen) > n) continue;  // truncated
      if (h.msg_type == MSG_EGR_DATA && (ssize_t)(sizeof h + plen) != n)
        continue;  // exact framing: segment == packet
      MemSource body(pkt.data() + sizeof h, plen);
      if (!sink_->on_frame(0, h, body)) return;
    }
  }

  PoeConfig cfg_;                     // ACCL_INIT_CONST
  std::vector<uint16_t> ports_;       // ACCL_INIT_CONST
  std::vector<sockaddr_in> peer_sa_;  // ACCL_INIT_CONST
  // atomic: begin_shutdown revokes the socket while the rx loop reads
  // it for recvfrom
  std::atomic<int> fd_{-1};
  // socket revoked by begin_shutdown, closed by join() after the rx
  // thread is reaped (shutdown-then-deferred-close teardown)
  int doomed_ = -1;                   // ACCL_ROLE_ONLY(fini)
  std::thread rx_thread_;
  std::atomic<bool> stop_{false};
  PoeSink *sink_ = nullptr;           // ACCL_INIT_CONST
  PoeStats stats_;
};

// ---------------------------------------------------------------------------
// Local POE: intra-process registry, frames delivered by direct call on
// the sender's thread
// ---------------------------------------------------------------------------

class LocalPoe;
std::mutex g_local_mu;
std::condition_variable g_local_cv;
std::unordered_map<uint16_t, LocalPoe *> g_local_ports;

class LocalPoe final : public Poe {
 public:
  explicit LocalPoe(const PoeConfig &cfg)
      : cfg_(cfg), ports_(cfg.ports, cfg.ports + cfg.world) {}
  ~LocalPoe() override {
    begin_shutdown();
    join();
  }

  bool connect(PoeSink *sink) override {
    sink_ = sink;
    std::lock_guard<std::mutex> g(g_local_mu);
    if (g_local_ports.count(ports_[cfg_.rank]))
      return false;  // port collision: refuse rather than misroute
    g_local_ports[ports_[cfg_.rank]] = this;
    registered_ = true;
    g_local_cv.notify_all();
    return true;
  }

  // Resolve + pin the peer POE, deliver on THIS thread, unpin.
  // Bring-up is the registry itself: a peer not yet constructed
  // registers within the creation barrier, so wait briefly. The two
  // g_local_mu acquisitions per batch are deliberate: the registry
  // lock is what makes peer TEARDOWN safe (begin_shutdown deregisters,
  // then waits refs==0 — a lock-free cached-pointer pin would race
  // destruction between load and increment). Streamed hops are jumbo
  // segments, so big transfers take a handful of round trips, and the
  // measured bottleneck on the CI host is scheduler parking, not this
  // futex.
  bool send_frames(uint32_t dst, uint32_t lane, const FrameView *fv,
                   size_t n) override {
    LocalPoe *peer = nullptr;
    {
      std::unique_lock<std::mutex> g(g_local_mu);
      auto deadline =
          std::chrono::steady_clock::now() + std::chrono::seconds(10);
      for (;;) {
        auto it = g_local_ports.find(ports_[dst]);
        if (it != g_local_ports.end()) {
          peer = it->second;
          peer->refs_++;
          break;
        }
        if (stop_.load() ||
            cv_wait_deadline(g_local_cv, g, deadline) ==
                std::cv_status::timeout)
          return false;
      }
    }
    bool ok = true;
    for (size_t i = 0; i < n && ok; i++) {
      MemSource body(fv[i].payload, fv[i].payload_len);
      ok = peer->sink_->on_frame(lane, fv[i].h, body);
    }
    {
      std::lock_guard<std::mutex> g(g_local_mu);
      peer->refs_--;
      g_local_cv.notify_all();
    }
    return ok;
  }

  void begin_shutdown() override {
    if (stop_.exchange(true)) return;
    // deregister, then drain in-flight deliveries pinned on this POE
    // (each is one bounded on_frame call into our sink)
    std::unique_lock<std::mutex> g(g_local_mu);
    if (registered_) {
      g_local_ports.erase(ports_[cfg_.rank]);
      registered_ = false;
    }
    g_local_cv.notify_all();
    while (refs_ > 0) g_local_cv.wait(g);
  }

  void join() override {}

  uint32_t lanes() const override { return 1; }
  uint64_t tx_syscalls() const override { return 0; }
  uint64_t tx_batched() const override { return 0; }
  uint64_t payload_copies() const override { return 0; }

 private:
  PoeConfig cfg_;                // ACCL_INIT_CONST
  std::vector<uint16_t> ports_;  // ACCL_INIT_CONST
  PoeSink *sink_ = nullptr;      // ACCL_INIT_CONST
  std::atomic<bool> stop_{false};
  bool registered_ = false;  // ACCL_GUARDED_BY(g_local_mu)
  // in-flight deliveries INTO us
  int refs_ = 0;             // ACCL_GUARDED_BY(g_local_mu)
};

}  // namespace

std::unique_ptr<Poe> make_tcp_poe(const PoeConfig &cfg) {
  return std::unique_ptr<Poe>(new TcpPoe(cfg));
}
std::unique_ptr<Poe> make_udp_poe(const PoeConfig &cfg) {
  return std::unique_ptr<Poe>(new UdpPoe(cfg));
}
std::unique_ptr<Poe> make_local_poe(const PoeConfig &cfg) {
  return std::unique_ptr<Poe>(new LocalPoe(cfg));
}

}  // namespace acclw
