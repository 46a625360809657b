#include "collectives.h"

#include <errno.h>
#include <fcntl.h>
#include <limits.h>
#include <netinet/in.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <string.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstdlib>
#include <deque>
#include <stdexcept>

#include "debug_lock.h"
#include "reduce.h"

// MSG_ZEROCOPY plumbing (zerocopy tier). The flag and the error-queue
// notification layout are stable kernel ABI, so spell out fallbacks for
// toolchains whose userspace headers predate them.
#if defined(__has_include)
#if __has_include(<linux/errqueue.h>)
#include <linux/errqueue.h>
#define HVD_HAVE_ERRQUEUE 1
#endif
#endif
#ifndef HVD_HAVE_ERRQUEUE
struct sock_extended_err {
  uint32_t ee_errno;
  uint8_t ee_origin;
  uint8_t ee_type;
  uint8_t ee_code;
  uint8_t ee_pad;
  uint32_t ee_info;
  uint32_t ee_data;
};
#endif
#ifndef MSG_ZEROCOPY
#define MSG_ZEROCOPY 0x4000000
#endif
#ifndef SO_EE_ORIGIN_ZEROCOPY
#define SO_EE_ORIGIN_ZEROCOPY 5
#endif
#ifndef SO_EE_CODE_ZEROCOPY_COPIED
#define SO_EE_CODE_ZEROCOPY_COPIED 1
#endif
#ifndef SOL_IP
#define SOL_IP 0
#endif
#ifndef IP_RECVERR
#define IP_RECVERR 11
#endif

namespace hvd {

namespace {

int IndexOf(const std::vector<int32_t>& members, int rank) {
  for (size_t i = 0; i < members.size(); i++)
    if (members[i] == rank) return (int)i;
  throw std::runtime_error("rank not in process set members");
}

// Even-ish split of nelem into m chunks (remainder spread over the first
// chunks), matching the reference's fusion-chunk layout.
std::vector<int64_t> SplitChunks(int64_t nelem, int m) {
  std::vector<int64_t> lens(m, nelem / m);
  for (int i = 0; i < (int)(nelem % m); i++) lens[i]++;
  return lens;
}

std::vector<int64_t> Offsets(const std::vector<int64_t>& lens) {
  std::vector<int64_t> off(lens.size() + 1, 0);
  for (size_t i = 0; i < lens.size(); i++) off[i + 1] = off[i] + lens[i];
  return off;
}

int64_t MonoUs() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// Bytes remaining in an iovec list from index `i` onward.
size_t IovBytes(const std::vector<iovec>& v, size_t i) {
  size_t n = 0;
  for (; i < v.size(); i++) n += v[i].iov_len;
  return n;
}

// Consume k transferred bytes: advance past finished iovecs, bump the
// partial one, and land *idx on the next non-empty entry.
void IovAdvance(std::vector<iovec>& v, size_t* idx, size_t k) {
  while (k > 0) {
    iovec& io = v[*idx];
    if (k >= io.iov_len) {
      k -= io.iov_len;
      io.iov_len = 0;
      (*idx)++;
    } else {
      io.iov_base = (uint8_t*)io.iov_base + k;
      io.iov_len -= k;
      k = 0;
    }
  }
  while (*idx < v.size() && v[*idx].iov_len == 0) (*idx)++;
}

// Append iovecs covering elements [first, first+count) of the segment list
// (segments are laid end to end in list order, like the fusion buffer the
// scatter-gather path replaces).
void SliceIov(const std::vector<Segment>& segs, int64_t first, int64_t count,
              size_t esz, std::vector<iovec>* out) {
  int64_t pos = 0;
  for (const auto& s : segs) {
    if (count == 0) break;
    int64_t seg_end = pos + s.elems;
    if (seg_end > first) {
      int64_t lo = std::max(first, pos);
      int64_t take = std::min(count, seg_end - lo);
      if (take > 0)
        out->push_back({s.base + (size_t)(lo - pos) * esz,
                        (size_t)take * esz});
      first += take;
      count -= take;
    }
    pos = seg_end;
  }
}

// Walk parallel in/out segment lists (identical element layout) over
// [first, first+count) elements, calling fn(out_ptr, in_ptr, n) for each
// maximal run inside one segment.
template <typename F>
void ForEachSpan(const std::vector<Segment>& in,
                 const std::vector<Segment>& out, int64_t first,
                 int64_t count, size_t esz, F fn) {
  int64_t pos = 0;
  for (size_t i = 0; i < in.size() && count > 0; i++) {
    int64_t seg_end = pos + in[i].elems;
    if (seg_end > first) {
      int64_t lo = std::max(first, pos);
      int64_t take = std::min(count, seg_end - lo);
      if (take > 0)
        fn(out[i].base + (size_t)(lo - pos) * esz,
           in[i].base + (size_t)(lo - pos) * esz, take);
      first += take;
      count -= take;
    }
    pos = seg_end;
  }
}

}  // namespace

// --- wire tier plumbing ------------------------------------------------------

void DataPlane::set_wire_tier(int tier) {
  if (tier == wire::kUring) {
    // 64 SQ entries is far beyond the engine's 2 in-flight ops; sized for
    // headroom, not throughput. A setup failure here (fd exhaustion after a
    // successful probe) degrades rather than fails.
    if (!uring_.valid() && !uring_.Init(64)) tier = wire::kZeroCopy;
  }
  if (tier != wire::kUring && uring_.valid()) uring_.Close();
  if (tier == wire::kZeroCopy)
    for (auto& s : peers_)
      if (s.valid()) s.EnableZeroCopy();
  wire_tier_ = tier;
  if (tier == wire::kUring && !scratch_.empty())
    uring_.RegisterScratch(scratch_.data(), scratch_.size());
}

uint8_t* DataPlane::Scratch(size_t n) {
  if (scratch_.size() < n) {
    scratch_.resize(n);
    // Growth moves the allocation, invalidating the fixed-buffer
    // registration; re-register so receives keep riding READ_FIXED.
    if (uring_.valid())
      uring_.RegisterScratch(scratch_.data(), scratch_.size());
  }
  return scratch_.data();
}

ssize_t DataPlane::WireSend(Socket& to, const void* p, size_t n,
                            int* zc_pending) {
  bool zc = wire_tier_ == wire::kZeroCopy && to.zerocopy() &&
            (int64_t)n >= zc_threshold_;
  stat_wire_syscalls++;
  ssize_t k =
      ::send(to.fd(), p, n, zc ? MSG_NOSIGNAL | MSG_ZEROCOPY : MSG_NOSIGNAL);
  if (k < 0 && zc && errno == ENOBUFS) {
    // Pinned-page budget (net.core.optmem_max) exhausted: reap outstanding
    // completions and retry plain — correctness never depends on zerocopy
    // engaging.
    ReapZeroCopy(to, zc_pending);
    stat_wire_syscalls++;
    k = ::send(to.fd(), p, n, MSG_NOSIGNAL);
    zc = false;
  }
  if (k > 0 && zc) {
    (*zc_pending)++;
    stat_zc_sends++;
  }
  return k;
}

ssize_t DataPlane::WireSendMsg(Socket& to, msghdr* mh, size_t left,
                               int* zc_pending) {
  bool zc = wire_tier_ == wire::kZeroCopy && to.zerocopy() &&
            (int64_t)left >= zc_threshold_;
  stat_wire_syscalls++;
  ssize_t k =
      ::sendmsg(to.fd(), mh, zc ? MSG_NOSIGNAL | MSG_ZEROCOPY : MSG_NOSIGNAL);
  if (k < 0 && zc && errno == ENOBUFS) {
    ReapZeroCopy(to, zc_pending);
    stat_wire_syscalls++;
    k = ::sendmsg(to.fd(), mh, MSG_NOSIGNAL);
    zc = false;
  }
  if (k > 0 && zc) {
    (*zc_pending)++;
    stat_zc_sends++;
  }
  return k;
}

// Drain whatever completion notifications are queued right now (never
// blocks). Returns the number reaped; 0 when the queue is empty or holds
// only non-zerocopy errors (the caller's normal error paths surface those).
int DataPlane::TryReapZeroCopy(Socket& to, int* zc_pending) {
  int reaped = 0;
  while (*zc_pending > 0) {
    uint8_t ctrl[512];
    msghdr mh = {};
    mh.msg_control = ctrl;
    mh.msg_controllen = sizeof(ctrl);
    stat_wire_syscalls++;
    ssize_t k = ::recvmsg(to.fd(), &mh, MSG_ERRQUEUE | MSG_DONTWAIT);
    if (k < 0) break;  // EAGAIN (drained) or a real error — caller's problem
    for (cmsghdr* c = CMSG_FIRSTHDR(&mh); c; c = CMSG_NXTHDR(&mh, c)) {
      if (!(c->cmsg_level == SOL_IP && c->cmsg_type == IP_RECVERR)) continue;
      sock_extended_err ee;
      memcpy(&ee, CMSG_DATA(c), sizeof(ee));
      if (ee.ee_errno != 0 || ee.ee_origin != SO_EE_ORIGIN_ZEROCOPY) continue;
      // One notification covers the send range [ee_info, ee_data].
      int done = (int)(ee.ee_data - ee.ee_info) + 1;
      *zc_pending -= done;
      if (*zc_pending < 0) *zc_pending = 0;
      reaped += done;
      stat_zc_completions += done;
      if (ee.ee_code & SO_EE_CODE_ZEROCOPY_COPIED) stat_zc_copied += done;
    }
  }
  return reaped;
}

void DataPlane::ReapZeroCopy(Socket& to, int* zc_pending) {
  if (*zc_pending <= 0) return;
  int64_t t0 = MonoUs();
  while (*zc_pending > 0) {
    if (TryReapZeroCopy(to, zc_pending) > 0) continue;
    if (*zc_pending <= 0) break;
    // Error-queue readiness reports as POLLERR even with no events
    // requested, so an empty events mask waits for exactly that.
    pollfd pfd{to.fd(), 0, 0};
    fault::Check("poll");
    lockdep::OnBlockingSyscall("poll");
    stat_wire_syscalls++;
    int rc = ::poll(&pfd, 1, poll_timeout_ms_);
    if (rc < 0) {
      if (errno == EINTR) continue;
      throw std::runtime_error("zerocopy completion poll failed");
    }
    if (rc == 0)
      throw std::runtime_error(
          "zerocopy completion timeout (" +
          std::to_string(poll_timeout_ms_ / 1000) +
          "s waiting on the error queue; HVD_DATA_TIMEOUT_SECONDS to tune)");
  }
  stat_zc_us += MonoUs() - t0;
}

void DataPlane::UringDuplex(
    Socket& to, std::vector<iovec>& sv, Socket& from, std::vector<iovec>& rv,
    size_t rblock, const std::function<void(size_t, size_t)>& on_block) {
  int64_t t0 = MonoUs();
  size_t si = 0, ri = 0;
  while (si < sv.size() && sv[si].iov_len == 0) si++;
  while (ri < rv.size() && rv[ri].iov_len == 0) ri++;
  size_t sleft = IovBytes(sv, si);
  size_t rleft = IovBytes(rv, ri);
  const size_t rtotal = rleft;
  size_t recvd = 0, delivered = 0;
  // Sockets stay BLOCKING on this tier: io_uring attempts each op
  // non-blocking internally and poll-arms the retry itself, so the
  // O_NONBLOCK juggling of the classic loops is unnecessary.
  bool send_inflight = false;
  int recv_inflight = 0;
  msghdr smh = {}, rmh = {};
  constexpr uint64_t kSend = 1, kRecv = 2;
  // Chained-wave bookkeeping: the address/length each receive SQE was
  // armed with, FIFO — IOSQE_IO_LINK executes the chain sequentially, so
  // completions arrive in push order. `shift` accumulates the deficit of
  // rare short WAITALL completions (signal hit mid-receive): successors
  // were armed at precomputed offsets, so their landed bytes memmove back
  // by the running deficit to stay stream-contiguous.
  std::deque<std::pair<uint8_t*, size_t>> armed;
  size_t shift = 0;
  // Streamed receives into one contiguous region arm a whole WAVE of
  // block-bounded MSG_WAITALL recvs as one linked chain: a single submit
  // replaces the entire per-chunk poll/readv cycle, completions are
  // reaped from the CQ ring in user space (no syscall), and the kernel
  // keeps draining the socket behind the on_block reduction.
  const bool chain_mode = rblock > 0 && rv.size() - ri == 1;
  while (sleft > 0 || rleft > 0 || send_inflight || recv_inflight > 0) {
    bool pushed_now = false;
    if (sleft > 0 && !send_inflight) {
      // One SENDMSG SQE covers the whole remaining iovec run: the kernel
      // executes it like a blocking sendmsg (retrying partial progress off
      // write-readiness), so a multi-MB chunk is one submission, not a
      // poll-loop of MTU-sized slices.
      smh = msghdr{};
      smh.msg_iov = &sv[si];
      smh.msg_iovlen = std::min(sv.size() - si, (size_t)IOV_MAX);
      // Large sends go IOSQE_ASYNC: a blocking kernel-side sendmsg walks
      // the socket buffer itself and posts ONE completion, where the
      // inline attempt would hand back partial progress per buffer-full
      // and cost a resubmit enter each time. Small sends fit the first
      // attempt anyway and skip the worker handoff.
      if (!uring_.PushSendmsg(to.fd(), &smh, kSend,
                              sleft > (size_t)256 * 1024))
        throw std::runtime_error("io_uring submission queue overflow (send)");
      send_inflight = true;
      pushed_now = true;
      stat_uring_sqes++;
    }
    if (rleft > 0 && recv_inflight == 0) {
      if (chain_mode) {
        // Size the wave first (bounded by free SQ slots, one reserved for
        // a send resubmit) so every push below is guaranteed a slot and
        // no trailing IOSQE_IO_LINK can dangle into a later submission.
        unsigned room = uring_.SqRoom();
        size_t wave = room > 1 ? room - 1 : 1;
        std::vector<size_t> lens;
        size_t off = 0;
        while (off < rleft && lens.size() < wave) {
          size_t want = std::min(rleft - off,
                                 rblock - (recvd + off - delivered) % rblock);
          want = std::min(want, (size_t)(1u << 30));
          lens.push_back(want);
          off += want;
        }
        uint8_t* base = (uint8_t*)rv[ri].iov_base;
        shift = 0;
        armed.clear();
        off = 0;
        for (size_t i = 0; i < lens.size(); i++) {
          if (!uring_.PushRecv(from.fd(), base + off, (unsigned)lens[i],
                               MSG_WAITALL, kRecv, i + 1 < lens.size()))
            throw std::runtime_error(
                "io_uring submission queue overflow (recv chain)");
          armed.push_back({base + off, lens[i]});
          recv_inflight++;
          pushed_now = true;
          stat_uring_sqes++;
          off += lens[i];
        }
      } else {
        bool pushed;
        uint8_t* sb = (uint8_t*)uring_.scratch_base();
        bool in_scratch = rv.size() - ri == 1 &&
                          uring_.scratch_registered() &&
                          (uint8_t*)rv[ri].iov_base >= sb &&
                          (uint8_t*)rv[ri].iov_base + rv[ri].iov_len <=
                              sb + uring_.scratch_len();
        if (in_scratch) {
          // Registered-buffer receive (no per-op page pinning). Completes
          // with whatever is available, like recv(2) — fine for a serial
          // chunk that is usually one socket-buffer burst anyway.
          unsigned len =
              (unsigned)std::min(rv[ri].iov_len, (size_t)(1u << 30));
          pushed =
              uring_.PushReadFixed(from.fd(), rv[ri].iov_base, len, kRecv);
        } else if (rv.size() - ri > 1) {
          // Segmented receive (allgather wiring output segments directly):
          // MSG_WAITALL makes the kernel retry short receives, so the whole
          // segmented chunk lands in one completion.
          rmh = msghdr{};
          rmh.msg_iov = &rv[ri];
          rmh.msg_iovlen = std::min(rv.size() - ri, (size_t)IOV_MAX);
          pushed = uring_.PushRecvmsg(from.fd(), &rmh, MSG_WAITALL, kRecv);
        } else {
          // Contiguous serial receive outside the scratch: the full chunk
          // as one kernel-completed op.
          unsigned len =
              (unsigned)std::min(rv[ri].iov_len, (size_t)(1u << 30));
          pushed = uring_.PushRecv(from.fd(), rv[ri].iov_base, len,
                                   MSG_WAITALL, kRecv);
        }
        if (!pushed)
          throw std::runtime_error(
              "io_uring submission queue overflow (recv)");
        recv_inflight = 1;
        pushed_now = true;
        stat_uring_sqes++;
      }
    }
    // The tier's whole point: ONE syscall submits every SQE pushed above
    // AND waits (bounded) for completions. The submit enter waits for just
    // one CQE so early blocks reduce while the kernel drains the rest of
    // the chain; a PURE wait (nothing newly pushed) asks for everything
    // still in flight at once — safe only while every send completes full
    // (MSG_WAITALL honored): a partial send's tail is resubmitted from
    // HERE, and two ranks both sleeping past a partial-send CQE while
    // their peers wait on the unsent tail is a mutual stall. The first
    // short send therefore flips uring_full_sends_ off for good and every
    // wait drops back to one-CQE wakeups.
    unsigned want = 1;
    if (!pushed_now && uring_full_sends_) {
      size_t inflight = (size_t)recv_inflight + (send_inflight ? 1 : 0);
      if (inflight > 1) want = (unsigned)inflight;
    }
    stat_uring_submits++;
    stat_wire_syscalls++;
    int rc = uring_.SubmitAndWait(want, poll_timeout_ms_);
    if (rc < 0)
      throw std::runtime_error(std::string("io_uring_enter failed: ") +
                               strerror(-rc));
    uint64_t ud = 0;
    int32_t res = 0;
    bool reaped = false;
    while (uring_.PopCompletion(&ud, &res)) {
      stat_uring_cqes++;
      reaped = true;
      if (ud == kSend) {
        send_inflight = false;
        if (res == -EINTR || res == -EAGAIN) {
          uring_full_sends_ = false;  // kernel handed the op back unfinished
          continue;                   // resubmit next round
        }
        if (res < 0)
          throw std::runtime_error(
              std::string("data-plane send failed (io_uring): ") +
              strerror(-res));
        if ((size_t)res < sleft) uring_full_sends_ = false;
        IovAdvance(sv, &si, (size_t)res);
        sleft -= (size_t)res;
        to.note_tx((size_t)res);
      } else {
        recv_inflight--;
        uint8_t* abuf = nullptr;
        size_t alen = 0;
        if (!armed.empty()) {
          abuf = armed.front().first;
          alen = armed.front().second;
          armed.pop_front();
        }
        // A failed link predecessor cancels the rest of its chain; the
        // outer loop re-arms a fresh wave from the true stream position
        // once every cancelled CQE has drained.
        if (res == -ECANCELED) continue;
        if (res == -EINTR || res == -EAGAIN) continue;
        if (res == 0) throw std::runtime_error("data-plane peer closed");
        if (res < 0)
          throw std::runtime_error(
              std::string("data-plane recv failed (io_uring): ") +
              strerror(-res));
        if (abuf != nullptr) {
          if (shift > 0) memmove(abuf - shift, abuf, (size_t)res);
          if ((size_t)res < alen) shift += alen - (size_t)res;
        }
        IovAdvance(rv, &ri, (size_t)res);
        rleft -= (size_t)res;
        recvd += (size_t)res;
        if (on_block && rblock > 0) {
          size_t bound = recvd == rtotal
                             ? rtotal
                             : delivered + (recvd - delivered) / rblock * rblock;
          if (bound > delivered) {
            on_block(delivered, bound - delivered);
            delivered = bound;
          }
        }
      }
    }
    if (!reaped)
      throw std::runtime_error(
          "data-plane poll timeout (" +
          std::to_string(poll_timeout_ms_ / 1000) +
          "s with no completions; HVD_DATA_TIMEOUT_SECONDS to tune)");
  }
  stat_uring_us += MonoUs() - t0;
  stat_wire_ops++;
}

void DataPlane::FullDuplex(Socket& to, const void* sbuf, size_t sn,
                           Socket& from, void* rbuf, size_t rn) {
  if (UringReady()) {
    std::vector<iovec> sv, rv;
    if (sn) sv.push_back({(void*)sbuf, sn});
    if (rn) rv.push_back({rbuf, rn});
    UringDuplex(to, sv, from, rv, 0, {});
    return;
  }
  const uint8_t* sp = (const uint8_t*)sbuf;
  uint8_t* rp = (uint8_t*)rbuf;
  size_t sent = 0, recvd = 0;
  int zc_pending = 0;
  bool same = to.fd() == from.fd();
  to.SetNonBlocking(true);
  if (!same) from.SetNonBlocking(true);
  try {
    while (sent < sn || recvd < rn) {
      pollfd fds[2];
      int nfds = 0;
      if (same) {
        fds[0] = {to.fd(), 0, 0};
        if (sent < sn) fds[0].events |= POLLOUT;
        if (recvd < rn) fds[0].events |= POLLIN;
        nfds = 1;
      } else {
        if (sent < sn) fds[nfds++] = {to.fd(), POLLOUT, 0};
        if (recvd < rn) fds[nfds++] = {from.fd(), POLLIN, 0};
      }
      fault::Check("poll");
      lockdep::OnBlockingSyscall("poll");
      stat_wire_syscalls++;
      int rc = ::poll(fds, nfds, poll_timeout_ms_);
      if (rc < 0) {
        if (errno == EINTR) continue;
        throw std::runtime_error("poll failed");
      }
      if (rc == 0)
        throw std::runtime_error(
            "data-plane poll timeout (" +
            std::to_string(poll_timeout_ms_ / 1000) +
            "s with no bytes moved; HVD_DATA_TIMEOUT_SECONDS to tune)");
      for (int i = 0; i < nfds; i++) {
        if ((fds[i].revents & (POLLERR | POLLHUP | POLLNVAL)) &&
            !(fds[i].revents & (POLLIN | POLLOUT))) {
          // On the zerocopy tier a bare POLLERR can simply mean completion
          // notifications are queued; only a sterile error queue is fatal.
          if (zc_pending > 0 && fds[i].fd == to.fd() &&
              TryReapZeroCopy(to, &zc_pending) > 0)
            continue;
          throw std::runtime_error("data-plane peer failed");
        }
        if ((fds[i].revents & POLLOUT) && sent < sn) {
          ssize_t k = WireSend(to, sp + sent, sn - sent, &zc_pending);
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
            throw std::runtime_error(
                std::string("data-plane send failed: ") + strerror(errno));
          if (k > 0) {
            sent += (size_t)k;
            to.note_tx((size_t)k);
          }
        }
        if ((fds[i].revents & POLLIN) && recvd < rn) {
          stat_wire_syscalls++;
          ssize_t k = ::recv(from.fd(), rp + recvd, rn - recvd, 0);
          if (k == 0) throw std::runtime_error("data-plane peer closed");
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
            throw std::runtime_error("data-plane recv failed");
          if (k > 0) recvd += (size_t)k;
        }
      }
    }
    ReapZeroCopy(to, &zc_pending);
  } catch (...) {
    to.SetNonBlocking(false);
    if (!same) from.SetNonBlocking(false);
    throw;
  }
  to.SetNonBlocking(false);
  if (!same) from.SetNonBlocking(false);
  stat_wire_ops++;
}

void DataPlane::FullDuplexV(Socket& to, std::vector<iovec>& sv, Socket& from,
                            std::vector<iovec>& rv) {
  if (UringReady()) {
    UringDuplex(to, sv, from, rv, 0, {});
    return;
  }
  size_t si = 0, ri = 0;
  while (si < sv.size() && sv[si].iov_len == 0) si++;
  while (ri < rv.size() && rv[ri].iov_len == 0) ri++;
  size_t sleft = IovBytes(sv, si);
  size_t rleft = IovBytes(rv, ri);
  int zc_pending = 0;
  bool same = to.fd() == from.fd();
  to.SetNonBlocking(true);
  if (!same) from.SetNonBlocking(true);
  try {
    while (sleft > 0 || rleft > 0) {
      pollfd fds[2];
      int nfds = 0;
      if (same) {
        fds[0] = {to.fd(), 0, 0};
        if (sleft > 0) fds[0].events |= POLLOUT;
        if (rleft > 0) fds[0].events |= POLLIN;
        nfds = 1;
      } else {
        if (sleft > 0) fds[nfds++] = {to.fd(), POLLOUT, 0};
        if (rleft > 0) fds[nfds++] = {from.fd(), POLLIN, 0};
      }
      fault::Check("poll");
      lockdep::OnBlockingSyscall("poll");
      stat_wire_syscalls++;
      int rc = ::poll(fds, nfds, poll_timeout_ms_);
      if (rc < 0) {
        if (errno == EINTR) continue;
        throw std::runtime_error("poll failed");
      }
      if (rc == 0)
        throw std::runtime_error(
            "data-plane poll timeout (" +
            std::to_string(poll_timeout_ms_ / 1000) +
            "s with no bytes moved; HVD_DATA_TIMEOUT_SECONDS to tune)");
      for (int i = 0; i < nfds; i++) {
        if ((fds[i].revents & (POLLERR | POLLHUP | POLLNVAL)) &&
            !(fds[i].revents & (POLLIN | POLLOUT))) {
          if (zc_pending > 0 && fds[i].fd == to.fd() &&
              TryReapZeroCopy(to, &zc_pending) > 0)
            continue;
          throw std::runtime_error("data-plane peer failed");
        }
        if ((fds[i].revents & POLLOUT) && sleft > 0) {
          // sendmsg, not writev: MSG_NOSIGNAL keeps a dead peer an error
          // return instead of a SIGPIPE, matching the byte path.
          msghdr mh = {};
          mh.msg_iov = &sv[si];
          mh.msg_iovlen = std::min(sv.size() - si, (size_t)IOV_MAX);
          ssize_t k = WireSendMsg(to, &mh, sleft, &zc_pending);
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK &&
              errno != EINTR)
            throw std::runtime_error(
                std::string("data-plane send failed: ") + strerror(errno));
          if (k > 0) {
            IovAdvance(sv, &si, (size_t)k);
            sleft -= (size_t)k;
            to.note_tx((size_t)k);
          }
        }
        if ((fds[i].revents & POLLIN) && rleft > 0) {
          stat_wire_syscalls++;
          ssize_t k = ::readv(from.fd(), &rv[ri],
                              (int)std::min(rv.size() - ri, (size_t)IOV_MAX));
          if (k == 0) throw std::runtime_error("data-plane peer closed");
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK &&
              errno != EINTR)
            throw std::runtime_error("data-plane recv failed");
          if (k > 0) {
            IovAdvance(rv, &ri, (size_t)k);
            rleft -= (size_t)k;
          }
        }
      }
    }
    ReapZeroCopy(to, &zc_pending);
  } catch (...) {
    to.SetNonBlocking(false);
    if (!same) from.SetNonBlocking(false);
    throw;
  }
  to.SetNonBlocking(false);
  if (!same) from.SetNonBlocking(false);
  stat_wire_ops++;
}

// Sub-block size for streaming a chunk_bytes receive. Auto depth (pipeline_
// == 0) targets ~256 KiB sub-blocks, capped at 32 per chunk — deep enough
// to overlap most of the reduce on MB-scale chunks, shallow enough that the
// per-block dispatch overhead stays noise. A 4 KiB floor keeps tiny chunks
// from degenerating into per-packet callbacks.
size_t DataPlane::StreamBlockBytes(size_t chunk_bytes, size_t esz) const {
  size_t depth = (size_t)pipeline_;
  if (depth == 0)
    depth = std::min<size_t>(32, std::max<size_t>(1, chunk_bytes >> 18));
  if (depth <= 1 || chunk_bytes < 2 * esz) return 0;
  size_t block = chunk_bytes / depth;
  if (block < 4096) block = 4096;
  block = block / esz * esz;
  if (block == 0) block = esz;
  if (block >= chunk_bytes) return 0;
  return block;
}

void DataPlane::FullDuplexStream(
    Socket& to, const void* sbuf, size_t sn, Socket& from, void* rbuf,
    size_t rn, size_t rblock,
    const std::function<void(size_t, size_t)>& on_block) {
  if (UringReady()) {
    std::vector<iovec> sv, rv;
    if (sn) sv.push_back({(void*)sbuf, sn});
    if (rn) rv.push_back({rbuf, rn});
    UringDuplex(to, sv, from, rv, rblock, on_block);
    return;
  }
  const uint8_t* sp = (const uint8_t*)sbuf;
  uint8_t* rp = (uint8_t*)rbuf;
  size_t sent = 0, recvd = 0, delivered = 0;
  int zc_pending = 0;
  bool same = to.fd() == from.fd();
  to.SetNonBlocking(true);
  if (!same) from.SetNonBlocking(true);
  try {
    while (sent < sn || recvd < rn) {
      pollfd fds[2];
      int nfds = 0;
      if (same) {
        fds[0] = {to.fd(), 0, 0};
        if (sent < sn) fds[0].events |= POLLOUT;
        if (recvd < rn) fds[0].events |= POLLIN;
        nfds = 1;
      } else {
        if (sent < sn) fds[nfds++] = {to.fd(), POLLOUT, 0};
        if (recvd < rn) fds[nfds++] = {from.fd(), POLLIN, 0};
      }
      fault::Check("poll");
      lockdep::OnBlockingSyscall("poll");
      stat_wire_syscalls++;
      int rc = ::poll(fds, nfds, poll_timeout_ms_);
      if (rc < 0) {
        if (errno == EINTR) continue;
        throw std::runtime_error("poll failed");
      }
      if (rc == 0)
        throw std::runtime_error(
            "data-plane poll timeout (" +
            std::to_string(poll_timeout_ms_ / 1000) +
            "s with no bytes moved; HVD_DATA_TIMEOUT_SECONDS to tune)");
      for (int i = 0; i < nfds; i++) {
        if ((fds[i].revents & (POLLERR | POLLHUP | POLLNVAL)) &&
            !(fds[i].revents & (POLLIN | POLLOUT))) {
          if (zc_pending > 0 && fds[i].fd == to.fd() &&
              TryReapZeroCopy(to, &zc_pending) > 0)
            continue;
          throw std::runtime_error("data-plane peer failed");
        }
        if ((fds[i].revents & POLLOUT) && sent < sn) {
          ssize_t k = WireSend(to, sp + sent, sn - sent, &zc_pending);
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
            throw std::runtime_error(
                std::string("data-plane send failed: ") + strerror(errno));
          if (k > 0) {
            sent += (size_t)k;
            to.note_tx((size_t)k);
          }
        }
        if ((fds[i].revents & POLLIN) && recvd < rn) {
          stat_wire_syscalls++;
          ssize_t k = ::recv(from.fd(), rp + recvd, rn - recvd, 0);
          if (k == 0) throw std::runtime_error("data-plane peer closed");
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
            throw std::runtime_error("data-plane recv failed");
          if (k > 0) recvd += (size_t)k;
          // Reduce every completed rblock-aligned run now, while the socket
          // buffers keep filling/draining underneath us. The final partial
          // block rides along as soon as the last byte lands.
          size_t bound = recvd == rn
                             ? rn
                             : delivered + (recvd - delivered) / rblock * rblock;
          if (bound > delivered) {
            on_block(delivered, bound - delivered);
            delivered = bound;
          }
        }
      }
    }
    ReapZeroCopy(to, &zc_pending);
  } catch (...) {
    to.SetNonBlocking(false);
    if (!same) from.SetNonBlocking(false);
    throw;
  }
  to.SetNonBlocking(false);
  if (!same) from.SetNonBlocking(false);
  stat_wire_ops++;
}

void DataPlane::FullDuplexVStream(
    Socket& to, std::vector<iovec>& sv, Socket& from, void* rbuf, size_t rn,
    size_t rblock, const std::function<void(size_t, size_t)>& on_block) {
  if (UringReady()) {
    std::vector<iovec> rv;
    if (rn) rv.push_back({rbuf, rn});
    UringDuplex(to, sv, from, rv, rblock, on_block);
    return;
  }
  size_t si = 0;
  while (si < sv.size() && sv[si].iov_len == 0) si++;
  size_t sleft = IovBytes(sv, si);
  uint8_t* rp = (uint8_t*)rbuf;
  size_t recvd = 0, delivered = 0;
  int zc_pending = 0;
  bool same = to.fd() == from.fd();
  to.SetNonBlocking(true);
  if (!same) from.SetNonBlocking(true);
  try {
    while (sleft > 0 || recvd < rn) {
      pollfd fds[2];
      int nfds = 0;
      if (same) {
        fds[0] = {to.fd(), 0, 0};
        if (sleft > 0) fds[0].events |= POLLOUT;
        if (recvd < rn) fds[0].events |= POLLIN;
        nfds = 1;
      } else {
        if (sleft > 0) fds[nfds++] = {to.fd(), POLLOUT, 0};
        if (recvd < rn) fds[nfds++] = {from.fd(), POLLIN, 0};
      }
      fault::Check("poll");
      lockdep::OnBlockingSyscall("poll");
      stat_wire_syscalls++;
      int rc = ::poll(fds, nfds, poll_timeout_ms_);
      if (rc < 0) {
        if (errno == EINTR) continue;
        throw std::runtime_error("poll failed");
      }
      if (rc == 0)
        throw std::runtime_error(
            "data-plane poll timeout (" +
            std::to_string(poll_timeout_ms_ / 1000) +
            "s with no bytes moved; HVD_DATA_TIMEOUT_SECONDS to tune)");
      for (int i = 0; i < nfds; i++) {
        if ((fds[i].revents & (POLLERR | POLLHUP | POLLNVAL)) &&
            !(fds[i].revents & (POLLIN | POLLOUT))) {
          if (zc_pending > 0 && fds[i].fd == to.fd() &&
              TryReapZeroCopy(to, &zc_pending) > 0)
            continue;
          throw std::runtime_error("data-plane peer failed");
        }
        if ((fds[i].revents & POLLOUT) && sleft > 0) {
          msghdr mh = {};
          mh.msg_iov = &sv[si];
          mh.msg_iovlen = std::min(sv.size() - si, (size_t)IOV_MAX);
          ssize_t k = WireSendMsg(to, &mh, sleft, &zc_pending);
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK &&
              errno != EINTR)
            throw std::runtime_error(
                std::string("data-plane send failed: ") + strerror(errno));
          if (k > 0) {
            IovAdvance(sv, &si, (size_t)k);
            sleft -= (size_t)k;
            to.note_tx((size_t)k);
          }
        }
        if ((fds[i].revents & POLLIN) && recvd < rn) {
          stat_wire_syscalls++;
          ssize_t k = ::recv(from.fd(), rp + recvd, rn - recvd, 0);
          if (k == 0) throw std::runtime_error("data-plane peer closed");
          if (k < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
            throw std::runtime_error("data-plane recv failed");
          if (k > 0) recvd += (size_t)k;
          size_t bound = recvd == rn
                             ? rn
                             : delivered + (recvd - delivered) / rblock * rblock;
          if (bound > delivered) {
            on_block(delivered, bound - delivered);
            delivered = bound;
          }
        }
      }
    }
    ReapZeroCopy(to, &zc_pending);
  } catch (...) {
    to.SetNonBlocking(false);
    if (!same) from.SetNonBlocking(false);
    throw;
  }
  to.SetNonBlocking(false);
  if (!same) from.SetNonBlocking(false);
  stat_wire_ops++;
}

void DataPlane::RingAllreduce(void* buf, int64_t nelem, DataType dtype,
                              ReduceOp op, const std::vector<int32_t>& members) {
  int m = (int)members.size();
  if (m <= 1 || nelem == 0) return;
  int my = IndexOf(members, rank_);
  Socket& next = peer(members[(my + 1) % m]);
  Socket& prev = peer(members[(my - 1 + m) % m]);
  size_t esz = DataTypeSize(dtype);
  auto lens = SplitChunks(nelem, m);
  auto off = Offsets(lens);
  uint8_t* p = (uint8_t*)buf;

  if (UseShm(members, nelem * (int64_t)esz)) {
    // Same-host ring over pointer handoffs: both phases consume the
    // peer's slot in place (reduce into the owned chunk, then copy the
    // finished chunk) — no scratch buffer, no socket copies.
    int64_t t0 = MonoUs();
    int to = members[(my + 1) % m], from = members[(my - 1 + m) % m];
    for (int s = 0; s < m - 1; s++) {
      int sc = ((my - s) % m + m) % m;
      int rc = ((my - s - 1) % m + m) % m;
      uint8_t* dst = p + off[rc] * esz;
      bool ok = shm_.Exchange(
          to, p + off[sc] * esz, lens[sc] * (int64_t)esz, from,
          lens[rc] * (int64_t)esz, poll_timeout_ms_,
          [&](const uint8_t* ptr, int64_t len, int64_t boff) {
            PoolAccumulate(dst + boff, ptr, (int64_t)(len / esz), dtype, op);
          });
      if (!ok) throw std::runtime_error("shm allreduce exchange failed");
    }
    for (int s = 0; s < m - 1; s++) {
      int sc = ((my + 1 - s) % m + m) % m;
      int rc = ((my - s) % m + m) % m;
      uint8_t* dst = p + off[rc] * esz;
      bool ok = shm_.Exchange(
          to, p + off[sc] * esz, lens[sc] * (int64_t)esz, from,
          lens[rc] * (int64_t)esz, poll_timeout_ms_,
          [&](const uint8_t* ptr, int64_t len, int64_t boff) {
            memcpy(dst + boff, ptr, (size_t)len);
          });
      if (!ok) throw std::runtime_error("shm allreduce exchange failed");
    }
    stat_shm_us += MonoUs() - t0;
    return;
  }

  int64_t max_len = *std::max_element(lens.begin(), lens.end());
  // Persistent scratch, registered with the uring as a fixed buffer — on
  // the batched tier each receive into it is an IORING_OP_READ_FIXED.
  uint8_t* tmp = Scratch((size_t)max_len * esz);

  // Phase 1: reduce-scatter. After m-1 steps, member i owns the complete
  // reduction of chunk (i+1) mod m. When the pipeline is on, each received
  // chunk streams through Accumulate sub-block by sub-block from inside the
  // poll loop, overlapping reduction of block k with the transfer of k+1.
  for (int s = 0; s < m - 1; s++) {
    int sc = ((my - s) % m + m) % m;
    int rc = ((my - s - 1) % m + m) % m;
    size_t rbytes = (size_t)lens[rc] * esz;
    size_t block = StreamBlockBytes(rbytes, esz);
    if (block == 0) {
      FullDuplex(next, p + off[sc] * esz, (size_t)lens[sc] * esz, prev, tmp,
                 rbytes);
      PoolAccumulate(p + off[rc] * esz, tmp, lens[rc], dtype, op);
      stat_serial_steps++;
    } else {
      uint8_t* dst = p + off[rc] * esz;
      FullDuplexStream(next, p + off[sc] * esz, (size_t)lens[sc] * esz, prev,
                       tmp, rbytes, block,
                       [&](size_t boff, size_t blen) {
                         int64_t t0 = MonoUs();
                         PoolAccumulate(dst + boff, tmp + boff,
                                        (int64_t)(blen / esz), dtype, op);
                         stat_overlap_us += MonoUs() - t0;
                         stat_stream_blocks++;
                       });
      stat_stream_steps++;
    }
  }
  // Phase 2: allgather of completed chunks.
  for (int s = 0; s < m - 1; s++) {
    int sc = ((my + 1 - s) % m + m) % m;
    int rc = ((my - s) % m + m) % m;
    FullDuplex(next, p + off[sc] * esz, (size_t)lens[sc] * esz, prev,
               p + off[rc] * esz, (size_t)lens[rc] * esz);
  }
}

void DataPlane::RingAllreduceSG(const std::vector<Segment>& in,
                                const std::vector<Segment>& out,
                                int64_t nelem, DataType dtype, ReduceOp op,
                                const std::vector<int32_t>& members) {
  int m = (int)members.size();
  size_t esz = DataTypeSize(dtype);
  if (nelem == 0) return;
  if (m <= 1) {
    // Reduction of a single contribution is the contribution itself.
    for (size_t i = 0; i < in.size(); i++)
      if (out[i].base != in[i].base && in[i].elems > 0)
        memcpy(out[i].base, in[i].base, (size_t)in[i].elems * esz);
    return;
  }
  int my = IndexOf(members, rank_);
  Socket& next = peer(members[(my + 1) % m]);
  Socket& prev = peer(members[(my - 1 + m) % m]);
  auto lens = SplitChunks(nelem, m);
  auto off = Offsets(lens);
  int64_t max_len = *std::max_element(lens.begin(), lens.end());
  uint8_t* tmp = Scratch((size_t)max_len * esz);
  std::vector<iovec> sv, rv;

  // Phase 1: reduce-scatter. Each chunk is RS-touched exactly once per
  // rank (rc walks my-1, my-2, ... — never my), so the reduction of the
  // received scratch with the INPUT chunk lands directly in the OUTPUT
  // chunk (three-address first touch: no input->output bulk copy). Step 0
  // therefore sends untouched input; later steps send the partials already
  // reduced into the output segments.
  for (int s = 0; s < m - 1; s++) {
    int sc = ((my - s) % m + m) % m;
    int rc = ((my - s - 1) % m + m) % m;
    sv.clear();
    rv.clear();
    SliceIov(s == 0 ? in : out, off[sc], lens[sc], esz, &sv);
    size_t rbytes = (size_t)lens[rc] * esz;
    size_t block = StreamBlockBytes(rbytes, esz);
    if (block == 0) {
      rv.push_back({tmp, rbytes});
      FullDuplexV(next, sv, prev, rv);
      const uint8_t* t = tmp;
      ForEachSpan(in, out, off[rc], lens[rc], esz,
                  [&](uint8_t* o, const uint8_t* a, int64_t n) {
                    PoolAccumulateTo(o, a, t, n, dtype, op);
                    t += (size_t)n * esz;
                  });
      stat_serial_steps++;
    } else {
      // The SG receive side is already one contiguous chunk of scratch, so
      // the streamed variant reduces each completed sub-block through the
      // same three-address first-touch spans, shifted by the block offset.
      FullDuplexVStream(
          next, sv, prev, tmp, rbytes, block,
          [&](size_t boff, size_t blen) {
            int64_t t0 = MonoUs();
            const uint8_t* t = tmp + boff;
            ForEachSpan(in, out, off[rc] + (int64_t)(boff / esz),
                        (int64_t)(blen / esz), esz,
                        [&](uint8_t* o, const uint8_t* a, int64_t n) {
                          PoolAccumulateTo(o, a, t, n, dtype, op);
                          t += (size_t)n * esz;
                        });
            stat_overlap_us += MonoUs() - t0;
            stat_stream_blocks++;
          });
      stat_stream_steps++;
    }
  }
  // Phase 2: allgather of completed chunks, wired directly between output
  // segments on both sides (readv overwrites the stale RS partials).
  for (int s = 0; s < m - 1; s++) {
    int sc = ((my + 1 - s) % m + m) % m;
    int rc = ((my - s) % m + m) % m;
    sv.clear();
    rv.clear();
    SliceIov(out, off[sc], lens[sc], esz, &sv);
    SliceIov(out, off[rc], lens[rc], esz, &rv);
    FullDuplexV(next, sv, prev, rv);
  }
}

void DataPlane::HierarchicalAllreduce(void* buf, int64_t nelem,
                                      DataType dtype, ReduceOp op,
                                      const std::vector<int32_t>& members,
                                      int local_size) {
  int m = (int)members.size();
  if (m <= 1 || nelem == 0) return;
  int groups = local_size > 0 ? m / local_size : 0;
  // A single-host set (groups == 1) still benefits from the hierarchical
  // decomposition when the local phases ride the shm plane: reduce-scatter
  // + allgather over pointer handoffs, with a no-op cross phase. Without
  // shm it degenerates to extra memcpys, so fall back to the flat ring.
  size_t hesz = DataTypeSize(dtype);
  bool single_host_shm =
      groups == 1 && ShmRouted(members, nelem * (int64_t)hesz);
  if (local_size <= 1 || m % local_size != 0 || nelem < local_size ||
      (groups <= 1 && !single_host_shm)) {
    RingAllreduce(buf, nelem, dtype, op, members);
    return;
  }
  int my = IndexOf(members, rank_);
  int host = my / local_size;
  int lr = my % local_size;
  std::vector<int32_t> local(members.begin() + host * local_size,
                             members.begin() + (host + 1) * local_size);
  std::vector<int32_t> cross;
  cross.reserve(groups);
  for (int h = 0; h < groups; h++)
    cross.push_back(members[h * local_size + lr]);

  size_t esz = DataTypeSize(dtype);
  auto lens = SplitChunks(nelem, local_size);
  auto off = Offsets(lens);

  // 1) Local reduce-scatter: this rank finishes owning the local reduction
  //    of chunk lr (buf is scratch afterwards — rebuilt in phase 3).
  std::vector<uint8_t> chunk((size_t)lens[lr] * esz);
  RingReduceScatter(buf, chunk.data(), lens, dtype, op, local);
  // 2) Cross-plane allreduce of the owned shard: 1/local_size of the data
  //    rides the slow plane.
  RingAllreduce(chunk.data(), lens[lr], dtype, op, cross);
  // 3) Local allgather of the finished chunks.
  uint8_t* p = (uint8_t*)buf;
  memcpy(p + off[lr] * esz, chunk.data(), chunk.size());
  std::vector<int64_t> bytes(local_size);
  for (int i = 0; i < local_size; i++) bytes[i] = lens[i] * (int64_t)esz;
  RingAllgatherv(p + off[lr] * esz, p, bytes, local);
}

void DataPlane::RingAllgatherv(const void* my_data, void* out,
                               const std::vector<int64_t>& bytes_per_member,
                               const std::vector<int32_t>& members) {
  int m = (int)members.size();
  auto off = Offsets(bytes_per_member);
  int my = IndexOf(members, rank_);
  uint8_t* o = (uint8_t*)out;
  // Place own contribution.
  if (bytes_per_member[my] > 0 && my_data != o + off[my])
    memcpy(o + off[my], my_data, (size_t)bytes_per_member[my]);
  if (m <= 1) return;
  if (UseShm(members, off[m])) {
    int to = members[(my + 1) % m], from = members[(my - 1 + m) % m];
    int64_t t0 = MonoUs();
    for (int s = 0; s < m - 1; s++) {
      int sc = ((my - s) % m + m) % m;
      int rc = ((my - s - 1) % m + m) % m;
      uint8_t* dst = o + off[rc];
      bool ok = shm_.Exchange(
          to, o + off[sc], bytes_per_member[sc], from, bytes_per_member[rc],
          poll_timeout_ms_,
          [&](const uint8_t* ptr, int64_t len, int64_t boff) {
            // Slot-to-destination is the one required copy (the readv
            // equivalent); there is no staging buffer in between.
            memcpy(dst + boff, ptr, (size_t)len);
          });
      if (!ok) throw std::runtime_error("shm allgather exchange failed");
    }
    stat_shm_us += MonoUs() - t0;
    return;
  }
  Socket& next = peer(members[(my + 1) % m]);
  Socket& prev = peer(members[(my - 1 + m) % m]);
  // Ring: at step s, forward chunk (my - s) and receive chunk (my - s - 1).
  for (int s = 0; s < m - 1; s++) {
    int sc = ((my - s) % m + m) % m;
    int rc = ((my - s - 1) % m + m) % m;
    FullDuplex(next, o + off[sc], (size_t)bytes_per_member[sc], prev,
               o + off[rc], (size_t)bytes_per_member[rc]);
  }
}

void DataPlane::Broadcast(void* buf, int64_t nbytes, int root_idx,
                          const std::vector<int32_t>& members) {
  int m = (int)members.size();
  if (m <= 1 || nbytes == 0) return;
  int my = IndexOf(members, rank_);
  int vr = (my - root_idx + m) % m;  // rank relative to root
  int mask = 1;
  while (mask < m) {
    if (vr & mask) {
      int src = ((vr - mask + root_idx) % m + m) % m;
      peer(members[src]).RecvAll(buf, (size_t)nbytes);
      break;
    }
    mask <<= 1;
  }
  mask >>= 1;
  while (mask > 0) {
    if (vr + mask < m && !(vr & mask)) {
      int dst = (vr + mask + root_idx) % m;
      peer(members[dst]).SendAll(buf, (size_t)nbytes);
    }
    mask >>= 1;
  }
}

void DataPlane::AlltoAllv(const void* send,
                          const std::vector<int64_t>& send_bytes, void* out,
                          const std::vector<int64_t>& recv_bytes,
                          const std::vector<int32_t>& members) {
  int m = (int)members.size();
  auto soff = Offsets(send_bytes);
  auto roff = Offsets(recv_bytes);
  int my = IndexOf(members, rank_);
  const uint8_t* s = (const uint8_t*)send;
  uint8_t* o = (uint8_t*)out;
  stat_alltoall_ops++;
  stat_alltoall_bytes += soff[m] - send_bytes[my];
  // Self chunk never touches a tier.
  if (send_bytes[my] > 0)
    memcpy(o + roff[my], s + soff[my], (size_t)send_bytes[my]);
  if (m <= 1) return;
  // Intra-host tier: the whole pairwise schedule rides the shm rings —
  // each step's payload is a pointer handoff through the peer's mapped
  // slot, the consume callback lands bytes straight in the packed output
  // (same shape as the RingAllgatherv shm branch).
  if (alltoall_tiered_ && UseShm(members, soff[m] + roff[m])) {
    stat_alltoall_shm++;
    int64_t t0 = MonoUs();
    for (int k = 1; k < m; k++) {
      int to_idx = (my + k) % m;
      int from_idx = (my - k + m) % m;
      uint8_t* dst = o + roff[from_idx];
      bool ok = shm_.Exchange(
          members[to_idx], s + soff[to_idx], send_bytes[to_idx],
          members[from_idx], recv_bytes[from_idx], poll_timeout_ms_,
          [&](const uint8_t* ptr, int64_t len, int64_t boff) {
            memcpy(dst + boff, ptr, (size_t)len);
          });
      if (!ok) throw std::runtime_error("shm alltoallv exchange failed");
    }
    stat_shm_us += MonoUs() - t0;
    return;
  }
  // Pairwise exchange with increasing offset.
  for (int k = 1; k < m; k++) {
    int to_idx = (my + k) % m;
    int from_idx = (my - k + m) % m;
    size_t sn = (size_t)send_bytes[to_idx];
    size_t rn = (size_t)recv_bytes[from_idx];
    // SG linked-wave rung: at or above the scatter-gather threshold the
    // step goes straight to UringDuplex with a block-streamed receive —
    // rblock > 0 plus the single contiguous receive iovec engage
    // chain_mode, so the whole step is chained MSG_WAITALL waves with the
    // short-completion repair, not the per-round poll/readv dance.
    if (alltoall_tiered_ && UringReady() &&
        (int64_t)(sn + rn) >= zc_threshold_) {
      stat_alltoall_sg++;
      std::vector<iovec> sv, rv;
      if (sn > 0) sv.push_back({(void*)(s + soff[to_idx]), sn});
      if (rn > 0) rv.push_back({o + roff[from_idx], rn});
      size_t rblock = rn > 0 ? StreamBlockBytes(rn, 1) : 0;
      UringDuplex(peer(members[to_idx]), sv, peer(members[from_idx]), rv,
                  rblock, {});
      continue;
    }
    FullDuplex(peer(members[to_idx]), s + soff[to_idx], sn,
               peer(members[from_idx]), o + roff[from_idx], rn);
  }
}

void DataPlane::RingReduceScatter(void* work, void* out,
                                  const std::vector<int64_t>& chunk_elems,
                                  DataType dtype, ReduceOp op,
                                  const std::vector<int32_t>& members) {
  int m = (int)members.size();
  int my = IndexOf(members, rank_);
  size_t esz = DataTypeSize(dtype);
  auto off = Offsets(chunk_elems);
  uint8_t* p = (uint8_t*)work;
  if (m == 1) {
    if (chunk_elems[0] > 0) memcpy(out, p, (size_t)chunk_elems[0] * esz);
    return;
  }
  int64_t total = 0;
  for (int64_t c : chunk_elems) total += c;
  if (UseShm(members, total * (int64_t)esz)) {
    // Host-plane path: the received sub-chunk is reduced straight out of
    // the peer's mapped slot (pointer handoff), sharded across the reduce
    // pool — no scratch buffer, no socket copies.
    int to = members[(my + 1) % m], from = members[(my - 1 + m) % m];
    int64_t t0 = MonoUs();
    for (int s = 0; s < m - 1; s++) {
      int sc = ((my - s - 1) % m + m) % m;
      int rc = ((my - s - 2) % m + m) % m;
      uint8_t* dst = p + off[rc] * esz;
      bool ok = shm_.Exchange(
          to, p + off[sc] * esz, chunk_elems[sc] * (int64_t)esz, from,
          chunk_elems[rc] * (int64_t)esz, poll_timeout_ms_,
          [&](const uint8_t* ptr, int64_t len, int64_t boff) {
            PoolAccumulate(dst + boff, ptr, len / (int64_t)esz, dtype, op);
          });
      if (!ok) throw std::runtime_error("shm reduce-scatter exchange failed");
    }
    stat_shm_us += MonoUs() - t0;
    if (chunk_elems[my] > 0)
      memcpy(out, p + off[my] * esz, (size_t)chunk_elems[my] * esz);
    return;
  }
  Socket& next = peer(members[(my + 1) % m]);
  Socket& prev = peer(members[(my - 1 + m) % m]);
  int64_t max_len = *std::max_element(chunk_elems.begin(), chunk_elems.end());
  uint8_t* tmp = Scratch((size_t)max_len * esz);
  // Shifted reduce-scatter so member i finishes owning chunk i: at step s,
  // send chunk (i - s - 1) and reduce into chunk (i - s - 2).
  for (int s = 0; s < m - 1; s++) {
    int sc = ((my - s - 1) % m + m) % m;
    int rc = ((my - s - 2) % m + m) % m;
    FullDuplex(next, p + off[sc] * esz, (size_t)chunk_elems[sc] * esz, prev,
               tmp, (size_t)chunk_elems[rc] * esz);
    PoolAccumulate(p + off[rc] * esz, tmp, chunk_elems[rc], dtype, op);
  }
  if (chunk_elems[my] > 0)
    memcpy(out, p + off[my] * esz, (size_t)chunk_elems[my] * esz);
}

}  // namespace hvd
