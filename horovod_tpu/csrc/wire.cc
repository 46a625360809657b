#include "wire.h"

#include <errno.h>
#include <linux/errqueue.h>
#include <netinet/in.h>
#include <poll.h>
#include <pthread.h>
#include <sched.h>
#include <stdio.h>
#include <string.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <unistd.h>

#include <atomic>
#include <vector>

#include "debug_lock.h"
#include "tcp.h"  // fault::Check

// The whole io_uring side compiles to stubs when the toolchain lacks the
// uapi header (or ships one too old for EXT_ARG bounded waits): Probe then
// reports at most kZeroCopy and the duplex engine never sees a valid ring.
#if defined(__linux__) && defined(__has_include)
#if __has_include(<linux/io_uring.h>) && __has_include(<linux/time_types.h>)
#include <linux/io_uring.h>
#include <linux/time_types.h>  // __kernel_timespec (EXT_ARG bounded waits)
#if defined(IORING_FEAT_EXT_ARG) && defined(IORING_ENTER_EXT_ARG) && \
    defined(__NR_io_uring_setup)
#define HVD_HAVE_URING 1
#endif
#endif
#endif

#ifndef SO_ZEROCOPY
#define SO_ZEROCOPY 60
#endif
#ifndef MSG_ZEROCOPY
#define MSG_ZEROCOPY 0x4000000
#endif
#ifndef SO_EE_ORIGIN_ZEROCOPY
#define SO_EE_ORIGIN_ZEROCOPY 5
#endif

namespace hvd {
namespace wire {

const char* TierName(int tier) {
  switch (tier) {
    case kUring:
      return "uring";
    case kZeroCopy:
      return "zerocopy";
    default:
      return "basic";
  }
}

int TierFromName(const char* name) {
  if (name == nullptr) return -1;
  if (strcmp(name, "uring") == 0) return kUring;
  if (strcmp(name, "zerocopy") == 0) return kZeroCopy;
  if (strcmp(name, "basic") == 0) return kBasic;
  return -1;  // "auto" and anything unrecognized
}

namespace {

// One MSG_ZEROCOPY send over a loopback pair, its completion reaped. A
// kernel can accept SO_ZEROCOPY and still fail the send (EINVAL) or never
// post the completion — the sandboxed kernel of the v5e chip machines does
// both — and the data plane then fails or stalls on its first large
// message. So the tier is offered only where it has been seen to work.
bool ZeroCopyRoundTrip() {
  int ls = ::socket(AF_INET, SOCK_STREAM, 0), c = -1, a = -1;
  bool ok = false;
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  socklen_t len = sizeof(addr);
  int one = 1;
  std::vector<char> buf(1 << 16);  // above the tier's default threshold
  do {
    if (ls < 0 || ::bind(ls, (sockaddr*)&addr, sizeof(addr)) != 0 ||
        ::listen(ls, 1) != 0 ||
        ::getsockname(ls, (sockaddr*)&addr, &len) != 0)
      break;
    c = ::socket(AF_INET, SOCK_STREAM, 0);
    fault::Check("connect");
    lockdep::OnBlockingSyscall("connect");
    if (c < 0 || ::connect(c, (sockaddr*)&addr, sizeof(addr)) != 0) break;
    a = ::accept(ls, nullptr, nullptr);
    if (a < 0 ||
        setsockopt(c, SOL_SOCKET, SO_ZEROCOPY, &one, sizeof(one)) != 0)
      break;
    if (::send(c, buf.data(), buf.size(), MSG_NOSIGNAL | MSG_ZEROCOPY) <= 0)
      break;
    // The completion is posted once the peer holds the bytes: drain them,
    // then wait (half a second at most) for the error queue.
    for (int tries = 0; tries < 50 && !ok; tries++) {
      while (::recv(a, buf.data(), buf.size(), MSG_DONTWAIT) > 0) {
      }
      pollfd pfd{c, 0, 0};  // error-queue readiness reports as POLLERR
      fault::Check("poll");
      lockdep::OnBlockingSyscall("poll");
      if (::poll(&pfd, 1, 10) <= 0) continue;
      uint8_t ctrl[128];
      msghdr mh{};
      mh.msg_control = ctrl;
      mh.msg_controllen = sizeof(ctrl);
      if (::recvmsg(c, &mh, MSG_ERRQUEUE | MSG_DONTWAIT) < 0) continue;
      for (cmsghdr* cm = CMSG_FIRSTHDR(&mh); cm; cm = CMSG_NXTHDR(&mh, cm)) {
        sock_extended_err ee;
        memcpy(&ee, CMSG_DATA(cm), sizeof(ee));
        if (ee.ee_errno == 0 && ee.ee_origin == SO_EE_ORIGIN_ZEROCOPY)
          ok = true;
      }
    }
  } while (false);
  for (int fd : {ls, c, a})
    if (fd >= 0) ::close(fd);
  return ok;
}

}  // namespace

int Probe(int want, int deny_mask, int64_t* probe_failures) {
  int got = kBasic;
  if (want >= kUring) {
    bool ok = false;
    if (!(deny_mask & (1 << kUring))) {
      Uring probe;
      ok = probe.Init(8);
    }
    if (ok)
      got = kUring;
    else if (probe_failures)
      (*probe_failures)++;
  }
  if (got < kZeroCopy && want >= kZeroCopy) {
    bool ok = false;
    if (!(deny_mask & (1 << kZeroCopy))) ok = ZeroCopyRoundTrip();
    if (ok)
      got = kZeroCopy;
    else if (probe_failures)
      (*probe_failures)++;
  }
  return got;
}

#ifdef HVD_HAVE_URING

namespace {

int UringSetup(unsigned entries, io_uring_params* p) {
  return (int)syscall(__NR_io_uring_setup, entries, p);
}

int UringRegister(int fd, unsigned op, const void* arg, unsigned nr) {
  return (int)syscall(__NR_io_uring_register, fd, op, arg, nr);
}

}  // namespace

bool Uring::Init(unsigned entries) {
  Close();
  io_uring_params p;
  memset(&p, 0, sizeof(p));
  int fd = UringSetup(entries, &p);
  if (fd < 0) return false;  // ENOSYS / EPERM (seccomp) / EMFILE
  // EXT_ARG is the bounded-wait mechanism (one syscall submits AND waits
  // with a timeout); without it the engine would need a second timeout SQE
  // per wait, so pre-5.11 kernels stay on the zerocopy/basic tiers.
  if (!(p.features & IORING_FEAT_EXT_ARG)) {
    ::close(fd);
    return false;
  }
  fd_ = fd;
  entries_ = p.sq_entries;
  sq_ring_len_ = p.sq_off.array + p.sq_entries * sizeof(unsigned);
  cq_ring_len_ = p.cq_off.cqes + p.cq_entries * sizeof(io_uring_cqe);
  if (p.features & IORING_FEAT_SINGLE_MMAP) {
    size_t len = sq_ring_len_ > cq_ring_len_ ? sq_ring_len_ : cq_ring_len_;
    sq_ring_ = mmap(nullptr, len, PROT_READ | PROT_WRITE,
                    MAP_SHARED | MAP_POPULATE, fd_, IORING_OFF_SQ_RING);
    if (sq_ring_ == MAP_FAILED) {
      sq_ring_ = nullptr;
      Close();
      return false;
    }
    sq_ring_len_ = cq_ring_len_ = len;
    cq_ring_ = sq_ring_;
  } else {
    sq_ring_ = mmap(nullptr, sq_ring_len_, PROT_READ | PROT_WRITE,
                    MAP_SHARED | MAP_POPULATE, fd_, IORING_OFF_SQ_RING);
    if (sq_ring_ == MAP_FAILED) {
      sq_ring_ = nullptr;
      Close();
      return false;
    }
    cq_ring_ = mmap(nullptr, cq_ring_len_, PROT_READ | PROT_WRITE,
                    MAP_SHARED | MAP_POPULATE, fd_, IORING_OFF_CQ_RING);
    if (cq_ring_ == MAP_FAILED) {
      cq_ring_ = nullptr;
      Close();
      return false;
    }
  }
  sqe_mem_len_ = p.sq_entries * sizeof(io_uring_sqe);
  sqe_mem_ = mmap(nullptr, sqe_mem_len_, PROT_READ | PROT_WRITE,
                  MAP_SHARED | MAP_POPULATE, fd_, IORING_OFF_SQES);
  if (sqe_mem_ == MAP_FAILED) {
    sqe_mem_ = nullptr;
    Close();
    return false;
  }
  uint8_t* sq = (uint8_t*)sq_ring_;
  sq_head_ = (unsigned*)(sq + p.sq_off.head);
  sq_tail_ = (unsigned*)(sq + p.sq_off.tail);
  sq_mask_ = (unsigned*)(sq + p.sq_off.ring_mask);
  sq_array_ = (unsigned*)(sq + p.sq_off.array);
  uint8_t* cq = (uint8_t*)cq_ring_;
  cq_head_ = (unsigned*)(cq + p.cq_off.head);
  cq_tail_ = (unsigned*)(cq + p.cq_off.tail);
  cq_mask_ = (unsigned*)(cq + p.cq_off.ring_mask);
  cqes_ = cq + p.cq_off.cqes;
  sqes_ = sqe_mem_;
  pending_ = 0;
  return true;
}

void Uring::Close() {
  if (sqe_mem_) munmap(sqe_mem_, sqe_mem_len_);
  if (cq_ring_ && cq_ring_ != sq_ring_) munmap(cq_ring_, cq_ring_len_);
  if (sq_ring_) munmap(sq_ring_, sq_ring_len_);
  sq_ring_ = cq_ring_ = sqe_mem_ = nullptr;
  sq_head_ = sq_tail_ = sq_mask_ = sq_array_ = nullptr;
  cq_head_ = cq_tail_ = cq_mask_ = nullptr;
  cqes_ = sqes_ = nullptr;
  if (fd_ >= 0) ::close(fd_);
  fd_ = -1;
  pending_ = 0;
  scratch_registered_ = false;
  scratch_base_ = nullptr;
  scratch_len_ = 0;
}

bool Uring::RegisterScratch(void* buf, size_t len) {
  if (!valid() || buf == nullptr || len == 0) return false;
  if (scratch_registered_) {
    UringRegister(fd_, IORING_UNREGISTER_BUFFERS, nullptr, 0);
    scratch_registered_ = false;
  }
  // Registered buffers charge RLIMIT_MEMLOCK; a denial here just means the
  // receive side uses READV instead of READ_FIXED.
  iovec iv{buf, len};
  if (UringRegister(fd_, IORING_REGISTER_BUFFERS, &iv, 1) < 0) return false;
  scratch_registered_ = true;
  scratch_base_ = buf;
  scratch_len_ = len;
  return true;
}

void* Uring::NextSqe() {
  unsigned head = __atomic_load_n(sq_head_, __ATOMIC_ACQUIRE);
  unsigned tail = *sq_tail_;
  if (tail - head >= entries_) return nullptr;
  unsigned idx = tail & *sq_mask_;
  io_uring_sqe* sqe = (io_uring_sqe*)sqes_ + idx;
  memset(sqe, 0, sizeof(*sqe));
  sq_array_[idx] = idx;
  __atomic_store_n(sq_tail_, tail + 1, __ATOMIC_RELEASE);
  pending_++;
  return sqe;
}

bool Uring::PushSendmsg(int fd, const msghdr* mh, uint64_t user_data,
                        bool async) {
  io_uring_sqe* sqe = (io_uring_sqe*)NextSqe();
  if (!sqe) return false;
  sqe->opcode = IORING_OP_SENDMSG;
  sqe->fd = fd;
  sqe->addr = (uint64_t)(uintptr_t)mh;
  sqe->len = 1;
  // MSG_WAITALL on a send: 5.19+ kernels retry short sends internally
  // (poll-armed), so the whole run completes as ONE CQE and user space
  // never has to resubmit a tail. Older kernels ignore it and may still
  // complete short — the duplex engine detects that and stays on its
  // conservative wait policy.
  sqe->msg_flags = MSG_NOSIGNAL | MSG_WAITALL;
  sqe->user_data = user_data;
  if (async) sqe->flags |= IOSQE_ASYNC;
  return true;
}

bool Uring::PushRecv(int fd, void* buf, unsigned len, int flags,
                     uint64_t user_data, bool link) {
  io_uring_sqe* sqe = (io_uring_sqe*)NextSqe();
  if (!sqe) return false;
  sqe->opcode = IORING_OP_RECV;
  sqe->fd = fd;
  sqe->addr = (uint64_t)(uintptr_t)buf;
  sqe->len = len;
  sqe->msg_flags = (uint32_t)flags;
  sqe->user_data = user_data;
  if (link) sqe->flags |= IOSQE_IO_LINK;
  return true;
}

bool Uring::PushRecvmsg(int fd, msghdr* mh, int flags, uint64_t user_data) {
  io_uring_sqe* sqe = (io_uring_sqe*)NextSqe();
  if (!sqe) return false;
  sqe->opcode = IORING_OP_RECVMSG;
  sqe->fd = fd;
  sqe->addr = (uint64_t)(uintptr_t)mh;
  sqe->len = 1;
  sqe->msg_flags = (uint32_t)flags;
  sqe->user_data = user_data;
  return true;
}

bool Uring::PushReadFixed(int fd, void* buf, unsigned len,
                          uint64_t user_data) {
  io_uring_sqe* sqe = (io_uring_sqe*)NextSqe();
  if (!sqe) return false;
  sqe->opcode = IORING_OP_READ_FIXED;
  sqe->fd = fd;
  sqe->addr = (uint64_t)(uintptr_t)buf;
  sqe->len = len;
  sqe->buf_index = 0;
  sqe->user_data = user_data;
  return true;
}

int Uring::SubmitAndWait(unsigned wait_nr, int timeout_ms) {
  unsigned to_submit = pending_;
  io_uring_getevents_arg arg;
  memset(&arg, 0, sizeof(arg));
  struct __kernel_timespec ts;
  ts.tv_sec = timeout_ms / 1000;
  ts.tv_nsec = (long long)(timeout_ms % 1000) * 1000000;
  arg.ts = (uint64_t)(uintptr_t)&ts;
  fault::Check("uring_enter");
  lockdep::OnBlockingSyscall("uring_enter");
  int rc = (int)syscall(__NR_io_uring_enter, fd_, to_submit, wait_nr,
                        IORING_ENTER_GETEVENTS | IORING_ENTER_EXT_ARG, &arg,
                        sizeof(arg));
  if (rc < 0) {
    // ETIME: the bounded wait expired — submission already happened (the
    // kernel submits before it sleeps), so the SQEs are consumed and the
    // caller decides whether zero completions means a stall. EINTR: same,
    // just woken early.
    if (errno == ETIME || errno == EINTR) {
      pending_ = 0;
      return (int)to_submit;
    }
    return -errno;
  }
  pending_ -= (unsigned)rc < pending_ ? (unsigned)rc : pending_;
  return rc;
}

bool Uring::PopCompletion(uint64_t* user_data, int32_t* res) {
  unsigned head = *cq_head_;
  unsigned tail = __atomic_load_n(cq_tail_, __ATOMIC_ACQUIRE);
  if (head == tail) return false;
  io_uring_cqe* cqe = (io_uring_cqe*)cqes_ + (head & *cq_mask_);
  *user_data = cqe->user_data;
  *res = cqe->res;
  __atomic_store_n(cq_head_, head + 1, __ATOMIC_RELEASE);
  return true;
}

unsigned Uring::SqRoom() const {
  if (fd_ < 0) return 0;
  unsigned head = __atomic_load_n(sq_head_, __ATOMIC_ACQUIRE);
  return entries_ - (*sq_tail_ - head);
}

#else  // !HVD_HAVE_URING

bool Uring::Init(unsigned) { return false; }
void Uring::Close() { fd_ = -1; }
bool Uring::RegisterScratch(void*, size_t) { return false; }
void* Uring::NextSqe() { return nullptr; }
bool Uring::PushSendmsg(int, const msghdr*, uint64_t, bool) {
  return false;
}
bool Uring::PushRecv(int, void*, unsigned, int, uint64_t, bool) {
  return false;
}
bool Uring::PushRecvmsg(int, msghdr*, int, uint64_t) { return false; }
bool Uring::PushReadFixed(int, void*, unsigned, uint64_t) { return false; }
int Uring::SubmitAndWait(unsigned, int) { return -ENOSYS; }
bool Uring::PopCompletion(uint64_t*, int32_t*) { return false; }
unsigned Uring::SqRoom() const { return 0; }

#endif  // HVD_HAVE_URING

}  // namespace wire

namespace numa {

namespace {

// Parse a sysfs cpulist ("0-3,8,10-11") into cpu ids.
std::vector<int> ParseCpuList(const char* s) {
  std::vector<int> out;
  const char* p = s;
  while (*p) {
    char* end = nullptr;
    long lo = strtol(p, &end, 10);
    if (end == p) break;
    long hi = lo;
    p = end;
    if (*p == '-') {
      hi = strtol(p + 1, &end, 10);
      if (end == p + 1) break;
      p = end;
    }
    for (long c = lo; c <= hi && c >= 0; c++) out.push_back((int)c);
    if (*p == ',') p++;
  }
  return out;
}

std::vector<int> ReadCpuListFile(const std::string& path) {
  FILE* f = fopen(path.c_str(), "r");
  if (!f) return {};
  char buf[4096];
  size_t n = fread(buf, 1, sizeof(buf) - 1, f);
  fclose(f);
  buf[n] = '\0';
  return ParseCpuList(buf);
}

std::vector<int> AffinityCpus() {
  cpu_set_t set;
  CPU_ZERO(&set);
  std::vector<int> out;
  if (sched_getaffinity(0, sizeof(set), &set) != 0) return out;
  for (int c = 0; c < CPU_SETSIZE; c++)
    if (CPU_ISSET(c, &set)) out.push_back(c);
  return out;
}

std::string RangeString(const std::vector<int>& cpus) {
  if (cpus.empty()) return "?";
  std::string out;
  size_t i = 0;
  while (i < cpus.size()) {
    size_t j = i;
    while (j + 1 < cpus.size() && cpus[j + 1] == cpus[j] + 1) j++;
    if (!out.empty()) out += ".";
    out += std::to_string(cpus[i]);
    if (j > i) out += "-" + std::to_string(cpus[j]);
    i = j + 1;
  }
  return out;
}

}  // namespace

int NodeCount() {
  auto nodes = ReadCpuListFile("/sys/devices/system/node/online");
  return nodes.empty() ? 1 : (int)nodes.size();
}

std::vector<int> NodeCpus(int node) {
  auto cpus = ReadCpuListFile("/sys/devices/system/node/node" +
                              std::to_string(node) + "/cpulist");
  auto allowed = AffinityCpus();
  if (cpus.empty()) return allowed;
  std::vector<int> out;
  for (int c : cpus)
    for (int a : allowed)
      if (a == c) {
        out.push_back(c);
        break;
      }
  return out.empty() ? allowed : out;
}

bool PinThisThread(const std::vector<int>& cpus) {
  if (cpus.empty()) return false;
  cpu_set_t set;
  CPU_ZERO(&set);
  for (int c : cpus)
    if (c >= 0 && c < CPU_SETSIZE) CPU_SET(c, &set);
  return pthread_setaffinity_np(pthread_self(), sizeof(set), &set) == 0;
}

bool BindMemory(void* p, size_t len, int node) {
#if defined(__linux__) && defined(__NR_mbind)
  if (p == nullptr || len == 0 || node < 0 || node >= 64) return false;
  // MPOL_BIND == 2 in the stable kernel ABI; spelled numerically so the
  // build needs no libnuma headers.
  unsigned long mask = 1UL << node;
  long rc = syscall(__NR_mbind, p, len, 2 /*MPOL_BIND*/, &mask,
                    sizeof(mask) * 8 + 1, 0);
  return rc == 0;
#else
  (void)p;
  (void)len;
  (void)node;
  return false;
#endif
}

std::string AffinityString() { return RangeString(AffinityCpus()); }

}  // namespace numa
}  // namespace hvd
