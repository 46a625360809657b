// shm.cc — intra-host shared-memory data plane (see shm.h).

#include "shm.h"

#include <fcntl.h>
#include <sched.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <chrono>
#include <cstring>
#include <new>

#include "auth.h"
#include "debug_lock.h"
#include "logging.h"
#include "wire.h"  // numa::BindMemory

namespace hvd {

namespace {

int64_t MonoUs() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// Escalating wait for the lock-free loops: spin, then yield, then sleep.
// Returns the updated spin count.
int Backoff(int spins) {
  if (spins < 64) {
    // busy spin
  } else if (spins < 256) {
    sched_yield();
  } else {
    struct timespec ts = {0, 100 * 1000};  // 100us
    nanosleep(&ts, nullptr);
  }
  return spins + 1;
}

}  // namespace

// SPSC ring control block. The producer publishes slot `head % nslots`
// (payload + len[] first, then a release store of head+1); the consumer
// acquires head, reduces straight out of the mapped slot, then release-
// stores tail+1 to return the slot. One writer, one reader per channel,
// so plain len[] slots are ordered by the head/tail atomics.
struct alignas(64) ShmPlane::Channel {
  std::atomic<uint64_t> head;
  std::atomic<uint64_t> tail;
  uint64_t len[ShmPlane::kMaxSlots];
};

// Segment header. `tag` is HmacSha256(job key, geometry + segment name):
// an attacher rejects a segment whose tag it can't reproduce, exactly as
// the TCP planes reject an unauthenticated dial (auth.h).
struct alignas(64) ShmPlane::Header {
  uint64_t magic;
  uint32_t version;
  uint32_t nslots;
  uint64_t slot_bytes;
  uint32_t nchannels;
  int32_t owner_rank;
  uint8_t tag[32];
  std::atomic<uint32_t> ready;     // owner stores 1 after init
  std::atomic<uint32_t> attached;  // validated attachers fetch_add
};

namespace {

// /dev/shm name for `rank`'s outbox: "/hvd_" + 16 hex chars of
// HMAC(key, "shm:<job_tag>:<rank>"). Keyed so concurrent jobs on one box
// can't collide, and so the name itself is unguessable without the
// secret.
std::string SegName(const std::vector<uint8_t>& key,
                    const std::string& job_tag, int rank) {
  std::string material = "shm:" + job_tag + ":" + std::to_string(rank);
  std::vector<uint8_t> mac = HmacSha256(
      key, reinterpret_cast<const uint8_t*>(material.data()),
      material.size());
  static const char* kHex = "0123456789abcdef";
  std::string name = "/hvd_";
  for (int i = 0; i < 8; i++) {
    name += kHex[mac[i] >> 4];
    name += kHex[mac[i] & 0xf];
  }
  return name;
}

// The authenticated header fields, serialized for the HMAC.
std::vector<uint8_t> TagMaterial(uint64_t magic, uint32_t version,
                                 uint32_t nslots, uint64_t slot_bytes,
                                 uint32_t nchannels, int32_t owner_rank,
                                 const std::string& name) {
  std::vector<uint8_t> m;
  auto put = [&m](const void* p, size_t n) {
    const uint8_t* b = static_cast<const uint8_t*>(p);
    m.insert(m.end(), b, b + n);
  };
  put(&magic, sizeof(magic));
  put(&version, sizeof(version));
  put(&nslots, sizeof(nslots));
  put(&slot_bytes, sizeof(slot_bytes));
  put(&nchannels, sizeof(nchannels));
  put(&owner_rank, sizeof(owner_rank));
  put(name.data(), name.size());
  return m;
}

size_t Align64(size_t n) { return (n + 63) & ~size_t(63); }

size_t ChannelsOff() { return Align64(sizeof(ShmPlane::Header)); }

size_t PayloadOff(int nchannels) {
  return Align64(ChannelsOff() + nchannels * sizeof(ShmPlane::Channel));
}

size_t SegmentLen(int nchannels, int nslots, int64_t slot_bytes) {
  return PayloadOff(nchannels) +
         (size_t)nchannels * nslots * (size_t)slot_bytes;
}

}  // namespace

ShmPlane::~ShmPlane() { Shutdown(); }

int ShmPlane::peer_index(int rank) const {
  for (size_t i = 0; i < host_ranks_.size(); i++)
    if (host_ranks_[i] == rank) return (int)i;
  return -1;
}

ShmPlane::Channel* ShmPlane::channel_at(int seg_index, int ch_index) {
  uint8_t* base = static_cast<uint8_t*>(segments_[seg_index].base);
  return reinterpret_cast<Channel*>(base + ChannelsOff()) + ch_index;
}

uint8_t* ShmPlane::slot_at(int seg_index, int ch_index, uint64_t seq) {
  uint8_t* base = static_cast<uint8_t*>(segments_[seg_index].base);
  size_t slot = (size_t)(seq % (uint64_t)nslots_);
  return base + PayloadOff((int)host_ranks_.size()) +
         ((size_t)ch_index * nslots_ + slot) * (size_t)slot_bytes_;
}

bool ShmPlane::Covers(const std::vector<int32_t>& members) const {
  if (!active_) return false;
  for (int m : members)
    if (peer_index(m) < 0) return false;
  return true;
}

bool ShmPlane::Init(int rank, const std::vector<int>& host_ranks,
                    const std::vector<uint8_t>& key,
                    const std::string& job_tag, int64_t slot_bytes,
                    int nslots, double timeout_s) {
  Shutdown();
  if (host_ranks.size() < 2 || key.empty()) return false;
  rank_ = rank;
  host_ranks_ = host_ranks;
  my_index_ = peer_index(rank);
  if (my_index_ < 0) return false;
  nslots_ = std::max(2, std::min(nslots, (int)kMaxSlots));
  slot_bytes_ = std::max<int64_t>(4096, (slot_bytes + 63) & ~int64_t(63));
  const int L = (int)host_ranks_.size();
  const size_t seg_len = SegmentLen(L, nslots_, slot_bytes_);
  segments_.assign(L, Segment{});
  const int64_t deadline = MonoUs() + (int64_t)(timeout_s * 1e6);

  // 1. Create our outbox. Unlink any stale name first (a crashed prior
  // job with the same secret+tag), then O_EXCL-create so two live ranks
  // can never share one segment.
  my_name_ = SegName(key, job_tag, rank_);
  shm_unlink(my_name_.c_str());
  int fd = shm_open(my_name_.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
  if (fd < 0) {
    LogF(LogLevel::kWarn, "shm: create %s failed: %s", my_name_.c_str(),
         strerror(errno));
    Shutdown();
    return false;
  }
  bool ok = ftruncate(fd, (off_t)seg_len) == 0;
  void* base = ok ? mmap(nullptr, seg_len, PROT_READ | PROT_WRITE,
                         MAP_SHARED, fd, 0)
                  : MAP_FAILED;
  close(fd);
  if (!ok || base == MAP_FAILED) {
    LogF(LogLevel::kWarn, "shm: map %s (%zu bytes) failed: %s",
         my_name_.c_str(), seg_len, strerror(errno));
    shm_unlink(my_name_.c_str());
    Shutdown();
    return false;
  }
  segments_[my_index_] = Segment{base, seg_len};
  // Bind our outbox to this rank's NUMA node (HVD_NUMA) before first touch,
  // so the pages the local peers read land next to the writer. Best-effort.
  if (numa_node_ >= 0) numa::BindMemory(base, seg_len, numa_node_);
  Header* h = new (base) Header();
  h->magic = kMagic;
  h->version = kVersion;
  h->nslots = (uint32_t)nslots_;
  h->slot_bytes = (uint64_t)slot_bytes_;
  h->nchannels = (uint32_t)L;
  h->owner_rank = rank_;
  std::vector<uint8_t> material =
      TagMaterial(h->magic, h->version, h->nslots, h->slot_bytes,
                  h->nchannels, h->owner_rank, my_name_);
  std::vector<uint8_t> tag =
      HmacSha256(key, material.data(), material.size());
  memcpy(h->tag, tag.data(), sizeof(h->tag));
  for (int c = 0; c < L; c++) new (channel_at(my_index_, c)) Channel();
  h->attached.store(0, std::memory_order_relaxed);
  h->ready.store(1, std::memory_order_release);

  // 2. Attach every peer's outbox, validating geometry + HMAC tag.
  lockdep::OnBlockingSyscall("shm-attach");
  for (int i = 0; i < L; i++) {
    if (i == my_index_) continue;
    std::string name = SegName(key, job_tag, host_ranks_[i]);
    int pfd = -1;
    int spins = 0;
    while ((pfd = shm_open(name.c_str(), O_RDWR, 0)) < 0) {
      if (errno != ENOENT || MonoUs() > deadline) {
        LogF(LogLevel::kWarn, "shm: open %s (rank %d) failed: %s",
             name.c_str(), host_ranks_[i], strerror(errno));
        Shutdown();
        return false;
      }
      spins = Backoff(spins);
    }
    // The name exists from the owner's shm_open, its length only from the
    // ftruncate after it: touching a mapping of the still-empty file is
    // SIGBUS. Wait for the full length before mapping.
    struct stat st{};
    spins = 0;
    while (fstat(pfd, &st) == 0 && (size_t)st.st_size < seg_len &&
           MonoUs() <= deadline)
      spins = Backoff(spins);
    if ((size_t)st.st_size < seg_len) {
      LogF(LogLevel::kWarn, "shm: peer %d's segment %s never reached %zu bytes",
           host_ranks_[i], name.c_str(), seg_len);
      close(pfd);
      Shutdown();
      return false;
    }
    void* pbase =
        mmap(nullptr, seg_len, PROT_READ | PROT_WRITE, MAP_SHARED, pfd, 0);
    close(pfd);
    if (pbase == MAP_FAILED) {
      LogF(LogLevel::kWarn, "shm: map peer %s failed: %s", name.c_str(),
           strerror(errno));
      Shutdown();
      return false;
    }
    segments_[i] = Segment{pbase, seg_len};
    Header* ph = static_cast<Header*>(pbase);
    spins = 0;
    while (ph->ready.load(std::memory_order_acquire) != 1) {
      if (MonoUs() > deadline) {
        LogF(LogLevel::kWarn, "shm: peer %d never became ready",
             host_ranks_[i]);
        Shutdown();
        return false;
      }
      spins = Backoff(spins);
    }
    std::vector<uint8_t> pm =
        TagMaterial(ph->magic, ph->version, ph->nslots, ph->slot_bytes,
                    ph->nchannels, ph->owner_rank, name);
    std::vector<uint8_t> want = HmacSha256(key, pm.data(), pm.size());
    if (ph->magic != kMagic || ph->version != kVersion ||
        ph->nslots != (uint32_t)nslots_ ||
        ph->slot_bytes != (uint64_t)slot_bytes_ ||
        ph->nchannels != (uint32_t)L ||
        ph->owner_rank != host_ranks_[i] ||
        memcmp(ph->tag, want.data(), sizeof(ph->tag)) != 0) {
      LogF(LogLevel::kWarn,
           "shm: segment %s failed authentication/geometry check",
           name.c_str());
      Shutdown();
      return false;
    }
    ph->attached.fetch_add(1, std::memory_order_acq_rel);
  }

  // 3. Once every peer holds a mapping of OUR segment, drop the name:
  // the memory lives as long as the mappings do, and a crash after this
  // point can't leak a /dev/shm entry.
  int spins = 0;
  while (h->attached.load(std::memory_order_acquire) != (uint32_t)(L - 1)) {
    if (MonoUs() > deadline) {
      LogF(LogLevel::kWarn, "shm: only %u/%d peers attached before timeout",
           h->attached.load(std::memory_order_relaxed), L - 1);
      Shutdown();
      return false;
    }
    spins = Backoff(spins);
  }
  shm_unlink(my_name_.c_str());
  active_ = true;
  LogF(LogLevel::kDebug,
       "shm: host plane up — %d ranks, %d slots x %lld bytes", L, nslots_,
       (long long)slot_bytes_);
  return true;
}

void ShmPlane::Shutdown() {
  for (Segment& s : segments_)
    if (s.base) munmap(s.base, s.len);
  segments_.clear();
  // Defensive: normally already unlinked at the end of Init; a failure
  // path between create and unlink lands here.
  if (!my_name_.empty()) shm_unlink(my_name_.c_str());
  my_name_.clear();
  host_ranks_.clear();
  active_ = false;
  my_index_ = -1;
}

bool ShmPlane::Exchange(int to_rank, const void* src, int64_t sendlen,
                        int from_rank, int64_t recvlen, int64_t timeout_ms,
                        const SpanFn& on_span) {
  if (!active_) return false;
  if (to_rank < 0 || sendlen < 0) sendlen = 0;
  if (from_rank < 0 || recvlen < 0) recvlen = 0;
  if (sendlen == 0 && recvlen == 0) return true;
  int to_idx = sendlen > 0 ? peer_index(to_rank) : -1;
  int from_idx = recvlen > 0 ? peer_index(from_rank) : -1;
  if ((sendlen > 0 && to_idx < 0) || (recvlen > 0 && from_idx < 0))
    return false;
  // A DebugMutex held across this loop would serialize the host plane
  // behind one rank's reduce — flag it exactly like a blocked read(2).
  lockdep::OnBlockingSyscall("shm-exchange");
  Channel* sc = sendlen > 0 ? channel_at(my_index_, to_idx) : nullptr;
  Channel* rc = recvlen > 0 ? channel_at(from_idx, my_index_) : nullptr;
  const int64_t deadline = MonoUs() + timeout_ms * 1000;
  int64_t sent = 0, recvd = 0;
  int spins = 0;
  // Interleaved non-blocking progress on both directions: never park on
  // the send side while the receive side has data (the FullDuplex
  // deadlock-freedom argument, minus the syscalls).
  while (sent < sendlen || recvd < recvlen) {
    bool progress = false;
    if (sent < sendlen) {
      uint64_t head = sc->head.load(std::memory_order_relaxed);
      uint64_t tail = sc->tail.load(std::memory_order_acquire);
      if (head - tail < (uint64_t)nslots_) {
        int64_t n = std::min<int64_t>(slot_bytes_, sendlen - sent);
        memcpy(slot_at(my_index_, to_idx, head),
               static_cast<const uint8_t*>(src) + sent, (size_t)n);
        sc->len[head % (uint64_t)nslots_] = (uint64_t)n;
        sc->head.store(head + 1, std::memory_order_release);
        sent += n;
        progress = true;
      }
    }
    if (recvd < recvlen) {
      uint64_t head = rc->head.load(std::memory_order_acquire);
      uint64_t tail = rc->tail.load(std::memory_order_relaxed);
      if (head != tail) {
        int64_t n = (int64_t)rc->len[tail % (uint64_t)nslots_];
        if (n <= 0 || n > recvlen - recvd) {
          LogF(LogLevel::kError,
               "shm: protocol violation from rank %d (%lld-byte slot, "
               "%lld expected)",
               from_rank, (long long)n, (long long)(recvlen - recvd));
          return false;
        }
        // Pointer handoff: the consumer reduces straight out of the
        // producer's slot — no staging buffer on this path.
        if (on_span) on_span(slot_at(from_idx, my_index_, tail), n, recvd);
        rc->tail.store(tail + 1, std::memory_order_release);
        recvd += n;
        progress = true;
      }
    }
    if (progress) {
      spins = 0;
      continue;
    }
    spins = Backoff(spins);
    if (spins > 256 && MonoUs() > deadline) {
      LogF(LogLevel::kError,
           "shm: exchange timeout (to=%d %lld/%lld, from=%d %lld/%lld)",
           to_rank, (long long)sent, (long long)sendlen, from_rank,
           (long long)recvd, (long long)recvlen);
      return false;
    }
  }
  stat_tx_ops++;
  stat_tx_bytes += sendlen + recvlen;
  return true;
}

}  // namespace hvd
