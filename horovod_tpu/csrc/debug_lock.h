// debug_lock.h — in-core lockdep: runtime lock-order and blocking-syscall
// checking for the core's mutexes.
//
// Modeled on the kernel's lockdep: every instrumented mutex belongs to a
// *lock class* keyed by the name passed at construction (all TensorQueue
// instances share one class, etc.). On each acquisition the checker records
// a directed edge from every class currently held by this thread to the
// class being acquired; an edge that would close a cycle in that graph is a
// potential deadlock (an AB-BA inversion) and is reported instead of added.
// The TCP plane additionally calls OnBlockingSyscall() before send/recv/
// poll/accept/connect so any instrumented lock held across a blocking
// syscall is flagged — a lock held while a peer stalls wedges the whole
// background loop.
//
// Enabled by HVD_LOCKDEP=1 at load time, or by default in a `make debug`
// build (-DHVD_DEBUG, where HVD_LOCKDEP=0 still force-disables). When off,
// the only cost is one latched-bool branch per lock operation. Findings are
// surfaced through hvd_lockdep_stats()/hvd_lockdep_report() (core.cc) and
// hvd.lockdep_stats() in Python. docs/static_analysis.md has the usage
// guide; hvd_lockdep_selftest() seeds a deterministic AB-BA inversion for
// the negative test.
#pragma once

#include <atomic>
#include <cstdio>
#include <map>
#include <mutex>
#include <set>
#include <string>
#include <vector>

#include "logging.h"

namespace hvd {
namespace lockdep {

inline bool Enabled() {
  static const bool on = [] {
    const char* v = EnvRaw("HVD_LOCKDEP");
#ifdef HVD_DEBUG
    return !(v && v[0] == '0');
#else
    return v && v[0] == '1';
#endif
  }();
  return on;
}

struct State {
  // Raw std::mutex on purpose: the checker's own lock must never be
  // instrumented (it nests inside every tracked acquisition).
  std::mutex mu;
  // edges[a] contains b  <=>  some thread acquired class b while holding a.
  std::map<std::string, std::set<std::string>> edges;
  std::vector<std::string> violations;  // human-readable, deduped
  std::set<std::string> dedupe;
  std::atomic<int64_t> cycles{0};        // lock-order inversions found
  std::atomic<int64_t> blocking{0};      // locks held across blocking syscalls
  std::atomic<int64_t> edge_count{0};    // distinct order edges observed
  std::atomic<int64_t> acquisitions{0};  // total instrumented acquisitions

  // Never destroyed: the core's own static destructors and its threads
  // still take tracked locks while the process exits.
  static State& Get() {
    static State* s = new State;
    return *s;
  }
};

// Stack of lock-class names currently held by this thread, in acquisition
// order. Unlock erases the *last matching* entry, not necessarily the top:
// the core occasionally releases out of LIFO order via unique_lock.
// Leaked with its thread for the same reason: a thread's locals are
// destroyed before the static destructors that run on it, and those lock.
inline std::vector<std::string>& Held() {
  thread_local std::vector<std::string>* held =
      new std::vector<std::string>;
  return *held;
}

// DFS: is `to` reachable from `from` in the recorded order graph?
inline bool Reachable(const std::map<std::string, std::set<std::string>>& g,
                      const std::string& from, const std::string& to,
                      std::set<std::string>& seen) {
  if (from == to) return true;
  if (!seen.insert(from).second) return false;
  auto it = g.find(from);
  if (it == g.end()) return false;
  for (const auto& next : it->second)
    if (Reachable(g, next, to, seen)) return true;
  return false;
}

inline void AddViolation(State& s, const std::string& key,
                         const std::string& msg) {
  if (!s.dedupe.insert(key).second) return;
  s.violations.push_back(msg);
  fprintf(stderr, "[hvd lockdep] %s\n", msg.c_str());
}

// Called BEFORE the real mutex::lock() so an inversion is reported even when
// the acquisition would actually deadlock.
inline void PreAcquire(const char* name) {
  auto& held = Held();
  if (held.empty()) return;
  State& s = State::Get();
  std::lock_guard<std::mutex> g(s.mu);
  for (const auto& h : held) {
    if (h == name) continue;  // same-class re-entry is TSAN's problem, not ours
    auto& out = s.edges[h];
    if (out.count(name)) continue;  // edge already known (and known-acyclic)
    std::set<std::string> seen;
    if (Reachable(s.edges, name, h, seen)) {
      // Adding h->name would close a cycle: name ~> h already exists, so
      // some other thread can take them in the opposite order. Report, and
      // keep the graph acyclic so later DFS stays meaningful.
      s.cycles.fetch_add(1, std::memory_order_relaxed);
      AddViolation(s, "cycle:" + h + ":" + name,
                   "lock-order inversion: acquiring \"" + std::string(name) +
                       "\" while holding \"" + h + "\", but \"" + name +
                       "\" -> ... -> \"" + h +
                       "\" was already observed (potential deadlock)");
      continue;
    }
    out.insert(name);
    s.edge_count.fetch_add(1, std::memory_order_relaxed);
  }
}

// Called after the real lock is held.
inline void PostAcquire(const char* name) {
  State::Get().acquisitions.fetch_add(1, std::memory_order_relaxed);
  Held().push_back(name);
}

inline void OnRelease(const char* name) {
  auto& held = Held();
  for (auto it = held.rbegin(); it != held.rend(); ++it) {
    if (*it == name) {
      held.erase(std::next(it).base());
      return;
    }
  }
}

// TCP plane hook: `what` names the syscall about to block (send/recv/poll/
// accept/connect). Any instrumented lock held here can stall every other
// thread that wants it for as long as the peer takes.
inline void OnBlockingSyscall(const char* what) {
  if (!Enabled()) return;
  auto& held = Held();
  if (held.empty()) return;
  State& s = State::Get();
  std::string joined;
  for (const auto& h : held) {
    if (!joined.empty()) joined += ", ";
    joined += "\"" + h + "\"";
  }
  std::lock_guard<std::mutex> g(s.mu);
  s.blocking.fetch_add(1, std::memory_order_relaxed);
  AddViolation(s, "syscall:" + std::string(what) + ":" + joined,
               "lock(s) held across blocking " + std::string(what) + "(): " +
                   joined);
}

}  // namespace lockdep

// Drop-in replacement for std::mutex on the core's tracked locks. Meets
// Lockable, so std::lock_guard<DebugMutex>, std::unique_lock<DebugMutex>
// and std::condition_variable_any all work unchanged.
class DebugMutex {
 public:
  explicit DebugMutex(const char* name) : name_(name) {}
  DebugMutex(const DebugMutex&) = delete;
  DebugMutex& operator=(const DebugMutex&) = delete;

  void lock() {
    if (lockdep::Enabled()) lockdep::PreAcquire(name_);
    mu_.lock();
    if (lockdep::Enabled()) lockdep::PostAcquire(name_);
  }

  bool try_lock() {
    if (!mu_.try_lock()) return false;
    if (lockdep::Enabled()) {
      lockdep::PreAcquire(name_);
      lockdep::PostAcquire(name_);
    }
    return true;
  }

  void unlock() {
    if (lockdep::Enabled()) lockdep::OnRelease(name_);
    mu_.unlock();
  }

  const char* name() const { return name_; }

 private:
  std::mutex mu_;
  const char* name_;
};

}  // namespace hvd
