// A handle that outlives its engine (engine.cc, raftlog.cc).
//
// The C ABI hands Python a `Handle<T>*`, never a `T*`.  The handle is never
// freed, so whoever kept a copy (a snapshot, a cursor, a thread that a
// stopping store gave up joining) can pass it in at any time:
//
//   * a call in flight when close is called finishes normally: every entry
//     point holds `mu` shared for its whole body, and `take` waits for them
//     with `mu` held exclusively before the engine is freed;
//   * a call made after close finds `eng == nullptr` and returns kClosed,
//     which the Python binding raises as EngineClosed;
//   * a second close takes nullptr and does nothing.
//
// `closing` turns new calls away before they queue on `mu`: glibc's rwlock
// prefers readers, and a cursor stepping in a loop on two threads would
// otherwise keep a closer waiting for as long as they overlap.

#pragma once

#include <atomic>
#include <mutex>
#include <shared_mutex>

namespace guard {

// the one error code every guarded entry point shares; unsigned returns
// (sequence numbers, byte counts) say it as their type's maximum
constexpr int kClosed = -9;

template <class T>
struct Handle {
  std::shared_mutex mu;
  std::atomic<bool> closing{false};
  T* eng;
  explicit Handle(T* e) : eng(e) {}
};

// One call in flight: the engine, or nullptr once it is closed.
template <class T>
class Ref {
 public:
  explicit Ref(void* h) {
    auto* g = static_cast<Handle<T>*>(h);
    if (g == nullptr || g->closing.load(std::memory_order_acquire)) return;
    lk_ = std::shared_lock<std::shared_mutex>(g->mu);
    e_ = g->eng;
  }
  T* get() const { return e_; }

 private:
  std::shared_lock<std::shared_mutex> lk_;
  T* e_ = nullptr;
};

// Close: waits for every call in flight, then hands the engine to the
// caller to free.  nullptr when it was taken before.
template <class T>
T* take(void* h) {
  auto* g = static_cast<Handle<T>*>(h);
  if (g == nullptr) return nullptr;
  g->closing.store(true, std::memory_order_release);
  std::unique_lock<std::shared_mutex> lk(g->mu);
  T* e = g->eng;
  g->eng = nullptr;
  return e;
}

}  // namespace guard

// The first line of an entry point `f(void* h, ...)`: `e` is the engine for
// the length of the call, or the call returns `closed`.
#define GUARD_OR(T, closed)  \
  guard::Ref<T> ref_(h);     \
  T* e = ref_.get();         \
  if (e == nullptr) return closed
