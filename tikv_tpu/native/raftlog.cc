// raftlog.cc — purpose-built, append-optimized raft log store.
//
// The role of the reference's raft-engine crate (selected at
// components/server/src/server.rs:153-157, trait surface
// components/raft_log_engine/src/engine.rs:25): raft log entries and hard
// state live in segmented append-only files with GROUP-COMMIT fdatasync,
// logical purge markers instead of range deletes, and rewrite of live tail
// records out of mostly-dead segments so old files can be unlinked.  This is
// deliberately NOT the LSM in engine.cc — an LSM pays sorted-run machinery
// (memtable ordering, run merges, bloom filters) for point-lookup workloads
// the raft log never has: the log is written append-only in index order and
// read back only as contiguous ranges (catch-up) or sequentially (recovery).
//
// On-disk format, per segment file "%010u.rlog":
//   record  := crc32(u32, over type+payload) | len(u32, payload bytes) |
//              type(u8) | payload
//   ENTRIES := region(u64) | first_index(u64) | count(u32) |
//              count x len(u32) | count x blob        (type 1)
//   STATE   := region(u64) | blob                     (type 2)
//   PURGE   := region(u64) | to(u64)                  (type 3)
//   CLEAN   := region(u64)                            (type 4)
//   REWRITE := same payload as ENTRIES                (type 5)
//
// Replay rules (which make crash recovery a pure left fold over segments):
//   ENTRIES  truncates any indexed suffix >= first_index, then appends —
//            the raft conflict-truncation rule, applied at the storage layer.
//   REWRITE  replaces the stored location of indexes it already holds and is
//            otherwise ignored — relocation only, never truncation, so a
//            rewrite record replayed after a later conflicting append cannot
//            resurrect dead entries.
//   PURGE    drops indexed entries <= to.
//   CLEAN    forgets the region entirely.
// A torn record at the tail of the LAST segment is truncated (crash mid
// append); corruption anywhere else fails open() loudly.
//
// Concurrency: appends serialize on wmu (one writer to the active file);
// index updates take mu exclusively but are O(batch); readers (fetch/term
// queries) take mu shared and pread segment files through shared_ptr-held
// fds, so a concurrent segment unlink never yanks a file out from under a
// reader.  fdatasync is group-committed: every waiter whose append landed
// before the in-flight fsync started piggybacks on it; the rest elect one
// new syncer (sync_done covers all appends <= the covered sequence).

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <shared_mutex>
#include <string>
#include <unordered_map>
#include <vector>

#include <dirent.h>
#include <fcntl.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <unistd.h>

#include "crypt.h"
#include "guard.h"

namespace {

// ---------------------------------------------------------------------------
// crc32 (IEEE, table-driven) — same polynomial engine.cc uses, re-derived
// here so the two libraries stay independently buildable.
// ---------------------------------------------------------------------------

uint32_t crc_table[256];
struct CrcInit {
  CrcInit() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      crc_table[i] = c;
    }
  }
} crc_init;

uint32_t crc32(const uint8_t* p, size_t n, uint32_t seed = 0) {
  uint32_t c = seed ^ 0xFFFFFFFFu;
  for (size_t i = 0; i < n; i++) c = crc_table[(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------------------
// little-endian scalar IO on byte buffers
// ---------------------------------------------------------------------------

void put_u32(std::string& b, uint32_t v) { b.append(reinterpret_cast<const char*>(&v), 4); }
void put_u64(std::string& b, uint64_t v) { b.append(reinterpret_cast<const char*>(&v), 8); }
uint32_t get_u32(const uint8_t* p) { uint32_t v; memcpy(&v, p, 4); return v; }
uint64_t get_u64(const uint8_t* p) { uint64_t v; memcpy(&v, p, 8); return v; }

constexpr uint8_t REC_ENTRIES = 1;
constexpr uint8_t REC_STATE = 2;
constexpr uint8_t REC_PURGE = 3;
constexpr uint8_t REC_CLEAN = 4;
constexpr uint8_t REC_REWRITE = 5;
constexpr size_t REC_HDR = 9;  // crc(4) + len(4) + type(1)

struct Seg {
  uint32_t id;
  int fd;
  enc::FileKey fk;  // per-segment encryption (sidecar-derived)
  explicit Seg(uint32_t i, int f) : id(i), fd(f) {}
  ~Seg() {
    if (fd >= 0) close(fd);
  }
  Seg(const Seg&) = delete;
  Seg& operator=(const Seg&) = delete;
};

struct Loc {
  uint32_t seg;
  uint32_t off;  // byte offset of the entry blob within the segment file
  uint32_t len;
};

struct RegionIdx {
  uint64_t first = 0;  // raft index of locs.front(); meaningless when empty
  std::deque<Loc> locs;
  std::string state;     // latest hard-state blob (served from memory)
  uint32_t state_seg = 0;  // segment holding the latest STATE record (0=none)
  bool has_state = false;
  uint64_t last() const { return first + locs.size() - 1; }
};

int fsync_dir(const std::string& dir) {
  int fd = open(dir.c_str(), O_RDONLY | O_DIRECTORY);
  if (fd < 0) return -1;
  int r = fsync(fd);
  close(fd);
  return r;
}

struct RaftLogEng {
  enc::State enc;  // data-key registry (DataKeyManager over the FFI)
  std::string dir;
  uint64_t seg_bytes;
  int sync_default;          // 1 = grouped fdatasync per append, 0 = buffered
  uint32_t rewrite_max;      // rewrite a dead-ish segment holding <= this many live entries

  std::shared_mutex mu;      // index + segment map
  std::mutex wmu;            // file appends (one writer to the active file)
  std::map<uint32_t, std::shared_ptr<Seg>> segs;
  uint32_t active = 0;
  std::atomic<uint64_t> active_size{0};
  std::unordered_map<uint64_t, RegionIdx> regions;
  std::unordered_map<uint32_t, uint64_t> live;  // live entry count per segment

  // group fsync state
  std::mutex smu;
  std::condition_variable scv;
  uint64_t append_seq = 0;   // bumped under wmu after each append lands
  uint64_t sync_done = 0;    // all appends <= this are fdatasync-durable
  bool syncing = false;

  // stats
  uint64_t rewrites = 0;
  uint64_t purged_entries = 0;

  std::string err;

  // ---- segment lifecycle (wmu held) ----
  std::string seg_path(uint32_t id) const {
    char name[32];
    snprintf(name, sizeof(name), "%010u.rlog", id);
    return dir + "/" + name;
  }

  bool roll_segment() {
    // finish the old active: its bytes must be durable before the new file
    // supersedes it, otherwise sync_done (which a later fsync of the NEW
    // file advances past them) would lie about them
    if (active != 0) {
      auto it = segs.find(active);
      if (it != segs.end()) fdatasync(it->second->fd);
      std::lock_guard<std::mutex> lk(smu);
      sync_done = append_seq;
    }
    uint32_t id = active + 1;
    enc::FileKey fk;
    if (enc::file_begin(enc, seg_path(id), &fk) != 0) {
      err = "encryption sidecar write failed: " + seg_path(id);
      return false;
    }
    int fd = open(seg_path(id).c_str(), O_CREAT | O_RDWR | O_APPEND, 0644);
    if (fd < 0) {
      err = "open segment failed: " + seg_path(id);
      return false;
    }
    fsync_dir(dir);
    std::unique_lock<std::shared_mutex> lk(mu);
    auto seg = std::make_shared<Seg>(id, fd);
    seg->fk = fk;
    segs.emplace(id, seg);
    active = id;
    active_size = 0;
    return true;
  }

  // append one framed record; returns payload offset in the active segment
  // or UINT64_MAX on IO error.  wmu held.
  uint64_t write_record(uint8_t type, const std::string& payload) {
    if (active == 0 || active_size >= seg_bytes) {
      if (!roll_segment()) return UINT64_MAX;
    }
    std::string frame;
    frame.reserve(REC_HDR + payload.size());
    uint32_t crc = crc32(&type, 1);
    crc = crc32(reinterpret_cast<const uint8_t*>(payload.data()), payload.size(), crc);
    put_u32(frame, crc);
    put_u32(frame, static_cast<uint32_t>(payload.size()));
    frame.push_back(static_cast<char>(type));
    frame += payload;
    int fd;
    enc::FileKey fk;
    {
      // gc can erase other map nodes under mu concurrently; the active
      // segment itself is never a gc victim, but the map needs the lock
      std::shared_lock<std::shared_mutex> lk(mu);
      fd = segs[active]->fd;
      fk = segs[active]->fk;
    }
    enc::maybe_xor(fk, active_size, &frame[0], frame.size());
    const char* p = frame.data();
    size_t left = frame.size();
    while (left > 0) {
      ssize_t w = write(fd, p, left);
      if (w < 0) {
        err = "segment write failed";
        return UINT64_MAX;
      }
      p += w;
      left -= static_cast<size_t>(w);
    }
    uint64_t payload_off = active_size + REC_HDR;
    active_size += frame.size();
    return payload_off;
  }

  // group-commit: wait until everything appended up to my_seq is fsynced,
  // doing the fsync ourselves if no in-flight sync will cover it.
  void sync_to(uint64_t my_seq) {
    std::unique_lock<std::mutex> lk(smu);
    for (;;) {
      if (sync_done >= my_seq) return;
      if (!syncing) break;
      scv.wait(lk);
    }
    syncing = true;
    // everything appended so far rides this fsync (the group)
    uint64_t covered = append_seq;
    lk.unlock();
    // mu is never taken while smu is held (ABBA guard: rl_stats and this
    // function both order mu -> smu / smu-released -> mu).  A roll between
    // the capture above and the pread of `active` fsyncs the old file, so
    // fsyncing whatever is active NOW still covers every append <= covered.
    std::shared_ptr<Seg> s;
    {
      std::shared_lock<std::shared_mutex> ilk(mu);
      auto it = segs.find(active);
      if (it != segs.end()) s = it->second;
    }
    if (s) fdatasync(s->fd);
    lk.lock();
    syncing = false;
    if (covered > sync_done) sync_done = covered;
    scv.notify_all();
  }

  // ---- index mutation (mu exclusive) ----

  void index_append(uint64_t region, uint64_t first_index, uint32_t count,
                    const uint32_t* lens, uint64_t blob_base, uint32_t seg) {
    RegionIdx& ri = regions[region];
    if (!ri.locs.empty()) {
      if (first_index <= ri.last()) {
        // conflict truncation: drop indexed suffix >= first_index
        uint64_t keep = first_index > ri.first ? first_index - ri.first : 0;
        while (ri.locs.size() > keep) {
          live[ri.locs.back().seg]--;
          ri.locs.pop_back();
        }
      }
      // a gap (first_index > last+1) only happens after snapshot-install
      // purged everything; with a non-empty deque it means corruption
      if (!ri.locs.empty() && first_index != ri.last() + 1) {
        // defensive: reset to the new contiguous run
        for (const Loc& l : ri.locs) live[l.seg]--;
        ri.locs.clear();
      }
    }
    if (ri.locs.empty()) ri.first = first_index;
    uint64_t off = blob_base;
    for (uint32_t i = 0; i < count; i++) {
      ri.locs.push_back(Loc{seg, static_cast<uint32_t>(off), lens[i]});
      off += lens[i];
    }
    live[seg] += count;
  }

  // REWRITE semantics: relocate indexes we already hold, and (re)insert
  // contiguously-adjacent ones we don't — after gc unlinks the victim
  // segment, a REWRITE record in a later segment is the ONLY copy of those
  // entries on replay, and they may sit BELOW the region's current first
  // (their original record died with the victim).  Never truncates, so a
  // rewrite replayed after a conflicting append cannot resurrect a dead
  // suffix; non-contiguous leftovers (purged later in the record stream
  // than this rewrite was written) are dropped by the PURGE replay anyway.
  void index_rewrite(uint64_t region, uint64_t first_index, uint32_t count,
                     const uint32_t* lens, uint64_t blob_base, uint32_t seg) {
    if (count == 0) return;
    std::vector<uint64_t> offs(count);
    uint64_t off = blob_base;
    for (uint32_t i = 0; i < count; i++) {
      offs[i] = off;
      off += lens[i];
    }
    RegionIdx& ri = regions[region];
    if (ri.locs.empty()) {
      ri.first = first_index;
      for (uint32_t i = 0; i < count; i++)
        ri.locs.push_back(Loc{seg, static_cast<uint32_t>(offs[i]), lens[i]});
      live[seg] += count;
      return;
    }
    uint64_t lo = ri.first;  // portion below this prepends (descending pass)
    for (int64_t i = static_cast<int64_t>(count) - 1; i >= 0; i--) {
      uint64_t idx = first_index + static_cast<uint64_t>(i);
      if (idx >= lo) continue;
      if (idx == ri.first - 1) {
        ri.locs.push_front(Loc{seg, static_cast<uint32_t>(offs[i]), lens[i]});
        ri.first--;
        live[seg]++;
      }  // else: non-adjacent below-front — unreachable entry, drop
    }
    for (uint32_t i = 0; i < count; i++) {
      uint64_t idx = first_index + i;
      if (idx < lo) continue;  // handled (or dropped) above
      if (idx <= ri.last()) {
        Loc& l = ri.locs[idx - ri.first];
        live[l.seg]--;
        l = Loc{seg, static_cast<uint32_t>(offs[i]), lens[i]};
        live[seg]++;
      } else if (idx == ri.last() + 1) {
        ri.locs.push_back(Loc{seg, static_cast<uint32_t>(offs[i]), lens[i]});
        live[seg]++;
      }
    }
  }

  void index_purge(uint64_t region, uint64_t to) {
    auto it = regions.find(region);
    if (it == regions.end()) return;
    RegionIdx& ri = it->second;
    while (!ri.locs.empty() && ri.first <= to) {
      live[ri.locs.front().seg]--;
      ri.locs.pop_front();
      ri.first++;
      purged_entries++;
    }
  }

  void index_clean(uint64_t region) {
    auto it = regions.find(region);
    if (it == regions.end()) return;
    for (const Loc& l : it->second.locs) live[l.seg]--;
    regions.erase(it);
  }

  // ---- segment GC: unlink dead segments, rewrite nearly-dead ones ----

  struct RewritePlan {
    uint64_t region;
    uint64_t first_index;
    std::vector<Loc> locs;  // contiguous run living in the victim segment
  };

  // Re-check a plan against the live index (caller holds wmu, takes mu
  // shared): every planned index must still point at exactly the loc we
  // preread, else a concurrent conflict-truncating append replaced those
  // entries and writing the stale REWRITE record would poison replay.
  bool plan_still_valid(const RewritePlan& p) {
    std::shared_lock<std::shared_mutex> lk(mu);
    auto it = regions.find(p.region);
    if (it == regions.end() || it->second.locs.empty()) return false;
    const RegionIdx& ri = it->second;
    for (size_t i = 0; i < p.locs.size(); i++) {
      uint64_t idx = p.first_index + i;
      if (idx < ri.first || idx > ri.last()) return false;
      const Loc& cur = ri.locs[idx - ri.first];
      const Loc& old = p.locs[i];
      if (cur.seg != old.seg || cur.off != old.off || cur.len != old.len) return false;
    }
    return true;
  }

  // Decide what (if anything) to do about the oldest segment.  Returns:
  // 0 = nothing, 1 = deleted it, 2 = caller should run `plans` rewrites.
  int gc_step(std::vector<RewritePlan>& plans, std::vector<uint64_t>& state_regions) {
    std::unique_lock<std::shared_mutex> lk(mu);
    if (segs.size() <= 1) return 0;
    uint32_t victim = segs.begin()->first;
    if (victim == active) return 0;
    uint64_t nlive = 0;
    auto lit = live.find(victim);
    if (lit != live.end()) nlive = lit->second;
    bool state_pinned = false;
    for (auto& [rid, ri] : regions) {
      if (ri.has_state && ri.state_seg == victim) {
        state_pinned = true;
        state_regions.push_back(rid);
      }
    }
    if (nlive == 0 && !state_pinned) {
      std::string path = seg_path(victim);
      segs.erase(victim);  // shared_ptr: open readers keep the fd alive
      live.erase(victim);
      lk.unlock();
      unlink(path.c_str());
      unlink(enc::sidecar_path(path).c_str());
      fsync_dir(dir);
      return 1;
    }
    if (nlive > rewrite_max) return 0;
    // collect contiguous runs of victim-resident entries per region
    for (auto& [rid, ri] : regions) {
      uint64_t idx = ri.first;
      RewritePlan cur{rid, 0, {}};
      for (const Loc& l : ri.locs) {
        if (l.seg == victim) {
          if (cur.locs.empty()) cur.first_index = idx;
          if (!cur.locs.empty() && cur.first_index + cur.locs.size() != idx) {
            plans.push_back(std::move(cur));
            cur = RewritePlan{rid, idx, {}};
          }
          cur.locs.push_back(l);
        } else if (!cur.locs.empty()) {
          plans.push_back(std::move(cur));
          cur = RewritePlan{rid, 0, {}};
        }
        idx++;
      }
      if (!cur.locs.empty()) plans.push_back(std::move(cur));
    }
    return 2;
  }

  bool pread_exact(const std::shared_ptr<Seg>& s, uint64_t off, uint32_t len, uint8_t* out) {
    ssize_t r = pread(s->fd, out, len, static_cast<off_t>(off));
    if (r != static_cast<ssize_t>(len)) return false;
    enc::maybe_xor(s->fk, off, out, len);
    return true;
  }

  // run the GC loop after a purge/clean.  Never holds mu across file IO.
  void gc() {
    for (int guard = 0; guard < 64; guard++) {
      std::vector<RewritePlan> plans;
      std::vector<uint64_t> state_regions;
      int what = gc_step(plans, state_regions);
      if (what == 0) return;
      if (what == 1) continue;  // deleted one; try the next oldest
      // rewrite: copy live records out of the victim into the active seg
      bool wrote_any = false;
      for (const RewritePlan& p : plans) {
        std::shared_ptr<Seg> src;
        {
          std::shared_lock<std::shared_mutex> lk(mu);
          auto it = segs.find(p.locs[0].seg);
          if (it == segs.end()) continue;  // raced with delete
          src = it->second;
        }
        std::string payload;
        put_u64(payload, p.region);
        put_u64(payload, p.first_index);
        put_u32(payload, static_cast<uint32_t>(p.locs.size()));
        std::vector<uint32_t> lens;
        lens.reserve(p.locs.size());
        for (const Loc& l : p.locs) {
          put_u32(payload, l.len);
          lens.push_back(l.len);
        }
        size_t blobs_at = payload.size();
        size_t total = 0;
        for (const Loc& l : p.locs) total += l.len;
        payload.resize(blobs_at + total);
        uint8_t* dst = reinterpret_cast<uint8_t*>(&payload[blobs_at]);
        bool ok = true;
        for (const Loc& l : p.locs) {
          if (!pread_exact(src, l.off, l.len, dst)) {
            ok = false;
            break;
          }
          dst += l.len;
        }
        if (!ok) return;  // IO error: leave the segment alone
        std::lock_guard<std::mutex> wlk(wmu);
        // a conflicting append may have replaced these indexes between plan
        // capture and now; appends serialize on wmu, so a validation here
        // stays true through the write below.  Abort the whole plan on any
        // change — the next purge re-plans from fresh state.
        if (!plan_still_valid(p)) continue;
        uint64_t payload_off = write_record(REC_REWRITE, payload);
        if (payload_off == UINT64_MAX) return;
        wrote_any = true;
        uint32_t seg_now;
        {
          std::unique_lock<std::shared_mutex> lk(mu);
          seg_now = active;
          index_rewrite(p.region, p.first_index, static_cast<uint32_t>(lens.size()),
                        lens.data(), payload_off + 20 + 4 * lens.size(), seg_now);
        }
        std::lock_guard<std::mutex> slk(smu);
        append_seq++;
      }
      // re-home pinned states (served from memory; just re-emit)
      for (uint64_t rid : state_regions) {
        std::string blob;
        {
          std::shared_lock<std::shared_mutex> lk(mu);
          auto it = regions.find(rid);
          if (it == regions.end() || !it->second.has_state) continue;
          blob = it->second.state;
        }
        std::string payload;
        put_u64(payload, rid);
        payload += blob;
        std::lock_guard<std::mutex> wlk(wmu);
        if (write_record(REC_STATE, payload) == UINT64_MAX) return;
        wrote_any = true;
        {
          std::unique_lock<std::shared_mutex> lk(mu);
          auto it = regions.find(rid);
          if (it != regions.end()) it->second.state_seg = active;
        }
        std::lock_guard<std::mutex> slk(smu);
        append_seq++;
      }
      rewrites++;
      if (wrote_any) {
        // the relocated records MUST be durable before the next gc_step
        // unlinks their only other copy — regardless of sync_default, since
        // unlink itself is immediately durable (fsync_dir)
        uint64_t seq;
        {
          std::lock_guard<std::mutex> slk(smu);
          seq = append_seq;
        }
        sync_to(seq);
      }
      // loop: next gc_step sees the victim fully dead and unlinks it
    }
  }

  // ---- replay ----

  bool replay_segment(uint32_t id, int fd, const enc::FileKey& fk, bool is_last) {
    struct stat st;
    if (fstat(fd, &st) != 0) {
      err = "fstat failed";
      return false;
    }
    uint64_t size = static_cast<uint64_t>(st.st_size);
    std::vector<uint8_t> buf(size);
    if (size > 0) {
      ssize_t r = pread(fd, buf.data(), size, 0);
      if (r != static_cast<ssize_t>(size)) {
        err = "segment read failed";
        return false;
      }
      enc::maybe_xor(fk, 0, buf.data(), size);
    }
    uint64_t pos = 0;
    while (pos + REC_HDR <= size) {
      uint32_t crc = get_u32(&buf[pos]);
      uint32_t len = get_u32(&buf[pos + 4]);
      uint8_t type = buf[pos + 8];
      if (pos + REC_HDR + len > size) break;  // torn tail
      uint32_t got = crc32(&buf[pos + 8], 1);
      got = crc32(&buf[pos + 9], len, got);
      if (got != crc) break;  // torn/corrupt tail
      const uint8_t* pl = &buf[pos + 9];
      uint64_t payload_off = pos + REC_HDR;
      switch (type) {
        case REC_ENTRIES:
        case REC_REWRITE: {
          if (len < 20) break;
          uint64_t region = get_u64(pl);
          uint64_t first_index = get_u64(pl + 8);
          uint32_t count = get_u32(pl + 16);
          if (20 + 4ull * count > len) break;
          std::vector<uint32_t> lens(count);
          for (uint32_t i = 0; i < count; i++) lens[i] = get_u32(pl + 20 + 4 * i);
          uint64_t blob_base = payload_off + 20 + 4ull * count;
          if (type == REC_ENTRIES)
            index_append(region, first_index, count, lens.data(), blob_base, id);
          else
            index_rewrite(region, first_index, count, lens.data(), blob_base, id);
          break;
        }
        case REC_STATE: {
          if (len < 8) break;
          uint64_t region = get_u64(pl);
          RegionIdx& ri = regions[region];
          ri.state.assign(reinterpret_cast<const char*>(pl + 8), len - 8);
          ri.state_seg = id;
          ri.has_state = true;
          break;
        }
        case REC_PURGE: {
          if (len < 16) break;
          index_purge(get_u64(pl), get_u64(pl + 8));
          break;
        }
        case REC_CLEAN: {
          if (len < 8) break;
          index_clean(get_u64(pl));
          break;
        }
        default:
          break;  // forward-compat: unknown record types are skipped
      }
      pos += REC_HDR + len;
    }
    if (pos < size) {
      if (!is_last) {
        char msg[96];
        snprintf(msg, sizeof(msg), "corrupt record in non-tail segment %u at offset %llu",
                 id, static_cast<unsigned long long>(pos));
        err = msg;
        return false;
      }
      if (ftruncate(fd, static_cast<off_t>(pos)) != 0) {
        err = "tail truncate failed";
        return false;
      }
    }
    if (is_last) active_size = pos;
    return true;
  }

  bool open_dir() {
    mkdir(dir.c_str(), 0755);
    std::vector<uint32_t> ids;
    DIR* d = opendir(dir.c_str());
    if (d == nullptr) {
      err = "opendir failed: " + dir;
      return false;
    }
    while (dirent* de = readdir(d)) {
      unsigned id = 0;
      if (sscanf(de->d_name, "%10u.rlog", &id) == 1 && id > 0) ids.push_back(id);
    }
    closedir(d);
    std::sort(ids.begin(), ids.end());
    for (size_t i = 0; i < ids.size(); i++) {
      enc::FileKey fk;
      if (enc::sidecar_read(enc, seg_path(ids[i]), &fk) < 0) {
        err = "unreadable encryption sidecar: " + seg_path(ids[i]);
        return false;
      }
      int fd = open(seg_path(ids[i]).c_str(), O_RDWR | O_APPEND);
      if (fd < 0) {
        err = "open segment failed: " + seg_path(ids[i]);
        return false;
      }
      auto seg = std::make_shared<Seg>(ids[i], fd);
      seg->fk = fk;
      segs.emplace(ids[i], seg);
      if (!replay_segment(ids[i], fd, fk, i + 1 == ids.size())) return false;
    }
    if (!ids.empty()) active = ids.back();
    return true;
  }
};

}  // namespace

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

extern "C" {

// Every handle that crosses the C ABI is a guard::Handle<RaftLogEng>
// (guard.h): never freed, so a call after rl_close is turned away with
// guard::kClosed, and a call in flight when rl_close comes holds the log
// until it returns.
#define RL_OR(closed) GUARD_OR(RaftLogEng, closed)

static enc::State rl_make_enc(uint32_t current_id, const uint32_t* ids,
                              const uint8_t* keys32, int n) {
  enc::State st;
  for (int i = 0; i < n; i++) {
    std::array<uint8_t, 32> k;
    memcpy(k.data(), keys32 + 32 * i, 32);
    st.keys[ids[i]] = k;
  }
  st.current = current_id;
  st.on = n > 0;
  return st;
}

void* rl_open_enc(const char* dir, uint64_t seg_bytes, int sync_default,
                  uint32_t rewrite_max, uint32_t current_id,
                  const uint32_t* ids, const uint8_t* keys32, int n,
                  char* errbuf, int errcap);

void* rl_open(const char* dir, uint64_t seg_bytes, int sync_default,
              uint32_t rewrite_max, char* errbuf, int errcap) {
  return rl_open_enc(dir, seg_bytes, sync_default, rewrite_max, 0, nullptr,
                     nullptr, 0, errbuf, errcap);
}

// Encrypted open (and the ONE open path — rl_open delegates with an empty
// registry): segments written from here on encrypt under current_id;
// existing segments decrypt per their sidecar; sidecar-less files read as
// plaintext (CF_RAFT-era migration continues to work).
void* rl_open_enc(const char* dir, uint64_t seg_bytes, int sync_default,
                  uint32_t rewrite_max, uint32_t current_id,
                  const uint32_t* ids, const uint8_t* keys32, int n,
                  char* errbuf, int errcap) {
  auto* e = new RaftLogEng();
  e->dir = dir;
  e->seg_bytes = seg_bytes ? seg_bytes : (64ull << 20);
  e->sync_default = sync_default;
  e->rewrite_max = rewrite_max ? rewrite_max : 4096;
  e->enc = rl_make_enc(current_id, ids, keys32, n);
  if (!e->open_dir()) {
    if (errbuf != nullptr && errcap > 0) {
      snprintf(errbuf, static_cast<size_t>(errcap), "%s", e->err.c_str());
    }
    delete e;
    return nullptr;
  }
  return new guard::Handle<RaftLogEng>(e);
}

// Data-key rotation on a running log: new segments use current_id.
int rl_set_encryption(void* h, uint32_t current_id, const uint32_t* ids,
                      const uint8_t* keys32, int n) {
  RL_OR(guard::kClosed);
  std::lock_guard<std::mutex> wlk(e->wmu);
  std::unique_lock<std::shared_mutex> lk(e->mu);
  e->enc = rl_make_enc(current_id, ids, keys32, n);
  return 0;
}

// Waits for the calls in flight, then frees the log; the handle stays.  A
// second close finds nothing to free.
void rl_close(void* h) { delete guard::take<RaftLogEng>(h); }

// Append `count` entries (concatenated blobs + lens) starting at first_index,
// optionally with a new hard-state blob, in ONE durable record batch.
int rl_append(void* h, uint64_t region, uint64_t first_index, uint32_t count,
              const uint8_t* blobs, const uint32_t* lens, const uint8_t* state,
              uint32_t state_len, int sync) {
  RL_OR(guard::kClosed);
  uint64_t my_seq;
  {
    std::lock_guard<std::mutex> wlk(e->wmu);
    uint64_t blob_base = 0;
    if (count > 0) {
      std::string payload;
      size_t total = 0;
      for (uint32_t i = 0; i < count; i++) total += lens[i];
      payload.reserve(20 + 4 * count + total);
      put_u64(payload, region);
      put_u64(payload, first_index);
      put_u32(payload, count);
      for (uint32_t i = 0; i < count; i++) put_u32(payload, lens[i]);
      payload.append(reinterpret_cast<const char*>(blobs), total);
      uint64_t payload_off = e->write_record(REC_ENTRIES, payload);
      if (payload_off == UINT64_MAX) return -1;
      blob_base = payload_off + 20 + 4ull * count;
    }
    uint32_t entry_seg = e->active;
    if (state != nullptr && state_len > 0) {
      std::string payload;
      put_u64(payload, region);
      payload.append(reinterpret_cast<const char*>(state), state_len);
      if (e->write_record(REC_STATE, payload) == UINT64_MAX) return -1;
    }
    {
      std::unique_lock<std::shared_mutex> lk(e->mu);
      if (count > 0) e->index_append(region, first_index, count, lens, blob_base, entry_seg);
      if (state != nullptr && state_len > 0) {
        RegionIdx& ri = e->regions[region];
        ri.state.assign(reinterpret_cast<const char*>(state), state_len);
        ri.state_seg = e->active;
        ri.has_state = true;
      }
    }
    std::lock_guard<std::mutex> slk(e->smu);
    my_seq = ++e->append_seq;
  }
  int want_sync = sync < 0 ? e->sync_default : sync;
  if (want_sync != 0) e->sync_to(my_seq);
  return 0;
}

int rl_put_state(void* h, uint64_t region, const uint8_t* blob, uint32_t len, int sync) {
  return rl_append(h, region, 0, 0, nullptr, nullptr, blob, len, sync);
}

int64_t rl_first_index(void* h, uint64_t region) {
  RL_OR(guard::kClosed);
  std::shared_lock<std::shared_mutex> lk(e->mu);
  auto it = e->regions.find(region);
  if (it == e->regions.end() || it->second.locs.empty()) return 0;
  return static_cast<int64_t>(it->second.first);
}

int64_t rl_last_index(void* h, uint64_t region) {
  RL_OR(guard::kClosed);
  std::shared_lock<std::shared_mutex> lk(e->mu);
  auto it = e->regions.find(region);
  if (it == e->regions.end() || it->second.locs.empty()) return 0;
  return static_cast<int64_t>(it->second.last());
}

// Bytes needed by rl_fetch for [lo, hi) — framing is idx(u64) + len(u32) + blob.
int64_t rl_fetch_size(void* h, uint64_t region, uint64_t lo, uint64_t hi) {
  RL_OR(guard::kClosed);
  std::shared_lock<std::shared_mutex> lk(e->mu);
  auto it = e->regions.find(region);
  if (it == e->regions.end() || it->second.locs.empty()) return 0;
  const RegionIdx& ri = it->second;
  uint64_t a = std::max(lo, ri.first), b = std::min(hi, ri.last() + 1);
  int64_t total = 0;
  for (uint64_t i = a; i < b; i++) total += 12 + ri.locs[i - ri.first].len;
  return total;
}

// Copy entries [lo, hi) into out as idx(u64)|len(u32)|blob frames.
// Returns the number of entries written, or -1 if cap is too small.
int64_t rl_fetch(void* h, uint64_t region, uint64_t lo, uint64_t hi, uint8_t* out,
                 uint64_t cap) {
  RL_OR(guard::kClosed);
  struct Piece {
    uint64_t idx;
    std::shared_ptr<Seg> seg;
    uint32_t off, len;
  };
  std::vector<Piece> pieces;
  {
    std::shared_lock<std::shared_mutex> lk(e->mu);
    auto it = e->regions.find(region);
    if (it == e->regions.end() || it->second.locs.empty()) return 0;
    const RegionIdx& ri = it->second;
    uint64_t a = std::max(lo, ri.first), b = std::min(hi, ri.last() + 1);
    uint64_t need = 0;
    for (uint64_t i = a; i < b; i++) need += 12 + ri.locs[i - ri.first].len;
    if (need > cap) return -1;
    pieces.reserve(b > a ? b - a : 0);
    for (uint64_t i = a; i < b; i++) {
      const Loc& l = ri.locs[i - ri.first];
      auto sit = e->segs.find(l.seg);
      if (sit == e->segs.end()) return -2;  // should not happen
      pieces.push_back(Piece{i, sit->second, l.off, l.len});
    }
  }
  // file IO outside the index lock; shared_ptr keeps unlinked files readable
  uint8_t* p = out;
  for (const Piece& pc : pieces) {
    memcpy(p, &pc.idx, 8);
    memcpy(p + 8, &pc.len, 4);
    if (pc.len > 0) {
      if (pread(pc.seg->fd, p + 12, pc.len, static_cast<off_t>(pc.off)) !=
          static_cast<ssize_t>(pc.len)) {
        return -2;
      }
      enc::maybe_xor(pc.seg->fk, pc.off, p + 12, pc.len);
    }
    p += 12 + pc.len;
  }
  return static_cast<int64_t>(pieces.size());
}

// Latest hard-state blob; returns its length, -1 if cap too small, -2 if none.
int rl_state(void* h, uint64_t region, uint8_t* out, uint32_t cap) {
  RL_OR(guard::kClosed);
  std::shared_lock<std::shared_mutex> lk(e->mu);
  auto it = e->regions.find(region);
  if (it == e->regions.end() || !it->second.has_state) return -2;
  const std::string& s = it->second.state;
  if (s.size() > cap) return -1;
  memcpy(out, s.data(), s.size());
  return static_cast<int>(s.size());
}

int rl_purge(void* h, uint64_t region, uint64_t to) {
  RL_OR(guard::kClosed);
  {
    std::lock_guard<std::mutex> wlk(e->wmu);
    std::string payload;
    put_u64(payload, region);
    put_u64(payload, to);
    if (e->write_record(REC_PURGE, payload) == UINT64_MAX) return -1;
    std::unique_lock<std::shared_mutex> lk(e->mu);
    e->index_purge(region, to);
    std::lock_guard<std::mutex> slk(e->smu);
    e->append_seq++;
  }
  e->gc();
  return 0;
}

int rl_clean(void* h, uint64_t region) {
  RL_OR(guard::kClosed);
  {
    std::lock_guard<std::mutex> wlk(e->wmu);
    std::string payload;
    put_u64(payload, region);
    if (e->write_record(REC_CLEAN, payload) == UINT64_MAX) return -1;
    std::unique_lock<std::shared_mutex> lk(e->mu);
    e->index_clean(region);
    std::lock_guard<std::mutex> slk(e->smu);
    e->append_seq++;
  }
  e->gc();
  return 0;
}

// All region ids with any indexed entries or state; returns count (caller
// re-calls with a bigger buffer when count > cap).
int64_t rl_regions(void* h, uint64_t* out, uint32_t cap) {
  RL_OR(guard::kClosed);
  std::shared_lock<std::shared_mutex> lk(e->mu);
  uint32_t n = 0;
  for (auto& [rid, ri] : e->regions) {
    if (ri.locs.empty() && !ri.has_state) continue;
    if (n < cap) out[n] = rid;
    n++;
  }
  return n;
}

int rl_sync(void* h) {
  RL_OR(guard::kClosed);
  uint64_t seq;
  {
    std::lock_guard<std::mutex> slk(e->smu);
    seq = e->append_seq;
  }
  e->sync_to(seq);
  return 0;
}

// segments | active_size | live_total | rewrites | purged | append_seq
int rl_stats(void* h, uint64_t* out6) {
  RL_OR(guard::kClosed);
  {
    std::shared_lock<std::shared_mutex> lk(e->mu);
    uint64_t live_total = 0;
    for (auto& [s, n] : e->live) live_total += n;
    out6[0] = e->segs.size();
    out6[1] = e->active_size;
    out6[2] = live_total;
    out6[3] = e->rewrites;
    out6[4] = e->purged_entries;
  }
  std::lock_guard<std::mutex> slk(e->smu);
  out6[5] = e->append_seq;
  return 0;
}

}  // extern "C"
