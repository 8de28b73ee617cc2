// Native ordered multi-CF storage engine.
//
// Plays the role RocksDB plays in the reference (components/engine_rocks):
// the storage medium under the engine-trait layer.  Design is a versioned
// ordered memtable (rocksdb-memtable-like): every write carries a sequence
// number; a snapshot is just a sequence, so snapshots are O(1) and never
// copy; iterators resolve the newest version <= snapshot per key.  Obsolete
// versions are compacted away once no live snapshot can see them.
//
// Durability (engine_rocks WAL + memtable flush, raft_log_engine's purpose
// built log): when opened on a directory, every committed write batch is
// appended to a CRC-framed write-ahead log (group commit: the batch IS the
// group) and fdatasync'd before the write call returns; a checkpoint spills
// the full visible state to an SST-like immutable file via atomic
// tmp+rename, after which older WAL segments are deleted.  Open() recovers
// the newest valid checkpoint then replays WAL segments, stopping at the
// first torn record (standard WAL semantics).
//
// Exposed as a C API consumed via ctypes (no pybind11 in this image).  Scans
// return length-prefixed buffers so one FFI crossing moves a whole range.

#include <dirent.h>
#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <optional>
#include <set>
#include <shared_mutex>
#include <string>
#include <vector>

#include "crypt.h"
#include "guard.h"

namespace {

struct Version {
  uint64_t seq;
  bool tombstone;
  std::string value;
};

// newest-first version chain per key
using Chain = std::vector<Version>;
using Table = std::map<std::string, Chain>;

constexpr int kNumCfs = 4;  // default, lock, write, raft

// crc32c (Castagnoli), table-driven — integrity check for WAL records and
// checkpoint bodies (the role rocksdb's kCRC32c block checksums play)
uint32_t crc32c_table[256];
struct CrcInit {
  CrcInit() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = c & 1 ? 0x82f63b78u ^ (c >> 1) : c >> 1;
      crc32c_table[i] = c;
    }
  }
} crc_init;

uint32_t crc32c(const uint8_t* p, size_t n, uint32_t crc = 0) {
  crc = ~crc;
  for (size_t i = 0; i < n; i++) crc = crc32c_table[(crc ^ p[i]) & 0xff] ^ (crc >> 8);
  return ~crc;
}

// a range delete as a first-class record (rocksdb DeleteRange shape): keys
// in [start, end) with version seq < this are masked.  Lives in the
// memtable's side list until flushed into a run; dies at a bottom-level
// merge once no snapshot can see below it.  Never expanded into per-key
// tombstones — a range delete is O(1) on the write path regardless of how
// many flushed keys it covers.
struct RangeTomb {
  std::string start, end;  // end exclusive
  uint64_t seq = 0;
};

// newest covering range-tombstone seq <= snap for `key`, 0 if none
uint64_t rtomb_covering(const std::vector<RangeTomb>& v, const std::string& key,
                        uint64_t snap) {
  uint64_t best = 0;
  for (const auto& rt : v)
    if (rt.seq <= snap && rt.seq > best && rt.start <= key && key < rt.end)
      best = rt.seq;
  return best;
}

// one immutable sorted-run file on disk (the LSM level structure rocksdb's
// SSTs provide, engine_rocks/src/ + properties.rs): block-partitioned sorted
// (key, seq, tomb, value) entries with a first-key block index and a bloom
// filter, loaded at open; data blocks pread on demand (OS page cache is the
// block cache)
struct Run {
  std::string path;
  int fd = -1;
  int cf = 0;
  int kind = 0;  // 0 = memtable flush, 1 = full-cf merge output
  uint64_t max_seq = 0;   // every version in this run has seq <= max_seq
  uint64_t n_entries = 0;
  struct Block {
    uint64_t off;
    uint32_t len;
    uint32_t crc;
    std::string first_key;
  };
  std::vector<Block> blocks;
  std::vector<uint64_t> bloom;  // bit words; empty = no filter
  uint32_t bloom_k = 0;
  std::vector<RangeTomb> rtombs;  // range deletes flushed with this run
  enc::FileKey fk;                // per-file encryption (sidecar-derived)
  ~Run() { if (fd >= 0) close(fd); }
};

// per-read statistics (engine_rocks/src/perf_context.rs role)
struct Perf {
  std::atomic<uint64_t> gets{0};
  std::atomic<uint64_t> memtable_hits{0};
  std::atomic<uint64_t> run_probes{0};   // run consulted for a point read
  std::atomic<uint64_t> bloom_skips{0};  // run skipped by its bloom filter
  std::atomic<uint64_t> blocks_read{0};  // data blocks pread + crc-checked
  std::atomic<uint64_t> flushes{0};
  std::atomic<uint64_t> run_merges{0};
};

struct Engine {
  Table cfs[kNumCfs];
  uint64_t seq = 0;
  // per CF, the seq of the newest batch that put, deleted or range-deleted
  // in it: set where the batch is applied, so under the same unique mu that
  // publishes seq, and never above it for a reader under mu.  "Has this CF
  // changed between two snapshots" is then two integers, not a scan (the
  // region column cache's lock check, docs/write_path.md).  Flushes, merges
  // and compaction change where versions live, not what a snapshot reads,
  // and leave it alone.
  uint64_t cf_touched_seq[kNumCfs] = {};
  std::multiset<uint64_t> snapshots;
  mutable std::shared_mutex mu;
  // Writer serialization, SEPARATE from mu: the WAL append + fdatasync —
  // the slow part of every commit — runs under write_mu only, so readers
  // (shared mu) never stall behind a disk sync; mu is then taken unique
  // just for the in-memory apply + seq publish.  Lock order: write_mu
  // before mu, always.  WAL state (wal_fd/sync_mode/failed) is guarded by
  // write_mu; memtables/runs/seq/snapshots stay under mu.
  std::mutex write_mu;
  // sorted runs per CF, NEWEST FIRST: all versions in runs[cf][i] are newer
  // than any in runs[cf][i+1], and the memtable is newer than every run
  std::vector<std::shared_ptr<Run>> runs[kNumCfs];
  std::vector<RangeTomb> mem_rtombs[kNumCfs];  // unflushed range deletes
  uint64_t flushed_seq = 0;          // all state <= this lives in runs
  uint64_t mem_limit = 256ull << 20; // memtable flush threshold; 0 = manual
  std::mutex compact_mu;             // one run-merge at a time
  Perf perf;

  // --- durability state (empty dir => pure in-memory engine) ---
  std::string dir;        // "" = in-memory
  int wal_fd = -1;
  int sync_mode = 1;      // 0 = buffered, 1 = fdatasync per commit
  uint64_t wal_bytes = 0;         // bytes in the live WAL segment
  uint64_t wal_off = 0;           // absolute file offset (encryption stream)
  // data keys (fed by the DataKeyManager FFI).  Guarded by enc_mu: rotation
  // runs concurrently with background compaction's writer setup
  enc::State enc;
  mutable std::mutex enc_mu;
  enc::FileKey wal_key;           // live WAL segment's file key

  enc::State enc_snapshot() const {
    std::lock_guard<std::mutex> lk(enc_mu);
    return enc;
  }
  uint64_t wal_limit = 64ull << 20;  // auto-checkpoint threshold; 0 = manual
  uint64_t mem_bytes = 0;         // approximate key+value bytes resident
  bool failed = false;  // a WAL append failed mid-record: the log tail is
                        // torn, so further appends could shadow-lose acked
                        // writes — refuse everything (rocksdb read-only mode)

  uint64_t min_live_snapshot() const {
    return snapshots.empty() ? UINT64_MAX : *snapshots.begin();
  }
};

// tri-state resolve: MISS means "no version visible here, consult older
// sources (runs)"; TOMB stops the lookup (the delete masks older sources).
// out_seq carries the hit's version so callers can test range-tombstone
// masking (a range delete at a later seq covers the value).
enum class Res { MISS, HIT, TOMB };

Res resolve3(const Chain& chain, uint64_t snap_seq, const std::string** out,
             uint64_t* out_seq) {
  for (const auto& v : chain) {
    if (v.seq <= snap_seq) {
      if (v.tombstone) return Res::TOMB;
      *out = &v.value;
      *out_seq = v.seq;
      return Res::HIT;
    }
  }
  return Res::MISS;
}

constexpr uint64_t kVersionOverhead = 48;  // Version struct + string header
constexpr uint64_t kKeyOverhead = 80;      // map node + key string header

void push_version(Engine* e, Chain& chain, uint64_t seq, bool tomb,
                  std::string value, uint64_t min_snap) {
  e->mem_bytes += value.size() + kVersionOverhead;
  chain.insert(chain.begin(), Version{seq, tomb, std::move(value)});
  // compact: keep the newest version <= min_snap, drop everything older
  if (chain.size() > 1) {
    size_t keep = chain.size();
    for (size_t i = 0; i < chain.size(); i++) {
      if (chain[i].seq <= min_snap) {
        keep = i + 1;
        break;
      }
    }
    if (keep < chain.size()) {
      for (size_t i = keep; i < chain.size(); i++)
        e->mem_bytes -= std::min(e->mem_bytes,
                                 chain[i].value.size() + kVersionOverhead);
      chain.resize(keep);
    }
  }
}

void put_version(Engine* e, Table& t, std::string key, uint64_t seq, bool tomb,
                 std::string value, uint64_t min_snap) {
  // bulk ingestion (restore, snapshot apply, bench load) streams keys in
  // ascending order: appending past the current max is O(1) with an end
  // hint instead of a full O(log n) descent + key copy per record
  Chain* chain;
  size_t key_size = key.size();
  if (t.empty() || t.rbegin()->first < key) {
    chain = &t.emplace_hint(t.end(), std::move(key), Chain{})->second;
    e->mem_bytes += key_size + kKeyOverhead;
  } else {
    auto it = t.lower_bound(key);
    if (it != t.end() && it->first == key) {
      chain = &it->second;
    } else {
      chain = &t.emplace_hint(it, std::move(key), Chain{})->second;
      e->mem_bytes += key_size + kKeyOverhead;
    }
  }
  push_version(e, *chain, seq, tomb, std::move(value), min_snap);
}

// --- buffer helpers ---------------------------------------------------------

void append_u32(std::string& out, uint32_t v) {
  char b[4];
  memcpy(b, &v, 4);
  out.append(b, 4);
}

uint32_t read_u32(const uint8_t*& p) {
  uint32_t v;
  memcpy(&v, p, 4);
  p += 4;
  return v;
}

// batch format: repeated records
//   op u8 (1=put, 2=delete, 3=delete_range, 4=ingest_sst) | cf u8 |
//   klen u32 | key | vlen u32 | val      (val = end key for delete_range;
//   for ingest_sst the key is the SST file name inside the engine dir —
//   the WAL records the *reference*, rocksdb-manifest style, and replay
//   reloads the file)

// Structural validation WITHOUT applying: a malformed batch must be
// rejected before it reaches the WAL — once fsync'd, a bad record would
// poison replay and shadow-lose every later acked write.
int validate_batch(const uint8_t* data, uint64_t len) {
  const uint8_t* p = data;
  const uint8_t* end = data + len;
  while (p < end) {
    if (end - p < 2) return -1;
    uint8_t op = *p++;
    uint8_t cf = *p++;
    if (cf >= kNumCfs) return -2;
    // op 4 (ingest_sst) is NOT accepted from client batches: only
    // eng_ingest_sst forges it after validating the file, preserving the
    // "validated batch cannot fail to apply" invariant eng_write relies on
    if (op < 1 || op > 3) return -3;
    if (end - p < 4) return -1;
    uint32_t klen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(klen) + 4)
      return -1;
    p += klen;
    uint32_t vlen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < vlen) return -1;
    p += vlen;
  }
  return 0;
}

// --- SST files --------------------------------------------------------------
//
// Immutable sorted ingest file (the role sst_importer's SST plays):
//   "TKST1\n" | u32 count | repeated (cf u8|klen u32|key|vlen u32|val)
//   | "KSTE" | u32 crc32c(body)
// Entries must be sorted by (cf, key).  Ingest copies the file into the
// engine dir as sst-<seq>, WAL-appends an op-4 record naming it (the
// reference, not the bytes — rocksdb's manifest AddFile shape), then loads
// it; recovery replays the op-4 record and reloads from the dir.

constexpr char kSstMagic[] = "TKST1\n";
constexpr char kSstFoot[] = "KSTE";

int load_sst_file(Engine* e, const std::string& path, uint64_t seq);

// THE one batch applier: the live write path and WAL replay both come here.
int apply_batch(Engine* e, const uint8_t* data, uint64_t len, uint64_t seq) {
  uint64_t min_snap = e->min_live_snapshot();
  if (min_snap > seq) min_snap = seq;  // nothing older than this write is needed
  const uint8_t* p = data;
  const uint8_t* end = data + len;
  while (p < end) {
    if (end - p < 2) return -1;
    uint8_t op = *p++;
    uint8_t cf = *p++;
    if (cf >= kNumCfs) return -2;
    if (end - p < 4) return -1;
    uint32_t klen = read_u32(p);
    if (end - p < klen) return -1;
    std::string key(reinterpret_cast<const char*>(p), klen);
    p += klen;
    if (end - p < 4) return -1;
    uint32_t vlen = read_u32(p);
    if (end - p < vlen) return -1;
    std::string val(reinterpret_cast<const char*>(p), vlen);
    p += vlen;
    Table& t = e->cfs[cf];
    if (op != 4) e->cf_touched_seq[cf] = seq;  // an SST stamps the CFs it loads
    if (op == 1) {
      put_version(e, t, std::move(key), seq, false, std::move(val), min_snap);
    } else if (op == 2) {
      put_version(e, t, std::move(key), seq, true, "", min_snap);
    } else if (op == 3) {
      // range delete: O(1) on the write path no matter how many keys —
      // memtable and flushed alike — it covers.  Masking happens at read /
      // merge time (ties: a range delete at the same seq as a put in one
      // batch wins, matching per-key tombstone ordering)
      if (key < val) {
        e->mem_bytes += key.size() + val.size() + kVersionOverhead;
        e->mem_rtombs[cf].push_back(RangeTomb{std::move(key), std::move(val), seq});
      }
    } else if (op == 4) {
      std::string path = e->dir.empty() ? key : e->dir + "/" + key;
      if (load_sst_file(e, path, seq) != 0) return -6;
    } else {
      return -3;
    }
  }
  return 0;
}

// apply an already-validated SST image's entries at `seq`
int load_sst_from_buf(Engine* e, const uint8_t* data, uint64_t len, uint64_t seq) {
  if (len < 18) return -1;
  uint64_t min_snap = e->min_live_snapshot();
  if (min_snap > seq) min_snap = seq;
  const uint8_t* p = data + 10;
  const uint8_t* end = data + len - 8;
  while (p < end) {
    if (end - p < 5) return -1;
    uint8_t cf = *p++;
    if (cf >= kNumCfs) return -1;
    uint32_t klen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(klen) + 4) return -1;
    std::string key(reinterpret_cast<const char*>(p), klen);
    p += klen;
    uint32_t vlen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < vlen) return -1;
    e->cf_touched_seq[cf] = seq;
    // sorted input streams through the emplace-hint fast path in put_version
    put_version(e, e->cfs[cf], std::move(key), seq, false,
                std::string(reinterpret_cast<const char*>(p), vlen), min_snap);
    p += vlen;
  }
  return 0;
}

int sst_validate(const uint8_t* data, uint64_t len);

int load_sst_file(Engine* e, const std::string& path, uint64_t seq) {
  FILE* f = fopen(path.c_str(), "rb");
  if (!f) return -1;
  fseek(f, 0, SEEK_END);
  long sz = ftell(f);
  fseek(f, 0, SEEK_SET);
  if (sz < 18) { fclose(f); return -1; }
  std::string buf;
  buf.resize(sz);
  bool rok = fread(&buf[0], 1, sz, f) == static_cast<size_t>(sz);
  fclose(f);
  if (!rok) return -1;
  const uint8_t* d = reinterpret_cast<const uint8_t*>(buf.data());
  if (sst_validate(d, buf.size()) != 0) return -1;
  return load_sst_from_buf(e, d, buf.size(), seq);
}

// validate an SST byte buffer without applying (used before copy-in)
int sst_validate(const uint8_t* data, uint64_t len) {
  if (len < 18) return -1;
  if (memcmp(data, kSstMagic, 6) != 0) return -1;
  if (memcmp(data + len - 8, kSstFoot, 4) != 0) return -1;
  uint32_t crc;
  memcpy(&crc, data + len - 4, 4);
  if (crc32c(data + 10, len - 18) != crc) return -1;
  // entries sorted by (cf, key)?
  const uint8_t* p = data + 10;
  const uint8_t* end = data + len - 8;
  int last_cf = -1;
  std::string last_key;
  while (p < end) {
    if (end - p < 5) return -2;
    uint8_t cf = *p++;
    if (cf >= kNumCfs) return -2;
    uint32_t klen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(klen) + 4) return -2;
    std::string key(reinterpret_cast<const char*>(p), klen);
    p += klen;
    uint32_t vlen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < vlen) return -2;
    p += vlen;
    if (cf < last_cf || (cf == last_cf && key <= last_key)) return -3;
    last_cf = cf;
    last_key = std::move(key);
  }
  return 0;
}

// --- durability: WAL segments + checkpoint files ----------------------------
//
// Layout in e->dir:
//   wal-<start_seq:016x>   CRC-framed log; records carry seq > start_seq
//   ckpt-<seq:016x>        immutable full-state spill, atomic tmp+rename
//
// WAL record: u32 payload_len | u32 crc32c(seq||payload) | u64 seq | payload
// Checkpoint: "TKCK1\n" | u64 seq | repeated (cf u8|klen u32|key|vlen u32|
// val) | "KCE1" u32 crc32c(body)   — only live values spill (tombstones and
// version history die at the checkpoint boundary, like a full compaction).

constexpr char kCkptMagic[] = "TKCK1\n";
constexpr char kCkptFoot[] = "KCE1";

std::string seg_name(const char* prefix, uint64_t seq) {
  char buf[64];
  snprintf(buf, sizeof buf, "%s-%016llx", prefix,
           static_cast<unsigned long long>(seq));
  return buf;
}

bool parse_seg(const std::string& name, const char* prefix, uint64_t* seq) {
  size_t plen = strlen(prefix);
  if (name.size() != plen + 17 || name.compare(0, plen, prefix) != 0 ||
      name[plen] != '-')
    return false;
  *seq = strtoull(name.c_str() + plen + 1, nullptr, 16);
  return true;
}

void list_segs(const std::string& dir, const char* prefix,
               std::vector<uint64_t>* out) {
  DIR* d = opendir(dir.c_str());
  if (!d) return;
  struct dirent* ent;
  uint64_t seq;
  while ((ent = readdir(d)) != nullptr) {
    if (parse_seg(ent->d_name, prefix, &seq)) out->push_back(seq);
  }
  closedir(d);
  std::sort(out->begin(), out->end());
}

// data files and their encryption sidecars leave together
void unlink_with_sidecar(const std::string& path) {
  unlink(path.c_str());
  unlink(enc::sidecar_path(path).c_str());
}

int fsync_dir(const std::string& dir) {
  int fd = open(dir.c_str(), O_RDONLY | O_DIRECTORY);
  if (fd < 0) return -1;
  int r = fsync(fd);
  close(fd);
  return r;
}

int wal_open_segment(Engine* e, uint64_t start_seq) {
  if (e->wal_fd >= 0) close(e->wal_fd);
  e->wal_fd = -1;  // callers latch `failed` on wal_fd < 0: no stale fd here
  std::string path = e->dir + "/" + seg_name("wal", start_seq);
  bool existed = access(path.c_str(), F_OK) == 0;
  enc::State est = e->enc_snapshot();
  if (existed) {
    // reopening a recovered segment for append: its cipher identity is
    // whatever it was written with (plaintext when the sidecar is absent —
    // encryption then starts at the next rotation)
    if (enc::sidecar_read(est, path, &e->wal_key) < 0) return -1;
  } else if (est.on) {
    // sidecar persists (fsynced) BEFORE the segment becomes visible
    if (enc::file_begin(est, path, &e->wal_key) != 0) return -1;
  } else {
    e->wal_key.on = false;
  }
  e->wal_fd = open(path.c_str(), O_CREAT | O_WRONLY | O_APPEND, 0644);
  e->wal_bytes = 0;
  if (e->wal_fd < 0) return -1;
  off_t sz = lseek(e->wal_fd, 0, SEEK_END);
  e->wal_off = sz < 0 ? 0 : static_cast<uint64_t>(sz);
  fsync_dir(e->dir);  // the new segment name must survive a crash
  return 0;
}

int wal_append(Engine* e, uint64_t seq, const uint8_t* payload, uint64_t len) {
  if (e->dir.empty()) return 0;  // pure in-memory engine: no WAL
  if (e->wal_fd < 0) return -1;  // durable engine with a dead log fd
  std::string rec;
  rec.reserve(16 + len);
  append_u32(rec, static_cast<uint32_t>(len));
  uint8_t seq_le[8];
  memcpy(seq_le, &seq, 8);
  uint32_t crc = crc32c(seq_le, 8);
  crc = crc32c(payload, len, crc);
  append_u32(rec, crc);
  rec.append(reinterpret_cast<const char*>(seq_le), 8);
  rec.append(reinterpret_cast<const char*>(payload), len);
  enc::maybe_xor(e->wal_key, e->wal_off, &rec[0], rec.size());
  const char* p = rec.data();
  size_t left = rec.size();
  while (left > 0) {
    ssize_t n = ::write(e->wal_fd, p, left);
    if (n <= 0) return -1;
    p += n;
    left -= n;
  }
  e->wal_bytes += rec.size();
  e->wal_off += rec.size();
  if (e->sync_mode == 1 && fdatasync(e->wal_fd) != 0) return -1;
  return 0;
}

// replay one WAL segment; stops cleanly at the first torn/corrupt record and
// TRUNCATES the file to its valid prefix.  Without the truncate, reopening
// the same segment with O_APPEND (eng_open_at when e->seq equals the segment
// start) would append acked records BEHIND the torn bytes — unreachable by
// every later replay, i.e. silent loss of post-recovery writes.  Returns
// non-zero when a needed truncate FAILED — the caller must not open the
// engine for writing over a segment it could not repair.
int wal_replay(Engine* e, const std::string& path) {
  FILE* f = fopen(path.c_str(), "rb");
  if (!f) return 0;
  std::string buf;
  fseek(f, 0, SEEK_END);
  long sz = ftell(f);
  fseek(f, 0, SEEK_SET);
  buf.resize(sz);
  if (sz > 0 && fread(&buf[0], 1, sz, f) != static_cast<size_t>(sz)) {
    fclose(f);
    return -1;  // unreadable segment: do not trust the directory for writes
  }
  fclose(f);
  enc::FileKey fk;
  if (enc::sidecar_read(e->enc_snapshot(), path, &fk) < 0) return -1;
  if (sz > 0) enc::maybe_xor(fk, 0, &buf[0], buf.size());
  const uint8_t* base = reinterpret_cast<const uint8_t*>(buf.data());
  const uint8_t* p = base;
  const uint8_t* end = p + buf.size();
  uint64_t valid_end = buf.size();  // offset just past the last whole record
  bool torn = false;
  while (end - p >= 16) {
    const uint8_t* rec_start = p;
    uint32_t len = read_u32(p);
    uint32_t crc = read_u32(p);
    if (static_cast<uint64_t>(end - p) < 8 + static_cast<uint64_t>(len)) {
      valid_end = rec_start - base;
      torn = true;
      break;
    }
    uint64_t seq;
    memcpy(&seq, p, 8);
    uint32_t actual = crc32c(p, 8 + len);
    if (actual != crc) {  // torn tail: stop, later records unreachable
      valid_end = rec_start - base;
      torn = true;
      break;
    }
    p += 8;
    if (seq > e->seq) {  // records <= checkpoint seq are already folded in
      // CRC-valid records were individually acked (validated before the
      // append), so an apply failure skips just this record
      if (apply_batch(e, p, len, seq) == 0) e->seq = seq;
    }
    p += len;
  }
  // a partial header at the tail (loop exhausted, <16 bytes left) is torn too
  if (!torn && end - p > 0) valid_end = p - base;
  if (valid_end < static_cast<uint64_t>(sz)) {
    if (truncate(path.c_str(), static_cast<off_t>(valid_end)) != 0)
      return -1;  // unrepaired torn tail would hide acked writes appended later
  }
  return 0;
}

// --- LSM sorted runs --------------------------------------------------------
//
// run<cf>-<max_seq:016x>: immutable sorted run flushed from the memtable (or
// produced by a merge).  Layout:
//   "TKRN2\n" | u8 cf | u8 kind (0 flush, 1 merged) | u64 max_seq
//   data blocks: repeated (klen u32 | key | seq u64 | tomb u8 | vlen u32 | val)
//   index: u32 n_blocks | per block (off u64 | len u32 | crc u32 |
//          first_klen u32 | first_key)
//   bloom: u64 n_bits | u32 k | u32 pad | words u64[]
//   rtombs: u32 count | per rt (slen u32 | start | elen u32 | end | seq u64)
//   footer: u64 index_off | u64 bloom_off | u64 n_entries |
//           u32 crc32c(index..rtombs) | "TKRE"
// Entries are sorted by key; a key's versions are adjacent, newest first.
// Tombstones (point and range alike) are real entries: they mask older runs
// and die only when a merge reaches the oldest run.

constexpr char kRunMagic[] = "TKRN2\n";
constexpr char kRunFoot[] = "TKRE";
constexpr size_t kRunBlockTarget = 32 << 10;

const char* run_prefix(int cf) {
  static const char* names[kNumCfs] = {"run0", "run1", "run2", "run3"};
  return names[cf];
}

uint64_t hash64(const uint8_t* p, size_t n, uint64_t seed) {
  uint64_t h = 1469598103934665603ull ^ seed;
  for (size_t i = 0; i < n; i++) {
    h ^= p[i];
    h *= 1099511628211ull;
  }
  h ^= h >> 33;
  h *= 0xff51afd7ed558ccdull;
  h ^= h >> 33;
  return h;
}

struct RunWriter {
  FILE* f = nullptr;
  std::string tmp, fin;
  uint64_t off = 0;
  enc::FileKey fk;
  uint64_t n_entries = 0;
  std::string block;
  std::string block_first;
  std::vector<Run::Block> index;
  std::vector<uint64_t> key_hashes;  // one per distinct key
  std::string last_key;
  std::vector<RangeTomb> rtombs;  // set before finish(); written after bloom
  bool ok = true;

  // encrypt-then-write at the current offset (no-op when encryption is off)
  bool wr(const void* data, size_t len) {
    if (!fk.on) return fwrite(data, 1, len, f) == len;
    std::string tmpbuf(static_cast<const char*>(data), len);
    enc::maybe_xor(fk, off, &tmpbuf[0], len);
    return fwrite(tmpbuf.data(), 1, len, f) == len;
  }

  int open(const std::string& dir, const enc::State& est, int cf,
           uint64_t max_seq, int kind) {
    fin = dir + "/" + seg_name(run_prefix(cf), max_seq);
    // the sidecar for the FINAL name is durable before finish() renames the
    // data file into visibility — an encrypted run can never appear without
    // its metadata.  (Final names are unique per directory lifetime, see
    // below, so a sidecar never describes two generations of a file.)
    if (enc::file_begin(est, fin, &fk) != 0) return -1;
    // a flush (under the engine lock) and a merge (without it) may write
    // concurrently: the temp name must be private to this writer.  Final
    // names never collide — a flush's max_seq is the current seq, a merge
    // reuses its newest input's (older) name — so fin-derived is unique.
    tmp = fin + (kind == 1 ? ".mrg.tmp" : ".tmp");
    f = fopen(tmp.c_str(), "wb");
    if (!f) return -1;
    setvbuf(f, nullptr, _IOFBF, 1 << 20);
    std::string hdr(kRunMagic, 6);
    hdr.push_back(static_cast<char>(cf));
    hdr.push_back(static_cast<char>(kind));
    hdr.append(reinterpret_cast<const char*>(&max_seq), 8);
    off = 0;
    ok = wr(hdr.data(), hdr.size());
    off = hdr.size();
    return ok ? 0 : -1;
  }

  void flush_block() {
    if (block.empty()) return;
    Run::Block b;
    b.off = off;
    b.len = static_cast<uint32_t>(block.size());
    b.crc = crc32c(reinterpret_cast<const uint8_t*>(block.data()), block.size());
    b.first_key = block_first;
    ok = ok && wr(block.data(), block.size());
    off += block.size();
    index.push_back(std::move(b));
    block.clear();
  }

  void add(const std::string& key, uint64_t seq, bool tomb, const std::string& val) {
    if (block.empty()) block_first = key;
    append_u32(block, static_cast<uint32_t>(key.size()));
    block.append(key);
    block.append(reinterpret_cast<const char*>(&seq), 8);
    block.push_back(tomb ? 1 : 0);
    append_u32(block, static_cast<uint32_t>(val.size()));
    block.append(val);
    n_entries++;
    if (key != last_key) {
      key_hashes.push_back(
          hash64(reinterpret_cast<const uint8_t*>(key.data()), key.size(), 0));
      last_key = key;
    }
    // never split one key's version group across blocks: close only when the
    // NEXT key starts (callers add all versions of a key consecutively), so
    // flush at add() time happens on key boundaries via maybe_rotate()
  }

  void maybe_rotate(const std::string& next_key) {
    if (block.size() >= kRunBlockTarget && next_key != last_key) flush_block();
  }

  // returns a loaded Run (fd open) or nullptr
  std::shared_ptr<Run> finish(int cf, uint64_t max_seq, int kind = 0) {
    flush_block();
    auto run = std::make_shared<Run>();
    run->cf = cf;
    run->kind = kind;
    run->max_seq = max_seq;
    run->n_entries = n_entries;
    run->path = fin;
    // index section
    std::string sec;
    uint64_t index_off = off;
    append_u32(sec, static_cast<uint32_t>(index.size()));
    for (const auto& b : index) {
      sec.append(reinterpret_cast<const char*>(&b.off), 8);
      append_u32(sec, b.len);
      append_u32(sec, b.crc);
      append_u32(sec, static_cast<uint32_t>(b.first_key.size()));
      sec.append(b.first_key);
    }
    // bloom section (10 bits/key, 6 probes)
    uint64_t n_bits = key_hashes.empty() ? 64 : key_hashes.size() * 10;
    n_bits = (n_bits + 63) / 64 * 64;
    std::vector<uint64_t> bloom(n_bits / 64, 0);
    uint32_t k = 6;
    for (uint64_t h : key_hashes) {
      uint64_t h2 = h * 0x9e3779b97f4a7c15ull + 1;
      for (uint32_t i = 0; i < k; i++) {
        uint64_t bit = (h + i * h2) % n_bits;
        bloom[bit / 64] |= 1ull << (bit % 64);
      }
    }
    uint64_t bloom_off = index_off + sec.size();
    sec.append(reinterpret_cast<const char*>(&n_bits), 8);
    append_u32(sec, k);
    append_u32(sec, 0);
    sec.append(reinterpret_cast<const char*>(bloom.data()), bloom.size() * 8);
    // range-tombstone section
    append_u32(sec, static_cast<uint32_t>(rtombs.size()));
    for (const auto& rt : rtombs) {
      append_u32(sec, static_cast<uint32_t>(rt.start.size()));
      sec.append(rt.start);
      append_u32(sec, static_cast<uint32_t>(rt.end.size()));
      sec.append(rt.end);
      sec.append(reinterpret_cast<const char*>(&rt.seq), 8);
    }
    uint32_t sec_crc = crc32c(reinterpret_cast<const uint8_t*>(sec.data()), sec.size());
    std::string foot;
    foot.append(reinterpret_cast<const char*>(&index_off), 8);
    foot.append(reinterpret_cast<const char*>(&bloom_off), 8);
    foot.append(reinterpret_cast<const char*>(&n_entries), 8);
    append_u32(foot, sec_crc);
    foot.append(kRunFoot, 4);
    bool w1 = wr(sec.data(), sec.size());
    off += sec.size();
    bool w2 = wr(foot.data(), foot.size());
    off += foot.size();
    ok = ok && w1 && w2 && fflush(f) == 0 && fsync(fileno(f)) == 0;
    fclose(f);
    f = nullptr;
    if (!ok || rename(tmp.c_str(), fin.c_str()) != 0) {
      unlink(tmp.c_str());
      return nullptr;
    }
    run->blocks = std::move(index);
    run->bloom = std::move(bloom);
    run->bloom_k = k;
    run->rtombs = std::move(rtombs);
    run->fk = fk;
    run->fd = ::open(fin.c_str(), O_RDONLY);
    if (run->fd < 0) return nullptr;
    return run;
  }
};

// open + validate an existing run file; nullptr on structural damage
std::shared_ptr<Run> run_open_with(const std::string& path, const enc::FileKey& fk) {
  int fd = ::open(path.c_str(), O_RDONLY);
  if (fd < 0) return nullptr;
  off_t sz = lseek(fd, 0, SEEK_END);
  if (sz < 16 + 32) { close(fd); return nullptr; }
  char foot[32];
  if (pread(fd, foot, 32, sz - 32) != 32) { close(fd); return nullptr; }
  enc::maybe_xor(fk, sz - 32, foot, 32);
  if (memcmp(foot + 28, kRunFoot, 4) != 0) {
    close(fd);
    return nullptr;
  }
  uint64_t index_off, bloom_off, n_entries;
  uint32_t sec_crc;
  memcpy(&index_off, foot, 8);
  memcpy(&bloom_off, foot + 8, 8);
  memcpy(&n_entries, foot + 16, 8);
  memcpy(&sec_crc, foot + 24, 4);
  if (index_off < 16 || index_off > static_cast<uint64_t>(sz) ||
      bloom_off < index_off || bloom_off > static_cast<uint64_t>(sz)) {
    close(fd);
    return nullptr;
  }
  char hdr[16];
  if (pread(fd, hdr, 16, 0) != 16) { close(fd); return nullptr; }
  enc::maybe_xor(fk, 0, hdr, 16);
  if (memcmp(hdr, kRunMagic, 6) != 0) {
    close(fd);
    return nullptr;
  }
  auto run = std::make_shared<Run>();
  run->path = path;
  run->fk = fk;
  run->cf = static_cast<uint8_t>(hdr[6]);
  run->kind = static_cast<uint8_t>(hdr[7]);
  memcpy(&run->max_seq, hdr + 8, 8);
  run->n_entries = n_entries;
  size_t sec_len = sz - 32 - index_off;
  std::string sec(sec_len, '\0');
  if (pread(fd, &sec[0], sec_len, index_off) != static_cast<ssize_t>(sec_len)) {
    close(fd);
    return nullptr;
  }
  enc::maybe_xor(fk, index_off, &sec[0], sec_len);
  if (crc32c(reinterpret_cast<const uint8_t*>(sec.data()), sec_len) != sec_crc) {
    close(fd);
    return nullptr;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(sec.data());
  const uint8_t* end = p + sec_len;
  if (end - p < 4) { close(fd); return nullptr; }
  uint32_t n_blocks = read_u32(p);
  for (uint32_t i = 0; i < n_blocks; i++) {
    if (end - p < 20) { close(fd); return nullptr; }
    Run::Block b;
    memcpy(&b.off, p, 8);
    p += 8;
    b.len = read_u32(p);
    b.crc = read_u32(p);
    uint32_t klen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < klen) { close(fd); return nullptr; }
    b.first_key.assign(reinterpret_cast<const char*>(p), klen);
    p += klen;
    run->blocks.push_back(std::move(b));
  }
  if (end - p < 16) { close(fd); return nullptr; }
  uint64_t n_bits;
  memcpy(&n_bits, p, 8);
  p += 8;
  run->bloom_k = read_u32(p);
  p += 4;  // pad
  if (static_cast<uint64_t>(end - p) < n_bits / 8) { close(fd); return nullptr; }
  run->bloom.resize(n_bits / 64);
  memcpy(run->bloom.data(), p, n_bits / 8);
  p += n_bits / 8;
  if (end - p < 4) { close(fd); return nullptr; }
  uint32_t n_rt = read_u32(p);
  for (uint32_t i = 0; i < n_rt; i++) {
    RangeTomb rt;
    if (end - p < 4) { close(fd); return nullptr; }
    uint32_t slen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(slen) + 4) {
      close(fd);
      return nullptr;
    }
    rt.start.assign(reinterpret_cast<const char*>(p), slen);
    p += slen;
    uint32_t elen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(elen) + 8) {
      close(fd);
      return nullptr;
    }
    rt.end.assign(reinterpret_cast<const char*>(p), elen);
    p += elen;
    memcpy(&rt.seq, p, 8);
    p += 8;
    run->rtombs.push_back(std::move(rt));
  }
  run->fd = fd;
  return run;
}

// Open + validate a run, trying every cipher identity its sidecar lists
// (newest first) and finally plaintext: a compaction that crashed between
// sidecar update and data rename leaves the OLD file behind the NEW entry,
// and the file's own magic + section CRC identify which candidate fits.
std::shared_ptr<Run> run_open(const std::string& path, const enc::State& est) {
  std::vector<enc::FileKey> cands;
  int r = enc::sidecar_read_all(est, path, &cands);
  if (r < 0) return nullptr;  // sidecar damaged or its keys unknown
  cands.push_back(enc::FileKey{});  // plaintext fallback (migration / crash)
  for (const enc::FileKey& fk : cands) {
    auto run = run_open_with(path, fk);
    if (run) return run;
  }
  return nullptr;
}

bool bloom_may_contain(const Run& r, const std::string& key) {
  if (r.bloom.empty()) return true;
  uint64_t n_bits = r.bloom.size() * 64;
  uint64_t h = hash64(reinterpret_cast<const uint8_t*>(key.data()), key.size(), 0);
  uint64_t h2 = h * 0x9e3779b97f4a7c15ull + 1;
  for (uint32_t i = 0; i < r.bloom_k; i++) {
    uint64_t bit = (h + i * h2) % n_bits;
    if (!(r.bloom[bit / 64] & (1ull << (bit % 64)))) return false;
  }
  return true;
}

int run_read_block(const Run& r, size_t bi, std::string* out, Perf* perf) {
  const Run::Block& b = r.blocks[bi];
  out->resize(b.len);
  if (pread(r.fd, &(*out)[0], b.len, b.off) != static_cast<ssize_t>(b.len))
    return -1;
  if (b.len) enc::maybe_xor(r.fk, b.off, &(*out)[0], b.len);
  if (crc32c(reinterpret_cast<const uint8_t*>(out->data()), b.len) != b.crc)
    return -1;
  if (perf) perf->blocks_read.fetch_add(1, std::memory_order_relaxed);
  return 0;
}

// last block whose first_key <= key (the block that could hold it)
long run_block_for(const Run& r, const std::string& key) {
  long lo = 0, hi = static_cast<long>(r.blocks.size()) - 1, ans = -1;
  while (lo <= hi) {
    long mid = (lo + hi) / 2;
    if (r.blocks[mid].first_key <= key) {
      ans = mid;
      lo = mid + 1;
    } else {
      hi = mid - 1;
    }
  }
  return ans;
}

// point lookup in one run: 0 = absent, 1 = value, 2 = tombstone, <0 = error
int run_get(const Run& r, const std::string& key, uint64_t snap_seq,
            std::string* val, uint64_t* out_seq, Perf* perf) {
  if (!bloom_may_contain(r, key)) {
    if (perf) perf->bloom_skips.fetch_add(1, std::memory_order_relaxed);
    return 0;
  }
  long bi = run_block_for(r, key);
  if (bi < 0) return 0;
  if (perf) perf->run_probes.fetch_add(1, std::memory_order_relaxed);
  std::string block;
  if (run_read_block(r, bi, &block, perf) != 0) return -1;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(block.data());
  const uint8_t* end = p + block.size();
  while (p < end) {
    if (end - p < 4) return -1;
    uint32_t klen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(klen) + 13) return -1;
    int cmp = memcmp(p, key.data(), std::min<size_t>(klen, key.size()));
    if (cmp == 0) cmp = (klen < key.size()) ? -1 : (klen > key.size() ? 1 : 0);
    p += klen;
    uint64_t seq;
    memcpy(&seq, p, 8);
    p += 8;
    uint8_t tomb = *p++;
    uint32_t vlen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < vlen) return -1;
    if (cmp > 0) return 0;  // past the key: absent in this run
    if (cmp == 0 && seq <= snap_seq) {
      if (tomb) return 2;
      val->assign(reinterpret_cast<const char*>(p), vlen);
      *out_seq = seq;
      return 1;
    }
    p += vlen;
  }
  return 0;
}

// sequential cursor over one run's per-key version groups, range-aware
struct RunCursor {
  const Run* run;
  Perf* perf;
  std::string block;
  size_t bi = 0;          // next block index to load
  const uint8_t* p = nullptr;
  const uint8_t* end = nullptr;
  std::string key;
  std::vector<Version> versions;  // newest first (run entry order)
  bool valid = false;

  void seek(const Run* r, const std::string& start, Perf* pf) {
    run = r;
    perf = pf;
    long b = run_block_for(*r, start);
    bi = b < 0 ? 0 : static_cast<size_t>(b);
    p = end = nullptr;
    valid = true;
    next_group();
    while (valid && key < start) next_group();
  }

  bool load_next_block() {
    while (bi < run->blocks.size()) {
      if (run_read_block(*run, bi, &block, perf) != 0) { valid = false; return false; }
      bi++;
      p = reinterpret_cast<const uint8_t*>(block.data());
      end = p + block.size();
      if (p < end) return true;
    }
    return false;
  }

  // parse one entry at p (advances); false on exhaustion/corruption
  bool parse(std::string* k, uint64_t* seq, bool* tomb, std::string* v) {
    if (p >= end && !load_next_block()) return false;
    if (end - p < 4) { valid = false; return false; }
    uint32_t klen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(klen) + 13) {
      valid = false;
      return false;
    }
    k->assign(reinterpret_cast<const char*>(p), klen);
    p += klen;
    memcpy(seq, p, 8);
    p += 8;
    *tomb = *p++ != 0;
    uint32_t vlen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < vlen) { valid = false; return false; }
    v->assign(reinterpret_cast<const char*>(p), vlen);
    p += vlen;
    return true;
  }

  std::string pending_key;
  std::vector<Version> pending;
  bool have_pending = false;

  void next_group() {
    if (!valid) return;
    key.clear();
    versions.clear();
    bool have_key = false;
    if (have_pending) {
      key = std::move(pending_key);
      versions = std::move(pending);
      pending.clear();
      have_pending = false;
      have_key = true;
    }
    std::string k, v;
    uint64_t seq;
    bool tomb;
    while (parse(&k, &seq, &tomb, &v)) {
      if (!have_key) {
        key = k;
        have_key = true;
      }
      if (k == key) {
        versions.push_back(Version{seq, tomb, std::move(v)});
        continue;
      }
      // next key's first version: stash it
      pending_key = std::move(k);
      pending.clear();
      pending.push_back(Version{seq, tomb, std::move(v)});
      have_pending = true;
      return;
    }
    if (versions.empty()) valid = false;
  }
};

// One materialized memtable resolution: the key's visible state at the
// snapshot, captured under the engine lock so the merge can run without it.
struct MemEntry {
  std::string key;
  bool tomb;
  uint64_t seq;
  std::string value;
};

// forward merged iterator over memtable + all runs of one CF, resolving
// versions at a snapshot and filtering tombstones.  init() is called under
// (at least) the shared engine lock and copies everything it needs — the
// memtable subrange resolved at the snapshot, run shared_ptrs (which pin
// the files across a concurrent merge swap), and the relevant range
// tombstones — so next(), which does run-block file IO (pread + crc),
// runs with NO engine lock held: range scans no longer serialize writers
// behind disk IO (the eng_get treatment, extended to ranges).
struct MergeIter {
  std::vector<MemEntry> mem;  // resolved memtable subrange, ascending
  size_t mpos = 0;
  std::vector<std::shared_ptr<Run>> runs_keep;
  std::vector<RunCursor> cursors;
  Perf* perf = nullptr;
  uint64_t snap;
  std::string lower;  // run-cursor seek start
  std::string upper;  // exclusive; empty + !has_upper = unbounded
  bool has_upper = false;
  bool seeked = false;  // run cursors positioned (deferred: seeking reads)
  // mem_cap / mem_bytes_cap bound how many memtable entries (and copied
  // bytes) init may walk under the lock (0 = unlimited).  When hit,
  // `truncated` is set and `resume_key` names the first un-walked key: the
  // whole merge is clamped below it and the caller continues from there
  // with a fresh init (ChunkedMerge).
  uint64_t mem_cap = 0;
  uint64_t mem_bytes_cap = 0;
  bool truncated = false;
  std::string resume_key;

  std::vector<RangeTomb> rts;  // tombstones visible at snap touching range

  void init(Engine* e, int cf, uint64_t snap_seq, const std::string& start,
            const std::string& end, bool bounded) {
    snap = snap_seq;
    lower = start;
    upper = end;
    has_upper = bounded;
    if (bounded && end <= start) {
      seeked = true;  // empty range: nothing to position
      return;
    }
    const Table& t = e->cfs[cf];
    auto endit = bounded ? t.lower_bound(end) : t.end();
    uint64_t walked = 0, bytes = 0;
    for (auto it = t.lower_bound(start); it != endit; ++it) {
      if ((mem_cap != 0 && walked == mem_cap) ||
          (mem_bytes_cap != 0 && bytes >= mem_bytes_cap)) {
        truncated = true;
        resume_key = it->first;
        break;
      }
      walked++;
      const std::string* v = nullptr;
      uint64_t v_seq = 0;
      Res r = resolve3(it->second, snap_seq, &v, &v_seq);
      if (r == Res::MISS) continue;  // runs decide, same as key-absent
      bytes += it->first.size() + (r == Res::HIT ? v->size() : 0);
      mem.push_back(MemEntry{it->first, r == Res::TOMB, v_seq,
                             r == Res::HIT ? *v : std::string()});
    }
    runs_keep = e->runs[cf];
    perf = &e->perf;
    // hoist the relevant range tombstones once: per-key masking below walks
    // only this (usually empty) filtered list, not every run's full set
    auto want = [&](const RangeTomb& rt) {
      return rt.seq <= snap_seq && rt.end > start && (!bounded || rt.start < end);
    };
    for (const auto& rt : e->mem_rtombs[cf])
      if (want(rt)) rts.push_back(rt);
    for (const auto& run : runs_keep)
      for (const auto& rt : run->rtombs)
        if (want(rt)) rts.push_back(rt);
  }

  // next visible (key, value); false when exhausted.  Run-block IO happens
  // here, after init's lock is released.
  bool next(std::string* out_k, std::string* out_v) {
    if (!seeked) {
      seeked = true;
      cursors.resize(runs_keep.size());
      for (size_t i = 0; i < cursors.size(); i++)
        cursors[i].seek(runs_keep[i].get(), lower, perf);
    }
    while (true) {
      const std::string* min_key = nullptr;
      if (mpos < mem.size()) min_key = &mem[mpos].key;
      for (auto& c : cursors) {
        if (!c.valid) continue;
        if (has_upper && c.key >= upper) { c.valid = false; continue; }
        if (min_key == nullptr || c.key < *min_key) min_key = &c.key;
      }
      if (min_key == nullptr) return false;
      if (truncated && *min_key >= resume_key) return false;  // chunk edge
      std::string key = *min_key;
      // resolve newest-source-first: memtable, then runs in list order
      Res r = Res::MISS;
      const std::string* v = nullptr;
      uint64_t v_seq = 0;
      bool mem_here = mpos < mem.size() && mem[mpos].key == key;
      if (mem_here) {
        r = mem[mpos].tomb ? Res::TOMB : Res::HIT;
        v = &mem[mpos].value;
        v_seq = mem[mpos].seq;
      }
      std::string run_val;
      if (r == Res::MISS) {
        for (auto& c : cursors) {
          if (!c.valid || c.key != key) continue;
          for (const auto& ver : c.versions) {
            if (ver.seq <= snap) {
              if (ver.tombstone) {
                r = Res::TOMB;
              } else {
                run_val = ver.value;
                v_seq = ver.seq;
                r = Res::HIT;
                v = &run_val;
              }
              break;
            }
          }
          if (r != Res::MISS) break;
        }
      }
      // advance every source positioned at this key
      if (mem_here) mpos++;
      for (auto& c : cursors)
        if (c.valid && c.key == key) c.next_group();
      if (r == Res::HIT && rtomb_covering(rts, key, snap) < v_seq) {
        *out_k = std::move(key);
        *out_v = *v;
        return true;
      }
      // MISS (all newer than snap), TOMB, or range-delete-masked: skip
    }
  }
};

// reverse merged iteration materializes per-key resolution walking backward:
// run blocks are forward-parsed but visited in reverse block order
struct ReverseRunCursor {
  const Run* run = nullptr;
  Perf* perf;
  long bi = -1;  // block currently loaded
  std::vector<std::pair<std::string, std::vector<Version>>> groups;
  long gi = -1;  // current group (descending)
  bool valid = false;

  void seek_last_below(const Run* r, const std::string& upper, bool bounded,
                       Perf* pf) {
    run = r;
    perf = pf;
    bi = static_cast<long>(r->blocks.size()) - 1;
    if (bounded) {
      long b = run_block_for(*r, upper);
      bi = b < 0 ? -1 : b;
    }
    valid = bi >= 0;
    groups.clear();
    gi = -1;
    if (valid) load(bounded ? &upper : nullptr);
  }

  void load(const std::string* upper) {
    groups.clear();
    gi = -1;
    while (bi >= 0 && groups.empty()) {
      std::string block;
      if (run_read_block(*run, bi, &block, perf) != 0) { valid = false; return; }
      const uint8_t* p = reinterpret_cast<const uint8_t*>(block.data());
      const uint8_t* end = p + block.size();
      while (p < end) {
        if (end - p < 4) { valid = false; return; }
        uint32_t klen = read_u32(p);
        if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(klen) + 13) {
          valid = false;
          return;
        }
        std::string k(reinterpret_cast<const char*>(p), klen);
        p += klen;
        uint64_t seq;
        memcpy(&seq, p, 8);
        p += 8;
        bool tomb = *p++ != 0;
        uint32_t vlen = read_u32(p);
        if (static_cast<uint64_t>(end - p) < vlen) { valid = false; return; }
        if (upper == nullptr || k < *upper) {
          if (groups.empty() || groups.back().first != k)
            groups.emplace_back(std::move(k), std::vector<Version>{});
          groups.back().second.push_back(
              Version{seq, tomb, std::string(reinterpret_cast<const char*>(p), vlen)});
        }
        p += vlen;
      }
      bi--;
    }
    if (groups.empty()) {
      valid = false;
      return;
    }
    gi = static_cast<long>(groups.size()) - 1;
  }

  const std::string& key() const { return groups[gi].first; }
  const std::vector<Version>& versions() const { return groups[gi].second; }

  void prev_group() {
    // a key can span a block boundary (its versions split across blocks) —
    // the writer prevents that (maybe_rotate splits only at key boundaries),
    // so stepping is purely positional
    gi--;
    if (gi < 0 && valid) load(nullptr);
    if (gi < 0) valid = false;
  }
};

struct ReverseMergeIter {
  std::vector<MemEntry> mem;  // resolved memtable subrange, DESCENDING
  size_t mpos = 0;
  std::vector<std::shared_ptr<Run>> runs_keep;
  std::vector<ReverseRunCursor> cursors;
  Perf* perf = nullptr;
  uint64_t snap;
  std::string lower;   // inclusive bound
  std::string upper_;  // exclusive cursor-seek bound
  bool bounded_ = false;
  bool seeked = false;
  // bounded memtable walk, mirroring MergeIter: when the cap is hit,
  // resume_key is the key at which the descending walk stopped (NOT
  // materialized).  The merge is clamped to keys strictly above it, and the
  // next chunk's exclusive upper bound is resume_key + one zero byte so the
  // stopped-at key itself is included there.
  uint64_t mem_cap = 0;
  uint64_t mem_bytes_cap = 0;
  bool truncated = false;
  std::string resume_key;

  std::vector<RangeTomb> rts;  // tombstones visible at snap touching range

  // Same locking contract as MergeIter: init under the shared engine lock
  // (no file IO), next() unlocked.
  void init(Engine* e, int cf, uint64_t snap_seq, const std::string& start,
            const std::string& end, bool bounded) {
    snap = snap_seq;
    lower = start;
    upper_ = end;
    bounded_ = bounded;
    if (bounded && end <= start) {
      seeked = true;  // empty range
      return;
    }
    const Table& t = e->cfs[cf];
    auto mbegin = t.lower_bound(start);
    auto it = bounded ? t.lower_bound(end) : t.end();
    uint64_t walked = 0, bytes = 0;
    while (it != mbegin) {
      --it;
      if ((mem_cap != 0 && walked == mem_cap) ||
          (mem_bytes_cap != 0 && bytes >= mem_bytes_cap)) {
        truncated = true;
        resume_key = it->first;  // un-materialized; next chunk includes it
        break;
      }
      walked++;
      const std::string* v = nullptr;
      uint64_t v_seq = 0;
      Res r = resolve3(it->second, snap_seq, &v, &v_seq);
      if (r == Res::MISS) continue;
      bytes += it->first.size() + (r == Res::HIT ? v->size() : 0);
      mem.push_back(MemEntry{it->first, r == Res::TOMB, v_seq,
                             r == Res::HIT ? *v : std::string()});
    }
    runs_keep = e->runs[cf];
    perf = &e->perf;
    auto want = [&](const RangeTomb& rt) {
      return rt.seq <= snap_seq && rt.end > start && (!bounded || rt.start < end);
    };
    for (const auto& rt : e->mem_rtombs[cf])
      if (want(rt)) rts.push_back(rt);
    for (const auto& run : runs_keep)
      for (const auto& rt : run->rtombs)
        if (want(rt)) rts.push_back(rt);
  }

  bool next(std::string* out_k, std::string* out_v) {
    if (!seeked) {
      seeked = true;
      cursors.resize(runs_keep.size());
      for (size_t i = 0; i < cursors.size(); i++) {
        cursors[i].seek_last_below(runs_keep[i].get(), upper_, bounded_, perf);
        if (cursors[i].valid && cursors[i].key() < lower)
          cursors[i].valid = false;
      }
    }
    while (true) {
      const std::string* max_key = nullptr;
      if (mpos < mem.size()) max_key = &mem[mpos].key;
      for (auto& c : cursors) {
        if (!c.valid) continue;
        if (c.key() < lower) { c.valid = false; continue; }
        if (max_key == nullptr || c.key() > *max_key) max_key = &c.key();
      }
      if (max_key == nullptr) return false;
      if (truncated && *max_key <= resume_key) return false;  // chunk edge
      std::string key = *max_key;
      Res r = Res::MISS;
      const std::string* v = nullptr;
      uint64_t v_seq = 0;
      bool mem_here = mpos < mem.size() && mem[mpos].key == key;
      if (mem_here) {
        r = mem[mpos].tomb ? Res::TOMB : Res::HIT;
        v = &mem[mpos].value;
        v_seq = mem[mpos].seq;
      }
      std::string run_val;
      if (r == Res::MISS) {
        for (auto& c : cursors) {
          if (!c.valid || c.key() != key) continue;
          for (const auto& ver : c.versions()) {
            if (ver.seq <= snap) {
              if (ver.tombstone) {
                r = Res::TOMB;
              } else {
                run_val = ver.value;
                v_seq = ver.seq;
                r = Res::HIT;
                v = &run_val;
              }
              break;
            }
          }
          if (r != Res::MISS) break;
        }
      }
      if (mem_here) mpos++;
      for (auto& c : cursors) {
        if (c.valid && c.key() == key) {
          c.prev_group();
          if (c.valid && c.key() < lower) c.valid = false;
        }
      }
      if (r == Res::HIT && rtomb_covering(rts, key, snap) < v_seq) {
        *out_k = std::move(key);
        *out_v = *v;
        return true;
      }
    }
  }
};

// Drives MergeIter in bounded-memtable chunks.  Each chunk takes a fresh
// shared-lock view at the SAME registered snapshot — safe, because versions
// visible at a live snapshot can neither disappear (the snapshot pins them
// against compaction and version-chain trimming; a flush only moves them
// into a run the fresh view includes) nor appear (new writes carry seqs
// above it).  So no lock is ever held across run-block IO and no single
// init walks more than `cap` memtable entries.
constexpr uint64_t kScanMemChunk = 65536;     // memtable entries / locked walk
constexpr uint64_t kMemChunkBytes = 4 << 20;  // copied bytes / locked walk

struct ChunkedMerge {
  Engine* e;
  int cf;
  uint64_t snap;
  std::string cur, upper;
  bool has_upper;
  uint64_t cap;  // grows ×4 per re-init: single-row seeks start tiny
  MergeIter mi;

  ChunkedMerge(Engine* e_, int cf_, uint64_t snap_, std::string start,
               std::string end, bool bounded, uint64_t cap_)
      : e(e_), cf(cf_), snap(snap_), cur(std::move(start)),
        upper(std::move(end)), has_upper(bounded), cap(cap_) {
    open();
  }

  void open() {
    mi = MergeIter{};
    mi.mem_cap = cap;
    mi.mem_bytes_cap = kMemChunkBytes;
    std::shared_lock lk(e->mu);
    mi.init(e, cf, snap, cur, upper, has_upper);
  }

  bool next(std::string* k, std::string* v) {
    while (true) {
      if (mi.next(k, v)) return true;
      if (!mi.truncated) return false;
      cur = mi.resume_key;  // strictly advances: ≥1 entry walked per chunk
      cap = std::min<uint64_t>(cap * 4, kScanMemChunk);
      open();
    }
  }
};

struct ReverseChunkedMerge {
  Engine* e;
  int cf;
  uint64_t snap;
  std::string lower, cur_upper;
  bool has_upper;
  uint64_t cap;
  ReverseMergeIter mi;

  ReverseChunkedMerge(Engine* e_, int cf_, uint64_t snap_, std::string start,
                      std::string end, bool bounded, uint64_t cap_)
      : e(e_), cf(cf_), snap(snap_), lower(std::move(start)),
        cur_upper(std::move(end)), has_upper(bounded), cap(cap_) {
    open();
  }

  void open() {
    mi = ReverseMergeIter{};
    mi.mem_cap = cap;
    mi.mem_bytes_cap = kMemChunkBytes;
    std::shared_lock lk(e->mu);
    mi.init(e, cf, snap, lower, cur_upper, has_upper);
  }

  bool next(std::string* k, std::string* v) {
    while (true) {
      if (mi.next(k, v)) return true;
      if (!mi.truncated) return false;
      // stopped-at key was not materialized: include it in the next chunk
      cur_upper = mi.resume_key + std::string(1, '\0');
      has_upper = true;
      cap = std::min<uint64_t>(cap * 4, kScanMemChunk);
      open();
    }
  }
};

// write the whole memtable of one CF (chains + range tombstones) as a run
std::shared_ptr<Run> run_from_table(Engine* e, int cf, uint64_t max_seq) {
  RunWriter w;
  if (w.open(e->dir, e->enc_snapshot(), cf, max_seq, 0) != 0) return nullptr;
  for (const auto& [key, chain] : e->cfs[cf]) {
    w.maybe_rotate(key);
    for (const auto& v : chain) w.add(key, v.seq, v.tombstone, v.value);
  }
  w.rtombs = e->mem_rtombs[cf];
  return w.finish(cf, max_seq);
}

// spill the whole memtable to per-CF runs, clear it, rotate the WAL — the
// incremental replacement for the O(DB) checkpoint spill: each flush costs
// O(memtable), never O(database).  Caller holds the write lock.
int flush_memtable(Engine* e) {
  if (e->dir.empty()) return -1;
  uint64_t at = e->seq;
  std::vector<std::shared_ptr<Run>> created;
  if (at > e->flushed_seq) {
    for (int cf = 0; cf < kNumCfs; cf++) {
      if (e->cfs[cf].empty() && e->mem_rtombs[cf].empty()) continue;
      auto run = run_from_table(e, cf, at);
      if (!run) {
        for (auto& r : created) unlink_with_sidecar(r->path);
        return -1;
      }
      created.push_back(run);
    }
    fsync_dir(e->dir);
    // completion marker: a flush is visible to recovery only once ALL its
    // per-CF runs are durable (multi-file atomicity).  Written even when
    // no run was produced (every record since the last flush was a no-op):
    // the marker is what tells recovery the older WAL is fully covered, so
    // it must advance whenever the WAL is about to be truncated — deleting
    // mark-N without a successor would make recovery distrust every run.
    std::string mark = e->dir + "/" + seg_name("mark", at);
    int mfd = ::open(mark.c_str(), O_CREAT | O_WRONLY, 0644);
    if (mfd < 0) {
      for (auto& r : created) unlink_with_sidecar(r->path);
      return -1;
    }
    fsync(mfd);
    close(mfd);
    fsync_dir(e->dir);
  }
  // new WAL segment BEFORE deleting old ones: if the open fails the previous
  // log remains intact and the engine refuses further writes, losing nothing
  if (wal_open_segment(e, at) != 0) return -1;
  for (auto& r : created)
    e->runs[r->cf].insert(e->runs[r->cf].begin(), r);
  if (at > e->flushed_seq) {
    for (int cf = 0; cf < kNumCfs; cf++) {
      e->cfs[cf].clear();
      e->mem_rtombs[cf].clear();
    }
    e->mem_bytes = 0;
    e->flushed_seq = at;
    e->perf.flushes.fetch_add(1, std::memory_order_relaxed);
  }
  std::vector<uint64_t> old;
  list_segs(e->dir, "wal", &old);
  for (uint64_t s : old)
    if (s < at) unlink_with_sidecar(e->dir + "/" + seg_name("wal", s));
  // legacy checkpoints and folded ingests are superseded: the flush captured
  // the whole memtable, which included anything they had loaded
  old.clear();
  list_segs(e->dir, "ckpt", &old);
  for (uint64_t s : old)
    if (s <= at) unlink((e->dir + "/" + seg_name("ckpt", s)).c_str());
  old.clear();
  list_segs(e->dir, "sst", &old);
  for (uint64_t s : old)
    if (s <= at) unlink((e->dir + "/" + seg_name("sst", s)).c_str());
  old.clear();
  list_segs(e->dir, "mark", &old);
  for (uint64_t s : old)
    if (s < at) unlink((e->dir + "/" + seg_name("mark", s)).c_str());
  return 0;
}

// k-way merge of every current run of one CF into a single run, dropping
// version history below the snapshot horizon and bottom-level tombstones.
// Runs are immutable, so the merge reads WITHOUT the engine lock; the swap
// takes it briefly (rocksdb compaction's locking shape).
int merge_runs_cf(Engine* e, int cf) {
  std::unique_lock cl(e->compact_mu);
  std::vector<std::shared_ptr<Run>> inputs;
  uint64_t min_snap;
  {
    std::shared_lock lk(e->mu);
    if (e->runs[cf].size() < 2) return 0;
    inputs = e->runs[cf];
    min_snap = std::min(e->min_live_snapshot(), e->seq);
  }
  uint64_t max_seq = inputs.front()->max_seq;
  RunWriter w;
  if (w.open(e->dir, e->enc_snapshot(), cf, max_seq, 1) != 0) return -1;
  // range tombstones: ones no snapshot can see below fold into the output
  // now (applied to the merged versions, then dropped — this is the only
  // level, so nothing older remains for them to mask; memtable versions are
  // all newer than any run seq, out of reach by construction).  Newer ones
  // ride along into the output run.
  std::vector<RangeTomb> dying_rtombs, kept_rtombs;
  for (const auto& r : inputs)
    for (const auto& rt : r->rtombs)
      (rt.seq <= min_snap ? dying_rtombs : kept_rtombs).push_back(rt);
  w.rtombs = kept_rtombs;
  std::vector<RunCursor> cur(inputs.size());
  for (size_t i = 0; i < inputs.size(); i++)
    cur[i].seek(inputs[i].get(), std::string(), &e->perf);
  std::vector<Version> merged;
  while (true) {
    const std::string* min_key = nullptr;
    for (auto& c : cur)
      if (c.valid && (min_key == nullptr || c.key < *min_key)) min_key = &c.key;
    if (min_key == nullptr) break;
    std::string key = *min_key;
    merged.clear();
    for (auto& c : cur) {  // newest source first: global newest-first order
      if (c.valid && c.key == key) {
        for (auto& v : c.versions) merged.push_back(std::move(v));
        c.next_group();
      }
    }
    // trim: versions > min_snap plus the newest <= min_snap
    size_t keep = merged.size();
    for (size_t i = 0; i < merged.size(); i++) {
      if (merged[i].seq <= min_snap) {
        keep = i + 1;
        break;
      }
    }
    merged.resize(keep);
    // apply dying range tombstones now: a version at/below a folded range
    // delete is invisible to every future snapshot (all >= min_snap)
    uint64_t rts = 0;
    for (const auto& rt : dying_rtombs)
      if (rt.seq > rts && rt.start <= key && key < rt.end) rts = rt.seq;
    while (!merged.empty() && merged.back().seq <= rts) merged.pop_back();
    if (merged.empty()) continue;
    // bottom level: a tombstone no snapshot can miss masks nothing anymore
    if (merged.size() == 1 && merged[0].tombstone && merged[0].seq <= min_snap)
      continue;
    w.maybe_rotate(key);
    for (const auto& v : merged) w.add(key, v.seq, v.tombstone, v.value);
  }
  // the output keeps inputs.front()'s name: rename clobbers that path (old
  // readers keep their fd; POSIX keeps the old inode alive), so it must NOT
  // be unlinked below
  auto out = w.finish(cf, max_seq, 1);
  if (!out) return -1;
  // the rename must be on disk before the input unlinks below can be:
  // otherwise a crash could persist the unlinks but not the rename, leaving
  // only the stale pre-merge run at the output's path
  fsync_dir(e->dir);
  {
    std::unique_lock lk(e->mu);
    auto& rs = e->runs[cf];
    // inputs occupy a contiguous tail (flushes only prepend); replace it
    size_t pos = 0;
    while (pos < rs.size() && rs[pos] != inputs.front()) pos++;
    if (pos == rs.size()) { unlink_with_sidecar(out->path); return -1; }  // raced
    rs.resize(pos);
    rs.push_back(out);
  }
  for (size_t i = 1; i < inputs.size(); i++) unlink_with_sidecar(inputs[i]->path);
  e->perf.run_merges.fetch_add(1, std::memory_order_relaxed);
  return 1;
}

// load the newest structurally-valid checkpoint; returns its seq (0 = none)
uint64_t ckpt_load(Engine* e) {
  std::vector<uint64_t> cks;
  list_segs(e->dir, "ckpt", &cks);
  for (auto it = cks.rbegin(); it != cks.rend(); ++it) {
    std::string path = e->dir + "/" + seg_name("ckpt", *it);
    FILE* f = fopen(path.c_str(), "rb");
    if (!f) continue;
    fseek(f, 0, SEEK_END);
    long sz = ftell(f);
    fseek(f, 0, SEEK_SET);
    if (sz < 22) { fclose(f); continue; }
    std::string buf;
    buf.resize(sz);
    bool rok = fread(&buf[0], 1, sz, f) == static_cast<size_t>(sz);
    fclose(f);
    if (!rok || buf.compare(0, 6, kCkptMagic) != 0) continue;
    if (buf.compare(sz - 8, 4, kCkptFoot) != 0) continue;
    uint32_t crc;
    memcpy(&crc, buf.data() + sz - 4, 4);
    const uint8_t* body = reinterpret_cast<const uint8_t*>(buf.data()) + 14;
    size_t body_len = sz - 22;
    if (crc32c(body, body_len) != crc) continue;
    uint64_t at;
    memcpy(&at, buf.data() + 6, 8);
    const uint8_t* p = body;
    const uint8_t* end = body + body_len;
    while (p < end) {
      uint8_t cf = *p++;
      if (cf >= kNumCfs || end - p < 4) break;
      uint32_t klen = read_u32(p);
      if (static_cast<uint64_t>(end - p) < static_cast<uint64_t>(klen) + 4)
        break;
      std::string key(reinterpret_cast<const char*>(p), klen);
      p += klen;
      uint32_t vlen = read_u32(p);
      if (static_cast<uint64_t>(end - p) < vlen) break;
      // checkpoints are written in cf-then-key order: O(1) hinted appends
      put_version(e, e->cfs[cf], std::move(key), at, false,
                  std::string(reinterpret_cast<const char*>(p), vlen), at);
      p += vlen;
    }
    e->seq = at;
    return at;
  }
  return 0;
}

// The frame a batched read answers an absent key with, in place of a length.
constexpr uint32_t kAbsent = 0xFFFFFFFFu;

// The n keys of a batched read: repeated (klen u32 | key); false when the
// buffer does not hold exactly n of them.
bool parse_keys(const uint8_t* p, uint64_t len, uint64_t n,
                std::vector<std::string>* keys) {
  const uint8_t* end = p + len;
  keys->reserve(n);
  for (uint64_t i = 0; i < n; i++) {
    if (end - p < 4) return false;
    uint32_t klen = read_u32(p);
    if (static_cast<uint64_t>(end - p) < klen) return false;
    keys->emplace_back(reinterpret_cast<const char*>(p), klen);
    p += klen;
  }
  return p == end;
}

long hand_out(const std::string& buf, long n, uint8_t** out, uint64_t* out_len) {
  *out = static_cast<uint8_t*>(malloc(buf.size() ? buf.size() : 1));
  memcpy(*out, buf.data(), buf.size());
  *out_len = buf.size();
  return n;
}

// The visible values of keys at snap_seq: res[i] HIT with vals[i], or not
// (MISS/TOMB: absent).  One short critical section for all of them:
// memtable resolve + the (memory-only) range-tombstone check + ONE
// shared_ptr copy of the run list.  Run probing does file IO (pread + crc)
// and must NOT hold the engine lock — runs are immutable and the copied
// shared_ptrs keep their files alive across a concurrent merge swap.  A
// memtable MISS stays valid after unlock: only versions newer than snap can
// appear, and a flush moving versions to a run moves none visible at snap
// (they would have resolved HIT/TOMB here).  0, or <0 on a run read error.
int get_many(Engine* e, int cf, uint64_t snap_seq,
             const std::vector<std::string>& ks, std::vector<std::string>* vals,
             std::vector<Res>* res) {
  size_t n = ks.size();
  res->assign(n, Res::MISS);
  vals->assign(n, std::string());
  std::vector<uint64_t> rts(n, 0);  // newest covering range delete <= snap
  std::vector<std::shared_ptr<Run>> runs_copy;
  {
    std::shared_lock lk(e->mu);
    const Table& t = e->cfs[cf];
    for (size_t i = 0; i < n; i++) {
      e->perf.gets.fetch_add(1, std::memory_order_relaxed);
      const std::string* v = nullptr;
      uint64_t v_seq = 0;
      auto it = t.find(ks[i]);
      if (it != t.end()) (*res)[i] = resolve3(it->second, snap_seq, &v, &v_seq);
      if ((*res)[i] == Res::TOMB) continue;
      rts[i] = rtomb_covering(e->mem_rtombs[cf], ks[i], snap_seq);
      for (const auto& run : e->runs[cf]) {
        uint64_t s = rtomb_covering(run->rtombs, ks[i], snap_seq);
        if (s > rts[i]) rts[i] = s;
      }
      if ((*res)[i] == Res::HIT) {
        e->perf.memtable_hits.fetch_add(1, std::memory_order_relaxed);
        // a range delete masks the memtable value; copy under the lock, the
        // chain may mutate after
        if (rts[i] >= v_seq) (*res)[i] = Res::TOMB;
        else (*vals)[i] = *v;
      }
    }
    runs_copy = e->runs[cf];
  }
  for (size_t i = 0; i < n; i++) {
    if ((*res)[i] != Res::MISS) continue;
    // newest run first; a hit or tombstone in a newer run masks older ones
    for (const auto& run : runs_copy) {
      uint64_t v_seq = 0;
      int rr = run_get(*run, ks[i], snap_seq, &(*vals)[i], &v_seq, &e->perf);
      if (rr < 0) return -3;
      if (rr == 0) continue;
      // a range delete masks the run value too
      (*res)[i] = (rr == 1 && rts[i] < v_seq) ? Res::HIT : Res::TOMB;
      break;
    }
  }
  return 0;
}

}  // namespace

extern "C" {

static thread_local const enc::State* g_pending_enc = nullptr;

// Every handle that crosses the C ABI is a guard::Handle<Engine> (guard.h):
// never freed, so a call through a copy of it made after eng_close is turned
// away with guard::kClosed instead of walking a freed engine, and a call in
// flight when eng_close comes holds the engine until it returns.
#define ENG_OR(closed) GUARD_OR(Engine, closed)

static void* guarded(Engine* e) {
  return e == nullptr ? nullptr : new guard::Handle<Engine>(e);
}

void* eng_open() { return guarded(new Engine()); }

// Open (or create) a durable engine on a directory.  sync_mode: 1 = WAL
// fdatasync on every commit (crash-durable), 0 = OS-buffered (fast, loses
// the tail on power loss — still consistent via WAL framing).
static enc::State make_enc_state(uint32_t current_id, const uint32_t* ids,
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

static Engine* open_at(const char* path, int sync_mode) {
  Engine* e = new Engine();
  e->dir = path;
  e->sync_mode = sync_mode;
  if (g_pending_enc) e->enc = *g_pending_enc;
  mkdir(path, 0755);
  // drop temp files of crashed flushes/merges (never renamed = never trusted)
  if (DIR* d = opendir(path)) {
    struct dirent* ent;
    while ((ent = readdir(d)) != nullptr) {
      std::string n = ent->d_name;
      if (n.size() > 4 && n.compare(n.size() - 4, 4, ".tmp") == 0)
        unlink((e->dir + "/" + n).c_str());
    }
    closedir(d);
  }
  // sorted runs first (newest list position = highest seq).  Only runs at or
  // below the newest completion marker are trusted: runs above it belong to
  // a flush that crashed mid-way (its data is still in the WAL), and once a
  // merged-kind run is seen, everything older in that CF was its input.
  std::vector<uint64_t> marks;
  list_segs(e->dir, "mark", &marks);
  uint64_t mark = marks.empty() ? 0 : marks.back();
  bool have_runs = false;
  for (int cf = 0; cf < kNumCfs; cf++) {
    std::vector<uint64_t> seqs;
    list_segs(e->dir, run_prefix(cf), &seqs);
    for (auto it = seqs.rbegin(); it != seqs.rend(); ++it) {
      std::string rp = e->dir + "/" + seg_name(run_prefix(cf), *it);
      if (*it > mark) {
        unlink_with_sidecar(rp);  // partial flush: WAL still covers these records
        continue;
      }
      if (!e->runs[cf].empty() && e->runs[cf].back()->kind == 1) {
        unlink_with_sidecar(rp);  // leftover input of a completed full-cf merge
        continue;
      }
      auto run = run_open(rp, e->enc_snapshot());
      if (!run) {
        // a trusted run (at/below the marker) is damaged and the WAL that
        // covered it is gone: opening would silently lose acked writes —
        // refuse, like a torn WAL segment
        delete e;
        return nullptr;
      }
      e->runs[cf].push_back(run);
      have_runs = true;
    }
  }
  e->flushed_seq = mark;
  e->seq = e->flushed_seq;
  // legacy full-state checkpoints load only when no runs exist (runs always
  // supersede them: a flush deletes folded checkpoints, and loading an older
  // checkpoint into the memtable would break the memtable-newest invariant)
  uint64_t ck = have_runs ? e->flushed_seq : ckpt_load(e);
  if (ck > e->seq) e->seq = ck;
  std::vector<uint64_t> wals;
  list_segs(e->dir, "wal", &wals);
  for (uint64_t s : wals) {
    if (s < ck) continue;  // fully folded into the checkpoint/runs
    if (wal_replay(e, e->dir + "/" + seg_name("wal", s)) != 0) {
      delete e;  // could not repair a torn segment: refuse the open
      return nullptr;
    }
  }
  // recovered WAL segments are re-folded on the next checkpoint; append to a
  // fresh segment so replay order stays strictly by start-seq
  if (wal_open_segment(e, e->seq) != 0) {
    delete e;
    return nullptr;
  }
  // which CF the runs' newest batch touched is not recorded: every CF starts
  // as touched at the recovered seq (too high costs a scan, never skips one)
  for (int cf = 0; cf < kNumCfs; cf++) e->cf_touched_seq[cf] = e->seq;
  return e;
}

void* eng_open_at(const char* path, int sync_mode) {
  return guarded(open_at(path, sync_mode));
}

// Durable open with encryption at rest: (ids, keys32) is the data-key
// registry from the Python DataKeyManager (manager/mod.rs:398 role); files
// written from here on encrypt under `current_id`, existing files decrypt
// under whichever key their sidecar names, and sidecar-less files read as
// plaintext (migration).  An unknown key id in any sidecar fails the open.
void* eng_open_at_enc(const char* path, int sync_mode, uint32_t current_id,
                      const uint32_t* ids, const uint8_t* keys32, int n) {
  // recovery must decrypt, so the key registry has to exist before the
  // directory scan — stage it on a throwaway engine, then hand it to the
  // real open through a thread-local (the open path stays ONE function)
  enc::State st = make_enc_state(current_id, ids, keys32, n);
  g_pending_enc = &st;
  Engine* e = open_at(path, sync_mode);
  g_pending_enc = nullptr;
  return guarded(e);
}

// Rotate the data-key registry on a RUNNING engine: new runs/WAL segments
// use `current_id`; files already on disk keep their sidecar key.
int eng_set_encryption(void* h, uint32_t current_id, const uint32_t* ids,
                       const uint8_t* keys32, int n) {
  ENG_OR(guard::kClosed);
  // write_mu keeps the live WAL segment's identity stable; enc_mu covers
  // concurrent readers of the registry (background compaction writers)
  std::lock_guard<std::mutex> wl(e->write_mu);
  std::lock_guard<std::mutex> el(e->enc_mu);
  e->enc = make_enc_state(current_id, ids, keys32, n);
  return 0;
}

// Waits for the calls in flight, then frees the engine; the handle stays.
// A second close finds nothing to free.
void eng_close(void* h) {
  Engine* e = guard::take<Engine>(h);
  if (e == nullptr) return;
  if (e->wal_fd >= 0) close(e->wal_fd);
  delete e;
}

int eng_write(void* h, const uint8_t* data, uint64_t len) {
  ENG_OR(guard::kClosed);
  std::unique_lock wlk(e->write_mu);
  if (e->failed) return -5;
  // validate BEFORE logging: a malformed batch must never reach the WAL
  int r = validate_batch(data, len);
  if (r != 0) return r;
  // seq is only mutated by writers, and writers serialize on write_mu —
  // reading it here without mu races nothing
  uint64_t seq = e->seq + 1;
  // WAL first: a batch is committed iff its record is durable (fsync'd
  // before apply, exactly rocksdb's WriteBatch-then-memtable order).
  // Deliberately OUTSIDE mu: the fdatasync must not stall readers.
  if (wal_append(e, seq, data, len) != 0) {
    e->failed = true;
    return -4;
  }
  bool need_flush;
  {
    std::unique_lock lk(e->mu);
    r = apply_batch(e, data, len, seq);
    if (r != 0) return r;  // unreachable after validate; defensive
    e->seq = seq;
    need_flush = !e->dir.empty() &&
        ((e->wal_limit > 0 && e->wal_bytes >= e->wal_limit) ||
         (e->mem_limit > 0 && e->mem_bytes >= e->mem_limit));
  }
  if (need_flush) {
    // inline memtable flush (rocksdb's memtable-full write stall, bounded
    // by memtable size — never O(database)); a failed flush that lost its
    // log fd must stop acking writes, not go silently non-durable
    std::unique_lock lk(e->mu);
    if (flush_memtable(e) != 0 && e->wal_fd < 0) e->failed = true;
  }
  return 0;
}

// Build an SST file at `path` from a serialized run of (cf|klen|key|vlen|val)
// records (must be sorted by (cf, key)).  Standalone: no engine handle.
int eng_build_sst(const char* path, const uint8_t* body, uint64_t len) {
  // frame it, then validate the full image (sortedness + crc round-trip)
  std::string img;
  img.reserve(18 + len);
  img.append(kSstMagic, 6);
  append_u32(img, 0);  // count unused (size-delimited records); kept for layout
  img.append(reinterpret_cast<const char*>(body), len);
  img.append(kSstFoot, 4);
  append_u32(img, crc32c(body, len));
  if (sst_validate(reinterpret_cast<const uint8_t*>(img.data()), img.size()) != 0)
    return -3;
  std::string tmp = std::string(path) + ".tmp";
  FILE* f = fopen(tmp.c_str(), "wb");
  if (!f) return -1;
  bool ok = fwrite(img.data(), 1, img.size(), f) == img.size() &&
            fflush(f) == 0 && fsync(fileno(f)) == 0;
  fclose(f);
  if (!ok || rename(tmp.c_str(), path) != 0) {
    unlink(tmp.c_str());
    return -1;
  }
  return 0;
}

// Ingest an external SST: validate, copy into the engine dir as sst-<seq>,
// WAL-log the op-4 reference, load.  For a pure in-memory engine the file
// is loaded in place (no copy, no WAL).
int eng_ingest_sst(void* h, const char* src_path) {
  ENG_OR(guard::kClosed);
  std::unique_lock wlk(e->write_mu);  // WAL writer: ahead of mu (lock order)
  std::unique_lock lk(e->mu);
  if (e->failed) return -5;
  FILE* f = fopen(src_path, "rb");
  if (!f) return -1;
  fseek(f, 0, SEEK_END);
  long sz = ftell(f);
  fseek(f, 0, SEEK_SET);
  if (sz < 18 || sz > (1ll << 40)) {  // bounds BEFORE resize: a directory
    fclose(f);                         // fopen succeeds and ftell lies
    return -1;
  }
  std::string buf;
  buf.resize(sz);
  bool rok = fread(&buf[0], 1, sz, f) == static_cast<size_t>(sz);
  fclose(f);
  if (!rok) return -1;
  int v = sst_validate(reinterpret_cast<const uint8_t*>(buf.data()), buf.size());
  if (v != 0) return v;
  uint64_t seq = e->seq + 1;
  std::string rec_key;
  if (e->dir.empty()) {
    rec_key = src_path;  // in-memory: reference the source directly
  } else {
    rec_key = seg_name("sst", seq);
    std::string dst = e->dir + "/" + rec_key;
    std::string tmp = dst + ".tmp";
    FILE* out = fopen(tmp.c_str(), "wb");
    if (!out) return -1;
    bool ok = fwrite(buf.data(), 1, buf.size(), out) == buf.size() &&
              fflush(out) == 0 && fsync(fileno(out)) == 0;
    fclose(out);
    if (!ok || rename(tmp.c_str(), dst.c_str()) != 0) {
      unlink(tmp.c_str());
      return -1;
    }
    fsync_dir(e->dir);  // the file must exist before its WAL reference
  }
  // op-4 batch record: | op | cf | klen | name | vlen=0 |
  std::string rec;
  rec.push_back(4);
  rec.push_back(0);
  append_u32(rec, static_cast<uint32_t>(rec_key.size()));
  rec.append(rec_key);
  append_u32(rec, 0);
  const uint8_t* rp = reinterpret_cast<const uint8_t*>(rec.data());
  if (wal_append(e, seq, rp, rec.size()) != 0) {
    e->failed = true;
    return -4;
  }
  // apply straight from the validated bytes — no second read/parse of the
  // copy; WAL replay goes through apply_batch → load_sst_file instead
  int r = load_sst_from_buf(
      e, reinterpret_cast<const uint8_t*>(buf.data()), buf.size(), seq);
  if (r != 0) {
    // The WAL record for this seq is already durable; failing to apply it
    // without bumping e->seq would let the next write reuse the seq and make
    // replay silently drop the second (acked) record.  Stop acking instead.
    e->failed = true;
    return r;
  }
  e->seq = seq;
  return 0;
}

int eng_checkpoint(void* h) {
  // checkpoint == memtable flush: durable sorted runs + WAL truncation.
  // (The legacy O(DB) full-state spill is gone; ckpt_load remains for
  // reading directories written by it.)
  ENG_OR(guard::kClosed);
  std::unique_lock wlk(e->write_mu);  // flush rotates the WAL segment
  std::unique_lock lk(e->mu);
  if (e->dir.empty()) return -1;
  int r = flush_memtable(e);
  if (r != 0 && e->wal_fd < 0) e->failed = true;  // log fd lost: stop acking
  return r;
}

int eng_flush(void* h) { return eng_checkpoint(h); }

int eng_set_mem_limit(void* h, uint64_t bytes) {
  ENG_OR(guard::kClosed);
  std::unique_lock lk(e->mu);
  e->mem_limit = bytes;
  return 0;
}

// number of on-disk sorted runs for one CF
int eng_run_count(void* h, int cf) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::shared_lock lk(e->mu);
  return static_cast<int>(e->runs[cf].size());
}

// merge all runs of one CF into a single run (background compaction step);
// returns 1 when a merge happened, 0 when <2 runs, <0 on error
int eng_merge_runs(void* h, int cf) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  return merge_runs_cf(e, cf);
}

// perf context (engine_rocks/src/perf_context.rs):
// out[0]=gets out[1]=memtable_hits out[2]=run_probes out[3]=bloom_skips
// out[4]=blocks_read out[5]=flushes out[6]=run_merges
int eng_perf(void* h, uint64_t* out) {
  ENG_OR(guard::kClosed);
  out[0] = e->perf.gets.load(std::memory_order_relaxed);
  out[1] = e->perf.memtable_hits.load(std::memory_order_relaxed);
  out[2] = e->perf.run_probes.load(std::memory_order_relaxed);
  out[3] = e->perf.bloom_skips.load(std::memory_order_relaxed);
  out[4] = e->perf.blocks_read.load(std::memory_order_relaxed);
  out[5] = e->perf.flushes.load(std::memory_order_relaxed);
  out[6] = e->perf.run_merges.load(std::memory_order_relaxed);
  return 0;
}

int eng_set_wal_limit(void* h, uint64_t bytes) {
  ENG_OR(guard::kClosed);
  std::unique_lock lk(e->mu);
  e->wal_limit = bytes;
  return 0;
}

// import-mode tuning (sst_importer/src/import_mode.rs): bulk loads drop to
// buffered WAL writes, then restore sync + checkpoint when done.  Returns
// non-zero if the flush that closes the unsynced window fails — in that case
// the buffered tail is NOT durable and the engine stops acking writes rather
// than promising per-commit durability it cannot deliver.
int eng_set_sync(void* h, int sync_mode) {
  ENG_OR(guard::kClosed);
  std::unique_lock wlk(e->write_mu);  // WAL state lives under write_mu
  if (e->sync_mode == 0 && sync_mode == 1 && e->wal_fd >= 0) {
    if (fdatasync(e->wal_fd) != 0) {
      e->failed = true;
      return -4;
    }
  }
  e->sync_mode = sync_mode;
  return 0;
}

uint64_t eng_seq(void* h) {
  ENG_OR(UINT64_MAX);
  std::shared_lock lk(e->mu);
  return e->seq;
}

// seq of the newest batch that touched `cf` (Engine::cf_touched_seq)
uint64_t eng_cf_touched_seq(void* h, int cf) {
  ENG_OR(UINT64_MAX);
  if (cf < 0 || cf >= kNumCfs) return UINT64_MAX;
  std::shared_lock lk(e->mu);
  return e->cf_touched_seq[cf];
}

uint64_t eng_mem_bytes(void* h) {
  ENG_OR(UINT64_MAX);
  std::shared_lock lk(e->mu);
  return e->mem_bytes;
}

uint64_t eng_wal_bytes(void* h) {
  ENG_OR(UINT64_MAX);
  std::lock_guard<std::mutex> wlk(e->write_mu);  // wal state's guard
  return e->wal_bytes;
}

uint64_t eng_snapshot(void* h) {
  ENG_OR(UINT64_MAX);
  std::unique_lock lk(e->mu);
  e->snapshots.insert(e->seq);
  return e->seq;
}

void eng_release_snapshot(void* h, uint64_t seq) {
  ENG_OR();
  std::unique_lock lk(e->mu);
  auto it = e->snapshots.find(seq);
  if (it != e->snapshots.end()) e->snapshots.erase(it);
}

// get: returns 1 + copies value if found, 0 if not, <0 on error.
// caller frees *out with eng_free.
int eng_get(void* h, int cf, const uint8_t* key, uint64_t klen,
            uint64_t snap_seq, uint8_t** out, uint64_t* out_len) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::vector<std::string> ks{std::string(reinterpret_cast<const char*>(key), klen)};
  std::vector<std::string> vals;
  std::vector<Res> res;
  if (get_many(e, cf, snap_seq, ks, &vals, &res) != 0) return -3;
  if (res[0] != Res::HIT) return 0;
  *out = static_cast<uint8_t*>(malloc(vals[0].size()));
  memcpy(*out, vals[0].data(), vals[0].size());
  *out_len = vals[0].size();
  return 1;
}

// scan [start, end) visible at snap_seq; limit 0 = unlimited.
// Output buffer: repeated (klen u32 | key | vlen u32 | val); caller eng_free.
// Returns number of pairs, or <0 on error.
// The shared lock covers only MergeIter::init (a bounded memtable
// materialization + run shared_ptr copies — memory-only); the run-block
// pread+crc IO runs unlocked, so a cold range scan never stalls writers,
// and ChunkedMerge re-inits keep any single locked walk ≤ kScanMemChunk
// memtable entries.
long eng_scan(void* h, int cf, uint64_t snap_seq, const uint8_t* start,
              uint64_t start_len, const uint8_t* end_key, uint64_t end_len,
              int has_end, uint64_t limit, int reverse, uint8_t** out,
              uint64_t* out_len) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::string s(reinterpret_cast<const char*>(start), start_len);
  std::string en(reinterpret_cast<const char*>(end_key), end_len);
  std::string buf;
  long n = 0;
  auto emit = [&](const std::string& k, const std::string& v) {
    append_u32(buf, static_cast<uint32_t>(k.size()));
    buf.append(k);
    append_u32(buf, static_cast<uint32_t>(v.size()));
    buf.append(v);
    n++;
  };
  // a limited scan caps its locked walk proportionally to the output it can
  // produce (tombstone-heavy ranges continue via chunk re-init)
  uint64_t cap = limit ? std::max<uint64_t>(2 * limit, 4096) : kScanMemChunk;
  std::string k, v;
  if (!reverse) {
    ChunkedMerge cm(e, cf, snap_seq, s, en, has_end != 0, cap);
    while ((limit == 0 || n < static_cast<long>(limit)) && cm.next(&k, &v))
      emit(k, v);
  } else {
    ReverseChunkedMerge cm(e, cf, snap_seq, s, en, has_end != 0, cap);
    while ((limit == 0 || n < static_cast<long>(limit)) && cm.next(&k, &v))
      emit(k, v);
  }
  *out = static_cast<uint8_t*>(malloc(buf.size()));
  memcpy(*out, buf.data(), buf.size());
  *out_len = buf.size();
  return n;
}

// cursor-style seek: find first key >= target (or last key <= target when
// for_prev) within [lower, upper); returns 1 + key/value copies, else 0.
int eng_seek(void* h, int cf, uint64_t snap_seq, const uint8_t* target,
             uint64_t target_len, const uint8_t* lower, uint64_t lower_len,
             const uint8_t* upper, uint64_t upper_len, int has_upper,
             int for_prev, uint8_t** kout, uint64_t* kout_len, uint8_t** vout,
             uint64_t* vout_len) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::string tg(reinterpret_cast<const char*>(target), target_len);
  std::string lo(reinterpret_cast<const char*>(lower), lower_len);
  std::string up(reinterpret_cast<const char*>(upper), upper_len);
  std::string k, v;
  bool found;
  // single-row seeks start with a tiny locked walk (cursor stepping issues
  // one seek per row); a run of snapshot-invisible or tombstoned entries
  // continues via chunk re-init with ×4 growth
  constexpr uint64_t kSeekMemChunk = 16;
  if (!for_prev) {
    ChunkedMerge cm(e, cf, snap_seq, tg < lo ? lo : tg, up, has_upper != 0,
                    kSeekMemChunk);
    found = cm.next(&k, &v);
  } else {
    // last visible key <= target within [lower, upper): the reverse bound is
    // exclusive, so extend the inclusive target by one zero byte
    std::string end_incl = tg + std::string(1, '\0');
    if (has_upper && up < end_incl) end_incl = up;
    ReverseChunkedMerge cm(e, cf, snap_seq, lo, end_incl, true, kSeekMemChunk);
    found = cm.next(&k, &v);
  }
  if (!found) return 0;
  *kout = static_cast<uint8_t*>(malloc(k.size()));
  memcpy(*kout, k.data(), k.size());
  *kout_len = k.size();
  *vout = static_cast<uint8_t*>(malloc(v.size()));
  memcpy(*vout, v.data(), v.size());
  *vout_len = v.size();
  return 1;
}

// eng_get of n keys at one snapshot, in one crossing and one hold of the
// shared lock (get_many).  Keys in: repeated (klen u32 | key).  Out, a frame
// a key in order: (vlen u32 | val), or kAbsent.  Returns n, <0 on error;
// caller eng_free.
long eng_multi_get(void* h, int cf, uint64_t snap_seq, const uint8_t* keys,
                   uint64_t keys_len, uint64_t n, uint8_t** out,
                   uint64_t* out_len) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::vector<std::string> ks, vals;
  std::vector<Res> res;
  if (!parse_keys(keys, keys_len, n, &ks)) return -4;
  if (get_many(e, cf, snap_seq, ks, &vals, &res) != 0) return -3;
  std::string buf;
  for (uint64_t i = 0; i < n; i++) {
    if (res[i] != Res::HIT) {
      append_u32(buf, kAbsent);
      continue;
    }
    append_u32(buf, static_cast<uint32_t>(vals[i].size()));
    buf.append(vals[i]);
  }
  return hand_out(buf, static_cast<long>(n), out, out_len);
}

// For each of n user keys, what eng_seek gives at user_key ++ desc(ts)
// within [lower, upper) if its user-key part (all but the last 8 bytes) is
// user_key: the key's newest version at or below ts.  Only that key's
// versions can answer, so each merge is bounded to them; the memtable walks
// of all n are taken under ONE hold of the shared lock (a key with more
// versions than a walk's cap continues as eng_seek's chunked merge does).
// Keys in: repeated (klen u32 | key).  Out, a frame a key in order:
// (klen u32 | key | vlen u32 | val), or kAbsent.  Returns n, <0 on error;
// caller eng_free.
long eng_multi_seek_newest(void* h, int cf, uint64_t snap_seq,
                           const uint8_t* keys, uint64_t keys_len, uint64_t n,
                           uint64_t ts, const uint8_t* lower,
                           uint64_t lower_len, const uint8_t* upper,
                           uint64_t upper_len, int has_upper, uint8_t** out,
                           uint64_t* out_len) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::vector<std::string> ks;
  if (!parse_keys(keys, keys_len, n, &ks)) return -4;
  std::string lo(reinterpret_cast<const char*>(lower), lower_len);
  std::string up(reinterpret_cast<const char*>(upper), upper_len);
  char desc[8];
  for (int b = 0; b < 8; b++)
    desc[b] = static_cast<char>((~ts >> (56 - 8 * b)) & 0xFF);
  constexpr uint64_t kVersionsWalk = 64;  // memtable entries a key, locked
  std::vector<std::string> starts(n), ends(n);
  std::vector<MergeIter> its(n);
  {
    std::shared_lock lk(e->mu);
    for (uint64_t i = 0; i < n; i++) {
      std::string tg = ks[i] + std::string(desc, 8);
      starts[i] = tg < lo ? lo : tg;
      // past the key's oldest possible version; nothing beyond has it as
      // its user-key part
      ends[i] = ks[i] + std::string(8, '\xff') + std::string(1, '\0');
      if (has_upper && up < ends[i]) ends[i] = up;
      its[i].mem_cap = kVersionsWalk;
      its[i].mem_bytes_cap = kMemChunkBytes;
      its[i].init(e, cf, snap_seq, starts[i], ends[i], true);
    }
  }
  std::string buf, k, v;
  for (uint64_t i = 0; i < n; i++) {
    bool found = its[i].next(&k, &v);
    if (!found && its[i].truncated) {
      ChunkedMerge cm(e, cf, snap_seq, its[i].resume_key, ends[i], true,
                      kVersionsWalk * 4);
      found = cm.next(&k, &v);
    }
    if (!found || k.size() != ks[i].size() + 8 ||
        k.compare(0, ks[i].size(), ks[i]) != 0) {
      append_u32(buf, kAbsent);
      continue;
    }
    append_u32(buf, static_cast<uint32_t>(k.size()));
    buf.append(k);
    append_u32(buf, static_cast<uint32_t>(v.size()));
    buf.append(v);
  }
  return hand_out(buf, static_cast<long>(n), out, out_len);
}

void eng_free(uint8_t* p) { free(p); }

uint64_t eng_stats_keys(void* h, int cf) {
  ENG_OR(UINT64_MAX);
  std::shared_lock lk(e->mu);
  return e->cfs[cf].size();
}

// --- compaction -------------------------------------------------------------
//
// The write path only trims a key's version chain when that key is written
// again; deleted-and-never-touched keys would otherwise hold a tombstone
// forever (rocksdb removes them in background compaction).  One compaction
// step walks at most max_keys keys of one CF under the write lock, drops
// versions no live snapshot can see, and physically erases keys whose
// newest reachable state is a tombstone.  The caller (a Python driver
// thread — the GIL is released during the call, so it is genuinely
// background work) resumes from *resume to bound write-lock hold times,
// exactly the slice-by-slice shape of rocksdb's per-file compactions.
//
// Returns versions dropped (erased keys count their whole chain); sets
// *done=1 when the CF is exhausted, else *resume/*resume_len (caller
// eng_free) is the key to continue from.
long eng_compact_step(void* h, int cf, const uint8_t* from, uint64_t from_len,
                      uint64_t max_keys, uint8_t** resume,
                      uint64_t* resume_len, int* done) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::unique_lock lk(e->mu);
  Table& t = e->cfs[cf];
  uint64_t min_snap = std::min(e->min_live_snapshot(), e->seq);
  long dropped = 0;
  // deferred range-delete application: with no runs (in-memory engines, or
  // durable CFs before their first flush) the memtable is the whole store,
  // so a range tombstone no snapshot can see below is applied here and
  // reclaimed — compaction is where deferred deletes get paid for.  With
  // runs present the tombstone still masks flushed data and must stay
  // until flush carries it into a run and a merge folds it.
  if (e->runs[cf].empty() && !e->mem_rtombs[cf].empty()) {
    std::vector<RangeTomb> still_needed;
    for (auto& rt : e->mem_rtombs[cf]) {
      if (rt.seq > min_snap) {
        still_needed.push_back(std::move(rt));
        continue;
      }
      auto rit = t.lower_bound(rt.start);
      auto stop = t.lower_bound(rt.end);
      while (rit != stop) {
        Chain& ch = rit->second;
        while (!ch.empty() && ch.back().seq <= rt.seq) {
          e->mem_bytes -= std::min(e->mem_bytes,
                                   ch.back().value.size() + kVersionOverhead);
          ch.pop_back();
          dropped++;
        }
        if (ch.empty()) {
          e->mem_bytes -= std::min(e->mem_bytes,
                                   rit->first.size() + kKeyOverhead);
          rit = t.erase(rit);
        } else {
          ++rit;
        }
      }
      e->mem_bytes -= std::min(
          e->mem_bytes, rt.start.size() + rt.end.size() + kVersionOverhead);
    }
    e->mem_rtombs[cf] = std::move(still_needed);
  }
  uint64_t seen = 0;
  auto it = t.lower_bound(std::string(reinterpret_cast<const char*>(from), from_len));
  while (it != t.end() && seen < max_keys) {
    Chain& chain = it->second;
    // trim: keep versions newer than min_snap plus the newest one <= min_snap
    size_t keep = chain.size();
    for (size_t i = 0; i < chain.size(); i++) {
      if (chain[i].seq <= min_snap) {
        keep = i + 1;
        break;
      }
    }
    for (size_t i = keep; i < chain.size(); i++) {
      e->mem_bytes -= std::min(e->mem_bytes,
                               chain[i].value.size() + kVersionOverhead);
      dropped++;
    }
    chain.resize(keep);
    // erase: the newest version overall is a tombstone no snapshot can miss
    // — but only when no sorted run could hold an older value it still
    // masks; with runs present the tombstone must survive in the memtable
    // (and later in a run) until a bottom-level merge drops it
    if (!chain.empty() && chain.front().tombstone &&
        chain.front().seq <= min_snap && e->runs[cf].empty()) {
      dropped += static_cast<long>(chain.size());
      uint64_t key_cost = it->first.size() + kKeyOverhead;
      for (const auto& v : chain)
        key_cost += v.value.size() + kVersionOverhead;
      e->mem_bytes -= std::min(e->mem_bytes, key_cost);
      it = t.erase(it);
    } else {
      ++it;
    }
    seen++;
  }
  if (it == t.end()) {
    *done = 1;
  } else {
    *done = 0;
    *resume = static_cast<uint8_t*>(malloc(it->first.size()));
    memcpy(*resume, it->first.data(), it->first.size());
    *resume_len = it->first.size();
  }
  return dropped;
}

// --- MVCC range properties --------------------------------------------------
//
// The role of engine_rocks' MvccPropertiesCollector (properties.rs): cheap
// per-range statistics that tell GC whether a sweep is worth it at all.
// The collector knows this framework's CF_WRITE shape — keys carry an
// 8-byte descending-encoded commit_ts suffix, values start with the write
// type byte ('P'ut/'D'elete/'L'ock/'R'ollback).
//
// out[0]=num_entries  out[1]=num_rows (distinct user keys)
// out[2]=num_puts     out[3]=num_deletes
// out[4]=num_locks_rollbacks       out[5]=min_commit_ts  out[6]=max_commit_ts
// out[7]=max_row_versions (worst per-key version count)
int eng_mvcc_props(void* h, int cf, const uint8_t* start, uint64_t start_len,
                   const uint8_t* end_key, uint64_t end_len, int has_end,
                   uint64_t snap_seq, uint64_t* out) {
  ENG_OR(guard::kClosed);
  if (cf < 0 || cf >= kNumCfs) return -2;
  std::string s(reinterpret_cast<const char*>(start), start_len);
  std::string en(reinterpret_cast<const char*>(end_key), end_len);
  uint64_t entries = 0, rows = 0, puts = 0, dels = 0, other = 0;
  uint64_t min_ts = UINT64_MAX, max_ts = 0, max_row = 0, cur_row = 0;
  std::string cur_user;
  bool have_user = false;
  // Callers pass the CURRENT seq, not a registered snapshot; ChunkedMerge's
  // chunk re-inits are only consistent at a *pinned* seq (otherwise version
  // chains visible at snap_seq can be trimmed between chunks), so register
  // it for the duration of the walk.
  {
    std::unique_lock lk(e->mu);
    e->snapshots.insert(snap_seq);
  }
  ChunkedMerge mi(e, cf, snap_seq, s, en, has_end != 0, kScanMemChunk);
  std::string k, val;
  while (mi.next(&k, &val)) {
    const std::string* v = &val;
    entries++;
    if (k.size() >= 8) {
      // commit_ts rides the last 8 key bytes, bit-inverted big-endian
      uint64_t ts = 0;
      for (int i = 0; i < 8; i++)
        ts = (ts << 8) | static_cast<uint8_t>(~k[k.size() - 8 + i]);
      if (ts < min_ts) min_ts = ts;
      if (ts > max_ts) max_ts = ts;
      std::string user = k.substr(0, k.size() - 8);
      if (!have_user || user != cur_user) {
        rows++;
        cur_user = std::move(user);
        have_user = true;
        cur_row = 0;
      }
      cur_row++;
      if (cur_row > max_row) max_row = cur_row;
    }
    if (!v->empty()) {
      char wt = (*v)[0];
      if (wt == 'P') puts++;
      else if (wt == 'D') dels++;
      else other++;
    }
  }
  {
    std::unique_lock lk(e->mu);
    auto sit = e->snapshots.find(snap_seq);
    if (sit != e->snapshots.end()) e->snapshots.erase(sit);
  }
  out[0] = entries;
  out[1] = rows;
  out[2] = puts;
  out[3] = dels;
  out[4] = other;
  out[5] = min_ts == UINT64_MAX ? 0 : min_ts;
  out[6] = max_ts;
  out[7] = max_row;
  return 0;
}

}  // extern "C"
