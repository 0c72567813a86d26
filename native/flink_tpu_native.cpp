// Native host-runtime components for flink_tpu.
//
// The reference keeps its hot host-side structures native: off-heap
// MemorySegments (flink-core .../core/memory/MemorySegment.java:70), the
// Netty buffer pool (NetworkBufferPool.java:63), JNI LSM state stores
// (frocksdbjni / forstjni), and Cython record coders
// (flink-python fn_execution/coder_impl_fast.pyx). This module provides the
// TPU-native equivalents for the host half of the pipeline — everything
// between the wire/file format and the device arrays:
//
//   1. KeyDict     — batch open-addressing key dictionary: raw keys -> dense
//                    device row ids (the host half of keyBy; the device half
//                    is the all-to-all in ops/exchange.py).
//   2. csv codec   — delimited text -> columnar (int64 key-ish column,
//                    double value column, int64 timestamp column).
//   3. SegmentRing — fixed-size-segment SPSC ring: bounded ingest queue
//                    between a producer (network/file thread) and the step
//                    loop; "no free segment" is the backpressure signal
//                    (LocalBufferPool exhaustion analogue).
//   4. record lanes — one dispatch's record steps written into its staging
//                    arrays (srel and the staged fields) in one call, each
//                    record row read once.
//
// C ABI only (ctypes binding in flink_tpu/utils/native_bridge.py).

#include <pthread.h>

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <vector>

extern "C" {

// ===========================================================================
// 1. KeyDict
// ===========================================================================

struct KeyDict {
  // open addressing, power-of-two capacity, linear probing
  std::vector<int64_t> hashes;     // slot -> key hash (or EMPTY)
  std::vector<int32_t> ids;        // slot -> dense id
  std::vector<int64_t> int_keys;   // id -> int key (int mode)
  std::vector<int64_t> str_off;    // id -> offset into arena (str mode)
  std::vector<int32_t> str_len;    // id -> length
  std::vector<char> arena;         // string storage
  int64_t mask = 0;
  int64_t size = 0;
  bool string_mode = false;
};

static const int64_t EMPTY = INT64_MIN;

static inline uint64_t mix64(uint64_t x) {
  x ^= x >> 33;
  x *= 0xff51afd7ed558ccdULL;
  x ^= x >> 33;
  x *= 0xc4ceb9fe1a85ec53ULL;
  x ^= x >> 33;
  return x;
}

static inline uint64_t hash_bytes(const char* data, int64_t len) {
  // FNV-1a 64 then mixed; sufficient dispersion for a dictionary
  uint64_t h = 1469598103934665603ULL;
  for (int64_t i = 0; i < len; i++) {
    h ^= (unsigned char)data[i];
    h *= 1099511628211ULL;
  }
  return mix64(h);
}

static void kd_rehash(KeyDict* kd, int64_t new_cap) {
  std::vector<int64_t> old_hashes = std::move(kd->hashes);
  std::vector<int32_t> old_ids = std::move(kd->ids);
  kd->hashes.assign(new_cap, EMPTY);
  kd->ids.assign(new_cap, -1);
  kd->mask = new_cap - 1;
  for (size_t i = 0; i < old_hashes.size(); i++) {
    if (old_hashes[i] == EMPTY) continue;
    uint64_t slot = (uint64_t)old_hashes[i] & kd->mask;
    while (kd->hashes[slot] != EMPTY) slot = (slot + 1) & kd->mask;
    kd->hashes[slot] = old_hashes[i];
    kd->ids[slot] = old_ids[i];
  }
}

KeyDict* kd_new(int64_t initial_capacity, int string_mode) {
  KeyDict* kd = new KeyDict();
  int64_t cap = 64;
  while (cap < initial_capacity * 2) cap <<= 1;
  kd->hashes.assign(cap, EMPTY);
  kd->ids.assign(cap, -1);
  kd->mask = cap - 1;
  kd->string_mode = string_mode != 0;
  return kd;
}

void kd_free(KeyDict* kd) { delete kd; }

int64_t kd_size(KeyDict* kd) { return kd->size; }

static inline void kd_maybe_grow(KeyDict* kd) {
  if (kd->size * 10 >= (kd->mask + 1) * 7) kd_rehash(kd, (kd->mask + 1) * 2);
}

// int64 keys -> dense ids; out_new[i]=1 when the key was first seen in this
// call (caller appends those keys to its id->key list in lane order).
int64_t kd_lookup_or_insert_i64(KeyDict* kd, const int64_t* keys, int64_t n,
                                int32_t* out_ids, uint8_t* out_new) {
  for (int64_t i = 0; i < n; i++) {
    kd_maybe_grow(kd);
    int64_t h = (int64_t)mix64((uint64_t)keys[i]);
    if (h == EMPTY) h = 0;
    uint64_t slot = (uint64_t)h & kd->mask;
    for (;;) {
      if (kd->hashes[slot] == EMPTY) {
        kd->hashes[slot] = h;
        int32_t id = (int32_t)kd->size++;
        kd->ids[slot] = id;
        kd->int_keys.push_back(keys[i]);
        out_ids[i] = id;
        out_new[i] = 1;
        break;
      }
      if (kd->hashes[slot] == h && kd->int_keys[kd->ids[slot]] == keys[i]) {
        out_ids[i] = kd->ids[slot];
        out_new[i] = 0;
        break;
      }
      slot = (slot + 1) & kd->mask;
    }
  }
  return kd->size;
}

// fixed-width (numpy 'S<w>') byte keys; trailing NULs are part of the key
// (numpy pads consistently, so equality is well-defined).
int64_t kd_lookup_or_insert_fixed(KeyDict* kd, const char* data, int64_t width,
                                  int64_t n, int32_t* out_ids, uint8_t* out_new) {
  for (int64_t i = 0; i < n; i++) {
    kd_maybe_grow(kd);
    const char* key = data + i * width;
    int64_t h = (int64_t)hash_bytes(key, width);
    if (h == EMPTY) h = 0;
    uint64_t slot = (uint64_t)h & kd->mask;
    for (;;) {
      if (kd->hashes[slot] == EMPTY) {
        kd->hashes[slot] = h;
        int32_t id = (int32_t)kd->size++;
        kd->ids[slot] = id;
        kd->str_off.push_back((int64_t)kd->arena.size());
        kd->str_len.push_back((int32_t)width);
        kd->arena.insert(kd->arena.end(), key, key + width);
        out_ids[i] = id;
        out_new[i] = 1;
        break;
      }
      int32_t id = kd->ids[slot];
      if (kd->hashes[slot] == h && kd->str_len[id] == (int32_t)width &&
          memcmp(kd->arena.data() + kd->str_off[id], key, width) == 0) {
        out_ids[i] = id;
        out_new[i] = 0;
        break;
      }
      slot = (slot + 1) & kd->mask;
    }
  }
  return kd->size;
}

// ===========================================================================
// 2. CSV codec: "key,value,timestamp\n" lines -> columns
// ===========================================================================

// Parses up to max_rows lines; returns rows parsed. key column is written as
// fixed-width bytes (width = key_width, truncated/padded).
int64_t codec_parse_csv(const char* data, int64_t len, int64_t max_rows,
                        char* out_keys, int64_t key_width, double* out_values,
                        int64_t* out_timestamps) {
  int64_t row = 0;
  int64_t pos = 0;
  while (pos < len && row < max_rows) {
    // field 1: key
    int64_t start = pos;
    while (pos < len && data[pos] != ',' && data[pos] != '\n') pos++;
    if (pos >= len || data[pos] != ',') {  // malformed: skip line
      while (pos < len && data[pos] != '\n') pos++;
      pos++;
      continue;
    }
    int64_t klen = pos - start;
    if (klen > key_width) klen = key_width;
    memcpy(out_keys + row * key_width, data + start, klen);
    if (klen < key_width) memset(out_keys + row * key_width + klen, 0, key_width - klen);
    pos++;  // skip comma

    // field 2: value (double)
    char* endp = nullptr;
    out_values[row] = strtod(data + pos, &endp);
    pos = endp - data;
    if (pos < len && data[pos] == ',') {
      pos++;
      out_timestamps[row] = strtoll(data + pos, &endp, 10);
      pos = endp - data;
    } else {
      out_timestamps[row] = 0;
    }
    while (pos < len && data[pos] != '\n') pos++;
    pos++;  // skip newline
    row++;
  }
  return row;
}

// ===========================================================================
// 3. SegmentRing: bounded SPSC queue of fixed-size segments
// ===========================================================================

struct SegmentRing {
  char* memory;
  int64_t segment_size;
  int64_t num_segments;
  std::vector<int64_t> lengths;  // payload length per segment
  volatile int64_t head = 0;     // consumer cursor
  volatile int64_t tail = 0;     // producer cursor
};

SegmentRing* ring_new(int64_t segment_size, int64_t num_segments) {
  SegmentRing* r = new SegmentRing();
  r->memory = (char*)malloc(segment_size * num_segments);
  r->segment_size = segment_size;
  r->num_segments = num_segments;
  r->lengths.assign(num_segments, 0);
  return r;
}

void ring_free(SegmentRing* r) {
  free(r->memory);
  delete r;
}

// returns 1 on success, 0 when full (backpressure: producer must wait)
int ring_offer(SegmentRing* r, const char* data, int64_t len) {
  if (len > r->segment_size) return 0;
  if (r->tail - r->head >= r->num_segments) return 0;  // full
  int64_t slot = r->tail % r->num_segments;
  memcpy(r->memory + slot * r->segment_size, data, len);
  r->lengths[slot] = len;
  __sync_synchronize();
  r->tail++;
  return 1;
}

// returns payload length (>0), or -1 when empty
int64_t ring_poll(SegmentRing* r, char* out, int64_t out_cap) {
  if (r->head >= r->tail) return -1;
  int64_t slot = r->head % r->num_segments;
  int64_t len = r->lengths[slot];
  if (len > out_cap) return -2;
  memcpy(out, r->memory + slot * r->segment_size, len);
  __sync_synchronize();
  r->head++;
  return len;
}

int64_t ring_available(SegmentRing* r) { return r->tail - r->head; }
int64_t ring_free_segments(SegmentRing* r) { return r->num_segments - (r->tail - r->head); }

}  // extern "C"

// ===========================================================================
// 4. Record lanes: a dispatch's record steps into its staging arrays
// ===========================================================================

namespace {

struct LaneJob {
  const int64_t* rows;        // staging row of each step
  const int64_t* live;        // records of each step (lanes [0, live) live)
  const uint64_t* src;        // address of each step's first record row
  const int64_t* stride;      // bytes from one record row to the next
  const int32_t* srel_value;  // each step's srel where it has no array
  const uint64_t* srel_src;   // each step's int32 srel array, or 0
  int64_t lanes;              // B: lanes of a staging row
  int64_t ncols;
  const int64_t* col_offset;  // byte offset of each staged field in a row
  int32_t* srel_dst;          // [T, B]
  const uint64_t* field_dst;  // [ncols] addresses of [T, B] arrays
};

// rows are cut in blocks that stay in L1 while each field is gathered
// from them, so a record row comes from memory once whatever ncols is
const int64_t kBlockRows = 1024;

template <typename V>
void write_steps(const LaneJob& job, int64_t lo, int64_t hi) {
  for (int64_t i = lo; i < hi; i++) {
    const int64_t n = job.live[i];
    const int64_t base = job.rows[i] * job.lanes;
    int32_t* srel = job.srel_dst + base;
    if (job.srel_src[i] != 0) {
      memcpy(srel, (const void*)job.srel_src[i], n * sizeof(int32_t));
    } else {
      const int32_t v = job.srel_value[i];
      for (int64_t r = 0; r < n; r++) srel[r] = v;
    }
    for (int64_t r = n; r < job.lanes; r++) srel[r] = -1;  // the dead tail
    const char* rec = (const char*)job.src[i];
    const int64_t stride = job.stride[i];
    for (int64_t b = 0; b < n; b += kBlockRows) {
      const int64_t e = b + kBlockRows < n ? b + kBlockRows : n;
      for (int64_t c = 0; c < job.ncols; c++) {
        V* dst = (V*)job.field_dst[c] + base;
        const char* from = rec + job.col_offset[c];
        for (int64_t r = b; r < e; r++) {
          V v;
          memcpy(&v, from + r * stride, sizeof(V));
          dst[r] = v;
        }
      }
    }
  }
}

struct LaneRange {
  const LaneJob* job;
  int64_t itemsize, lo, hi;
};

void* write_range(void* arg) {
  const LaneRange& r = *(const LaneRange*)arg;
  switch (r.itemsize) {
    case 1: write_steps<uint8_t>(*r.job, r.lo, r.hi); break;
    case 2: write_steps<uint16_t>(*r.job, r.lo, r.hi); break;
    case 4: write_steps<uint32_t>(*r.job, r.lo, r.hi); break;
    case 8: write_steps<uint64_t>(*r.job, r.lo, r.hi); break;
  }
  return nullptr;
}

}  // namespace

extern "C" {

// Writes `steps` record steps into a staging set: for step i, row rows[i]
// of srel gets the step's srel (its array, else its value) in lanes
// [0, live[i]) and -1 in [live[i], lanes); row rows[i] of each staged field
// c gets field c of the step's records in lanes [0, live[i]) (a record row
// is `stride[i]` bytes, field c at byte `col_offset[c]`, all of `itemsize`
// bytes, 1, 2, 4 or 8), the lanes after them left as they were. Steps are
// split in contiguous ranges over `writers` threads, the calling thread one
// of them; a thread that cannot be started leaves its range to the caller.
void stage_record_lanes(int64_t steps, const int64_t* rows,
                           const int64_t* live, const uint64_t* src,
                           const int64_t* stride, const int32_t* srel_value,
                           const uint64_t* srel_src, int64_t lanes,
                           int64_t itemsize, int64_t ncols,
                           const int64_t* col_offset, int32_t* srel_dst,
                           const uint64_t* field_dst, int64_t writers) {
  const LaneJob job{rows, live, src, stride, srel_value, srel_src,
                    lanes, ncols, col_offset, srel_dst, field_dst};
  if (writers > steps) writers = steps;
  if (writers < 1) writers = 1;
  std::vector<LaneRange> ranges(writers);
  std::vector<pthread_t> threads(writers);
  std::vector<char> started(writers, 0);
  for (int64_t w = 0; w < writers; w++)
    ranges[w] = {&job, itemsize, steps * w / writers,
                 steps * (w + 1) / writers};
  for (int64_t w = 1; w < writers; w++)
    started[w] = pthread_create(&threads[w], nullptr, write_range,
                                &ranges[w]) == 0;
  write_range(&ranges[0]);
  for (int64_t w = 1; w < writers; w++) {
    if (started[w]) {
      pthread_join(threads[w], nullptr);
    } else {
      write_range(&ranges[w]);
    }
  }
}

}  // extern "C"
