// Native host ops for the tez_tpu data plane.
//
// The reference's byte-crunching data path is JVM code (SURVEY.md: the
// performance-critical path is plain Java over byte[]); here the device
// kernels do the heavy lifting and the host side only permutes/concatenates
// ragged byte arrays when materializing runs.  That gather is memory-bound
// and single-threaded in numpy (fancy indexing builds an index array of one
// int64 per BYTE); this C++ version does per-row memcpy across threads and
// skips the index materialization entirely.
//
// Build: make -C native   (g++ -O3 -shared; loaded via ctypes, with a numpy
// fallback when the .so is missing).

#include <atomic>
#include <cstdint>
#include <cstring>
#include <algorithm>
#include <thread>
#include <vector>

extern "C" {

// Permute rows of a ragged u8 array.
//   data/offsets     : source (n_src rows; offsets has n_src+1 entries)
//   perm             : n_out row indices into the source
//   out_offsets      : n_out+1 entries, PRECOMPUTED by the caller
//   out_data         : out_offsets[n_out] bytes
void gather_ragged_u8(const uint8_t* data, const int64_t* offsets,
                      const int64_t* perm, int64_t n_out,
                      const int64_t* out_offsets, uint8_t* out_data,
                      int32_t n_threads) {
    if (n_out <= 0) return;
    int threads = std::max(1, (int)n_threads);
    int64_t total = out_offsets[n_out];
    // Partition output rows so each thread copies ~equal BYTES, not rows
    // (row sizes are ragged; equal-row chunks would skew badly).
    std::vector<std::thread> pool;
    pool.reserve(threads);
    int64_t bytes_per_thread = (total + threads - 1) / threads;
    int64_t row = 0;
    for (int t = 0; t < threads && row < n_out; t++) {
        int64_t start_row = row;
        int64_t target = std::min(total, (int64_t)(t + 1) * bytes_per_thread);
        // advance to the first row whose start offset reaches the target
        while (row < n_out && out_offsets[row] < target) row++;
        int64_t end_row = row;
        pool.emplace_back([=]() {
            for (int64_t i = start_row; i < end_row; i++) {
                int64_t src = perm[i];
                int64_t len = offsets[src + 1] - offsets[src];
                if (len > 0) {
                    std::memcpy(out_data + out_offsets[i],
                                data + offsets[src], (size_t)len);
                }
            }
        });
    }
    for (auto& th : pool) th.join();
}

// Concatenate ragged u8 arrays: caller passes flattened descriptor arrays.
void concat_ragged_u8(const uint8_t** datas, const int64_t* sizes,
                      int64_t n_parts, uint8_t* out_data,
                      int32_t n_threads) {
    std::vector<int64_t> starts(n_parts + 1, 0);
    for (int64_t i = 0; i < n_parts; i++) starts[i + 1] = starts[i] + sizes[i];
    int threads = std::max(1, (int)n_threads);
    std::vector<std::thread> pool;
    int64_t per = (n_parts + threads - 1) / threads;
    for (int t = 0; t < threads; t++) {
        int64_t lo = t * per, hi = std::min<int64_t>(n_parts, lo + per);
        if (lo >= hi) break;
        pool.emplace_back([=, &starts]() {
            for (int64_t i = lo; i < hi; i++) {
                if (sizes[i] > 0)
                    std::memcpy(out_data + starts[i], datas[i],
                                (size_t)sizes[i]);
            }
        });
    }
    for (auto& th : pool) th.join();
}


// Adjacent-row equality over a ragged u8 array: for each candidate index
// cand[j] (caller guarantees rows cand[j] and cand[j]+1 have equal byte
// length), out[j] = 1 iff the two rows' bytes match.  Per-pair memcmp
// across threads — the numpy formulation materializes an int64 index per
// BYTE (8x expansion) on the grouping/combine hot path.
void adjacent_equal_u8(const uint8_t* data, const int64_t* offsets,
                       const int64_t* cand, int64_t n_cand,
                       uint8_t* out, int32_t n_threads) {
    if (n_cand <= 0) return;
    int threads = std::max(1, (int)n_threads);
    std::vector<std::thread> pool;
    int64_t per = (n_cand + threads - 1) / threads;
    for (int t = 0; t < threads; t++) {
        int64_t lo = t * per, hi = std::min<int64_t>(n_cand, lo + per);
        if (lo >= hi) break;
        pool.emplace_back([=]() {
            for (int64_t j = lo; j < hi; j++) {
                int64_t i = cand[j];
                int64_t len = offsets[i + 1] - offsets[i];
                out[j] = (len == 0) ||
                    std::memcmp(data + offsets[i], data + offsets[i + 1],
                                (size_t)len) == 0;
            }
        });
    }
    for (auto& th : pool) th.join();
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Key partitioning (host engine).  The span sort itself lives in
// spansort.cpp (v2: register-packed 12-byte prefixes + duplication-aware
// dedup-rank fast path).
// ---------------------------------------------------------------------------

extern "C" {

// 32-bit FNV-1a over each full key, mod num_partitions — must stay
// byte-identical to the device kernel and numpy host partitioner.
void tz_fnv32_partition(const uint8_t* key_bytes, const int64_t* key_offsets,
                        int64_t n, int32_t num_partitions, int32_t* parts,
                        int32_t n_threads) {
    if (n <= 0) return;
    int threads = std::max(1, (int)n_threads);
    std::vector<std::thread> pool;
    int64_t per = (n + threads - 1) / threads;
    for (int t = 0; t < threads; t++) {
        int64_t lo = t * per, hi = std::min<int64_t>(n, lo + per);
        if (lo >= hi) break;
        pool.emplace_back([=]() {
            for (int64_t i = lo; i < hi; i++) {
                uint32_t h = 2166136261u;
                for (int64_t j = key_offsets[i]; j < key_offsets[i + 1]; j++) {
                    h ^= key_bytes[j];
                    h *= 16777619u;
                }
                parts[i] = (int32_t)(h % (uint32_t)num_partitions);
            }
        });
    }
    for (auto& th : pool) th.join();
}

// Stable grouping of rows by partition: one counting pass, one placing pass.
//   parts      : n partition ids, each in [0, num_partitions)
//   perm       : n entries out: the rows of partition 0 in arrival order,
//                then partition 1's, ...
//   row_index  : num_partitions + 1 entries out: partition p's rows are
//                perm[row_index[p] : row_index[p + 1]]
void tz_group_by_partition(const int32_t* parts, int64_t n,
                           int32_t num_partitions, int64_t* perm,
                           int64_t* row_index) {
    std::fill(row_index, row_index + num_partitions + 1, (int64_t)0);
    for (int64_t i = 0; i < n; i++) row_index[parts[i] + 1]++;
    for (int32_t p = 0; p < num_partitions; p++)
        row_index[p + 1] += row_index[p];
    std::vector<int64_t> at(row_index, row_index + num_partitions);
    for (int64_t i = 0; i < n; i++) perm[at[parts[i]]++] = i;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Hash aggregation (map-side combine).
//
// The reference runs its combiner AFTER the sort, over each spill
// (PipelinedSorter semantics); on TPU the economics invert — collapsing
// duplicate keys BEFORE the device sort shrinks the expensive step
// (pad/lanes/sort/gather) by the duplication factor.  These helpers give
// the host a C-speed open-addressing hash table for that pre-combine and
// for fused tokenize+count (the WordCount family's entire map task).
// ---------------------------------------------------------------------------

namespace {

inline uint64_t fnv1a(const uint8_t* p, int64_t len) {
    uint64_t h = 1469598103934665603ull;
    for (int64_t i = 0; i < len; i++) {
        h ^= p[i];
        h *= 1099511628211ull;
    }
    return h;
}

// Open-addressing table mapping byte-string keys -> int64 accumulator.
// Keys are appended to an arena on first occurrence; emit order is
// first-occurrence order (deterministic for a given input).
struct HashAgg {
    std::vector<int64_t> table;      // entry index + 1; 0 = empty
    struct Entry { uint64_t hash; int64_t off; int32_t len; int64_t acc; };
    std::vector<Entry> entries;
    std::vector<uint8_t> arena;
    uint64_t mask;

    HashAgg() : table(1 << 12, 0), mask((1 << 12) - 1) {}

    void grow() {
        size_t ns = table.size() * 2;
        std::vector<int64_t>(ns, 0).swap(table);
        mask = ns - 1;
        for (size_t e = 0; e < entries.size(); e++) {
            uint64_t slot = entries[e].hash & mask;
            while (table[slot]) slot = (slot + 1) & mask;
            table[slot] = (int64_t)e + 1;
        }
    }

    void add(const uint8_t* key, int64_t len, int64_t value) {
        uint64_t h = fnv1a(key, len);
        uint64_t slot = h & mask;
        while (true) {
            int64_t idx = table[slot];
            if (idx == 0) break;
            const Entry& e = entries[idx - 1];
            if (e.hash == h && e.len == len &&
                std::memcmp(arena.data() + e.off, key, (size_t)len) == 0) {
                entries[idx - 1].acc += value;
                return;
            }
            slot = (slot + 1) & mask;
        }
        int64_t off = (int64_t)arena.size();
        arena.insert(arena.end(), key, key + len);
        entries.push_back({h, off, (int32_t)len, value});
        table[slot] = (int64_t)entries.size();
        if (entries.size() * 10 > table.size() * 7) grow();
    }
};

}  // namespace

extern "C" {

// --- fused tokenize + count (stateful across feeds) ----------------------
// Contract: each feed() is whitespace-complete (the text reader yields
// line-aligned chunks), so tokens never span feed boundaries.

// bytes.split() whitespace set: space \t \n \v \f \r
static inline bool tz_is_ws(uint8_t c) {
    return c == 32 || (c >= 9 && c <= 13);
}

void* tz_wc_create() { return new HashAgg(); }

void tz_wc_feed(void* handle, const uint8_t* data, int64_t n) {
    HashAgg* agg = (HashAgg*)handle;
    int64_t i = 0;
    while (i < n) {
        while (i < n && tz_is_ws(data[i])) i++;
        int64_t start = i;
        while (i < n && !tz_is_ws(data[i])) i++;
        if (i > start) agg->add(data + start, i - start, 1);
    }
}

void tz_wc_stats(void* handle, int64_t* n_unique, int64_t* total_key_bytes) {
    HashAgg* agg = (HashAgg*)handle;
    *n_unique = (int64_t)agg->entries.size();
    *total_key_bytes = (int64_t)agg->arena.size();
}

// key_offsets: n_unique+1 entries; key_bytes: arena size; counts: n_unique
void tz_wc_emit(void* handle, uint8_t* key_bytes, int64_t* key_offsets,
                int64_t* counts) {
    HashAgg* agg = (HashAgg*)handle;
    std::memcpy(key_bytes, agg->arena.data(), agg->arena.size());
    int64_t off = 0;
    for (size_t e = 0; e < agg->entries.size(); e++) {
        key_offsets[e] = off;
        off += agg->entries[e].len;
        counts[e] = agg->entries[e].acc;
    }
    key_offsets[agg->entries.size()] = off;
}

void tz_wc_destroy(void* handle) { delete (HashAgg*)handle; }

// --- raw whitespace split (no combine): one pass, compacted words --------
// out_bytes: caller-allocated n bytes (worst case: no whitespace);
// out_offsets: caller-allocated (n+1)/2 + 2 entries.  Returns word count.
int64_t tz_split_ws(const uint8_t* data, int64_t n, uint8_t* out_bytes,
                    int64_t* out_offsets) {
    int64_t words = 0, out = 0, i = 0;
    out_offsets[0] = 0;
    while (i < n) {
        while (i < n && tz_is_ws(data[i])) i++;
        int64_t start = i;
        while (i < n && !tz_is_ws(data[i])) i++;
        if (i > start) {
            std::memcpy(out_bytes + out, data + start, (size_t)(i - start));
            out += i - start;
            out_offsets[++words] = out;
        }
    }
    return words;
}

// --- generic pre-sort combine: sum int64 values of equal keys -------------
// first_idx[u] = record index of key u's first occurrence (caller gathers
// the key bytes); sums[u] = total value.  Both sized n by the caller.
// Returns the number of unique keys.
int64_t hash_sum_i64(const uint8_t* key_bytes, const int64_t* key_offsets,
                     int64_t n, const int64_t* values,
                     int64_t* first_idx, int64_t* sums) {
    HashAgg agg;
    // remember first-occurrence record index per unique key: the arena
    // offset uniquely identifies the entry, so track indices alongside
    std::vector<int64_t> firsts;
    firsts.reserve(1024);
    for (int64_t i = 0; i < n; i++) {
        size_t before = agg.entries.size();
        agg.add(key_bytes + key_offsets[i],
                key_offsets[i + 1] - key_offsets[i], values[i]);
        if (agg.entries.size() > before) firsts.push_back(i);
    }
    for (size_t u = 0; u < agg.entries.size(); u++) {
        first_idx[u] = firsts[u];
        sums[u] = agg.entries[u].acc;
    }
    return (int64_t)agg.entries.size();
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Mesh exchange row passes (parallel/coordinator.py).
//
// An exchange moves fixed-width rows — key lanes (big-endian u32 words of
// the zero-padded key), the key's length, value words (word 0 the value's
// length) — between ragged batches on the host and slotted buffers on the
// device.  Three passes touch every row: a producer ENCODES its batch,
// the coordinator PLACES each round's rows into the sender blocks the
// device program reads, a reader DECODES a device's output shard back to a
// ragged batch.  Each is one walk over the rows here, across threads, with
// no index array; the caller's arithmetic is on histograms only.
// ---------------------------------------------------------------------------

namespace {

// fn(chunk, first_row, end_row) over `chunks` even row ranges of [0, n), one
// thread a non-empty chunk; chunk c covers [c * per, (c + 1) * per).
template <typename F>
void over_row_chunks(int64_t n, int chunks, F fn) {
    chunks = std::max(1, chunks);
    int64_t per = (n + chunks - 1) / chunks;
    std::vector<std::thread> pool;
    for (int c = 0; c < chunks; c++) {
        int64_t lo = c * per, hi = std::min<int64_t>(n, lo + per);
        if (lo >= hi) break;
        pool.emplace_back([=]() { fn(c, lo, hi); });
    }
    for (auto& th : pool) th.join();
}

// fn(t) for t in [0, n): one thread each
template <typename F>
void thread_each(int32_t n, F fn) {
    std::vector<std::thread> pool;
    for (int32_t t = 0; t < n; t++) pool.emplace_back([=]() { fn(t); });
    for (auto& th : pool) th.join();
}

// fn(typed pointer) for destinations of 1, 2 or 4 bytes
template <typename F>
void with_dests(const void* dests, int32_t dest_size, F fn) {
    if (dest_size == 1) fn((const uint8_t*)dests);
    else if (dest_size == 2) fn((const uint16_t*)dests);
    else fn((const uint32_t*)dests);
}

inline uint32_t bswap32(uint32_t v) { return __builtin_bswap32(v); }

// `len` bytes -> `words` big-endian u32 words, zero-padded (len <= 4 * words)
inline void pack_be_words(const uint8_t* src, int64_t len, uint32_t* dst,
                          int32_t words) {
    int64_t full = len >> 2;
    for (int64_t j = 0; j < full; j++) {
        uint32_t w;
        std::memcpy(&w, src + 4 * j, 4);
        dst[j] = bswap32(w);
    }
    int64_t j = full;
    if (len & 3) {
        uint32_t w = 0;
        std::memcpy(&w, src + 4 * full, (size_t)(len & 3));
        dst[j++] = bswap32(w);
    }
    for (; j < words; j++) dst[j] = 0;
}

// the first `len` bytes of big-endian u32 words
inline void unpack_be_words(const uint32_t* src, int64_t len, uint8_t* dst) {
    int64_t full = len >> 2;
    for (int64_t j = 0; j < full; j++) {
        uint32_t w = bswap32(src[j]);
        std::memcpy(dst + 4 * j, &w, 4);
    }
    if (len & 3) {
        uint32_t w = bswap32(src[full]);
        std::memcpy(dst + 4 * full, &w, (size_t)(len & 3));
    }
}

// the sizes a decoded row takes: a length beyond its slot cannot come from
// an encoded row, and is held to the slot so no read leaves the shard
struct RowSizes { int64_t klen, vlen; };
inline RowSizes decoded_sizes(const uint32_t* klens, const uint32_t* vwords,
                              int64_t vstride, int64_t i, int64_t key_cap,
                              int64_t val_cap) {
    return {std::min<int64_t>(klens[i], key_cap),
            std::min<int64_t>(vwords[i * vstride], val_cap)};
}

struct PlaceArgs {
    const uint32_t* const* lanes;     // a chunk's first row, per chunk
    const uint32_t* const* klens;
    const uint32_t* const* vwords;
    const int32_t* chunk_lanes;       // the chunk's span's widths, in words
    const int32_t* chunk_vw;
    const int64_t* bounds;            // n_chunks + 1 rows into dests
    const int64_t* rank_base;         // [n_chunks][D]
    const int64_t* fill_base;         // [n_chunks][D]
    const int64_t* chunk_d;           // [D]
    const int64_t* loads;             // [D]
    int32_t n_chunks, D, num_lanes, value_words;
    int64_t lo, per_round, N;
    uint32_t *r_lanes, *r_klens, *r_vwords, *r_dests;
    uint8_t* r_valid;
};

template <typename DT>
void place_chunk(const PlaceArgs& a, const DT* dests, int32_t t) {
    const int32_t D = a.D, L = a.num_lanes, VW = a.value_words;
    const int32_t cl = a.chunk_lanes[t], cv = a.chunk_vw[t];
    std::vector<int64_t> rank(a.rank_base + (int64_t)t * D,
                              a.rank_base + (int64_t)(t + 1) * D);
    std::vector<int64_t> fill(a.fill_base + (int64_t)t * D,
                              a.fill_base + (int64_t)(t + 1) * D);
    const uint32_t* lanes = a.lanes[t];
    const uint32_t* klens = a.klens[t];
    const uint32_t* vwords = a.vwords[t];
    const int64_t first = a.bounds[t];
    for (int64_t i = first; i < a.bounds[t + 1]; i++) {
        const int64_t d = dests[i];
        const int64_t lrank = rank[d]++ - a.lo;
        if (lrank < 0 || lrank >= a.per_round) continue;
        const int64_t sender = lrank / a.chunk_d[d];
        const int64_t slot = sender * a.N + fill[sender]++;
        const int64_t row = i - first;
        uint32_t* ol = a.r_lanes + slot * L;
        std::memcpy(ol, lanes + row * cl, (size_t)cl * 4);
        for (int32_t j = cl; j < L; j++) ol[j] = 0;
        uint32_t* ov = a.r_vwords + slot * VW;
        std::memcpy(ov, vwords + row * cv, (size_t)cv * 4);
        for (int32_t j = cv; j < VW; j++) ov[j] = 0;
        a.r_klens[slot] = klens[row];
        a.r_valid[slot] = 1;
        a.r_dests[slot] = (uint32_t)d;
    }
    // the unfilled tail of every n_chunks-th sender block reads as zeros
    for (int64_t s = t; s < D; s += a.n_chunks) {
        const int64_t at = s * a.N + a.loads[s], n = a.N - a.loads[s];
        if (n <= 0) continue;
        std::memset(a.r_lanes + at * L, 0, (size_t)n * L * 4);
        std::memset(a.r_vwords + at * VW, 0, (size_t)n * VW * 4);
        std::memset(a.r_klens + at, 0, (size_t)n * 4);
        std::memset(a.r_valid + at, 0, (size_t)n);
        std::memset(a.r_dests + at, 0, (size_t)n * 4);
    }
}

}  // namespace

extern "C" {

// A producer's ragged batch -> lanes u32[n, num_lanes], klens u32[n],
// vwords u32[n, 1 + value_words] (word 0 the value's length).  A key or
// value longer than its slot keeps its true length and its slot's prefix,
// as keycodec.pad_to_matrix does; the caller sizes the slots to the data.
void tz_exchange_encode(const uint8_t* key_bytes, const int64_t* key_offsets,
                        const uint8_t* val_bytes, const int64_t* val_offsets,
                        int64_t n, int32_t num_lanes, int32_t value_words,
                        uint32_t* lanes, uint32_t* klens, uint32_t* vwords,
                        int32_t n_threads) {
    const int64_t key_cap = 4 * (int64_t)num_lanes;
    const int64_t val_cap = 4 * (int64_t)value_words;
    over_row_chunks(n, n_threads, [=](int, int64_t lo, int64_t hi) {
        for (int64_t i = lo; i < hi; i++) {
            int64_t klen = key_offsets[i + 1] - key_offsets[i];
            int64_t vlen = val_offsets[i + 1] - val_offsets[i];
            klens[i] = (uint32_t)klen;
            pack_be_words(key_bytes + key_offsets[i],
                          std::min(klen, key_cap), lanes + i * num_lanes,
                          num_lanes);
            uint32_t* vw = vwords + i * (1 + (int64_t)value_words);
            vw[0] = (uint32_t)vlen;
            pack_be_words(val_bytes + val_offsets[i],
                          std::min(vlen, val_cap), vw + 1, value_words);
        }
    });
}

// Ragged keys -> lanes u32[n, num_lanes] (big-endian words, zero-padded) and
// lens i32[n]: what keycodec.pad_to_matrix + matrix_to_lanes give at `width`
// bytes (num_lanes = width rounded up to words).  A key longer than `width`
// keeps its true length and its first `width` bytes.
void tz_encode_key_lanes(const uint8_t* key_bytes, const int64_t* key_offsets,
                         int64_t n, int32_t width, int32_t num_lanes,
                         uint32_t* lanes, int32_t* lens, int32_t n_threads) {
    const int64_t key_cap = width;
    over_row_chunks(n, n_threads, [=](int, int64_t lo, int64_t hi) {
        for (int64_t i = lo; i < hi; i++) {
            int64_t klen = key_offsets[i + 1] - key_offsets[i];
            lens[i] = (int32_t)klen;
            pack_be_words(key_bytes + key_offsets[i],
                          std::min(klen, key_cap), lanes + i * num_lanes,
                          num_lanes);
        }
    });
}

// Rows of each destination in each chunk of `dests` (elements of
// dest_size bytes): hist[n_chunks][D], one thread a chunk.
void tz_exchange_dest_hist(const void* dests, int32_t dest_size,
                           const int64_t* bounds, int32_t n_chunks,
                           int32_t D, int64_t* hist) {
    with_dests(dests, dest_size, [=](auto* typed) {
        thread_each(n_chunks, [=](int32_t t) {
            int64_t* h = hist + (int64_t)t * D;
            std::fill(h, h + D, 0);
            for (int64_t i = bounds[t]; i < bounds[t + 1]; i++) h[typed[i]]++;
        });
    });
}

// One round's placement.  Chunk t holds rows [bounds[t], bounds[t + 1]) of
// the edge in arrival order, all of one producer's span.  A row of
// destination d has rank rank_base[t][d] + (rows of d before it in the
// chunk); the round carries ranks [lo, lo + per_round) of every
// destination; the row's sender is (rank - lo) / chunk_d[d] and its slot
// sender * N + fill_base[t][sender] + (the chunk's rows of that sender
// before it).  Every slot of the five outputs is written: rows by the
// chunk that holds them, each sender block's tail past loads[sender] with
// zeros.  One thread a chunk; no two chunks share a slot.
void tz_exchange_place(int32_t n_chunks, const void* const* lanes,
                       const void* const* klens, const void* const* vwords,
                       const int32_t* chunk_lanes, const int32_t* chunk_vw,
                       const int64_t* bounds, const void* dests,
                       int32_t dest_size, const int64_t* rank_base,
                       const int64_t* fill_base,
                       int32_t D, int64_t lo, int64_t per_round,
                       const int64_t* chunk_d, const int64_t* loads,
                       int64_t N, int32_t num_lanes, int32_t value_words,
                       uint32_t* r_lanes, uint32_t* r_klens,
                       uint32_t* r_vwords, uint8_t* r_valid,
                       uint32_t* r_dests) {
    PlaceArgs a{(const uint32_t* const*)lanes, (const uint32_t* const*)klens,
                (const uint32_t* const*)vwords, chunk_lanes, chunk_vw, bounds,
                rank_base, fill_base, chunk_d, loads,
                n_chunks, D, num_lanes, value_words, lo, per_round, N,
                r_lanes, r_klens, r_vwords, r_dests, r_valid};
    with_dests(dests, dest_size, [&a](auto* typed) {
        thread_each(a.n_chunks,
                    [&a, typed](int32_t t) { place_chunk(a, typed, t); });
    });
}

// A shard's kept rows, counted: sizes[c] = {rows, key bytes, value bytes}
// of row chunk c (over_row_chunks' even chunks; `chunks` entries, zeros
// where a chunk is empty).  `vstride` is a vwords row in words.
void tz_exchange_decode_sizes(const uint32_t* klens, const uint32_t* vwords,
                              int64_t vstride, const uint8_t* keep,
                              int64_t n, int32_t num_lanes,
                              int32_t value_words, int32_t chunks,
                              int64_t* sizes) {
    std::fill(sizes, sizes + 3 * (int64_t)std::max(1, chunks), 0);
    const int64_t key_cap = 4 * (int64_t)num_lanes;
    const int64_t val_cap = 4 * (int64_t)value_words;
    over_row_chunks(n, chunks, [=](int c, int64_t lo, int64_t hi) {
        int64_t rows = 0, kb = 0, vb = 0;
        for (int64_t i = lo; i < hi; i++) {
            if (!keep[i]) continue;
            RowSizes s = decoded_sizes(klens, vwords, vstride, i, key_cap,
                                       val_cap);
            rows++; kb += s.klen; vb += s.vlen;
        }
        sizes[3 * c] = rows; sizes[3 * c + 1] = kb; sizes[3 * c + 2] = vb;
    });
}

// The kept rows, written: starts[c] = {row, key byte, value byte} at which
// chunk c's output begins (the exclusive prefix sums of the sizes above).
// key_offsets / val_offsets have one entry more than the kept rows; entry
// 0 is the caller's.
void tz_exchange_decode_rows(const uint32_t* lanes, const uint32_t* klens,
                             const uint32_t* vwords, int64_t vstride,
                             const uint8_t* keep, int64_t n,
                             int32_t num_lanes, int32_t value_words,
                             int32_t chunks, const int64_t* starts,
                             uint8_t* key_bytes, int64_t* key_offsets,
                             uint8_t* val_bytes, int64_t* val_offsets) {
    const int64_t key_cap = 4 * (int64_t)num_lanes;
    const int64_t val_cap = 4 * (int64_t)value_words;
    over_row_chunks(n, chunks, [=](int c, int64_t lo, int64_t hi) {
        int64_t row = starts[3 * c], kb = starts[3 * c + 1],
                vb = starts[3 * c + 2];
        for (int64_t i = lo; i < hi; i++) {
            if (!keep[i]) continue;
            RowSizes s = decoded_sizes(klens, vwords, vstride, i, key_cap,
                                       val_cap);
            unpack_be_words(lanes + i * num_lanes, s.klen, key_bytes + kb);
            unpack_be_words(vwords + i * vstride + 1, s.vlen, val_bytes + vb);
            kb += s.klen; vb += s.vlen; row++;
            key_offsets[row] = kb;
            val_offsets[row] = vb;
        }
    });
}

}  // extern "C"
