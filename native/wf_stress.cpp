// Seeded sanitizer stress corpus for the native runtime (ISSUE 20).
//
// TSan cannot be dlopen'd into an uninstrumented CPython, so the
// sanitizer lane links wf_native.cpp INTO this standalone driver
// (native/Makefile `tsan` / `asan` targets) instead of loading
// libwfnative.so.  scripts/wf_sanitize.py builds and runs it; any
// sanitizer report or stress assertion fails the lane.
//
// Three phases per seeded case:
//
//   1. queue MPMC   — producers mixing push / try_push / push_timed
//                     against consumers mixing pop / try_pop, closed
//                     mid-stream; conservation of count and payload sum
//                     is asserted after the drain.
//   2. close race   — producers parked on a FULL queue while close()
//                     fires, then wf_queue_free's idle-spin teardown
//                     (the documented destructor race, under TSan).
//   3. state ABI    — per-thread cores exercising the PR 17 surface
//                     (wf_core_state_export/import, per-key export /
//                     import / neutralize, and the refusal codes) while
//                     a background thread hammers an unrelated queue —
//                     any accidental shared global between the
//                     subsystems becomes a TSan report.  Each case
//                     starts with the bulk path (bulk_case): archive
//                     columns grown unwritten, a block rolled back, the
//                     windows' ts read from the input chunk.  Then the
//                     stream fold (sfold_case): wf_sfold over arrays the
//                     caller owns, as VecStreamCore does — the lanes and
//                     the index grown and the index rebuilt between
//                     calls, slots retired and their keys seen again —
//                     against a std::map.
//
//   ./wf_stress_tsan --seed 1 --n 4

#include <algorithm>
#include <array>
#include <atomic>
#include <cstddef>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <thread>
#include <utility>
#include <vector>

using i64 = int64_t;
using u64 = uint64_t;
using u8 = uint8_t;

extern "C" {
void *wf_queue_new(i64 capacity);
void wf_queue_free(void *h);
int wf_queue_push(void *h, i64 src, i64 slot);
int wf_queue_pop(void *h, i64 *src, i64 *slot);
int wf_queue_try_push(void *h, i64 src, i64 slot);
int wf_queue_push_timed(void *h, i64 src, i64 slot, i64 timeout_ms);
int wf_queue_try_pop(void *h, i64 *src, i64 *slot);
void wf_queue_close(void *h);

void *wf_core_new(i64 win, i64 slide, int win_type, int role,
                  i64 id_outer, i64 n_outer, i64 slide_outer,
                  i64 id_inner, i64 n_inner, i64 slide_inner,
                  i64 map_idx0, i64 map_idx1, i64 result_ts_slide,
                  i64 batch_len, i64 flush_rows, int max_wire);
void wf_core_free(void *h);
i64 wf_core_process(void *h, const void *base, i64 n, i64 itemsize,
                    i64 o_key, i64 o_id, i64 o_ts, i64 o_marker,
                    i64 o_val);
i64 wf_core_force_flush(void *h);
i64 wf_core_fast_rows(void *h);
int wf_launch_peek(void *h, i64 *K, i64 *R, i64 *B, int *wire, int *rebase,
                   i64 *KP, i64 *cap);
void wf_launch_take(void *h, void *blk, i64 *offs, int32_t *wrows,
                    int32_t *wstarts, int32_t *wlens, i64 *hkey, i64 *hid,
                    i64 *hts, i64 *hlen);
i64 wf_core_state_size(void *h);
i64 wf_core_state_export(void *h, void *buf, i64 cap);
i64 wf_core_state_import(void *h, const void *buf, i64 nbytes);
i64 wf_core_key_count(void *h);
i64 wf_core_key_list(void *h, i64 *out, i64 cap);
i64 wf_core_key_state_size(void *h, i64 key);
i64 wf_core_key_export(void *h, i64 key, void *buf, i64 cap);
i64 wf_core_key_import(void *h, const void *buf, i64 nbytes);
i64 wf_core_key_neutralize(void *h, i64 key);
void wf_sfold_index(i64 *tab, i64 cap, const i64 *keys, i64 n);
i64 wf_sfold(const char *base, i64 stride, i64 lo, i64 n, i64 off_key,
             i64 off_ts, i64 off_marker, i64 bound, const i64 *geom,
             i64 *tab, i64 cap, i64 *slot_keys, i64 slot_cap,
             i64 *lane_rows, i64 n_parts, const i64 *desc, i64 *const *acc,
             i64 *io);
}

#if defined(__SANITIZE_THREAD__)
// gcc-10's libstdc++ implements condition_variable::wait_for via
// pthread_cond_clockwait (glibc 2.30+), which this toolchain's libtsan
// predates: the missing interceptor makes TSan blind to the unlock /
// relock inside the wait, producing bogus "double lock" and data-race
// reports on every timed wait (NativeQueue::push_timed).  Routing the
// call through the intercepted pthread_cond_timedwait keeps the lock
// modeling intact; the clock conversion below is racy by a scheduling
// quantum, which only stretches a stress timeout, never correctness.
#include <pthread.h>
#include <time.h>
extern "C" int pthread_cond_clockwait(pthread_cond_t *cond,
                                      pthread_mutex_t *mu,
                                      clockid_t clockid,
                                      const struct timespec *abstime) {
    struct timespec rt = *abstime;
    if (clockid != CLOCK_REALTIME) {
        struct timespec now_c, now_rt;
        clock_gettime(clockid, &now_c);
        clock_gettime(CLOCK_REALTIME, &now_rt);
        long long ns =
            (long long)(abstime->tv_sec - now_c.tv_sec) * 1000000000LL +
            (abstime->tv_nsec - now_c.tv_nsec);
        if (ns < 0) ns = 0;
        long long t =
            (long long)now_rt.tv_sec * 1000000000LL + now_rt.tv_nsec + ns;
        rt.tv_sec = (time_t)(t / 1000000000LL);
        rt.tv_nsec = (long)(t % 1000000000LL);
    }
    return pthread_cond_timedwait(cond, mu, &rt);
}
#endif

#define CHECK(cond, ...)                                                   \
    do {                                                                   \
        if (!(cond)) {                                                     \
            std::fprintf(stderr, "wf_stress FAILED %s:%d: %s — ",          \
                         __FILE__, __LINE__, #cond);                       \
            std::fprintf(stderr, __VA_ARGS__);                             \
            std::fprintf(stderr, "\n");                                    \
            std::exit(1);                                                  \
        }                                                                  \
    } while (0)

// splitmix-style seeded generator: deterministic per (seed, stream)
struct Rng {
    u64 s;
    explicit Rng(u64 seed) : s(seed * 0x9e3779b97f4a7c15ULL + 1) {}
    u64 next() {
        u64 z = (s += 0x9e3779b97f4a7c15ULL);
        z = (z ^ (z >> 30)) * 0xbf58476d1ce4e5b9ULL;
        z = (z ^ (z >> 27)) * 0x94d049bb133111ebULL;
        return z ^ (z >> 31);
    }
    i64 range(i64 lo, i64 hi) {  // [lo, hi)
        return lo + (i64)(next() % (u64)(hi - lo));
    }
};

// ------------------------------------------------------ phase 1: queue

static void queue_mpmc_case(u64 seed, int round) {
    Rng cfg(seed ^ (u64)(round * 1315423911ULL));
    const i64 cap = cfg.range(2, 64);
    const int n_prod = (int)cfg.range(2, 5);
    const int n_cons = (int)cfg.range(2, 5);
    const i64 per_prod = cfg.range(200, 1200);
    void *q = wf_queue_new(cap);

    std::atomic<i64> pushed{0}, push_sum{0};
    std::vector<std::thread> prods, cons;
    for (int p = 0; p < n_prod; ++p) {
        prods.emplace_back([&, p] {
            Rng r(seed + 7919 * (u64)(p + 1));
            for (i64 i = 0; i < per_prod; ++i) {
                const i64 slot = r.range(0, 1 << 20);
                int rc;
                switch (r.range(0, 3)) {
                case 0: rc = wf_queue_push(q, p, slot); break;
                case 1:
                    // spin try_push until accepted (1 = would block)
                    do {
                        rc = wf_queue_try_push(q, p, slot);
                    } while (rc == 1);
                    break;
                default:
                    do {
                        rc = wf_queue_push_timed(q, p, slot, 5);
                    } while (rc == 1);
                }
                CHECK(rc == 0 || rc == -1, "push rc=%d", rc);
                if (rc == -1) return;  // closed under us: stop producing
                pushed.fetch_add(1, std::memory_order_relaxed);
                push_sum.fetch_add(slot, std::memory_order_relaxed);
            }
        });
    }
    std::atomic<i64> popped{0}, pop_sum{0};
    for (int cix = 0; cix < n_cons; ++cix) {
        cons.emplace_back([&, cix] {
            Rng r(seed + 104729 * (u64)(cix + 1));
            i64 src, slot;
            for (;;) {
                int rc;
                if (r.range(0, 2) == 0) {
                    do {
                        rc = wf_queue_try_pop(q, &src, &slot);
                    } while (rc == 1);
                } else {
                    rc = wf_queue_pop(q, &src, &slot);
                }
                if (rc == -1) return;  // closed and drained
                CHECK(rc == 0, "pop rc=%d", rc);
                CHECK(src >= 0 && src < n_prod, "src=%lld",
                      (long long)src);
                popped.fetch_add(1, std::memory_order_relaxed);
                pop_sum.fetch_add(slot, std::memory_order_relaxed);
            }
        });
    }
    for (auto &t : prods) t.join();
    wf_queue_close(q);  // wakes the consumers once the buffer drains
    for (auto &t : cons) t.join();
    CHECK(popped.load() == pushed.load(),
          "conservation: pushed=%lld popped=%lld",
          (long long)pushed.load(), (long long)popped.load());
    CHECK(pop_sum.load() == push_sum.load(),
          "payload sum diverged (dup or corruption)");
    wf_queue_free(q);
}

static void queue_close_race_case(u64 seed) {
    // producers parked on a FULL queue when close() lands: every parked
    // push must return -1 (closed), then the idle-spin free() tears the
    // mutex down only after the last waiter left
    Rng cfg(seed);
    const i64 cap = cfg.range(1, 4);
    void *q = wf_queue_new(cap);
    for (i64 i = 0; i < cap; ++i)
        CHECK(wf_queue_push(q, 0, i) == 0, "prefill");
    std::vector<std::thread> prods;
    std::atomic<int> woken{0};
    for (int p = 0; p < 4; ++p) {
        prods.emplace_back([&, p] {
            int rc = wf_queue_push(q, 1, p);  // parks: queue is full
            CHECK(rc == -1, "parked push survived close, rc=%d", rc);
            woken.fetch_add(1);
        });
    }
    std::this_thread::sleep_for(std::chrono::milliseconds(20));
    wf_queue_close(q);
    for (auto &t : prods) t.join();
    CHECK(woken.load() == 4, "woken=%d", woken.load());
    wf_queue_free(q);
}

// -------------------------------------------------- phase 3: state ABI

#pragma pack(push, 1)
struct Row {
    i64 key, id, ts;
    u8 marker;
    i64 value;
};
#pragma pack(pop)
static_assert(sizeof(Row) == 33, "packed row layout");

static void *new_core() {
    // the hand-driven config the in-suite native tests use: win 8,
    // slide 8, CB, SEQ role, identity distribution, huge batch_len so
    // nothing flushes, flush_rows 64, int16 wire
    return wf_core_new(8, 8, 0, 0, 0, 1, 8, 0, 1, 8, 0, 1, 8,
                       (i64)1 << 20, 64, 2);
}

// consume every queued launch (the ship thread's role): export refuses
// while c->queue is non-empty.  `each(B, hkey, hid, hts)` sees every
// launch's result headers
template <class F>
static void drain_launches(void *h, F each) {
    i64 K, R, B, KP, cap;
    int wire, rebase;
    while (wf_launch_peek(h, &K, &R, &B, &wire, &rebase, &KP, &cap) == 1) {
        const i64 nb = B > 0 ? B : 1;
        std::vector<u8> blk((size_t)(K * R) << wire);
        std::vector<i64> offs((size_t)K);
        std::vector<i64> h8((size_t)(4 * nb));
        std::vector<int32_t> w4((size_t)(3 * nb));
        wf_launch_take(h, blk.data(), offs.data(), w4.data(),
                       w4.data() + nb, w4.data() + 2 * nb, h8.data(),
                       h8.data() + nb, h8.data() + 2 * nb,
                       h8.data() + 3 * nb);
        each(B, h8.data(), h8.data() + nb, h8.data() + 2 * nb);
    }
}

static void drain_launches(void *h) {
    drain_launches(h, [](i64, const i64 *, const i64 *, const i64 *) {});
}

static void feed(void *h, i64 n_keys, i64 rows_per_key, i64 id0) {
    // PARTIAL windows only (rows_per_key + id0 < win 8): no window
    // fires, so the per-key archives stay non-empty and exportable;
    // force_flush + drain_launches then settles pend_rows and the
    // launch queue — the two halves of the core_drained export gate
    std::vector<Row> rows;
    for (i64 k = 0; k < n_keys; ++k)
        for (i64 i = 0; i < rows_per_key; ++i)
            rows.push_back(Row{k, id0 + i, id0 + i, 0, 100 * k + i});
    const i64 got = wf_core_process(
        h, rows.data(), (i64)rows.size(), (i64)sizeof(Row),
        offsetof(Row, key), offsetof(Row, id), offsetof(Row, ts),
        offsetof(Row, marker), offsetof(Row, value));
    CHECK(got >= 0, "process refused: %lld", (long long)got);
    wf_core_force_flush(h);
    drain_launches(h);
}

// The bulk path (key-periodic chunks) grows its archive columns unwritten
// and rolls a block back on a pattern break; a window's result ts is read
// from the input chunk, no ts column is archived.  Two chunks, the second
// with two neighbours swapped mid-block: every full window's ts must be
// its last row's (id 8w + 7 of its key).
static void bulk_case(u64 seed, int tid) {
    Rng r(seed + 7919 * (u64)(tid + 1));
    const i64 P = r.range(2, 9), per = 40;
    void *h = wf_core_new(8, 8, 0, 0, 0, 1, 8, 0, 1, 8, 0, 1, 8, 4, 64, 2);
    i64 n_windows = 0;
    for (int chunk = 0; chunk < 2; ++chunk) {
        std::vector<Row> rows;
        for (i64 i = chunk * per; i < (chunk + 1) * per; ++i)
            for (i64 k = 0; k < P; ++k)
                rows.push_back(Row{k, i, i * 7 + k, 0, (i * 31 + k) % 100});
        if (chunk == 1)
            std::swap(rows[(size_t)(P * 9)], rows[(size_t)(P * 9 + 1)]);
        CHECK(wf_core_process(h, rows.data(), (i64)rows.size(),
                              (i64)sizeof(Row), offsetof(Row, key),
                              offsetof(Row, id), offsetof(Row, ts),
                              offsetof(Row, marker),
                              offsetof(Row, value)) >= 0, "bulk process");
        wf_core_force_flush(h);
        drain_launches(h, [&](i64 B, const i64 *hkey, const i64 *hid,
                              const i64 *hts) {
            for (i64 i = 0; i < B; ++i)
                CHECK(hts[i] == (hid[i] * 8 + 7) * 7 + hkey[i],
                      "window ts: key %lld id %lld ts %lld",
                      (long long)hkey[i], (long long)hid[i],
                      (long long)hts[i]);
            n_windows += B;
        });
    }
    // the first chunk whole and the second up to the broken block went
    // the bulk way, the rest through the general loop
    CHECK(wf_core_fast_rows(h) >= P * per
              && wf_core_fast_rows(h) < 2 * P * per,
          "fast_rows=%lld", (long long)wf_core_fast_rows(h));
    // (the last window waits for a row past its end)
    CHECK(n_windows == P * (2 * per / 8 - 1), "bulk windows=%lld",
          (long long)n_windows);
    wf_core_free(h);
}

// The stream fold over caller-owned arrays (core/vecinc.py's part played
// here): every array sized exactly as the call is told and no larger, so a
// write past it is ASan's, and with room for a few new keys only, so most
// stretches stop short and go on after the arrays have grown and the index
// was rebuilt; chunks cut at window boundaries with markers and late rows
// among them; at every boundary the oldest window's lane is taken, slots
// left empty are retired and the index is rebuilt.  Counts, sums and maxima
// per (key, window) against a std::map; the slots a stretch adds come out
// in key order.
static void sfold_case(u64 seed, int tid) {
    Rng r(seed + 104729 * (u64)(tid + 1));
    const i64 S = r.range(3, 9), W = r.range(1, 4), L = S * W;
    const i64 desc[9] = {0, 0, 0, 1, (i64)offsetof(Row, value), 8,
                         3, (i64)offsetof(Row, ts), 8};
    const i64 kMin = -((i64)1 << 62);
    std::vector<i64> tab, slot_keys, lane_rows, sum, mx;
    i64 n_slots = 0, fired = 0, clock = 0, next_key = 0, late_seen = 0,
        stops = 0;
    std::map<std::pair<i64, i64>, std::array<i64, 3>> want;  // key, window
    auto reserve = [&](i64 need) {
        slot_keys.resize((size_t)need);
        lane_rows.resize((size_t)(need * W), 0);
        sum.resize((size_t)(need * W), 0);
        mx.resize((size_t)(need * W), kMin);
        i64 cap = 2;
        while (cap < 2 * need) cap *= 2;
        if ((i64)tab.size() != 2 * cap) {       // grown (or shrunk): rebuilt
            tab.assign((size_t)(2 * cap), 0);
            wf_sfold_index(tab.data(), cap, slot_keys.data(), n_slots);
        }
    };
    for (int chunk = 0; chunk < 40; ++chunk) {
        std::vector<Row> rows;
        const i64 n = r.range(1, 400);
        for (i64 i = 0; i < n; ++i) {
            clock += r.range(0, 3) == 0;
            const bool hot = r.range(0, 3) == 0;
            const i64 key = hot ? 7 : 4 * (next_key - r.range(0, 30)) + 1;
            next_key += r.range(0, 4) == 0;
            const i64 ts = clock - (r.range(0, 5) ? 0 : r.range(0, 2 * L));
            rows.push_back(Row{key, i, ts, (u8)(r.range(0, 9) == 0),
                               r.range(-9, 100)});
        }
        i64 lo = 0;
        while (lo < n) {
            const i64 geom[4] = {L, S, W, fired};
            const i64 bound = fired * S + L, n0 = n_slots;
            i64 io[4] = {n_slots, 0, n0, 1}, cut = lo, late_got = 0;
            while (io[3]) {             // a stretch, in as many calls
                reserve(io[0] + r.range(1, 6));
                i64 *acc[3] = {nullptr, sum.data(), mx.data()};
                cut = wf_sfold(
                    (const char *)rows.data(), (i64)sizeof(Row), cut, n,
                    offsetof(Row, key), offsetof(Row, ts),
                    offsetof(Row, marker), bound, geom, tab.data(),
                    (i64)tab.size() / 2, slot_keys.data(),
                    (i64)slot_keys.size(), lane_rows.data(), 3, desc, acc,
                    io);
                n_slots = io[0];
                late_got += io[1];
                stops += io[3];
            }
            CHECK(cut >= lo && cut <= n, "cut=%lld", (long long)cut);
            for (i64 s = n0 + 1; s < n_slots; ++s)
                CHECK(slot_keys[(size_t)s - 1] < slot_keys[(size_t)s],
                      "new slots out of key order at %lld", (long long)s);
            i64 late = 0;
            for (i64 i = lo; i < cut; ++i) {
                const Row &x = rows[(size_t)i];
                CHECK(x.ts < bound, "row past the bound folded");
                if (x.marker) continue;
                const i64 hi = x.ts >= 0 ? x.ts / S : -((-x.ts + S - 1) / S);
                i64 first = x.ts - L >= 0 ? (x.ts - L) / S + 1
                                          : -((L - x.ts + S - 1) / S) + 1;
                first = std::max(first, fired);
                late += hi < first;
                for (i64 w = first; w <= hi; ++w) {
                    auto it = want.find({x.key, w});
                    if (it == want.end())
                        it = want.insert({{x.key, w}, {0, 0, kMin}}).first;
                    it->second[0] += 1;
                    it->second[1] += x.value;
                    it->second[2] = std::max(it->second[2], x.ts);
                }
            }
            CHECK(late_got == late, "late %lld != %lld", (long long)late_got,
                  (long long)late);
            late_seen += late;
            lo = cut;
            if (cut == n) break;
            CHECK(rows[(size_t)cut].ts >= bound, "cut before the bound");
            // the boundary: window `fired` leaves, empty slots are retired
            const i64 lane = ((fired % W) + W) % W;
            i64 kept = 0;
            for (i64 s = 0; s < n_slots; ++s) {
                const size_t at = (size_t)(s * W + lane);
                auto it = want.find({slot_keys[(size_t)s], fired});
                if (lane_rows[at]) {
                    CHECK(it != want.end()
                              && it->second[0] == lane_rows[at]
                              && it->second[1] == sum[at]
                              && it->second[2] == mx[at],
                          "key %lld window %lld",
                          (long long)slot_keys[(size_t)s], (long long)fired);
                    want.erase(it);
                } else {
                    CHECK(it == want.end(), "a window lost its rows");
                }
                lane_rows[at] = 0;
                sum[at] = 0;
                mx[at] = kMin;
                bool live = false;
                for (i64 l = 0; l < W; ++l)
                    live |= lane_rows[(size_t)(s * W + l)] != 0;
                if (!live) continue;
                for (i64 l = 0; l < W; ++l) {
                    lane_rows[(size_t)(kept * W + l)] =
                        lane_rows[(size_t)(s * W + l)];
                    sum[(size_t)(kept * W + l)] = sum[(size_t)(s * W + l)];
                    mx[(size_t)(kept * W + l)] = mx[(size_t)(s * W + l)];
                }
                slot_keys[(size_t)kept++] = slot_keys[(size_t)s];
            }
            for (i64 s = kept; s < n_slots; ++s)
                for (i64 l = 0; l < W; ++l) {
                    lane_rows[(size_t)(s * W + l)] = 0;
                    sum[(size_t)(s * W + l)] = 0;
                    mx[(size_t)(s * W + l)] = kMin;
                }
            n_slots = kept;
            ++fired;
            tab.clear();        // rebuilt by the next reserve()
        }
    }
    for (const auto &kv : want)
        CHECK(kv.first.second >= fired, "window %lld of key %lld never left",
              (long long)kv.first.second, (long long)kv.first.first);
    CHECK(fired > 3 && late_seen > 0 && stops > 3,
          "fired=%lld late=%lld stops=%lld", (long long)fired,
          (long long)late_seen, (long long)stops);
}

static void state_abi_case(u64 seed, int tid) {
    bulk_case(seed, tid);
    sfold_case(seed, tid);

    Rng r(seed + 31337 * (u64)(tid + 1));
    const i64 n_keys = r.range(2, 9);
    void *a = new_core();
    feed(a, n_keys, r.range(3, 6), 0);
    CHECK(wf_core_key_count(a) == n_keys, "key_count");

    // full-state round trip into a fresh twin
    const i64 sz = wf_core_state_size(a);
    CHECK(sz > 0, "state_size=%lld", (long long)sz);
    std::vector<u8> blob((size_t)sz);
    CHECK(wf_core_state_export(a, blob.data(), sz) == sz, "export");
    void *b = new_core();
    CHECK(wf_core_state_import(b, blob.data(), sz) == 0, "import");
    CHECK(wf_core_state_size(b) == sz, "round-trip size");
    CHECK(wf_core_key_count(b) == n_keys, "imported key_count");
    std::vector<i64> ka((size_t)n_keys), kb((size_t)n_keys);
    CHECK(wf_core_key_list(a, ka.data(), n_keys) == n_keys, "key_list a");
    CHECK(wf_core_key_list(b, kb.data(), n_keys) == n_keys, "key_list b");
    CHECK(std::memcmp(ka.data(), kb.data(),
                      (size_t)n_keys * 8) == 0, "key sets differ");

    // refusals: import into a non-fresh core, then a corrupted magic
    CHECK(wf_core_state_import(b, blob.data(), sz) == -2,
          "non-fresh import must refuse -2");
    std::vector<u8> bad(blob);
    bad[0] ^= 0xff;
    void *fresh = new_core();
    CHECK(wf_core_state_import(fresh, bad.data(), sz) == -3,
          "bad magic must refuse -3");

    // per-key migration: export + neutralize on A, import on C
    const i64 mk = ka[(size_t)r.range(0, n_keys)];
    const i64 ksz = wf_core_key_state_size(a, mk);
    CHECK(ksz > 0, "key_state_size=%lld", (long long)ksz);
    std::vector<u8> kblob((size_t)ksz);
    CHECK(wf_core_key_export(a, mk, kblob.data(), ksz) == ksz, "kexport");
    CHECK(wf_core_key_neutralize(a, mk) == 0, "neutralize");
    CHECK(wf_core_key_count(a) == n_keys - 1, "count after neutralize");
    CHECK(wf_core_key_state_size(a, mk) == -2,
          "neutralized key must be gone (-2)");
    void *cc = new_core();
    CHECK(wf_core_key_import(cc, kblob.data(), ksz) == 0, "kimport");
    CHECK(wf_core_key_count(cc) == 1, "migrated key_count");
    CHECK(wf_core_key_state_size(cc, mk) == ksz, "migrated key size");

    // the migrated-away key keeps flowing on the NEW owner: tail rows
    // append cleanly to the imported state
    std::vector<Row> tail{Row{mk, 6, 6, 0, 7}};
    CHECK(wf_core_process(cc, tail.data(), 1, (i64)sizeof(Row),
                          offsetof(Row, key), offsetof(Row, id),
                          offsetof(Row, ts), offsetof(Row, marker),
                          offsetof(Row, value)) >= 0, "tail process");

    wf_core_free(a);
    wf_core_free(b);
    wf_core_free(fresh);
    wf_core_free(cc);
}

static void state_abi_phase(u64 seed) {
    // ABI work on per-thread cores while a background thread hammers an
    // unrelated queue: a TSan report here means the two subsystems
    // share state they must not
    void *q = wf_queue_new(8);
    std::atomic<bool> stop{false};
    std::thread noise([&] {
        i64 src, slot, i = 0;
        while (!stop.load(std::memory_order_relaxed)) {
            if (wf_queue_try_push(q, 0, i++) == 0)
                wf_queue_try_pop(q, &src, &slot);
        }
    });
    std::vector<std::thread> workers;
    for (int t = 0; t < 3; ++t)
        workers.emplace_back([=] { state_abi_case(seed, t); });
    for (auto &t : workers) t.join();
    stop.store(true);
    noise.join();
    wf_queue_close(q);
    wf_queue_free(q);
}

int main(int argc, char **argv) {
    u64 seed = 1;
    int n = 4;
    for (int i = 1; i < argc - 1; ++i) {
        if (!std::strcmp(argv[i], "--seed"))
            seed = (u64)std::strtoull(argv[i + 1], nullptr, 10);
        if (!std::strcmp(argv[i], "--n"))
            n = (int)std::strtol(argv[i + 1], nullptr, 10);
    }
    for (int c = 0; c < n; ++c) {
        const u64 cs = seed + (u64)c * 1000003ULL;
        queue_mpmc_case(cs, c);
        queue_close_race_case(cs);
        state_abi_phase(cs);
        std::printf("wf_stress: case %d/%d ok (seed=%llu)\n", c + 1, n,
                    (unsigned long long)cs);
        std::fflush(stdout);
    }
    std::printf("wf_stress: OK (seed=%llu cases=%d)\n",
                (unsigned long long)seed, n);
    return 0;
}
