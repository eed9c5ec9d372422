// windflow-tpu native host runtime: the window-core hot loop in C++.
//
// The reference library's entire hot path is C++ (win_seq.hpp:268-474 runs
// per tuple on a pinned thread).  This translation unit is its counterpart
// for the TPU framework: the per-row window bookkeeping — out-of-order
// drops, per-key archives, window creation/firing arithmetic, PLQ/MAP
// result renumbering, result-timestamp rules, EOS marker handling
// (win_seq.hpp:268-474, window.hpp:63-87, basic.hpp:136) — plus the
// device-staging assembly of the resident-archive path (ops/resident.py):
// narrow-dtype append rectangles, per-key ring offsets, fired-window
// descriptors in ring coordinates, and ring rebase decisions.
//
// Semantics are kept bit-identical to the Python cores (core/winseq.py,
// patterns/win_seq_tpu.py:ResidentWinSeqCore); tests/test_native.py asserts
// the differential.  Python calls in through a plain C ABI via ctypes, so
// every call releases the GIL — farm workers get true multicore host
// parallelism, like the reference's FastFlow pinned threads.
//
// Build: `make -C native` -> libwfnative.so (loaded by windflow_tpu/native).

#include <algorithm>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <functional>
#if defined(__GLIBC__)
#include <malloc.h>
#endif
#include <memory>
#include <mutex>
#include <new>
#include <thread>
#include <unordered_map>
#include <utility>
#include <vector>

using i64 = long long;
using u8 = unsigned char;

static const i64 NEG_INF = -(1LL << 62);

// deepest buddy-coalescing multiplicity the ring is provisioned for (the
// shape ladder stays {1x, 2x, 4x, 8x, 16x} — powers of two, so merged
// dispatches land on a small, warmup-coverable set of compile buckets)
static const i64 kCoalesceLadderMax = 16;
// absolute ring budget (KP * cap cells): 2^25 int32 cells = 128 MB of
// HBM per core — deep-merge provisioning backs off before exceeding it
static const i64 kMaxRingCells = 1LL << 25;
// multi-field staging bound: a core stages up to this many int64 payload
// columns per row (one device ring per field — ops/resident.py
// MultiFieldResidentExecutor); richer aggregates fall back to the Python
// core.  4 covers every tracked workload (YSB --rich-stats ships 2).
static const int kMaxFields = 4;
// arg-extremum cores (wf_core_set_arg) archive up to this many further
// int64 columns per row that never ship: the tie-break id and the fields
// of the winning row that the result carries (read back at harvest,
// wf_core_arg_gather)
static const int kMaxCarry = 8;

static inline i64 bucket(i64 n, i64 lo = 8) {
    i64 b = lo;
    while (b < n) b *= 2;
    return b;
}

static inline i64 pymod(i64 a, i64 m) {  // Python's nonnegative modulo
    i64 r = a % m;
    return r < 0 ? r + m : r;
}

// splitmix64 — the shard hash must not correlate with the farm routing
// modulus (default_routing is key % n_workers, so a keyed-farm worker sees
// only keys congruent mod n; sharding by key % S again would collapse
// every row onto one shard)
static inline unsigned long long mix64(unsigned long long x) {
    x += 0x9e3779b97f4a7c15ULL;
    x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
    x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
    return x ^ (x >> 31);
}

namespace {

enum Role { SEQ = 0, PLQ = 1, WLQ = 2, MAP = 3, REDUCE = 4 };
enum WinKind { CB = 0, TB = 1 };

// int64 column -> wire-dtype rectangle row (the H2D payload narrowing)
static inline void copy_narrow(u8 *dst, const i64 *src, i64 cnt, int wire) {
    if (wire == 0)
        for (i64 c = 0; c < cnt; ++c) ((int8_t *)dst)[c] = (int8_t)src[c];
    else if (wire == 1)
        for (i64 c = 0; c < cnt; ++c) ((int16_t *)dst)[c] = (int16_t)src[c];
    else if (wire == 2)
        for (i64 c = 0; c < cnt; ++c) ((int32_t *)dst)[c] = (int32_t)src[c];
    else
        std::memcpy(dst, src, (size_t)cnt * 8);
}

static inline i64 fdiv(i64 a, i64 b) {  // floor division, b > 0
    i64 q = a / b;
    return (a % b != 0 && a < 0) ? q - 1 : q;
}

// A stream-time stage's held-back rows of one key (wf_core_set_stream): the
// rows at or past the core's release bound, which the watermark has not
// passed yet.  Records are flat, `1 + n_cols` int64 each: the position, then
// the archived columns.  `ord` is the run that arrived in order (appended at
// its back, released from its front); a row that arrives behind `ord`'s
// newest goes into the bucket of its time slot instead (`lbk[j]` holds the
// positions of slot `lb0 + j`, unsorted), so taking it in is O(1) and a
// release sorts only the few slots the watermark has passed.
struct Hold {
    std::vector<i64> ord;
    size_t ord_start = 0;              // records released from ord's front
    std::deque<std::vector<i64>> lbk;
    i64 lb0 = 0;
};

// An archive column.  Its allocator default-initialises, so a resize() that
// grows the column leaves the new rows unwritten instead of zero-filling
// them: the bulk path (process_fast) writes every one of them in the pass
// that follows, or rolls them back.  Every other use means what it meant on
// a std::vector<i64> (assign and insert take their value, erase and reserve
// touch no new row).
template <class T>
struct NoInitAlloc : std::allocator<T> {
    template <class U> struct rebind { using other = NoInitAlloc<U>; };
    NoInitAlloc() = default;
    template <class U> NoInitAlloc(const NoInitAlloc<U> &) {}
    template <class U> void construct(U *p) { ::new ((void *)p) U; }
    template <class U, class... A> void construct(U *p, A &&...a) {
        ::new ((void *)p) U(std::forward<A>(a)...);
    }
};
using Col = std::vector<i64, NoInitAlloc<i64>>;

struct KeyState {
    // live archive: SoA ordered by pos, purge advances `start`
    // (core/archive.py's KeyArchive, reference stream_archive.hpp).  `ts`
    // holds rows only on a core that can be asked for an arbitrary row's
    // own timestamp later (Core::keep_ts: a count-based arg-extremum);
    // everywhere else a time-based row's `pos` is its ts and a count-based
    // window's result ts is carried in `tail_ts`
    Col pos, ts, val;
    // extra payload columns (fields 1..F-1 of a multi-field core);
    // empty on the default single-field cores so per-key memory stays flat
    std::vector<Col> xval;
    size_t start = 0;
    i64 appended = 0;      // rows ever archived (absolute row domain)
    i64 launched = 0;      // rows already shipped to the device ring
    i64 ring_base = 0;     // absolute row index of ring column 0
    i64 last_pos = NEG_INF;
    i64 initial_id = 0, first_gwid = 0;
    i64 next_lwid = 0, n_fired = 0, emit_counter = 0;
    i64 marker_pos = NEG_INF, marker_ts = 0;
    // the newest archived row (count-based cores): every window still to
    // fire ends above it, so it is the last row of each of them that the
    // next row closes -- what a CB result's ts is read from (emit_windows)
    i64 tail_pos = NEG_INF, tail_ts = 0;
    i64 purge_pos = NEG_INF;  // purge deferred to flush (rebase invariant)
    // per-field value range of UNSHIPPED rows, tracked at append time so
    // flush()'s wire-dtype choice needs no re-scan of the pending rows
    i64 pend_vmin[kMaxFields] = {0}, pend_vmax[kMaxFields] = {0};
    bool pend_any = false;
    int row = -1;             // dense ring row
    // key migrated away at a rescale barrier (wf_core_key_neutralize):
    // eos() and the state ABI skip it so the old owner never emits its
    // windows again; a late row for the key clears the flag and the key
    // restarts from fresh state (same as first contact on a new owner).
    // The dense row itself stays registered — queued launches and wrow
    // entries index rows by position, so rows are never renumbered.
    bool neutral = false;
    // arg-extremum cores: absolute start row of every fired window whose
    // winning row has not been read back yet (wf_core_arg_gather pops it);
    // purge() keeps the archive from the oldest of them on
    std::deque<i64> held;
    // stream-time cores only: the rows the watermark has not passed yet
    std::unique_ptr<Hold> hold;

    inline void note_vals(int nf, const i64 *vs) {
        if (!pend_any) {
            for (int f = 0; f < nf; ++f) pend_vmin[f] = pend_vmax[f] = vs[f];
            pend_any = true;
            return;
        }
        for (int f = 0; f < nf; ++f) {
            if (vs[f] < pend_vmin[f]) pend_vmin[f] = vs[f];
            if (vs[f] > pend_vmax[f]) pend_vmax[f] = vs[f];
        }
    }
    // block-range over-approximation, field 0 (the single-field bulk path)
    inline void note_range0(i64 lo, i64 hi) {
        if (!pend_any) {
            pend_vmin[0] = lo;
            pend_vmax[0] = hi;
            pend_any = true;
        } else {
            if (lo < pend_vmin[0]) pend_vmin[0] = lo;
            if (hi > pend_vmax[0]) pend_vmax[0] = hi;
        }
    }
    // hot-loop threshold caches (derived from next_lwid / n_fired; kept
    // in sync at the only sites that mutate them in the streaming path)
    i64 next_create = 0;      // initial_id + next_lwid*slide
    i64 fire_pos = 0;         // initial_id + n_fired*slide + win

    size_t live() const { return pos.size() - start; }

    void purge(bool keep_ts) {
        if (purge_pos <= NEG_INF) return;
        const i64 *p = pos.data() + start;
        size_t cut = std::lower_bound(p, p + live(), purge_pos) - p;
        bool limited = false;
        if (!held.empty()) {
            const i64 lim = held.front() - (appended - (i64)live());
            if (lim < (i64)cut) {
                cut = lim > 0 ? (size_t)lim : 0;
                limited = true;   // the rest goes at a later flush
            }
        }
        start += cut;
        if (!limited) purge_pos = NEG_INF;
        // amortised compaction (archive.py:purge_below)
        if (start > 4096 && start > live()) {
            pos.erase(pos.begin(), pos.begin() + start);
            if (keep_ts) ts.erase(ts.begin(), ts.begin() + start);
            val.erase(val.begin(), val.begin() + start);
            for (auto &xv : xval)
                xv.erase(xv.begin(), xv.begin() + start);
            start = 0;
        }
    }
};

struct Launch {
    i64 K = 0, R = 0, B = 0, KP = 0, cap = 0;
    int wire = 0;   // 0=int8 1=int16 2=int32 3=int64
    int rebase = 0;
    // regular-descriptor compression: when every key's windows form an
    // arithmetic sequence (start0 + i*slide, constant len — the steady
    // state of CB sliding windows), only (count, start0, len) per key
    // cross the wire and the device expands them with an iota; widx maps
    // each pending window to its index within its key (host-side gather)
    int regular = 0;
    i64 cmax = 0;
    int mult = 1;   // coalescing multiplicity (buddy scheme: 1, 2, 4, ...)
    std::vector<int32_t> rcount, rstart0, rlen, widx;   // K, K, K, B
    std::vector<u8> blk;              // K*R in wire dtype (field 0)
    // fields 1..F-1 of a multi-field core: one rectangle + wire dtype
    // each (field 0 stays in blk/wire so the single-field ABI and every
    // existing consumer are untouched)
    std::vector<std::vector<u8>> xblk;
    int xwire[kMaxFields] = {0};
    std::vector<i64> offs;            // K ring write offsets
    std::vector<int32_t> rows;        // K per-key valid row counts in blk
    std::vector<int32_t> wrows, wstarts, wlens;   // B window descriptors
    std::vector<i64> hkey, hid, hts, hlen;        // B result headers
    std::vector<i64> hpmax;   // B per-window max position (free from the
                              // ordered archive: the window's last row) —
                              // host-side MAX(ts)/MAX(id) for multi-stat
                              // aggregates without shipping the column
    std::vector<i64> hpmin;   // B per-window MIN position: the window's
                              // FIRST row, free by the same ordering —
                              // first-update style stats never ship
    // arg-extremum cores only: each window's absolute start row (the
    // harvest reads the winning row at habs + its ring-relative index);
    // rebase == 2 marks an on-device compaction — every ring row slides
    // left by shifts[k] before the append, nothing re-ships
    std::vector<i64> habs, shifts;
    // the padded rectangle width the core reserved ring room for, where
    // that is more than bucket(R): arg-extremum and early launches
    i64 Rb = 0;
    int trigger = 0;   // what cut the launch (FlushTrigger); a merged
                       // launch keeps its first part's
    // stream-time cores: this launch ends a fire, and the last window that
    // fire closed (the progress row that follows its results)
    int has_progress = 0;
    i64 progress_wid = 0;
};

// what cut a launch: a row or window count reached (flush_rows, batch_len),
// the device-following flush below them (wf_core_flush_early), the caller
// (the max-delay timer, a progress row), the end of the stream, a
// checkpoint barrier's drain (wf_core_barrier_flush)
enum FlushTrigger { NATURAL = 0, EARLY = 1, FORCED = 2, EOS = 3, BARRIER = 4 };

struct Core {
    i64 win, slide;
    int kind, role;
    i64 id_outer, n_outer, slide_outer, id_inner, n_inner, slide_inner;
    i64 map_idx0, map_idx1, result_ts_slide;
    i64 batch_len, flush_rows;
    int max_wire;   // widest wire dtype: 2=int32 (default), 3=int64
    // multi-field staging (wf_core_set_fields): number of payload columns
    // and each field's widest admissible wire dtype (max_wire_f[0] shadows
    // max_wire so the per-field logic has one source of truth)
    int n_fields = 1;
    int max_wire_f[kMaxFields];
    // arg-extremum mode (wf_core_set_arg): n_carry archived-only columns
    // after the shipped ones, ring rows bucketed from 1 (a key-less stream
    // is one row), a ring that never shrinks and starts at cap_floor (from
    // the window_rows the function declared, reserve_declared_window), a
    // steady rectangle width (rb_floor), purge at the NEXT window's start
    // and compaction on the device instead of a re-shipping rebase.
    // arg_field is the ship field the extremum runs over (ties are resolved
    // on its archive column)
    int arg_mode = 0, n_carry = 0, arg_field = 0;
    i64 window_rows = 0, cap_floor = 0, rb_floor = 0, kp_lo = 8;
    int n_cols() const { return n_fields + n_carry; }
    // whether the archives hold a `ts` column: only a count-based
    // arg-extremum's result carries the ts of an arbitrary archived row
    // (wf_core_arg_gather); set with arg_mode, before any row
    bool keep_ts = false;
    // int64 columns a row takes in its key's archive: pos, the shipped
    // fields, the carried ones, ts where it is kept
    int archive_cols() const { return 1 + n_cols() + (keep_ts ? 1 : 0); }
    // the shape of the last natural launch: rectangle width, windows a key
    // (regular launches) and wire dtypes.  An early flush pads itself up to
    // it, so a launch cut short of flush_rows runs the step executable the
    // natural path compiled and meets no shape of its own
    i64 nat_rb = 0, nat_cmax = 0;
    int nat_wire[kMaxFields] = {0};
    bool hopping;

    // Stream-time stage (wf_core_set_stream; time-based sliding or tumbling
    // windows of a plain sequential worker): window w is [w*slide,
    // w*slide + win) for every key and every integer w, and closes when the
    // watermark -- `clock`, the highest ts taken in (rows and markers), less
    // `holdback` -- reaches its end.  Rows wait in their key's Hold until
    // the watermark has passed them and reach archive and ring in position
    // order, so a window's rows are a range of both exactly when it may fire.
    int stream_mode = 0;
    i64 holdback = 0;
    i64 clock = NEG_INF;
    bool clock_set = false;
    i64 fired = 0;        // windows below it are closed
    i64 next_end = 0;     // end of window `fired`: the next fire
    i64 rel = NEG_INF;    // release bound: rows below it are archived
    i64 slot_w = 1;       // width of a Hold's late-row time slot
    i64 lag_max = 0;      // a release is made once the watermark leads
                          // `rel` by more (bounds the slots a Hold keeps)
    bool need_rebase = false;    // a row was inserted behind shipped ones
    int progress_pending = 0;    // a fire no launch carries yet
    i64 progress_wid = 0;
    std::vector<std::vector<i64>> spare_slots;   // emptied slot buffers
    std::vector<std::pair<i64, const i64 *>> late_scratch;
    // what the stage counts (wf_core_stream_stats)
    i64 n_ooo = 0, n_late = 0, n_behind = 0, n_held = 0, n_held_peak = 0,
        n_fires = 0, n_merges = 0, merge_ns = 0, fire_ns = 0;

    std::unordered_map<i64, int> rowmap;
    std::vector<int> direct;          // fast dense map for small keys
    std::vector<KeyState> keys;       // dense by ring row
    std::vector<i64> rowkey;

    // pending fired windows (absolute row coords; ring coords at flush)
    std::vector<int32_t> wrow;
    std::vector<i64> wlo, wlen, hkey, hid, hts, hpm, hpmn;
    i64 pend_rows = 0;

    i64 KP = 0, cap = 0;              // current ring geometry
    i64 room_mult = 2;                // per-key append room, in launch
                                      // widths (grows on ring-full rebase)
    std::deque<Launch> queue;
    std::mutex qmu;  // producer (process/eos on the node thread) vs
                     // consumer (wf_launch_peek/take on a ship thread)
    i64 fast_rows = 0;      // rows the bulk path took (wf_core_fast_rows)
    i64 launches_made = 0;  // produced-launch counter; only the producer
                            // thread reads/writes it (queue.size() is
                            // not safe to read unlocked)

    Core(i64 win_, i64 slide_, int kind_, int role_,
         i64 io, i64 no, i64 so, i64 ii, i64 ni, i64 si,
         i64 m0, i64 m1, i64 rts, i64 bl, i64 fr, int mw)
        : win(win_), slide(slide_), kind(kind_), role(role_),
          id_outer(io), n_outer(no), slide_outer(so),
          id_inner(ii), n_inner(ni), slide_inner(si),
          map_idx0(m0), map_idx1(m1), result_ts_slide(rts),
          batch_len(bl), flush_rows(fr), max_wire(mw),
          hopping(slide_ > win_), direct(4096, -1) {
        for (int f = 0; f < kMaxFields; ++f) max_wire_f[f] = mw;
    }

    KeyState &state(i64 key) {
        int r;
        if (key >= 0 && key < (i64)direct.size()) {
            r = direct[(size_t)key];
            if (r >= 0) return keys[r];
        } else {
            auto it = rowmap.find(key);
            if (it != rowmap.end()) return keys[it->second];
        }
        r = (int)keys.size();
        if (key >= 0 && key < (i64)direct.size()) direct[(size_t)key] = r;
        else rowmap.emplace(key, r);
        rowkey.push_back(key);
        keys.emplace_back();
        KeyState &st = keys.back();
        st.row = r;
        if (n_cols() > 1) st.xval.resize((size_t)(n_cols() - 1));
        // farm distribution math (windows.py PatternConfig,
        // reference win_seq.hpp:307-314)
        i64 a = pymod(id_inner - pymod(key, n_inner), n_inner);
        i64 b = pymod(id_outer - pymod(key, n_outer), n_outer);
        st.first_gwid = a * n_outer + b;
        i64 init_outer = b * slide_outer, init_inner = a * slide_inner;
        st.initial_id = (role == WLQ || role == REDUCE)
                            ? init_inner : init_outer + init_inner;
        st.emit_counter = (role == MAP) ? map_idx0 : 0;
        st.next_create = st.initial_id;
        st.fire_pos = st.initial_id + win;
        return st;
    }

    // Where a count-based window's result ts is read from: the ts of its
    // last row, the newest row below its end.  A window fires on the first
    // row at or past its end, so that is the newest row archived before the
    // trigger (`tail_*`; the newest row of all at eos()) -- or, on the bulk
    // path, which fires a block's windows after copying all of it, a row of
    // that block: the key's `m` rows there hold positions q0 .. q0+m-1 and
    // their ts stand `stride` bytes apart in the input chunk from `ts0`.
    struct LastRow {
        i64 tail_pos, tail_ts;
        i64 q0 = 0, m = 0, stride = 0;
        const u8 *ts0 = nullptr;
    };

    void emit_windows(KeyState &st, i64 key, i64 w_from, i64 w_to, bool eos,
                      const LastRow &last) {
        const i64 stride = n_outer * n_inner;
        const i64 *p = st.pos.data() + st.start;
        const size_t n = st.live();
        for (i64 w = w_from; w < w_to; ++w) {
            i64 gwid = st.first_gwid + w * stride;
            i64 s_abs = w * slide + st.initial_id;
            i64 e_abs = s_abs + win;
            size_t lo = std::lower_bound(p, p + n, s_abs) - p;
            size_t hi = eos ? n : (std::lower_bound(p, p + n, e_abs) - p);
            // result ts (winseq.py:_result_ts; window.hpp:121-124,154)
            i64 out_ts = 0;
            if (kind == TB) {
                out_ts = gwid * result_ts_slide + win - 1;
            } else {
                // 0 when the last row below the end lies below the start
                // too (an empty window)
                i64 lp = last.tail_pos, lt = last.tail_ts;
                if (last.m > 0 && e_abs > last.q0) {
                    const i64 r = std::min(e_abs - 1 - last.q0, last.m - 1);
                    lp = last.q0 + r;
                    std::memcpy(&lt, last.ts0 + r * last.stride, 8);
                }
                if (lp >= s_abs) out_ts = lt;
            }
            // marker rows overwrite the result ts of windows they fall
            // below — CB only: TB keeps the closed form above
            // (winseq.py:_result_ts returns before the marker clause)
            if (kind != TB && st.marker_pos > NEG_INF
                && st.marker_pos < e_abs)
                out_ts = st.marker_ts;
            // result id incl. PLQ/MAP renumbering (win_seq.hpp:396-405)
            i64 rid;
            if (role == MAP) {
                rid = st.emit_counter;
                st.emit_counter += map_idx1;
            } else if (role == PLQ) {
                i64 ioff = pymod(id_inner - pymod(key, n_inner), n_inner);
                rid = ioff + st.emit_counter * n_inner;
                st.emit_counter += 1;
            } else {
                rid = gwid;
            }
            i64 abs_lo = (st.appended - (i64)n) + (i64)lo;
            wrow.push_back(st.row);
            wlo.push_back(abs_lo);
            wlen.push_back((i64)(hi - lo));
            hkey.push_back(key);
            hid.push_back(rid);
            hts.push_back(out_ts);
            hpm.push_back(hi > lo ? p[hi - 1] : 0);
            hpmn.push_back(hi > lo ? p[lo] : 0);
            if (arg_mode) st.held.push_back(abs_lo);
            // an arg-extremum window's rows are dead once its winner is
            // read back: nothing below the NEXT window's start is needed
            if (!eos)
                st.purge_pos = std::max(st.purge_pos,
                                        arg_mode ? s_abs + slide : s_abs);
        }
    }

    // arg-extremum, first flush, where the function declared its window's
    // rows (ArgReducer window_rows: a time-based window's length in rows is
    // not in its spec): the ring starts twice as wide as one such window
    // plus one rectangle (`slack`; live rows and the rectangle never pass
    // half the ring: flush() widens it first) and the archives of the keys
    // met so far reserve one ring half, so the stream meets in its first
    // windows neither a growth of the ring (a compile, an HBM copy and a
    // fresh zero ring each) nor the doubling copies of gigabyte columns.
    // Only a start: a stream that outgrows the declaration still widens
    // its ring.
    void reserve_declared_window(i64 slack) {
        cap_floor = bucket(2 * (window_rows + slack), 16);
        const size_t rows = (size_t)(cap_floor / 2);
        for (auto &st : keys) {
            st.pos.reserve(rows);
            if (keep_ts) st.ts.reserve(rows);
            st.val.reserve(rows);
            for (auto &xv : st.xval) xv.reserve(rows);
        }
    }

    // An EARLY flush (wf_core_flush_early) ships what the core holds below
    // flush_rows / batch_len because a fired window waits and the ring is
    // idle.  It is made only where it costs no shape and no ring of its own:
    // after a natural launch has set both, padded to that launch's shape,
    // and never as a rebase — where the ring is full or a key is new it
    // leaves everything pending for the natural trigger.  A BARRIER flush
    // (wf_core_barrier_flush: a checkpoint's drain, once an epoch) ships
    // whatever is pending like a FORCED one, rebase included, but padded to
    // the last natural launch's shape where there is one: the rows since
    // the last launch are any number below flush_rows, and each bucket of
    // them would else be a step executable of its own.
    void flush(int trigger = NATURAL) {
        // (a fire that closed nothing still owes its progress row, which
        // rides on a launch so that it leaves after the earlier results)
        if (hkey.empty() && pend_rows == 0
            && !(progress_pending && !keys.empty()))
            return;
        const bool early = trigger == EARLY;
        if (early && (hkey.empty() || arg_mode || nat_rb == 0)) return;
        // takes the last natural launch's width and wire dtypes
        const bool padded = early || (trigger == BARRIER && nat_rb != 0
                                      && !arg_mode && !stream_mode);
        const i64 K = (i64)keys.size();
        const i64 KPb = bucket(std::max<i64>(K, 1), kp_lo);
        // a row-triggered FIRST flush marks a throughput stream: provision
        // the full coalescing ladder's ring room up front, so the steady
        // state has no room-growth rebases at all (each one is an
        // unmergeable dispatch; r3 measured ~4 of them costing ~5 extra
        // RTTs on the 16M-row bench).  Force/EOS-triggered first flushes
        // (tiny or latency-bound streams) keep the minimal ring.
        if (cap == 0 && pend_rows >= flush_rows)
            room_mult = kCoalesceLadderMax + 2;
        bool rebase = (cap == 0) || (KP < KPb) || need_rebase;
        if (early && rebase) return;
        need_rebase = false;
        // a stream-time stage cuts a launch at every fire, whatever rows it
        // holds then: one rectangle width for those and for the launches
        // flush_rows cuts (two of its shares a key), so the stage meets one
        // step shape a ring and not one a row count
        if (stream_mode)
            rb_floor = bucket(2 * std::max<i64>(
                flush_rows / std::max<i64>(K, 1), 64));
        i64 maxpend = 0;
        for (auto &st : keys)
            maxpend = std::max(maxpend, st.appended - st.launched);
        Launch L;
        if (!rebase) {
            const i64 Rb = std::max(bucket(std::max<i64>(maxpend, 1)),
                                    padded ? nat_rb : rb_floor);
            if (arg_mode) {
                // an arg-extremum ring is never re-shipped.  It is kept at
                // least twice as wide as the live rows plus one rectangle
                // (a harvest that lags holds rows back, and they must not
                // overrun it): past that the device widens it in place
                // (ArgExtResidentExecutor.grow) to at least four times, so
                // that a stream has to double before it grows again (each
                // growth is a compile of the step at the new width, an HBM
                // copy and a fresh zero ring) ...
                i64 need = 0;
                for (auto &st : keys)
                    need = std::max(need, st.launched
                                    - (st.appended - (i64)st.live()) + Rb);
                if (2 * need > cap) cap = std::max(cap, bucket(4 * need));
                // ... and when its tail is reached the live rows slide to
                // the front on the device (one HBM copy)
                bool full = false;
                for (auto &st : keys)
                    if (st.launched - st.ring_base + Rb > cap) full = true;
                if (full) {
                    L.shifts.assign((size_t)K, 0);
                    for (auto &st : keys) {
                        const i64 live_start = st.appended - (i64)st.live();
                        L.shifts[(size_t)st.row] = live_start - st.ring_base;
                        st.ring_base = live_start;
                    }
                }
            }
            for (auto &st : keys) {
                if (st.launched - st.ring_base + Rb > cap) {
                    if (early) return;
                    rebase = true;
                    // the stream keeps outrunning the ring: provision more
                    // append room next time, up to the full coalescing
                    // ladder's worth — steady streams converge on a ring
                    // deep merges fit in, one-shot streams never pay for it
                    room_mult = std::min<i64>(room_mult * 2,
                                              kCoalesceLadderMax + 2);
                    break;
                }
            }
        }
        i64 R;
        if (rebase) {
            i64 maxlive = 0;
            for (auto &st : keys)
                maxlive = std::max(maxlive, (i64)st.live());
            i64 slack =
                std::max<i64>(flush_rows / std::max<i64>(K, 1), 64);
            KP = KPb;
            if (cap == 0 && window_rows > 0) reserve_declared_window(slack);
            // ring room for room_mult launch widths per key: try_merge's
            // offset guard (maxoff + bucket(newR) <= cap) can only admit
            // merges the ring has room for, so coalescing depth is capped
            // by this provisioning (r2: the fixed 2*slack stopped the
            // ladder at ~2x).  room_mult grows on ring-full rebases above,
            // bounded by the absolute ring budget.
            // the ring budget is per CORE: a multi-field core allocates
            // one (KP, cap) device ring per field, so each field's share
            // of the cell budget shrinks accordingly
            while (room_mult > 2
                   && KPb * bucket(std::max<i64>(
                          2 * maxlive + room_mult * slack, 16))
                          > kMaxRingCells / n_fields)
                room_mult /= 2;
            cap = std::max(bucket(std::max<i64>(
                               2 * maxlive + room_mult * slack, 16)),
                           arg_mode ? std::max(cap, cap_floor) : (i64)0);
            L.shifts.clear();
            R = maxlive;
            for (auto &st : keys) {
                st.ring_base = st.appended - (i64)st.live();
                st.launched = st.ring_base;
            }
        } else {
            R = maxpend;
        }
        // narrowest wire dtype PER FIELD over the rows to ship.  Steady
        // state uses the per-key ranges tracked at append time (no
        // re-scan); a REBASE re-ships every live row — including
        // previously shipped ones outside the pending range — so it must
        // scan the actual ship range or wide old values would truncate
        // into a narrow wire
        bool anyv = false;
        i64 vmin[kMaxFields] = {0}, vmax[kMaxFields] = {0};
        if (rebase) {
            for (auto &st : keys) {
                for (size_t j = st.start; j < st.pos.size(); ++j) {
                    if (!anyv) {
                        vmin[0] = vmax[0] = st.val[j];
                        for (int f = 1; f < n_fields; ++f)
                            vmin[f] = vmax[f] = st.xval[(size_t)(f - 1)][j];
                        anyv = true;
                        continue;
                    }
                    for (int f = 0; f < n_fields; ++f) {
                        const i64 v = f == 0 ? st.val[j]
                                             : st.xval[(size_t)(f - 1)][j];
                        if (v < vmin[f]) vmin[f] = v;
                        if (v > vmax[f]) vmax[f] = v;
                    }
                }
            }
        } else {
            for (auto &st : keys) {
                if (!st.pend_any) continue;
                if (!anyv) {
                    for (int f = 0; f < n_fields; ++f) {
                        vmin[f] = st.pend_vmin[f];
                        vmax[f] = st.pend_vmax[f];
                    }
                    anyv = true;
                } else {
                    for (int f = 0; f < n_fields; ++f) {
                        vmin[f] = std::min(vmin[f], st.pend_vmin[f]);
                        vmax[f] = std::max(vmax[f], st.pend_vmax[f]);
                    }
                }
            }
        }
        for (int f = 0; f < n_fields; ++f) {
            int w;
            if (!anyv || (vmin[f] >= -128 && vmax[f] <= 127)) w = 0;
            else if (vmin[f] >= -32768 && vmax[f] <= 32767) w = 1;
            else if (max_wire_f[f] <= 2
                     || (vmin[f] >= INT32_MIN && vmax[f] <= INT32_MAX))
                w = 2;
            else w = 3;   // int64 wire (64-bit accumulate dtype)
            L.xwire[f] = padded ? std::max(w, nat_wire[f]) : w;
        }
        L.wire = L.xwire[0];
        const i64 Rr = std::max<i64>(R, 1);
        L.blk.assign((size_t)(K * Rr) << L.wire, 0);
        if (n_fields > 1) {
            L.xblk.resize((size_t)(n_fields - 1));
            for (int f = 1; f < n_fields; ++f)
                L.xblk[(size_t)(f - 1)].assign(
                    (size_t)(K * Rr) << L.xwire[f], 0);
        }
        L.offs.assign((size_t)K, 0);
        L.rows.assign((size_t)K, 0);
        for (auto &st : keys) {
            i64 live_start = st.appended - (i64)st.live();
            size_t j0 = st.start + (size_t)(st.launched - live_start);
            i64 cnt = (i64)(st.pos.size() - j0);
            L.offs[(size_t)st.row] = st.launched - st.ring_base;
            L.rows[(size_t)st.row] = (int32_t)cnt;
            copy_narrow(L.blk.data() + ((size_t)(st.row * Rr) << L.wire),
                        st.val.data() + j0, cnt, L.wire);
            for (int f = 1; f < n_fields; ++f)
                copy_narrow(L.xblk[(size_t)(f - 1)].data()
                                + ((size_t)(st.row * Rr) << L.xwire[f]),
                            st.xval[(size_t)(f - 1)].data() + j0, cnt,
                            L.xwire[f]);
            st.launched = st.appended;
            st.pend_any = false;
        }
        const i64 B = (i64)hkey.size();
        L.wrows.resize((size_t)B);
        L.wstarts.resize((size_t)B);
        L.wlens.resize((size_t)B);
        L.hlen.resize((size_t)B);
        for (i64 i = 0; i < B; ++i) {
            int rr = wrow[(size_t)i];
            L.wrows[(size_t)i] = rr;
            L.wstarts[(size_t)i] =
                (int32_t)(wlo[(size_t)i] - keys[(size_t)rr].ring_base);
            L.wlens[(size_t)i] = (int32_t)wlen[(size_t)i];
            L.hlen[(size_t)i] = wlen[(size_t)i];
        }
        // regularity detection (one pass): per key, windows must advance
        // by `slide` ring positions with one constant length
        if (B > 0 && kind == CB && !hopping) {
            L.rcount.assign((size_t)K, 0);
            L.rstart0.assign((size_t)K, 0);
            L.rlen.assign((size_t)K, 0);
            L.widx.resize((size_t)B);
            std::vector<int32_t> expect((size_t)K, 0);
            bool ok = true;
            for (i64 i = 0; i < B; ++i) {
                const size_t r = (size_t)L.wrows[(size_t)i];
                if (L.rcount[r] == 0) {
                    L.rstart0[r] = L.wstarts[(size_t)i];
                    L.rlen[r] = L.wlens[(size_t)i];
                    expect[r] = L.wstarts[(size_t)i];
                }
                if (L.wstarts[(size_t)i] != expect[r]
                    || L.wlens[(size_t)i] != L.rlen[r]) {
                    ok = false;
                    break;
                }
                L.widx[(size_t)i] = L.rcount[r]++;
                expect[r] += (int32_t)slide;
            }
            if (ok) {
                L.regular = 1;
                for (i64 r = 0; r < K; ++r)
                    L.cmax = std::max<i64>(L.cmax, L.rcount[(size_t)r]);
            }
        }
        L.hkey = std::move(hkey);
        L.hid = std::move(hid);
        L.hts = std::move(hts);
        L.hpmax = std::move(hpm);
        L.hpmin = std::move(hpmn);
        L.K = K; L.R = Rr; L.B = B; L.KP = KP; L.cap = cap;
        L.rebase = rebase ? 1 : (L.shifts.empty() ? 0 : 2);
        L.trigger = trigger;
        if (arg_mode) {
            L.habs = wlo;
            L.Rb = std::max(bucket(Rr), rb_floor);
            rb_floor = std::min(L.Rb, bucket(std::max<i64>(flush_rows, 1)));
        } else if (stream_mode) {
            L.Rb = std::max(bucket(Rr), rb_floor);
            L.has_progress = progress_pending;
            L.progress_wid = progress_wid;
            progress_pending = 0;
        } else if (padded) {
            L.Rb = std::max(bucket(Rr), nat_rb);
            if (L.regular) L.cmax = std::max(L.cmax, nat_cmax);
        } else if (trigger == NATURAL) {
            // (a rebase re-ships the live rows too: the steady shape is
            // that of the pending ones)
            nat_rb = bucket(std::max<i64>(maxpend, 1));
            nat_cmax = L.regular ? L.cmax : 0;
            for (int f = 0; f < n_fields; ++f) nat_wire[f] = L.xwire[f];
        }
        {
            std::lock_guard<std::mutex> lk(qmu);
            queue.push_back(std::move(L));
        }
        ++launches_made;
        for (auto &st : keys) st.purge(keep_ts);
        pend_rows = 0;
        wrow.clear(); wlo.clear(); wlen.clear();
        hkey = {}; hid = {}; hts = {}; hpm = {}; hpmn = {};
    }

    // Bulk path for key-PERIODIC in-order chunks — the shape every
    // benchmark generator produces (row i carries key i % P with per-key
    // ids advancing by 1: bench.py make_stream, the sum_test fixtures'
    // tile layout, reference sum_cb.hpp:89-117).  ONE fused pass verifies
    // the pattern row-by-row against cached expectations (key_of[idx],
    // nextpos[idx]) while copying — no state lookup, no threshold
    // compares, no marker branch beyond one byte test; window math runs
    // once per key per block.  Any pattern break rolls the current block
    // back and returns the consumed prefix; the general loop finishes the
    // tail.  Returns rows consumed (0 = chunk head not periodic).
    i64 process_fast(const u8 *base, i64 n, i64 itemsize, i64 o_key,
                     i64 o_id, i64 o_ts, i64 o_marker, i64 o_val) {
        // single-field only: the bulk path's fused verify+copy is the
        // bench hot loop and stays specialized; multi-field streams (none
        // of which are key-periodic in the tracked workloads) take the
        // general loop
        if (kind != CB || hopping || n < 2 || n_cols() > 1 || arg_mode)
            return 0;
        i64 key0;
        std::memcpy(&key0, base + o_key, 8);
        i64 P = -1;
        const i64 scan = std::min<i64>(n, 4096);
        for (i64 i = 1; i < scan; ++i) {
            i64 k;
            std::memcpy(&k, base + i * itemsize + o_key, 8);
            if (k == key0) { P = i; break; }
        }
        if (P <= 0 || n < 2 * P) return 0;
        // admission over the first period: no markers, distinct keys,
        // in-order continuation at/after the worker's initial position
        std::vector<i64> key_of((size_t)P), nextpos((size_t)P);
        for (i64 k = 0; k < P; ++k) {
            const u8 *rp = base + k * itemsize;
            if (rp[o_marker]) return 0;
            std::memcpy(&key_of[(size_t)k], rp + o_key, 8);
            std::memcpy(&nextpos[(size_t)k], rp + o_id, 8);
        }
        {
            // duplicate keys within one period would alias KeyStates and
            // interleave unsorted positions into one archive: bail out
            std::vector<i64> sorted = key_of;
            std::sort(sorted.begin(), sorted.end());
            if (std::adjacent_find(sorted.begin(), sorted.end())
                != sorted.end())
                return 0;
        }
        // state() first for every key (it may grow `keys`, invalidating
        // pointers), then resolve pointers
        for (i64 k = 0; k < P; ++k)
            state(key_of[(size_t)k]);
        std::vector<KeyState *> sts((size_t)P);
        for (i64 k = 0; k < P; ++k) {
            KeyState &st = state(key_of[(size_t)k]);
            if (st.neutral)   // general loop clears the flag per row
                return 0;
            if (nextpos[(size_t)k] < st.last_pos
                || nextpos[(size_t)k] < st.initial_id)
                return 0;
            sts[(size_t)k] = &st;
        }
        // process in blocks so the flush_rows / batch_len launch
        // granularity matches the general loop's
        i64 block = flush_rows;
        if (batch_len < (i64)1 << 40)
            block = std::min(block, batch_len * slide);
        block = std::max(block, P);
        std::vector<i64 *> pw((size_t)P), vw((size_t)P);
        std::vector<i64> mcnt((size_t)P), save_next((size_t)P);
        std::vector<size_t> save_sz((size_t)P);
        i64 consumed = 0;
        i64 idx0 = 0;   // key index of row `consumed`
        while (consumed < n) {
            const i64 take = std::min(block, n - consumed);
            for (i64 k = 0; k < P; ++k) {
                // rows i in [consumed, consumed+take) with (i - k) % P == 0
                const i64 first = (k - idx0 + P) % P;
                const i64 m = first < take ? (take - 1 - first) / P + 1 : 0;
                mcnt[(size_t)k] = m;
                KeyState &st = *sts[(size_t)k];
                save_sz[(size_t)k] = st.pos.size();
                save_next[(size_t)k] = nextpos[(size_t)k];
                // (grown unwritten: Col's allocator fills nothing in)
                st.pos.resize(st.pos.size() + (size_t)m);
                st.val.resize(st.val.size() + (size_t)m);
                pw[(size_t)k] = st.pos.data() + save_sz[(size_t)k];
                vw[(size_t)k] = st.val.data() + save_sz[(size_t)k];
            }
            // fused verify + copy: one sequential pass over the block
            const u8 *rp = base + consumed * itemsize;
            i64 idx = idx0;
            i64 bmin = INT64_MAX, bmax = INT64_MIN;
            i64 done = 0;
            for (; done < take; ++done) {
                i64 k, id, v;
                std::memcpy(&k, rp + o_key, 8);
                std::memcpy(&id, rp + o_id, 8);
                if (k != key_of[(size_t)idx] || id != nextpos[(size_t)idx]
                    || rp[o_marker])
                    break;
                std::memcpy(&v, rp + o_val, 8);
                if (v < bmin) bmin = v;
                if (v > bmax) bmax = v;
                // the columns were grown unwritten, so nothing has pulled
                // their lines into the cache (the zero-fill used to): ask
                // for the line after next as a column enters a new one, or
                // each of the 2P write streams waits on its own misses
                if ((((uintptr_t)vw[(size_t)idx]) & 63) == 0)
                    __builtin_prefetch(vw[(size_t)idx] + 16, 1);
                if ((((uintptr_t)pw[(size_t)idx]) & 63) == 0)
                    __builtin_prefetch(pw[(size_t)idx] + 16, 1);
                *vw[(size_t)idx]++ = v;
                *pw[(size_t)idx]++ = nextpos[(size_t)idx]++;
                rp += itemsize;
                if (++idx == P) idx = 0;
            }
            if (done < take) {
                // pattern broke mid-block: roll this block back (committed
                // blocks stand); the general loop takes the tail
                for (i64 k = 0; k < P; ++k) {
                    KeyState &st = *sts[(size_t)k];
                    st.pos.resize(save_sz[(size_t)k]);
                    st.val.resize(save_sz[(size_t)k]);
                    nextpos[(size_t)k] = save_next[(size_t)k];
                }
                return consumed;
            }
            // bookkeeping for all keys first (flush() during the firing
            // loop below purges/compacts archives, so no block pointer is
            // touched past this point), then firing with the thresholds
            // evaluated once per key per block
            for (i64 k = 0; k < P; ++k) {
                const i64 m = mcnt[(size_t)k];
                if (m == 0) continue;
                KeyState &st = *sts[(size_t)k];
                st.appended += m;
                pend_rows += m;
                st.last_pos = nextpos[(size_t)k] - 1;
                // the block-wide value range over-approximates per key —
                // safe for wire-dtype choice (never narrower than exact)
                st.note_range0(bmin, bmax);
            }
            for (i64 k = 0; k < P; ++k) {
                const i64 m = mcnt[(size_t)k];
                if (m == 0) continue;
                KeyState &st = *sts[(size_t)k];
                const i64 endpos = st.last_pos;
                // the key's rows of this block, as the input chunk holds
                // them: the ts column is read there and not archived
                const LastRow last{
                    st.tail_pos, st.tail_ts, save_next[(size_t)k], m,
                    P * itemsize,
                    base + (consumed + (k - idx0 + P) % P) * itemsize + o_ts};
                st.tail_pos = endpos;
                std::memcpy(&st.tail_ts, last.ts0 + (m - 1) * last.stride, 8);
                if (endpos >= st.next_create) {
                    st.next_lwid = (endpos - st.initial_id) / slide + 1;
                    st.next_create = st.next_lwid * slide + st.initial_id;
                }
                if (endpos >= st.fire_pos) {
                    i64 to = (endpos - st.initial_id - win) / slide + 1;
                    if (to > st.next_lwid) to = st.next_lwid;
                    const i64 from = st.n_fired;
                    st.n_fired = to;
                    st.fire_pos = to * slide + win + st.initial_id;
                    emit_windows(st, key_of[(size_t)k], from, to, false,
                                 last);
                    if ((i64)hkey.size() >= batch_len) flush();
                }
            }
            consumed += take;
            idx0 = (idx0 + take) % P;
            if (pend_rows >= flush_rows) flush();
        }
        return consumed;
    }


    // ------------------------------------------------- stream-time stage
    static inline i64 now_ns() {
        return (i64)std::chrono::duration_cast<std::chrono::nanoseconds>(
            std::chrono::steady_clock::now().time_since_epoch()).count();
    }

    // one released record onto its key's archive (position order kept by
    // the caller)
    inline void archive_row(KeyState &st, const i64 *rec) {
        st.pos.push_back(rec[0]);
        st.val.push_back(rec[1]);
        const int nc = n_cols();
        for (int f = 1; f < nc; ++f)
            st.xval[(size_t)(f - 1)].push_back(rec[1 + f]);
        st.note_vals(n_fields, rec + 1);
        st.appended++;
        pend_rows++;
    }

    inline void hold_row(KeyState &st, const i64 *rec) {
        if (!st.hold) st.hold.reset(new Hold());
        Hold &h = *st.hold;
        const size_t stride = (size_t)(1 + n_cols());
        const size_t on = h.ord.size();
        if (on == h.ord_start * stride || rec[0] >= h.ord[on - stride]) {
            h.ord.insert(h.ord.end(), rec, rec + stride);
        } else {
            // behind the in-order run: into its time slot
            const i64 slot = fdiv(rec[0], slot_w);
            if (h.lbk.empty()) h.lb0 = slot;
            while (slot < h.lb0) {
                h.lbk.emplace_front(take_slot());
                --h.lb0;
            }
            while (slot - h.lb0 >= (i64)h.lbk.size())
                h.lbk.emplace_back(take_slot());
            std::vector<i64> &b = h.lbk[(size_t)(slot - h.lb0)];
            b.insert(b.end(), rec, rec + stride);
        }
        if (++n_held > n_held_peak) n_held_peak = n_held;
    }

    std::vector<i64> take_slot() {
        if (spare_slots.empty()) return std::vector<i64>();
        std::vector<i64> v = std::move(spare_slots.back());
        spare_slots.pop_back();
        return v;
    }

    // move the held rows of one key that lie below `bound` onto its
    // archive: the front of the in-order run merged with the late slots the
    // bound has passed, those sorted first
    void merge_key(KeyState &st, i64 bound) {
        Hold &h = *st.hold;
        const size_t stride = (size_t)(1 + n_cols());
        const i64 *o = h.ord.data();
        size_t a = h.ord_start, hi = h.ord.size() / stride, lo = a;
        while (lo < hi) {
            const size_t mid = lo + (hi - lo) / 2;
            if (o[mid * stride] < bound) lo = mid + 1;
            else hi = mid;
        }
        const size_t b = lo;
        auto &late = late_scratch;
        late.clear();
        i64 full = 0;
        if (!h.lbk.empty()) {
            const i64 bslot = fdiv(bound, slot_w);
            full = std::min<i64>(std::max<i64>(bslot - h.lb0, 0),
                                 (i64)h.lbk.size());
            for (i64 j = 0; j < full; ++j) {
                const std::vector<i64> &v = h.lbk[(size_t)j];
                for (size_t r = 0; r < v.size(); r += stride)
                    late.emplace_back(v[r], v.data() + r);
            }
            if (full < (i64)h.lbk.size() && h.lb0 + full == bslot) {
                // the slot the bound lies in: only what is below it
                for (size_t r = 0; r < h.lbk[(size_t)full].size();
                     r += stride) {
                    const i64 *rec = h.lbk[(size_t)full].data() + r;
                    if (rec[0] < bound) late.emplace_back(rec[0], rec);
                }
            }
        }
        if (a == b && late.empty()) return;
        std::stable_sort(late.begin(), late.end(),
                         [](const std::pair<i64, const i64 *> &x,
                            const std::pair<i64, const i64 *> &y) {
                             return x.first < y.first;
                         });
        const size_t total = (b - a) + late.size();
        size_t li = 0;
        for (size_t i = a; i < b; ++i) {
            const i64 *rec = o + i * stride;
            while (li < late.size() && late[li].first < rec[0])
                archive_row(st, late[li++].second);
            archive_row(st, rec);
        }
        while (li < late.size()) archive_row(st, late[li++].second);
        n_held -= (i64)total;
        // the in-order run: its front is gone
        h.ord_start = b;
        if (b * stride == h.ord.size()) {
            h.ord.clear();
            h.ord_start = 0;
        } else if (b > 4096 && b * stride > h.ord.size() / 2) {
            h.ord.erase(h.ord.begin(), h.ord.begin() + (ptrdiff_t)(b * stride));
            h.ord_start = 0;
        }
        // the late slots: the passed ones go, the bound's own is compacted
        if (full < (i64)h.lbk.size() && !late.empty()) {
            std::vector<i64> &v = h.lbk[(size_t)full];
            size_t w = 0;
            for (size_t r = 0; r < v.size(); r += stride) {
                if (v[r] >= bound) {
                    if (w != r)
                        std::memmove(v.data() + w, v.data() + r, stride * 8);
                    w += stride;
                }
            }
            v.resize(w);
        }
        for (i64 j = 0; j < full; ++j) {
            h.lbk.front().clear();
            spare_slots.push_back(std::move(h.lbk.front()));
            h.lbk.pop_front();
        }
        h.lb0 += full;
    }

    // the watermark has reached `bound`: every held row below it goes to its
    // archive (and, at the next flush, to the ring)
    void release(i64 bound) {
        if (bound <= rel) return;
        i64 t0 = now_ns();
        for (size_t r = 0; r < keys.size(); ++r) {
            if (keys[r].hold) merge_key(keys[r], bound);
            if (pend_rows >= flush_rows) {   // (a launch's cut is not the
                merge_ns += now_ns() - t0;   // merge's time)
                flush();
                t0 = now_ns();
            }
        }
        rel = bound;
        merge_ns += now_ns() - t0;
        ++n_merges;
    }

    // one window of one key, if it holds a row
    void emit_stream_window(KeyState &st, i64 key, i64 w) {
        const i64 *p = st.pos.data() + st.start;
        const size_t n = st.live();
        const i64 s = w * slide, e = s + win;
        const size_t lo = std::lower_bound(p, p + n, s) - p;
        const size_t hi = std::lower_bound(p, p + n, e) - p;
        if (hi == lo) return;
        wrow.push_back(st.row);
        wlo.push_back((st.appended - (i64)n) + (i64)lo);
        wlen.push_back((i64)(hi - lo));
        hkey.push_back(key);
        hid.push_back(w);
        hts.push_back(w * result_ts_slide + win - 1);
        hpm.push_back(p[hi - 1]);
        hpmn.push_back(p[lo]);
        if ((i64)hkey.size() >= batch_len) flush();
    }

    // windows [fired, upto) of every key, window by window, over what the
    // archives hold
    void fire_windows(i64 upto, bool eos = false) {
        i64 pmin = INT64_MAX, pmax = INT64_MIN;
        for (auto &st : keys) {
            if (st.neutral || st.live() == 0) continue;
            pmin = std::min(pmin, st.pos[st.start]);
            pmax = std::max(pmax, st.pos.back());
        }
        if (pmin <= pmax) {
            const i64 w_lo = std::max(fired, fdiv(pmin - win, slide) + 1);
            const i64 w_hi = std::min(upto, fdiv(pmax, slide) + 1);
            for (i64 w = w_lo; w < w_hi; ++w)
                for (size_t r = 0; r < keys.size(); ++r)
                    if (!keys[r].neutral)
                        emit_stream_window(keys[r], rowkey[r], w);
        }
        if (!eos && upto > fired) {
            fired = upto;
            next_end = upto * slide + win;
            // nothing below the last closed window's start is read again
            for (auto &st : keys)
                st.purge_pos = std::max(st.purge_pos, (upto - 1) * slide);
        }
    }

    // the watermark reached the end of window `fired`
    void fire_upto(i64 wm) {
        release(wm);
        const i64 t0 = now_ns();
        fire_windows(fdiv(wm - win, slide) + 1);
        progress_wid = fired - 1;
        progress_pending = 1;
        ++n_fires;
        flush();
        fire_ns += now_ns() - t0;
    }

    inline void tick(i64 t) {
        clock = t;
        if (!clock_set) {
            // the stage starts at window 0, unless its first watermark
            // lies before time 0: then at the first window that has not
            // closed (core/windows.run_stream_clock)
            clock_set = true;
            fired = t - holdback < 0 ? fdiv(t - holdback - win, slide) + 1
                                     : 0;
            next_end = fired * slide + win;
            rel = fired * slide;
        }
        const i64 wm = t - holdback;
        if (wm >= next_end) fire_upto(wm);
        else if (wm - rel > lag_max) release(wm);
    }

    // a row behind the release bound: late if every window of its is
    // closed; else it is inserted where it belongs and the next flush
    // re-ships the live rows (a rebase), so archive and ring stay ordered.
    // Only a hold-back below the stream's disorder brings rows here:
    // correct, and slow.  (No fired window is pending between two fires,
    // so no descriptor's row coordinates move.)
    void behind(KeyState &st, const i64 *rec) {
        if (fdiv(rec[0], slide) < fired) {
            ++n_late;
            return;
        }
        ++n_behind;
        const size_t at = (size_t)(std::upper_bound(
            st.pos.begin() + (ptrdiff_t)st.start, st.pos.end(), rec[0])
            - st.pos.begin());
        st.pos.insert(st.pos.begin() + (ptrdiff_t)at, rec[0]);
        st.val.insert(st.val.begin() + (ptrdiff_t)at, rec[1]);
        for (int f = 1; f < n_cols(); ++f)
            st.xval[(size_t)(f - 1)].insert(
                st.xval[(size_t)(f - 1)].begin() + (ptrdiff_t)at, rec[1 + f]);
        st.appended++;
        pend_rows++;
        need_rebase = true;
    }

    i64 process_stream(const u8 *base, i64 n, i64 itemsize, i64 o_key,
                       i64 o_ts, i64 o_marker, i64 o_val,
                       const i64 *o_xval) {
        const i64 q0 = launches_made;
        const int nc = n_cols();
        if (nc > 1 && o_xval == nullptr) return -1;
        i64 rec[1 + kMaxFields + kMaxCarry];
        for (i64 i = 0; i < n; ++i) {
            const u8 *rp = base + i * itemsize;
            std::memcpy(&rec[0], rp + o_ts, 8);
            if (rec[0] > clock) tick(rec[0]);
            if (rp[o_marker]) continue;   // moves the clock, folds nowhere
            i64 key;
            std::memcpy(&key, rp + o_key, 8);
            std::memcpy(&rec[1], rp + o_val, 8);
            for (int f = 1; f < nc; ++f)
                std::memcpy(&rec[1 + f], rp + o_xval[f - 1], 8);
            KeyState &st = state(key);
            if (st.neutral) st.neutral = false;
            if (rec[0] < st.last_pos) ++n_ooo;
            else st.last_pos = rec[0];
            if (rec[0] >= rel) hold_row(st, rec);
            else behind(st, rec);
        }
        // what the watermark has passed by the end of the chunk goes on
        if (clock_set) release(clock - holdback);
        if (pend_rows >= flush_rows || need_rebase) flush();
        return launches_made - q0;
    }

    i64 process(const u8 *base, i64 n, i64 itemsize, i64 o_key, i64 o_id,
                i64 o_ts, i64 o_marker, i64 o_val,
                i64 shard_mod = 1, i64 shard_id = 0,
                const u8 *shard_of = nullptr,
                const i64 *o_xval = nullptr) {
        if (stream_mode)
            return process_stream(base, n, itemsize, o_key, o_ts, o_marker,
                                  o_val, o_xval);
        const i64 q0 = launches_made;
        if (shard_of == nullptr && shard_mod == 1) {
            const i64 fdone = process_fast(base, n, itemsize, o_key, o_id,
                                           o_ts, o_marker, o_val);
            fast_rows += fdone;
            if (fdone >= n) return launches_made - q0;
            base += fdone * itemsize;
            n -= fdone;
        }
        // One sequential pass (reads stay prefetch-friendly even with
        // interleaved keys); the per-row divisions of the closed-form
        // firing arithmetic (core/winseq.py) are replaced by two monotone
        // comparisons against cached create/fire position thresholds —
        // divisions only run on the (rare) create/fire events.  With
        // shard_mod > 1 this core owns only keys with mix64(key) %%
        // shard_mod == shard_id (the multithreaded key-sharded path);
        // `shard_of` is the precomputed per-row shard-id byte array from
        // wf_cores_process_mt — a 1-byte compare per foreign row instead
        // of a hash + division per row per shard.
        // a multi-field core driven through the single-field entry points
        // has no extra offsets: refuse (defined error) instead of
        // dereferencing null per appended row
        if (n_cols() > 1 && o_xval == nullptr) return -1;
        const u8 sid = (u8)shard_id;
        for (i64 i = 0; i < n; ++i) {
            const u8 *rp = base + i * itemsize;
            if (shard_of != nullptr && shard_of[i] != sid)
                continue;
            i64 key, id, tsv, val;
            std::memcpy(&key, rp + o_key, 8);
            if (shard_of == nullptr && shard_mod > 1
                && (i64)(mix64((unsigned long long)key)
                         % (unsigned long long)shard_mod) != shard_id)
                continue;
            std::memcpy(&id, rp + o_id, 8);
            std::memcpy(&tsv, rp + o_ts, 8);
            std::memcpy(&val, rp + o_val, 8);
            const bool mk = rp[o_marker] != 0;
            KeyState &st = state(key);
            if (st.neutral) st.neutral = false;
            // the newest row archived before this one: the last row of the
            // windows this one closes
            const LastRow last{st.tail_pos, st.tail_ts};
            const i64 pos = (kind == CB) ? id : tsv;
            if (pos < st.last_pos) continue;       // out-of-order drop
            st.last_pos = pos;
            if (pos < st.initial_id) continue;     // before worker's slice
            if (mk) {
                st.marker_pos = pos;
                st.marker_ts = tsv;
            } else {
                if (hopping && ((pos - st.initial_id) % slide) >= win)
                    continue;                      // hopping gap
                st.pos.push_back(pos);
                if (keep_ts) st.ts.push_back(tsv);
                st.val.push_back(val);
                st.tail_pos = pos;
                st.tail_ts = tsv;
                i64 vrow[kMaxFields];
                vrow[0] = val;
                for (int f = 1; f < n_cols(); ++f) {
                    i64 v;
                    std::memcpy(&v, rp + o_xval[f - 1], 8);
                    st.xval[(size_t)(f - 1)].push_back(v);
                    if (f < n_fields) vrow[f] = v;
                }
                st.note_vals(n_fields, vrow);
                st.appended++;
                pend_rows++;
            }
            if (pos >= st.next_create) {           // lazy window creation
                st.next_lwid = (pos - st.initial_id) / slide + 1;
                st.next_create = st.next_lwid * slide + st.initial_id;
            }
            if (pos >= st.fire_pos) {              // triggerer fired
                i64 to = (pos - st.initial_id - win) / slide + 1;
                if (to > st.next_lwid) to = st.next_lwid;
                const i64 from = st.n_fired;
                st.n_fired = to;
                st.fire_pos = to * slide + win + st.initial_id;
                emit_windows(st, key, from, to, false, last);
                if ((i64)hkey.size() >= batch_len) flush();
            }
            // rows-only flush: giant windows accumulate rows long before
            // any fire event; ship bounded rectangles regardless
            if (pend_rows >= flush_rows) flush();
        }
        return launches_made - q0;
    }

    i64 eos() {
        const i64 q0 = launches_made;
        if (stream_mode) {
            // every held row goes on, every window that holds a row fires
            release(INT64_MAX);
            fire_windows(INT64_MAX, true);
            flush(EOS);
            return launches_made - q0;
        }
        for (size_t r = 0; r < keys.size(); ++r) {
            KeyState &st = keys[r];
            if (st.neutral) continue;   // key migrated away at a rescale
            if (st.n_fired < st.next_lwid) {
                const i64 from = st.n_fired;
                st.n_fired = st.next_lwid;
                emit_windows(st, rowkey[r], from, st.next_lwid, true,
                             LastRow{st.tail_pos, st.tail_ts});
            }
        }
        flush(EOS);
        return launches_made - q0;
    }
};

// ---------------------------------------------------------------------------
// Blocking MPSC channel — the FastFlow-queue analog for the threaded engine
// (runtime/engine.py's Inbox).  Carries (src_slot, payload_slot) int pairs;
// the Python side keeps the actual batch objects in a side table keyed by
// payload_slot, so no Python object crosses the ABI.  Blocking push/pop run
// with the GIL released (ctypes), replacing the 50 ms polling loops of the
// queue.Queue fallback with futex waits.  close() is the failure path: it
// wakes everyone; pushes fail immediately, pops drain what is left first.
// ---------------------------------------------------------------------------

struct NativeQueue {
    std::vector<std::pair<i64, i64>> buf;
    size_t cap, head = 0, count = 0;
    std::mutex mu;
    std::condition_variable cv_space, cv_items;
    bool closed = false;
    int waiters = 0;   // threads inside push/pop; free() spins on 0

    explicit NativeQueue(size_t c) : buf(c), cap(c) {}

    int push(i64 src, i64 slot) {
        std::unique_lock<std::mutex> lk(mu);
        ++waiters;
        cv_space.wait(lk, [&] { return count < cap || closed; });
        --waiters;
        if (closed) return -1;
        buf[(head + count) % cap] = {src, slot};
        ++count;
        cv_items.notify_one();
        return 0;
    }

    int pop(i64 *src, i64 *slot) {
        std::unique_lock<std::mutex> lk(mu);
        ++waiters;
        cv_items.wait(lk, [&] { return count > 0 || closed; });
        --waiters;
        if (count == 0) return -1;  // closed and drained
        auto &e = buf[head];
        *src = e.first;
        *slot = e.second;
        head = (head + 1) % cap;
        --count;
        cv_space.notify_one();
        return 0;
    }

    // Overload-policy entry points (runtime/overload.py): non-blocking and
    // deadline-bounded variants.  Return codes: 0 = done, 1 = would block
    // (full / empty / deadline expired), -1 = closed.

    int try_push(i64 src, i64 slot) {
        std::lock_guard<std::mutex> lk(mu);
        if (closed) return -1;
        if (count >= cap) return 1;
        buf[(head + count) % cap] = {src, slot};
        ++count;
        cv_items.notify_one();
        return 0;
    }

    int push_timed(i64 src, i64 slot, i64 timeout_ms) {
        std::unique_lock<std::mutex> lk(mu);
        ++waiters;
        bool ready = cv_space.wait_for(
            lk, std::chrono::milliseconds(timeout_ms),
            [&] { return count < cap || closed; });
        --waiters;
        if (closed) return -1;
        if (!ready) return 1;
        buf[(head + count) % cap] = {src, slot};
        ++count;
        cv_items.notify_one();
        return 0;
    }

    int try_pop(i64 *src, i64 *slot) {
        std::lock_guard<std::mutex> lk(mu);
        if (count == 0) return closed ? -1 : 1;
        auto &e = buf[head];
        *src = e.first;
        *slot = e.second;
        head = (head + 1) % cap;
        --count;
        cv_space.notify_one();
        return 0;
    }

    void close() {
        std::lock_guard<std::mutex> lk(mu);
        closed = true;
        cv_space.notify_all();
        cv_items.notify_all();
    }

    bool idle() {
        std::lock_guard<std::mutex> lk(mu);
        return waiters == 0;
    }
};

}  // namespace

extern "C" {

void *wf_queue_new(i64 capacity) {
    return new NativeQueue((size_t)(capacity > 0 ? capacity : 1 << 16));
}

void wf_queue_free(void *h) {
    // destroying a mutex/condvar another thread is blocked on is undefined
    // behavior: close() wakes everyone, then spin until the last waiter has
    // left push/pop before deleting
    NativeQueue *q = (NativeQueue *)h;
    q->close();
    while (!q->idle()) std::this_thread::yield();
    delete q;
}

int wf_queue_push(void *h, i64 src, i64 slot) {
    return ((NativeQueue *)h)->push(src, slot);
}

int wf_queue_pop(void *h, i64 *src, i64 *slot) {
    return ((NativeQueue *)h)->pop(src, slot);
}

int wf_queue_try_push(void *h, i64 src, i64 slot) {
    return ((NativeQueue *)h)->try_push(src, slot);
}

int wf_queue_push_timed(void *h, i64 src, i64 slot, i64 timeout_ms) {
    return ((NativeQueue *)h)->push_timed(src, slot, timeout_ms);
}

int wf_queue_try_pop(void *h, i64 *src, i64 *slot) {
    return ((NativeQueue *)h)->try_pop(src, slot);
}

void wf_queue_close(void *h) { ((NativeQueue *)h)->close(); }

void *wf_core_new(i64 win, i64 slide, int win_type, int role,
                  i64 id_outer, i64 n_outer, i64 slide_outer,
                  i64 id_inner, i64 n_inner, i64 slide_inner,
                  i64 map_idx0, i64 map_idx1, i64 result_ts_slide,
                  i64 batch_len, i64 flush_rows, int max_wire) {
    return new Core(win, slide, win_type, role, id_outer, n_outer,
                    slide_outer, id_inner, n_inner, slide_inner, map_idx0,
                    map_idx1, result_ts_slide, batch_len, flush_rows,
                    max_wire);
}

void wf_core_free(void *h) { delete (Core *)h; }

i64 wf_core_process(void *h, const void *base, i64 n, i64 itemsize,
                    i64 o_key, i64 o_id, i64 o_ts, i64 o_marker,
                    i64 o_val) {
    return ((Core *)h)->process((const u8 *)base, n, itemsize, o_key, o_id,
                                o_ts, o_marker, o_val);
}

// rows the key-periodic bulk path (process_fast) has taken so far
i64 wf_core_fast_rows(void *h) { return ((Core *)h)->fast_rows; }

// bytes one row takes in its key's archive (8 a column: pos, the shipped
// fields, the carried ones, ts on a count-based arg-extremum alone); fixed
// once the core is configured
i64 wf_core_archive_row_bytes(void *h) {
    return 8 * (i64)((Core *)h)->archive_cols();
}

// single source of truth for the staging bound (Python guards read it)
i64 wf_max_fields(void) { return kMaxFields; }

// Multi-field staging (one device ring per payload column,
// ops/resident.py MultiFieldResidentExecutor): declare the column count
// and each field's widest admissible wire dtype.  Contract: call once,
// right after wf_core_new, before any process call — keys registered
// earlier would lack the extra archive columns.  Returns the accepted
// field count; a caller asking for more than kMaxFields MUST treat the
// short return as a refusal (staging only the prefix would hand the
// device uninitialized rectangles for the missing columns).
i64 wf_core_set_fields(void *h, i64 n_fields, const int *max_wires) {
    Core *c = (Core *)h;
    int nf = (int)(n_fields < 1 ? 1 : n_fields);
    if (nf > kMaxFields) nf = kMaxFields;
    c->n_fields = nf;
    for (int f = 0; f < nf; ++f)
        c->max_wire_f[f] = max_wires ? max_wires[f] : c->max_wire;
    c->max_wire = c->max_wire_f[0];
    return nf;
}

// Arg-extremum mode (ops/functions.py ArgReducer on the native resident
// core).  Same contract as wf_core_set_fields: once, before any process
// call, after wf_core_set_fields.  The core then archives `n_carry` more
// int64 columns per row (offsets follow the shipped fields' in the _f
// entry point; never shipped) and keeps every fired window's rows until
// wf_core_arg_gather has read its winner; a count-based core also archives
// every row's own ts from then on (keep_ts), which the winner's result may
// carry.  `arg_field` is the index among the shipped fields of the one the
// extremum runs over; `window_rows` the rows one key's window is declared
// to hold on this core (0: not declared, the ring grows as the stream
// shows).  Returns the carry count accepted (a short return, or -1 for an
// arg_field that is no shipped field, is a refusal).
i64 wf_core_set_arg(void *h, i64 n_carry, i64 arg_field, i64 window_rows) {
    Core *c = (Core *)h;
    if (arg_field < 0 || arg_field >= c->n_fields) return -1;
    c->window_rows = window_rows > 0 ? window_rows : 0;
    int nc = (int)(n_carry < 0 ? 0 : n_carry);
    if (nc > kMaxCarry) nc = kMaxCarry;
    c->arg_mode = 1;
    c->keep_ts = c->kind == CB;
    c->n_carry = nc;
    c->arg_field = (int)arg_field;
    c->kp_lo = 1;
    return nc;
}

// The winning row of each window of a harvested arg-extremum launch, in
// launch order (node thread only: it reads the archives).  Window i spans
// `hlen[i]` rows from absolute row `habs[i]`; the device found `ext[i]`
// first at relative index `idx[i]`, `nties[i]` times in all.  With ties the
// window's rows at the extremum (the archive column of ship field
// `arg_field`) are scanned for the lowest value in carry column 0 (the
// tie-break id, by the caller's contract).  Writes the row's ts and carry columns
// (out_cols is n_carry x B, column-major by carry), releases the window's
// hold on the archive, and returns how many windows had ties (-1: a row
// was no longer archived).
i64 wf_core_arg_gather(void *h, i64 B, const i64 *hkey, const i64 *habs,
                       const i64 *hlen, const i64 *ext, const int32_t *idx,
                       const int32_t *nties, i64 *out_ts, i64 *out_cols) {
    Core *c = (Core *)h;
    i64 tied = 0;
    bool lost = false;
    for (i64 i = 0; i < B; ++i) {
        KeyState &st = c->state(hkey[i]);
        if (!st.held.empty()) st.held.pop_front();
        out_ts[i] = 0;
        for (int k = 0; k < c->n_carry; ++k) out_cols[k * B + i] = 0;
        if (hlen[i] <= 0) continue;
        const i64 live_start = st.appended - (i64)st.live();
        const i64 j0 = habs[i] - live_start;
        if (j0 < 0 || j0 + hlen[i] > (i64)st.live()) { lost = true; continue; }
        i64 rel = idx[i];
        if (rel < 0 || rel >= hlen[i]) rel = 0;
        size_t j = st.start + (size_t)(j0 + rel);
        if (nties[i] > 1) {
            ++tied;
            if (c->n_carry > 0) {
                const Col &vals =
                    c->arg_field == 0
                        ? st.val : st.xval[(size_t)(c->arg_field - 1)];
                const Col &ids = st.xval[(size_t)(c->n_fields - 1)];
                const size_t lo = st.start + (size_t)j0;
                for (size_t q = lo; q < lo + (size_t)hlen[i]; ++q)
                    if (vals[q] == ext[i] && ids[q] < ids[j]) j = q;
            }
        }
        // (a time-based row's position is its ts)
        out_ts[i] = c->keep_ts ? st.ts[j] : st.pos[j];
        for (int k = 0; k < c->n_carry; ++k)
            out_cols[k * B + i] =
                st.xval[(size_t)(c->n_fields - 1 + k)][j];
    }
    return lost ? -1 : tied;
}

// Persistent shard worker pool: threads park on a condvar between chunks
// instead of being spawned/joined per call (the hot path runs one
// wf_cores_process_mt per engine batch).  Leaked at process exit on
// purpose — destroying parked threads during static teardown is riskier
// than letting process exit reap them.
namespace {

struct ShardPool {
    std::vector<std::thread> threads;
    std::mutex run_mu;  // serializes run() callers: a second engine thread
                        // entering mid-run would overwrite job/counters and
                        // silently drop the first caller's shard tasks
    std::mutex mu;
    std::condition_variable cv_task, cv_done;
    const std::function<void(i64)> *job = nullptr;
    i64 n_tasks = 0, next_task = 0, done = 0;
    unsigned long long gen = 0;

    void ensure(i64 n) {  // call with mu held
        while ((i64)threads.size() < n) {
            threads.emplace_back([this] { worker(); });
        }
    }

    void worker() {
        std::unique_lock<std::mutex> lk(mu);
        unsigned long long seen = 0;
        for (;;) {
            cv_task.wait(lk, [&] { return gen != seen; });
            seen = gen;
            while (next_task < n_tasks) {
                const i64 t = next_task++;
                lk.unlock();
                (*job)(t);
                lk.lock();
                if (++done == n_tasks) cv_done.notify_all();
            }
        }
    }

    void run(i64 n, const std::function<void(i64)> &fn) {
        std::lock_guard<std::mutex> outer(run_mu);
        std::unique_lock<std::mutex> lk(mu);
        ensure(n);
        job = &fn;
        n_tasks = n;
        next_task = 0;
        done = 0;
        ++gen;
        cv_task.notify_all();
        cv_done.wait(lk, [&] { return done == n_tasks; });
        job = nullptr;
    }
};

ShardPool *shard_pool() {
    static ShardPool *p = new ShardPool();  // intentionally never deleted
    return p;
}

}  // namespace

// Key-sharded multithreaded processing: sub-core t consumes keys with
// mix64(key) % n_shards == t.  Two pool phases: (A) striped parallel fill
// of a per-row shard-id byte array (one hash per row TOTAL), then (B)
// every shard processes the chunk, skipping foreign rows with a 1-byte
// compare — instead of each of the S shards paying a hash + integer
// division per row (S*n divisions dominated the r1 profile at 56 ns/row).
// Returns total launches queued.
static i64 cores_process_mt_impl(void **hs, i64 n_shards, const void *base,
                                 i64 n, i64 itemsize, i64 o_key, i64 o_id,
                                 i64 o_ts, i64 o_marker, i64 o_val,
                                 const i64 *o_xval) {
    if (n_shards == 1)
        return ((Core *)hs[0])->process((const u8 *)base, n, itemsize,
                                        o_key, o_id, o_ts, o_marker, o_val,
                                        1, 0, nullptr, o_xval);
    // shared scratch: both phases must run under one lock so a second
    // engine thread cannot overwrite the byte array between them (leaked
    // at exit on purpose, like the pool)
    static std::mutex *mt_mu = new std::mutex();
    static std::vector<u8> *shard_of = new std::vector<u8>();
    std::lock_guard<std::mutex> lk(*mt_mu);
    if ((i64)shard_of->size() < n) shard_of->resize((size_t)n);
    u8 *so = shard_of->data();
    const u8 *b8 = (const u8 *)base;
    const unsigned long long mod = (unsigned long long)n_shards;
    const bool pow2 = (mod & (mod - 1)) == 0;
    const unsigned long long mask = mod - 1;
    const i64 stripes = n_shards;
    std::function<void(i64)> assign = [&](i64 t) {
        const i64 lo = t * n / stripes, hi = (t + 1) * n / stripes;
        for (i64 i = lo; i < hi; ++i) {
            i64 key;
            std::memcpy(&key, b8 + i * itemsize + o_key, 8);
            const unsigned long long h = mix64((unsigned long long)key);
            so[i] = (u8)(pow2 ? (h & mask) : (h % mod));
        }
    };
    shard_pool()->run(stripes, assign);
    std::vector<i64> res((size_t)n_shards, 0);
    std::function<void(i64)> fn = [&](i64 t) {
        res[(size_t)t] = ((Core *)hs[t])->process(
            (const u8 *)base, n, itemsize, o_key, o_id, o_ts, o_marker,
            o_val, n_shards, t, so, o_xval);
    };
    shard_pool()->run(n_shards, fn);
    i64 total = 0;
    for (i64 t = 0; t < n_shards; ++t) total += res[(size_t)t];
    return total;
}

i64 wf_cores_process_mt(void **hs, i64 n_shards, const void *base, i64 n,
                        i64 itemsize, i64 o_key, i64 o_id, i64 o_ts,
                        i64 o_marker, i64 o_val) {
    return cores_process_mt_impl(hs, n_shards, base, n, itemsize, o_key,
                                 o_id, o_ts, o_marker, o_val, nullptr);
}

// multi-field form: o_vals carries n_fields payload-column offsets
i64 wf_cores_process_mt_f(void **hs, i64 n_shards, const void *base, i64 n,
                          i64 itemsize, i64 o_key, i64 o_id, i64 o_ts,
                          i64 o_marker, const i64 *o_vals) {
    return cores_process_mt_impl(hs, n_shards, base, n, itemsize, o_key,
                                 o_id, o_ts, o_marker, o_vals[0],
                                 o_vals + 1);
}

i64 wf_core_eos(void *h) { return ((Core *)h)->eos(); }

// After the end of the stream has been harvested nothing reads the
// archives again: give their memory back now (a long window's archives are
// gigabytes, and the core may outlive its stream by as long as the graph's
// objects do).  Node thread, queue drained.
void wf_core_release(void *h) {
    Core *c = (Core *)h;
    for (auto &st : c->keys) {
        Col().swap(st.pos);
        if (c->keep_ts) Col().swap(st.ts);
        Col().swap(st.val);
        for (auto &xv : st.xval) Col().swap(xv);
        st.start = 0;
        st.held.clear();
        st.hold.reset();
    }
    std::vector<std::vector<i64>>().swap(c->spare_slots);
#if defined(__GLIBC__)
    // (freed to the arena of the thread that grew them is not freed to the
    // system: a worker's arena keeps what its stream peaked at, and the next
    // pipeline's workers grow their own beside it)
    malloc_trim(0);
#endif
}

// --------------------------------------------------------------- renumber
// Per-key dense id renumbering for the ordering layer's single-channel
// TS_RENUMBERING fast path: out[i] = counter[key[i]]++ in one pass, the
// counter table living in the handle so it persists across batches (the
// Python groupby-cumcount needs a stable argsort per batch — measured
// 2026-07-31 at ~6.5M rows/s against this loop's memory-speed pass).
// Small non-negative keys ride a dense vector; anything else the map.
struct Renumber {
    std::vector<i64> dense;
    std::unordered_map<i64, i64> sparse;
};

void *wf_renum_new() { return new Renumber(); }

void wf_renum_free(void *h) { delete (Renumber *)h; }

void wf_renum_run(void *h, const i64 *keys, i64 n, i64 *out) {
    Renumber *r = (Renumber *)h;
    for (i64 i = 0; i < n; ++i) {
        const i64 k = keys[i];
        if (k >= 0 && k < (1 << 20)) {
            if ((i64)r->dense.size() <= k)
                r->dense.resize((size_t)(k + 1), 0);
            out[i] = r->dense[(size_t)k]++;
        } else {
            out[i] = r->sparse[k]++;
        }
    }
}

// counter lookup + post-increment for one key (marker replay at flush:
// the marker row takes the next id exactly like the general path's
// per-key emit_counter)
i64 wf_renum_next(void *h, i64 key) {
    Renumber *r = (Renumber *)h;
    if (key >= 0 && key < (1 << 20)) {
        if ((i64)r->dense.size() <= key)
            r->dense.resize((size_t)(key + 1), 0);
        return r->dense[(size_t)key]++;
    }
    return r->sparse[key]++;
}

// latency-bounded flushing: ship whatever windows/rows are pending even
// though neither batch_len nor flush_rows has been reached (the host core
// calls this when its max-delay timer expires; no-op when nothing pends)
i64 wf_core_force_flush(void *h) {
    Core *c = (Core *)h;
    const i64 q0 = c->launches_made;
    c->flush(FORCED);
    return c->launches_made - q0;
}

// a checkpoint barrier's drain (NativeResidentCore.checkpoint_drain_batches):
// ship what is pending, as wf_core_force_flush does, padded to the last
// natural launch's shape where there is one (Core::flush)
i64 wf_core_barrier_flush(void *h) {
    Core *c = (Core *)h;
    const i64 q0 = c->launches_made;
    c->flush(BARRIER);
    return c->launches_made - q0;
}

// fired windows the core holds that no launch carries yet (read-only;
// producer thread): without one an early flush buys no latency
i64 wf_core_fired_pending(void *h) {
    return (i64)((Core *)h)->hkey.size();
}

// device-following flush (NativeResidentCore._flush_early): launch what is
// pending below flush_rows / batch_len, padded to the last natural launch's
// shape (Core::flush).  Returns the launches made, 0 or 1; *rows the rows
// that launch carries
i64 wf_core_flush_early(void *h, i64 *rows) {
    Core *c = (Core *)h;
    const i64 q0 = c->launches_made;
    *rows = c->pend_rows;
    c->flush(EARLY);
    return c->launches_made - q0;
}

i64 wf_launch_pending(void *h) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    return (i64)c->queue.size();
}

// --------------------------------------------------------------- coalescing
// Merge adjacent queued launches into one bigger dispatch.  Each dispatch
// pays one launch service (dispatch -> result ready) that does not shrink
// with its size, so when launches pile up behind a slow service, fusing
// them trades per-dispatch latency for fewer of them — the adaptive form
// of a larger flush_rows.  Regular pairs
// whose window sequences stay arithmetic keep the compressed form; any
// other pair (TB windows, mixed) merges on its explicit descriptors.
// Never across a ring rebase.

static inline i64 rd_elem(const u8 *p, int wire, i64 i) {
    switch (wire) {
        case 0: return ((const int8_t *)p)[i];
        case 1: return ((const int16_t *)p)[i];
        case 2: return ((const int32_t *)p)[i];
        default: return ((const i64 *)p)[i];
    }
}

static inline void wr_elem(u8 *p, int wire, i64 i, i64 v) {
    switch (wire) {
        case 0: ((int8_t *)p)[i] = (int8_t)v; break;
        case 1: ((int16_t *)p)[i] = (int16_t)v; break;
        case 2: ((int32_t *)p)[i] = (int32_t)v; break;
        default: ((i64 *)p)[i] = v; break;
    }
}

// merge B into A (A dispatched first; B's rows append right after A's in
// ring order).  When both launches carry regular descriptors and B's
// window sequences continue A's arithmetic, the merged launch stays
// regular; otherwise it falls back to the explicit per-window descriptors
// both launches always carry (wstarts/wlens are RING coordinates, valid
// verbatim after the merge — so TB and mixed launches coalesce too).
// Returns false — leaving both untouched — when the pair is incompatible.
static bool try_merge(Launch &A, Launch &B, i64 slide, i64 max_cells,
                      i64 max_mult, int n_fields) {
    if (A.xblk.size() != B.xblk.size()) return false;
    // never across a ring rebase, in either role: a rebase launch resets
    // the ring geometry, and the invariant is simplest (and testable) when
    // rebases are dispatch barriers (ADVICE r2: A.rebase was previously
    // admitted as a merge target — sound in the cases exercised, but
    // asymmetric with this documented rule)
    if (A.rebase || B.rebase) return false;
    if (A.KP != B.KP || A.cap != B.cap) return false;
    // buddy rule: only equal-multiplicity launches merge, so merged sizes
    // stay at power-of-2 multiples of flush_rows and the device sees a
    // SMALL, warmup-coverable set of shape buckets (a free-form merge
    // produces odd multiplicities whose first dispatch compiles cold,
    // mid-run, wrecking the run that hits it).
    // `max_mult` is the caller's adaptive depth cap (launch service time
    // driven, <= kCoalesceLadderMax: the ring is provisioned for that);
    // one dispatch then carries <= max_mult launches' worth of work.  (A cell
    // budget relative to flush_rows would silently disable merging
    // whenever the padded K*bucket(R) rectangle dwarfs the row count —
    // many keys, or one hot key — so the area guard below is absolute
    // instead.)
    if (max_mult > kCoalesceLadderMax) max_mult = kCoalesceLadderMax;
    if (A.mult != B.mult || A.mult * 2 > max_mult) return false;
    // only LIKE pairs merge: two regular launches stay compressed, two
    // irregular launches concatenate explicit descriptors (their base
    // plain-step shapes are in the compile cache, so the merged diagonal
    // sibling is prewarmed).  A mixed pair — or a regular pair whose
    // window sequences broke continuity — would have to dispatch an
    // irregular shape that NO prior launch compiled, handing the run the
    // very cold mid-stall compile coalescing exists to avoid: reject.
    if (A.regular != B.regular) return false;
    const bool regular = A.regular != 0;
    const i64 K2 = std::max(A.K, B.K);
    // per-key row continuity (B's rows must land right after A's in the
    // ring for B's descriptors to stay valid — true by construction for
    // adjacent flushes, verified here), regularity continuity, width
    i64 newR = 1, maxoff = 0, cmaxA = 0, cmaxB = 0, cmaxM = 0;
    for (i64 k = 0; k < K2; ++k) {
        const i64 ra = k < A.K ? A.rows[(size_t)k] : 0;
        const i64 rb = k < B.K ? B.rows[(size_t)k] : 0;
        if (k < A.K && k < B.K
            && B.offs[(size_t)k] != A.offs[(size_t)k] + ra)
            return false;
        if (regular) {
            const i64 ca = k < A.K ? A.rcount[(size_t)k] : 0;
            const i64 cb = k < B.K ? B.rcount[(size_t)k] : 0;
            if (ca && cb
                && (B.rlen[(size_t)k] != A.rlen[(size_t)k]
                    || B.rstart0[(size_t)k]
                           != A.rstart0[(size_t)k] + (int32_t)(ca * slide)))
                return false;
            cmaxA = std::max(cmaxA, ca);
            cmaxB = std::max(cmaxB, cb);
            cmaxM = std::max(cmaxM, ca + cb);
        }
        newR = std::max(newR, ra + rb);
        maxoff = std::max(maxoff,
                          k < A.K ? A.offs[(size_t)k] : B.offs[(size_t)k]);
    }
    if (K2 * bucket(newR) > max_cells) return false;
    // the Python-side overflow guard is offs.max() + bucket(R) <= cap;
    // respect the same conservative bound so a merged launch never trips it
    // (an arg-extremum launch pads to at least its reserved width)
    if (maxoff + std::max(bucket(newR), std::max(A.Rb, B.Rb)) > A.cap)
        return false;
    if (regular) {
        // regular dispatch shapes are keyed on (bucket(R), bucket(cmax)).
        // Small per-key window counts can grow the row bucket while the
        // window-count bucket stays clamped — bucket(ca+cb)==bucket(ca)
        // — so merged shapes live on the LOWER TRIANGLE {(Rb*a, C*b),
        // b <= a} of the pair's base shape, which is exactly the set
        // prewarm_regular_ladder compiles (ADVICE r3: the diagonal alone
        // left (2*Rb, C) cold).  Guard the triangle invariant: equal
        // buckets in (both axes), and the window-count bucket may grow at
        // most as fast as the row bucket — a pair whose C bucket would
        // outgrow its R bucket (possible when one launch packs many more
        // windows per row) dispatches a shape no warmup compiled: reject,
        // the pair simply stays unmerged.
        if (bucket(A.R) != bucket(B.R)
            || bucket(std::max<i64>(cmaxA, 1))
                   != bucket(std::max<i64>(cmaxB, 1)))
            return false;
        const i64 rr = bucket(newR) / bucket(A.R);
        const i64 rc = bucket(std::max<i64>(cmaxM, 1))
                       / bucket(std::max<i64>(cmaxA, 1));
        if (rc > rr) return false;
    } else {
        // irregular dispatch shapes are keyed on (bucket(R), bucket(B)):
        // keep merged shapes on the DIAGONAL ladder of the pair's base
        // shape — equal buckets in, proportional buckets out — so the
        // prewarmed {2x..16x} siblings cover every reachable shape and a
        // merge can never manufacture an off-diagonal bucket that
        // compiles cold mid-stall (the exact failure the prewarm exists
        // to prevent).  Rejected pairs simply stay unmerged.
        if (bucket(A.R) != bucket(B.R)
            || bucket(std::max<i64>(A.B, 1)) != bucket(std::max<i64>(B.B, 1)))
            return false;
        const i64 rr = bucket(newR) / bucket(A.R);
        const i64 rb2 = bucket(std::max<i64>(A.B + B.B, 1))
                        / bucket(std::max<i64>(A.B, 1));
        if (rr != rb2) return false;
    }
    // merge every field's rectangle at that field's widened wire dtype
    // (field 0 in blk/wire, extras in xblk/xwire — same geometry)
    std::vector<std::vector<u8>> nblks((size_t)n_fields);
    int nwires[kMaxFields];
    for (int f = 0; f < n_fields; ++f) {
        const std::vector<u8> &Ab = f == 0 ? A.blk : A.xblk[(size_t)(f - 1)];
        const std::vector<u8> &Bb = f == 0 ? B.blk : B.xblk[(size_t)(f - 1)];
        const int wa = f == 0 ? A.wire : A.xwire[f];
        const int wb = f == 0 ? B.wire : B.xwire[f];
        const int wire2 = std::max(wa, wb);
        const i64 isz2 = 1LL << wire2;
        nwires[f] = wire2;
        std::vector<u8> &nblk = nblks[(size_t)f];
        nblk.assign((size_t)(K2 * newR * isz2), 0);
        for (i64 k = 0; k < K2; ++k) {
            const i64 ra = k < A.K ? A.rows[(size_t)k] : 0;
            const i64 rb = k < B.K ? B.rows[(size_t)k] : 0;
            u8 *dst = nblk.data() + (size_t)(k * newR * isz2);
            if (ra) {
                const u8 *src = Ab.data() + (size_t)(k * A.R << wa);
                if (wa == wire2)
                    std::memcpy(dst, src, (size_t)(ra * isz2));
                else
                    for (i64 i = 0; i < ra; ++i)
                        wr_elem(dst, wire2, i, rd_elem(src, wa, i));
            }
            if (rb) {
                const u8 *src = Bb.data() + (size_t)(k * B.R << wb);
                if (wb == wire2)
                    std::memcpy(dst + (size_t)(ra * isz2), src,
                                (size_t)(rb * isz2));
                else
                    for (i64 i = 0; i < rb; ++i)
                        wr_elem(dst, wire2, ra + i, rd_elem(src, wb, i));
            }
        }
    }
    // merged per-key state: offsets are A's (B's new keys keep B's),
    // counts add, window sequences concatenate
    std::vector<i64> noffs((size_t)K2, 0);
    std::vector<int32_t> nrows((size_t)K2, 0), nrc, nrs0, nrl;
    if (regular) {
        nrc.assign((size_t)K2, 0);
        nrs0.assign((size_t)K2, 0);
        nrl.assign((size_t)K2, 0);
    }
    i64 cmax = 0;
    for (i64 k = 0; k < K2; ++k) {
        const i64 ra = k < A.K ? A.rows[(size_t)k] : 0;
        const i64 rb = k < B.K ? B.rows[(size_t)k] : 0;
        noffs[(size_t)k] = k < A.K ? A.offs[(size_t)k] : B.offs[(size_t)k];
        nrows[(size_t)k] = (int32_t)(ra + rb);
        if (regular) {
            const i64 ca = k < A.K ? A.rcount[(size_t)k] : 0;
            const i64 cb = k < B.K ? B.rcount[(size_t)k] : 0;
            nrc[(size_t)k] = (int32_t)(ca + cb);
            nrs0[(size_t)k] = ca ? A.rstart0[(size_t)k]
                                 : (cb ? B.rstart0[(size_t)k] : 0);
            nrl[(size_t)k] = ca ? A.rlen[(size_t)k]
                                : (cb ? B.rlen[(size_t)k] : 0);
            cmax = std::max<i64>(cmax, ca + cb);
        }
    }
    const i64 B1 = A.B, B2 = B.B;
    if (regular) {
        // B's windows index after A's within each key
        A.widx.resize((size_t)(B1 + B2));
        for (i64 i = 0; i < B2; ++i) {
            const i64 r = B.wrows[(size_t)i];
            const i64 base = r < A.K ? A.rcount[(size_t)r] : 0;
            A.widx[(size_t)(B1 + i)] = B.widx[(size_t)i] + (int32_t)base;
        }
    } else {
        A.widx.clear();
    }
    auto cat32 = [](std::vector<int32_t> &a, const std::vector<int32_t> &b) {
        a.insert(a.end(), b.begin(), b.end());
    };
    auto cat64 = [](std::vector<i64> &a, const std::vector<i64> &b) {
        a.insert(a.end(), b.begin(), b.end());
    };
    cat32(A.wrows, B.wrows);
    cat32(A.wstarts, B.wstarts);
    cat32(A.wlens, B.wlens);
    cat64(A.hkey, B.hkey);
    cat64(A.hid, B.hid);
    cat64(A.hts, B.hts);
    cat64(A.hlen, B.hlen);
    cat64(A.hpmax, B.hpmax);
    cat64(A.hpmin, B.hpmin);
    cat64(A.habs, B.habs);
    A.Rb = std::max(A.Rb, B.Rb);
    if (B.has_progress) {   // the later fire's progress covers the earlier
        A.has_progress = 1;
        A.progress_wid = B.progress_wid;
    }
    A.blk = std::move(nblks[0]);
    for (int f = 1; f < n_fields; ++f)
        A.xblk[(size_t)(f - 1)] = std::move(nblks[(size_t)f]);
    for (int f = 0; f < n_fields; ++f) A.xwire[f] = nwires[f];
    A.offs = std::move(noffs);
    A.rows = std::move(nrows);
    A.rcount = std::move(nrc);
    A.rstart0 = std::move(nrs0);
    A.rlen = std::move(nrl);
    A.cmax = cmax;
    A.wire = nwires[0];
    A.K = K2;
    A.R = newR;
    A.B = B1 + B2;
    A.mult *= 2;
    A.regular = regular ? 1 : 0;
    return true;
}

// Fuse adjacent queued launch pairs (buddy scheme) while merged
// rectangles stay under max_cells (K * R cells), up to max_merge merges.
// Consumer-side only (the one ship thread consumes; the producer only
// push_backs), so popping interior pairs is race-free; the heavy merge
// runs outside the queue lock so the producer's flush() never stalls
// behind it.  Returns the number of merges performed.
i64 wf_launch_coalesce(void *h, i64 max_cells, i64 max_merge,
                       i64 max_mult) {
    Core *c = (Core *)h;
    i64 merged = 0;
    size_t i = 0;
    const i64 mcap = std::min<i64>(std::max<i64>(max_mult, 1),
                                   kCoalesceLadderMax);
    while (merged < max_merge) {
        Launch A, B;
        {
            std::lock_guard<std::mutex> lk(c->qmu);
            // find the next adjacent candidate pair at or after i (LIKE
            // pairs only: regular+regular compressed, irregular+irregular
            // on explicit descriptors)
            while (i + 1 < c->queue.size()) {
                Launch &a = c->queue[i], &b = c->queue[i + 1];
                if (!a.rebase && !b.rebase && a.regular == b.regular
                    && a.mult == b.mult && a.mult * 2 <= mcap)
                    break;
                ++i;
            }
            if (i + 1 >= c->queue.size()) break;
            A = std::move(c->queue[i]);
            B = std::move(c->queue[i + 1]);
            c->queue.erase(c->queue.begin() + i, c->queue.begin() + i + 2);
        }
        const bool ok = try_merge(A, B, c->slide, max_cells, mcap,
                                  c->n_fields);
        {
            std::lock_guard<std::mutex> lk(c->qmu);
            if (!ok) {
                c->queue.insert(c->queue.begin() + i, std::move(B));
                c->queue.insert(c->queue.begin() + i, std::move(A));
            } else {
                c->queue.insert(c->queue.begin() + i, std::move(A));
            }
        }
        if (ok) {
            ++merged;
            i = 0;   // the merged launch may now neighbor an equal buddy
        } else {
            ++i;     // this pair can never merge; move on
        }
    }
    return merged;
}

int wf_launch_peek(void *h, i64 *K, i64 *R, i64 *B, int *wire, int *rebase,
                   i64 *KP, i64 *cap) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    if (c->queue.empty()) return 0;
    Launch &L = c->queue.front();
    *K = L.K; *R = L.R; *B = L.B; *wire = L.wire; *rebase = L.rebase;
    *KP = L.KP; *cap = L.cap;
    return 1;
}

// Stream-time firing (fire_on="stream" with a hold-back, core/windows.py):
// once, right after wf_core_new / set_fields, before any process call.
// Time-based sliding or tumbling windows of a plain sequential worker, no
// arg-extremum; returns 0 where the core cannot (the caller refuses).
int wf_core_set_stream(void *h, i64 holdback) {
    Core *c = (Core *)h;
    if (c->kind != TB || c->hopping || c->arg_mode || c->role != SEQ
        || c->n_outer != 1 || c->n_inner != 1 || holdback < 0
        || !c->keys.empty())
        return 0;
    c->stream_mode = 1;
    // (a throughput stream by its nature: the full coalescing ladder's ring
    // room from the first flush, as after a row-triggered one)
    c->room_mult = kCoalesceLadderMax + 2;
    c->holdback = holdback;
    c->lag_max = holdback + c->win;
    c->slot_w = std::max<i64>((holdback + c->win) >> 8, 1);
    return 1;
}

// what a stream-time core counts, cumulative (node thread): rows taken in
// behind their key's newest, rows dropped as late, rows inserted behind
// shipped ones, rows held now and at their peak, fires, releases and the
// nanoseconds inside them, nanoseconds closing windows, the keys met
// (native_core.py _STREAM_STATS names them in this order)
void wf_core_stream_stats(void *h, i64 *out) {
    Core *c = (Core *)h;
    out[0] = c->n_ooo; out[1] = c->n_late; out[2] = c->n_behind;
    out[3] = c->n_held; out[4] = c->n_held_peak; out[5] = c->n_fires;
    out[6] = c->n_merges; out[7] = c->merge_ns; out[8] = c->fire_ns;
    out[9] = (i64)c->keys.size();
}

// whether the front launch ends a fire, and the last window it closed (call
// between peek and take)
int wf_launch_peek_progress(void *h, i64 *wid) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    if (c->queue.empty() || !c->queue.front().has_progress) return 0;
    *wid = c->queue.front().progress_wid;
    return 1;
}

// how the front launch was cut (call between peek and take): its trigger
// (FlushTrigger) and the rectangle width the core reserved ring room for,
// 0 where that is bucket(R) — an arg-extremum launch keeps a steady width,
// an early launch is as wide as the last natural one
int wf_launch_peek_cut(void *h, int *trigger, i64 *Rb) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    if (c->queue.empty()) return 0;
    Launch &L = c->queue.front();
    *trigger = L.trigger;
    *Rb = L.Rb;
    return 1;
}

// arg-extremum extras of the front launch (call between peek and take):
// each window's absolute start row (B values) and, when peek said
// rebase == 2, each ring row's compaction shift (K values)
int wf_launch_peek_arg(void *h, i64 *habs, i64 *shifts) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    if (c->queue.empty()) return 0;
    Launch &L = c->queue.front();
    if (!L.habs.empty())
        std::memcpy(habs, L.habs.data(), L.habs.size() * 8);
    if (!L.shifts.empty())
        std::memcpy(shifts, L.shifts.data(), L.shifts.size() * 8);
    return 1;
}

// live rows of the front launch's rectangle, before padding: the sum of
// its per-key row counts (the padded K*R is what crosses the wire)
i64 wf_launch_live_rows(void *h) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    if (c->queue.empty()) return 0;
    i64 n = 0;
    for (int32_t r : c->queue.front().rows) n += r;
    return n;
}

// regular-descriptor metadata of the front launch (call between peek and
// take): returns 0 when the front launch is irregular
int wf_launch_peek_regular(void *h, i64 *cmax) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    if (c->queue.empty()) return 0;
    Launch &L = c->queue.front();
    if (!L.regular) return 0;
    *cmax = L.cmax;
    return 1;
}

// fills the per-key regular descriptors + per-window index map of the
// front launch (valid only when wf_launch_peek_regular returned 1)
void wf_launch_take_regular(void *h, int32_t *rcount, int32_t *rstart0,
                            int32_t *rlen, int32_t *widx) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    Launch &L = c->queue.front();
    std::memcpy(rcount, L.rcount.data(), (size_t)L.K * 4);
    std::memcpy(rstart0, L.rstart0.data(), (size_t)L.K * 4);
    std::memcpy(rlen, L.rlen.data(), (size_t)L.K * 4);
    if (L.B)
        std::memcpy(widx, L.widx.data(), (size_t)L.B * 4);
}

// one field's rectangle into the caller's buffer (padded when rows_pad>0)
static void take_block(Launch &L, int f, void *blk, i64 rows_pad,
                       i64 cols_pad) {
    const std::vector<u8> &src_v = f == 0 ? L.blk : L.xblk[(size_t)(f - 1)];
    const int wire = f == 0 ? L.wire : L.xwire[f];
    const i64 isz = 1LL << wire;
    if (rows_pad <= 0) {
        std::memcpy(blk, src_v.data(), (size_t)(L.K * L.R * isz));
        return;
    }
    // write straight into the caller's (rows_pad, cols_pad) rectangle,
    // zeroing the padding — saves the ship thread's _pad2 re-copy
    u8 *dst = (u8 *)blk;
    const u8 *src = src_v.data();
    const i64 rowb = L.R * isz, padb = cols_pad * isz;
    for (i64 r = 0; r < L.K; ++r) {
        std::memcpy(dst + r * padb, src + r * rowb, (size_t)rowb);
        std::memset(dst + r * padb + rowb, 0, (size_t)(padb - rowb));
    }
    std::memset(dst + L.K * padb, 0, (size_t)((rows_pad - L.K) * padb));
}

static void take_common(Launch &L, void *blk, i64 rows_pad,
                        i64 cols_pad, i64 *offs, int32_t *wrows,
                        int32_t *wstarts, int32_t *wlens, i64 *hkey,
                        i64 *hid, i64 *hts, i64 *hlen, i64 *hpmax,
                        i64 *hpmin) {
    take_block(L, 0, blk, rows_pad, cols_pad);
    std::memcpy(offs, L.offs.data(), (size_t)L.K * 8);
    if (L.B) {
        std::memcpy(wrows, L.wrows.data(), (size_t)L.B * 4);
        // callers on the regular path pass null: the per-window start/len
        // arrays are replaced by the compressed per-key descriptors
        if (wstarts) std::memcpy(wstarts, L.wstarts.data(), (size_t)L.B * 4);
        if (wlens) std::memcpy(wlens, L.wlens.data(), (size_t)L.B * 4);
        std::memcpy(hkey, L.hkey.data(), (size_t)L.B * 8);
        std::memcpy(hid, L.hid.data(), (size_t)L.B * 8);
        std::memcpy(hts, L.hts.data(), (size_t)L.B * 8);
        std::memcpy(hlen, L.hlen.data(), (size_t)L.B * 8);
        // callers with no host-side position-extremum stats pass null
        if (hpmax) std::memcpy(hpmax, L.hpmax.data(), (size_t)L.B * 8);
        if (hpmin) std::memcpy(hpmin, L.hpmin.data(), (size_t)L.B * 8);
    }
}

static Launch pop_front(Core *c) {
    // move the launch out under the lock; the (potentially multi-MB)
    // copies afterwards must not stall the producer's flush() push
    std::lock_guard<std::mutex> lk(c->qmu);
    Launch L = std::move(c->queue.front());
    c->queue.pop_front();
    return L;
}

void wf_launch_take(void *h, void *blk, i64 *offs, int32_t *wrows,
                    int32_t *wstarts, int32_t *wlens, i64 *hkey, i64 *hid,
                    i64 *hts, i64 *hlen) {
    Core *c = (Core *)h;
    Launch L = pop_front(c);
    take_common(L, blk, 0, 0, offs, wrows, wstarts, wlens,
                hkey, hid, hts, hlen, nullptr, nullptr);
}

// wf_launch_take writing blk into a zero-padded (rows_pad, cols_pad)
// rectangle ready for the device (the ship thread hands it to device_put
// with no further copy)
void wf_launch_take_padded(void *h, void *blk, i64 rows_pad, i64 cols_pad,
                           i64 *offs, int32_t *wrows, int32_t *wstarts,
                           int32_t *wlens, i64 *hkey, i64 *hid, i64 *hts,
                           i64 *hlen, i64 *hpmax, i64 *hpmin) {
    Core *c = (Core *)h;
    Launch L = pop_front(c);
    take_common(L, blk, rows_pad, cols_pad, offs, wrows, wstarts, wlens,
                hkey, hid, hts, hlen, hpmax, hpmin);
}

// per-field wire dtypes of the front launch (size n_fields; call between
// peek and take — the consumer allocates one rectangle per field)
int wf_launch_peek_wires(void *h, int *wires) {
    Core *c = (Core *)h;
    std::lock_guard<std::mutex> lk(c->qmu);
    if (c->queue.empty()) return 0;
    Launch &L = c->queue.front();
    wires[0] = L.wire;
    for (int f = 1; f < c->n_fields; ++f) wires[f] = L.xwire[f];
    return 1;
}

// multi-field wf_launch_take_padded: blks carries n_fields destination
// rectangles (same (rows_pad, cols_pad) geometry, each field's own wire
// dtype as reported by wf_launch_peek_wires)
void wf_launch_take_padded_f(void *h, void **blks, i64 rows_pad,
                             i64 cols_pad, i64 *offs, int32_t *wrows,
                             int32_t *wstarts, int32_t *wlens, i64 *hkey,
                             i64 *hid, i64 *hts, i64 *hlen, i64 *hpmax,
                             i64 *hpmin) {
    Core *c = (Core *)h;
    const int nf = c->n_fields;
    Launch L = pop_front(c);
    take_common(L, blks[0], rows_pad, cols_pad, offs, wrows, wstarts,
                wlens, hkey, hid, hts, hlen, hpmax, hpmin);
    for (int f = 1; f < nf; ++f)
        take_block(L, f, blks[f], rows_pad, cols_pad);
}

// ---------------------------------------------------------------- keymap
// First-appearance key->slot map + ordered-stream scan for the window
// emitters' per-batch bookkeeping (runtime/emitters.py KeyedStreamState,
// semantics of wf_nodes.hpp:104-121's out-of-order drop): one memory-speed
// pass replaces a binary-search slot lookup + stable argsort + segmented
// running max per batch, which together cost ~150 ms per 1M-row batch of
// pure host time on the pipe benchmark.  Layout mirrors Renumber: dense
// vector for small non-negative keys, hash map for the rest.
struct KeyMap {
    std::vector<i64> dense;  // key -> slot+1 (0 = unseen)
    std::unordered_map<i64, i64> sparse;
    i64 n_slots = 0;
};

void *wf_keymap_new() { return new KeyMap(); }
void wf_keymap_free(void *h) { delete (KeyMap *)h; }

// Map keys -> slots, registering unseen keys in first-appearance order
// (the same slot numbering SlotMap produces); returns the total slot
// count after registration so the caller can grow its slot-indexed
// buffers before the scan.
i64 wf_keymap_lookup(void *h, const i64 *keys, i64 n, i64 *slots) {
    KeyMap *m = (KeyMap *)h;
    for (i64 i = 0; i < n; ++i) {
        const i64 k = keys[i];
        i64 *e;
        if (k >= 0 && k < (1 << 20)) {
            if ((i64)m->dense.size() <= k)
                m->dense.resize((size_t)(k + 1), 0);
            e = &m->dense[(size_t)k];
        } else {
            e = &m->sparse[k];
        }
        if (!*e) *e = ++m->n_slots;
        slots[i] = *e - 1;
    }
    return m->n_slots;
}

// In-order scan over (slots, pos): returns 1 when every row's pos is >=
// its slot's running last position (batch-internal predecessors
// included) — the emitter's in-order fast path.  Fills the per-slot
// last-occurrence index for the last-row capture:
//   touched[0..*n_touched) = slots seen in this batch
//   last_idx[s] = index of slot s's LAST row in this batch
// The caller passes last_idx pre-filled with -1 and must reset the
// touched entries afterwards; last_pos is read-only here (on return 0
// the caller runs the general drop path against unchanged state).
i64 wf_keyscan_ordered(const i64 *slots, const i64 *pos, i64 n,
                       const i64 *last_pos, i64 *last_idx,
                       i64 *touched, i64 *n_touched) {
    i64 ok = 1, nt = 0;
    for (i64 i = 0; i < n; ++i) {
        const i64 s = slots[i];
        const i64 li = last_idx[s];
        if (li < 0) {
            touched[nt++] = s;
            if (pos[i] < last_pos[s]) ok = 0;
        } else if (pos[i] < pos[li]) {
            ok = 0;
        }
        last_idx[s] = i;
    }
    *n_touched = nt;
    return ok;
}

// ---------------------------------------------------------------- stream fold
// The fold of a stream-time host core (core/vecinc.py VecStreamCore): the
// rows of a chunk between two window boundaries go into the lanes of their
// key's open windows in ONE pass, where numpy sorted the rows, cut them
// into groups, searched two sorted levels for every group's slot and
// updated the lanes by fancy index (some forty calls a chunk over 100 MB of
// state, a cost per distinct key: PERF.md §5-6, PR 38).  All state stays
// the caller's: numpy arrays handed in by address, so the core is plain
// data and the rest of it (fire, retire, flush, a deep-copy snapshot) works
// on the same arrays.
//
// The index is an open-addressing table of `cap` (a power of two) pairs
// {key, slot}; slot -1 is an empty cell.  Slot numbers come out as the
// numpy fold's: it looked up its SORTED group keys, so the unseen keys of
// one fold are numbered in ascending key order — and a fire's results leave
// in slot order.  Hence: an unseen key takes the next slot as it is met and
// its rows are folded there like any other's; when the stretch is through,
// the slots it added are put into their keys' order (a few thousand lanes
// moved, the rows not looked at again).

namespace {

inline size_t sfold_home(i64 key, int shift) {
    return (size_t)(((unsigned long long)key * 0x9E3779B97F4A7C15ULL)
                    >> shift);
}

inline i64 sfold_load_i64(const char *p) {
    i64 v;
    std::memcpy(&v, p, 8);
    return v;
}

// an integer field of 1, 2, 4 or 8 bytes as int64; `kind` is its size,
// negative where unsigned (what ndarray.astype(int64) gives)
inline i64 sfold_load(const char *p, i64 kind) {
    switch (kind) {
    case 8: case -8: return sfold_load_i64(p);
    case 4: { int32_t v; std::memcpy(&v, p, 4); return v; }
    case -4: { uint32_t v; std::memcpy(&v, p, 4); return v; }
    case 2: { int16_t v; std::memcpy(&v, p, 2); return v; }
    case -2: { uint16_t v; std::memcpy(&v, p, 2); return v; }
    case 1: return *(const signed char *)p;
    default: return *(const unsigned char *)p;
    }
}

// The windows [lo, hi] a row at `ts` lies in that have not fired (none
// where hi < lo: the row is late), and lo's lane.  The divisions are redone
// only when `ts` leaves the stretch on which they are constant (between two
// boundaries: nearly never).
struct SfoldRange {
    i64 L, S, W, fired;
    i64 from = 0, to = 0, lo = 0, hi = 0, lane = 0;
    inline void at(i64 ts) {
        if (ts >= from && ts < to) return;
        hi = fdiv(ts, S);
        const i64 first = fdiv(ts - L, S) + 1;
        from = std::max(hi * S, (first - 1) * S + L);
        to = std::min((hi + 1) * S, first * S + L);
        lo = std::max(first, fired);
        lane = lo % W;
        if (lane < 0) lane += W;
    }
};

const int kSfoldMaxParts = 8;

// The lanes: rows a lane, and the accumulators of the parts that keep one
// (a count is its lane's rows).
struct Sfold {
    i64 W, m = 0;
    i64 *lane_rows;
    i64 op[kSfoldMaxParts], off[kSfoldMaxParts], kind[kSfoldMaxParts];
    i64 *acc[kSfoldMaxParts];

    // desc, per part: op (0 count, 1 sum, 2 min, 3 max), the field's
    // offset in a row, its kind (sfold_load)
    Sfold(i64 W_, i64 *lane_rows_, i64 n_parts, const i64 *desc,
          i64 *const *accs) : W(W_), lane_rows(lane_rows_) {
        for (i64 p = 0; p < n_parts; ++p) {
            if (!desc[3 * p]) continue;
            op[m] = desc[3 * p];
            off[m] = desc[3 * p + 1];
            kind[m] = desc[3 * p + 2];
            acc[m++] = accs[p];
        }
    }

    inline void row(i64 slot, const char *rec, const SfoldRange &r) const {
        i64 v[kSfoldMaxParts];
        for (i64 q = 0; q < m; ++q) v[q] = sfold_load(rec + off[q], kind[q]);
        i64 lane = r.lane;
        const i64 at = slot * W;
        for (i64 w = r.lo; w <= r.hi; ++w) {
            lane_rows[at + lane] += 1;
            for (i64 q = 0; q < m; ++q) {
                i64 &a = acc[q][at + lane];
                switch (op[q]) {
                case 1: a = (i64)((unsigned long long)a
                                  + (unsigned long long)v[q]); break;
                case 2: a = std::min(a, v[q]); break;
                default: a = std::max(a, v[q]);
                }
            }
            if (++lane == W) lane = 0;
        }
    }

    // Slots [n0, n1), numbered as their keys were met, into the order of
    // their keys: keys, lanes and the index's cells.
    void sort_slots(i64 *tab, int shift, size_t mask, i64 *slot_keys,
                    i64 n0, i64 n1) const {
        const i64 k = n1 - n0;
        std::vector<std::pair<i64, i64>> by_key((size_t)k);  // key, old slot
        for (i64 j = 0; j < k; ++j)
            by_key[(size_t)j] = {slot_keys[n0 + j], n0 + j};
        std::sort(by_key.begin(), by_key.end());
        std::vector<i64> moved((size_t)(k * W));
        auto permute = [&](i64 *lanes) {
            for (i64 j = 0; j < k; ++j)
                std::memcpy(&moved[(size_t)(j * W)],
                            lanes + by_key[(size_t)j].second * W,
                            (size_t)W * 8);
            std::memcpy(lanes + n0 * W, moved.data(), (size_t)(k * W) * 8);
        };
        permute(lane_rows);
        for (i64 q = 0; q < m; ++q) permute(acc[q]);
        for (i64 j = 0; j < k; ++j) {
            const i64 key = by_key[(size_t)j].first;
            slot_keys[n0 + j] = key;
            size_t at = sfold_home(key, shift);
            while (tab[2 * at] != key || tab[2 * at + 1] < n0)
                at = (at + 1) & mask;
            tab[2 * at + 1] = n0 + j;
        }
    }
};

}  // namespace

i64 wf_sfold_max_parts(void) { return kSfoldMaxParts; }

// (Re)build the index over the keys of slots 0..n-1; `cap` >= 2n.
void wf_sfold_index(i64 *tab, i64 cap, const i64 *keys, i64 n) {
    std::memset(tab, 0xff, (size_t)cap * 16);
    const int shift = 64 - __builtin_ctzll((unsigned long long)cap);
    const size_t mask = (size_t)cap - 1;
    const i64 ahead = 16;
    for (i64 s = 0; s < n; ++s) {
        if (s + ahead < n)
            __builtin_prefetch(tab + 2 * sfold_home(keys[s + ahead], shift),
                               1);
        size_t at = sfold_home(keys[s], shift);
        while (tab[2 * at + 1] != -1) at = (at + 1) & mask;
        tab[2 * at] = keys[s];
        tab[2 * at + 1] = s;
    }
}

// Fold rows [lo, n) of a structured chunk (`base`, `stride` bytes a row; a
// key and a ts of 8 bytes, a marker of 1) up to the first row, marker or
// not, whose ts has reached `bound`: returns its index, or n.  geom = {L,
// S, W, fired}.  Marker rows are skipped; a row all of whose windows have
// fired is dropped and counted.  `slot_keys`, `lane_rows` (W lanes a slot,
// zero where a slot is new) and each `acc[p]` (the part's identity there)
// have room for `slot_cap` slots, `tab` has `cap` cells.  io: [0] the live
// slots, in and out; [1] the late rows, out; [2] the live slots when this
// stretch began, in; [3] out, 1 where the call stopped short, at the row it
// returns, because that row's key is unseen and there is no slot left for
// it or the index would pass half full: the caller makes room (the index
// rebuilt) and calls again from that row with io[2] as it was -- the
// stretch's new slots are put into key order when its last call ends.
i64 wf_sfold(const char *base, i64 stride, i64 lo, i64 n, i64 off_key,
             i64 off_ts, i64 off_marker, i64 bound, const i64 *geom,
             i64 *tab, i64 cap, i64 *slot_keys, i64 slot_cap,
             i64 *lane_rows, i64 n_parts, const i64 *desc, i64 *const *acc,
             i64 *io) {
    SfoldRange range{geom[0], geom[1], geom[2], geom[3]};
    const Sfold fold(geom[2], lane_rows, n_parts, desc, acc);
    const int shift = 64 - __builtin_ctzll((unsigned long long)cap);
    const size_t mask = (size_t)cap - 1;
    const i64 room = std::min(slot_cap, cap / 2), ahead = 8;
    i64 n1 = io[0], late = 0, cut = n;
    io[3] = 0;
    for (i64 i = lo; i < n; ++i) {
        const char *rec = base + i * stride;
        if (i + ahead < n)
            __builtin_prefetch(tab + 2 * sfold_home(sfold_load_i64(
                rec + ahead * stride + off_key), shift));
        const i64 ts = sfold_load_i64(rec + off_ts);
        if (ts >= bound) {
            cut = i;
            break;
        }
        if (rec[off_marker]) continue;
        range.at(ts);
        if (range.hi < range.lo) {
            ++late;
            continue;
        }
        const i64 key = sfold_load_i64(rec + off_key);
        size_t at = sfold_home(key, shift);
        while (tab[2 * at + 1] != -1 && tab[2 * at] != key)
            at = (at + 1) & mask;
        if (tab[2 * at + 1] == -1) {        // unseen: the next slot
            if (n1 >= room) {
                cut = i;
                io[3] = 1;
                break;
            }
            tab[2 * at] = key;
            tab[2 * at + 1] = n1;
            slot_keys[n1++] = key;
        }
        fold.row(tab[2 * at + 1], rec, range);
    }
    io[0] = n1;
    io[1] = late;
    if (!io[3] && n1 - io[2] > 1
        && !std::is_sorted(slot_keys + io[2], slot_keys + n1))
        fold.sort_slots(tab, shift, mask, slot_keys, io[2], n1);
    return cut;
}

// ---------------------------------------------------------------- state ABI
// Exactly-once checkpoint / keyed-migration support (docs/ROBUSTNESS.md
// "Native state ABI").  Blobs are flat little-endian i64 streams: a tagged
// header (magic, ABI version, config echo) followed by per-key records —
// the archive rows still needed by future windows plus the window/ordering
// counters.  Export REQUIRES a drained core (no pending rows, no pending
// fired windows, empty launch queue): the Python barrier protocol
// force-flushes and drains first, so device ring contents never cross the
// ABI — import zeroes the ring geometry (cap = 0) and the next flush
// rebases, re-shipping every live row from the imported archives exactly
// like the no-ring-snapshot restore path of the Python resident core.
//
// kStateAbiVersion stamps every blob and is exposed via wf_abi_version();
// tests compare it against the source constant to catch a stale .so.
// Version 2: a key's record carries its newest archived row's position and
// ts (tail_pos, tail_ts: what a count-based window's result ts is read
// from) and a `ts` array only where the core keeps the column
// (Core::keep_ts); the config echo names the archive's column count.  A
// version-1 blob (a `ts` array in every record, no tail) is refused with
// -4 like any other version: a durable checkpoint an older library wrote
// does not restore into this one.

static const i64 kStateAbiVersion = 2;
static const i64 kStateMagicCore = 0x57464E5354415445LL;  // "WFNSTATE"
static const i64 kStateMagicKey = 0x57464E534B455931LL;   // "WFNSKEY1"

i64 wf_abi_version(void) { return kStateAbiVersion; }

namespace {

struct StateWr {
    u8 *p;
    const u8 *end;
    bool ok = true;
    void put(i64 v) {
        if (p + 8 > end) { ok = false; return; }
        std::memcpy(p, &v, 8);
        p += 8;
    }
    void put_arr(const i64 *a, size_t n) {
        if (n == 0) return;
        if (p + 8 * n > end) { ok = false; return; }
        std::memcpy(p, a, n * 8);
        p += n * 8;
    }
};

struct StateRd {
    const u8 *p;
    const u8 *end;
    bool ok = true;
    i64 get() {
        if (p + 8 > end) { ok = false; return 0; }
        i64 v;
        std::memcpy(&v, p, 8);
        p += 8;
        return v;
    }
    bool get_arr(i64 *a, size_t n) {
        if (n == 0) return true;
        if (p + 8 * n > end) { ok = false; return false; }
        std::memcpy(a, p, n * 8);
        p += n * 8;
        return true;
    }
};

// export/import precondition: everything the core buffers between the
// append path and the device has been flushed and shipped.  pend_rows == 0
// also implies launched == appended for every key (each append bumps
// pend_rows; only flush() clears it, setting launched = appended).
inline bool core_drained(Core *c) {
    if (c->pend_rows != 0 || !c->wrow.empty()) return false;
    std::lock_guard<std::mutex> lk(c->qmu);
    return c->queue.empty();
}

inline int find_row(Core *c, i64 key) {
    if (key >= 0 && key < (i64)c->direct.size())
        return c->direct[(size_t)key];
    auto it = c->rowmap.find(key);
    return it == c->rowmap.end() ? -1 : it->second;
}

inline i64 key_rec_i64s(const Core *c, const KeyState &st) {
    return 13 + (i64)st.live() * c->archive_cols();
}

void export_key(const Core *c, const KeyState &st, i64 key, StateWr &w) {
    const i64 L = (i64)st.live();
    w.put(key);
    w.put(st.appended);
    w.put(st.last_pos);
    w.put(st.initial_id);
    w.put(st.first_gwid);
    w.put(st.next_lwid);
    w.put(st.n_fired);
    w.put(st.emit_counter);
    w.put(st.marker_pos);
    w.put(st.marker_ts);
    w.put(st.tail_pos);
    w.put(st.tail_ts);
    w.put(L);
    w.put_arr(st.pos.data() + st.start, (size_t)L);
    if (c->keep_ts) w.put_arr(st.ts.data() + st.start, (size_t)L);
    w.put_arr(st.val.data() + st.start, (size_t)L);
    for (int f = 1; f < c->n_cols(); ++f)
        w.put_arr(st.xval[(size_t)(f - 1)].data() + st.start, (size_t)L);
}

bool import_key(Core *c, StateRd &r) {
    const i64 key = r.get();
    const i64 appended = r.get(), last_pos = r.get();
    const i64 initial_id = r.get(), first_gwid = r.get();
    const i64 next_lwid = r.get(), n_fired = r.get();
    const i64 emit_counter = r.get(), marker_pos = r.get();
    const i64 marker_ts = r.get();
    const i64 tail_pos = r.get(), tail_ts = r.get();
    const i64 L = r.get();
    if (!r.ok || L < 0 || appended < L) return false;
    KeyState &st = c->state(key);
    if (!st.neutral && !(st.appended == 0 && st.n_fired == 0
                         && st.last_pos <= NEG_INF))
        return false;   // live state on the importing side: refuse
    st.pos.assign((size_t)L, 0);
    st.val.assign((size_t)L, 0);
    if (!r.get_arr(st.pos.data(), (size_t)L)) return false;
    if (c->keep_ts) {
        st.ts.assign((size_t)L, 0);
        if (!r.get_arr(st.ts.data(), (size_t)L)) return false;
    }
    if (!r.get_arr(st.val.data(), (size_t)L)) return false;
    for (int f = 1; f < c->n_cols(); ++f) {
        auto &xv = st.xval[(size_t)(f - 1)];
        xv.assign((size_t)L, 0);
        if (!r.get_arr(xv.data(), (size_t)L)) return false;
    }
    st.start = 0;
    st.appended = appended;
    st.last_pos = last_pos;
    st.initial_id = initial_id;
    st.first_gwid = first_gwid;
    st.next_lwid = next_lwid;
    st.n_fired = n_fired;
    st.emit_counter = emit_counter;
    st.marker_pos = marker_pos;
    st.marker_ts = marker_ts;
    st.tail_pos = tail_pos;
    st.tail_ts = tail_ts;
    st.purge_pos = NEG_INF;
    st.pend_any = false;
    st.neutral = false;
    // nothing of this key is in any ring (the caller zeroes cap so the
    // next flush rebases and re-ships the live rows)
    st.launched = st.ring_base = appended - L;
    st.next_create = st.initial_id + st.next_lwid * c->slide;
    st.fire_pos = st.initial_id + st.n_fired * c->slide + c->win;
    return true;
}

}  // namespace

// Whole-core blob: header (magic, abi, win, slide, kind, role, archive
// columns, room_mult, launches_made, n_keys) + one record per non-neutral key.
// Size/export return -1 when the core is not drained.
i64 wf_core_state_size(void *h) {
    Core *c = (Core *)h;
    if (!core_drained(c)) return -1;
    i64 n = 10;
    for (auto &st : c->keys)
        if (!st.neutral) n += key_rec_i64s(c, st);
    return n * 8;
}

i64 wf_core_state_export(void *h, void *buf, i64 cap) {
    Core *c = (Core *)h;
    if (!core_drained(c)) return -1;
    StateWr w{(u8 *)buf, (const u8 *)buf + cap};
    w.put(kStateMagicCore);
    w.put(kStateAbiVersion);
    w.put(c->win);
    w.put(c->slide);
    w.put((i64)c->kind);
    w.put((i64)c->role);
    w.put((i64)c->archive_cols());
    w.put(c->room_mult);
    w.put(c->launches_made);
    i64 nk = 0;
    for (auto &st : c->keys)
        if (!st.neutral) ++nk;
    w.put(nk);
    for (size_t r = 0; r < c->keys.size(); ++r) {
        if (c->keys[r].neutral) continue;
        export_key(c, c->keys[r], c->rowkey[r], w);
    }
    if (!w.ok) return -1;
    return (i64)(w.p - (u8 *)buf);
}

// Import requires a FRESH core (same wf_core_new config, no keys, empty
// queue) — restore builds new handles rather than scrubbing live ones.
// Returns 0 on success; negative codes name the refusal (-2 not fresh,
// -3 bad magic, -4 ABI version mismatch, -5 config echo mismatch,
// -6 truncated/invalid records).
i64 wf_core_state_import(void *h, const void *buf, i64 nbytes) {
    Core *c = (Core *)h;
    if (!c->keys.empty() || c->pend_rows != 0) return -2;
    {
        std::lock_guard<std::mutex> lk(c->qmu);
        if (!c->queue.empty()) return -2;
    }
    StateRd r{(const u8 *)buf, (const u8 *)buf + nbytes};
    if (r.get() != kStateMagicCore) return -3;
    if (r.get() != kStateAbiVersion) return -4;
    if (r.get() != c->win || r.get() != c->slide
        || r.get() != (i64)c->kind || r.get() != (i64)c->role
        || r.get() != (i64)c->archive_cols())
        return -5;
    c->room_mult = r.get();
    c->launches_made = r.get();
    const i64 nk = r.get();
    if (!r.ok || nk < 0) return -6;
    for (i64 i = 0; i < nk; ++i)
        if (!import_key(c, r)) return -6;
    // ring geometry resets: the next flush rebases and re-ships every
    // live row from the imported archives (device state never crosses)
    c->KP = 0;
    c->cap = 0;
    return 0;
}

// -- per-key variants (control-plane keyed migration) -----------------------

i64 wf_core_key_count(void *h) {
    Core *c = (Core *)h;
    i64 n = 0;
    for (auto &st : c->keys)
        if (!st.neutral) ++n;
    return n;
}

i64 wf_core_key_list(void *h, i64 *out, i64 cap) {
    Core *c = (Core *)h;
    i64 n = 0;
    for (size_t r = 0; r < c->keys.size(); ++r) {
        if (c->keys[r].neutral) continue;
        if (n < cap) out[n] = c->rowkey[r];
        ++n;
    }
    return n;
}

i64 wf_core_key_state_size(void *h, i64 key) {
    Core *c = (Core *)h;
    if (!core_drained(c)) return -1;
    const int row = find_row(c, key);
    if (row < 0 || c->keys[(size_t)row].neutral) return -2;
    return (3 + key_rec_i64s(c, c->keys[(size_t)row])) * 8;
}

i64 wf_core_key_export(void *h, i64 key, void *buf, i64 cap) {
    Core *c = (Core *)h;
    if (!core_drained(c)) return -1;
    const int row = find_row(c, key);
    if (row < 0 || c->keys[(size_t)row].neutral) return -2;
    StateWr w{(u8 *)buf, (const u8 *)buf + cap};
    w.put(kStateMagicKey);
    w.put(kStateAbiVersion);
    w.put((i64)c->archive_cols());
    export_key(c, c->keys[(size_t)row], key, w);
    if (!w.ok) return -1;
    return (i64)(w.p - (u8 *)buf);
}

// Move semantics for migration: after exporting, the old owner
// neutralizes the key — archives and counters reset to fresh-registration
// values, eos()/export skip it — so the migrated key's windows are never
// emitted twice.  The dense row stays registered (launch descriptors
// index rows by position).
i64 wf_core_key_neutralize(void *h, i64 key) {
    Core *c = (Core *)h;
    if (!core_drained(c)) return -1;
    const int row = find_row(c, key);
    if (row < 0) return -2;
    KeyState &st = c->keys[(size_t)row];
    st.pos.clear();
    if (c->keep_ts) st.ts.clear();
    st.val.clear();
    for (auto &xv : st.xval) xv.clear();
    st.start = 0;
    st.appended = st.launched = st.ring_base = 0;
    st.last_pos = NEG_INF;
    st.next_lwid = st.n_fired = 0;
    st.emit_counter = (c->role == MAP) ? c->map_idx0 : 0;
    st.marker_pos = NEG_INF;
    st.marker_ts = 0;
    st.tail_pos = NEG_INF;
    st.tail_ts = 0;
    st.purge_pos = NEG_INF;
    st.held.clear();
    st.pend_any = false;
    st.next_create = st.initial_id;
    st.fire_pos = st.initial_id + c->win;
    st.neutral = true;
    return 0;
}

i64 wf_core_key_import(void *h, const void *buf, i64 nbytes) {
    Core *c = (Core *)h;
    if (!core_drained(c)) return -1;
    StateRd r{(const u8 *)buf, (const u8 *)buf + nbytes};
    if (r.get() != kStateMagicKey) return -3;
    if (r.get() != kStateAbiVersion) return -4;
    if (r.get() != (i64)c->archive_cols()) return -5;
    if (!r.ok || !import_key(c, r)) return -6;
    // the imported rows are in no ring: force a rebase at the next flush
    c->KP = 0;
    c->cap = 0;
    return 0;
}

}  // extern "C"
