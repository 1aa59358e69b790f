// Native BN254 host library: Montgomery field arithmetic + G1/G2 fixed-base.
//
// The runtime role rapidsnark's x86-asm field library plays in the
// reference (SURVEY.md §2.2): the host-side hot loops — trusted-setup
// query-point generation, witness-side bignum math — run here instead of
// Python bigints (~400x).  The TPU compute path stays JAX/XLA; this is
// the CPU runtime around it.  Exposed as extern "C" for ctypes
// (zkp2p_tpu.native.lib); every entry point is batch-oriented.
//
// Two entry points take the proving thread's Python curve arithmetic
// out of the interpreter, each with its Python function kept as oracle:
// groth16_verify_bn254 (the sample verify's pairing equation, whole) and
// groth16_assemble_bn254 (a proof's blinding and assembly from its five
// MSM accumulators: pi_a, pi_b, pi_c).
//
// Field elements: 4 x 64-bit little-endian limbs, Montgomery form with
// R = 2^256.  unsigned __int128 provides the 64x64->128 multiply.

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <array>
#include <deque>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <utility>
#include <cassert>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <unistd.h>
#include <vector>

// ---------------------------------------------------------------------------
// Env-gated MSM phase profile (ZKP2P_MSM_PROF=1, a registered debug knob):
// per-process accumulated wall ns for the G1 Pippenger phases of the 52-bit
// tier, printed to stderr by zkp2p_msm_prof_dump() (and readable any time via
// the exported counters) so the fill/schedule/reduction balance can be read
// off a real prove instead of modeled (no perf(1) on the driver box).
#include <chrono>
#include <cstdio>
static std::atomic<long long> g_prof_fill_ns(0), g_prof_apply_ns(0),
    g_prof_suffix_ns(0), g_prof_bailfill_ns(0);
static bool msm_prof_enabled() {
  static int v = -1;
  if (v < 0) {
    const char *e = getenv("ZKP2P_MSM_PROF");
    v = (e && e[0] == '1') ? 1 : 0;
  }
  return v == 1;
}
static inline long long prof_now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// ---------------------------------------------------------------------------
// Always-on runtime stats (zkp2p_stats_snapshot / zkp2p_stats_reset): a
// lock-free block of relaxed atomics the Python side reads as one array.
// Unlike the ZKP2P_MSM_PROF counters above (env-gated, stderr-oriented)
// these are ON in every build and every run — the cost budget is one or
// two clock reads per CHUNK/WINDOW/CALL, never per point (the rare
// doubling/cancellation lanes are tallied locally per window and flushed
// with one atomic add), so the measured overhead on the MSM path stays
// under the 2% instrumentation budget.
//
// Slot order is the ABI the ctypes bridge mirrors (native/lib.py
// STATS_FIELDS) — append only, never reorder.
enum StatSlot {
  ST_MSM_G1_CALLS = 0,        // plain G1 Pippenger driver entries
  ST_MSM_G2_CALLS,            // G2 driver entries
  ST_MSM_GLV_CALLS,           // GLV G1 driver entries
  ST_MSM_BATCH_AFFINE_CALLS,  // driver entries with the batch-affine arm on
  ST_MSM_POINTS,              // scalar/point pairs handed to the drivers
  ST_MSM_WALL_NS,             // total wall ns inside the MSM drivers
  ST_MSM_FILL_NS,             // batch-affine bucket fill (incl. apply)
  ST_MSM_APPLY_NS,            // batched affine apply alone
  ST_MSM_SUFFIX_NS,           // window suffix reductions (serial + vector)
  ST_MSM_BAILFILL_NS,         // conflict-bail Jacobian refill
  ST_MSM_WINDOW_LAST,         // window size c of the most recent MSM (gauge)
  ST_MSM_DBL_LANES,           // batch-round P+P doubling lane hits
  ST_MSM_CANCEL_LANES,        // batch-round P+(-P) cancellation hits
  ST_MSM_DEFER_HITS,          // same-chunk bucket conflicts deferred a pass
  ST_POOL_JOBS,               // parallel regions run through the WorkPool
  ST_POOL_TASKS,              // region indices executed by workers
  ST_POOL_WAIT_NS,            // enqueue -> FIRST task claim, summed per job
  ST_POOL_RUN_NS,             // task fn execution ns, summed per task
  ST_POOL_DEPTH_PEAK,         // max queued-region depth observed (gauge)
  ST_POOL_WORKERS,            // current worker-thread count (gauge)
  ST_MSM_MULTI_CALLS,         // multi-column G1 driver entries (plain + GLV)
  ST_MSM_MULTI_COLS,          // scalar columns summed over multi calls
  ST_MSM_MULTI_COLS_LAST,     // S of the most recent multi call (gauge)
  ST_MSM_MULTI_PREP_NS,       // per-column classify/ones/digit prep, summed
  ST_MSM_FIXED_CALLS,         // fixed-base precomputed-table driver entries
  ST_MSM_FIXED_PREP_NS,       // fixed-tier digit recode/scatter, summed
  ST_PRECOMP_BUILD_NS,        // g1_precomp_build wall ns, summed
  ST_PRECOMP_TABLE_BYTES,     // mont256 table bytes built this process, summed
  ST_MATVEC_NS,               // wall ns inside fr_matvec + fr_matvec_seg
  ST_MATVEC_SEG_CALLS,        // segmented-plan matvec driver entries
  ST_NTT_STAGE_NS,            // wall ns inside the vectorized NTT stage pipeline
  ST_MSM_INFLIGHT,            // MSM driver entries currently executing (gauge)
  ST_COUNT
};
static std::atomic<long long> g_stats[ST_COUNT];
static inline void stat_add(int slot, long long v) {
  g_stats[slot].fetch_add(v, std::memory_order_relaxed);
}
static inline void stat_set(int slot, long long v) {
  g_stats[slot].store(v, std::memory_order_relaxed);
}
static inline void stat_max(int slot, long long v) {
  long long cur = g_stats[slot].load(std::memory_order_relaxed);
  while (v > cur &&
         !g_stats[slot].compare_exchange_weak(cur, v, std::memory_order_relaxed)) {
  }
}
// Scoped in-flight gauge: +1 on driver entry, -1 on EVERY exit path
// (RAII covers early returns).  An external sampler reading the stats
// block mid-call can tell "an MSM is executing right now" apart from
// "the wall counters moved between my two reads".
struct InflightStat {
  int slot;
  explicit InflightStat(int s) : slot(s) { stat_add(slot, 1); }
  ~InflightStat() { stat_add(slot, -1); }
};

extern "C" {
int zkp2p_stats_count(void) { return ST_COUNT; }
void zkp2p_stats_snapshot(long long *out) {
  for (int i = 0; i < ST_COUNT; ++i) out[i] = g_stats[i].load(std::memory_order_relaxed);
}
void zkp2p_stats_reset(void) {
  for (int i = 0; i < ST_COUNT; ++i) g_stats[i].store(0, std::memory_order_relaxed);
}
}  // extern "C"

// Batch-affine Pippenger bucket accumulation (ZKP2P_MSM_BATCH_AFFINE,
// default ON; off only on a leading '0', the ZKP2P_NATIVE_IFMA rule).
// Gates the affine-bucket fill tiers of the G1/G2 MSMs — off routes
// every window through the plain mixed-Jacobian fill, which is the
// honest A/B arm for what the shared-inversion affine adds buy.
// Deliberately NOT cached: re-read once per MSM (and per G2 window), so
// a single process can diff both arms (tests monkeypatch the env).
static bool batch_affine_enabled() {
  const char *e = getenv("ZKP2P_MSM_BATCH_AFFINE");
  return !(e && e[0] == '0');
}

// Apply-chain interleave (ZKP2P_MSM_INTERLEAVE, default ON; same '0'
// rule): two levers under one knob, both attacking the chunk apply's
// stalls.  (1) The batched-affine chunk apply splits its blocks into
// TWO independent prefix/suffix chains issued through one register
// schedule (mont52_mul8x2), so the second chain's muls fill the IFMA
// latency bubbles of the first.  (2) The gather/schedule loops issue
// software prefetches down the already-known (bucket, point) index
// streams — the apply's phase profile shows the random-index Aff52
// gathers (DRAM-latency, hardware-prefetch-blind) cost more than the
// mul chains themselves.  Off = the original schedule — the byte-parity
// A/B arm (outputs are canonically folded either way and prefetch never
// changes an architectural value, so neither lever can change a proof
// byte).  Fresh-read per chunk-apply call, like the batch-affine gate
// above.
static bool msm_interleave_enabled() {
  const char *e = getenv("ZKP2P_MSM_INTERLEAVE");
  return !(e && e[0] == '0');
}

// Radix-8 NTT stage fusion (ZKP2P_NTT_RADIX8, default OFF — set '1'
// to arm): the vectorized SoA stage pipeline fuses THREE radix-2
// stages per load/store pass (12 muls / 8 elements — the same mul
// count as the radix-4 arrangement, one memory pass instead of 1.5).
// Measured slightly SLOWER (0.95x at 2^19) on the 1-core IFMA box —
// the extra live registers spill and the muls are throughput-bound, so
// the saved memory pass does not pay there; the knob stays for wider
// hosts.  Off = the radix-4 stage-pair fusion — the byte-parity A/B
// arm (identical butterflies in a different pass grouping).
// Fresh-read per transform.
static bool ntt_radix8_enabled() {
  const char *e = getenv("ZKP2P_NTT_RADIX8");
  return e && e[0] == '1';
}

typedef unsigned __int128 u128;
typedef uint64_t u64;

// ---------------------------------------------------------------------------
// Persistent worker pool.  Every parallel region in this library (the
// Pippenger window sums, the 3-way h_ladder split) used to spawn-and-join
// its own std::thread vector per CALL — ~5 MSMs + 1 ladder per prove, each
// paying thread creation latency and a cold stack/TLB.  The pool spawns
// workers once (lazily, or via zkp2p_pool_init) and keeps them parked on a
// condition variable between regions.  Concurrency semantics are unchanged:
// ZKP2P_NATIVE_THREADS still bounds how many indices run at once (the pool
// grows to the largest n_threads any caller has asked for, never shrinks
// below it), and n_threads <= 1 keeps the exact serial caller-thread path.
//
// The pool is MPMC-safe: multiple Python threads may each submit a region
// (the prover's stage task-graph overlaps independent MSMs), and workers
// drain region index spaces FIFO.  Each region carries a WIDTH cap — at
// most `width` workers join its index space, so a caller's n_threads
// request bounds ITS region even when the pool has grown wider for some
// other caller.  pool_run() must not be called from a pool worker (no
// region in this library nests).
// Set inside worker_loop for the thread's lifetime: parallel regions
// must never be SUBMITTED from a pool worker (run() blocks the caller,
// and a worker blocked on a nested region is a deadlock waiting for the
// pool to shrink).  Helpers that can be reached both from Python threads
// and from pool workers (the NTT stage splitter under the knob-off
// 3-wide ladder) consult this and degrade to the inline serial path.
static thread_local bool g_pool_worker = false;

struct PoolJob {
  std::function<void(long)> fn;
  long n = 0;
  int width = 1;           // max workers on this job (caller's n_threads)
  int active = 0;          // workers currently on it (guarded by pool mu_)
  long long enqueue_ns = 0;  // stats: task wait = claim time - this
  std::atomic<long> next{0};
  std::atomic<long> done{0};
  std::mutex mu;
  std::condition_variable cv;
};

class WorkPool {
 public:
  ~WorkPool() { shutdown(); }

  // Grow to at least n workers (never shrinks: a one-off wide caller
  // leaves capacity parked, which is the point of persistence).
  void ensure(int n) {
    std::lock_guard<std::mutex> life(lifecycle_mu_);
    ensure_inner(n);
  }

  int size() {
    std::lock_guard<std::mutex> lk(mu_);
    return (int)workers_.size();
  }

  // Run fn(0..n-1) on at most `width` workers; blocks until every index
  // completed.  The caller thread does NOT execute indices itself —
  // n_threads keeps its historical meaning (worker count), and a
  // blocked caller is what lets overlapped submissions share one
  // bounded worker set.  lifecycle_mu_ brackets the ensure+enqueue pair
  // so a concurrent shutdown() either drains this job with the old
  // workers or sees it after respawn — never in between (a job enqueued
  // onto a pool mid-join would wait forever).
  void run(long n, std::function<void(long)> fn, int width) {
    if (n <= 0) return;
    auto job = std::make_shared<PoolJob>();
    job->fn = std::move(fn);
    job->n = n;
    job->width = width > 0 ? width : 1;
    job->enqueue_ns = prof_now_ns();
    stat_add(ST_POOL_JOBS, 1);
    {
      std::lock_guard<std::mutex> life(lifecycle_mu_);
      ensure_inner(1);  // a job on an empty pool would wait forever
      std::lock_guard<std::mutex> lk(mu_);
      jobs_.push_back(job);
      stat_max(ST_POOL_DEPTH_PEAK, (long long)jobs_.size());
    }
    cv_.notify_all();
    std::unique_lock<std::mutex> lk(job->mu);
    job->cv.wait(lk, [&] { return job->done.load() >= job->n; });
  }

  // Join all workers (draining queued jobs first).  The pool respawns
  // lazily on the next run()/ensure(), so shutdown is safe mid-process
  // (tests cycle it; services can drop the threads while idle).
  // lifecycle_mu_ serializes against ensure()/run(), closing the race
  // where a worker spawned during the join would exit immediately yet
  // linger in workers_, leaving later jobs waiting on a dead pool.
  void shutdown() {
    std::lock_guard<std::mutex> life(lifecycle_mu_);
    {
      std::lock_guard<std::mutex> lk(mu_);
      stop_ = true;
    }
    cv_.notify_all();
    std::vector<std::thread> ws;
    {
      std::lock_guard<std::mutex> lk(mu_);
      ws.swap(workers_);
    }
    for (auto &t : ws) t.join();
    std::lock_guard<std::mutex> lk(mu_);
    stop_ = false;
  }

 private:
  void ensure_inner(int n) {
    std::lock_guard<std::mutex> lk(mu_);
    while ((int)workers_.size() < n) workers_.emplace_back([this] { worker_loop(); });
    stat_set(ST_POOL_WORKERS, (long long)workers_.size());
  }

  // Under mu_: drop jobs whose index space is fully handed out (their
  // in-flight indices finish on the workers that claimed them; run()
  // waits on the done counter, not queue presence) and return the first
  // job with free indices AND head-room under its width cap.
  std::shared_ptr<PoolJob> runnable_locked() {
    for (auto it = jobs_.begin(); it != jobs_.end();) {
      if ((*it)->next.load() >= (*it)->n) {
        it = jobs_.erase(it);
        continue;
      }
      if ((*it)->active < (*it)->width) return *it;
      ++it;
    }
    return nullptr;
  }

  void worker_loop() {
    g_pool_worker = true;
    for (;;) {
      std::shared_ptr<PoolJob> job;
      {
        std::unique_lock<std::mutex> lk(mu_);
        cv_.wait(lk, [&] { return stop_ || runnable_locked() != nullptr; });
        job = runnable_locked();
        if (!job) return;  // stop_ set and nothing left to join
        ++job->active;
      }
      long i;
      while ((i = job->next.fetch_add(1)) < job->n) {
        long long t0 = prof_now_ns();
        // queueing latency per JOB: enqueue -> first task claim (index 0
        // is the chronologically first fetch_add).  Summing it per TASK
        // would count predecessors' run time as "wait" and fabricate
        // contention on an idle pool.
        if (i == 0) stat_add(ST_POOL_WAIT_NS, t0 - job->enqueue_ns);
        job->fn(i);
        stat_add(ST_POOL_RUN_NS, prof_now_ns() - t0);
        stat_add(ST_POOL_TASKS, 1);
        if (job->done.fetch_add(1) + 1 == job->n) {
          std::lock_guard<std::mutex> jlk(job->mu);
          job->cv.notify_all();
        }
      }
      std::lock_guard<std::mutex> lk(mu_);
      --job->active;  // width slot back (job is exhausted, not re-joined)
    }
  }

  std::mutex mu_;
  std::mutex lifecycle_mu_;  // serializes shutdown vs ensure/enqueue
  std::condition_variable cv_;
  std::vector<std::thread> workers_;
  std::deque<std::shared_ptr<PoolJob>> jobs_;
  bool stop_ = false;
};

static WorkPool &work_pool() {
  static WorkPool pool;  // joined by the static destructor at exit
  return pool;
}

// Split [0, n) into contiguous ranges across the pool and run
// fn(lo, hi) on each, blocking until all complete.  Falls back to one
// inline fn(0, n) when the caller pinned a single thread, the range is
// below `grain` (per-chunk minimum — tiny jobs cost more in pool
// handoff than they save), or the caller IS a pool worker (regions
// never nest — see g_pool_worker).  Used by the NTT stage splitter and
// the segmented matvec, where every range is independent by
// construction.
static void pool_parallel_ranges(long n, long grain, int n_threads,
                                 const std::function<void(long, long)> &fn) {
  if (n <= 0) return;
  long max_chunks = grain > 0 ? (n + grain - 1) / grain : n;
  if (n_threads <= 1 || g_pool_worker || max_chunks <= 1) {
    fn(0, n);
    return;
  }
  // a few chunks per worker smooths uneven ranges without drowning the
  // queue in micro-tasks
  long nchunk = (long)n_threads * 4;
  if (nchunk > max_chunks) nchunk = max_chunks;
  long per = (n + nchunk - 1) / nchunk;
  work_pool().ensure(n_threads);
  work_pool().run(
      nchunk,
      [&](long ci) {
        long lo = ci * per;
        long hi = lo + per < n ? lo + per : n;
        if (lo < hi) fn(lo, hi);
      },
      n_threads);
}

// Pool-parallel NTT stage splitting (ZKP2P_NTT_POOL, default ON; off
// only on a leading '0', the ZKP2P_NATIVE_IFMA rule).  Gates both the
// per-stage butterfly-block fan-out inside the vectorized NTT and the
// fused-ladder pipeline in fr_h_ladder; off restores the 3-wide
// whole-transform split — the honest A/B arm.  Fresh-read per call so
// one process can diff both arms (tests monkeypatch the env).
static bool ntt_pool_enabled() {
  const char *e = getenv("ZKP2P_NTT_POOL");
  return !(e && e[0] == '0');
}

// The env-resolved default worker count (ZKP2P_NATIVE_THREADS, else the
// core count) — the same rule fr_h_ladder applied per call before.
static int pool_default_threads() {
  const char *tenv = getenv("ZKP2P_NATIVE_THREADS");
  int nt = tenv ? atoi(tenv) : (int)std::thread::hardware_concurrency();
  return nt > 0 ? nt : 1;
}

extern "C" {
// Explicit lifecycle (optional — every parallel entry point lazily
// ensures capacity): init pre-spawns n workers (n <= 0 resolves
// ZKP2P_NATIVE_THREADS / core count), shutdown joins them all.
void zkp2p_pool_init(int n_threads) {
  work_pool().ensure(n_threads > 0 ? n_threads : pool_default_threads());
}
void zkp2p_pool_shutdown(void) { work_pool().shutdown(); }
int zkp2p_pool_size(void) { return work_pool().size(); }
}  // extern "C"

// BN254 base field p and scalar field r moduli (little-endian limbs).
static const u64 P[4] = {0x3c208c16d87cfd47ULL, 0x97816a916871ca8dULL,
                         0xb85045b68181585dULL, 0x30644e72e131a029ULL};
static const u64 PINV = 0x87d20782e4866389ULL;  // -p^-1 mod 2^64
// R^2 mod p
static const u64 R2P[4] = {0xf32cfc5b538afa89ULL, 0xb5e71911d44501fbULL,
                           0x47ab1eff0a417ff6ULL, 0x06d89f71cab8351fULL};

struct Fp {
  u64 v[4];
};

static inline bool geq(const u64 a[4], const u64 b[4]) {
  for (int i = 3; i >= 0; --i) {
    if (a[i] != b[i]) return a[i] > b[i];
  }
  return true;
}

static inline void sub_nored(u64 out[4], const u64 a[4], const u64 b[4]) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a[i] - b[i] - borrow;
    out[i] = (u64)d;
    borrow = (d >> 64) & 1;
  }
}

static inline void add_mod(u64 out[4], const u64 a[4], const u64 b[4]) {
  u64 t[5] = {0, 0, 0, 0, 0};
  u128 carry = 0;
  for (int i = 0; i < 4; ++i) {
    u128 s = (u128)a[i] + b[i] + carry;
    t[i] = (u64)s;
    carry = s >> 64;
  }
  t[4] = (u64)carry;
  if (t[4] || geq(t, P)) {
    sub_nored(out, t, P);
  } else {
    memcpy(out, t, 32);
  }
}

static inline void sub_mod(u64 out[4], const u64 a[4], const u64 b[4]) {
  if (geq(a, b)) {
    sub_nored(out, a, b);
  } else {
    u64 t[4];
    sub_nored(t, b, a);
    sub_nored(out, P, t);
  }
}

// CIOS Montgomery multiplication.
static void mont_mul(u64 out[4], const u64 a[4], const u64 b[4]) {
  u64 t[6] = {0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 s = (u128)t[j] + (u128)a[i] * b[j] + carry;
      t[j] = (u64)s;
      carry = s >> 64;
    }
    u128 s = (u128)t[4] + carry;
    t[4] = (u64)s;
    t[5] = (u64)(s >> 64);

    u64 m = t[0] * PINV;
    carry = ((u128)t[0] + (u128)m * P[0]) >> 64;
    for (int j = 1; j < 4; ++j) {
      u128 s2 = (u128)t[j] + (u128)m * P[j] + carry;
      t[j - 1] = (u64)s2;
      carry = s2 >> 64;
    }
    u128 s3 = (u128)t[4] + carry;
    t[3] = (u64)s3;
    t[4] = t[5] + (u64)(s3 >> 64);
  }
  if (t[4] || geq(t, P)) {
    sub_nored(out, t, P);
  } else {
    memcpy(out, t, 32);
  }
}

static inline void mont_sqr(u64 out[4], const u64 a[4]) { mont_mul(out, a, a); }

static const u64 ZERO[4] = {0, 0, 0, 0};

struct G1Jac {
  u64 X[4], Y[4], Z[4];
};
struct G1Aff {
  u64 x[4], y[4];  // Montgomery; (0,0) = infinity
};

static inline bool is_zero4(const u64 a[4]) {
  return !(a[0] | a[1] | a[2] | a[3]);
}

static void jac_double(G1Jac &r, const G1Jac &p) {
  if (is_zero4(p.Z)) {
    r = p;
    return;
  }
  u64 A[4], B[4], C[4], D[4], E[4], F[4], t[4], t2[4];
  mont_sqr(A, p.X);
  mont_sqr(B, p.Y);
  mont_sqr(C, B);
  add_mod(t, p.X, B);
  mont_sqr(t, t);
  sub_mod(t, t, A);
  sub_mod(t, t, C);
  add_mod(D, t, t);
  add_mod(E, A, A);
  add_mod(E, E, A);
  mont_sqr(F, E);
  // X3 = F - 2D
  add_mod(t, D, D);
  sub_mod(r.X, F, t);
  // Y3 = E(D - X3) - 8C
  sub_mod(t, D, r.X);
  mont_mul(t, E, t);
  add_mod(t2, C, C);
  add_mod(t2, t2, t2);
  add_mod(t2, t2, t2);
  u64 y3[4];
  sub_mod(y3, t, t2);
  // Z3 = 2 Y Z
  mont_mul(t, p.Y, p.Z);
  add_mod(r.Z, t, t);
  memcpy(r.Y, y3, 32);
}

// r = p + (x2, y2) affine (Montgomery), standard madd-2007-bl shape.
static void jac_add_mixed(G1Jac &r, const G1Jac &p, const u64 x2[4], const u64 y2[4]) {
  if (is_zero4(x2) && is_zero4(y2)) {
    r = p;
    return;
  }
  if (is_zero4(p.Z)) {
    memcpy(r.X, x2, 32);
    memcpy(r.Y, y2, 32);
    // Z = 1 in Montgomery form = R mod p
    static const u64 ONE_M[4] = {0xd35d438dc58f0d9dULL, 0x0a78eb28f5c70b3dULL,
                                 0x666ea36f7879462cULL, 0x0e0a77c19a07df2fULL};
    memcpy(r.Z, ONE_M, 32);
    return;
  }
  u64 Z1Z1[4], U2[4], S2[4], H[4], HH[4], HHH[4], V[4], Rr[4], t[4];
  mont_sqr(Z1Z1, p.Z);
  mont_mul(U2, x2, Z1Z1);
  mont_mul(t, y2, p.Z);
  mont_mul(S2, t, Z1Z1);
  sub_mod(H, U2, p.X);
  sub_mod(Rr, S2, p.Y);
  if (is_zero4(H)) {
    if (is_zero4(Rr)) {
      jac_double(r, p);
      return;
    }
    memset(&r, 0, sizeof(r));  // infinity
    return;
  }
  mont_sqr(HH, H);
  mont_mul(HHH, H, HH);
  mont_mul(V, p.X, HH);
  // X3 = Rr^2 - HHH - 2V
  mont_sqr(t, Rr);
  sub_mod(t, t, HHH);
  u64 v2[4];
  add_mod(v2, V, V);
  sub_mod(r.X, t, v2);
  // Y3 = Rr (V - X3) - Y1 HHH
  sub_mod(t, V, r.X);
  mont_mul(t, Rr, t);
  u64 t2[4];
  mont_mul(t2, p.Y, HHH);
  sub_mod(r.Y, t, t2);
  // Z3 = Z1 H
  u64 z3[4];
  mont_mul(z3, p.Z, H);
  memcpy(r.Z, z3, 32);
}

// Full Jacobian + Jacobian G1 add (defined with the Pippenger MSM below;
// also the accumulate step of the fixed-base batches).
static void g1_add_jac(G1Jac &acc, const G1Jac &e);

// Fermat inverse via exponentiation (p - 2); only used once per output.
static void mont_inv(u64 out[4], const u64 a[4]) {
  // exponent p-2, big-endian bit scan
  u64 e[4];
  u64 two[4] = {2, 0, 0, 0};
  sub_nored(e, P, two);
  // out = 1 (Montgomery)
  static const u64 ONE_M[4] = {0xd35d438dc58f0d9dULL, 0x0a78eb28f5c70b3dULL,
                               0x666ea36f7879462cULL, 0x0e0a77c19a07df2fULL};
  u64 acc[4];
  memcpy(acc, ONE_M, 32);
  for (int i = 255; i >= 0; --i) {
    mont_sqr(acc, acc);
    if ((e[i / 64] >> (i % 64)) & 1) mont_mul(acc, acc, a);
  }
  memcpy(out, acc, 32);
}

extern "C" {

// Dump + reset the ZKP2P_MSM_PROF counters (ns): fill total (incl. apply),
// batched apply alone, suffix reduction.  No-op zeros when profiling is off.
// Counters are summed across worker threads — on an n_threads > 1 run the
// fill total overstates wall contribution by up to the thread count, so
// phase RATIOS are only comparable single-threaded (the driver box).
void zkp2p_msm_prof_dump(long long out4[4]) {
  out4[0] = g_prof_fill_ns.exchange(0);
  out4[1] = g_prof_apply_ns.exchange(0);
  out4[2] = g_prof_suffix_ns.exchange(0);
  out4[3] = g_prof_bailfill_ns.exchange(0);
}

// std -> Montgomery and back (batch), for the Python bridge.
void fp_to_mont(const u64 *in, u64 *out, int n) {
  for (int i = 0; i < n; ++i) mont_mul(out + 4 * i, in + 4 * i, R2P);
}
void fp_from_mont(const u64 *in, u64 *out, int n) {
  static const u64 ONE[4] = {1, 0, 0, 0};
  for (int i = 0; i < n; ++i) mont_mul(out + 4 * i, in + 4 * i, ONE);
}

// Fixed-base batch scalar-mul over G1.
//   base: affine (x, y) standard form; scalars: 4-limb standard form;
//   out: n affine points, standard form, (0,0) for infinity.
// Window-8 table built per call (n is large in setup, so amortised).
void g1_fixed_base_batch(const u64 *base_xy, const u64 *scalars, int n, u64 *out_xy) {
  // Build table[32][256] affine-in-Jacobian: keep Jacobian to skip inversions.
  // Heap per call: ctypes releases the GIL, so a function-local static
  // would be shared (and corrupted) by concurrent callers (r3 advisor).
  G1Jac(*table)[256] = new G1Jac[32][256];
  u64 bx[4], by[4];
  fp_to_mont(base_xy, bx, 1);
  fp_to_mont(base_xy + 4, by, 1);

  G1Jac wbase;
  memcpy(wbase.X, bx, 32);
  memcpy(wbase.Y, by, 32);
  static const u64 ONE_M[4] = {0xd35d438dc58f0d9dULL, 0x0a78eb28f5c70b3dULL,
                               0x666ea36f7879462cULL, 0x0e0a77c19a07df2fULL};
  memcpy(wbase.Z, ONE_M, 32);

  for (int w = 0; w < 32; ++w) {
    memset(&table[w][0], 0, sizeof(G1Jac));
    // normalize wbase to affine for mixed adds: one inversion per window
    u64 zi[4], zi2[4], zi3[4], ax[4], ay[4];
    mont_inv(zi, wbase.Z);
    mont_sqr(zi2, zi);
    mont_mul(zi3, zi2, zi);
    mont_mul(ax, wbase.X, zi2);
    mont_mul(ay, wbase.Y, zi3);
    for (int d = 1; d < 256; ++d) {
      jac_add_mixed(table[w][d], table[w][d - 1], ax, ay);
    }
    for (int k = 0; k < 8; ++k) jac_double(wbase, wbase);
  }

  for (int i = 0; i < n; ++i) {
    const u64 *s = scalars + 4 * i;
    G1Jac acc;
    memset(&acc, 0, sizeof(acc));
    for (int w = 0; w < 32; ++w) {
      int d = (int)((s[w / 8] >> ((w % 8) * 8)) & 0xff);
      if (!d) continue;
      g1_add_jac(acc, table[w][d]);
    }
    u64 *o = out_xy + 8 * i;
    if (is_zero4(acc.Z)) {
      memset(o, 0, 64);
      continue;
    }
    u64 zi[4], zi2[4], zi3[4], mx[4], my[4];
    mont_inv(zi, acc.Z);
    mont_sqr(zi2, zi);
    mont_mul(zi3, zi2, zi);
    mont_mul(mx, acc.X, zi2);
    mont_mul(my, acc.Y, zi3);
    fp_from_mont(mx, o, 1);
    fp_from_mont(my, o + 4, 1);
  }
  delete[] table;
}

// Self-test hook: c = a*b mod p (standard form in/out).
void fp_mul_std(const u64 *a, const u64 *b, u64 *c) {
  u64 am[4], bm[4], cm[4];
  fp_to_mont(a, am, 1);
  fp_to_mont(b, bm, 1);
  mont_mul(cm, am, bm);
  fp_from_mont(cm, c, 1);
}

}  // extern "C"

// ---------------------------------------------------------------- Fq2 / G2
//
// Fq2 = Fq[u]/(u^2 + 1); G2 is the twist curve over Fq2.  Needed for the
// b2_query of trusted setup (one G2 fixed-base mul per wire — at venmo
// scale that is millions of muls, unreachable for Python bigints).

struct Fp2 {
  u64 c0[4], c1[4];
};

static inline void fp2_add(Fp2 &r, const Fp2 &a, const Fp2 &b) {
  add_mod(r.c0, a.c0, b.c0);
  add_mod(r.c1, a.c1, b.c1);
}
static inline void fp2_sub(Fp2 &r, const Fp2 &a, const Fp2 &b) {
  sub_mod(r.c0, a.c0, b.c0);
  sub_mod(r.c1, a.c1, b.c1);
}
static void fp2_mul(Fp2 &r, const Fp2 &a, const Fp2 &b) {
  // Karatsuba: v0 = a0 b0, v1 = a1 b1; c0 = v0 - v1; c1 = (a0+a1)(b0+b1) - v0 - v1
  u64 v0[4], v1[4], s[4], t[4], u[4];
  mont_mul(v0, a.c0, b.c0);
  mont_mul(v1, a.c1, b.c1);
  add_mod(s, a.c0, a.c1);
  add_mod(t, b.c0, b.c1);
  mont_mul(u, s, t);
  sub_mod(r.c0, v0, v1);
  sub_mod(u, u, v0);
  sub_mod(r.c1, u, v1);
}
static inline void fp2_sqr(Fp2 &r, const Fp2 &a) { fp2_mul(r, a, a); }
static inline bool fp2_is_zero(const Fp2 &a) {
  return is_zero4(a.c0) && is_zero4(a.c1);
}

struct G2Jac {
  Fp2 X, Y, Z;
};

static void g2_double(G2Jac &r, const G2Jac &p) {
  if (fp2_is_zero(p.Z)) {
    r = p;
    return;
  }
  Fp2 A, B, C, D, E, F, t, t2;
  fp2_sqr(A, p.X);
  fp2_sqr(B, p.Y);
  fp2_sqr(C, B);
  fp2_add(t, p.X, B);
  fp2_sqr(t, t);
  fp2_sub(t, t, A);
  fp2_sub(t, t, C);
  fp2_add(D, t, t);
  fp2_add(E, A, A);
  fp2_add(E, E, A);
  fp2_sqr(F, E);
  fp2_add(t, D, D);
  fp2_sub(r.X, F, t);
  fp2_sub(t, D, r.X);
  fp2_mul(t, E, t);
  fp2_add(t2, C, C);
  fp2_add(t2, t2, t2);
  fp2_add(t2, t2, t2);
  Fp2 y3;
  fp2_sub(y3, t, t2);
  fp2_mul(t, p.Y, p.Z);
  fp2_add(r.Z, t, t);
  r.Y = y3;
}

static const u64 ONE_MONT[4] = {0xd35d438dc58f0d9dULL, 0x0a78eb28f5c70b3dULL,
                                0x666ea36f7879462cULL, 0x0e0a77c19a07df2fULL};

static void g2_add_mixed(G2Jac &r, const G2Jac &p, const Fp2 &x2, const Fp2 &y2) {
  if (fp2_is_zero(x2) && fp2_is_zero(y2)) {
    r = p;
    return;
  }
  if (fp2_is_zero(p.Z)) {
    r.X = x2;
    r.Y = y2;
    memcpy(r.Z.c0, ONE_MONT, 32);
    memset(r.Z.c1, 0, 32);
    return;
  }
  Fp2 Z1Z1, U2, S2, H, HH, HHH, V, Rr, t, t2;
  fp2_sqr(Z1Z1, p.Z);
  fp2_mul(U2, x2, Z1Z1);
  fp2_mul(t, y2, p.Z);
  fp2_mul(S2, t, Z1Z1);
  fp2_sub(H, U2, p.X);
  fp2_sub(Rr, S2, p.Y);
  if (fp2_is_zero(H)) {
    if (fp2_is_zero(Rr)) {
      g2_double(r, p);
      return;
    }
    memset(&r, 0, sizeof(r));
    return;
  }
  fp2_sqr(HH, H);
  fp2_mul(HHH, H, HH);
  fp2_mul(V, p.X, HH);
  fp2_sqr(t, Rr);
  fp2_sub(t, t, HHH);
  Fp2 v2;
  fp2_add(v2, V, V);
  fp2_sub(r.X, t, v2);
  fp2_sub(t, V, r.X);
  fp2_mul(t, Rr, t);
  fp2_mul(t2, p.Y, HHH);
  fp2_sub(r.Y, t, t2);
  Fp2 z3;
  fp2_mul(z3, p.Z, H);
  r.Z = z3;
}

static void g2_add(G2Jac &acc, const G2Jac &e) {
  if (fp2_is_zero(e.Z)) return;
  if (fp2_is_zero(acc.Z)) {
    acc = e;
    return;
  }
  Fp2 Z1Z1, Z2Z2, U1, U2, S1, S2, H, Rr, t;
  fp2_sqr(Z1Z1, acc.Z);
  fp2_sqr(Z2Z2, e.Z);
  fp2_mul(U1, acc.X, Z2Z2);
  fp2_mul(U2, e.X, Z1Z1);
  fp2_mul(t, acc.Y, e.Z);
  fp2_mul(S1, t, Z2Z2);
  fp2_mul(t, e.Y, acc.Z);
  fp2_mul(S2, t, Z1Z1);
  fp2_sub(H, U2, U1);
  fp2_sub(Rr, S2, S1);
  if (fp2_is_zero(H)) {
    if (fp2_is_zero(Rr)) {
      G2Jac d;
      g2_double(d, acc);
      acc = d;
      return;
    }
    memset(&acc, 0, sizeof(acc));
    return;
  }
  Fp2 HH, HHH, V, x3, y3, z3, t2, v2;
  fp2_sqr(HH, H);
  fp2_mul(HHH, H, HH);
  fp2_mul(V, U1, HH);
  fp2_sqr(t, Rr);
  fp2_sub(t, t, HHH);
  fp2_add(v2, V, V);
  fp2_sub(x3, t, v2);
  fp2_sub(t, V, x3);
  fp2_mul(t, Rr, t);
  fp2_mul(t2, S1, HHH);
  fp2_sub(y3, t, t2);
  fp2_mul(t, acc.Z, e.Z);
  fp2_mul(z3, t, H);
  acc.X = x3;
  acc.Y = y3;
  acc.Z = z3;
}

static void fp2_inv(Fp2 &r, const Fp2 &a) {
  // (a0 + a1 u)^-1 = (a0 - a1 u) / (a0^2 + a1^2)
  u64 n0[4], n1[4], d[4], di[4];
  mont_sqr(n0, a.c0);
  mont_sqr(n1, a.c1);
  add_mod(d, n0, n1);
  mont_inv(di, d);
  mont_mul(r.c0, a.c0, di);
  u64 neg[4];
  sub_mod(neg, (const u64 *)ZERO, a.c1);
  mont_mul(r.c1, neg, di);
}

extern "C" {

// G1 fixed-base batch, Montgomery-form output, batch-inverted
// normalization (one field inversion for the whole batch instead of one
// per point — the Montgomery trick).  out: n * 8 u64 (x, y) Montgomery;
// (0,0) = infinity.
void g1_fixed_base_batch_mont(const u64 *base_xy, const u64 *scalars, int n, u64 *out_xy) {
  G1Jac(*table)[256] = new G1Jac[32][256];  // heap per call: GIL-free concurrent safety
  u64 bx[4], by[4];
  fp_to_mont(base_xy, bx, 1);
  fp_to_mont(base_xy + 4, by, 1);

  G1Jac wbase;
  memcpy(wbase.X, bx, 32);
  memcpy(wbase.Y, by, 32);
  memcpy(wbase.Z, ONE_MONT, 32);
  for (int w = 0; w < 32; ++w) {
    memset(&table[w][0], 0, sizeof(G1Jac));
    u64 zi[4], zi2[4], zi3[4], ax[4], ay[4];
    mont_inv(zi, wbase.Z);
    mont_sqr(zi2, zi);
    mont_mul(zi3, zi2, zi);
    mont_mul(ax, wbase.X, zi2);
    mont_mul(ay, wbase.Y, zi3);
    for (int d = 1; d < 256; ++d) jac_add_mixed(table[w][d], table[w][d - 1], ax, ay);
    for (int k = 0; k < 8; ++k) jac_double(wbase, wbase);
  }

  G1Jac *accs = new G1Jac[n];
  for (int i = 0; i < n; ++i) {
    const u64 *s = scalars + 4 * i;
    G1Jac acc;
    memset(&acc, 0, sizeof(acc));
    for (int w = 0; w < 32; ++w) {
      int d = (int)((s[w / 8] >> ((w % 8) * 8)) & 0xff);
      if (!d) continue;
      g1_add_jac(acc, table[w][d]);
    }
    accs[i] = acc;
  }

  // Batch inversion of all Zs (Montgomery trick), skipping infinities.
  u64 *prefix = new u64[4 * (n + 1)];
  memcpy(prefix, ONE_MONT, 32);
  for (int i = 0; i < n; ++i) {
    const u64 *z = accs[i].Z;
    if (is_zero4(z)) {
      memcpy(prefix + 4 * (i + 1), prefix + 4 * i, 32);
    } else {
      mont_mul(prefix + 4 * (i + 1), prefix + 4 * i, z);
    }
  }
  u64 inv_all[4];
  mont_inv(inv_all, prefix + 4 * n);
  for (int i = n - 1; i >= 0; --i) {
    u64 *o = out_xy + 8 * i;
    if (is_zero4(accs[i].Z)) {
      memset(o, 0, 64);
      continue;
    }
    u64 zi[4], zi2[4], zi3[4];
    mont_mul(zi, prefix + 4 * i, inv_all);        // Z_i^-1
    mont_mul(inv_all, inv_all, accs[i].Z);        // strip Z_i
    mont_sqr(zi2, zi);
    mont_mul(zi3, zi2, zi);
    mont_mul(o, accs[i].X, zi2);
    mont_mul(o + 4, accs[i].Y, zi3);
  }
  delete[] prefix;
  delete[] accs;
  delete[] table;
}

// G2 fixed-base batch, Montgomery output.  base: (x.c0, x.c1, y.c0, y.c1)
// standard form (16 u64); out: n * 16 u64 Montgomery; all-zero = infinity.
void g2_fixed_base_batch_mont(const u64 *base, const u64 *scalars, int n, u64 *out) {
  G2Jac(*table)[256] = new G2Jac[32][256];  // heap per call: GIL-free concurrent safety
  Fp2 bx, by;
  fp_to_mont(base, bx.c0, 1);
  fp_to_mont(base + 4, bx.c1, 1);
  fp_to_mont(base + 8, by.c0, 1);
  fp_to_mont(base + 12, by.c1, 1);

  G2Jac wbase;
  wbase.X = bx;
  wbase.Y = by;
  memcpy(wbase.Z.c0, ONE_MONT, 32);
  memset(wbase.Z.c1, 0, 32);
  for (int w = 0; w < 32; ++w) {
    memset(&table[w][0], 0, sizeof(G2Jac));
    Fp2 zi, zi2, zi3, ax, ay;
    fp2_inv(zi, wbase.Z);
    fp2_sqr(zi2, zi);
    fp2_mul(zi3, zi2, zi);
    fp2_mul(ax, wbase.X, zi2);
    fp2_mul(ay, wbase.Y, zi3);
    for (int d = 1; d < 256; ++d) g2_add_mixed(table[w][d], table[w][d - 1], ax, ay);
    G2Jac t;
    for (int k = 0; k < 8; ++k) {
      g2_double(t, wbase);
      wbase = t;
    }
  }

  G2Jac *accs = new G2Jac[n];
  for (int i = 0; i < n; ++i) {
    const u64 *s = scalars + 4 * i;
    G2Jac acc;
    memset(&acc, 0, sizeof(acc));
    for (int w = 0; w < 32; ++w) {
      int d = (int)((s[w / 8] >> ((w % 8) * 8)) & 0xff);
      if (!d) continue;
      g2_add(acc, table[w][d]);
    }
    accs[i] = acc;
  }

  // Batch inversion in Fq2 via prefix products.
  Fp2 *prefix = new Fp2[n + 1];
  memcpy(prefix[0].c0, ONE_MONT, 32);
  memset(prefix[0].c1, 0, 32);
  for (int i = 0; i < n; ++i) {
    if (fp2_is_zero(accs[i].Z)) {
      prefix[i + 1] = prefix[i];
    } else {
      fp2_mul(prefix[i + 1], prefix[i], accs[i].Z);
    }
  }
  Fp2 inv_all;
  fp2_inv(inv_all, prefix[n]);
  for (int i = n - 1; i >= 0; --i) {
    u64 *o = out + 16 * i;
    if (fp2_is_zero(accs[i].Z)) {
      memset(o, 0, 128);
      continue;
    }
    Fp2 zi, zi2, zi3, mx, my, t;
    fp2_mul(zi, prefix[i], inv_all);
    fp2_mul(t, inv_all, accs[i].Z);
    inv_all = t;
    fp2_sqr(zi2, zi);
    fp2_mul(zi3, zi2, zi);
    fp2_mul(mx, accs[i].X, zi2);
    fp2_mul(my, accs[i].Y, zi3);
    memcpy(o, mx.c0, 32);
    memcpy(o + 4, mx.c1, 32);
    memcpy(o + 8, my.c0, 32);
    memcpy(o + 12, my.c1, 32);
  }
  delete[] prefix;
  delete[] accs;
  delete[] table;
}

}  // extern "C"

// ===================================================================
// Fr scalar field + NTT + Pippenger MSM: the native Groth16 prover
// runtime.  This is the rapidsnark-analog of the framework (the
// reference's fastest prover is native C++, dizkus-scripts/
// 6_gen_proof_rapidsnark.sh); the TPU path (prover/groth16_tpu.py) is
// the accelerator backend, this is the portable-CPU one.  Same
// dataflow as prove_tpu: sparse matvec -> iNTT/coset/NTT ladder ->
// variable-base MSMs -> (host) blind+assemble, differentially tested
// against prove_host in tests/test_native_prover.py.
// ===================================================================

// BN254 scalar field r (little-endian limbs) and Montgomery constants.
static const u64 R_MOD[4] = {0x43e1f593f0000001ULL, 0x2833e84879b97091ULL,
                             0xb85045b68181585dULL, 0x30644e72e131a029ULL};
static const u64 RINV = 0xc2e1f593efffffffULL;  // -r^-1 mod 2^64
static const u64 R2R[4] = {0x1bb8e645ae216da7ULL, 0x53fe3ab1e35c59e3ULL,
                           0x8c49833d53bb8085ULL, 0x0216d0b17f4e44a5ULL};
static const u64 ONE_R[4] = {0xac96341c4ffffffbULL, 0x36fc76959f60cd29ULL,
                             0x666ea36f7879462eULL, 0x0e0a77c19a07df2fULL};

static inline void fr_add(u64 out[4], const u64 a[4], const u64 b[4]) {
  u64 t[5];
  u128 carry = 0;
  for (int i = 0; i < 4; ++i) {
    u128 s = (u128)a[i] + b[i] + carry;
    t[i] = (u64)s;
    carry = s >> 64;
  }
  t[4] = (u64)carry;
  if (t[4] || geq(t, R_MOD)) {
    sub_nored(out, t, R_MOD);
  } else {
    memcpy(out, t, 32);
  }
}

static inline void fr_sub(u64 out[4], const u64 a[4], const u64 b[4]) {
  if (geq(a, b)) {
    sub_nored(out, a, b);
  } else {
    u64 t[4];
    sub_nored(t, b, a);
    sub_nored(out, R_MOD, t);
  }
}

// CIOS Montgomery multiplication over r (mirror of mont_mul over p).
static void fr_mul(u64 out[4], const u64 a[4], const u64 b[4]) {
  u64 t[6] = {0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 s = (u128)t[j] + (u128)a[i] * b[j] + carry;
      t[j] = (u64)s;
      carry = s >> 64;
    }
    u128 s = (u128)t[4] + carry;
    t[4] = (u64)s;
    t[5] = (u64)(s >> 64);

    u64 m = t[0] * RINV;
    carry = ((u128)t[0] + (u128)m * R_MOD[0]) >> 64;
    for (int j = 1; j < 4; ++j) {
      u128 s2 = (u128)t[j] + (u128)m * R_MOD[j] + carry;
      t[j - 1] = (u64)s2;
      carry = s2 >> 64;
    }
    u128 s3 = (u128)t[4] + carry;
    t[3] = (u64)s3;
    t[4] = t[5] + (u64)(s3 >> 64);
  }
  if (t[4] || geq(t, R_MOD)) {
    sub_nored(out, t, R_MOD);
  } else {
    memcpy(out, t, 32);
  }
}

// Montgomery exponentiation a^e over r (big-endian bit scan of e).
static void fr_pow(u64 out[4], const u64 a[4], const u64 e[4]) {
  u64 acc[4];
  memcpy(acc, ONE_R, 32);
  for (int i = 255; i >= 0; --i) {
    fr_mul(acc, acc, acc);
    if ((e[i / 64] >> (i % 64)) & 1) fr_mul(acc, acc, a);
  }
  memcpy(out, acc, 32);
}

static void fr_inv_mont(u64 out[4], const u64 a[4]) {
  u64 e[4];
  u64 two[4] = {2, 0, 0, 0};
  sub_nored(e, R_MOD, two);
  fr_pow(out, a, e);
}

// ----------------------------------------------- AVX-512 IFMA field core
//
// 8-wide Montgomery arithmetic in a 5x52-bit limb representation
// (R = 2^260), the layout vpmadd52luq/vpmadd52huq are built for.  This
// is the single-core SIMD answer to rapidsnark's x86-64 asm field layer
// (SURVEY.md §2.2): the driver box exposes exactly one core, so lane
// parallelism is the only parallel axis the native tier has.
//
// Domain bookkeeping ("carrier trick"): a value stored as y = x·2^256
// (the scalar tier's mont256 form) times a constant stored as c·2^260
// (mont260) under mont260 multiplication yields (y·c·2^260)·2^-260 =
// (x·c)·2^256 — i.e. data can stay in the scalar tier's Montgomery form
// through the whole vector pipeline as long as every CONSTANT table
// (twiddles, coset powers) is prepared in mont260 form.  No conversion
// passes over the data, ever.
//
// Lazy reduction: all vector values live in [0, 2p).  mont260 output is
// < p + a·b/2^260 < 2p for inputs < 2p because 4p < 2^260; add/sub
// conditionally fold by 2p.  Full reduction happens only at unpack.

#if defined(__AVX512IFMA__)
#include <immintrin.h>
#define ZKP2P_HAVE_IFMA 1

static const u64 M52 = (1ULL << 52) - 1;

// Per-field constant pack for the 52-bit core (Fr for NTT, Fq later for
// the MSM lambda lanes).
struct Ifma52Field {
  u64 p52[5];      // modulus
  u64 p2_52[5];    // 2p
  u64 comp2p[5];   // 2^260 - 2p  (complement used for the cond-subtract)
  u64 pinv52;      // -p^-1 mod 2^52
  u64 r260sq[5];   // 2^520 mod p (std -> mont260 via one mont260 mul)
  u64 c256[5];     // 2^256 mod p (mont260 -> mont256 carrier)
  u64 c264[5];     // 2^264 mod p (mont256 -> mont260 carrier)
  u64 compp[5];    // 2^260 - p (complement for the canonical fold)
};

static void limbs4_to_52(u64 out[5], const u64 a[4]) {
  out[0] = a[0] & M52;
  out[1] = ((a[0] >> 52) | (a[1] << 12)) & M52;
  out[2] = ((a[1] >> 40) | (a[2] << 24)) & M52;
  out[3] = ((a[2] >> 28) | (a[3] << 36)) & M52;
  out[4] = a[3] >> 16;
}

static void limbs52_to_4(u64 out[4], const u64 t[5]) {
  out[0] = t[0] | (t[1] << 52);
  out[1] = (t[1] >> 12) | (t[2] << 40);
  out[2] = (t[2] >> 24) | (t[3] << 28);
  out[3] = (t[3] >> 36) | (t[4] << 16);
}

// 1-lane 52-limb mont260 multiply (u128 scalar): table building only.
static void mont52_mul_scalar(u64 out[5], const u64 a[5], const u64 b[5],
                              const Ifma52Field &F) {
  u128 t[6] = {0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 5; ++i) {
    u64 bi = b[i];
    for (int j = 0; j < 5; ++j) {
      u128 prod = (u128)a[j] * bi;
      t[j] += (u64)prod & M52;
      t[j + 1] += (u64)(prod >> 52);
    }
    u64 mi = ((u64)t[0] * F.pinv52) & M52;
    for (int j = 0; j < 5; ++j) {
      u128 prod = (u128)mi * F.p52[j];
      t[j] += (u64)prod & M52;
      t[j + 1] += (u64)(prod >> 52);
    }
    t[1] += (u64)(t[0] >> 52);
    for (int j = 0; j < 5; ++j) t[j] = t[j + 1];
    t[5] = 0;
  }
  u64 c = 0;
  for (int j = 0; j < 5; ++j) {
    u128 s = t[j] + c;
    out[j] = (u64)s & M52;
    c = (u64)(s >> 52);
  }
}

// Build the constant pack from 4x64 modulus + -p^-1 mod 2^64.
static void ifma52_init(Ifma52Field &F, const u64 p4[4], u64 pinv64,
                        void (*add_modp)(u64 *, const u64 *, const u64 *)) {
  limbs4_to_52(F.p52, p4);
  F.pinv52 = pinv64 & M52;
  // 2p as a raw 255-bit value (p < 2^254, so the shift cannot overflow)
  u64 two_p[4];
  u64 carry = 0;
  for (int i = 0; i < 4; ++i) {
    two_p[i] = (p4[i] << 1) | carry;
    carry = p4[i] >> 63;
  }
  limbs4_to_52(F.p2_52, two_p);
  // comp2p = 2^260 - 2p = (~2p + 1) over 5x52 limbs (mod 2^260)
  u64 c2 = 1;
  for (int j = 0; j < 5; ++j) {
    u64 s = ((~F.p2_52[j]) & M52) + c2;
    F.comp2p[j] = s & M52;
    c2 = s >> 52;
  }
  // compp = 2^260 - p (canonical fold: subtract p when >= p)
  c2 = 1;
  for (int j = 0; j < 5; ++j) {
    u64 s = ((~F.p52[j]) & M52) + c2;
    F.compp[j] = s & M52;
    c2 = s >> 52;
  }
  // 2^520 mod p by 520 reducing doublings of 1, snapshotting the
  // carrier-conversion constants 2^256 and 2^264 on the way up
  u64 x[4] = {1, 0, 0, 0};
  for (int i = 0; i < 520; ++i) {
    add_modp(x, x, x);
    if (i == 255) limbs4_to_52(F.c256, x);
    if (i == 263) limbs4_to_52(F.c264, x);
  }
  limbs4_to_52(F.r260sq, x);
}

// add thunks with the reducing signature ifma52_init expects
static void fr_add_thunk(u64 *o, const u64 *a, const u64 *b) { fr_add(o, a, b); }
static void fp_add_thunk(u64 *o, const u64 *a, const u64 *b) { add_mod(o, a, b); }

static Ifma52Field &fr52_field() {
  static Ifma52Field F;
  static bool init = false;
  static std::mutex mu;
  std::lock_guard<std::mutex> lk(mu);
  if (!init) {
    ifma52_init(F, R_MOD, RINV, fr_add_thunk);
    init = true;
  }
  return F;
}

static Ifma52Field &fq52_field() {
  static Ifma52Field F;
  static bool init = false;
  static std::mutex mu;
  std::lock_guard<std::mutex> lk(mu);
  if (!init) {
    ifma52_init(F, P, PINV, fp_add_thunk);
    init = true;
  }
  return F;
}

static bool ifma_enabled() {
  // atomic, not a plain int: the first call can come from several pool
  // workers at once (TSan caught the plain-int version racing here).
  // Both racers compute the same value, so relaxed ordering suffices —
  // the atomic only removes the UB, not any needed synchronization.
  static std::atomic<int> cached{-1};
  int v = cached.load(std::memory_order_relaxed);
  if (v < 0) {
    const char *e = getenv("ZKP2P_NATIVE_IFMA");
    bool off = e && e[0] == '0';
    v = (!off && __builtin_cpu_supports("avx512ifma")) ? 1 : 0;
    cached.store(v, std::memory_order_relaxed);
  }
  return v == 1;
}

// ---- vector kernel: out = a*b*2^-260, lanes independent, in/out < 2p.
// Accumulator headroom: each 64-bit lane absorbs <= 4 madd52 terms plus
// one sub-2^12 carry per outer iteration (5 iterations -> < 25·2^52 <
// 2^57), far under 2^64.
static inline void mont52_mul8(__m512i out[5], const __m512i a[5],
                               const __m512i b[5], const __m512i p[5],
                               const __m512i pinv) {
  const __m512i z = _mm512_setzero_si512();
  __m512i t0 = z, t1 = z, t2 = z, t3 = z, t4 = z, t5 = z;
  for (int i = 0; i < 5; ++i) {
    const __m512i bi = b[i];
    t0 = _mm512_madd52lo_epu64(t0, a[0], bi);
    t1 = _mm512_madd52lo_epu64(t1, a[1], bi);
    t2 = _mm512_madd52lo_epu64(t2, a[2], bi);
    t3 = _mm512_madd52lo_epu64(t3, a[3], bi);
    t4 = _mm512_madd52lo_epu64(t4, a[4], bi);
    t1 = _mm512_madd52hi_epu64(t1, a[0], bi);
    t2 = _mm512_madd52hi_epu64(t2, a[1], bi);
    t3 = _mm512_madd52hi_epu64(t3, a[2], bi);
    t4 = _mm512_madd52hi_epu64(t4, a[3], bi);
    t5 = _mm512_madd52hi_epu64(t5, a[4], bi);
    const __m512i mi = _mm512_madd52lo_epu64(z, t0, pinv);
    t0 = _mm512_madd52lo_epu64(t0, mi, p[0]);
    t1 = _mm512_add_epi64(t1, _mm512_srli_epi64(t0, 52));
    t1 = _mm512_madd52lo_epu64(t1, mi, p[1]);
    t2 = _mm512_madd52lo_epu64(t2, mi, p[2]);
    t3 = _mm512_madd52lo_epu64(t3, mi, p[3]);
    t4 = _mm512_madd52lo_epu64(t4, mi, p[4]);
    t1 = _mm512_madd52hi_epu64(t1, mi, p[0]);
    t2 = _mm512_madd52hi_epu64(t2, mi, p[1]);
    t3 = _mm512_madd52hi_epu64(t3, mi, p[2]);
    t4 = _mm512_madd52hi_epu64(t4, mi, p[3]);
    t5 = _mm512_madd52hi_epu64(t5, mi, p[4]);
    t0 = t1; t1 = t2; t2 = t3; t3 = t4; t4 = t5; t5 = z;
  }
  // carry-normalize to 52-bit limbs
  const __m512i m52 = _mm512_set1_epi64((long long)M52);
  __m512i c;
  out[0] = _mm512_and_si512(t0, m52);           c = _mm512_srli_epi64(t0, 52);
  t1 = _mm512_add_epi64(t1, c);
  out[1] = _mm512_and_si512(t1, m52);           c = _mm512_srli_epi64(t1, 52);
  t2 = _mm512_add_epi64(t2, c);
  out[2] = _mm512_and_si512(t2, m52);           c = _mm512_srli_epi64(t2, 52);
  t3 = _mm512_add_epi64(t3, c);
  out[3] = _mm512_and_si512(t3, m52);           c = _mm512_srli_epi64(t3, 52);
  t4 = _mm512_add_epi64(t4, c);
  out[4] = t4;  // < 2^52 (result < 2p < 2^255)
}

// Two INDEPENDENT mont52_mul8 chains issued through one instruction
// schedule.  A single chain is latency-bound: each of the 5 outer
// iterations serializes t0 -> mi -> t0 (madd52lo latency ~4 cycles on
// 1-2 IFMA ports), leaving most multiplier slots idle.  Interleaving a
// second chain with no data dependence on the first fills those slots —
// the out-of-order window sees ~2x the independent madd52 work per
// serial step.  Lane semantics are exactly two mont52_mul8 calls; the
// fusion is purely an instruction-scheduling artifact, so callers can
// regroup chains freely without changing any result bit.
static inline void mont52_mul8x2(__m512i outA[5], const __m512i aA[5],
                                 const __m512i bA[5], __m512i outB[5],
                                 const __m512i aB[5], const __m512i bB[5],
                                 const __m512i p[5], const __m512i pinv) {
  const __m512i z = _mm512_setzero_si512();
  __m512i s0 = z, s1 = z, s2 = z, s3 = z, s4 = z, s5 = z;
  __m512i u0 = z, u1 = z, u2 = z, u3 = z, u4 = z, u5 = z;
  for (int i = 0; i < 5; ++i) {
    const __m512i bi = bA[i], ci = bB[i];
    s0 = _mm512_madd52lo_epu64(s0, aA[0], bi);
    u0 = _mm512_madd52lo_epu64(u0, aB[0], ci);
    s1 = _mm512_madd52lo_epu64(s1, aA[1], bi);
    u1 = _mm512_madd52lo_epu64(u1, aB[1], ci);
    s2 = _mm512_madd52lo_epu64(s2, aA[2], bi);
    u2 = _mm512_madd52lo_epu64(u2, aB[2], ci);
    s3 = _mm512_madd52lo_epu64(s3, aA[3], bi);
    u3 = _mm512_madd52lo_epu64(u3, aB[3], ci);
    s4 = _mm512_madd52lo_epu64(s4, aA[4], bi);
    u4 = _mm512_madd52lo_epu64(u4, aB[4], ci);
    s1 = _mm512_madd52hi_epu64(s1, aA[0], bi);
    u1 = _mm512_madd52hi_epu64(u1, aB[0], ci);
    s2 = _mm512_madd52hi_epu64(s2, aA[1], bi);
    u2 = _mm512_madd52hi_epu64(u2, aB[1], ci);
    s3 = _mm512_madd52hi_epu64(s3, aA[2], bi);
    u3 = _mm512_madd52hi_epu64(u3, aB[2], ci);
    s4 = _mm512_madd52hi_epu64(s4, aA[3], bi);
    u4 = _mm512_madd52hi_epu64(u4, aB[3], ci);
    s5 = _mm512_madd52hi_epu64(s5, aA[4], bi);
    u5 = _mm512_madd52hi_epu64(u5, aB[4], ci);
    const __m512i mA = _mm512_madd52lo_epu64(z, s0, pinv);
    const __m512i mB = _mm512_madd52lo_epu64(z, u0, pinv);
    s0 = _mm512_madd52lo_epu64(s0, mA, p[0]);
    u0 = _mm512_madd52lo_epu64(u0, mB, p[0]);
    s1 = _mm512_add_epi64(s1, _mm512_srli_epi64(s0, 52));
    u1 = _mm512_add_epi64(u1, _mm512_srli_epi64(u0, 52));
    s1 = _mm512_madd52lo_epu64(s1, mA, p[1]);
    u1 = _mm512_madd52lo_epu64(u1, mB, p[1]);
    s2 = _mm512_madd52lo_epu64(s2, mA, p[2]);
    u2 = _mm512_madd52lo_epu64(u2, mB, p[2]);
    s3 = _mm512_madd52lo_epu64(s3, mA, p[3]);
    u3 = _mm512_madd52lo_epu64(u3, mB, p[3]);
    s4 = _mm512_madd52lo_epu64(s4, mA, p[4]);
    u4 = _mm512_madd52lo_epu64(u4, mB, p[4]);
    s1 = _mm512_madd52hi_epu64(s1, mA, p[0]);
    u1 = _mm512_madd52hi_epu64(u1, mB, p[0]);
    s2 = _mm512_madd52hi_epu64(s2, mA, p[1]);
    u2 = _mm512_madd52hi_epu64(u2, mB, p[1]);
    s3 = _mm512_madd52hi_epu64(s3, mA, p[2]);
    u3 = _mm512_madd52hi_epu64(u3, mB, p[2]);
    s4 = _mm512_madd52hi_epu64(s4, mA, p[3]);
    u4 = _mm512_madd52hi_epu64(u4, mB, p[3]);
    s5 = _mm512_madd52hi_epu64(s5, mA, p[4]);
    u5 = _mm512_madd52hi_epu64(u5, mB, p[4]);
    s0 = s1; s1 = s2; s2 = s3; s3 = s4; s4 = s5; s5 = z;
    u0 = u1; u1 = u2; u2 = u3; u3 = u4; u4 = u5; u5 = z;
  }
  const __m512i m52 = _mm512_set1_epi64((long long)M52);
  __m512i c;
  outA[0] = _mm512_and_si512(s0, m52);          c = _mm512_srli_epi64(s0, 52);
  s1 = _mm512_add_epi64(s1, c);
  outA[1] = _mm512_and_si512(s1, m52);          c = _mm512_srli_epi64(s1, 52);
  s2 = _mm512_add_epi64(s2, c);
  outA[2] = _mm512_and_si512(s2, m52);          c = _mm512_srli_epi64(s2, 52);
  s3 = _mm512_add_epi64(s3, c);
  outA[3] = _mm512_and_si512(s3, m52);          c = _mm512_srli_epi64(s3, 52);
  s4 = _mm512_add_epi64(s4, c);
  outA[4] = s4;
  outB[0] = _mm512_and_si512(u0, m52);          c = _mm512_srli_epi64(u0, 52);
  u1 = _mm512_add_epi64(u1, c);
  outB[1] = _mm512_and_si512(u1, m52);          c = _mm512_srli_epi64(u1, 52);
  u2 = _mm512_add_epi64(u2, c);
  outB[2] = _mm512_and_si512(u2, m52);          c = _mm512_srli_epi64(u2, 52);
  u3 = _mm512_add_epi64(u3, c);
  outB[3] = _mm512_and_si512(u3, m52);          c = _mm512_srli_epi64(u3, 52);
  u4 = _mm512_add_epi64(u4, c);
  outB[4] = u4;
}

// conditional fold by an arbitrary complement (2^260 - M): subtract M
// when v >= M.  Used with comp2p (lazy fold) and compp (canonical fold).
static inline void cond_sub_c8(__m512i v[5], const __m512i comp[5]) {
  const __m512i m52 = _mm512_set1_epi64((long long)M52);
  __m512i u[5], c = _mm512_setzero_si512();
  for (int j = 0; j < 5; ++j) {
    __m512i s = _mm512_add_epi64(_mm512_add_epi64(v[j], comp[j]), c);
    u[j] = _mm512_and_si512(s, m52);
    c = _mm512_srli_epi64(s, 52);
  }
  __mmask8 ge = _mm512_cmpneq_epu64_mask(c, _mm512_setzero_si512());
  for (int j = 0; j < 5; ++j) v[j] = _mm512_mask_blend_epi64(ge, v[j], u[j]);
}

// u' = u + t (mod lazy 2p); limbs of u,t are 52-bit normalized.
static inline void add_lazy8(__m512i out[5], const __m512i u[5],
                             const __m512i t[5], const __m512i comp2p[5]) {
  const __m512i m52 = _mm512_set1_epi64((long long)M52);
  __m512i c = _mm512_setzero_si512();
  for (int j = 0; j < 5; ++j) {
    __m512i s = _mm512_add_epi64(_mm512_add_epi64(u[j], t[j]), c);
    out[j] = _mm512_and_si512(s, m52);
    c = _mm512_srli_epi64(s, 52);
  }
  cond_sub_c8(out, comp2p);
}

// v' = u - t + 2p (mod lazy 2p).
static inline void sub_lazy8(__m512i out[5], const __m512i u[5],
                             const __m512i t[5], const __m512i p2[5],
                             const __m512i comp2p[5]) {
  const __m512i m52 = _mm512_set1_epi64((long long)M52);
  // u + 2p + (~t + 1) over 52-bit limbs, mod 2^260
  __m512i c = _mm512_set1_epi64(1);
  for (int j = 0; j < 5; ++j) {
    __m512i nt = _mm512_andnot_si512(t[j], m52);  // M52 - t[j]
    __m512i s = _mm512_add_epi64(_mm512_add_epi64(u[j], p2[j]),
                                 _mm512_add_epi64(nt, c));
    out[j] = _mm512_and_si512(s, m52);
    c = _mm512_srli_epi64(s, 52);
  }
  cond_sub_c8(out, comp2p);
}

// -------- per-stage twiddle tables (mont260, SoA planes, contiguous j)
//
// For each radix-2 stage len >= 16 the vector path wants tw[j] for
// contiguous j in 0..half-1.  Tables are cached per (m, root) like the
// scalar twiddle cache, same 8-entry cap, shared_ptr for in-flight
// safety.  Layout: stages concatenated, each stage stored as 5 planes
// of `half` u64.
struct IfmaTwiddles {
  std::shared_ptr<u64[]> buf;
  // offsets[s] = start of stage (len = 16 << s) in buf, in u64s
  std::vector<size_t> offsets;
};

static IfmaTwiddles ifma_stage_twiddles(long m, const u64 root_std[4]) {
  static std::mutex mu;
  static std::map<std::array<u64, 5>, IfmaTwiddles> cache;
  std::lock_guard<std::mutex> lk(mu);
  std::array<u64, 5> key = {(u64)m, root_std[0], root_std[1], root_std[2], root_std[3]};
  auto it = cache.find(key);
  if (it != cache.end()) return it->second;

  Ifma52Field &F = fr52_field();
  IfmaTwiddles T;
  size_t total = 0;
  for (long len = 16; len <= m; len <<= 1) total += (size_t)(len >> 1) * 5;
  T.buf = std::shared_ptr<u64[]>(new u64[total]);
  // root in mont260: pack then one mont260 mul by 2^520
  u64 root52[5], root260[5];
  limbs4_to_52(root52, root_std);
  mont52_mul_scalar(root260, root52, F.r260sq, F);
  u64 one260[5];  // 2^260 mod p = mont260(1): 1*2^520*2^-260
  u64 one52[5] = {1, 0, 0, 0, 0};
  mont52_mul_scalar(one260, one52, F.r260sq, F);
  size_t off = 0;
  for (long len = 16; len <= m; len <<= 1) {
    long half = len >> 1;
    // wlen = root^(m/len) in mont260 (square root260 down the chain)
    u64 wlen[5];
    memcpy(wlen, root260, 40);
    for (long s = m / len; s > 1; s >>= 1) mont52_mul_scalar(wlen, wlen, wlen, F);
    T.offsets.push_back(off);
    u64 cur[5];
    memcpy(cur, one260, 40);
    u64 *planes = T.buf.get() + off;
    for (long j = 0; j < half; ++j) {
      for (int k = 0; k < 5; ++k) planes[(size_t)k * half + j] = cur[k];
      mont52_mul_scalar(cur, cur, wlen, F);
    }
    off += (size_t)half * 5;
  }
  while (cache.size() >= 8) cache.erase(cache.begin());
  cache[key] = T;
  return T;
}

// -------- SoA-plane pipeline helpers (shared by fr_ntt_ifma and the
// fused H ladder).  Layout: 5 planes of m u64 (plane k at soa + k*m),
// values in the lazy [0, 2p) 52-limb domain carrying the scalar tier's
// mont256 form (see the domain comment above).  Every helper takes the
// resolved worker count and degrades to the serial inline path through
// pool_parallel_ranges (nt <= 1, tiny m, or a pool-worker caller).

// Direct index bit-reversal (byte-table compose): the parallel permute
// passes can't ride the classic incremental-j walk — each range needs
// its own j, so compute rev(i) outright.  m <= 2^31 here (domains top
// out at 2^26 for the flagship).
struct Rev8Tab {
  unsigned char t[256];
  Rev8Tab() {
    for (int i = 0; i < 256; ++i) {
      int r = 0;
      for (int b = 0; b < 8; ++b) r |= ((i >> b) & 1) << (7 - b);
      t[i] = (unsigned char)r;
    }
  }
};
static const Rev8Tab REV8;
static inline long bitrev_idx(long i, int bits) {
  unsigned v = (unsigned)i;
  unsigned r = ((unsigned)REV8.t[v & 0xff] << 24) |
               ((unsigned)REV8.t[(v >> 8) & 0xff] << 16) |
               ((unsigned)REV8.t[(v >> 16) & 0xff] << 8) |
               (unsigned)REV8.t[(v >> 24) & 0xff];
  return (long)(r >> (32 - bits));
}

// (m, 4) mont256 rows -> SoA planes, BIT-REVERSED on the way in:
// soa[:, i] = pack(data[rev(i)]) — folding the permutation into the
// pack pass (sequential writes, gathered 32-byte row reads) removes the
// standalone swap pass the serial NTT entry used to run.
static void fr_soa_pack_rev(const u64 *data, long m, u64 *soa, int nt) {
  int bits = 0;
  while ((1L << bits) < m) ++bits;
  pool_parallel_ranges(m, 1L << 13, nt, [&](long lo, long hi) {
    for (long i = lo; i < hi; ++i) {
      u64 t[5];
      limbs4_to_52(t, data + 4 * bitrev_idx(i, bits));
      for (int k = 0; k < 5; ++k) soa[(size_t)k * m + i] = t[k];
    }
  });
}

// SoA planes -> (m, 4) mont256 rows with full canonical reduction.
static void fr_soa_unpack(const u64 *soa, long m, u64 *data, int nt) {
  pool_parallel_ranges(m, 1L << 13, nt, [&](long lo, long hi) {
    for (long i = lo; i < hi; ++i) {
      u64 t[5], o[4];
      for (int k = 0; k < 5; ++k) t[k] = soa[(size_t)k * m + i];
      limbs52_to_4(o, t);
      while (geq(o, R_MOD)) sub_nored(o, o, R_MOD);
      memcpy(data + 4 * i, o, 32);
    }
  });
}

// In-place bit-reversal of the SoA planes: the fused ladder re-enters
// the forward stages without unpacking to mont256 between transforms.
// Range-parallel: pair {i, rev(i)} is swapped only by the owner of the
// SMALLER index, and no other task reads either slot during the pass,
// so ranges never conflict.
static void fr_soa_bitrev(u64 *soa, long m, int nt) {
  int bits = 0;
  while ((1L << bits) < m) ++bits;
  pool_parallel_ranges(m, 1L << 14, nt, [&](long lo, long hi) {
    for (long i = lo; i < hi; ++i) {
      long j = bitrev_idx(i, bits);
      if (i < j) {
        for (int k = 0; k < 5; ++k) {
          u64 tmp = soa[(size_t)k * m + i];
          soa[(size_t)k * m + i] = soa[(size_t)k * m + j];
          soa[(size_t)k * m + j] = tmp;
        }
      }
    }
  });
}

// Pointwise vector multiply by a mont260 SoA constant table (the fused
// ladder's coset-shift + deferred-1/m-scale pass): soa[i] *= tbl[i],
// lazy domain preserved (mont260 constants keep the data's mont256
// carrier — the standing rule of this pipeline).
static void fr_soa_mul(u64 *soa, long m, const u64 *tbl, int nt) {
  Ifma52Field &F = fr52_field();
  __m512i p[5];
  for (int k = 0; k < 5; ++k) p[k] = _mm512_set1_epi64((long long)F.p52[k]);
  const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
  pool_parallel_ranges(m / 8, 512, nt, [&](long blo, long bhi) {
    for (long b = blo; b < bhi; ++b) {
      const long i = b * 8;
      __m512i x[5], t[5], o[5];
      for (int k = 0; k < 5; ++k) {
        x[k] = _mm512_loadu_si512(soa + (size_t)k * m + i);
        t[k] = _mm512_loadu_si512(tbl + (size_t)k * m + i);
      }
      mont52_mul8(o, x, t, p, pinv);
      for (int k = 0; k < 5; ++k) _mm512_storeu_si512(soa + (size_t)k * m + i, o[k]);
    }
  });
}

// ALL NTT stages over packed SoA planes (input bit-reversed): len 2/4/8
// in-register (permute + blended add/sub, constant twiddle vectors),
// then the radix-4-fused len>=16 loop.  Each pass's butterfly blocks
// are independent, so every pass fans out across the WorkPool
// (nt-gated) with the pool's run() barrier separating stages — the
// split that lets ONE transform use every core, where the ladder's old
// 3-wide whole-transform split stranded cores at 6 transforms / prove.
static void fr_ntt_soa_stages(u64 *soa, long m, const u64 root_std[4], int nt) {
  long long t_st = prof_now_ns();
  Ifma52Field &F = fr52_field();
  IfmaTwiddles T = ifma_stage_twiddles(m, root_std);
  __m512i p[5], p2[5], comp2p[5];
  for (int k = 0; k < 5; ++k) {
    p[k] = _mm512_set1_epi64((long long)F.p52[k]);
    p2[k] = _mm512_set1_epi64((long long)F.p2_52[k]);
    comp2p[k] = _mm512_set1_epi64((long long)F.comp2p[k]);
  }
  const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);

  // ---- stages len = 2, 4, 8 fully in-register (butterflies never
  // cross a 512-bit vector): permute u/v lanes, one constant-twiddle
  // mont mul (len 2 is mul-free: its only twiddle is 1), blended
  // add/sub.  Twiddle constant vectors repeat per vector:
  //   len 4: [1, w4] x4   len 8: [1, w8, w8^2, w8^3] x2
  {
    u64 one52v[5] = {1, 0, 0, 0, 0}, one260[5];
    mont52_mul_scalar(one260, one52v, F.r260sq, F);
    // root260 = root_std in mont260; w_len = root260^(m/len)
    u64 root52[5], root260[5];
    limbs4_to_52(root52, root_std);
    mont52_mul_scalar(root260, root52, F.r260sq, F);
    auto pow2k = [&](u64 out[5], long e_pow2) {
      // root260^(e_pow2) where e_pow2 is a power of two: squarings
      memcpy(out, root260, 40);
      for (long s = e_pow2; s > 1; s >>= 1) mont52_mul_scalar(out, out, out, F);
    };
    u64 w4[5], w8[5], w8sq[5], w8cu[5];
    pow2k(w4, m / 4);
    pow2k(w8, m / 8);
    mont52_mul_scalar(w8sq, w8, w8, F);
    mont52_mul_scalar(w8cu, w8sq, w8, F);

    const __m512i idx_even = _mm512_set_epi64(6, 6, 4, 4, 2, 2, 0, 0);
    const __m512i idx_odd = _mm512_set_epi64(7, 7, 5, 5, 3, 3, 1, 1);
    const __m512i idx_lo4 = _mm512_set_epi64(5, 4, 5, 4, 1, 0, 1, 0);
    const __m512i idx_hi4 = _mm512_set_epi64(7, 6, 7, 6, 3, 2, 3, 2);
    const __m512i idx_lo8 = _mm512_set_epi64(3, 2, 1, 0, 3, 2, 1, 0);
    const __m512i idx_hi8 = _mm512_set_epi64(7, 6, 5, 4, 7, 6, 5, 4);
    __m512i tw4[5], tw8[5];
    {
      u64 t4[5][8], t8[5][8];
      for (int k = 0; k < 5; ++k) {
        for (int l = 0; l < 8; ++l) {
          t4[k][l] = (l & 1) ? w4[k] : one260[k];
          t8[k][l] = (l & 3) == 0 ? one260[k]
                     : (l & 3) == 1 ? w8[k]
                     : (l & 3) == 2 ? w8sq[k]
                                    : w8cu[k];
        }
        tw4[k] = _mm512_loadu_si512(t4[k]);
        tw8[k] = _mm512_loadu_si512(t8[k]);
      }
    }
    pool_parallel_ranges(m / 8, 256, nt, [&](long blo, long bhi) {
    for (long blk = blo; blk < bhi; ++blk) {
      const long i = blk * 8;
      __m512i x[5];
      for (int k = 0; k < 5; ++k) x[k] = _mm512_loadu_si512(soa + (size_t)k * m + i);
      // stage len=2: pairs (0,1)(2,3)(4,5)(6,7), twiddle 1 (no mul)
      {
        __m512i u[5], v[5], s[5], d[5];
        for (int k = 0; k < 5; ++k) {
          u[k] = _mm512_permutexvar_epi64(idx_even, x[k]);
          v[k] = _mm512_permutexvar_epi64(idx_odd, x[k]);
        }
        add_lazy8(s, u, v, comp2p);
        sub_lazy8(d, u, v, p2, comp2p);
        for (int k = 0; k < 5; ++k) x[k] = _mm512_mask_blend_epi64(0xAA, s[k], d[k]);
      }
      // stage len=4: pairs (0,2)(1,3) per group of 4, twiddles [1, w4]
      {
        __m512i u[5], v[5], t[5], s[5], d[5];
        for (int k = 0; k < 5; ++k) {
          u[k] = _mm512_permutexvar_epi64(idx_lo4, x[k]);
          v[k] = _mm512_permutexvar_epi64(idx_hi4, x[k]);
        }
        mont52_mul8(t, v, tw4, p, pinv);
        add_lazy8(s, u, t, comp2p);
        sub_lazy8(d, u, t, p2, comp2p);
        for (int k = 0; k < 5; ++k) x[k] = _mm512_mask_blend_epi64(0xCC, s[k], d[k]);
      }
      // stage len=8: pairs (l, l+4), twiddles [1, w8, w8^2, w8^3]
      {
        __m512i u[5], v[5], t[5], s[5], d[5];
        for (int k = 0; k < 5; ++k) {
          u[k] = _mm512_permutexvar_epi64(idx_lo8, x[k]);
          v[k] = _mm512_permutexvar_epi64(idx_hi8, x[k]);
        }
        mont52_mul8(t, v, tw8, p, pinv);
        add_lazy8(s, u, t, comp2p);
        sub_lazy8(d, u, t, p2, comp2p);
        for (int k = 0; k < 5; ++k) x[k] = _mm512_mask_blend_epi64(0xF0, s[k], d[k]);
      }
      for (int k = 0; k < 5; ++k) _mm512_storeu_si512(soa + (size_t)k * m + i, x[k]);
    }
    });
  }
  // One radix-2 vector stage (the generic building block, and the odd
  // leading stage when the vector-stage count is odd).  The (block,
  // j-group) butterfly space is flattened so the pool splits within a
  // block too — the last stages have only a handful of blocks.
  auto radix2_stage = [&](long len, int stage) {
    const long half = len >> 1;
    const u64 *twp = T.buf.get() + T.offsets[stage];
    const long jblocks = half >> 3;
    pool_parallel_ranges((m / len) * jblocks, 256, nt, [&](long glo, long ghi) {
      for (long g = glo; g < ghi; ++g) {
        const long i0 = (g / jblocks) * len;
        const long j = (g % jblocks) * 8;
        __m512i u[5], v[5], tw[5], t[5], un[5], vn[5];
        for (int k = 0; k < 5; ++k) {
          u[k] = _mm512_loadu_si512(soa + (size_t)k * m + i0 + j);
          v[k] = _mm512_loadu_si512(soa + (size_t)k * m + i0 + j + half);
          tw[k] = _mm512_loadu_si512(twp + (size_t)k * half + j);
        }
        mont52_mul8(t, v, tw, p, pinv);
        add_lazy8(un, u, t, comp2p);
        sub_lazy8(vn, u, t, p2, comp2p);
        for (int k = 0; k < 5; ++k) {
          _mm512_storeu_si512(soa + (size_t)k * m + i0 + j, un[k]);
          _mm512_storeu_si512(soa + (size_t)k * m + i0 + j + half, vn[k]);
        }
      }
    });
  };
  // Radix-4 fusion of stage pairs (len, 2len): same 4 Montgomery muls
  // per 4 elements as two radix-2 passes, but ONE load/store pass over
  // the SoA planes instead of two — the stages are memory-bound at
  // these sizes.  Twiddles come straight from the existing per-stage
  // radix-2 tables: stage len's w^j plus stage 2len's w^j and w^{j+q}.
  auto radix4_pass = [&](long len4, int stg) {
    const long L = 2 * len4;   // fused block size
    const long q = len4 >> 1;  // quarter
    const u64 *tw1p = T.buf.get() + T.offsets[stg];      // stage len: q entries
    const u64 *tw2p = T.buf.get() + T.offsets[stg + 1];  // stage 2len: 2q entries
    const long jblocks = q >> 3;
    pool_parallel_ranges((m / L) * jblocks, 128, nt, [&](long glo, long ghi) {
      for (long g = glo; g < ghi; ++g) {
        const long i0 = (g / jblocks) * L;
        const long j = (g % jblocks) * 8;
        __m512i a[5], b[5], c[5], d[5], w1[5], w2[5], w2q[5];
        for (int k = 0; k < 5; ++k) {
          a[k] = _mm512_loadu_si512(soa + (size_t)k * m + i0 + j);
          b[k] = _mm512_loadu_si512(soa + (size_t)k * m + i0 + j + q);
          c[k] = _mm512_loadu_si512(soa + (size_t)k * m + i0 + j + 2 * q);
          d[k] = _mm512_loadu_si512(soa + (size_t)k * m + i0 + j + 3 * q);
          w1[k] = _mm512_loadu_si512(tw1p + (size_t)k * q + j);
          w2[k] = _mm512_loadu_si512(tw2p + (size_t)k * (2 * q) + j);
          w2q[k] = _mm512_loadu_si512(tw2p + (size_t)k * (2 * q) + j + q);
        }
        __m512i t1[5], t2[5], a1[5], b1[5], c1[5], d1[5];
        // stage len: (a,b) and (c,d) with twiddle w1 — independent
        // chains, one fused schedule
        mont52_mul8x2(t1, b, w1, t2, d, w1, p, pinv);
        add_lazy8(a1, a, t1, comp2p);
        sub_lazy8(b1, a, t1, p2, comp2p);
        add_lazy8(c1, c, t2, comp2p);
        sub_lazy8(d1, c, t2, p2, comp2p);
        // stage 2len: (a1,c1) with w2[j], (b1,d1) with w2[j+q]
        __m512i u1[5], u2[5], o0[5], o1[5], o2[5], o3[5];
        mont52_mul8x2(u1, c1, w2, u2, d1, w2q, p, pinv);
        add_lazy8(o0, a1, u1, comp2p);
        sub_lazy8(o2, a1, u1, p2, comp2p);
        add_lazy8(o1, b1, u2, comp2p);
        sub_lazy8(o3, b1, u2, p2, comp2p);
        for (int k = 0; k < 5; ++k) {
          _mm512_storeu_si512(soa + (size_t)k * m + i0 + j, o0[k]);
          _mm512_storeu_si512(soa + (size_t)k * m + i0 + j + q, o1[k]);
          _mm512_storeu_si512(soa + (size_t)k * m + i0 + j + 2 * q, o2[k]);
          _mm512_storeu_si512(soa + (size_t)k * m + i0 + j + 3 * q, o3[k]);
        }
      }
    });
  };
  // Radix-8 fusion of stage triples (len, 2len, 4len): 12 Montgomery
  // muls per 8 elements — the same butterfly count as three radix-2
  // passes or 1.5 radix-4 passes, but ONE load/store trip over the SoA
  // planes, and every mul paired with an independent partner through
  // mont52_mul8x2 so the serial madd52 recurrences overlap.  The fused
  // ladder at 2^19 is compute-bound on exactly those chains.  Twiddle
  // indexing per element s of the 8q block
  // (q = len/2): stage len pairs (2t, 2t+1) ×w1[j]; stage 2len pairs
  // (4t+s, 4t+s+2) ×w2[j+s·q]; stage 4len pairs (s, s+4) ×w3[j+s·q].
  // The op sequence per element is exactly the radix-2 decomposition,
  // so the lazy-domain residues — and the final proof bytes — are
  // bit-identical to the radix-4 arrangement.
  auto radix8_pass = [&](long len8, int stg) {
    const long q = len8 >> 1;
    const long L8 = 8 * q;  // fused block: three stages span 4·len8
    const u64 *tw1p = T.buf.get() + T.offsets[stg];      // q entries
    const u64 *tw2p = T.buf.get() + T.offsets[stg + 1];  // 2q entries
    const u64 *tw3p = T.buf.get() + T.offsets[stg + 2];  // 4q entries
    const long jblocks = q >> 3;
    pool_parallel_ranges((m / L8) * jblocks, 64, nt, [&](long glo, long ghi) {
      for (long g = glo; g < ghi; ++g) {
        const long i0 = (g / jblocks) * L8;
        const long j = (g % jblocks) * 8;
        __m512i x0[5], x1[5], x2[5], x3[5], x4[5], x5[5], x6[5], x7[5];
        __m512i w1[5], w2a[5], w2b[5], w3a[5], w3b[5], w3c[5], w3d[5];
        for (int k = 0; k < 5; ++k) {
          const size_t o = (size_t)k * m + i0 + j;
          x0[k] = _mm512_loadu_si512(soa + o);
          x1[k] = _mm512_loadu_si512(soa + o + q);
          x2[k] = _mm512_loadu_si512(soa + o + 2 * q);
          x3[k] = _mm512_loadu_si512(soa + o + 3 * q);
          x4[k] = _mm512_loadu_si512(soa + o + 4 * q);
          x5[k] = _mm512_loadu_si512(soa + o + 5 * q);
          x6[k] = _mm512_loadu_si512(soa + o + 6 * q);
          x7[k] = _mm512_loadu_si512(soa + o + 7 * q);
          w1[k] = _mm512_loadu_si512(tw1p + (size_t)k * q + j);
          w2a[k] = _mm512_loadu_si512(tw2p + (size_t)k * (2 * q) + j);
          w2b[k] = _mm512_loadu_si512(tw2p + (size_t)k * (2 * q) + j + q);
          w3a[k] = _mm512_loadu_si512(tw3p + (size_t)k * (4 * q) + j);
          w3b[k] = _mm512_loadu_si512(tw3p + (size_t)k * (4 * q) + j + q);
          w3c[k] = _mm512_loadu_si512(tw3p + (size_t)k * (4 * q) + j + 2 * q);
          w3d[k] = _mm512_loadu_si512(tw3p + (size_t)k * (4 * q) + j + 3 * q);
        }
        __m512i tA[5], tB[5];
        // stage len: (x0,x1)(x2,x3)(x4,x5)(x6,x7), all ×w1[j]
        __m512i a0[5], a1[5], a2[5], a3[5], a4[5], a5[5], a6[5], a7[5];
        mont52_mul8x2(tA, x1, w1, tB, x3, w1, p, pinv);
        add_lazy8(a0, x0, tA, comp2p);
        sub_lazy8(a1, x0, tA, p2, comp2p);
        add_lazy8(a2, x2, tB, comp2p);
        sub_lazy8(a3, x2, tB, p2, comp2p);
        mont52_mul8x2(tA, x5, w1, tB, x7, w1, p, pinv);
        add_lazy8(a4, x4, tA, comp2p);
        sub_lazy8(a5, x4, tA, p2, comp2p);
        add_lazy8(a6, x6, tB, comp2p);
        sub_lazy8(a7, x6, tB, p2, comp2p);
        // stage 2len: (a0,a2)(a4,a6) ×w2[j], (a1,a3)(a5,a7) ×w2[j+q]
        __m512i b0[5], b1[5], b2[5], b3[5], b4[5], b5[5], b6[5], b7[5];
        mont52_mul8x2(tA, a2, w2a, tB, a3, w2b, p, pinv);
        add_lazy8(b0, a0, tA, comp2p);
        sub_lazy8(b2, a0, tA, p2, comp2p);
        add_lazy8(b1, a1, tB, comp2p);
        sub_lazy8(b3, a1, tB, p2, comp2p);
        mont52_mul8x2(tA, a6, w2a, tB, a7, w2b, p, pinv);
        add_lazy8(b4, a4, tA, comp2p);
        sub_lazy8(b6, a4, tA, p2, comp2p);
        add_lazy8(b5, a5, tB, comp2p);
        sub_lazy8(b7, a5, tB, p2, comp2p);
        // stage 4len: (b0,b4)×w3[j] (b1,b5)×w3[j+q] (b2,b6)×w3[j+2q]
        // (b3,b7)×w3[j+3q]
        __m512i o0[5], o1[5], o2[5], o3[5], o4[5], o5[5], o6[5], o7[5];
        mont52_mul8x2(tA, b4, w3a, tB, b5, w3b, p, pinv);
        add_lazy8(o0, b0, tA, comp2p);
        sub_lazy8(o4, b0, tA, p2, comp2p);
        add_lazy8(o1, b1, tB, comp2p);
        sub_lazy8(o5, b1, tB, p2, comp2p);
        mont52_mul8x2(tA, b6, w3c, tB, b7, w3d, p, pinv);
        add_lazy8(o2, b2, tA, comp2p);
        sub_lazy8(o6, b2, tA, p2, comp2p);
        add_lazy8(o3, b3, tB, comp2p);
        sub_lazy8(o7, b3, tB, p2, comp2p);
        for (int k = 0; k < 5; ++k) {
          const size_t o = (size_t)k * m + i0 + j;
          _mm512_storeu_si512(soa + o, o0[k]);
          _mm512_storeu_si512(soa + o + q, o1[k]);
          _mm512_storeu_si512(soa + o + 2 * q, o2[k]);
          _mm512_storeu_si512(soa + o + 3 * q, o3[k]);
          _mm512_storeu_si512(soa + o + 4 * q, o4[k]);
          _mm512_storeu_si512(soa + o + 5 * q, o5[k]);
          _mm512_storeu_si512(soa + o + 6 * q, o6[k]);
          _mm512_storeu_si512(soa + o + 7 * q, o7[k]);
        }
      }
    });
  };
  int n_vstages = 0;
  for (long len0 = 16; len0 <= m; len0 <<= 1) ++n_vstages;
  int stage = 0;
  long len = 16;
  if (ntt_radix8_enabled() && n_vstages >= 3) {
    // Radix-8 arm: clear the mod-3 remainder first (one radix-2 or
    // radix-4 pass), then triples all the way up.
    const int r = n_vstages % 3;
    if (r == 1) {
      radix2_stage(len, stage);
      ++stage;
      len <<= 1;
    } else if (r == 2) {
      radix4_pass(len, stage);
      stage += 2;
      len <<= 2;
    }
    for (; stage < n_vstages; len <<= 3, stage += 3) radix8_pass(len, stage);
  } else {
    if (n_vstages & 1) {
      radix2_stage(len, stage);
      ++stage;
      len <<= 1;
    }
    for (; len * 2 <= m; len <<= 2, stage += 2) radix4_pass(len, stage);
  }
  stat_add(ST_NTT_STAGE_NS, prof_now_ns() - t_st);
}

// Compat wrapper (fr_ntt_ifma's tier), NATURAL-order input: the input
// bit-reversal folds into the pack pass (fr_soa_pack_rev), so the
// standalone swap pass the serial entry used to run is gone.  The
// stage-pool gate resolves HERE: splitting engages when ZKP2P_NTT_POOL
// is on; a pool-worker caller (the knob-off 3-wide ladder runs each
// transform ON a worker) degrades to serial inside pool_parallel_ranges
// regardless, so regions never nest.
static void fr_ntt_ifma_stages(u64 *data, long m, const u64 root_std[4]) {
  int nt = ntt_pool_enabled() ? pool_default_threads() : 1;
  u64 *soa = new u64[(size_t)m * 5];
  fr_soa_pack_rev(data, m, soa, nt);
  fr_ntt_soa_stages(soa, m, root_std, nt);
  fr_soa_unpack(soa, m, data, nt);
  delete[] soa;
}

// Vectorized batch-affine chunk apply over Fq (the MSM hot loop): given
// the per-add arrays of one scheduled chunk (all Montgomery-256), run
// the whole inversion-and-apply pipeline 8 lanes at a time:
//   - lane-strided prefix products (lane l owns j ≡ l mod 8),
//   - ONE scalar field inversion for the 8 lane totals,
//   - vector suffix walk producing 1/den[j],
//   - lambda / x3 / y3 evaluation, all 8-wide mont260 with the lazy
//     [0,2p) domain, carriers converted 256<->260 at the edges.
// x3a/y3a come back fully reduced (< p) so the caller's memcmp-based
// bucket equality checks keep working.
// Caller-provided SoA scratch: 9 arrays x 5 planes x (chunk cap rounded
// to 8) u64 — hoisted out of the per-chunk hot loop by g1_window_sum.
static void g1_chunk_apply_ifma(const u64 (*x1a)[4], const u64 (*y1a)[4],
                                const u64 (*x2a)[4], const u64 (*y2a)[4],
                                const unsigned char *dbl, long m,
                                u64 (*x3a)[4], u64 (*y3a)[4], u64 *buf) {
  Ifma52Field &F = fq52_field();
  const long nblk = (m + 7) / 8, N = nblk * 8;
  // SoA scratch layout: den,num,x1,y1,x2,y2,prod,x3,y3
  u64 *d52 = buf, *n52 = buf + (size_t)5 * N, *x152 = buf + (size_t)10 * N,
      *y152 = buf + (size_t)15 * N, *x252 = buf + (size_t)20 * N,
      *y252 = buf + (size_t)25 * N, *pr52 = buf + (size_t)30 * N,
      *x352 = buf + (size_t)35 * N, *y352 = buf + (size_t)40 * N;
  u64 one52[5] = {1, 0, 0, 0, 0}, one260[5];
  mont52_mul_scalar(one260, one52, F.r260sq, F);
  auto pack_arr = [&](const u64 (*src)[4], u64 *dst, const u64 *pad) {
    for (long j = 0; j < N; ++j) {
      u64 t[5];
      if (j < m) {
        limbs4_to_52(t, src[j]);
      } else {
        memcpy(t, pad, 40);
      }
      for (int k = 0; k < 5; ++k) dst[(size_t)k * N + j] = t[k];
    }
  };
  static const u64 Z5[5] = {0, 0, 0, 0, 0};
  pack_arr(x1a, x152, Z5);
  pack_arr(y1a, y152, Z5);
  // x2/y2 pad with x1-ish zeros; den derives below and pads to the
  // Montgomery-256 ONE so padded lanes are no-ops in the product chains
  pack_arr(x2a, x252, Z5);
  pack_arr(y2a, y252, Z5);

  __m512i p[5], p2[5], comp2p[5], c264v[5], c256v[5];
  for (int k = 0; k < 5; ++k) {
    p[k] = _mm512_set1_epi64((long long)F.p52[k]);
    p2[k] = _mm512_set1_epi64((long long)F.p2_52[k]);
    comp2p[k] = _mm512_set1_epi64((long long)F.comp2p[k]);
    c264v[k] = _mm512_set1_epi64((long long)F.c264[k]);
    c256v[k] = _mm512_set1_epi64((long long)F.c256[k]);
  }
  const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
  // carrier 256 -> 260 for the coordinate arrays, then derive num/den
  // IN VECTOR FORM: chord lanes are (y2-y1, x2-x1); the rare doubling
  // lanes (3x1^2, 2y1) blend in per-block only when flagged.
  for (long t = 0; t < nblk; ++t) {
    u64 *arrs[4] = {x152, y152, x252, y252};
    __m512i conv[4][5];
    for (int a = 0; a < 4; ++a) {
      __m512i v[5];
      for (int k = 0; k < 5; ++k)
        v[k] = _mm512_loadu_si512(arrs[a] + (size_t)k * N + t * 8);
      mont52_mul8(conv[a], v, c264v, p, pinv);
      for (int k = 0; k < 5; ++k)
        _mm512_storeu_si512(arrs[a] + (size_t)k * N + t * 8, conv[a][k]);
    }
    __m512i denv[5], numv[5];
    sub_lazy8(denv, conv[2], conv[0], p2, comp2p);  // x2 - x1
    sub_lazy8(numv, conv[3], conv[1], p2, comp2p);  // y2 - y1
    unsigned char dm = 0;
    for (int l = 0; l < 8 && t * 8 + l < m; ++l)
      if (dbl[t * 8 + l]) dm |= (unsigned char)(1u << l);
    if (dm) {
      __m512i x1sq[5], numd[5], dend[5];
      mont52_mul8(x1sq, conv[0], conv[0], p, pinv);
      add_lazy8(numd, x1sq, x1sq, comp2p);
      add_lazy8(numd, numd, x1sq, comp2p);           // 3 x1^2
      add_lazy8(dend, conv[1], conv[1], comp2p);     // 2 y1
      const __mmask8 k = (__mmask8)dm;
      for (int q = 0; q < 5; ++q) {
        denv[q] = _mm512_mask_blend_epi64(k, denv[q], dend[q]);
        numv[q] = _mm512_mask_blend_epi64(k, numv[q], numd[q]);
      }
    }
    // padded lanes: force den to the mont260 ONE (no-op in chains)
    if (t == nblk - 1 && m < N) {
      __mmask8 padk = (__mmask8)(0xFFu << (8 - (N - m)));
      for (int q = 0; q < 5; ++q)
        denv[q] = _mm512_mask_blend_epi64(
            padk, denv[q], _mm512_set1_epi64((long long)one260[q]));
    }
    for (int k2 = 0; k2 < 5; ++k2) {
      _mm512_storeu_si512(d52 + (size_t)k2 * N + t * 8, denv[k2]);
      _mm512_storeu_si512(n52 + (size_t)k2 * N + t * 8, numv[k2]);
    }
  }
  // phase A: lane-strided prefix products
  __m512i run[5];
  for (int k = 0; k < 5; ++k) run[k] = _mm512_set1_epi64((long long)one260[k]);
  for (long t = 0; t < nblk; ++t) {
    __m512i dv[5];
    for (int k = 0; k < 5; ++k) {
      _mm512_storeu_si512(pr52 + (size_t)k * N + t * 8, run[k]);
      dv[k] = _mm512_loadu_si512(d52 + (size_t)k * N + t * 8);
    }
    mont52_mul8(run, run, dv, p, pinv);
  }
  // ONE inversion for the 8 lane totals (scalar mont256)
  u64 tl8[5][8];
  for (int k = 0; k < 5; ++k) _mm512_storeu_si512(tl8[k], run[k]);
  u64 T4[8][4];
  for (int l = 0; l < 8; ++l) {
    u64 t52[5], t256[5];
    for (int k = 0; k < 5; ++k) t52[k] = tl8[k][l];
    mont52_mul_scalar(t256, t52, F.c256, F);  // carrier 260 -> 256
    limbs52_to_4(T4[l], t256);
    while (geq(T4[l], P)) sub_nored(T4[l], T4[l], P);
  }
  u64 pre[8][4], G[4], Ginv[4], suf[4], Tinv[8][4];
  memcpy(pre[0], ONE_MONT, 32);
  for (int l = 1; l < 8; ++l) mont_mul(pre[l], pre[l - 1], T4[l - 1]);
  mont_mul(G, pre[7], T4[7]);
  mont_inv(Ginv, G);
  memcpy(suf, Ginv, 32);
  for (int l = 7; l >= 0; --l) {
    mont_mul(Tinv[l], suf, pre[l]);
    mont_mul(suf, suf, T4[l]);
  }
  __m512i inv_run[5];
  {
    u64 ir8[5][8];
    for (int l = 0; l < 8; ++l) {
      u64 t52[5], t260[5];
      limbs4_to_52(t52, Tinv[l]);
      mont52_mul_scalar(t260, t52, F.c264, F);  // carrier 256 -> 260
      for (int k = 0; k < 5; ++k) ir8[k][l] = t260[k];
    }
    for (int k = 0; k < 5; ++k) inv_run[k] = _mm512_loadu_si512(ir8[k]);
  }
  // phase B: backward suffix walk + apply
  for (long t = nblk - 1; t >= 0; --t) {
    __m512i prv[5], dv[5], nv[5], x1v[5], y1v[5], x2v[5];
    for (int k = 0; k < 5; ++k) {
      prv[k] = _mm512_loadu_si512(pr52 + (size_t)k * N + t * 8);
      dv[k] = _mm512_loadu_si512(d52 + (size_t)k * N + t * 8);
      nv[k] = _mm512_loadu_si512(n52 + (size_t)k * N + t * 8);
      x1v[k] = _mm512_loadu_si512(x152 + (size_t)k * N + t * 8);
      y1v[k] = _mm512_loadu_si512(y152 + (size_t)k * N + t * 8);
      x2v[k] = _mm512_loadu_si512(x252 + (size_t)k * N + t * 8);
    }
    __m512i dinv[5], lam[5], lam2[5], x3[5], tt[5], yy[5], y3[5];
    mont52_mul8(dinv, inv_run, prv, p, pinv);
    mont52_mul8(inv_run, inv_run, dv, p, pinv);
    mont52_mul8(lam, nv, dinv, p, pinv);
    mont52_mul8(lam2, lam, lam, p, pinv);
    sub_lazy8(x3, lam2, x1v, p2, comp2p);
    sub_lazy8(x3, x3, x2v, p2, comp2p);
    sub_lazy8(tt, x1v, x3, p2, comp2p);
    mont52_mul8(yy, lam, tt, p, pinv);
    sub_lazy8(y3, yy, y1v, p2, comp2p);
    mont52_mul8(x3, x3, c256v, p, pinv);  // carrier back to 256
    mont52_mul8(y3, y3, c256v, p, pinv);
    for (int k = 0; k < 5; ++k) {
      _mm512_storeu_si512(x352 + (size_t)k * N + t * 8, x3[k]);
      _mm512_storeu_si512(y352 + (size_t)k * N + t * 8, y3[k]);
    }
  }
  // unpack, fully reduced
  for (long j = 0; j < m; ++j) {
    u64 t[5], o[4];
    for (int k = 0; k < 5; ++k) t[k] = x352[(size_t)k * N + j];
    limbs52_to_4(o, t);
    while (geq(o, P)) sub_nored(o, o, P);
    memcpy(x3a[j], o, 32);
    for (int k = 0; k < 5; ++k) t[k] = y352[(size_t)k * N + j];
    limbs52_to_4(o, t);
    while (geq(o, P)) sub_nored(o, o, P);
    memcpy(y3a[j], o, 32);
  }
}

// -------- persistent 52-limb mont260 MSM storage (G1)
//
// Bases and buckets live in 5x52-limb mont260 form for the WHOLE MSM:
// the chunk apply loses its six carrier-conversion vector muls per
// block and all per-add limb-shift packing — conversion happens once
// per MSM (bases, vectorized) and once per bucket at reduction time.
// Components are kept CANONICAL (< p) so memcmp equality (doubling /
// cancellation detection) still works.

struct Aff52 {
  u64 x[5], y[5];  // canonical mont260; all-zero = infinity/empty
};

static void fold52_canonical(u64 v[5], const Ifma52Field &F);

// y -> p - y over canonical 52-limb components (the signed-digit negation).
static inline void neg52(u64 out[5], const u64 y[5], const Ifma52Field &F) {
  bool z = true;
  for (int j = 0; j < 5 && z; ++j) z = y[j] == 0;
  if (z) {
    memset(out, 0, 40);
    return;
  }
  u64 borrow = 0;
  for (int j = 0; j < 5; ++j) {
    u64 yb = y[j] + borrow;  // <= 2^52, no overflow
    if (F.p52[j] >= yb) {
      out[j] = F.p52[j] - yb;
      borrow = 0;
    } else {
      out[j] = (F.p52[j] + (1ULL << 52)) - yb;
      borrow = 1;
    }
  }
}

// mont256 affine pairs -> canonical mont260 Aff52, 8 points per step.
static void g1_bases_to_52(const u64 *bases_xy, long n, Aff52 *out) {
  Ifma52Field &F = fq52_field();
  __m512i p[5], c264v[5], comppv[5];
  for (int k = 0; k < 5; ++k) {
    p[k] = _mm512_set1_epi64((long long)F.p52[k]);
    c264v[k] = _mm512_set1_epi64((long long)F.c264[k]);
    comppv[k] = _mm512_set1_epi64((long long)F.compp[k]);
  }
  const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
  long i = 0;
  for (; i + 8 <= n; i += 8) {
    u64 xv[5][8], yv[5][8];
    for (int l = 0; l < 8; ++l) {
      u64 t[5];
      limbs4_to_52(t, bases_xy + 8 * (i + l));
      for (int k = 0; k < 5; ++k) xv[k][l] = t[k];
      limbs4_to_52(t, bases_xy + 8 * (i + l) + 4);
      for (int k = 0; k < 5; ++k) yv[k][l] = t[k];
    }
    __m512i X[5], Y[5];
    for (int k = 0; k < 5; ++k) {
      X[k] = _mm512_loadu_si512(xv[k]);
      Y[k] = _mm512_loadu_si512(yv[k]);
    }
    __m512i Xm[5], Ym[5];
    mont52_mul8(Xm, X, c264v, p, pinv);
    cond_sub_c8(Xm, comppv);
    mont52_mul8(Ym, Y, c264v, p, pinv);
    cond_sub_c8(Ym, comppv);
    u64 ox[5][8], oy[5][8];
    for (int k = 0; k < 5; ++k) {
      _mm512_storeu_si512(ox[k], Xm[k]);
      _mm512_storeu_si512(oy[k], Ym[k]);
    }
    for (int l = 0; l < 8; ++l) {
      for (int k = 0; k < 5; ++k) {
        out[i + l].x[k] = ox[k][l];
        out[i + l].y[k] = oy[k][l];
      }
    }
  }
  for (; i < n; ++i) {
    u64 t[5], m260[5];
    limbs4_to_52(t, bases_xy + 8 * i);
    mont52_mul_scalar(m260, t, F.c264, F);
    fold52_canonical(m260, F);
    memcpy(out[i].x, m260, 40);
    limbs4_to_52(t, bases_xy + 8 * i + 4);
    mont52_mul_scalar(m260, t, F.c264, F);
    fold52_canonical(m260, F);
    memcpy(out[i].y, m260, 40);
  }
}

// canonical fold of a < 2p 52-limb value (scalar path).
static void fold52_canonical(u64 v[5], const Ifma52Field &F) {
  bool ge = true;
  for (int j = 4; j >= 0; --j) {
    if (v[j] != F.p52[j]) {
      ge = v[j] > F.p52[j];
      break;
    }
  }
  if (!ge) return;
  u64 borrow = 0;
  for (int j = 0; j < 5; ++j) {
    u64 pb = F.p52[j] + borrow;
    if (v[j] >= pb) {
      v[j] -= pb;
      borrow = 0;
    } else {
      v[j] = (v[j] + (1ULL << 52)) - pb;
      borrow = 1;
    }
  }
}

// canonical mont260 component -> canonical mont256 u64x4.
static void limb52_to_mont256(const u64 a[5], u64 out[4], const Ifma52Field &F) {
  u64 t[5];
  mont52_mul_scalar(t, a, F.c256, F);
  limbs52_to_4(out, t);
  while (geq(out, P)) sub_nored(out, out, P);
}

// The 52-native chunk apply: same pipeline as g1_chunk_apply_ifma but
// with NO carrier conversions and NO limb-shift packing — stashes are
// already 5-limb mont260 canonical.  Outputs canonical.
// buf: 8 x 5 x roundup8(m) u64 scratch (den,num,x1,y1,x2,prod,x3,y3 —
// y2 is derived per block from b52 + the sign flag, no plane kept).
// Gathers operands by INDEX (bucket id + point id + sign) straight
// from the bucket array and the converted bases — the schedule loop
// stores three small ints per add instead of 160 bytes of coordinate
// stashes.
static void g1_chunk_apply_52(const Aff52 *bk, const Aff52 *b52,
                              const long *add_bkt, const long *add_pt,
                              const unsigned char *negf,
                              const unsigned char *dbl, long m,
                              u64 (*x3a)[5], u64 (*y3a)[5], u64 *buf) {
  Ifma52Field &F = fq52_field();
  const long nblk = (m + 7) / 8, N = nblk * 8;
  u64 *d52 = buf, *n52 = buf + (size_t)5 * N, *x152 = buf + (size_t)10 * N,
      *y152 = buf + (size_t)15 * N, *x252 = buf + (size_t)20 * N,
      *pr52 = buf + (size_t)25 * N, *x352 = buf + (size_t)30 * N,
      *y352 = buf + (size_t)35 * N;
  u64 one52[5] = {1, 0, 0, 0, 0}, one260[5];
  mont52_mul_scalar(one260, one52, F.r260sq, F);
  const bool ilv_pf = msm_interleave_enabled();
  // Prefetch distance down the schedule's index streams.  The gathered
  // Aff52s (80 bytes, two cache lines) sit at random offsets in a
  // bases/buckets working set far beyond L2 at bench shape — without
  // prefetch every add eats a demand-miss latency twice.
  const long PF = 24;
  // gather-transpose into SoA planes (x1 = bucket, x2 = incoming point)
  for (long j = 0; j < N; ++j) {
    if (j < m) {
      if (ilv_pf && j + PF < m) {
        const char *pb = (const char *)&bk[add_bkt[j + PF]];
        const char *pp = (const char *)&b52[add_pt[j + PF]];
        _mm_prefetch(pb, _MM_HINT_T0);
        _mm_prefetch(pb + 64, _MM_HINT_T0);
        _mm_prefetch(pp, _MM_HINT_T0);
        _mm_prefetch(pp + 64, _MM_HINT_T0);
      }
      const Aff52 &B1 = bk[add_bkt[j]];
      const Aff52 &P2 = b52[add_pt[j]];
      for (int k = 0; k < 5; ++k) {
        x152[(size_t)k * N + j] = B1.x[k];
        y152[(size_t)k * N + j] = B1.y[k];
        x252[(size_t)k * N + j] = P2.x[k];
      }
    } else {
      for (int k = 0; k < 5; ++k)
        x152[(size_t)k * N + j] = y152[(size_t)k * N + j] = x252[(size_t)k * N + j] = 0;
    }
  }
  // y2 goes straight into the num derivation below (no plane kept)

  __m512i p[5], p2[5], comp2p[5], comppv[5];
  for (int k = 0; k < 5; ++k) {
    p[k] = _mm512_set1_epi64((long long)F.p52[k]);
    p2[k] = _mm512_set1_epi64((long long)F.p2_52[k]);
    comp2p[k] = _mm512_set1_epi64((long long)F.comp2p[k]);
    comppv[k] = _mm512_set1_epi64((long long)F.compp[k]);
  }
  const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
  for (long t = 0; t < nblk; ++t) {
    __m512i x1v[5], y1v[5], x2v[5], y2v[5];
    for (int k = 0; k < 5; ++k) {
      x1v[k] = _mm512_loadu_si512(x152 + (size_t)k * N + t * 8);
      y1v[k] = _mm512_loadu_si512(y152 + (size_t)k * N + t * 8);
      x2v[k] = _mm512_loadu_si512(x252 + (size_t)k * N + t * 8);
    }
    {
      u64 y2v8[5][8];
      for (int l = 0; l < 8; ++l) {
        long j = t * 8 + l;
        if (j < m) {
          if (ilv_pf && j + PF < m) {
            const char *pp = (const char *)b52[add_pt[j + PF]].y;
            _mm_prefetch(pp, _MM_HINT_T0);
            _mm_prefetch(pp + 39, _MM_HINT_T0);
          }
          u64 py[5];
          if (negf[j]) {
            neg52(py, b52[add_pt[j]].y, F);
          } else {
            memcpy(py, b52[add_pt[j]].y, 40);
          }
          for (int k = 0; k < 5; ++k) y2v8[k][l] = py[k];
        } else {
          for (int k = 0; k < 5; ++k) y2v8[k][l] = 0;
        }
      }
      for (int k = 0; k < 5; ++k) y2v[k] = _mm512_loadu_si512(y2v8[k]);
    }
    __m512i denv[5], numv[5];
    sub_lazy8(denv, x2v, x1v, p2, comp2p);
    sub_lazy8(numv, y2v, y1v, p2, comp2p);
    unsigned char dm = 0;
    for (int l = 0; l < 8 && t * 8 + l < m; ++l)
      if (dbl[t * 8 + l]) dm |= (unsigned char)(1u << l);
    if (dm) {
      __m512i x1sq[5], numd[5], dend[5];
      mont52_mul8(x1sq, x1v, x1v, p, pinv);
      add_lazy8(numd, x1sq, x1sq, comp2p);
      add_lazy8(numd, numd, x1sq, comp2p);
      add_lazy8(dend, y1v, y1v, comp2p);
      const __mmask8 kk = (__mmask8)dm;
      for (int q = 0; q < 5; ++q) {
        denv[q] = _mm512_mask_blend_epi64(kk, denv[q], dend[q]);
        numv[q] = _mm512_mask_blend_epi64(kk, numv[q], numd[q]);
      }
    }
    if (t == nblk - 1 && m < N) {
      __mmask8 padk = (__mmask8)(0xFFu << (m & 7));
      for (int q = 0; q < 5; ++q)
        denv[q] = _mm512_mask_blend_epi64(
            padk, denv[q], _mm512_set1_epi64((long long)one260[q]));
    }
    for (int k = 0; k < 5; ++k) {
      _mm512_storeu_si512(d52 + (size_t)k * N + t * 8, denv[k]);
      _mm512_storeu_si512(n52 + (size_t)k * N + t * 8, numv[k]);
    }
  }
  if (msm_interleave_enabled() && nblk >= 2) {
    // Interleaved arm (ZKP2P_MSM_INTERLEAVE): split the block range at
    // hA and drive BOTH halves' prefix/apply chains through one fused
    // schedule (mont52_mul8x2).  A single chain is latency-bound —
    // every block's prefix multiply waits on the previous block's — so
    // the second, data-independent chain fills the IFMA port bubbles.
    // The two group products meet in ONE shared 16-lane scalar
    // inversion (same mont_inv count as before).  Each group is its
    // own batch-inversion domain, so every lane still computes the
    // exact same field values; the canonical fold at the end erases
    // representative drift, keeping outputs byte-identical to the
    // single-chain arm.
    const long hA = (nblk + 1) / 2, nB = nblk - hA;
    __m512i runA[5], runB[5];
    for (int k = 0; k < 5; ++k)
      runA[k] = runB[k] = _mm512_set1_epi64((long long)one260[k]);
    for (long t = 0; t < hA; ++t) {
      const bool hasB = t < nB;
      __m512i dvA[5], dvB[5];
      for (int k = 0; k < 5; ++k) {
        _mm512_storeu_si512(pr52 + (size_t)k * N + t * 8, runA[k]);
        dvA[k] = _mm512_loadu_si512(d52 + (size_t)k * N + t * 8);
        if (hasB) {
          _mm512_storeu_si512(pr52 + (size_t)k * N + (hA + t) * 8, runB[k]);
          dvB[k] = _mm512_loadu_si512(d52 + (size_t)k * N + (hA + t) * 8);
        }
      }
      if (hasB)
        mont52_mul8x2(runA, runA, dvA, runB, runB, dvB, p, pinv);
      else
        mont52_mul8(runA, runA, dvA, p, pinv);
    }
    u64 tl16[2][5][8];
    for (int k = 0; k < 5; ++k) {
      _mm512_storeu_si512(tl16[0][k], runA[k]);
      _mm512_storeu_si512(tl16[1][k], runB[k]);
    }
    u64 T4[16][4];
    for (int l = 0; l < 16; ++l) {
      u64 t52[5];
      for (int k = 0; k < 5; ++k) t52[k] = tl16[l >> 3][k][l & 7];
      limb52_to_mont256(t52, T4[l], F);
    }
    u64 pre16[16][4], G[4], Ginv[4], suf[4], Tinv[16][4];
    memcpy(pre16[0], ONE_MONT, 32);
    for (int l = 1; l < 16; ++l) mont_mul(pre16[l], pre16[l - 1], T4[l - 1]);
    mont_mul(G, pre16[15], T4[15]);
    mont_inv(Ginv, G);
    memcpy(suf, Ginv, 32);
    for (int l = 15; l >= 0; --l) {
      mont_mul(Tinv[l], suf, pre16[l]);
      mont_mul(suf, suf, T4[l]);
    }
    __m512i inv_runA[5], inv_runB[5];
    {
      u64 ir16[2][5][8];
      for (int l = 0; l < 16; ++l) {
        u64 t52[5], t260[5];
        limbs4_to_52(t52, Tinv[l]);
        mont52_mul_scalar(t260, t52, F.c264, F);
        for (int k = 0; k < 5; ++k) ir16[l >> 3][k][l & 7] = t260[k];
      }
      for (int k = 0; k < 5; ++k) {
        inv_runA[k] = _mm512_loadu_si512(ir16[0][k]);
        inv_runB[k] = _mm512_loadu_si512(ir16[1][k]);
      }
    }
    // phase B: two interleaved backward walks (A: hA-1..0, B: nblk-1..hA)
    for (long i = 0; i < hA; ++i) {
      const long tA = hA - 1 - i, tB = nblk - 1 - i;
      const bool hasB = i < nB;
      __m512i prvA[5], dvA[5], nvA[5], x1A[5], y1A[5], x2A[5];
      __m512i prvB[5], dvB[5], nvB[5], x1B[5], y1B[5], x2B[5];
      for (int k = 0; k < 5; ++k) {
        prvA[k] = _mm512_loadu_si512(pr52 + (size_t)k * N + tA * 8);
        dvA[k] = _mm512_loadu_si512(d52 + (size_t)k * N + tA * 8);
        nvA[k] = _mm512_loadu_si512(n52 + (size_t)k * N + tA * 8);
        x1A[k] = _mm512_loadu_si512(x152 + (size_t)k * N + tA * 8);
        y1A[k] = _mm512_loadu_si512(y152 + (size_t)k * N + tA * 8);
        x2A[k] = _mm512_loadu_si512(x252 + (size_t)k * N + tA * 8);
        if (hasB) {
          prvB[k] = _mm512_loadu_si512(pr52 + (size_t)k * N + tB * 8);
          dvB[k] = _mm512_loadu_si512(d52 + (size_t)k * N + tB * 8);
          nvB[k] = _mm512_loadu_si512(n52 + (size_t)k * N + tB * 8);
          x1B[k] = _mm512_loadu_si512(x152 + (size_t)k * N + tB * 8);
          y1B[k] = _mm512_loadu_si512(y152 + (size_t)k * N + tB * 8);
          x2B[k] = _mm512_loadu_si512(x252 + (size_t)k * N + tB * 8);
        }
      }
      __m512i dinvA[5], lamA[5], lam2A[5], x3A[5], ttA[5], yyA[5], y3A[5];
      if (hasB) {
        __m512i dinvB[5], lamB[5], lam2B[5], x3B[5], ttB[5], yyB[5], y3B[5];
        mont52_mul8x2(dinvA, inv_runA, prvA, dinvB, inv_runB, prvB, p, pinv);
        mont52_mul8x2(inv_runA, inv_runA, dvA, inv_runB, inv_runB, dvB, p,
                      pinv);
        mont52_mul8x2(lamA, nvA, dinvA, lamB, nvB, dinvB, p, pinv);
        mont52_mul8x2(lam2A, lamA, lamA, lam2B, lamB, lamB, p, pinv);
        sub_lazy8(x3A, lam2A, x1A, p2, comp2p);
        sub_lazy8(x3A, x3A, x2A, p2, comp2p);
        sub_lazy8(ttA, x1A, x3A, p2, comp2p);
        sub_lazy8(x3B, lam2B, x1B, p2, comp2p);
        sub_lazy8(x3B, x3B, x2B, p2, comp2p);
        sub_lazy8(ttB, x1B, x3B, p2, comp2p);
        mont52_mul8x2(yyA, lamA, ttA, yyB, lamB, ttB, p, pinv);
        sub_lazy8(y3A, yyA, y1A, p2, comp2p);
        sub_lazy8(y3B, yyB, y1B, p2, comp2p);
        // canonical fold for the memcmp-equality contract
        cond_sub_c8(x3A, comppv);
        cond_sub_c8(y3A, comppv);
        cond_sub_c8(x3B, comppv);
        cond_sub_c8(y3B, comppv);
        for (int k = 0; k < 5; ++k) {
          _mm512_storeu_si512(x352 + (size_t)k * N + tA * 8, x3A[k]);
          _mm512_storeu_si512(y352 + (size_t)k * N + tA * 8, y3A[k]);
          _mm512_storeu_si512(x352 + (size_t)k * N + tB * 8, x3B[k]);
          _mm512_storeu_si512(y352 + (size_t)k * N + tB * 8, y3B[k]);
        }
      } else {
        mont52_mul8(dinvA, inv_runA, prvA, p, pinv);
        mont52_mul8(inv_runA, inv_runA, dvA, p, pinv);
        mont52_mul8(lamA, nvA, dinvA, p, pinv);
        mont52_mul8(lam2A, lamA, lamA, p, pinv);
        sub_lazy8(x3A, lam2A, x1A, p2, comp2p);
        sub_lazy8(x3A, x3A, x2A, p2, comp2p);
        sub_lazy8(ttA, x1A, x3A, p2, comp2p);
        mont52_mul8(yyA, lamA, ttA, p, pinv);
        sub_lazy8(y3A, yyA, y1A, p2, comp2p);
        cond_sub_c8(x3A, comppv);
        cond_sub_c8(y3A, comppv);
        for (int k = 0; k < 5; ++k) {
          _mm512_storeu_si512(x352 + (size_t)k * N + tA * 8, x3A[k]);
          _mm512_storeu_si512(y352 + (size_t)k * N + tA * 8, y3A[k]);
        }
      }
    }
  } else {
    // Single-chain arm (gate off, or a one-block chunk).
    // phase A: lane-strided prefix products
    __m512i run[5];
    for (int k = 0; k < 5; ++k)
      run[k] = _mm512_set1_epi64((long long)one260[k]);
    for (long t = 0; t < nblk; ++t) {
      __m512i dv[5];
      for (int k = 0; k < 5; ++k) {
        _mm512_storeu_si512(pr52 + (size_t)k * N + t * 8, run[k]);
        dv[k] = _mm512_loadu_si512(d52 + (size_t)k * N + t * 8);
      }
      mont52_mul8(run, run, dv, p, pinv);
    }
    u64 tl8[5][8];
    for (int k = 0; k < 5; ++k) _mm512_storeu_si512(tl8[k], run[k]);
    u64 T4[8][4];
    for (int l = 0; l < 8; ++l) {
      u64 t52[5];
      for (int k = 0; k < 5; ++k) t52[k] = tl8[k][l];
      limb52_to_mont256(t52, T4[l], F);
    }
    u64 pre8[8][4], G[4], Ginv[4], suf[4], Tinv[8][4];
    memcpy(pre8[0], ONE_MONT, 32);
    for (int l = 1; l < 8; ++l) mont_mul(pre8[l], pre8[l - 1], T4[l - 1]);
    mont_mul(G, pre8[7], T4[7]);
    mont_inv(Ginv, G);
    memcpy(suf, Ginv, 32);
    for (int l = 7; l >= 0; --l) {
      mont_mul(Tinv[l], suf, pre8[l]);
      mont_mul(suf, suf, T4[l]);
    }
    __m512i inv_run[5];
    {
      u64 ir8[5][8];
      for (int l = 0; l < 8; ++l) {
        u64 t52[5], t260[5];
        limbs4_to_52(t52, Tinv[l]);
        mont52_mul_scalar(t260, t52, F.c264, F);
        for (int k = 0; k < 5; ++k) ir8[k][l] = t260[k];
      }
      for (int k = 0; k < 5; ++k) inv_run[k] = _mm512_loadu_si512(ir8[k]);
    }
    // phase B backwards
    for (long t = nblk - 1; t >= 0; --t) {
      __m512i prv[5], dv[5], nv[5], x1v[5], y1v[5], x2v[5];
      for (int k = 0; k < 5; ++k) {
        prv[k] = _mm512_loadu_si512(pr52 + (size_t)k * N + t * 8);
        dv[k] = _mm512_loadu_si512(d52 + (size_t)k * N + t * 8);
        nv[k] = _mm512_loadu_si512(n52 + (size_t)k * N + t * 8);
        x1v[k] = _mm512_loadu_si512(x152 + (size_t)k * N + t * 8);
        y1v[k] = _mm512_loadu_si512(y152 + (size_t)k * N + t * 8);
        x2v[k] = _mm512_loadu_si512(x252 + (size_t)k * N + t * 8);
      }
      __m512i dinv[5], lam[5], lam2[5], x3[5], tt[5], yy[5], y3[5];
      mont52_mul8(dinv, inv_run, prv, p, pinv);
      mont52_mul8(inv_run, inv_run, dv, p, pinv);
      mont52_mul8(lam, nv, dinv, p, pinv);
      mont52_mul8(lam2, lam, lam, p, pinv);
      sub_lazy8(x3, lam2, x1v, p2, comp2p);
      sub_lazy8(x3, x3, x2v, p2, comp2p);
      sub_lazy8(tt, x1v, x3, p2, comp2p);
      mont52_mul8(yy, lam, tt, p, pinv);
      sub_lazy8(y3, yy, y1v, p2, comp2p);
      // canonical fold for the memcmp-equality contract
      cond_sub_c8(x3, comppv);
      cond_sub_c8(y3, comppv);
      for (int k = 0; k < 5; ++k) {
        _mm512_storeu_si512(x352 + (size_t)k * N + t * 8, x3[k]);
        _mm512_storeu_si512(y352 + (size_t)k * N + t * 8, y3[k]);
      }
    }
  }
  for (long j = 0; j < m; ++j) {
    for (int k = 0; k < 5; ++k) {
      x3a[j][k] = x352[(size_t)k * N + j];
      y3a[j][k] = y352[(size_t)k * N + j];
    }
  }
}

static inline bool aff52_is_zero(const u64 a[5]) {
  return !(a[0] | a[1] | a[2] | a[3] | a[4]);
}

// defined later in this file (shared with the non-IFMA tiers)
static void g1_window_sum_jac(const u64 *bases_xy, const int32_t *sd, long n,
                              int c, int nwin, int wi, G1Jac *out);
static inline void signed_pt_y(u64 out[4], const u64 y[4], bool negate);
static void g1_tree_sum(u64 (*xs)[4], u64 (*ys)[4], long n, G1Jac *out);
static void g1_add_jac(G1Jac &acc, const G1Jac &e);

// Tiny-digit-range windows (the TOP window at big domains has only a
// few effective bits): instead of the serial Jacobian fill — every
// point lands in one of a handful of buckets — partition points by
// digit and run each bucket through the vectorized tree sum, then do
// the standard suffix reduction over the few bucket sums.
static void g1_window_sum_small(const u64 *bases_xy, const int32_t *sd,
                                long n, int c, int nwin, int wi,
                                int bits_here, G1Jac *out) {
  const long nbuckets = (1L << bits_here) + 2;  // +carry headroom
  std::vector<std::vector<long>> members((size_t)nbuckets);
  for (long i = 0; i < n; ++i) {
    int32_t d = sd[i * nwin + wi];
    if (!d) continue;
    long b = d < 0 ? -d : d;
    if (b >= nbuckets) {  // cannot happen for a true top window; bail
      g1_window_sum_jac(bases_xy, sd, n, c, nwin, wi, out);
      return;
    }
    const u64 *x = bases_xy + 8 * i;
    if (is_zero4(x) && is_zero4(x + 4)) continue;
    members[b].push_back(i);  // sign re-read from sd at drain time
  }
  long cap = 0;
  for (auto &v : members) cap = std::max(cap, (long)v.size());
  u64 (*xs)[4] = new u64[cap > 0 ? cap : 1][4];
  u64 (*ys)[4] = new u64[cap > 0 ? cap : 1][4];
  G1Jac run, wsum;
  memset(&run, 0, sizeof(run));
  memset(&wsum, 0, sizeof(wsum));
  for (long b = nbuckets - 1; b >= 1; --b) {
    if (!members[b].empty()) {
      long k = 0;
      for (long i : members[b]) {
        const u64 *x = bases_xy + 8 * i;
        memcpy(xs[k], x, 32);
        signed_pt_y(ys[k], x + 4, sd[i * nwin + wi] < 0);
        ++k;
      }
      G1Jac bsum;
      g1_tree_sum(xs, ys, k, &bsum);
      g1_add_jac(run, bsum);
    }
    g1_add_jac(wsum, run);
  }
  delete[] xs;
  delete[] ys;
  *out = wsum;
}

// ---- 8-lane vectorized suffix reduction (one lane = one window) -----------
//
// The per-window suffix walk (run += bucket[d]; wsum += run) is serial in d
// but independent across windows, and profiles at ~27% of the G1 phase time
// of a full prove (ZKP2P_MSM_PROF / tools/msm_native_prof.py) now that the
// fill is 8-wide.  These helpers run up to 8 windows' walks in AVX-512 IFMA
// lanes: a masked Jacobian mixed add (bucket -> run) and a masked full
// Jacobian add (run -> wsum) per bucket index, in the same lazy [0,2p)
// mont260 domain as the chunk pipeline.  Exceptional lanes (doubling,
// P+(-P), infinity transitions beyond the common masks) blend out and
// re-run through the complete scalar ops — for bucket sums they cannot
// occur except adversarially, so the patch path is correctness-only.

// v == 0 (mod p) for lazy [0,2p) 52-limb values: exact 0 or exact p.
static inline __mmask8 is0_lazy8v(const __m512i v[5], const __m512i p[5]) {
  __mmask8 z = 0xFF, e = 0xFF;
  const __m512i zero = _mm512_setzero_si512();
  for (int j = 0; j < 5; ++j) {
    z &= _mm512_cmpeq_epu64_mask(v[j], zero);
    e &= _mm512_cmpeq_epu64_mask(v[j], p[j]);
  }
  return (__mmask8)(z | e);
}

struct Jac8 {
  __m512i X[5], Y[5], Z[5];
  __mmask8 inf;  // lanes at the point at infinity (coords then arbitrary)
};

static inline void v8_lane52(const __m512i V[5], int l, u64 out52[5]) {
  alignas(64) u64 b[8];
  for (int k = 0; k < 5; ++k) {
    _mm512_store_si512(b, V[k]);
    out52[k] = b[l];
  }
}

static inline void v8_set_lane52(__m512i V[5], int l, const u64 in52[5]) {
  alignas(64) u64 b[8];
  for (int k = 0; k < 5; ++k) {
    _mm512_store_si512(b, V[k]);
    b[l] = in52[k];
    V[k] = _mm512_load_si512(b);
  }
}

// One lane -> scalar G1Jac (canonical mont256 coords).
static G1Jac jac8_lane(const Jac8 &s, int l, const Ifma52Field &F) {
  G1Jac g;
  if ((s.inf >> l) & 1) {
    memset(&g, 0, sizeof(g));
    return g;
  }
  u64 c52[5];
  v8_lane52(s.X, l, c52);
  limb52_to_mont256(c52, g.X, F);
  v8_lane52(s.Y, l, c52);
  limb52_to_mont256(c52, g.Y, F);
  v8_lane52(s.Z, l, c52);
  limb52_to_mont256(c52, g.Z, F);
  return g;
}

// Scalar G1Jac -> one lane (mont256 -> mont260 carrier), inf mask updated.
static void jac8_set_lane(Jac8 &s, int l, const G1Jac &g, const Ifma52Field &F) {
  if (is_zero4(g.Z)) {
    s.inf |= (__mmask8)(1u << l);
    return;
  }
  s.inf &= (__mmask8)~(1u << l);
  u64 t52[5], t260[5];
  limbs4_to_52(t52, g.X);
  mont52_mul_scalar(t260, t52, F.c264, F);
  v8_set_lane52(s.X, l, t260);
  limbs4_to_52(t52, g.Y);
  mont52_mul_scalar(t260, t52, F.c264, F);
  v8_set_lane52(s.Y, l, t260);
  limbs4_to_52(t52, g.Z);
  mont52_mul_scalar(t260, t52, F.c264, F);
  v8_set_lane52(s.Z, l, t260);
}

// Run up to SUFFIX_MAX_LANES windows' suffix walks in lanes (8 per
// group, groups interleaved).  allbk: nwin x nbuckets canonical-mont260
// bucket arrays (all-zero = empty); wis[0..nl_total): the window index
// each lane reduces; outs[l]: that window's sum (Jacobian mont256).
// Up to MAXG groups of 8 window-lanes walk INTERLEAVED inside one
// d-loop: each group's mixed/full adds are a serial mont52_mul8
// dependency chain (~25 muls deep), so consecutive independent groups
// give the out-of-order engine real overlap that back-to-back
// single-group calls cannot.
static constexpr int SUFFIX_MAXG = 3;           // interleaved lane-groups
static constexpr int SUFFIX_MAX_LANES = 8 * SUFFIX_MAXG;  // caller batch cap

static void g1_suffix8(const Aff52 *allbk, long nbuckets, const int *wis,
                       int nl_total, G1Jac *outs) {
  constexpr int MAXG = SUFFIX_MAXG;
  Ifma52Field &F = fq52_field();
  __m512i p[5], p2[5], comp2p[5], onev[5];
  u64 one52[5] = {1, 0, 0, 0, 0}, one260[5];
  mont52_mul_scalar(one260, one52, F.r260sq, F);
  for (int k = 0; k < 5; ++k) {
    p[k] = _mm512_set1_epi64((long long)F.p52[k]);
    p2[k] = _mm512_set1_epi64((long long)F.p2_52[k]);
    comp2p[k] = _mm512_set1_epi64((long long)F.comp2p[k]);
    onev[k] = _mm512_set1_epi64((long long)one260[k]);
  }
  const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);

  const int ngroups = (nl_total + 7) / 8;
  // Hard bound, not an assert: the fixed-size stack arrays below
  // (nlg/wisg/vbaseg/rung/wsg) are MAXG-sized, and an over-long lane
  // batch must abort even in an NDEBUG build rather than smash the
  // stack.
  if (ngroups > MAXG) {
    fprintf(stderr, "g1_suffix8: %d lanes exceeds SUFFIX_MAX_LANES=%d\n",
            nl_total, SUFFIX_MAX_LANES);
    abort();
  }
  int nlg[MAXG];
  const int *wisg[MAXG];
  __m512i vbaseg[MAXG];
  __mmask8 actg[MAXG];
  alignas(64) long long lane_baseg[MAXG][8];
  for (int g = 0; g < ngroups; ++g) {
    nlg[g] = nl_total - 8 * g > 8 ? 8 : nl_total - 8 * g;
    wisg[g] = wis + 8 * g;
    for (int l = 0; l < 8; ++l) {
      int w = l < nlg[g] ? wisg[g][l] : wisg[g][0];
      lane_baseg[g][l] = (long long)((size_t)w * (size_t)nbuckets * sizeof(Aff52));
    }
    vbaseg[g] = _mm512_load_si512(lane_baseg[g]);
    actg[g] = (__mmask8)((1u << nlg[g]) - 1);
  }

  Jac8 rung[MAXG], wsg[MAXG];
  for (int g = 0; g < ngroups; ++g) {
    for (int k = 0; k < 5; ++k) {
      rung[g].X[k] = rung[g].Y[k] = rung[g].Z[k] = onev[k];
      wsg[g].X[k] = wsg[g].Y[k] = wsg[g].Z[k] = onev[k];
    }
    rung[g].inf = 0xFF;
    wsg[g].inf = 0xFF;
  }

  const char *base_ptr = (const char *)allbk;
  for (long d = nbuckets - 1; d >= 1; --d) {
   for (int gi = 0; gi < ngroups; ++gi) {
    Jac8 &run = rung[gi];
    Jac8 &ws = wsg[gi];
    const __m512i vbase = vbaseg[gi];
    const __mmask8 act_lanes = actg[gi];
    const int nl = nlg[gi];
    const int *wisl = wisg[gi];
    const long long *lane_base = lane_baseg[gi];
    // the walk is perfectly predictable but gather-driven (no hardware
    // prefetch): pull the next TWO steps' bucket lines ahead of time —
    // 8 lanes x 80 B spans two cache lines each
    if (d > 2) {
      for (int l = 0; l < 8; ++l) {
        const char *nx = base_ptr + lane_base[l] + (d - 2) * (long long)sizeof(Aff52);
        _mm_prefetch(nx, _MM_HINT_T0);
        _mm_prefetch(nx + 64, _MM_HINT_T0);
      }
    }
    const __m512i doff = _mm512_add_epi64(
        vbase, _mm512_set1_epi64((long long)d * (long long)sizeof(Aff52)));
    __m512i x2[5], y2[5];
    for (int k = 0; k < 5; ++k) {
      x2[k] = _mm512_i64gather_epi64(
          _mm512_add_epi64(doff, _mm512_set1_epi64(8LL * k)),
          (const long long *)allbk, 1);
      y2[k] = _mm512_i64gather_epi64(
          _mm512_add_epi64(doff, _mm512_set1_epi64(40 + 8LL * k)),
          (const long long *)allbk, 1);
    }
    __mmask8 xz = 0xFF, yz = 0xFF;
    {
      const __m512i zero = _mm512_setzero_si512();
      for (int k = 0; k < 5; ++k) {
        xz &= _mm512_cmpeq_epu64_mask(x2[k], zero);
        yz &= _mm512_cmpeq_epu64_mask(y2[k], zero);
      }
    }
    const __mmask8 nz = act_lanes & (__mmask8)~(xz & yz);
    if (nz) {
      const __mmask8 fresh = nz & run.inf;
      const __mmask8 addm = nz & (__mmask8)~run.inf;
      if (addm) {
        // madd-2007-bl shape, all lanes computed, exceptional ones patched
        __m512i Z1Z1[5], U2[5], S2[5], H[5], Rr[5], HH[5], HHH[5], V[5];
        __m512i t[5], t2[5], X3[5], Y3[5], Z3[5];
        mont52_mul8(Z1Z1, run.Z, run.Z, p, pinv);
        mont52_mul8(U2, x2, Z1Z1, p, pinv);
        mont52_mul8(t, y2, run.Z, p, pinv);
        mont52_mul8(S2, t, Z1Z1, p, pinv);
        sub_lazy8(H, U2, run.X, p2, comp2p);
        sub_lazy8(Rr, S2, run.Y, p2, comp2p);
        const __mmask8 exc = addm & is0_lazy8v(H, p);
        const __mmask8 ok = addm & (__mmask8)~exc;
        mont52_mul8(HH, H, H, p, pinv);
        mont52_mul8(HHH, H, HH, p, pinv);
        mont52_mul8(V, run.X, HH, p, pinv);
        mont52_mul8(t, Rr, Rr, p, pinv);
        sub_lazy8(t, t, HHH, p2, comp2p);
        add_lazy8(t2, V, V, comp2p);
        sub_lazy8(X3, t, t2, p2, comp2p);
        sub_lazy8(t, V, X3, p2, comp2p);
        mont52_mul8(t, Rr, t, p, pinv);
        mont52_mul8(t2, run.Y, HHH, p, pinv);
        sub_lazy8(Y3, t, t2, p2, comp2p);
        mont52_mul8(Z3, run.Z, H, p, pinv);
        for (int k = 0; k < 5; ++k) {
          run.X[k] = _mm512_mask_blend_epi64(ok, run.X[k], X3[k]);
          run.Y[k] = _mm512_mask_blend_epi64(ok, run.Y[k], Y3[k]);
          run.Z[k] = _mm512_mask_blend_epi64(ok, run.Z[k], Z3[k]);
        }
        if (exc) {
          for (int l = 0; l < nl; ++l) {
            if (!((exc >> l) & 1)) continue;
            G1Jac g = jac8_lane(run, l, F);
            const Aff52 &b = allbk[(size_t)wisl[l] * (size_t)nbuckets + d];
            u64 bx4[4], by4[4];
            limb52_to_mont256(b.x, bx4, F);
            limb52_to_mont256(b.y, by4, F);
            jac_add_mixed(g, g, bx4, by4);
            jac8_set_lane(run, l, g, F);
          }
        }
      }
      if (fresh) {
        for (int k = 0; k < 5; ++k) {
          run.X[k] = _mm512_mask_blend_epi64(fresh, run.X[k], x2[k]);
          run.Y[k] = _mm512_mask_blend_epi64(fresh, run.Y[k], y2[k]);
          run.Z[k] = _mm512_mask_blend_epi64(fresh, run.Z[k], onev[k]);
        }
        run.inf &= (__mmask8)~fresh;
      }
    }
    // ws += run (add-2007-bl), lanes with run finite
    const __mmask8 a2 = act_lanes & (__mmask8)~run.inf;
    if (a2) {
      const __mmask8 copy = a2 & ws.inf;
      const __mmask8 addm = a2 & (__mmask8)~ws.inf;
      if (addm) {
        __m512i Z1Z1[5], Z2Z2[5], U1[5], U2[5], S1[5], S2[5], H[5], Rr[5];
        __m512i HH[5], HHH[5], V[5], t[5], t2[5], X3[5], Y3[5], Z3[5];
        mont52_mul8(Z1Z1, ws.Z, ws.Z, p, pinv);
        mont52_mul8(Z2Z2, run.Z, run.Z, p, pinv);
        mont52_mul8(U1, ws.X, Z2Z2, p, pinv);
        mont52_mul8(U2, run.X, Z1Z1, p, pinv);
        mont52_mul8(t, ws.Y, run.Z, p, pinv);
        mont52_mul8(S1, t, Z2Z2, p, pinv);
        mont52_mul8(t, run.Y, ws.Z, p, pinv);
        mont52_mul8(S2, t, Z1Z1, p, pinv);
        sub_lazy8(H, U2, U1, p2, comp2p);
        sub_lazy8(Rr, S2, S1, p2, comp2p);
        const __mmask8 exc = addm & is0_lazy8v(H, p);
        const __mmask8 ok = addm & (__mmask8)~exc;
        mont52_mul8(HH, H, H, p, pinv);
        mont52_mul8(HHH, H, HH, p, pinv);
        mont52_mul8(V, U1, HH, p, pinv);
        mont52_mul8(t, Rr, Rr, p, pinv);
        sub_lazy8(t, t, HHH, p2, comp2p);
        add_lazy8(t2, V, V, comp2p);
        sub_lazy8(X3, t, t2, p2, comp2p);
        sub_lazy8(t, V, X3, p2, comp2p);
        mont52_mul8(t, Rr, t, p, pinv);
        mont52_mul8(t2, S1, HHH, p, pinv);
        sub_lazy8(Y3, t, t2, p2, comp2p);
        mont52_mul8(t, ws.Z, run.Z, p, pinv);
        mont52_mul8(Z3, t, H, p, pinv);
        for (int k = 0; k < 5; ++k) {
          ws.X[k] = _mm512_mask_blend_epi64(ok, ws.X[k], X3[k]);
          ws.Y[k] = _mm512_mask_blend_epi64(ok, ws.Y[k], Y3[k]);
          ws.Z[k] = _mm512_mask_blend_epi64(ok, ws.Z[k], Z3[k]);
        }
        if (exc) {
          for (int l = 0; l < nl; ++l) {
            if (!((exc >> l) & 1)) continue;
            G1Jac g = jac8_lane(ws, l, F);
            G1Jac r = jac8_lane(run, l, F);
            g1_add_jac(g, r);
            jac8_set_lane(ws, l, g, F);
          }
        }
      }
      if (copy) {
        for (int k = 0; k < 5; ++k) {
          ws.X[k] = _mm512_mask_blend_epi64(copy, ws.X[k], run.X[k]);
          ws.Y[k] = _mm512_mask_blend_epi64(copy, ws.Y[k], run.Y[k]);
          ws.Z[k] = _mm512_mask_blend_epi64(copy, ws.Z[k], run.Z[k]);
        }
        ws.inf &= (__mmask8)~copy;
      }
    }
   }
  }
  for (int g = 0; g < ngroups; ++g)
    for (int l = 0; l < nlg[g]; ++l) outs[8 * g + l] = jac8_lane(wsg[g], l, F);
}

// 52-native batch-affine window fill: buckets AND bases in mont260
// 52-limb form.  `bases_xy` (mont256) is still taken for the Jacobian
// bail tier.
// Returns true when `bk_ext` (caller-zeroed, nbuckets entries) was filled
// and the caller must reduce it (the vectorized cross-window suffix);
// false when *out was already computed via a fallback tier (small/top
// window, conflict bail) or the internal suffix (bk_ext == nullptr).
static bool g1_window_sum_52(const u64 *bases_xy, const Aff52 *b52,
                             const int32_t *sd, long n, int c, int nwin,
                             int wi, G1Jac *out, Aff52 *bk_ext = nullptr,
                             int total_bits = 254) {
  Ifma52Field &F = fq52_field();
  const long nbuckets = (1L << (c - 1)) + 1;
  const long B = 2048;
  int bits_here = total_bits - wi * c;
  if (bits_here > c) bits_here = c;
  if (bits_here < 1 || (1L << bits_here) < 4 * B) {
    // bits_here == 0 is the GLV carry-only top window (GLV_MAX_BITS
    // divisible by c, e.g. 128 at c=16): digits are +-1 recoding
    // carries, exactly the few-buckets-many-points shape the small
    // path tree-sums (its nbuckets = (1<<bits)+2 headroom covers it).
    if (bits_here >= 0 && bits_here <= 8) {
      g1_window_sum_small(bases_xy, sd, n, c, nwin, wi, bits_here, out);
    } else {
      g1_window_sum_jac(bases_xy, sd, n, c, nwin, wi, out);
    }
    return false;
  }
  Aff52 *bk = bk_ext ? bk_ext : new Aff52[nbuckets]();
  int *stamp = new int[nbuckets];
  memset(stamp, 0xff, nbuckets * sizeof(int));
  std::vector<long> cur, next;
  cur.reserve(n);
  for (long i = 0; i < n; ++i) {
    if (!sd[i * nwin + wi]) continue;
    if (aff52_is_zero(b52[i].x) && aff52_is_zero(b52[i].y)) continue;
    cur.push_back(i);
  }
  long *add_bkt = new long[B];
  long *add_pt = new long[B];
  unsigned char *negf = new unsigned char[B];
  u64 (*x3a)[5] = new u64[B][5];
  u64 (*y3a)[5] = new u64[B][5];
  unsigned char *dbl = new unsigned char[B];
  u64 *scratch = new u64[(size_t)8 * 5 * B];
  auto cleanup = [&]() {
    if (!bk_ext) delete[] bk;
    delete[] stamp;
    delete[] add_bkt;
    delete[] add_pt;
    delete[] negf;
    delete[] x3a;
    delete[] y3a;
    delete[] dbl;
    delete[] scratch;
  };
  int chunk_id = 0;
  // stats: lane hits tallied in plain locals, flushed once per window —
  // the schedule loop itself must stay free of atomics
  long long n_dbl = 0, n_cancel = 0, n_defer = 0;
  long long fl0 = prof_now_ns();
  while (!cur.empty()) {
    next.clear();
    size_t processed = 0;
    bool bail = false;
    const bool pf = msm_interleave_enabled();
    for (size_t lo = 0; lo < cur.size() && !bail; lo += B, ++chunk_id) {
      size_t hi = lo + B < cur.size() ? lo + B : cur.size();
      long m = 0;
      for (size_t k = lo; k < hi; ++k) {
        // Two-level prefetch down the schedule: pull the digit word
        // first (far), then — once it is cheap to read — the dependent
        // stamp/bucket/base lines (near).  The bucket table and the
        // bases both sit beyond L2 at bench shape and the index
        // pattern is hardware-prefetch-blind.
        if (pf) {
          if (k + 32 < hi)
            _mm_prefetch((const char *)&sd[cur[k + 32] * nwin + wi],
                         _MM_HINT_T0);
          if (k + 16 < hi) {
            const long i2 = cur[k + 16];
            const int32_t d2 = sd[i2 * nwin + wi];
            const long b2 = d2 < 0 ? -d2 : d2;
            _mm_prefetch((const char *)&stamp[b2], _MM_HINT_T0);
            const char *pb = (const char *)&bk[b2];
            _mm_prefetch(pb, _MM_HINT_T0);
            _mm_prefetch(pb + 64, _MM_HINT_T0);
            const char *pp = (const char *)&b52[i2];
            _mm_prefetch(pp, _MM_HINT_T0);
            _mm_prefetch(pp + 64, _MM_HINT_T0);
          }
        }
        long i = cur[k];
        int32_t dgt = sd[i * nwin + wi];
        long bno = dgt < 0 ? -dgt : dgt;
        if (stamp[bno] == chunk_id) {
          next.push_back(i);
          ++n_defer;
          continue;
        }
        stamp[bno] = chunk_id;
        u64 py[5];
        if (dgt < 0) {
          neg52(py, b52[i].y, F);
        } else {
          memcpy(py, b52[i].y, 40);
        }
        if (aff52_is_zero(bk[bno].x) && aff52_is_zero(bk[bno].y)) {
          memcpy(bk[bno].x, b52[i].x, 40);
          memcpy(bk[bno].y, py, 40);
          continue;
        }
        if (memcmp(bk[bno].x, b52[i].x, 40) == 0) {
          if (memcmp(bk[bno].y, py, 40) == 0) {
            dbl[m] = 1;
            ++n_dbl;
          } else {
            memset(&bk[bno], 0, sizeof(Aff52));  // P + (-P)
            ++n_cancel;
            continue;
          }
        } else {
          dbl[m] = 0;
        }
        add_bkt[m] = bno;
        add_pt[m] = i;
        negf[m] = dgt < 0 ? 1 : 0;
        ++m;
      }
      processed = hi;
      if (!m) {
        if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
        continue;
      }
      long long ap0 = prof_now_ns();
      g1_chunk_apply_52(bk, b52, add_bkt, add_pt, negf, dbl, m, x3a, y3a, scratch);
      long long ap = prof_now_ns() - ap0;
      stat_add(ST_MSM_APPLY_NS, ap);
      if (msm_prof_enabled()) g_prof_apply_ns += ap;
      for (long j = 0; j < m; ++j) {
        // write-prefetch the bucket lines ahead: the chunk's working
        // set (~B x 160 B of buckets + scratch) evicted them since the
        // gather, so every writeback otherwise eats an RFO miss
        if (pf && j + 8 < m) {
          char *wb = (char *)&bk[add_bkt[j + 8]];
          __builtin_prefetch(wb, 1);
          __builtin_prefetch(wb + 64, 1);
        }
        memcpy(bk[add_bkt[j]].x, x3a[j], 40);
        memcpy(bk[add_bkt[j]].y, y3a[j], 40);
      }
      if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
    }
    if (bail || next.size() * 4 > cur.size()) {
      long long fl = prof_now_ns() - fl0;
      stat_add(ST_MSM_FILL_NS, fl);
      if (msm_prof_enabled()) g_prof_fill_ns += fl;
      stat_add(ST_MSM_DBL_LANES, n_dbl);
      stat_add(ST_MSM_CANCEL_LANES, n_cancel);
      stat_add(ST_MSM_DEFER_HITS, n_defer);
      long long bs0 = prof_now_ns();
      G1Jac *jb = new G1Jac[nbuckets];
      memset(jb, 0, (size_t)nbuckets * sizeof(G1Jac));
      next.insert(next.end(), cur.begin() + processed, cur.end());
      for (size_t bi = 0; bi < next.size(); ++bi) {
        // prefetch the next few adds' base/bucket lines: one Jacobian
        // mixed add (~16 scalar muls) is long enough to hide the miss
        if (pf && bi + 2 < next.size()) {
          const long i3 = next[bi + 2];
          const int32_t d3 = sd[i3 * nwin + wi];
          const char *px = (const char *)(bases_xy + 8 * i3);
          _mm_prefetch(px, _MM_HINT_T0);
          _mm_prefetch((const char *)&jb[d3 < 0 ? -d3 : d3], _MM_HINT_T0);
        }
        const long i = next[bi];
        int32_t dgt = sd[i * nwin + wi];
        long bno = dgt < 0 ? -dgt : dgt;
        const u64 *x = bases_xy + 8 * i;
        u64 ys[4];
        signed_pt_y(ys, x + 4, dgt < 0);
        jac_add_mixed(jb[bno], jb[bno], x, ys);
      }
      {
        long long bf = prof_now_ns() - bs0;
        stat_add(ST_MSM_BAILFILL_NS, bf);
        if (msm_prof_enabled()) g_prof_bailfill_ns += bf;
        bs0 = prof_now_ns();
      }
      G1Jac run, wsum;
      memset(&run, 0, sizeof(run));
      memset(&wsum, 0, sizeof(wsum));
      for (long d = nbuckets - 1; d >= 1; --d) {
        g1_add_jac(run, jb[d]);
        if (!(aff52_is_zero(bk[d].x) && aff52_is_zero(bk[d].y))) {
          u64 bx[4], by[4];
          limb52_to_mont256(bk[d].x, bx, F);
          limb52_to_mont256(bk[d].y, by, F);
          jac_add_mixed(run, run, bx, by);
        }
        g1_add_jac(wsum, run);
      }
      {
        long long sf = prof_now_ns() - bs0;
        stat_add(ST_MSM_SUFFIX_NS, sf);
        if (msm_prof_enabled()) g_prof_suffix_ns += sf;
      }
      delete[] jb;
      cleanup();
      *out = wsum;
      return false;
    }
    cur.swap(next);
  }
  {
    long long fl = prof_now_ns() - fl0;  // incl. apply; sched = fill - apply
    stat_add(ST_MSM_FILL_NS, fl);
    if (msm_prof_enabled()) g_prof_fill_ns += fl;
    stat_add(ST_MSM_DBL_LANES, n_dbl);
    stat_add(ST_MSM_CANCEL_LANES, n_cancel);
    stat_add(ST_MSM_DEFER_HITS, n_defer);
  }
  if (bk_ext) {
    // caller reduces this window through the 8-lane vector suffix
    cleanup();
    return true;
  }
  long long sf0 = prof_now_ns();
  G1Jac run, wsum;
  memset(&run, 0, sizeof(run));
  memset(&wsum, 0, sizeof(wsum));
  for (long d = nbuckets - 1; d >= 1; --d) {
    if (!(aff52_is_zero(bk[d].x) && aff52_is_zero(bk[d].y))) {
      u64 bx[4], by[4];
      limb52_to_mont256(bk[d].x, bx, F);
      limb52_to_mont256(bk[d].y, by, F);
      jac_add_mixed(run, run, bx, by);
    }
    g1_add_jac(wsum, run);
  }
  {
    long long sf = prof_now_ns() - sf0;
    stat_add(ST_MSM_SUFFIX_NS, sf);
    if (msm_prof_enabled()) g_prof_suffix_ns += sf;
  }
  cleanup();
  *out = wsum;
  return false;
}

// ---- Fq2 vector helpers (u^2 = -1): componentwise lazy-domain ops on
// top of mont52_mul8.  An Fq2 value is two limb-vector sets (c0, c1).

static inline void fq2_mul8(__m512i o0[5], __m512i o1[5],
                            const __m512i a0[5], const __m512i a1[5],
                            const __m512i b0[5], const __m512i b1[5],
                            const __m512i p[5], const __m512i p2[5],
                            const __m512i comp2p[5], const __m512i pinv) {
  // Karatsuba over the tower: t0=a0b0, t1=a1b1, t2=(a0+a1)(b0+b1)
  __m512i t0[5], t1[5], t2[5], sa[5], sb[5];
  mont52_mul8(t0, a0, b0, p, pinv);
  mont52_mul8(t1, a1, b1, p, pinv);
  add_lazy8(sa, a0, a1, comp2p);
  add_lazy8(sb, b0, b1, comp2p);
  mont52_mul8(t2, sa, sb, p, pinv);
  sub_lazy8(o0, t0, t1, p2, comp2p);            // a0b0 - a1b1
  sub_lazy8(t2, t2, t0, p2, comp2p);
  sub_lazy8(o1, t2, t1, p2, comp2p);            // a0b1 + a1b0
}

static inline void fq2_sqr8(__m512i o0[5], __m512i o1[5],
                            const __m512i a0[5], const __m512i a1[5],
                            const __m512i p[5], const __m512i p2[5],
                            const __m512i comp2p[5], const __m512i pinv) {
  // (a0+a1u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u
  __m512i s[5], d[5], m[5];
  add_lazy8(s, a0, a1, comp2p);
  sub_lazy8(d, a0, a1, p2, comp2p);
  mont52_mul8(o0, s, d, p, pinv);
  mont52_mul8(m, a0, a1, p, pinv);
  add_lazy8(o1, m, m, comp2p);
}

// The G2 mirror of g1_chunk_apply_ifma: every array carries TWO Fq
// components per value ((m,8) u64 rows: c0 then c1).  Batch inversion
// rides the NORM route (1/z = conj(z)/(c0^2+c1^2)): prefix/suffix over
// Fq norms + ONE scalar Fq2 inversion per chunk — fewer vector muls
// than an Fq2 product chain.  Outputs canonical (< p) per component so
// the caller's memcmp bucket checks keep working.
static void g2_chunk_apply_ifma(const u64 (*x1a)[8], const u64 (*y1a)[8],
                                const u64 (*x2a)[8], const u64 (*y2a)[8],
                                const unsigned char *dbl, long m,
                                u64 (*x3a)[8], u64 (*y3a)[8], u64 *buf) {
  Ifma52Field &F = fq52_field();
  const long nblk = (m + 7) / 8, N = nblk * 8;
  // SoA planes per COMPONENT: x1/y1/x2/y2/den/num (2 comps each) +
  // norm-prefix (1) + x3/y3 (2 each) = 17 arrays x 5 planes x N
  u64 *x10 = buf, *x11 = buf + (size_t)5 * N;
  u64 *y10 = buf + (size_t)10 * N, *y11 = buf + (size_t)15 * N;
  u64 *x20 = buf + (size_t)20 * N, *x21 = buf + (size_t)25 * N;
  u64 *y20 = buf + (size_t)30 * N, *y21 = buf + (size_t)35 * N;
  u64 *d0 = buf + (size_t)40 * N, *d1 = buf + (size_t)45 * N;
  u64 *n0 = buf + (size_t)50 * N, *n1 = buf + (size_t)55 * N;
  u64 *pr = buf + (size_t)60 * N;
  u64 *x30 = buf + (size_t)65 * N, *x31 = buf + (size_t)70 * N;
  u64 *y30 = buf + (size_t)75 * N, *y31 = buf + (size_t)80 * N;

  u64 one52[5] = {1, 0, 0, 0, 0}, one260[5];
  mont52_mul_scalar(one260, one52, F.r260sq, F);
  auto pack_comp = [&](const u64 (*src)[8], int comp, u64 *dst) {
    for (long j = 0; j < N; ++j) {
      u64 t[5] = {0, 0, 0, 0, 0};
      if (j < m) limbs4_to_52(t, src[j] + 4 * comp);
      for (int k = 0; k < 5; ++k) dst[(size_t)k * N + j] = t[k];
    }
  };
  pack_comp(x1a, 0, x10); pack_comp(x1a, 1, x11);
  pack_comp(y1a, 0, y10); pack_comp(y1a, 1, y11);
  pack_comp(x2a, 0, x20); pack_comp(x2a, 1, x21);
  pack_comp(y2a, 0, y20); pack_comp(y2a, 1, y21);

  __m512i p[5], p2[5], comp2p[5], c264v[5], c256v[5];
  for (int k = 0; k < 5; ++k) {
    p[k] = _mm512_set1_epi64((long long)F.p52[k]);
    p2[k] = _mm512_set1_epi64((long long)F.p2_52[k]);
    comp2p[k] = _mm512_set1_epi64((long long)F.comp2p[k]);
    c264v[k] = _mm512_set1_epi64((long long)F.c264[k]);
    c256v[k] = _mm512_set1_epi64((long long)F.c256[k]);
  }
  const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
  auto loadv = [&](const u64 *base, long off, __m512i v[5]) {
    for (int k = 0; k < 5; ++k) v[k] = _mm512_loadu_si512(base + (size_t)k * N + off);
  };
  auto storev = [&](u64 *base, long off, const __m512i v[5]) {
    for (int k = 0; k < 5; ++k) _mm512_storeu_si512(base + (size_t)k * N + off, v[k]);
  };
  // carrier 256 -> 260 + derive num/den per block
  for (long t = 0; t < nblk; ++t) {
    u64 *comps[8] = {x10, x11, y10, y11, x20, x21, y20, y21};
    __m512i cv[8][5];
    for (int a = 0; a < 8; ++a) {
      __m512i v[5];
      loadv(comps[a], t * 8, v);
      mont52_mul8(cv[a], v, c264v, p, pinv);
      storev(comps[a], t * 8, cv[a]);
    }
    __m512i dv0[5], dv1[5], nv0[5], nv1[5];
    sub_lazy8(dv0, cv[4], cv[0], p2, comp2p);  // x2 - x1 (c0)
    sub_lazy8(dv1, cv[5], cv[1], p2, comp2p);  // (c1)
    sub_lazy8(nv0, cv[6], cv[2], p2, comp2p);  // y2 - y1 (c0)
    sub_lazy8(nv1, cv[7], cv[3], p2, comp2p);
    unsigned char dm = 0;
    for (int l = 0; l < 8 && t * 8 + l < m; ++l)
      if (dbl[t * 8 + l]) dm |= (unsigned char)(1u << l);
    if (dm) {
      // doubling: num = 3 x1^2, den = 2 y1 (component-wise over Fq2)
      __m512i sq0[5], sq1[5], nd0[5], nd1[5], dd0[5], dd1[5];
      fq2_sqr8(sq0, sq1, cv[0], cv[1], p, p2, comp2p, pinv);
      add_lazy8(nd0, sq0, sq0, comp2p);
      add_lazy8(nd0, nd0, sq0, comp2p);
      add_lazy8(nd1, sq1, sq1, comp2p);
      add_lazy8(nd1, nd1, sq1, comp2p);
      add_lazy8(dd0, cv[2], cv[2], comp2p);
      add_lazy8(dd1, cv[3], cv[3], comp2p);
      const __mmask8 k = (__mmask8)dm;
      for (int q = 0; q < 5; ++q) {
        dv0[q] = _mm512_mask_blend_epi64(k, dv0[q], dd0[q]);
        dv1[q] = _mm512_mask_blend_epi64(k, dv1[q], dd1[q]);
        nv0[q] = _mm512_mask_blend_epi64(k, nv0[q], nd0[q]);
        nv1[q] = _mm512_mask_blend_epi64(k, nv1[q], nd1[q]);
      }
    }
    storev(d0, t * 8, dv0); storev(d1, t * 8, dv1);
    storev(n0, t * 8, nv0); storev(n1, t * 8, nv1);
  }
  // phase A: prefix products over the Fq NORMS (norm = d0^2 + d1^2);
  // padded lanes get norm ONE via a blend
  __m512i run[5];
  for (int k = 0; k < 5; ++k) run[k] = _mm512_set1_epi64((long long)one260[k]);
  for (long t = 0; t < nblk; ++t) {
    __m512i dv0[5], dv1[5], s0[5], s1[5], norm[5];
    loadv(d0, t * 8, dv0); loadv(d1, t * 8, dv1);
    mont52_mul8(s0, dv0, dv0, p, pinv);
    mont52_mul8(s1, dv1, dv1, p, pinv);
    add_lazy8(norm, s0, s1, comp2p);
    if (t == nblk - 1 && m < N) {
      __mmask8 padk = (__mmask8)(0xFFu << (m & 7 ? (m & 7) : 8));
      for (int q = 0; q < 5; ++q)
        norm[q] = _mm512_mask_blend_epi64(padk, norm[q], _mm512_set1_epi64((long long)one260[q]));
    }
    storev(pr, t * 8, run);  // product of norms BEFORE this block's lanes
    // interleave: we need a LANE-STRIDED chain like g1 — run *= norm
    mont52_mul8(run, run, norm, p, pinv);
    // stash the norm where den c0 plane... norms are recomputed in
    // phase B, so nothing extra to store
  }
  // one scalar Fq2-ish inversion: invert the 8 lane-total NORMS in Fq
  u64 tl8[5][8];
  for (int k = 0; k < 5; ++k) _mm512_storeu_si512(tl8[k], run[k]);
  u64 T4[8][4];
  for (int l = 0; l < 8; ++l) {
    u64 t52[5], t256[5];
    for (int k = 0; k < 5; ++k) t52[k] = tl8[k][l];
    mont52_mul_scalar(t256, t52, F.c256, F);
    limbs52_to_4(T4[l], t256);
    while (geq(T4[l], P)) sub_nored(T4[l], T4[l], P);
  }
  u64 pre8[8][4], G[4], Ginv[4], suf[4], Tinv[8][4];
  memcpy(pre8[0], ONE_MONT, 32);
  for (int l = 1; l < 8; ++l) mont_mul(pre8[l], pre8[l - 1], T4[l - 1]);
  mont_mul(G, pre8[7], T4[7]);
  mont_inv(Ginv, G);
  memcpy(suf, Ginv, 32);
  for (int l = 7; l >= 0; --l) {
    mont_mul(Tinv[l], suf, pre8[l]);
    mont_mul(suf, suf, T4[l]);
  }
  __m512i inv_run[5];
  {
    u64 ir8[5][8];
    for (int l = 0; l < 8; ++l) {
      u64 t52[5], t260[5];
      limbs4_to_52(t52, Tinv[l]);
      mont52_mul_scalar(t260, t52, F.c264, F);
      for (int k = 0; k < 5; ++k) ir8[k][l] = t260[k];
    }
    for (int k = 0; k < 5; ++k) inv_run[k] = _mm512_loadu_si512(ir8[k]);
  }
  // phase B backwards: norm_inv -> dinv = conj(den) * norm_inv -> apply
  for (long t = nblk - 1; t >= 0; --t) {
    __m512i prv[5], dv0[5], dv1[5], s0[5], s1[5], norm[5];
    loadv(pr, t * 8, prv);
    loadv(d0, t * 8, dv0); loadv(d1, t * 8, dv1);
    mont52_mul8(s0, dv0, dv0, p, pinv);
    mont52_mul8(s1, dv1, dv1, p, pinv);
    add_lazy8(norm, s0, s1, comp2p);
    if (t == nblk - 1 && m < N) {
      __mmask8 padk = (__mmask8)(0xFFu << (m & 7 ? (m & 7) : 8));
      for (int q = 0; q < 5; ++q)
        norm[q] = _mm512_mask_blend_epi64(padk, norm[q], _mm512_set1_epi64((long long)one260[q]));
    }
    __m512i ninv[5];
    mont52_mul8(ninv, inv_run, prv, p, pinv);    // 1/norm for these lanes
    mont52_mul8(inv_run, inv_run, norm, p, pinv);
    // dinv = (d0 - d1 u) * ninv
    __m512i di0[5], di1[5], zt[5];
    mont52_mul8(di0, dv0, ninv, p, pinv);
    mont52_mul8(zt, dv1, ninv, p, pinv);
    // negate: 2p - x (lazy) via sub_lazy8 from zero
    __m512i zero5[5];
    for (int k = 0; k < 5; ++k) zero5[k] = _mm512_setzero_si512();
    sub_lazy8(di1, zero5, zt, p2, comp2p);
    __m512i nv0[5], nv1[5], x1v0[5], x1v1[5], y1v0[5], y1v1[5], x2v0[5], x2v1[5];
    loadv(n0, t * 8, nv0); loadv(n1, t * 8, nv1);
    loadv(x10, t * 8, x1v0); loadv(x11, t * 8, x1v1);
    loadv(y10, t * 8, y1v0); loadv(y11, t * 8, y1v1);
    loadv(x20, t * 8, x2v0); loadv(x21, t * 8, x2v1);
    __m512i lam0[5], lam1[5], l20[5], l21[5], x3v0[5], x3v1[5], tt0[5], tt1[5], yy0[5], yy1[5], y3v0[5], y3v1[5];
    fq2_mul8(lam0, lam1, nv0, nv1, di0, di1, p, p2, comp2p, pinv);
    fq2_sqr8(l20, l21, lam0, lam1, p, p2, comp2p, pinv);
    sub_lazy8(x3v0, l20, x1v0, p2, comp2p);
    sub_lazy8(x3v1, l21, x1v1, p2, comp2p);
    sub_lazy8(x3v0, x3v0, x2v0, p2, comp2p);
    sub_lazy8(x3v1, x3v1, x2v1, p2, comp2p);
    sub_lazy8(tt0, x1v0, x3v0, p2, comp2p);
    sub_lazy8(tt1, x1v1, x3v1, p2, comp2p);
    fq2_mul8(yy0, yy1, lam0, lam1, tt0, tt1, p, p2, comp2p, pinv);
    sub_lazy8(y3v0, yy0, y1v0, p2, comp2p);
    sub_lazy8(y3v1, yy1, y1v1, p2, comp2p);
    // carrier back to 256
    mont52_mul8(x3v0, x3v0, c256v, p, pinv);
    mont52_mul8(x3v1, x3v1, c256v, p, pinv);
    mont52_mul8(y3v0, y3v0, c256v, p, pinv);
    mont52_mul8(y3v1, y3v1, c256v, p, pinv);
    storev(x30, t * 8, x3v0); storev(x31, t * 8, x3v1);
    storev(y30, t * 8, y3v0); storev(y31, t * 8, y3v1);
  }
  // unpack, fully reduced
  auto unpack_comp = [&](const u64 *src, u64 (*dst)[8], int comp) {
    for (long j = 0; j < m; ++j) {
      u64 t[5], o[4];
      for (int k = 0; k < 5; ++k) t[k] = src[(size_t)k * N + j];
      limbs52_to_4(o, t);
      while (geq(o, P)) sub_nored(o, o, P);
      memcpy(dst[j] + 4 * comp, o, 32);
    }
  };
  unpack_comp(x30, x3a, 0); unpack_comp(x31, x3a, 1);
  unpack_comp(y30, y3a, 0); unpack_comp(y31, y3a, 1);
}

// G2 pairwise tree sum (the scalar==±1 fast path, Fq2 mirror of
// g1_tree_sum).  xs/ys rows are (c0, c1) pairs = 8 u64; consumed.
static void g2_tree_sum(u64 (*xs)[8], u64 (*ys)[8], long n, G2Jac *out) {
  memset(out, 0, sizeof(G2Jac));
  if (n <= 0) return;
  auto is_inf = [](const u64 *x, const u64 *y) {
    return is_zero4(x) && is_zero4(x + 4) && is_zero4(y) && is_zero4(y + 4);
  };
  auto add_into = [&](const u64 *x, const u64 *y) {
    Fp2 xx, yy;
    memcpy(xx.c0, x, 32); memcpy(xx.c1, x + 4, 32);
    memcpy(yy.c0, y, 32); memcpy(yy.c1, y + 4, 32);
    g2_add_mixed(*out, *out, xx, yy);
  };
  if (ifma_enabled() && n >= 64) {
    const long B = 1024;
    u64 (*x1a)[8] = new u64[B][8];
    u64 (*y1a)[8] = new u64[B][8];
    u64 (*x2a)[8] = new u64[B][8];
    u64 (*y2a)[8] = new u64[B][8];
    u64 (*x3a)[8] = new u64[B][8];
    u64 (*y3a)[8] = new u64[B][8];
    unsigned char *dbl = new unsigned char[B];
    u64 *scratch = new u64[(size_t)17 * 5 * B];
    while (n > 1) {
      long w = 0, ppos = 0;
      while (ppos + 1 < n) {
        long m = 0;
        while (ppos + 1 < n && m < B) {
          u64 *x1 = xs[ppos], *y1 = ys[ppos], *x2 = xs[ppos + 1], *y2 = ys[ppos + 1];
          bool i1 = is_inf(x1, y1), i2 = is_inf(x2, y2);
          if (i1 && i2) { ppos += 2; continue; }
          if (i1 || i2) {
            memcpy(xs[w], i1 ? x2 : x1, 64);
            memcpy(ys[w], i1 ? y2 : y1, 64);
            ++w; ppos += 2; continue;
          }
          if (memcmp(x1, x2, 64) == 0) {
            if (memcmp(y1, y2, 64) == 0) {
              dbl[m] = 1;
            } else {
              ppos += 2; continue;  // P + (-P)
            }
          } else {
            dbl[m] = 0;
          }
          memcpy(x1a[m], x1, 64);
          memcpy(y1a[m], y1, 64);
          memcpy(x2a[m], x2, 64);
          memcpy(y2a[m], y2, 64);
          ++m; ppos += 2;
        }
        if (m > 0) {
          g2_chunk_apply_ifma(x1a, y1a, x2a, y2a, dbl, m, x3a, y3a, scratch);
          for (long j = 0; j < m; ++j) {
            memcpy(xs[w], x3a[j], 64);
            memcpy(ys[w], y3a[j], 64);
            ++w;
          }
        }
      }
      if (ppos < n) {
        memcpy(xs[w], xs[ppos], 64);
        memcpy(ys[w], ys[ppos], 64);
        ++w;
      }
      n = w;
    }
    delete[] x1a; delete[] y1a; delete[] x2a; delete[] y2a;
    delete[] x3a; delete[] y3a; delete[] dbl; delete[] scratch;
    if (n == 1 && !is_inf(xs[0], ys[0])) add_into(xs[0], ys[0]);
    return;
  }
  for (long i = 0; i < n; ++i) {
    if (!is_inf(xs[i], ys[i])) add_into(xs[i], ys[i]);
  }
}

#else
#define ZKP2P_HAVE_IFMA 0
static bool ifma_enabled() { return false; }
#endif  // __AVX512IFMA__

#if ZKP2P_HAVE_IFMA
// One 8-row step of the Fr batch-pass vector tier: pack 8 contiguous
// (4 u64) rows to 52-limb lanes, multiply by one or two mont260
// constant vectors (carrier bookkeeping lives in the CALLER's constant
// choice), canonical-fold, unpack.  Shared by the batch mul/convert
// passes below — each was a scalar fr_mul-per-row loop on the prove
// path (m rows each: the pointwise Cz product, the witness to-mont, the
// ladder's d from-mont), together ~3 full scalar Montgomery passes per
// proof.
static inline void fr_batch8_mul2(const u64 *a8, const __m512i *b52,
                                  const __m512i c1[5], const __m512i c2[5],
                                  const __m512i p[5], const __m512i pinv,
                                  const __m512i comppv[5], u64 *out8) {
  u64 tmp[5][8];
  for (int l = 0; l < 8; ++l) {
    u64 t[5];
    limbs4_to_52(t, a8 + 4 * l);
    for (int k = 0; k < 5; ++k) tmp[k][l] = t[k];
  }
  __m512i x[5], y[5];
  for (int k = 0; k < 5; ++k) x[k] = _mm512_loadu_si512(tmp[k]);
  if (b52 != nullptr) {
    mont52_mul8(y, x, b52, p, pinv);
  } else {
    for (int k = 0; k < 5; ++k) y[k] = x[k];
  }
  mont52_mul8(x, y, c1, p, pinv);
  if (c2 != nullptr) {
    mont52_mul8(y, x, c2, p, pinv);
  } else {
    for (int k = 0; k < 5; ++k) y[k] = x[k];
  }
  cond_sub_c8(y, comppv);  // canonical (< r): callers' memcmp contracts
  for (int k = 0; k < 5; ++k) _mm512_storeu_si512(tmp[k], y[k]);
  for (int l = 0; l < 8; ++l) {
    u64 t[5], o[4];
    for (int k = 0; k < 5; ++k) t[k] = tmp[k][l];
    limbs52_to_4(o, t);
    memcpy(out8 + 4 * l, o, 32);
  }
}

// The Fr batch-pass tier gate: vector core present AND the pool knob on
// (ZKP2P_NTT_POOL gates the whole Fr vector-batch tier — stages, fused
// ladder, and these passes — so the knob-off arm reproduces the full
// pre-tier scalar path for A/Bs).
static bool fr_batch_vector_on(long n) {
  return ifma_enabled() && ntt_pool_enabled() && n >= 256;
}
#endif  // ZKP2P_HAVE_IFMA

extern "C" {

// Batch std <-> Montgomery over r.  IFMA tier (pool-split, 8-wide):
// to-mont multiplies by 2^520 then the 2^256 carrier (in·2^260·2^-4 =
// in·2^256); from-mont is ONE mul by the plain constant 16
// (in·16·2^-260 = in·2^-256) — both exactly the scalar results,
// canonically reduced.
void fr_to_mont_batch(const u64 *in, u64 *out, long n) {
#if ZKP2P_HAVE_IFMA
  if (fr_batch_vector_on(n)) {
    Ifma52Field &F = fr52_field();
    __m512i p[5], comppv[5], c1[5], c2[5];
    for (int k = 0; k < 5; ++k) {
      p[k] = _mm512_set1_epi64((long long)F.p52[k]);
      comppv[k] = _mm512_set1_epi64((long long)F.compp[k]);
      c1[k] = _mm512_set1_epi64((long long)F.r260sq[k]);
      c2[k] = _mm512_set1_epi64((long long)F.c256[k]);
    }
    const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
    long nblk = n / 8;
    pool_parallel_ranges(nblk, 1024, pool_default_threads(), [&](long lo, long hi) {
      for (long b = lo; b < hi; ++b)
        fr_batch8_mul2(in + 32 * b, nullptr, c1, c2, p, pinv, comppv, out + 32 * b);
    });
    for (long i = nblk * 8; i < n; ++i) fr_mul(out + 4 * i, in + 4 * i, R2R);
    return;
  }
#endif
  for (long i = 0; i < n; ++i) fr_mul(out + 4 * i, in + 4 * i, R2R);
}
void fr_from_mont_batch(const u64 *in, u64 *out, long n) {
  static const u64 ONE_STD[4] = {1, 0, 0, 0};
#if ZKP2P_HAVE_IFMA
  if (fr_batch_vector_on(n)) {
    Ifma52Field &F = fr52_field();
    __m512i p[5], comppv[5], c1[5];
    for (int k = 0; k < 5; ++k) {
      p[k] = _mm512_set1_epi64((long long)F.p52[k]);
      comppv[k] = _mm512_set1_epi64((long long)F.compp[k]);
      c1[k] = _mm512_set1_epi64(k == 0 ? 16LL : 0LL);  // 2^4: 260 -> 256
    }
    const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
    long nblk = n / 8;
    pool_parallel_ranges(nblk, 1024, pool_default_threads(), [&](long lo, long hi) {
      for (long b = lo; b < hi; ++b)
        fr_batch8_mul2(in + 32 * b, nullptr, c1, nullptr, p, pinv, comppv, out + 32 * b);
    });
    for (long i = nblk * 8; i < n; ++i) fr_mul(out + 4 * i, in + 4 * i, ONE_STD);
    return;
  }
#endif
  for (long i = 0; i < n; ++i) fr_mul(out + 4 * i, in + 4 * i, ONE_STD);
}
// In-place x mod r for n rows of 4 u64, any x < 2^256.  The witness
// conversion hot loop: Python now serializes raw
// int bytes and this replaces the per-element bigint `w % R`.  Since
// 2^256 / r ~ 5.3 the loop runs at most 5 conditional subtracts, and
// the common already-reduced row exits on the first compare — the pass
// is memory-bound, so no vector tier applies (the IFMA build runs this
// same scalar loop; a transposed 8-wide compare-subtract was modeled
// and the limb shuffles alone exceed the subtract work).
void fr_reduce_batch(u64 *inout, long n) {
  for (long i = 0; i < n; ++i) {
    u64 *v = inout + 4 * i;
    while (geq(v, R_MOD)) sub_nored(v, v, R_MOD);
  }
}

// Pointwise Montgomery product (c_ev = a_ev . b_ev).  IFMA tier: two
// mul8 per 8 rows (a·b·2^-260 = ab·2^252, then the 2^264 carrier
// restores mont256) vs 8 scalar fr_muls — exactly the scalar bytes.
void fr_mul_batch(const u64 *a, const u64 *b, u64 *out, long n) {
#if ZKP2P_HAVE_IFMA
  if (fr_batch_vector_on(n)) {
    Ifma52Field &F = fr52_field();
    __m512i p[5], comppv[5], c1[5];
    for (int k = 0; k < 5; ++k) {
      p[k] = _mm512_set1_epi64((long long)F.p52[k]);
      comppv[k] = _mm512_set1_epi64((long long)F.compp[k]);
      c1[k] = _mm512_set1_epi64((long long)F.c264[k]);
    }
    const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
    long nblk = n / 8;
    pool_parallel_ranges(nblk, 1024, pool_default_threads(), [&](long lo, long hi) {
      for (long b8 = lo; b8 < hi; ++b8) {
        u64 tmp[5][8];
        for (int l = 0; l < 8; ++l) {
          u64 t[5];
          limbs4_to_52(t, b + 32 * b8 + 4 * l);
          for (int k = 0; k < 5; ++k) tmp[k][l] = t[k];
        }
        __m512i bv[5];
        for (int k = 0; k < 5; ++k) bv[k] = _mm512_loadu_si512(tmp[k]);
        fr_batch8_mul2(a + 32 * b8, bv, c1, nullptr, p, pinv, comppv, out + 32 * b8);
      }
    });
    for (long i = nblk * 8; i < n; ++i) fr_mul(out + 4 * i, a + 4 * i, b + 4 * i);
    return;
  }
#endif
  for (long i = 0; i < n; ++i) fr_mul(out + 4 * i, a + 4 * i, b + 4 * i);
}
// Self-test hook: c = a*b mod r, standard form in/out.
void fr_mul_std(const u64 *a, const u64 *b, u64 *c) {
  u64 am[4], bm[4], cm[4];
  static const u64 ONE_STD[4] = {1, 0, 0, 0};
  fr_mul(am, a, R2R);
  fr_mul(bm, b, R2R);
  fr_mul(cm, am, bm);
  fr_mul(c, cm, ONE_STD);
}

// Sparse QAP matvec: out[row[i]] += coeff[i] * w[wire[i]] (all Montgomery).
void fr_matvec(const u64 *coeff, const unsigned *wire, const unsigned *row,
               long nnz, const u64 *w, long m, u64 *out) {
  long long wall0 = prof_now_ns();
  memset(out, 0, (size_t)m * 32);
  u64 t[4];
  for (long i = 0; i < nnz; ++i) {
    fr_mul(t, coeff + 4 * i, w + 4 * (long)wire[i]);
    u64 *o = out + 4 * (long)row[i];
    fr_add(o, o, t);
  }
  stat_add(ST_MATVEC_NS, prof_now_ns() - wall0);
}

// ---------------------------------------------------------------------------
// Segmented matvec (the presorted-plan tier; docs/TUNING.md §non-MSM).
//
// fr_matvec above is a serial read-modify-write scatter: out[row[i]] +=
// coeff[i]*w[wire[i]] in nnz order, which blocks both vectorization (at
// ~2-4 nnz per QAP row the Montgomery mul IS the stage) and threading
// (two workers may hit one output row).  The plan — built once per key
// on the Python side (prover.matvec_plan) and persisted beside the
// precomp tables — presorts the nnz by output row, turning the stage
// into nseg independent "sum one contiguous run of products" segments:
//
//   * the PRODUCTS vectorize ACROSS segment boundaries (independent by
//     definition): 8-wide 5x52 IFMA Montgomery muls over gathered wire
//     values, canonically reduced in-register;
//   * the ACCUMULATION is a scalar fr_add walk over canonical products
//     — field addition is exact, so the output bytes match the scatter
//     oracle for any order;
//   * the SEGMENT space partitions across the WorkPool with zero
//     scatter conflicts by construction (each worker owns a disjoint
//     row range of the plan).
//
// Montgomery bookkeeping: w arrives mont256; the packed plan coeffs are
// pre-multiplied by the 2^264 carrier (mont256 -> mont260), so one
// mont260 vector mul yields the mont256 product directly — the same
// constants-in-mont260 rule the NTT vector pipeline rides (see the
// 52-bit core comment block).

// Pack the plan's permuted mont256 coeffs into mont260 8-lane SoA
// blocks (block b = plan entries 8b..8b+7; 5 planes x 8 u64 each, so
// ceil(nnz/8)*40 u64 out).  Returns 1 on the IFMA tier, 0 when the
// vector core is unavailable (caller then passes coeff52 = NULL and the
// segmented driver runs its scalar product loop — still pool-parallel).
int fr_matvec_pack52(const u64 *coeff_mont, long nnz, u64 *out52) {
#if ZKP2P_HAVE_IFMA
  if (!ifma_enabled() || nnz <= 0) return ifma_enabled() && nnz == 0 ? 1 : 0;
  Ifma52Field &F = fr52_field();
  long nblk = (nnz + 7) / 8;
  // zero the pad lanes of the last block so they never carry garbage
  // into a vector register (they are multiplied but never stored)
  memset(out52 + (size_t)(nblk - 1) * 40, 0, 40 * sizeof(u64));
  pool_parallel_ranges(nnz, 1L << 14, pool_default_threads(), [&](long lo, long hi) {
    for (long i = lo; i < hi; ++i) {
      u64 t[5], t260[5];
      limbs4_to_52(t, coeff_mont + 4 * i);
      mont52_mul_scalar(t260, t, F.c264, F);  // carrier 256 -> 260
      u64 *blk = out52 + (size_t)(i / 8) * 40;
      for (int k = 0; k < 5; ++k) blk[k * 8 + (i & 7)] = t260[k];
    }
  });
  return 1;
#else
  (void)coeff_mont;
  (void)nnz;
  (void)out52;
  return 0;
#endif
}

// Segmented-plan matvec: plan entries are presorted by output row;
// segment s covers plan indices [seg_starts[s], seg_starts[s+1]) and
// sums into out[seg_rows[s]].  coeff52 is the fr_matvec_pack52 output
// (NULL = scalar product tier); coeff_mont the permuted mont256 coeffs
// (always required: scalar tier, unaligned heads/tails).  Rows not
// named by any segment stay zero, matching the oracle's memset.
void fr_matvec_seg(const u64 *coeff52, const u64 *coeff_mont,
                   const unsigned *wire, const long long *seg_starts,
                   const unsigned *seg_rows, long nseg, const u64 *w,
                   long m, int n_threads, u64 *out) {
  long long wall0 = prof_now_ns();
  stat_add(ST_MATVEC_SEG_CALLS, 1);
  memset(out, 0, (size_t)m * 32);
  if (nseg <= 0) {
    stat_add(ST_MATVEC_NS, prof_now_ns() - wall0);
    return;
  }
  const long nnz_total = seg_starts[nseg];
  // chunk boundaries in SEGMENT space, balanced by nnz: worker c owns
  // segments [bounds[c], bounds[c+1]) — disjoint output rows, so no
  // two workers ever touch one out entry.
  int nchunk = 1;
  if (n_threads > 1 && !g_pool_worker && nseg > 1) {
    long want = (long)n_threads * 4;
    if (want > nseg) want = nseg;
    long by_grain = nnz_total / 4096;  // per-chunk minimum work
    if (want > by_grain) want = by_grain;
    nchunk = want > 1 ? (int)want : 1;
  }
  std::vector<long> bounds((size_t)nchunk + 1);
  bounds[0] = 0;
  for (int ci = 1; ci < nchunk; ++ci) {
    long target = nnz_total / nchunk * ci;
    long lo = bounds[ci - 1], hi = nseg;
    while (lo < hi) {  // first segment starting at/after the nnz target
      long mid = (lo + hi) / 2;
      if (seg_starts[mid] < target) lo = mid + 1; else hi = mid;
    }
    bounds[ci] = lo;
  }
  bounds[nchunk] = nseg;

  auto run_chunk = [&](long ci) {
    long sa = bounds[ci], sb = bounds[ci + 1];
    if (sa >= sb) return;
    const long i0 = seg_starts[sa], i1 = seg_starts[sb];
    const long CHV = 2048;  // product-slice length (4 planes -> 64 KB, L2-warm)
    static thread_local std::vector<u64> scratch;
    if ((long)scratch.size() < 4 * CHV) scratch.assign(4 * CHV, 0);
    u64 *pr0 = scratch.data(), *pr1 = pr0 + CHV, *pr2 = pr1 + CHV, *pr3 = pr2 + CHV;
    long seg = sa;
    u64 acc[4] = {0, 0, 0, 0};
    for (long base = i0; base < i1; base += CHV) {
      const long hi = base + CHV < i1 ? base + CHV : i1;
      long i = base;
      auto scalar_store = [&](long j) {
        u64 t[4];
        fr_mul(t, coeff_mont + 4 * j, w + 4 * (long)wire[j]);
        pr0[j - base] = t[0];
        pr1[j - base] = t[1];
        pr2[j - base] = t[2];
        pr3[j - base] = t[3];
      };
#if ZKP2P_HAVE_IFMA
      if (coeff52 != nullptr && ifma_enabled()) {
        Ifma52Field &F = fr52_field();
        __m512i p[5], comppv[5];
        for (int k = 0; k < 5; ++k) {
          p[k] = _mm512_set1_epi64((long long)F.p52[k]);
          comppv[k] = _mm512_set1_epi64((long long)F.compp[k]);
        }
        const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
        const __m512i m52v = _mm512_set1_epi64((long long)M52);
        long a0 = (base + 7) & ~7L;  // coeff52 blocks are GLOBAL-8-aligned
        if (a0 > hi) a0 = hi;
        for (; i < a0; ++i) scalar_store(i);
        for (; i + 8 <= hi; i += 8) {
          // gather the 8 wire rows limb-by-limb, then 4x64 -> 5x52
          // entirely in-register (the lane-wise limbs4_to_52)
          const __m512i idx = _mm512_slli_epi64(
              _mm512_cvtepu32_epi64(_mm256_loadu_si256((const __m256i *)(wire + i))), 2);
          __m512i wv[4];
          for (int k = 0; k < 4; ++k)
            wv[k] = _mm512_i64gather_epi64(
                _mm512_add_epi64(idx, _mm512_set1_epi64(k)), (const long long *)w, 8);
          __m512i w52[5];
          w52[0] = _mm512_and_si512(wv[0], m52v);
          w52[1] = _mm512_and_si512(
              _mm512_or_si512(_mm512_srli_epi64(wv[0], 52), _mm512_slli_epi64(wv[1], 12)), m52v);
          w52[2] = _mm512_and_si512(
              _mm512_or_si512(_mm512_srli_epi64(wv[1], 40), _mm512_slli_epi64(wv[2], 24)), m52v);
          w52[3] = _mm512_and_si512(
              _mm512_or_si512(_mm512_srli_epi64(wv[2], 28), _mm512_slli_epi64(wv[3], 36)), m52v);
          w52[4] = _mm512_srli_epi64(wv[3], 16);
          __m512i c52[5];
          const u64 *blk = coeff52 + (size_t)(i / 8) * 40;
          for (int k = 0; k < 5; ++k) c52[k] = _mm512_loadu_si512(blk + k * 8);
          __m512i prv[5];
          mont52_mul8(prv, w52, c52, p, pinv);  // mont256 product, [0, 2p)
          cond_sub_c8(prv, comppv);             // canonical: < r
          // lane-wise limbs52_to_4, stored to the product planes
          _mm512_storeu_si512(pr0 + (i - base),
                              _mm512_or_si512(prv[0], _mm512_slli_epi64(prv[1], 52)));
          _mm512_storeu_si512(pr1 + (i - base),
                              _mm512_or_si512(_mm512_srli_epi64(prv[1], 12),
                                              _mm512_slli_epi64(prv[2], 40)));
          _mm512_storeu_si512(pr2 + (i - base),
                              _mm512_or_si512(_mm512_srli_epi64(prv[2], 24),
                                              _mm512_slli_epi64(prv[3], 28)));
          _mm512_storeu_si512(pr3 + (i - base),
                              _mm512_or_si512(_mm512_srli_epi64(prv[3], 36),
                                              _mm512_slli_epi64(prv[4], 16)));
        }
      }
#endif
      for (; i < hi; ++i) scalar_store(i);
      // segmented accumulation over this slice; acc carries across
      // slice boundaries for segments longer than CHV
      i = base;
      while (i < hi) {
        const long send = seg_starts[seg + 1];
        const long stop = send < hi ? send : hi;
        for (; i < stop; ++i) {
          u64 t[4] = {pr0[i - base], pr1[i - base], pr2[i - base], pr3[i - base]};
          fr_add(acc, acc, t);
        }
        if (i == send) {
          memcpy(out + 4 * (long)seg_rows[seg], acc, 32);
          memset(acc, 0, 32);
          ++seg;
        }
      }
    }
  };
  if (nchunk > 1) {
    work_pool().ensure(n_threads);
    work_pool().run(nchunk, run_chunk, n_threads);
  } else {
    run_chunk(0);
  }
  stat_add(ST_MATVEC_NS, prof_now_ns() - wall0);
}

// In-place radix-2 NTT over Fr, natural order in/out, data Montgomery.
// root_std: standard-form primitive m-th root (forward: w, inverse:
// w^-1); scale_std: standard-form factor applied to every output (1 for
// forward, m^-1 for inverse).  Twiddles are a precomputed m/2 table so
// each butterfly costs one fr_mul.
// bit-reversal permutation (32-byte element swaps) — shared by the
// scalar and IFMA NTT entry points so the permutation can never diverge.
static void fr_bitrev(u64 *data, long m) {
  for (long i = 1, j = 0; i < m; ++i) {
    long bit = m >> 1;
    for (; j & bit; bit >>= 1) j ^= bit;
    j ^= bit;
    if (i < j) {
      u64 tmp[4];
      memcpy(tmp, data + 4 * i, 32);
      memcpy(data + 4 * i, data + 4 * j, 32);
      memcpy(data + 4 * j, tmp, 32);
    }
  }
}

// scale_std != 1 epilogue — shared for the same reason.
static void fr_apply_scale(u64 *data, long m, const u64 *scale_std) {
  static const u64 ONE_STD[4] = {1, 0, 0, 0};
  if (memcmp(scale_std, ONE_STD, 32) != 0) {
    u64 scale_m[4];
    fr_mul(scale_m, scale_std, R2R);
    for (long i = 0; i < m; ++i) fr_mul(data + 4 * i, data + 4 * i, scale_m);
  }
}

void fr_ntt(u64 *data, long m, const u64 *root_std, const u64 *scale_std) {
  int log_m = 0;
  while ((1L << log_m) < m) ++log_m;
  fr_bitrev(data, m);
  u64 root_m[4];
  fr_mul(root_m, root_std, R2R);
  long half_m = m / 2;
  // Twiddles depend only on (m, root): cache them across calls — the
  // ladder runs 6 NTTs per prove and the sequential m/2-mul rebuild was
  // ~5% of its time.  Guarded: ladder threads call fr_ntt concurrently.
  // Capacity-capped (each entry is 16*m bytes, ~128 MB per root at
  // m=2^23): a long-lived service proving across domain sizes must not
  // accumulate unbounded twiddle tables.  shared_ptr keeps an evicted
  // table alive for any thread still mid-butterfly on it.
  static std::mutex tw_mu;
  static std::map<std::array<u64, 5>, std::shared_ptr<u64[]>> tw_cache;
  std::shared_ptr<u64[]> tw_hold;
  {
    std::lock_guard<std::mutex> lk(tw_mu);
    std::array<u64, 5> key = {(u64)m, root_std[0], root_std[1], root_std[2], root_std[3]};
    auto it = tw_cache.find(key);
    if (it != tw_cache.end()) {
      tw_hold = it->second;
    } else {
      tw_hold = std::shared_ptr<u64[]>(new u64[(size_t)(half_m > 0 ? half_m : 1) * 4]);
      memcpy(tw_hold.get(), ONE_R, 32);
      for (long j = 1; j < half_m; ++j) fr_mul(tw_hold.get() + 4 * j, tw_hold.get() + 4 * (j - 1), root_m);
      // evict smallest-m entries first (cheapest to rebuild) until at
      // most 8 tables besides the one being inserted remain
      while (tw_cache.size() >= 8) tw_cache.erase(tw_cache.begin());
      tw_cache[key] = tw_hold;
    }
  }
  u64 *tw = tw_hold.get();
  for (long len = 2; len <= m; len <<= 1) {
    long half = len >> 1;
    long stride = m / len;
    for (long i0 = 0; i0 < m; i0 += len) {
      for (long j = 0; j < half; ++j) {
        u64 *u = data + 4 * (i0 + j);
        u64 *v = data + 4 * (i0 + j + half);
        u64 t[4];
        // j == 0 is the identity twiddle: every stage's first
        // butterfly (and ALL of stage len=2) — skipping the Montgomery
        // mul there removes ~m of the m/2·log2(m) twiddle muls
        if (j == 0) {
          memcpy(t, v, 32);
        } else {
          fr_mul(t, v, tw + 4 * (j * stride));
        }
        u64 usave[4];
        memcpy(usave, u, 32);
        fr_add(u, usave, t);
        fr_sub(v, usave, t);
      }
    }
  }
  fr_apply_scale(data, m, scale_std);
}

// 1 when the AVX-512 IFMA fast paths are compiled in, the CPU has the
// instructions, and ZKP2P_NATIVE_IFMA != 0.
int zkp2p_ifma_available(void) { return ifma_enabled() ? 1 : 0; }

// 1 when the batch-affine bucket tiers are active (ZKP2P_MSM_BATCH_AFFINE
// unset / not leading-'0').  Fresh-read, so tools can echo the live arm.
int zkp2p_batch_affine_enabled(void) { return batch_affine_enabled() ? 1 : 0; }

// 1 when the pool-parallel NTT stage splitting + fused ladder pipeline
// are active (ZKP2P_NTT_POOL unset / not leading-'0').  Fresh-read for
// the same reason.
int zkp2p_ntt_pool_enabled(void) { return ntt_pool_enabled() ? 1 : 0; }

// Host cache capacity in bytes for the tune subsystem's cache-conscious
// MSM schedule picking: level 1 = L1d, 2 = L2, 3 = L3 (LLC on most
// parts).  sysconf is the portable glibc surface over cpuid/sysfs; a
// kernel or libc that doesn't expose the level reports 0 = unknown and
// the Python side falls back to sysfs, then to documented constants.
long zkp2p_cache_size(int level) {
  long v = -1;
  switch (level) {
#ifdef _SC_LEVEL1_DCACHE_SIZE
    case 1: v = sysconf(_SC_LEVEL1_DCACHE_SIZE); break;
#endif
#ifdef _SC_LEVEL2_CACHE_SIZE
    case 2: v = sysconf(_SC_LEVEL2_CACHE_SIZE); break;
#endif
#ifdef _SC_LEVEL3_CACHE_SIZE
    case 3: v = sysconf(_SC_LEVEL3_CACHE_SIZE); break;
#endif
    default: break;
  }
  return v > 0 ? v : 0;
}

// Online logical CPU count as the runtime sees it (the same figure the
// WorkPool sizes from when ZKP2P_NATIVE_THREADS is unset); 0 = unknown.
long zkp2p_cpu_count(void) {
#ifdef _SC_NPROCESSORS_ONLN
  long v = sysconf(_SC_NPROCESSORS_ONLN);
  return v > 0 ? v : 0;
#else
  return 0;
#endif
}

// Differential-test hook for the 8-wide kernel: c[i] = a[i]*b[i] mod r,
// standard form in/out, driven through pack -> mont260 vector multiply
// -> unpack (the exact pipeline the NTT stages use).  Falls back to the
// scalar path when IFMA is unavailable so tests can always call it.
void fr52_mul_std_batch(const u64 *a, const u64 *b, u64 *c, long n) {
#if ZKP2P_HAVE_IFMA
  if (ifma_enabled()) {
    Ifma52Field &F = fr52_field();
    __m512i p[5];
    for (int k = 0; k < 5; ++k) p[k] = _mm512_set1_epi64((long long)F.p52[k]);
    const __m512i pinv = _mm512_set1_epi64((long long)F.pinv52);
    // r260sq lanes: one mont260 mul maps std a -> a·2^260 (mont260)
    __m512i rsq[5];
    for (int k = 0; k < 5; ++k) rsq[k] = _mm512_set1_epi64((long long)F.r260sq[k]);
    long i = 0;
    for (; i + 8 <= n; i += 8) {
      u64 av[5][8], bv[5][8];
      for (int l = 0; l < 8; ++l) {
        u64 t[5];
        limbs4_to_52(t, a + 4 * (i + l));
        for (int k = 0; k < 5; ++k) av[k][l] = t[k];
        limbs4_to_52(t, b + 4 * (i + l));
        for (int k = 0; k < 5; ++k) bv[k][l] = t[k];
      }
      __m512i A[5], B[5], Bm[5], C[5];
      for (int k = 0; k < 5; ++k) {
        A[k] = _mm512_loadu_si512(av[k]);
        B[k] = _mm512_loadu_si512(bv[k]);
      }
      mont52_mul8(Bm, B, rsq, p, pinv);  // b_std -> b·2^260
      mont52_mul8(C, A, Bm, p, pinv);    // (a_std)(b·2^260)·2^-260 = ab std
      u64 cv[5][8];
      for (int k = 0; k < 5; ++k) _mm512_storeu_si512(cv[k], C[k]);
      for (int l = 0; l < 8; ++l) {
        u64 t[5], o[4];
        for (int k = 0; k < 5; ++k) t[k] = cv[k][l];
        limbs52_to_4(o, t);
        while (geq(o, R_MOD)) sub_nored(o, o, R_MOD);
        memcpy(c + 4 * (i + l), o, 32);
      }
    }
    for (; i < n; ++i) fr_mul_std(a + 4 * i, b + 4 * i, c + 4 * i);
    return;
  }
#endif
  for (long i = 0; i < n; ++i) fr_mul_std(a + 4 * i, b + 4 * i, c + 4 * i);
}

// Drop-in fr_ntt with the len>=16 stages vectorized 8-wide (IFMA).
// Identical contract: data Montgomery, natural order in/out, root_std /
// scale_std standard form.
void fr_ntt_ifma(u64 *data, long m, const u64 *root_std, const u64 *scale_std) {
#if ZKP2P_HAVE_IFMA
  if (ifma_enabled() && m >= 64) {
    // ALL stages vectorized: len 2/4/8 via in-register permutes (the
    // scalar small-stage tier was ~1/3 of the NTT after radix-4), then
    // the radix-4-fused len>=16 loop — one pack/unpack for everything,
    // with the input bit-reversal folded into the pack
    fr_ntt_ifma_stages(data, m, root_std);
    fr_apply_scale(data, m, scale_std);
    return;
  }
#endif
  fr_ntt(data, m, root_std, scale_std);
}

#if ZKP2P_HAVE_IFMA
// gpow table for the FUSED ladder, in mont260 SoA planes, cached per
// (m, g): gpow[j] = (1/m)·g^j — the iNTT's deferred 1/m scale folded
// into the coset shift, applied as ONE vectorized SoA pass between the
// inverse and forward stage pipelines (fr_soa_mul).  Key-shape
// invariant, so it builds once per (domain, coset) like the twiddle
// tables and drops the old per-call sequential m-mul chain from the
// prove path (shared_ptr for in-flight safety; small cap — each entry
// is 40·m bytes).
static std::shared_ptr<u64[]> ladder_gpow260(long m, const u64 *g_std,
                                             const u64 *minv_std) {
  static std::mutex mu;
  static std::map<std::array<u64, 5>, std::shared_ptr<u64[]>> cache;
  std::lock_guard<std::mutex> lk(mu);
  std::array<u64, 5> key = {(u64)m, g_std[0], g_std[1], g_std[2], g_std[3]};
  auto it = cache.find(key);
  if (it != cache.end()) return it->second;
  Ifma52Field &F = fr52_field();
  std::shared_ptr<u64[]> buf(new u64[(size_t)m * 5]);
  u64 g52[5], g260[5], cur[5], t52[5];
  limbs4_to_52(g52, g_std);
  mont52_mul_scalar(g260, g52, F.r260sq, F);  // std -> mont260
  limbs4_to_52(t52, minv_std);
  mont52_mul_scalar(cur, t52, F.r260sq, F);   // (1/m) in mont260
  u64 *planes = buf.get();
  for (long j = 0; j < m; ++j) {
    for (int k = 0; k < 5; ++k) planes[(size_t)k * m + j] = cur[k];
    mont52_mul_scalar(cur, cur, g260, F);
  }
  while (cache.size() >= 4) cache.erase(cache.begin());
  cache[key] = buf;
  return buf;
}

// Fused-pipeline ladder (the ZKP2P_NTT_POOL arm): each transform stays
// in 52-limb SoA form across iNTT -> coset-mul -> forward NTT, so the
// unpack-to-mont256 and repack passes between the two transforms (plus
// the standalone scalar coset-mul pass) disappear — two full memory
// passes per transform — and every stage pass fans out across the
// WorkPool instead of the old 3-wide whole-transform split.  Byte
// parity with the unfused arm is exact: identical field values at every
// step, one canonical unpack at the end (tests/test_nonmsm.py pins it).
static void fr_h_ladder_fused(u64 *a, u64 *b, u64 *c, long m,
                              const u64 *w_std, const u64 *winv_std,
                              const u64 *g_std, const u64 *minv_std,
                              u64 *out_d, int nt) {
  std::shared_ptr<u64[]> gpow = ladder_gpow260(m, g_std, minv_std);
  u64 *soa = new u64[(size_t)m * 5];
  u64 *vecs[3] = {a, b, c};
  for (int v3 = 0; v3 < 3; ++v3) {
    u64 *v = vecs[v3];
    fr_soa_pack_rev(v, m, soa, nt);           // bitrev folded into the pack
    fr_ntt_soa_stages(soa, m, winv_std, nt);  // unscaled iNTT: evals -> m·coeffs
    fr_soa_mul(soa, m, gpow.get(), nt);       // fused (1/m)·g^j coset pass
    fr_soa_bitrev(soa, m, nt);                // natural -> bit-reversed for forward
    fr_ntt_soa_stages(soa, m, w_std, nt);     // coefficients -> coset evals
    fr_soa_unpack(soa, m, v, nt);             // canonical mont256 out
  }
  delete[] soa;
  // d = A·B - C on the coset, range-parallel (independent rows)
  pool_parallel_ranges(m, 1L << 13, nt, [&](long lo, long hi) {
    for (long j = lo; j < hi; ++j) {
      u64 t[4];
      fr_mul(t, a + 4 * j, b + 4 * j);
      fr_sub(out_d + 4 * j, t, c + 4 * j);
    }
  });
}
#endif  // ZKP2P_HAVE_IFMA

// The H-polynomial coset ladder (prove_tpu's h_evals, native):
// a/b/c are the domain evaluations (Montgomery, length m, clobbered);
// out_d[j] = (A.B - C)(g . w^j) Montgomery.  w_std is the primitive
// m-th root matching field.bn254.fr_domain_root(log_m); g_std the coset
// generator (snarkjs convention: w_{2m}).  Inverses computed here.
void fr_h_ladder(u64 *a, u64 *b, u64 *c, long m, const u64 *w_std,
                 const u64 *g_std, u64 *out_d) {
  // winv, minv (standard form): invert in Montgomery then strip.
  u64 wm[4], wim[4], winv_std[4], minv_std[4];
  static const u64 ONE_STD[4] = {1, 0, 0, 0};
  fr_mul(wm, w_std, R2R);
  fr_inv_mont(wim, wm);
  fr_mul(winv_std, wim, ONE_STD);
  u64 m_std[4] = {(u64)m, 0, 0, 0};
  u64 mm[4], mim[4];
  fr_mul(mm, m_std, R2R);
  fr_inv_mont(mim, mm);
  fr_mul(minv_std, mim, ONE_STD);
#if ZKP2P_HAVE_IFMA
  // the fused, stage-parallel pipeline (byte-identical; gated so the
  // knob-off arm below stays the honest A/B oracle)
  if (ifma_enabled() && ntt_pool_enabled() && m >= 64) {
    fr_h_ladder_fused(a, b, c, m, w_std, winv_std, g_std, minv_std, out_d,
                      pool_default_threads());
    return;
  }
#endif
  u64 gm[4];
  fr_mul(gm, g_std, R2R);
  // One shared table for all three ladders, with the iNTT's 1/m scale
  // FOLDED IN: gpow[j] = (1/m)·g^j in Montgomery form, so the unscaled
  // iNTT plus one coset mul replaces scale-pass + coset-pass (each
  // previously ran its own sequential m-mul power chain too).
  u64 minv_m[4];
  fr_mul(minv_m, minv_std, R2R);
  u64 *gpow = new u64[(size_t)m * 4];
  memcpy(gpow, minv_m, 32);
  for (long j = 1; j < m; ++j) fr_mul(gpow + 4 * j, gpow + 4 * (j - 1), gm);
  u64 *vecs[3] = {a, b, c};
  auto ladder_one = [&](u64 *v) {
    fr_ntt_ifma(v, m, winv_std, ONE_STD);  // unscaled iNTT: evals -> m·coeffs
    // coset shift + deferred 1/m scale in one pass: v[j] *= (1/m)·g^j
    for (long j = 0; j < m; ++j) fr_mul(v + 4 * j, v + 4 * j, gpow + 4 * j);
    fr_ntt_ifma(v, m, w_std, ONE_STD);  // forward: coefficients -> coset evals
  };
  // The three polynomial ladders are independent: run them on the
  // persistent pool when the host has cores to spare (same env-driven
  // knob as the MSM pool; spawn-per-call threads retired with it).
  int nt = pool_default_threads();
  if (nt > 1) {
    int w = nt < 3 ? nt : 3;
    work_pool().ensure(w);
    work_pool().run(3, [&](long k) { ladder_one(vecs[k]); }, w);
  } else {
    for (int k = 0; k < 3; ++k) ladder_one(vecs[k]);
  }
  delete[] gpow;
  for (long j = 0; j < m; ++j) {
    u64 t[4];
    fr_mul(t, a + 4 * j, b + 4 * j);
    fr_sub(out_d + 4 * j, t, c + 4 * j);
  }
}

}  // extern "C"

// ------------------------------------------------- Pippenger MSM (G1/G2)

// Full Jacobian + Jacobian add over G1 (mirror of g2_add).
static void g1_add_jac(G1Jac &acc, const G1Jac &e) {
  if (is_zero4(e.Z)) return;
  if (is_zero4(acc.Z)) {
    acc = e;
    return;
  }
  u64 Z1Z1[4], Z2Z2[4], U1[4], U2[4], S1[4], S2[4], H[4], Rr[4], t[4];
  mont_sqr(Z1Z1, acc.Z);
  mont_sqr(Z2Z2, e.Z);
  mont_mul(U1, acc.X, Z2Z2);
  mont_mul(U2, e.X, Z1Z1);
  mont_mul(t, acc.Y, e.Z);
  mont_mul(S1, t, Z2Z2);
  mont_mul(t, e.Y, acc.Z);
  mont_mul(S2, t, Z1Z1);
  sub_mod(H, U2, U1);
  sub_mod(Rr, S2, S1);
  if (is_zero4(H)) {
    if (is_zero4(Rr)) {
      G1Jac d;
      jac_double(d, acc);
      acc = d;
      return;
    }
    memset(&acc, 0, sizeof(acc));
    return;
  }
  u64 HH[4], HHH[4], V[4], x3[4], y3[4], z3[4], t2[4], v2[4];
  mont_sqr(HH, H);
  mont_mul(HHH, H, HH);
  mont_mul(V, U1, HH);
  mont_sqr(t, Rr);
  sub_mod(t, t, HHH);
  add_mod(v2, V, V);
  sub_mod(x3, t, v2);
  sub_mod(t, V, x3);
  mont_mul(t, Rr, t);
  mont_mul(t2, S1, HHH);
  sub_mod(y3, t, t2);
  mont_mul(t, acc.Z, e.Z);
  mont_mul(z3, t, H);
  memcpy(acc.X, x3, 32);
  memcpy(acc.Y, y3, 32);
  memcpy(acc.Z, z3, 32);
}

// c-bit digit of a 256-bit scalar starting at `bit`.
static inline unsigned digit_at(const u64 s[4], int bit, int c) {
  int limb = bit >> 6, off = bit & 63;
  u64 v = s[limb] >> off;
  if (off + c > 64 && limb < 3) v |= s[limb + 1] << (64 - off);
  return (unsigned)(v & ((1ULL << c) - 1));
}

// Signed base-2^c recoding of one scalar: digits in [-(2^(c-1)-1),
// 2^(c-1)], LSW first.  Halves the bucket count per window (a negative
// digit adds the NEGATED point: (x, p - y) is free next to a bucket
// add).  The top window absorbs the final carry whenever nwin*c >= 255
// (true for every c in the sweep range; asserted by the callers) since
// Fr scalars are < 2^254.
static void signed_digits(const u64 s[4], int c, int nwin, int32_t *out) {
  long half = 1L << (c - 1), full = 1L << c;
  long carry = 0;
  for (int wi = 0; wi < nwin; ++wi) {
    long d = (long)digit_at(s, wi * c, c) + carry;
    if (d > half) {
      out[wi] = (int32_t)(d - full);
      carry = 1;
    } else {
      out[wi] = (int32_t)d;
      carry = 0;
    }
  }
}

// y -> p - y (Montgomery), the negation used for negative digits.
static inline void neg_y(u64 out[4], const u64 y[4]) {
  if (is_zero4(y)) {
    memset(out, 0, 32);
    return;
  }
  sub_nored(out, P, y);
}

// The digit-signed y of a point: shared by every G1 fill path so the
// sign handling cannot diverge between the batch-affine, jac, and bail
// tiers.
static inline void signed_pt_y(u64 out[4], const u64 y[4], bool negate) {
  if (negate) {
    neg_y(out, y);
  } else {
    memcpy(out, y, 32);
  }
}

// One Pippenger window sum: bucket fill over all n points + suffix-sum
// reduction.  Windows are independent, which is the parallel axis (the
// same split rapidsnark's thread pool uses): each worker owns its bucket
// array, the combiner pays only nwin Horner steps of c doublings.
//
// The G1 fill uses BATCH-AFFINE bucket accumulation (the gnark/arkworks
// trick): buckets live as affine points, each bucket add is an
// affine+affine add whose one field inversion is amortized across a
// whole chunk by the Montgomery batch-inverse — ~7 muls per add instead
// of the ~12 of a mixed-Jacobian add, on the op that is ~85% of the MSM.
// Same-chunk bucket collisions are deferred to the next pass (rare:
// chunk << 2^c).

struct AffPt {
  u64 x[4], y[4];  // Montgomery; (0,0) = empty bucket
};

static inline bool aff_is_empty(const AffPt &p) {
  return is_zero4(p.x) && is_zero4(p.y);
}

// Plain mixed-Jacobian fill: the fallback for windows whose effective
// digit range is tiny (the TOP window often has only a few bits: its
// points pile into a handful of buckets and the batch-affine conflict
// queue degenerates into near-serial passes).
static void g1_window_sum_jac(const u64 *bases_xy, const int32_t *sd, long n,
                              int c, int nwin, int wi, G1Jac *out) {
  long nbuckets = (1L << (c - 1)) + 1;  // signed digits reach 2^(c-1)
  G1Jac *buckets = new G1Jac[nbuckets];
  memset(buckets, 0, (size_t)nbuckets * sizeof(G1Jac));
  for (long i = 0; i < n; ++i) {
    int32_t d = sd[i * nwin + wi];
    if (!d) continue;
    const u64 *x = bases_xy + 8 * i;
    const u64 *y = x + 4;
    if (is_zero4(x) && is_zero4(y)) continue;
    long b = d < 0 ? -d : d;
    u64 ys[4];
    signed_pt_y(ys, y, d < 0);
    jac_add_mixed(buckets[b], buckets[b], x, ys);
  }
  G1Jac run, wsum;
  memset(&run, 0, sizeof(run));
  memset(&wsum, 0, sizeof(wsum));
  for (long d = nbuckets - 1; d >= 1; --d) {
    g1_add_jac(run, buckets[d]);
    g1_add_jac(wsum, run);
  }
  delete[] buckets;
  *out = wsum;
}

static void g1_window_sum(const u64 *bases_xy, const int32_t *sd, long n,
                          int c, int nwin, int wi, G1Jac *out,
                          int total_bits = 254) {
  const long nbuckets = (1L << (c - 1)) + 1;  // signed digit magnitudes
  const long B = 2048;  // chunk size for the shared inversion
  int bits_here = total_bits - wi * c;
  if (bits_here > c) bits_here = c;
  if (bits_here < 1 || (1L << bits_here) < 4 * B) {
    g1_window_sum_jac(bases_xy, sd, n, c, nwin, wi, out);
    return;
  }
  AffPt *bk = new AffPt[nbuckets]();
  int *stamp = new int[nbuckets];
  memset(stamp, 0xff, nbuckets * sizeof(int));

  std::vector<long> cur, next;
  cur.reserve(n);
  for (long i = 0; i < n; ++i) {
    if (!sd[i * nwin + wi]) continue;
    const u64 *x = bases_xy + 8 * i;
    if (is_zero4(x) && is_zero4(x + 4)) continue;
    cur.push_back(i);
  }

  // scheduled-add scratch (per chunk)
  long *add_bkt = new long[B];
  long *add_pt = new long[B];
  u64 (*den)[4] = new u64[B][4];
  u64 (*num)[4] = new u64[B][4];   // lambda numerator
  u64 (*prod)[4] = new u64[B][4];  // batch-inverse prefix products
  // coordinate stashes (bucket state at schedule time + incoming point);
  // num/den derive from these AFTER scheduling — vectorized when IFMA
  // is up, per-j in the scalar fallback — so the schedule loop itself
  // does no field ops at all
  u64 (*x1a)[4] = new u64[B][4];
  u64 (*y1a)[4] = new u64[B][4];
  u64 (*x2a)[4] = new u64[B][4];
  u64 (*y2a)[4] = new u64[B][4];
  u64 (*x3a)[4] = new u64[B][4];
  u64 (*y3a)[4] = new u64[B][4];
  unsigned char *dbl = new unsigned char[B];
#if ZKP2P_HAVE_IFMA
  // chunk-apply SoA scratch, hoisted out of the per-chunk loop
  u64 *ifma_scratch = new u64[(size_t)9 * 5 * ((B + 7) / 8 * 8)];
#endif

  int chunk_id = 0;
  long long n_dbl = 0, n_cancel = 0, n_defer = 0;  // flushed once per window
  long long fl0 = prof_now_ns();
  while (!cur.empty()) {
    next.clear();
    size_t processed = 0;
    bool bail = false;
    for (size_t lo = 0; lo < cur.size() && !bail; lo += B, ++chunk_id) {
      size_t hi = lo + B < cur.size() ? lo + B : cur.size();
      long m = 0;
      for (size_t k = lo; k < hi; ++k) {
        long i = cur[k];
        int32_t dgt = sd[i * nwin + wi];
        long b = dgt < 0 ? -dgt : dgt;
        if (stamp[b] == chunk_id) {  // bucket already touched this chunk
          next.push_back(i);
          ++n_defer;
          continue;
        }
        stamp[b] = chunk_id;
        const u64 *px = bases_xy + 8 * i;
        u64 py[4];
        signed_pt_y(py, px + 4, dgt < 0);
        if (aff_is_empty(bk[b])) {  // install: no field ops at all
          memcpy(bk[b].x, px, 32);
          memcpy(bk[b].y, py, 32);
          continue;
        }
        if (memcmp(bk[b].x, px, 32) == 0) {
          if (memcmp(bk[b].y, py, 32) == 0) {
            dbl[m] = 1;  // doubling: lambda = 3x^2 / 2y (derived later)
            ++n_dbl;
          } else {
            // p + (-p): bucket becomes empty
            memset(&bk[b], 0, sizeof(AffPt));
            ++n_cancel;
            continue;
          }
        } else {
          dbl[m] = 0;  // chord: lambda = (y2 - y1) / (x2 - x1)
        }
        memcpy(x1a[m], bk[b].x, 32);
        memcpy(y1a[m], bk[b].y, 32);
        memcpy(x2a[m], px, 32);
        memcpy(y2a[m], py, 32);
        add_bkt[m] = b;
        add_pt[m] = i;
        ++m;
      }
      processed = hi;  // BEFORE the m==0 continue: install-only chunks
                       // are processed too (the bail tail starts here)
      if (!m) {
        if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
        continue;
      }
#if ZKP2P_HAVE_IFMA
      if (ifma_enabled() && m >= 48) {
        // 8-lane inversion + apply, one scalar inversion per chunk
        g1_chunk_apply_ifma(x1a, y1a, x2a, y2a, dbl, m, x3a, y3a, ifma_scratch);
        for (long j = 0; j < m; ++j) {
          memcpy(bk[add_bkt[j]].x, x3a[j], 32);
          memcpy(bk[add_bkt[j]].y, y3a[j], 32);
        }
      } else
#endif
      {
        // batch inversion of den[0..m): prefix products + one inversion
        // (num/den derived here from the schedule stashes)
        u64 run[4];
        memcpy(run, ONE_MONT, 32);
        for (long j = 0; j < m; ++j) {
          if (dbl[j]) {
            u64 xsq[4], t[4];
            mont_sqr(xsq, x1a[j]);
            add_mod(t, xsq, xsq);
            add_mod(num[j], t, xsq);
            add_mod(den[j], y1a[j], y1a[j]);
          } else {
            sub_mod(num[j], y2a[j], y1a[j]);
            sub_mod(den[j], x2a[j], x1a[j]);
          }
          memcpy(prod[j], run, 32);  // product of dens before j
          mont_mul(run, run, den[j]);
        }
        u64 inv_all[4];
        mont_inv(inv_all, run);
        for (long j = m - 1; j >= 0; --j) {
          u64 dinv[4];
          mont_mul(dinv, inv_all, prod[j]);      // 1/den[j]
          mont_mul(inv_all, inv_all, den[j]);    // strip den[j]
          long b = add_bkt[j];
          const u64 *px = bases_xy + 8 * add_pt[j];
          u64 lam[4], lam2[4], x3[4], y3[4], t[4];
          mont_mul(lam, num[j], dinv);
          mont_sqr(lam2, lam);
          // x3 = lam^2 - x1 - x2 ; y3 = lam (x1 - x3) - y1
          sub_mod(x3, lam2, bk[b].x);
          sub_mod(x3, x3, px);
          sub_mod(t, bk[b].x, x3);
          mont_mul(t, lam, t);
          sub_mod(y3, t, bk[b].y);
          memcpy(bk[b].x, x3, 32);
          memcpy(bk[b].y, y3, 32);
        }
      }
      // Concentrated digits (witness scalars are mostly bits: window 0
      // sees thousands of digit-1 points) defer most of every chunk —
      // batch-affine degenerates into a pass per point.  Bail to
      // mixed-Jacobian for whatever remains.
      if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
    }
    if (bail || next.size() * 4 > cur.size()) {
      // Finish all unfinished points (deferred + the unprocessed tail of
      // this pass) with plain mixed-Jacobian adds into a parallel bucket
      // array, then reduce both arrays together.
      stat_add(ST_MSM_FILL_NS, prof_now_ns() - fl0);
      stat_add(ST_MSM_DBL_LANES, n_dbl);
      stat_add(ST_MSM_CANCEL_LANES, n_cancel);
      stat_add(ST_MSM_DEFER_HITS, n_defer);
      long long bs0 = prof_now_ns();
      G1Jac *jb = new G1Jac[nbuckets];
      memset(jb, 0, (size_t)nbuckets * sizeof(G1Jac));
      next.insert(next.end(), cur.begin() + processed, cur.end());
      for (long i : next) {
        int32_t dgt = sd[i * nwin + wi];
        long b = dgt < 0 ? -dgt : dgt;
        const u64 *x = bases_xy + 8 * i;
        u64 ys[4];
        signed_pt_y(ys, x + 4, dgt < 0);
        jac_add_mixed(jb[b], jb[b], x, ys);
      }
      stat_add(ST_MSM_BAILFILL_NS, prof_now_ns() - bs0);
      bs0 = prof_now_ns();
      G1Jac run, wsum;
      memset(&run, 0, sizeof(run));
      memset(&wsum, 0, sizeof(wsum));
      for (long d = nbuckets - 1; d >= 1; --d) {
        g1_add_jac(run, jb[d]);
        if (!aff_is_empty(bk[d])) jac_add_mixed(run, run, bk[d].x, bk[d].y);
        g1_add_jac(wsum, run);
      }
      stat_add(ST_MSM_SUFFIX_NS, prof_now_ns() - bs0);
      delete[] jb;
      delete[] bk;
      delete[] stamp;
      delete[] add_bkt;
      delete[] add_pt;
      delete[] den;
      delete[] num;
      delete[] prod;
      delete[] x1a;
      delete[] y1a;
      delete[] x2a;
      delete[] y2a;
      delete[] x3a;
      delete[] y3a;
      delete[] dbl;
#if ZKP2P_HAVE_IFMA
      delete[] ifma_scratch;
#endif
      *out = wsum;
      return;
    }
    cur.swap(next);
  }

  stat_add(ST_MSM_FILL_NS, prof_now_ns() - fl0);
  stat_add(ST_MSM_DBL_LANES, n_dbl);
  stat_add(ST_MSM_CANCEL_LANES, n_cancel);
  stat_add(ST_MSM_DEFER_HITS, n_defer);
  // suffix-sum reduction over affine buckets (mixed adds into Jacobian)
  long long sf0 = prof_now_ns();
  G1Jac run, wsum;
  memset(&run, 0, sizeof(run));
  memset(&wsum, 0, sizeof(wsum));
  for (long d = nbuckets - 1; d >= 1; --d) {
    if (!aff_is_empty(bk[d])) jac_add_mixed(run, run, bk[d].x, bk[d].y);
    g1_add_jac(wsum, run);
  }
  stat_add(ST_MSM_SUFFIX_NS, prof_now_ns() - sf0);
  delete[] bk;
  delete[] stamp;
  delete[] add_bkt;
  delete[] add_pt;
  delete[] den;
  delete[] num;
  delete[] prod;
  delete[] x1a;
  delete[] y1a;
  delete[] x2a;
  delete[] y2a;
  delete[] x3a;
  delete[] y3a;
  delete[] dbl;
#if ZKP2P_HAVE_IFMA
  delete[] ifma_scratch;
#endif
  *out = wsum;
}

// Plain mixed-Jacobian G2 window fill (the non-IFMA tier and the
// vector tier's bail path).
static void g2_window_sum_jac(const u64 *bases, const int32_t *sd, long n,
                              int c, int nwin, int wi, G2Jac *out) {
  long nbuckets = (1L << (c - 1)) + 1;  // signed digit magnitudes
  G2Jac *buckets = new G2Jac[nbuckets];
  memset(buckets, 0, (size_t)nbuckets * sizeof(G2Jac));
  for (long i = 0; i < n; ++i) {
    int32_t dgt = sd[i * nwin + wi];
    if (!dgt) continue;
    long d = dgt < 0 ? -dgt : dgt;
    const u64 *b = bases + 16 * i;
    Fp2 x2, y2;
    memcpy(x2.c0, b, 32);
    memcpy(x2.c1, b + 4, 32);
    memcpy(y2.c0, b + 8, 32);
    memcpy(y2.c1, b + 12, 32);
    if (fp2_is_zero(x2) && fp2_is_zero(y2)) continue;
    if (dgt < 0) {  // -(y0 + y1 u) component-wise
      u64 t[4];
      neg_y(t, y2.c0);
      memcpy(y2.c0, t, 32);
      neg_y(t, y2.c1);
      memcpy(y2.c1, t, 32);
    }
    g2_add_mixed(buckets[d], buckets[d], x2, y2);
  }
  G2Jac run, wsum;
  memset(&run, 0, sizeof(run));
  memset(&wsum, 0, sizeof(wsum));
  for (long d = nbuckets - 1; d >= 1; --d) {
    g2_add(run, buckets[d]);
    g2_add(wsum, run);
  }
  delete[] buckets;
  *out = wsum;
}

#if ZKP2P_HAVE_IFMA
// Batch-affine G2 window fill: the Fq2 mirror of g1_window_sum's
// vector tier — affine buckets, stamp-deferred same-chunk conflicts,
// the 8-wide norm-route chunk apply, mixed-Jacobian bail for
// concentrated digit distributions.  An affine G2 add through the
// vector apply costs ~15 Fq vector muls per 8 adds vs the ~42 scalar
// Fq muls of a mixed-Jacobian G2 add.
static void g2_window_sum_affine(const u64 *bases, const int32_t *sd, long n,
                                 int c, int nwin, int wi, G2Jac *out) {
  const long nbuckets = (1L << (c - 1)) + 1;
  const long B = 1024;
  int bits_here = 254 - wi * c;
  if (bits_here > c) bits_here = c;
  if (bits_here < 1 || (1L << bits_here) < 4 * B) {
    g2_window_sum_jac(bases, sd, n, c, nwin, wi, out);
    return;
  }
  // affine buckets: rows of (x.c0 x.c1 y.c0 y.c1), all-zero = empty
  u64 (*bk)[16] = new u64[nbuckets][16]();
  int *stamp = new int[nbuckets];
  memset(stamp, 0xff, nbuckets * sizeof(int));
  std::vector<long> cur, next;
  cur.reserve(n);
  for (long i = 0; i < n; ++i) {
    if (!sd[i * nwin + wi]) continue;
    const u64 *b = bases + 16 * i;
    bool inf = true;
    for (int q = 0; q < 16 && inf; ++q) inf = b[q] == 0;
    if (!inf) cur.push_back(i);
  }
  long *add_bkt = new long[B];
  u64 (*x1a)[8] = new u64[B][8];
  u64 (*y1a)[8] = new u64[B][8];
  u64 (*x2a)[8] = new u64[B][8];
  u64 (*y2a)[8] = new u64[B][8];
  u64 (*x3a)[8] = new u64[B][8];
  u64 (*y3a)[8] = new u64[B][8];
  unsigned char *dbl = new unsigned char[B];
  u64 *scratch = new u64[(size_t)17 * 5 * B];
  auto cleanup = [&]() {
    delete[] bk; delete[] stamp; delete[] add_bkt;
    delete[] x1a; delete[] y1a; delete[] x2a; delete[] y2a;
    delete[] x3a; delete[] y3a; delete[] dbl; delete[] scratch;
  };
  int chunk_id = 0;
  while (!cur.empty()) {
    next.clear();
    size_t processed = 0;
    bool bail = false;
    const bool pf = msm_interleave_enabled();
    for (size_t lo = 0; lo < cur.size() && !bail; lo += B, ++chunk_id) {
      size_t hi = lo + B < cur.size() ? lo + B : cur.size();
      long m = 0;
      for (size_t k = lo; k < hi; ++k) {
        // Two-level prefetch down the schedule: pull the digit word
        // first (far), then — once it is cheap to read — the dependent
        // stamp/bucket/base lines (near).  The bucket table and the
        // bases both sit beyond L2 at bench shape and the index
        // pattern is hardware-prefetch-blind.
        if (pf) {
          if (k + 32 < hi)
            _mm_prefetch((const char *)&sd[cur[k + 32] * nwin + wi],
                         _MM_HINT_T0);
          if (k + 16 < hi) {
            const long i2 = cur[k + 16];
            const int32_t d2 = sd[i2 * nwin + wi];
            const long b2 = d2 < 0 ? -d2 : d2;
            _mm_prefetch((const char *)&stamp[b2], _MM_HINT_T0);
            const char *pb = (const char *)&bk[b2];
            _mm_prefetch(pb, _MM_HINT_T0);
            _mm_prefetch(pb + 64, _MM_HINT_T0);
            const char *pp = (const char *)(bases + 16 * i2);
            _mm_prefetch(pp, _MM_HINT_T0);
            _mm_prefetch(pp + 64, _MM_HINT_T0);
          }
        }
        long i = cur[k];
        int32_t dgt = sd[i * nwin + wi];
        long bno = dgt < 0 ? -dgt : dgt;
        if (stamp[bno] == chunk_id) {
          next.push_back(i);
          continue;
        }
        stamp[bno] = chunk_id;
        const u64 *b = bases + 16 * i;
        u64 px[8], py[8];
        memcpy(px, b, 64);
        if (dgt < 0) {
          neg_y(py, b + 8);
          neg_y(py + 4, b + 12);
        } else {
          memcpy(py, b + 8, 64);
        }
        bool empty = true;
        for (int q = 0; q < 16 && empty; ++q) empty = bk[bno][q] == 0;
        if (empty) {  // install
          memcpy(bk[bno], px, 64);
          memcpy(bk[bno] + 8, py, 64);
          continue;
        }
        if (memcmp(bk[bno], px, 64) == 0) {
          if (memcmp(bk[bno] + 8, py, 64) == 0) {
            dbl[m] = 1;
          } else {
            memset(bk[bno], 0, 128);  // P + (-P)
            continue;
          }
        } else {
          dbl[m] = 0;
        }
        memcpy(x1a[m], bk[bno], 64);
        memcpy(y1a[m], bk[bno] + 8, 64);
        memcpy(x2a[m], px, 64);
        memcpy(y2a[m], py, 64);
        add_bkt[m] = bno;
        ++m;
      }
      processed = hi;
      if (!m) {
        if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
        continue;
      }
      g2_chunk_apply_ifma(x1a, y1a, x2a, y2a, dbl, m, x3a, y3a, scratch);
      for (long j = 0; j < m; ++j) {
        memcpy(bk[add_bkt[j]], x3a[j], 64);
        memcpy(bk[add_bkt[j]] + 8, y3a[j], 64);
      }
      if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
    }
    if (bail || next.size() * 4 > cur.size()) {
      // finish the stragglers with mixed-Jacobian adds, then merge
      G2Jac *jb = new G2Jac[nbuckets];
      memset(jb, 0, (size_t)nbuckets * sizeof(G2Jac));
      next.insert(next.end(), cur.begin() + processed, cur.end());
      for (long i : next) {
        int32_t dgt = sd[i * nwin + wi];
        long bno = dgt < 0 ? -dgt : dgt;
        const u64 *b = bases + 16 * i;
        Fp2 x2, y2;
        memcpy(x2.c0, b, 32);
        memcpy(x2.c1, b + 4, 32);
        if (dgt < 0) {
          neg_y(y2.c0, b + 8);
          neg_y(y2.c1, b + 12);
        } else {
          memcpy(y2.c0, b + 8, 32);
          memcpy(y2.c1, b + 12, 32);
        }
        g2_add_mixed(jb[bno], jb[bno], x2, y2);
      }
      G2Jac run, wsum;
      memset(&run, 0, sizeof(run));
      memset(&wsum, 0, sizeof(wsum));
      for (long d = nbuckets - 1; d >= 1; --d) {
        g2_add(run, jb[d]);
        bool empty = true;
        for (int q = 0; q < 16 && empty; ++q) empty = bk[d][q] == 0;
        if (!empty) {
          Fp2 x2, y2;
          memcpy(x2.c0, bk[d], 32);
          memcpy(x2.c1, bk[d] + 4, 32);
          memcpy(y2.c0, bk[d] + 8, 32);
          memcpy(y2.c1, bk[d] + 12, 32);
          g2_add_mixed(run, run, x2, y2);
        }
        g2_add(wsum, run);
      }
      delete[] jb;
      cleanup();
      *out = wsum;
      return;
    }
    cur.swap(next);
  }
  G2Jac run, wsum;
  memset(&run, 0, sizeof(run));
  memset(&wsum, 0, sizeof(wsum));
  for (long d = nbuckets - 1; d >= 1; --d) {
    bool empty = true;
    for (int q = 0; q < 16 && empty; ++q) empty = bk[d][q] == 0;
    if (!empty) {
      Fp2 x2, y2;
      memcpy(x2.c0, bk[d], 32);
      memcpy(x2.c1, bk[d] + 4, 32);
      memcpy(y2.c0, bk[d] + 8, 32);
      memcpy(y2.c1, bk[d] + 12, 32);
      g2_add_mixed(run, run, x2, y2);
    }
    g2_add(wsum, run);
  }
  cleanup();
  *out = wsum;
}
#endif  // ZKP2P_HAVE_IFMA

static void g2_window_sum(const u64 *bases, const int32_t *sd, long n,
                          int c, int nwin, int wi, G2Jac *out) {
#if ZKP2P_HAVE_IFMA
  if (ifma_enabled() && batch_affine_enabled()) {
    g2_window_sum_affine(bases, sd, n, c, nwin, wi, out);
    return;
  }
#endif
  g2_window_sum_jac(bases, sd, n, c, nwin, wi, out);
}

// Run window sums 0..nwin-1 through `sum_one(wi, &out[wi])`, on worker
// Vectorized SUM of a set of affine points (the scalar==±1 fast path of
// the witness MSMs: venmo's wires are ~90% SHA/DFA bits, so Pippenger
// sees half a million scalar-1 points piling into ONE bucket and bails
// to serial Jacobian — a pairwise tree through the 8-wide batch-affine
// apply does the same sum in ~n vector adds).  `ys` carries the
// (possibly negated) y of each point; both arrays are CONSUMED as
// scratch.  Result accumulated into *out (Jacobian).
static void g1_tree_sum(u64 (*xs)[4], u64 (*ys)[4], long n, G1Jac *out) {
  memset(out, 0, sizeof(G1Jac));
  if (n <= 0) return;
#if ZKP2P_HAVE_IFMA
  if (ifma_enabled() && n >= 64) {
    const long B = 2048;
    u64 (*x1a)[4] = new u64[B][4];
    u64 (*y1a)[4] = new u64[B][4];
    u64 (*x2a)[4] = new u64[B][4];
    u64 (*y2a)[4] = new u64[B][4];
    u64 (*x3a)[4] = new u64[B][4];
    u64 (*y3a)[4] = new u64[B][4];
    unsigned char *dbl = new unsigned char[B];
    u64 *scratch = new u64[(size_t)9 * 5 * B];
    while (n > 1) {
      long w = 0;  // write cursor for the next level
      long p = 0;  // pair read cursor
      while (p + 1 < n) {
        long m = 0;
        // schedule up to B pairs
        while (p + 1 < n && m < B) {
          u64 *x1 = xs[p], *y1 = ys[p], *x2 = xs[p + 1], *y2 = ys[p + 1];
          bool inf1 = is_zero4(x1) && is_zero4(y1);
          bool inf2 = is_zero4(x2) && is_zero4(y2);
          if (inf1 && inf2) {
            p += 2;
            continue;  // drop
          }
          if (inf1 || inf2) {  // pass the finite one through
            memcpy(xs[w], inf1 ? x2 : x1, 32);
            memcpy(ys[w], inf1 ? y2 : y1, 32);
            ++w;
            p += 2;
            continue;
          }
          if (memcmp(x1, x2, 32) == 0) {
            if (memcmp(y1, y2, 32) == 0) {
              dbl[m] = 1;  // doubling lane (apply handles)
            } else {
              p += 2;  // P + (-P): drop
              continue;
            }
          } else {
            dbl[m] = 0;
          }
          memcpy(x1a[m], x1, 32);
          memcpy(y1a[m], y1, 32);
          memcpy(x2a[m], x2, 32);
          memcpy(y2a[m], y2, 32);
          ++m;
          p += 2;
        }
        if (m > 0) {
          g1_chunk_apply_ifma(x1a, y1a, x2a, y2a, dbl, m, x3a, y3a, scratch);
          for (long j = 0; j < m; ++j) {
            memcpy(xs[w], x3a[j], 32);
            memcpy(ys[w], y3a[j], 32);
            ++w;
          }
        }
      }
      if (p < n) {  // odd leftover carries to the next level
        memcpy(xs[w], xs[p], 32);
        memcpy(ys[w], ys[p], 32);
        ++w;
      }
      n = w;
    }
    delete[] x1a;
    delete[] y1a;
    delete[] x2a;
    delete[] y2a;
    delete[] x3a;
    delete[] y3a;
    delete[] dbl;
    delete[] scratch;
    if (n == 1 && !(is_zero4(xs[0]) && is_zero4(ys[0]))) {
      jac_add_mixed(*out, *out, xs[0], ys[0]);
    }
    return;
  }
#endif
  for (long i = 0; i < n; ++i) {
    if (is_zero4(xs[i]) && is_zero4(ys[i])) continue;
    jac_add_mixed(*out, *out, xs[i], ys[i]);
  }
}

// the persistent worker pool when n_threads > 1.  Shared by the G1 and
// G2 MSMs (one driver to tune, not two copies).  The pool is grown to
// n_threads once and reused across calls — no thread spawn per MSM.
template <typename P, typename F>
static void run_window_sums(int nwin, int n_threads, P *wins, F sum_one) {
  if (n_threads > 1) {
    int w = n_threads < nwin ? n_threads : nwin;
    work_pool().ensure(w);
    work_pool().run(nwin, [&](long wi) { sum_one((int)wi, &wins[wi]); }, w);
  } else {
    for (int wi = 0; wi < nwin; ++wi) sum_one(wi, &wins[wi]);
  }
}

extern "C" {

// Variable-base Pippenger MSM over G1.  bases: n x 8 u64 affine
// Montgomery ((0,0) = infinity); scalars: n x 4 u64 STANDARD form
// (< r); out_xy: 8 u64 affine STANDARD form, (0,0) = infinity.
// Window width c is caller-chosen (glue picks ~log2(n)-7, clamped).
// n_threads > 1 computes window sums on worker threads (per-thread
// bucket memory: 96 B * 2^c each).
// Partition scalar indices for the MSM drivers: 0 dropped, +-1 into
// (ones, ones_neg) for the tree-sum path, the rest into `rest`.  ONE
// helper for G1 and G2 so the classification can never diverge.
static void classify_scalars(const u64 *scalars, long n, std::vector<long> &rest,
                             std::vector<long> &ones, std::vector<unsigned char> &ones_neg) {
  static const u64 ONE_S[4] = {1, 0, 0, 0};
  u64 rm1[4];
  sub_nored(rm1, R_MOD, ONE_S);
  rest.reserve(n);
  for (long i = 0; i < n; ++i) {
    const u64 *s = scalars + 4 * i;
    if (is_zero4(s)) continue;
    if (memcmp(s, ONE_S, 32) == 0) {
      ones.push_back(i);
      ones_neg.push_back(0);
    } else if (memcmp(s, rm1, 32) == 0) {
      ones.push_back(i);
      ones_neg.push_back(1);
    } else {
      rest.push_back(i);
    }
  }
}

// Tree-sum the +-1-scalar lanes (the dominant witness-MSM case) — shared
// by the plain and GLV Pippenger drivers.
static void g1_ones_tree_sum(const u64 *bases_xy, const std::vector<long> &ones,
                             const std::vector<unsigned char> &ones_neg, G1Jac *out) {
  memset(out, 0, sizeof(G1Jac));
  if (ones.empty()) return;
  long no = (long)ones.size();
  u64 (*xs)[4] = new u64[no][4];
  u64 (*ys)[4] = new u64[no][4];
  for (long k = 0; k < no; ++k) {
    const u64 *bx = bases_xy + 8 * ones[k];
    memcpy(xs[k], bx, 32);
    signed_pt_y(ys[k], bx + 4, ones_neg[k] != 0);
    if (is_zero4(bx) && is_zero4(bx + 4)) memset(ys[k], 0, 32);  // keep holes (0,0)
  }
  g1_tree_sum(xs, ys, no, out);
  delete[] xs;
  delete[] ys;
}

// Jacobian accumulator -> standard-form affine out_xy (the shared MSM tail).
static void g1_jac_out(const G1Jac &acc, u64 *out_xy) {
  if (is_zero4(acc.Z)) {
    memset(out_xy, 0, 64);
    return;
  }
  u64 zi[4], zi2[4], zi3[4], mx[4], my[4];
  mont_inv(zi, acc.Z);
  mont_sqr(zi2, zi);
  mont_mul(zi3, zi2, zi);
  mont_mul(mx, acc.X, zi2);
  mont_mul(my, acc.Y, zi3);
  fp_from_mont(mx, out_xy, 1);
  fp_from_mont(my, out_xy + 4, 1);
}

// The window-parallel Pippenger middle shared by the plain and GLV G1
// drivers: precomputed signed digits in (nr points x nwin windows),
// window sums + Horner fold added into *acc (caller-zeroed).
// b52_ext (opaque u64 rows of 10 = Aff52) lets the fixed-base tier pass
// its PERSISTENT 52-limb table so the per-MSM mont256 -> mont260
// conversion disappears from the hot loop; nullptr keeps the per-call
// conversion the variable-base drivers have always paid.
static void g1_pippenger_core(const u64 *pb, const int32_t *sd, long nr, int c,
                              int nwin, int n_threads, G1Jac *acc_out,
                              int total_bits = 254,
                              const u64 *b52_ext = nullptr) {
  G1Jac &acc = *acc_out;
  // ZKP2P_MSM_BATCH_AFFINE=0: every window through the mixed-Jacobian
  // fill — the A/B arm measuring what affine buckets + the shared batch
  // inversion buy (both the IFMA 52-limb tier and the scalar tier are
  // batch-affine, so the gate sits above them, read once per MSM).
  const bool batch_affine = batch_affine_enabled();
  {
    G1Jac *wins = new G1Jac[nwin];
#if ZKP2P_HAVE_IFMA
    const Aff52 *b52 = nullptr;
    Aff52 *b52_own = nullptr;
    if (ifma_enabled() && batch_affine) {
      if (b52_ext) {
        b52 = (const Aff52 *)b52_ext;
      } else {
        // one mont256 -> mont260 conversion per MSM; every window's fill
        // then runs conversion-free (persistent 52-limb storage)
        b52_own = new Aff52[nr];
        g1_bases_to_52(pb, nr, b52_own);
        b52 = b52_own;
      }
    }
#endif
#if ZKP2P_HAVE_IFMA
    // Deferred windows leave their bucket arrays in allbk; the vector
    // suffix then reduces up to SUFFIX_MAX_LANES windows in one call
    // (8-lane groups, interleaved) instead of 2^(c-1) serial Jacobian
    // adds per window.
    const long nbuckets52 = (1L << (c - 1)) + 1;
    Aff52 *allbk = nullptr;
    unsigned char *defer = nullptr;
    // Defer only single-threaded: with worker threads each window's
    // serial suffix already runs CONCURRENTLY on its own worker, and a
    // post-join vector pass would serialize that tail instead.  The
    // size cap only matters for the fixed tier's wide windows (the
    // variable-base sweep range never approaches it): past it the
    // windows reduce serially rather than holding a multi-hundred-MB
    // lane block.
    if (b52 && n_threads <= 1 &&
        (size_t)nwin * (size_t)nbuckets52 * sizeof(Aff52) <= ((size_t)256 << 20)) {
      allbk = new Aff52[(size_t)nwin * (size_t)nbuckets52]();
      defer = new unsigned char[nwin]();
    }
#endif
    run_window_sums(nwin, n_threads, wins, [&](int wi, G1Jac *o) {
#if ZKP2P_HAVE_IFMA
      if (b52) {
        if (!allbk) {  // multi-threaded: internal per-worker suffix
          g1_window_sum_52(pb, b52, sd, nr, c, nwin, wi, o, nullptr, total_bits);
          return;
        }
        defer[wi] = g1_window_sum_52(pb, b52, sd, nr, c, nwin, wi, o,
                                     allbk + (size_t)wi * (size_t)nbuckets52,
                                     total_bits)
                        ? 1
                        : 0;
        return;
      }
#endif
      if (batch_affine) {
        g1_window_sum(pb, sd, nr, c, nwin, wi, o, total_bits);
      } else {
        g1_window_sum_jac(pb, sd, nr, c, nwin, wi, o);
      }
    });
#if ZKP2P_HAVE_IFMA
    if (allbk) {
      long long sf0 = prof_now_ns();
      int lanes[SUFFIX_MAX_LANES], nl = 0;
      G1Jac louts[SUFFIX_MAX_LANES];
      for (int wi = 0; wi <= nwin; ++wi) {
        if (wi < nwin && defer[wi]) lanes[nl++] = wi;
        if (nl == SUFFIX_MAX_LANES || (wi == nwin && nl > 0)) {
          g1_suffix8(allbk, nbuckets52, lanes, nl, louts);
          for (int k = 0; k < nl; ++k) wins[lanes[k]] = louts[k];
          nl = 0;
        }
      }
      {
        long long sf = prof_now_ns() - sf0;
        stat_add(ST_MSM_SUFFIX_NS, sf);
        if (msm_prof_enabled()) g_prof_suffix_ns += sf;
      }
      delete[] allbk;
      delete[] defer;
    }
#endif
#if ZKP2P_HAVE_IFMA
    delete[] b52_own;
#endif
    for (int wi = nwin - 1; wi >= 0; --wi) {
      if (wi != nwin - 1)
        for (int k = 0; k < c; ++k) jac_double(acc, acc);
      g1_add_jac(acc, wins[wi]);
    }
    delete[] wins;
  }
}

// ===================================================================
// Multi-column Pippenger: ONE sweep over a fixed base array fills S
// independent bucket sets per window (bucket id = s * nbuckets + |d|),
// so every batch-affine inversion round carries adds from ALL columns —
// the inversion batch density rises ~S x exactly where the 52-bit and
// scalar batch-affine tiers pay their per-round costs (the chunk
// schedule, the one mont_inv per chunk, the SoA gather/transpose).  The
// chunk-apply kernels (g1_chunk_apply_52, the scalar batch inversion)
// run UNCHANGED: they address buckets through add_bkt and bases through
// add_pt, and neither cares that the bucket space is S arrays long.
// The amortized wins stack: the mont256 -> mont260 base conversion runs
// once for S MSMs, every base cache line is touched once per window
// instead of S times, and partially-filled chunks still ship full
// inversion batches.
//
// A work item is (point i, column s) encoded as i*S + s, built i-outer
// so the sweep stays base-sequential; digits come from per-column digit
// arrays sds[s] (row-major over the shared compacted index space, with
// all-zero rows for scalars another tier handled).  Column outputs are
// the exact group elements of S sequential single-column MSMs — the
// final affine canonicalization makes them byte-identical, so the
// sequential driver stays the parity oracle.

// Work-item encoding for the multi fills: (point i, column s) packed as
// (i << sbits) | s — shift/mask decode, never a runtime division (the
// schedule loop visits tens of millions of entries per MSM and S is not
// a compile-time constant).
static inline int multi_sbits(int S) {
  int sb = 0;
  while ((1 << sb) < S) ++sb;
  return sb;
}

// Run fn(0..njobs-1) on the pool (width-capped) or inline — the multi
// drivers' job runner (a job may span several output slots, unlike
// run_window_sums' one-window-one-slot contract).
static void run_indexed_jobs(long njobs, int n_threads,
                             const std::function<void(long)> &fn) {
  if (n_threads > 1 && njobs > 1) {
    int w = (long)n_threads < njobs ? n_threads : (int)njobs;
    work_pool().ensure(w);
    work_pool().run(njobs, fn, w);
  } else {
    for (long j = 0; j < njobs; ++j) fn(j);
  }
}

#if ZKP2P_HAVE_IFMA
// 52-native multi-column window fill: the S-column mirror of
// g1_window_sum_52.  bk_ext (caller-zeroed, S*nbuckets entries) defers
// the suffix to the caller's 8-lane vector pass (lane id = wi*S + s);
// returns true when it was filled, false when *outs was computed via a
// fallback tier or the internal per-column suffix.
static bool g1_window_sum_52_multi(const u64 *bases_xy, const Aff52 *b52,
                                   const int32_t *const *sds, int S, long n,
                                   int c, int nwin, int wi, G1Jac *outs,
                                   Aff52 *bk_ext, int total_bits) {
  Ifma52Field &F = fq52_field();
  const long nbuckets = (1L << (c - 1)) + 1;
  // Chunk size matches the single-column fill.  (Scaling it to 2048*S —
  // per-column conflict parity, S x fewer inversion rounds — was tried
  // and measured the whole batch ~12% SLOWER: the apply's SoA scratch
  // grows with B and evicts the bucket lines the schedule loop just
  // touched, costing a second miss per add at writeback.)
  const long B = 2048;
  int bits_here = total_bits - wi * c;
  if (bits_here > c) bits_here = c;
  if (bits_here < 1 || (1L << bits_here) < 4 * B) {
    // small/top windows: per column through the same tiers the
    // single-column driver takes (arm parity with the oracle path)
    for (int s = 0; s < S; ++s) {
      if (bits_here >= 0 && bits_here <= 8) {
        g1_window_sum_small(bases_xy, sds[s], n, c, nwin, wi, bits_here, &outs[s]);
      } else {
        g1_window_sum_jac(bases_xy, sds[s], n, c, nwin, wi, &outs[s]);
      }
    }
    return false;
  }
  const int sbits = multi_sbits(S);
  const long smask = (1L << sbits) - 1;
  Aff52 *bk = bk_ext ? bk_ext : new Aff52[(size_t)S * nbuckets]();
  int *stamp = new int[(size_t)S * nbuckets];
  memset(stamp, 0xff, (size_t)S * nbuckets * sizeof(int));
  std::vector<long> cur, next;
  cur.reserve((size_t)n * S);
  // i-outer entry order: all S columns of one point are adjacent, so
  // each base line is loaded once per window for the whole batch.  (A
  // point-block x column tiling was tried for bucket locality — it
  // kept each run inside one column's bucket set but quadrupled the
  // same-bucket defers back to the sequential rate and measured
  // net-slower; the prefetch below is the cheaper answer to the S-wide
  // bucket block's misses.)
  for (long i = 0; i < n; ++i) {
    if (aff52_is_zero(b52[i].x) && aff52_is_zero(b52[i].y)) continue;
    for (int s = 0; s < S; ++s)
      if (sds[s][i * nwin + wi]) cur.push_back((i << sbits) | s);
  }
  long *add_bkt = new long[B];
  long *add_pt = new long[B];
  unsigned char *negf = new unsigned char[B];
  u64 (*x3a)[5] = new u64[B][5];
  u64 (*y3a)[5] = new u64[B][5];
  unsigned char *dbl = new unsigned char[B];
  u64 *scratch = new u64[(size_t)8 * 5 * B];
  auto cleanup = [&]() {
    if (!bk_ext) delete[] bk;
    delete[] stamp;
    delete[] add_bkt;
    delete[] add_pt;
    delete[] negf;
    delete[] x3a;
    delete[] y3a;
    delete[] dbl;
    delete[] scratch;
  };
  int chunk_id = 0;
  long long n_dbl = 0, n_cancel = 0, n_defer = 0;
  long long fl0 = prof_now_ns();
  while (!cur.empty()) {
    next.clear();
    size_t processed = 0;
    bool bail = false;
    for (size_t lo = 0; lo < cur.size() && !bail; lo += B, ++chunk_id) {
      size_t hi = lo + B < cur.size() ? lo + B : cur.size();
      long m = 0;
      for (size_t k = lo; k < hi; ++k) {
        // prefetch the bucket line + stamp a few entries ahead: the
        // S-wide bucket block (S x nbuckets x 80 B) outgrows L2, and a
        // demand-missed bucket read stalls the whole schedule walk —
        // this is where the first multi profile lost its S x win
        if (k + 16 < hi) {
          long e2 = cur[k + 16];
          long i2 = e2 >> sbits;
          int s2 = (int)(e2 & smask);
          int32_t d2 = sds[s2][i2 * nwin + wi];
          long pb2 = (long)s2 * nbuckets + (d2 < 0 ? -d2 : d2);
          __builtin_prefetch(&stamp[pb2]);
          __builtin_prefetch(&bk[pb2]);
          __builtin_prefetch((const char *)&bk[pb2] + 64);
        }
        long e = cur[k];
        long i = e >> sbits;
        int s = (int)(e & smask);
        int32_t dgt = sds[s][i * nwin + wi];
        long bno = (long)s * nbuckets + (dgt < 0 ? -dgt : dgt);
        if (stamp[bno] == chunk_id) {
          next.push_back(e);
          ++n_defer;
          continue;
        }
        stamp[bno] = chunk_id;
        u64 py[5];
        if (dgt < 0) {
          neg52(py, b52[i].y, F);
        } else {
          memcpy(py, b52[i].y, 40);
        }
        if (aff52_is_zero(bk[bno].x) && aff52_is_zero(bk[bno].y)) {
          memcpy(bk[bno].x, b52[i].x, 40);
          memcpy(bk[bno].y, py, 40);
          continue;
        }
        if (memcmp(bk[bno].x, b52[i].x, 40) == 0) {
          if (memcmp(bk[bno].y, py, 40) == 0) {
            dbl[m] = 1;
            ++n_dbl;
          } else {
            memset(&bk[bno], 0, sizeof(Aff52));  // P + (-P)
            ++n_cancel;
            continue;
          }
        } else {
          dbl[m] = 0;
        }
        add_bkt[m] = bno;
        add_pt[m] = i;
        negf[m] = dgt < 0 ? 1 : 0;
        ++m;
      }
      processed = hi;
      if (!m) {
        if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
        continue;
      }
      long long ap0 = prof_now_ns();
      g1_chunk_apply_52(bk, b52, add_bkt, add_pt, negf, dbl, m, x3a, y3a, scratch);
      stat_add(ST_MSM_APPLY_NS, prof_now_ns() - ap0);
      const bool pf_wb = msm_interleave_enabled();
      for (long j = 0; j < m; ++j) {
        // write-prefetch ahead — the chunk working set evicted these
        // bucket lines since the gather (see g1_window_sum_52)
        if (pf_wb && j + 8 < m) {
          char *wb = (char *)&bk[add_bkt[j + 8]];
          __builtin_prefetch(wb, 1);
          __builtin_prefetch(wb + 64, 1);
        }
        memcpy(bk[add_bkt[j]].x, x3a[j], 40);
        memcpy(bk[add_bkt[j]].y, y3a[j], 40);
      }
      if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
    }
    if (bail || next.size() * 4 > cur.size()) {
      stat_add(ST_MSM_FILL_NS, prof_now_ns() - fl0);
      stat_add(ST_MSM_DBL_LANES, n_dbl);
      stat_add(ST_MSM_CANCEL_LANES, n_cancel);
      stat_add(ST_MSM_DEFER_HITS, n_defer);
      long long bs0 = prof_now_ns();
      G1Jac *jb = new G1Jac[(size_t)S * nbuckets];
      memset(jb, 0, (size_t)S * nbuckets * sizeof(G1Jac));
      next.insert(next.end(), cur.begin() + processed, cur.end());
      for (long e : next) {
        long i = e >> sbits;
        int s = (int)(e & smask);
        int32_t dgt = sds[s][i * nwin + wi];
        long bno = (long)s * nbuckets + (dgt < 0 ? -dgt : dgt);
        const u64 *x = bases_xy + 8 * i;
        u64 ys[4];
        signed_pt_y(ys, x + 4, dgt < 0);
        jac_add_mixed(jb[bno], jb[bno], x, ys);
      }
      stat_add(ST_MSM_BAILFILL_NS, prof_now_ns() - bs0);
      bs0 = prof_now_ns();
      for (int s = 0; s < S; ++s) {
        G1Jac run, wsum;
        memset(&run, 0, sizeof(run));
        memset(&wsum, 0, sizeof(wsum));
        for (long d = nbuckets - 1; d >= 1; --d) {
          g1_add_jac(run, jb[(long)s * nbuckets + d]);
          const Aff52 &bd = bk[(long)s * nbuckets + d];
          if (!(aff52_is_zero(bd.x) && aff52_is_zero(bd.y))) {
            u64 bx[4], by[4];
            limb52_to_mont256(bd.x, bx, F);
            limb52_to_mont256(bd.y, by, F);
            jac_add_mixed(run, run, bx, by);
          }
          g1_add_jac(wsum, run);
        }
        outs[s] = wsum;
      }
      stat_add(ST_MSM_SUFFIX_NS, prof_now_ns() - bs0);
      delete[] jb;
      cleanup();
      return false;
    }
    cur.swap(next);
  }
  stat_add(ST_MSM_FILL_NS, prof_now_ns() - fl0);
  stat_add(ST_MSM_DBL_LANES, n_dbl);
  stat_add(ST_MSM_CANCEL_LANES, n_cancel);
  stat_add(ST_MSM_DEFER_HITS, n_defer);
  if (bk_ext) {
    cleanup();
    return true;  // caller reduces the S lanes through the vector suffix
  }
  long long sf0 = prof_now_ns();
  for (int s = 0; s < S; ++s) {
    G1Jac run, wsum;
    memset(&run, 0, sizeof(run));
    memset(&wsum, 0, sizeof(wsum));
    for (long d = nbuckets - 1; d >= 1; --d) {
      const Aff52 &bd = bk[(long)s * nbuckets + d];
      if (!(aff52_is_zero(bd.x) && aff52_is_zero(bd.y))) {
        u64 bx[4], by[4];
        limb52_to_mont256(bd.x, bx, F);
        limb52_to_mont256(bd.y, by, F);
        jac_add_mixed(run, run, bx, by);
      }
      g1_add_jac(wsum, run);
    }
    outs[s] = wsum;
  }
  stat_add(ST_MSM_SUFFIX_NS, prof_now_ns() - sf0);
  cleanup();
  return false;
}
#endif  // ZKP2P_HAVE_IFMA

// Scalar-Montgomery multi-column window fill: the S-column mirror of
// g1_window_sum (the batch-affine tier on hosts without IFMA, or with
// it disabled).  Same shared-chunk batch inversion over the S-wide
// bucket space; num/den derive from the live bucket + base by index
// (each bucket is touched once per chunk, so the bucket at derive time
// IS its schedule-time state).  Internal per-column suffix.
static void g1_window_sum_multi(const u64 *bases_xy, const int32_t *const *sds,
                                int S, long n, int c, int nwin, int wi,
                                G1Jac *outs, int total_bits) {
  const long nbuckets = (1L << (c - 1)) + 1;
  const long B = 2048;  // single-column chunk — see the 52-bit multi fill
  int bits_here = total_bits - wi * c;
  if (bits_here > c) bits_here = c;
  if (bits_here < 1 || (1L << bits_here) < 4 * B) {
    for (int s = 0; s < S; ++s)
      g1_window_sum_jac(bases_xy, sds[s], n, c, nwin, wi, &outs[s]);
    return;
  }
  const int sbits = multi_sbits(S);
  const long smask = (1L << sbits) - 1;
  AffPt *bk = new AffPt[(size_t)S * nbuckets]();
  int *stamp = new int[(size_t)S * nbuckets];
  memset(stamp, 0xff, (size_t)S * nbuckets * sizeof(int));
  std::vector<long> cur, next;
  cur.reserve((size_t)n * S);
  // i-outer entry order — see the 52-bit multi fill
  for (long i = 0; i < n; ++i) {
    const u64 *x = bases_xy + 8 * i;
    if (is_zero4(x) && is_zero4(x + 4)) continue;
    for (int s = 0; s < S; ++s)
      if (sds[s][i * nwin + wi]) cur.push_back((i << sbits) | s);
  }
  long *add_bkt = new long[B];
  long *add_pt = new long[B];
  unsigned char *negf = new unsigned char[B];
  u64 (*den)[4] = new u64[B][4];
  u64 (*num)[4] = new u64[B][4];
  u64 (*prod)[4] = new u64[B][4];
  unsigned char *dbl = new unsigned char[B];
  auto cleanup = [&]() {
    delete[] bk;
    delete[] stamp;
    delete[] add_bkt;
    delete[] add_pt;
    delete[] negf;
    delete[] den;
    delete[] num;
    delete[] prod;
    delete[] dbl;
  };
  int chunk_id = 0;
  long long n_dbl = 0, n_cancel = 0, n_defer = 0;
  long long fl0 = prof_now_ns();
  while (!cur.empty()) {
    next.clear();
    size_t processed = 0;
    bool bail = false;
    for (size_t lo = 0; lo < cur.size() && !bail; lo += B, ++chunk_id) {
      size_t hi = lo + B < cur.size() ? lo + B : cur.size();
      long m = 0;
      for (size_t k = lo; k < hi; ++k) {
        if (k + 16 < hi) {  // see the 52-bit multi fill: hide the S-wide
          long e2 = cur[k + 16];  // bucket block's L2 misses
          long i2 = e2 >> sbits;
          int s2 = (int)(e2 & smask);
          int32_t d2 = sds[s2][i2 * nwin + wi];
          long pb2 = (long)s2 * nbuckets + (d2 < 0 ? -d2 : d2);
          __builtin_prefetch(&stamp[pb2]);
          __builtin_prefetch(&bk[pb2]);
        }
        long e = cur[k];
        long i = e >> sbits;
        int s = (int)(e & smask);
        int32_t dgt = sds[s][i * nwin + wi];
        long bno = (long)s * nbuckets + (dgt < 0 ? -dgt : dgt);
        if (stamp[bno] == chunk_id) {
          next.push_back(e);
          ++n_defer;
          continue;
        }
        stamp[bno] = chunk_id;
        const u64 *px = bases_xy + 8 * i;
        u64 py[4];
        signed_pt_y(py, px + 4, dgt < 0);
        if (aff_is_empty(bk[bno])) {
          memcpy(bk[bno].x, px, 32);
          memcpy(bk[bno].y, py, 32);
          continue;
        }
        if (memcmp(bk[bno].x, px, 32) == 0) {
          if (memcmp(bk[bno].y, py, 32) == 0) {
            dbl[m] = 1;
            ++n_dbl;
          } else {
            memset(&bk[bno], 0, sizeof(AffPt));  // P + (-P)
            ++n_cancel;
            continue;
          }
        } else {
          dbl[m] = 0;
        }
        add_bkt[m] = bno;
        add_pt[m] = i;
        negf[m] = dgt < 0 ? 1 : 0;
        ++m;
      }
      processed = hi;
      if (!m) {
        if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
        continue;
      }
      // shared batch inversion across ALL columns' adds in this chunk
      u64 run[4];
      memcpy(run, ONE_MONT, 32);
      for (long j = 0; j < m; ++j) {
        long b = add_bkt[j];
        const u64 *px = bases_xy + 8 * add_pt[j];
        if (dbl[j]) {
          u64 xsq[4], t[4];
          mont_sqr(xsq, bk[b].x);
          add_mod(t, xsq, xsq);
          add_mod(num[j], t, xsq);
          add_mod(den[j], bk[b].y, bk[b].y);
        } else {
          u64 py[4];
          signed_pt_y(py, px + 4, negf[j] != 0);
          sub_mod(num[j], py, bk[b].y);
          sub_mod(den[j], px, bk[b].x);
        }
        memcpy(prod[j], run, 32);
        mont_mul(run, run, den[j]);
      }
      u64 inv_all[4];
      mont_inv(inv_all, run);
      for (long j = m - 1; j >= 0; --j) {
        u64 dinv[4];
        mont_mul(dinv, inv_all, prod[j]);
        mont_mul(inv_all, inv_all, den[j]);
        long b = add_bkt[j];
        const u64 *px = bases_xy + 8 * add_pt[j];
        u64 lam[4], lam2[4], x3[4], y3[4], t[4];
        mont_mul(lam, num[j], dinv);
        mont_sqr(lam2, lam);
        sub_mod(x3, lam2, bk[b].x);
        sub_mod(x3, x3, px);
        sub_mod(t, bk[b].x, x3);
        mont_mul(t, lam, t);
        sub_mod(y3, t, bk[b].y);
        memcpy(bk[b].x, x3, 32);
        memcpy(bk[b].y, y3, 32);
      }
      if (next.size() * 2 > processed && processed >= (size_t)B) bail = true;
    }
    if (bail || next.size() * 4 > cur.size()) {
      stat_add(ST_MSM_FILL_NS, prof_now_ns() - fl0);
      stat_add(ST_MSM_DBL_LANES, n_dbl);
      stat_add(ST_MSM_CANCEL_LANES, n_cancel);
      stat_add(ST_MSM_DEFER_HITS, n_defer);
      long long bs0 = prof_now_ns();
      G1Jac *jb = new G1Jac[(size_t)S * nbuckets];
      memset(jb, 0, (size_t)S * nbuckets * sizeof(G1Jac));
      next.insert(next.end(), cur.begin() + processed, cur.end());
      for (long e : next) {
        long i = e >> sbits;
        int s = (int)(e & smask);
        int32_t dgt = sds[s][i * nwin + wi];
        long bno = (long)s * nbuckets + (dgt < 0 ? -dgt : dgt);
        const u64 *x = bases_xy + 8 * i;
        u64 ys[4];
        signed_pt_y(ys, x + 4, dgt < 0);
        jac_add_mixed(jb[bno], jb[bno], x, ys);
      }
      stat_add(ST_MSM_BAILFILL_NS, prof_now_ns() - bs0);
      bs0 = prof_now_ns();
      for (int s = 0; s < S; ++s) {
        G1Jac run, wsum;
        memset(&run, 0, sizeof(run));
        memset(&wsum, 0, sizeof(wsum));
        for (long d = nbuckets - 1; d >= 1; --d) {
          g1_add_jac(run, jb[(long)s * nbuckets + d]);
          const AffPt &bd = bk[(long)s * nbuckets + d];
          if (!aff_is_empty(bd)) jac_add_mixed(run, run, bd.x, bd.y);
          g1_add_jac(wsum, run);
        }
        outs[s] = wsum;
      }
      stat_add(ST_MSM_SUFFIX_NS, prof_now_ns() - bs0);
      delete[] jb;
      cleanup();
      return;
    }
    cur.swap(next);
  }
  stat_add(ST_MSM_FILL_NS, prof_now_ns() - fl0);
  stat_add(ST_MSM_DBL_LANES, n_dbl);
  stat_add(ST_MSM_CANCEL_LANES, n_cancel);
  stat_add(ST_MSM_DEFER_HITS, n_defer);
  long long sf0 = prof_now_ns();
  for (int s = 0; s < S; ++s) {
    G1Jac run, wsum;
    memset(&run, 0, sizeof(run));
    memset(&wsum, 0, sizeof(wsum));
    for (long d = nbuckets - 1; d >= 1; --d) {
      const AffPt &bd = bk[(long)s * nbuckets + d];
      if (!aff_is_empty(bd)) jac_add_mixed(run, run, bd.x, bd.y);
      g1_add_jac(wsum, run);
    }
    outs[s] = wsum;
  }
  stat_add(ST_MSM_SUFFIX_NS, prof_now_ns() - sf0);
  cleanup();
}

// The multi-column Pippenger middle: window sums filled S columns at a
// time (batch-affine tiers — the shared-inversion win) or per (window,
// column) (the Jacobian A/B arm, which has no rounds to share and so
// takes the wider parallel axis), Horner-folded per column into
// accs[0..S) (caller-zeroed).
static void g1_pippenger_core_multi(const u64 *pb, const int32_t *const *sds,
                                    int S, long nr, int c, int nwin,
                                    int n_threads, G1Jac *accs,
                                    int total_bits = 254,
                                    const u64 *b52_ext = nullptr) {
  const bool batch_affine = batch_affine_enabled();
  G1Jac *wins = new G1Jac[(size_t)nwin * S];
  if (!batch_affine) {
    run_indexed_jobs((long)nwin * S, n_threads, [&](long j) {
      int wi = (int)(j / S), s = (int)(j % S);
      g1_window_sum_jac(pb, sds[s], nr, c, nwin, wi, &wins[(size_t)wi * S + s]);
    });
  } else {
#if ZKP2P_HAVE_IFMA
    if (ifma_enabled()) {
      // the fixed tier's persistent table, else ONE conversion for S columns
      Aff52 *b52_own = nullptr;
      const Aff52 *b52 = (const Aff52 *)b52_ext;
      if (!b52) {
        b52_own = new Aff52[nr];
        g1_bases_to_52(pb, nr, b52_own);
        b52 = b52_own;
      }
      const long nbuckets52 = (1L << (c - 1)) + 1;
      Aff52 *allbk = nullptr;
      unsigned char *defer = nullptr;
      // Deferred vector suffix single-threaded only, like the
      // single-column core.  (Engaging it at n_threads > 1 was tried —
      // a lone multi call DID win, the post-join vector pass beating
      // two workers' serial walks — but in the real prove several
      // concurrent multi calls each hold an nwin x S x nbuckets x 80 B
      // lane block, ~300 MB of extra fill-write/suffix-read traffic
      // that thrashed what per-window local bucket arrays keep
      // cache-resident, and the whole batch measured ~15% slower.)
      // Memory cap: S multiplies the single-column block.
      if (n_threads <= 1 &&
          (size_t)nwin * S * (size_t)nbuckets52 * sizeof(Aff52) <=
              ((size_t)160 << 20)) {
        allbk = new Aff52[(size_t)nwin * S * (size_t)nbuckets52]();
        defer = new unsigned char[nwin]();
      }
      run_indexed_jobs(nwin, n_threads, [&](long wi) {
        if (!allbk) {
          g1_window_sum_52_multi(pb, b52, sds, S, nr, c, nwin, (int)wi,
                                 &wins[(size_t)wi * S], nullptr, total_bits);
          return;
        }
        defer[wi] =
            g1_window_sum_52_multi(
                pb, b52, sds, S, nr, c, nwin, (int)wi, &wins[(size_t)wi * S],
                allbk + (size_t)wi * S * (size_t)nbuckets52, total_bits)
                ? 1
                : 0;
      });
      if (allbk) {
        // one vector suffix over ALL deferred (window, column) lanes:
        // lane id wi*S + s indexes allbk exactly like a window id
        // indexes the single-column block, so g1_suffix8 runs unchanged
        // — and S columns mean fuller 8-lane groups than nwin alone.
        long long sf0 = prof_now_ns();
        int lanes[SUFFIX_MAX_LANES], nl = 0;
        G1Jac louts[SUFFIX_MAX_LANES];
        const long nlanes = (long)nwin * S;
        for (long ln = 0; ln <= nlanes; ++ln) {
          if (ln < nlanes && defer[ln / S]) lanes[nl++] = (int)ln;
          if (nl == SUFFIX_MAX_LANES || (ln == nlanes && nl > 0)) {
            g1_suffix8(allbk, nbuckets52, lanes, nl, louts);
            for (int k = 0; k < nl; ++k) wins[lanes[k]] = louts[k];
            nl = 0;
          }
        }
        stat_add(ST_MSM_SUFFIX_NS, prof_now_ns() - sf0);
        delete[] allbk;
        delete[] defer;
      }
      delete[] b52_own;
    } else
#endif
    {
      run_indexed_jobs(nwin, n_threads, [&](long wi) {
        g1_window_sum_multi(pb, sds, S, nr, c, nwin, (int)wi,
                            &wins[(size_t)wi * S], total_bits);
      });
    }
  }
  for (int s = 0; s < S; ++s) {
    G1Jac &acc = accs[s];
    for (int wi = nwin - 1; wi >= 0; --wi) {
      if (wi != nwin - 1)
        for (int k = 0; k < c; ++k) jac_double(acc, acc);
      g1_add_jac(acc, wins[(size_t)wi * S + s]);
    }
  }
  delete[] wins;
}

void g1_msm_pippenger_mt(const u64 *bases_xy, const u64 *scalars, long n,
                         int c, int n_threads, u64 *out_xy) {
  long long t0 = prof_now_ns();
  InflightStat _ifs(ST_MSM_INFLIGHT);
  stat_add(ST_MSM_G1_CALLS, 1);
  stat_add(ST_MSM_POINTS, n);
  stat_set(ST_MSM_WINDOW_LAST, c);
  if (batch_affine_enabled()) stat_add(ST_MSM_BATCH_AFFINE_CALLS, 1);
  // Scalar classification: 0 (contributes nothing), +-1 (the dominant
  // case for witness MSMs — bit wires — whose Pippenger digits all pile
  // into ONE bucket and force the serial bail path) go through the
  // vectorized tree sum; everything else rides Pippenger.
  std::vector<long> rest, ones;
  std::vector<unsigned char> ones_neg;
  classify_scalars(scalars, n, rest, ones, ones_neg);
  G1Jac ones_acc;
  g1_ones_tree_sum(bases_xy, ones, ones_neg, &ones_acc);

  G1Jac acc;
  memset(&acc, 0, sizeof(acc));
  long nr = (long)rest.size();
  if (nr > 0) {
    // compact the Pippenger inputs unless nothing was stripped
    const u64 *pb = bases_xy;
    const u64 *ps = scalars;
    u64 *cb = nullptr, *csc = nullptr;
    if (nr != n) {
      cb = new u64[(size_t)nr * 8];
      csc = new u64[(size_t)nr * 4];
      for (long k = 0; k < nr; ++k) {
        memcpy(cb + 8 * k, bases_xy + 8 * rest[k], 64);
        memcpy(csc + 4 * k, scalars + 4 * rest[k], 32);
      }
      pb = cb;
      ps = csc;
    }
    int nwin = (254 + c - 1) / c;
    // signed recoding needs the top window to absorb the carry (Fr < 2^254)
    while ((long)nwin * c < 255) ++nwin;
    int32_t *sd = new int32_t[(size_t)nr * nwin];
    for (long i = 0; i < nr; ++i) signed_digits(ps + 4 * i, c, nwin, sd + (size_t)i * nwin);
    g1_pippenger_core(pb, sd, nr, c, nwin, n_threads, &acc);
    delete[] sd;
    delete[] cb;
    delete[] csc;
  }
  g1_add_jac(acc, ones_acc);
  g1_jac_out(acc, out_xy);
  stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
}

void g1_msm_pippenger(const u64 *bases_xy, const u64 *scalars, long n,
                      int c, u64 *out_xy) {
  g1_msm_pippenger_mt(bases_xy, scalars, n, c, 1, out_xy);
}

// ---------------------------------------------------------------------------
// GLV endomorphism MSM.  phi(x, y) = (beta*x, y) acts as multiplication
// by lambda (a cube root of unity in Fr), so each 254-bit scalar splits
// into two ~128-bit half-scalars k = k1 + k2*lambda and the n-point MSM
// runs as 2n points over HALF the windows.  All constants (beta in
// Montgomery form, the Barrett mus, the lattice-term magnitudes and
// subtract flags) are DERIVED in Python (field.bn254) and passed in as
// one u64 buffer — nothing curve-specific is hardcoded here, and the
// three implementations (host oracle, JAX limb kernel, this) are
// diffed integer-for-integer by the tests.
//
// glv_consts layout (u64 words):
//   [0..3]   beta (Montgomery)
//   [4..7]   mu1 = floor(|m1| * 2^256 / r)
//   [8..11]  mu2 = floor(|m2| * 2^256 / r)
//   [12..19] |a1|, |a2|   (k1 term magnitudes)
//   [20..27] |b1|, |b2|   (k2 term magnitudes)
//   [28]     flags: bit j   = subtract k1 term j
//                   bit 2+j = subtract k2 term j

static void mul256_full(const u64 a[4], const u64 b[4], u64 out[8]) {
  u64 t[8] = {0, 0, 0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)a[i] * b[j] + t[i + j] + (u64)carry;
      t[i + j] = (u64)cur;
      carry = cur >> 64;
    }
    t[i + 4] = (u64)carry;
  }
  memcpy(out, t, 64);
}

static inline void add256_mod(u64 a[4], const u64 b[4]) {
  u128 carry = 0;
  for (int i = 0; i < 4; ++i) {
    u128 cur = (u128)a[i] + b[i] + (u64)carry;
    a[i] = (u64)cur;
    carry = cur >> 64;
  }
}

static inline void sub256_mod(u64 a[4], const u64 b[4]) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 cur = (u128)a[i] - b[i] - (u64)borrow;
    a[i] = (u64)cur;
    borrow = (cur >> 64) & 1;
  }
}

static inline void neg256(u64 a[4]) {
  u64 z[4] = {0, 0, 0, 0};
  u64 t[4];
  memcpy(t, a, 32);
  memcpy(a, z, 32);
  sub256_mod(a, t);
}

// One scalar -> (|k1|, neg1, |k2|, neg2), mod-2^256 wraparound exactly
// like the host oracle field.bn254.glv_decompose.
static void glv_split(const u64 k[4], const u64 *gc, u64 k1[4], int *neg1,
                      u64 k2[4], int *neg2) {
  u64 p[8], c1[4], c2[4], t[8];
  mul256_full(k, gc + 4, p);
  memcpy(c1, p + 4, 32);  // floor(k * mu1 / 2^256)
  mul256_full(k, gc + 8, p);
  memcpy(c2, p + 4, 32);
  const u64 flags = gc[28];
  const u64 *cs[2] = {c1, c2};
  memcpy(k1, k, 32);
  memset(k2, 0, 32);
  for (int j = 0; j < 2; ++j) {
    mul256_full(cs[j], gc + 12 + 4 * j, t);  // lo 4 limbs = product mod 2^256
    if ((flags >> j) & 1) sub256_mod(k1, t); else add256_mod(k1, t);
    mul256_full(cs[j], gc + 20 + 4 * j, t);
    if ((flags >> (2 + j)) & 1) sub256_mod(k2, t); else add256_mod(k2, t);
  }
  *neg1 = (int)(k1[3] >> 63);
  if (*neg1) neg256(k1);
  *neg2 = (int)(k2[3] >> 63);
  if (*neg2) neg256(k2);
}

extern "C" void glv_decompose_batch(const u64 *scalars, long n, const u64 *gc,
                                    u64 *out, unsigned char *negs) {
  // out[i] = |k1_i|, out[n+i] = |k2_i| (u64x4 rows); negs likewise.
  for (long i = 0; i < n; ++i) {
    int n1, n2;
    glv_split(scalars + 4 * i, gc, out + 4 * i, &n1, out + 4 * (n + i), &n2);
    negs[i] = (unsigned char)n1;
    negs[n + i] = (unsigned char)n2;
  }
}

extern "C" void g1_glv_phi_bases(const u64 *bases_xy, long n,
                                 const u64 *beta_mont, u64 *out_xy) {
  // out[i] = phi(P_i) = (beta * x_i, y_i); (0,0) holes map to (0,0)
  // (beta * 0 = 0), so pruned-key padding survives the endomorphism.
  for (long i = 0; i < n; ++i) {
    mont_mul(out_xy + 8 * i, bases_xy + 8 * i, beta_mont);
    memcpy(out_xy + 8 * i + 4, bases_xy + 8 * i + 4, 32);
  }
}

// GLV Pippenger driver: bases2_xy is the 2*nb-point doubled base set
// [P_0..P_{nb-1}, phi(P_0)..phi(P_{nb-1})] (see g1_glv_phi_bases; the
// caller caches it per key, so the phi half sits at offset nb
// regardless of how many scalars this call brings); scalars stay the
// n (<= nb) original Fr scalars.  glv_bits bounds |k_i| (< 2^glv_bits),
// so nwin = ceil((glv_bits+1)/c) — HALF the plain entry's window count
// at the same c.
void g1_msm_pippenger_glv_mt(const u64 *bases2_xy, const u64 *scalars, long n,
                             long nb, int c, int n_threads,
                             const u64 *glv_consts, int glv_bits, u64 *out_xy) {
  long long t0 = prof_now_ns();
  InflightStat _ifs(ST_MSM_INFLIGHT);
  stat_add(ST_MSM_GLV_CALLS, 1);
  stat_add(ST_MSM_POINTS, n);
  stat_set(ST_MSM_WINDOW_LAST, c);
  if (batch_affine_enabled()) stat_add(ST_MSM_BATCH_AFFINE_CALLS, 1);
  std::vector<long> rest, ones;
  std::vector<unsigned char> ones_neg;
  classify_scalars(scalars, n, rest, ones, ones_neg);
  G1Jac ones_acc;
  g1_ones_tree_sum(bases2_xy, ones, ones_neg, &ones_acc);  // +-1: plain P_i half

  G1Jac acc;
  memset(&acc, 0, sizeof(acc));
  long nr = (long)rest.size();
  if (nr > 0) {
    int nwin = (glv_bits + c - 1) / c;
    while ((long)nwin * c < glv_bits + 1) ++nwin;  // top-window carry absorb
    // Compact only when needed (same rule as the plain driver): with
    // nothing stripped and n == nb the doubled base array already has
    // the exact [P.., phi(P)..] layout the core wants — skip the
    // 2n x 64 B allocation + copy (~67 MB per prove at the 2^19 shape).
    const bool compact = nr != n || n != nb;
    const u64 *pb = bases2_xy;
    u64 *cb = nullptr;
    if (compact) {
      cb = new u64[(size_t)2 * nr * 8];
      pb = cb;
    }
    int32_t *sd = new int32_t[(size_t)2 * nr * nwin];
    for (long k = 0; k < nr; ++k) {
      long i = rest[k];
      if (compact) {
        memcpy(cb + 8 * k, bases2_xy + 8 * i, 64);
        memcpy(cb + 8 * (nr + k), bases2_xy + 8 * (nb + i), 64);
      }
      u64 k1[4], k2[4];
      int neg1, neg2;
      glv_split(scalars + 4 * i, glv_consts, k1, &neg1, k2, &neg2);
      int32_t *d1 = sd + (size_t)k * nwin;
      int32_t *d2 = sd + (size_t)(nr + k) * nwin;
      signed_digits(k1, c, nwin, d1);
      signed_digits(k2, c, nwin, d2);
      // a negative half-scalar negates every digit (the fill then adds
      // (x, p - y) — sign handling identical to any negative digit)
      if (neg1)
        for (int w = 0; w < nwin; ++w) d1[w] = -d1[w];
      if (neg2)
        for (int w = 0; w < nwin; ++w) d2[w] = -d2[w];
    }
    g1_pippenger_core(pb, sd, 2 * nr, c, nwin, n_threads, &acc, glv_bits);
    delete[] sd;
    delete[] cb;
  }
  g1_add_jac(acc, ones_acc);
  g1_jac_out(acc, out_xy);
  stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
}

// Multi-column variable-base Pippenger over G1: one fixed base array,
// S scalar columns, S results (see the multi-column block above the
// single-column drivers).  scalars: S consecutive column blocks of
// n x 4 u64 STANDARD form (column s at scalars + s*n*4); out_xy: S x 8
// u64 affine STANDARD-form rows, (0,0) = infinity.
void g1_msm_pippenger_multi(const u64 *bases_xy, const u64 *scalars, long n,
                            int S, int c, int n_threads, u64 *out_xy) {
  if (S <= 0) return;
  long long t0 = prof_now_ns();
  InflightStat _ifs(ST_MSM_INFLIGHT);
  stat_add(ST_MSM_MULTI_CALLS, 1);
  stat_add(ST_MSM_MULTI_COLS, S);
  stat_set(ST_MSM_MULTI_COLS_LAST, S);
  stat_add(ST_MSM_G1_CALLS, 1);  // family counter, like the GLV multi's
  stat_add(ST_MSM_POINTS, (long long)n * S);
  stat_set(ST_MSM_WINDOW_LAST, c);
  if (batch_affine_enabled()) stat_add(ST_MSM_BATCH_AFFINE_CALLS, 1);

  std::vector<std::vector<long>> rest((size_t)S), ones((size_t)S);
  std::vector<std::vector<unsigned char>> ones_neg((size_t)S);
  std::vector<G1Jac> ones_acc((size_t)S);
  // union of the columns' Pippenger index sets: ONE compacted base
  // array serves every column (a column that stripped a point keeps
  // all-zero digits at its row — the fill skips them)
  std::vector<long> remap((size_t)n, -1);
  for (int s = 0; s < S; ++s) {
    classify_scalars(scalars + (size_t)4 * n * s, n, rest[s], ones[s], ones_neg[s]);
    for (long i : rest[s]) remap[i] = 0;
  }
  std::vector<long> idx;
  for (long i = 0; i < n; ++i)
    if (remap[i] == 0) {
      remap[i] = (long)idx.size();
      idx.push_back(i);
    }
  long nr = (long)idx.size();

  const u64 *pb = bases_xy;
  u64 *cb = nullptr;
  if (nr > 0 && nr != n) {
    cb = new u64[(size_t)nr * 8];
    for (long k = 0; k < nr; ++k) memcpy(cb + 8 * k, bases_xy + 8 * idx[k], 64);
    pb = cb;
  }
  int nwin = (254 + c - 1) / c;
  while ((long)nwin * c < 255) ++nwin;
  int32_t *sd = nr > 0 ? new int32_t[(size_t)S * nr * nwin]() : nullptr;
  // per-column prep: the +-1 tree sum and digit recode are column-local
  // and independent -> pool-parallel across columns
  run_indexed_jobs(S, n_threads, [&](long s) {
    long long p0 = prof_now_ns();
    g1_ones_tree_sum(bases_xy, ones[s], ones_neg[s], &ones_acc[s]);
    const u64 *col = scalars + (size_t)4 * n * s;
    int32_t *sdc = sd ? sd + (size_t)s * nr * nwin : nullptr;
    for (long i : rest[s])
      signed_digits(col + 4 * i, c, nwin, sdc + (size_t)remap[i] * nwin);
    stat_add(ST_MSM_MULTI_PREP_NS, prof_now_ns() - p0);
  });

  std::vector<G1Jac> accs((size_t)S);
  memset(accs.data(), 0, (size_t)S * sizeof(G1Jac));
  if (nr > 0) {
    std::vector<const int32_t *> sds((size_t)S);
    for (int s = 0; s < S; ++s) sds[s] = sd + (size_t)s * nr * nwin;
    g1_pippenger_core_multi(pb, sds.data(), S, nr, c, nwin, n_threads, accs.data());
  }
  for (int s = 0; s < S; ++s) {
    g1_add_jac(accs[s], ones_acc[s]);
    g1_jac_out(accs[s], out_xy + 8 * s);
  }
  delete[] sd;
  delete[] cb;
  stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
}

// GLV multi-column driver: the S-column mirror of
// g1_msm_pippenger_glv_mt over the cached doubled base set
// [P.., phi(P)..] (phi half at offset nb).  Each column's rest scalars
// split per glv_split into rows k (k1 half) and nr+k (k2 half) of its
// digit array; the shared core then sweeps the 2*nr-point compacted
// base array ONCE for all S columns.
void g1_msm_pippenger_glv_multi(const u64 *bases2_xy, const u64 *scalars,
                                long n, long nb, int S, int c, int n_threads,
                                const u64 *glv_consts, int glv_bits,
                                u64 *out_xy) {
  if (S <= 0) return;
  long long t0 = prof_now_ns();
  InflightStat _ifs(ST_MSM_INFLIGHT);
  stat_add(ST_MSM_MULTI_CALLS, 1);
  stat_add(ST_MSM_MULTI_COLS, S);
  stat_set(ST_MSM_MULTI_COLS_LAST, S);
  stat_add(ST_MSM_GLV_CALLS, 1);
  stat_add(ST_MSM_POINTS, (long long)n * S);
  stat_set(ST_MSM_WINDOW_LAST, c);
  if (batch_affine_enabled()) stat_add(ST_MSM_BATCH_AFFINE_CALLS, 1);

  std::vector<std::vector<long>> rest((size_t)S), ones((size_t)S);
  std::vector<std::vector<unsigned char>> ones_neg((size_t)S);
  std::vector<G1Jac> ones_acc((size_t)S);
  std::vector<long> remap((size_t)n, -1);
  for (int s = 0; s < S; ++s) {
    classify_scalars(scalars + (size_t)4 * n * s, n, rest[s], ones[s], ones_neg[s]);
    for (long i : rest[s]) remap[i] = 0;
  }
  std::vector<long> idx;
  for (long i = 0; i < n; ++i)
    if (remap[i] == 0) {
      remap[i] = (long)idx.size();
      idx.push_back(i);
    }
  long nr = (long)idx.size();

  int nwin = (glv_bits + c - 1) / c;
  while ((long)nwin * c < glv_bits + 1) ++nwin;  // top-window carry absorb
  // Compact only when needed (the single-column driver's rule): with
  // nothing stripped and n == nb the cached doubled array already has
  // the [P.., phi(P)..] layout the core wants.
  const bool compact = nr != n || n != nb;
  const u64 *pb = bases2_xy;
  u64 *cb = nullptr;
  if (nr > 0 && compact) {
    cb = new u64[(size_t)2 * nr * 8];
    for (long k = 0; k < nr; ++k) {
      memcpy(cb + 8 * k, bases2_xy + 8 * idx[k], 64);
      memcpy(cb + 8 * (nr + k), bases2_xy + 8 * (nb + idx[k]), 64);
    }
    pb = cb;
  }
  int32_t *sd = nr > 0 ? new int32_t[(size_t)S * 2 * nr * nwin]() : nullptr;
  run_indexed_jobs(S, n_threads, [&](long s) {
    long long p0 = prof_now_ns();
    g1_ones_tree_sum(bases2_xy, ones[s], ones_neg[s], &ones_acc[s]);  // +-1: plain P_i half
    const u64 *col = scalars + (size_t)4 * n * s;
    int32_t *sdc = sd ? sd + (size_t)s * 2 * nr * nwin : nullptr;
    for (long i : rest[s]) {
      long k = remap[i];
      u64 k1[4], k2[4];
      int neg1, neg2;
      glv_split(col + 4 * i, glv_consts, k1, &neg1, k2, &neg2);
      int32_t *d1 = sdc + (size_t)k * nwin;
      int32_t *d2 = sdc + (size_t)(nr + k) * nwin;
      signed_digits(k1, c, nwin, d1);
      signed_digits(k2, c, nwin, d2);
      if (neg1)
        for (int w = 0; w < nwin; ++w) d1[w] = -d1[w];
      if (neg2)
        for (int w = 0; w < nwin; ++w) d2[w] = -d2[w];
    }
    stat_add(ST_MSM_MULTI_PREP_NS, prof_now_ns() - p0);
  });

  std::vector<G1Jac> accs((size_t)S);
  memset(accs.data(), 0, (size_t)S * sizeof(G1Jac));
  if (nr > 0) {
    std::vector<const int32_t *> sds((size_t)S);
    for (int s = 0; s < S; ++s) sds[s] = sd + (size_t)s * 2 * nr * nwin;
    g1_pippenger_core_multi(pb, sds.data(), S, 2 * nr, c, nwin, n_threads,
                            accs.data(), glv_bits);
  }
  for (int s = 0; s < S; ++s) {
    g1_add_jac(accs[s], ones_acc[s]);
    g1_jac_out(accs[s], out_xy + 8 * s);
  }
  delete[] sd;
  delete[] cb;
  stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
}

// ===================================================================
// Fixed-base precomputed-window MSM.  The proving key's G1 base arrays
// are immutable for the life of a service, yet every prove re-ran the
// GLV split, the mont256 -> mont260 conversion, and a full bucket fill
// over them.  This tier trades that per-prove work for offline tables:
//
//   table level j holds  L_j[i] = 2^(j*q*c) * P_i   (affine Montgomery),
//
// built ONCE per (key, c, q, levels) by g1_precomp_build and persisted
// by the Python side.  A 254-bit scalar recoded into W signed base-2^c
// digits (W = ceil over 255 bits) then satisfies
//
//   k*P = sum_w d_w * 2^(w*c) * P
//       = sum_{r<q} 2^(r*c) * sum_j d_{j*q+r} * L_j[P]
//
// — i.e. the whole MSM is EXACTLY a plain Pippenger run over the
// "virtual" base array of levels*n table rows with only q windows
// (virtual point j*n+i carries digit d_{j*q+r} in virtual window r).
// g1_pippenger_core runs UNCHANGED on that framing: the batch-affine
// chunk pipeline, the IFMA 52-limb tier (fed the PERSISTENT converted
// table via b52_ext — no per-MSM conversion), the vector suffix, the
// bail path and the Horner fold (c doublings between the q virtual
// windows) all apply as-is.  What the hot loop no longer contains: the
// GLV split (wide windows beat halved scalars once the doubling chain
// is free), the base conversion, and (W - q) of the W per-window
// suffix reductions.  q is the depth knob's dual: levels = ceil(W/q)
// table copies cost levels*n*64 B (plus 80 B/row for the 52-limb form)
// and buy a q-window hot loop; q >= n_threads keeps the window-level
// parallel axis as wide as the pool.

// Windows needed by the fixed tier at width c: ceil(254/c) bumped until
// W*c >= 255 so the signed top-window carry is absorbed — the same rule
// the variable-base drivers apply inline.
static int fixed_nwin(int c) {
  int W = (254 + c - 1) / c;
  while ((long)W * c < 255) ++W;
  return W;
}

// Jacobian -> affine MONTGOMERY normalization with one shared field
// inversion per call (the Montgomery trick): the table-build tail.
// Z = 0 rows write the (0,0) infinity hole.
static void g1_jac_normalize_mont_batch(const G1Jac *in, long n, u64 *out_xy) {
  u64 (*pref)[4] = new u64[n][4];
  u64 run[4];
  memcpy(run, ONE_MONT, 32);
  for (long i = 0; i < n; ++i) {
    memcpy(pref[i], run, 32);
    if (!is_zero4(in[i].Z)) mont_mul(run, run, in[i].Z);
  }
  u64 inv[4];
  mont_inv(inv, run);
  for (long i = n - 1; i >= 0; --i) {
    u64 *o = out_xy + 8 * i;
    if (is_zero4(in[i].Z)) {
      memset(o, 0, 64);
      continue;
    }
    u64 zi[4], zi2[4], zi3[4];
    mont_mul(zi, inv, pref[i]);       // 1/Z_i
    mont_mul(inv, inv, in[i].Z);      // strip Z_i from the running inverse
    mont_sqr(zi2, zi);
    mont_mul(zi3, zi2, zi);
    mont_mul(o, in[i].X, zi2);
    mont_mul(o + 4, in[i].Y, zi3);
  }
  delete[] pref;
}

// Build the level tables: out_xy holds levels consecutive (n x 8 u64)
// affine-Montgomery blocks, level 0 a verbatim copy of bases_xy.  Each
// level is the previous one doubled q*c times — a Jacobian chain per
// point with ONE batched inversion per (level, point-chunk), so the
// per-point cost is ~q*c Jacobian doublings.  Pool-parallel over point
// chunks; (0,0) infinity holes propagate as holes through every level.
void g1_precomp_build(const u64 *bases_xy, long n, int c, int q, int levels,
                      int n_threads, u64 *out_xy) {
  long long t0 = prof_now_ns();
  memcpy(out_xy, bases_xy, (size_t)n * 64);
  if (levels > 1 && n > 0) {
    const int shift = q * c;
    const long CH = 2048;
    const long njobs = (n + CH - 1) / CH;
    run_indexed_jobs(njobs, n_threads, [&](long jb) {
      long lo = jb * CH;
      long hi = lo + CH < n ? lo + CH : n;
      long cnt = hi - lo;
      G1Jac *acc = new G1Jac[cnt];
      for (long k = 0; k < cnt; ++k) {
        const u64 *b = bases_xy + 8 * (lo + k);
        if (is_zero4(b) && is_zero4(b + 4)) {
          memset(&acc[k], 0, sizeof(G1Jac));
        } else {
          memcpy(acc[k].X, b, 32);
          memcpy(acc[k].Y, b + 4, 32);
          memcpy(acc[k].Z, ONE_MONT, 32);
        }
      }
      for (int lv = 1; lv < levels; ++lv) {
        for (long k = 0; k < cnt; ++k)
          for (int b = 0; b < shift; ++b) jac_double(acc[k], acc[k]);
        g1_jac_normalize_mont_batch(acc, cnt,
                                    out_xy + ((size_t)lv * n + lo) * 8);
      }
      delete[] acc;
    });
  }
  stat_add(ST_PRECOMP_BUILD_NS, prof_now_ns() - t0);
  stat_add(ST_PRECOMP_TABLE_BYTES, (long long)levels * n * 64);
}

// Convert a built table to the persistent 52-limb form the IFMA fill
// consumes (n_total rows of 10 u64 = one Aff52 each).  Returns 0 on a
// non-IFMA build/host — the caller then passes NULL to the fixed
// drivers and the scalar tier converts nothing (it reads mont256).
int g1_precomp_to52(const u64 *table_xy, long n_total, u64 *out52) {
#if ZKP2P_HAVE_IFMA
  if (ifma_enabled()) {
    g1_bases_to_52(table_xy, n_total, (Aff52 *)out52);
    return 1;
  }
#endif
  (void)table_xy;
  (void)n_total;
  (void)out52;
  return 0;
}

// Scatter one scalar's W-digit recoding into the virtual digit matrix:
// window w = j*q + r lands at virtual point j*n + i, virtual window r.
static inline void fixed_scatter_digits(const int32_t *dg, int W, int q,
                                        long n, long i, int32_t *sd) {
  for (int w = 0; w < W; ++w) {
    long v = (long)(w / q) * n + i;
    sd[(size_t)v * q + (w % q)] = dg[w];
  }
}

// Fixed-base precomputed-table Pippenger driver.  table_xy: the
// g1_precomp_build output (levels x n x 8 u64 affine Montgomery);
// table52: its g1_precomp_to52 form or NULL; scalars: nsc (<= n) rows
// of 4 u64 STANDARD form; out_xy: 8 u64 affine STANDARD form.  The
// result is the exact group element of the variable-base drivers for
// the same (bases, scalars) — canonicalization makes it byte-identical,
// so g1_msm_pippenger_mt stays the parity oracle.
void g1_msm_pippenger_fixed(const u64 *table_xy, const u64 *table52,
                            const u64 *scalars, long nsc, long n, int levels,
                            int c, int q, int n_threads, u64 *out_xy) {
  long long t0 = prof_now_ns();
  InflightStat _ifs(ST_MSM_INFLIGHT);
  stat_add(ST_MSM_FIXED_CALLS, 1);
  stat_add(ST_MSM_G1_CALLS, 1);
  stat_add(ST_MSM_POINTS, nsc);
  stat_set(ST_MSM_WINDOW_LAST, c);
  if (batch_affine_enabled()) stat_add(ST_MSM_BATCH_AFFINE_CALLS, 1);
  const int W = fixed_nwin(c);
  if (c < 4 || W > 64) abort();       // recode buffer bound (c >= 4 always)
  if ((long)levels * q < W) abort();  // table cannot cover the digit span
  std::vector<long> rest, ones;
  std::vector<unsigned char> ones_neg;
  classify_scalars(scalars, nsc, rest, ones, ones_neg);
  G1Jac ones_acc;
  g1_ones_tree_sum(table_xy, ones, ones_neg, &ones_acc);  // +-1: level 0
  G1Jac acc;
  memset(&acc, 0, sizeof(acc));
  long nr = (long)rest.size();
  if (nr > 0) {
    const long nv = (long)levels * n;
    // zero-initialized: non-rest virtual rows keep all-zero digits and
    // the fill skips them — the table is NEVER compacted or copied
    int32_t *sd = new int32_t[(size_t)nv * q]();
    long long p0 = prof_now_ns();
    const long CH = 8192;
    run_indexed_jobs((nr + CH - 1) / CH, n_threads, [&](long jb) {
      int32_t dg[64];  // W <= ceil(255/4) < 64 for every c >= 4
      long hi = (jb + 1) * CH < nr ? (jb + 1) * CH : nr;
      for (long k = jb * CH; k < hi; ++k) {
        long i = rest[k];
        signed_digits(scalars + 4 * i, c, W, dg);
        fixed_scatter_digits(dg, W, q, n, i, sd);
      }
    });
    stat_add(ST_MSM_FIXED_PREP_NS, prof_now_ns() - p0);
    // total_bits = q*c: every virtual window carries full c-bit digits
    // (middle real windows land in every lane), so no top-window
    // narrowing applies inside the core.
    g1_pippenger_core(table_xy, sd, nv, c, q, n_threads, &acc, q * c,
                      table52);
    delete[] sd;
  }
  g1_add_jac(acc, ones_acc);
  g1_jac_out(acc, out_xy);
  stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
}

// Multi-column fixed-base driver: S scalar columns over ONE table —
// the batch path's gather/add mirror of g1_msm_pippenger_multi.
// scalars: S consecutive column blocks of nsc x 4 u64 STANDARD form;
// out_xy: S x 8 u64 affine STANDARD-form rows.  Column outputs equal S
// sequential g1_msm_pippenger_fixed calls byte-for-byte.
void g1_msm_pippenger_fixed_multi(const u64 *table_xy, const u64 *table52,
                                  const u64 *scalars, long nsc, long n, int S,
                                  int levels, int c, int q, int n_threads,
                                  u64 *out_xy) {
  if (S <= 0) return;
  long long t0 = prof_now_ns();
  InflightStat _ifs(ST_MSM_INFLIGHT);
  stat_add(ST_MSM_FIXED_CALLS, 1);
  stat_add(ST_MSM_MULTI_CALLS, 1);
  stat_add(ST_MSM_MULTI_COLS, S);
  stat_set(ST_MSM_MULTI_COLS_LAST, S);
  stat_add(ST_MSM_G1_CALLS, 1);
  stat_add(ST_MSM_POINTS, (long long)nsc * S);
  stat_set(ST_MSM_WINDOW_LAST, c);
  if (batch_affine_enabled()) stat_add(ST_MSM_BATCH_AFFINE_CALLS, 1);
  const int W = fixed_nwin(c);
  if (c < 4 || W > 64) abort();
  if ((long)levels * q < W) abort();
  const long nv = (long)levels * n;
  std::vector<G1Jac> ones_acc((size_t)S);
  int32_t *sd = new int32_t[(size_t)S * nv * q]();
  // per-column prep (classify, +-1 tree sum, digit scatter) is
  // column-local -> pool-parallel across columns, like the multi driver
  run_indexed_jobs(S, n_threads, [&](long s) {
    long long p0 = prof_now_ns();
    const u64 *col = scalars + (size_t)4 * nsc * s;
    std::vector<long> rest, ones;
    std::vector<unsigned char> ones_neg;
    classify_scalars(col, nsc, rest, ones, ones_neg);
    g1_ones_tree_sum(table_xy, ones, ones_neg, &ones_acc[s]);
    int32_t dg[64];
    int32_t *sdc = sd + (size_t)s * nv * q;
    for (long i : rest) {
      signed_digits(col + 4 * i, c, W, dg);
      fixed_scatter_digits(dg, W, q, n, i, sdc);
    }
    stat_add(ST_MSM_FIXED_PREP_NS, prof_now_ns() - p0);
  });
  std::vector<G1Jac> accs((size_t)S);
  memset(accs.data(), 0, (size_t)S * sizeof(G1Jac));
  std::vector<const int32_t *> sds((size_t)S);
  for (int s = 0; s < S; ++s) sds[s] = sd + (size_t)s * nv * q;
  g1_pippenger_core_multi(table_xy, sds.data(), S, nv, c, q, n_threads,
                          accs.data(), q * c, table52);
  for (int s = 0; s < S; ++s) {
    g1_add_jac(accs[s], ones_acc[s]);
    g1_jac_out(accs[s], out_xy + 8 * s);
  }
  delete[] sd;
  stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
}

// Scale n affine STANDARD-form G1 points by ONE shared standard-form Fr
// scalar: out[i] = k * P[i].  The phase-2 ceremony hot loop (every
// contribution rescales the whole C and H query by 1/delta') — NAF of
// the shared scalar computed once, Jacobian double-add per point, one
// batched inversion for the final affine normalization.  (0,0) holes
// pass through.
void g1_scale_batch(const u64 *bases_xy, long n, const u64 *scalar, u64 *out_xy) {
  // width-2 NAF (digits -1/0/1), LSB first
  int naf[260];
  int nbits = 0;
  {
    u64 s[5] = {scalar[0], scalar[1], scalar[2], scalar[3], 0};
    auto is_zero = [&]() {
      for (int i = 0; i < 5; ++i)
        if (s[i]) return false;
      return true;
    };
    auto shr1 = [&]() {
      for (int i = 0; i < 4; ++i) s[i] = (s[i] >> 1) | (s[i + 1] << 63);
      s[4] >>= 1;
    };
    while (!is_zero() && nbits < 260) {
      if (s[0] & 1) {
        if ((s[0] & 3) == 3) {
          naf[nbits] = -1;  // d = -1, s += 1
          u128 c = 1;
          for (int i = 0; i < 5 && c; ++i) {
            u128 t = (u128)s[i] + c;
            s[i] = (u64)t;
            c = t >> 64;
          }
        } else {
          naf[nbits] = 1;  // d = 1, s -= 1
          s[0] -= 1;
        }
      } else {
        naf[nbits] = 0;
      }
      shr1();
      ++nbits;
    }
  }
  G1Jac *accs = new G1Jac[n > 0 ? n : 1];
  for (long i = 0; i < n; ++i) {
    const u64 *bx = bases_xy + 8 * i;
    const u64 *by = bx + 4;
    if (is_zero4(bx) && is_zero4(by)) {
      memset(&accs[i], 0, sizeof(G1Jac));
      continue;
    }
    u64 mx[4], my[4], nmy[4];
    mont_mul(mx, bx, R2P);
    mont_mul(my, by, R2P);
    sub_nored(nmy, P, my);
    G1Jac acc;
    memset(&acc, 0, sizeof(acc));
    for (int b = nbits - 1; b >= 0; --b) {
      jac_double(acc, acc);
      if (naf[b] == 1) {
        jac_add_mixed(acc, acc, mx, my);
      } else if (naf[b] == -1) {
        jac_add_mixed(acc, acc, mx, nmy);
      }
    }
    accs[i] = acc;
  }
  // batched affine normalization: one inversion for all nonzero Zs
  u64 *pref = new u64[(size_t)(n > 0 ? n : 1) * 4];
  u64 run[4];
  memcpy(run, ONE_MONT, 32);
  for (long i = 0; i < n; ++i) {
    memcpy(pref + 4 * i, run, 32);
    if (!is_zero4(accs[i].Z)) mont_mul(run, run, accs[i].Z);
  }
  u64 inv_all[4];
  mont_inv(inv_all, run);
  for (long i = n - 1; i >= 0; --i) {
    u64 *o = out_xy + 8 * i;
    if (is_zero4(accs[i].Z)) {
      memset(o, 0, 64);
      continue;
    }
    u64 zi[4], zi2[4], zi3[4], mx[4], my[4];
    mont_mul(zi, inv_all, pref + 4 * i);
    mont_mul(inv_all, inv_all, accs[i].Z);
    mont_sqr(zi2, zi);
    mont_mul(zi3, zi2, zi);
    mont_mul(mx, accs[i].X, zi2);
    mont_mul(my, accs[i].Y, zi3);
    fp_from_mont(mx, o, 1);
    fp_from_mont(my, o + 4, 1);
  }
  delete[] pref;
  delete[] accs;
}

// Variable-base Pippenger MSM over G2.  bases: n x 16 u64 affine
// Montgomery (x.c0, x.c1, y.c0, y.c1; all-zero = infinity); scalars
// standard form; out: 16 u64 affine STANDARD form, all-zero = infinity.
void g2_msm_pippenger_mt(const u64 *bases, const u64 *scalars, long n,
                         int c, int n_threads, u64 *out) {
  long long t0 = prof_now_ns();
  InflightStat _ifs(ST_MSM_INFLIGHT);
  stat_add(ST_MSM_G2_CALLS, 1);
  stat_add(ST_MSM_POINTS, n);
  stat_set(ST_MSM_WINDOW_LAST, c);
  if (batch_affine_enabled()) stat_add(ST_MSM_BATCH_AFFINE_CALLS, 1);
  // scalar classification, as the G1 driver: 0 skipped, +-1 through the
  // vectorized Fq2 tree sum, the rest through Pippenger
  std::vector<long> rest, ones;
  std::vector<unsigned char> ones_neg;
  classify_scalars(scalars, n, rest, ones, ones_neg);
  G2Jac ones_acc;
  memset(&ones_acc, 0, sizeof(ones_acc));
#if ZKP2P_HAVE_IFMA
  if (!ones.empty()) {
    long no = (long)ones.size();
    u64 (*xs)[8] = new u64[no][8];
    u64 (*ys)[8] = new u64[no][8];
    for (long k = 0; k < no; ++k) {
      const u64 *b = bases + 16 * ones[k];
      memcpy(xs[k], b, 64);
      if (ones_neg[k]) {
        u64 t[4];
        neg_y(t, b + 8);
        memcpy(ys[k], t, 32);
        neg_y(t, b + 12);
        memcpy(ys[k] + 4, t, 32);
      } else {
        memcpy(ys[k], b + 8, 64);
      }
      if (is_zero4(b) && is_zero4(b + 4) && is_zero4(b + 8) && is_zero4(b + 12))
        memset(ys[k], 0, 64);  // keep holes fully zero
    }
    g2_tree_sum(xs, ys, no, &ones_acc);
    delete[] xs;
    delete[] ys;
    ones.clear();
  }
#endif
  // non-IFMA COMPILE only: the tree path does not exist, so ones ride
  // Pippenger as before.  (On an IFMA build with the feature disabled
  // at runtime, g2_tree_sum above already handled them via its serial
  // g2_add_mixed fallback and cleared the list — this loop is a no-op.)
  for (long i : ones) rest.push_back(i);
  if (!ones.empty()) std::sort(rest.begin(), rest.end());

  G2Jac acc;
  memset(&acc, 0, sizeof(acc));
  long nr = (long)rest.size();
  if (nr > 0) {
    const u64 *pb = bases;
    const u64 *ps = scalars;
    u64 *cb = nullptr, *csc = nullptr;
    if (nr != n) {
      cb = new u64[(size_t)nr * 16];
      csc = new u64[(size_t)nr * 4];
      for (long k = 0; k < nr; ++k) {
        memcpy(cb + 16 * k, bases + 16 * rest[k], 128);
        memcpy(csc + 4 * k, scalars + 4 * rest[k], 32);
      }
      pb = cb;
      ps = csc;
    }
    int nwin = (254 + c - 1) / c;
    while ((long)nwin * c < 255) ++nwin;
    int32_t *sd = new int32_t[(size_t)nr * nwin];
    for (long i = 0; i < nr; ++i) signed_digits(ps + 4 * i, c, nwin, sd + (size_t)i * nwin);
    G2Jac *wins = new G2Jac[nwin];
    run_window_sums(nwin, n_threads, wins, [&](int wi, G2Jac *o) {
      g2_window_sum(pb, sd, nr, c, nwin, wi, o);
    });
    delete[] sd;
    for (int wi = nwin - 1; wi >= 0; --wi) {
      if (wi != nwin - 1)
        for (int k = 0; k < c; ++k) {
          G2Jac d2;
          g2_double(d2, acc);
          acc = d2;
        }
      g2_add(acc, wins[wi]);
    }
    delete[] wins;
    delete[] cb;
    delete[] csc;
  }
  g2_add(acc, ones_acc);
  if (fp2_is_zero(acc.Z)) {
    memset(out, 0, 128);
    stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
    return;
  }
  Fp2 zi, zi2, zi3, mx, my;
  fp2_inv(zi, acc.Z);
  fp2_sqr(zi2, zi);
  fp2_mul(zi3, zi2, zi);
  fp2_mul(mx, acc.X, zi2);
  fp2_mul(my, acc.Y, zi3);
  fp_from_mont(mx.c0, out, 1);
  fp_from_mont(mx.c1, out + 4, 1);
  fp_from_mont(my.c0, out + 8, 1);
  fp_from_mont(my.c1, out + 12, 1);
  stat_add(ST_MSM_WALL_NS, prof_now_ns() - t0);
}

void g2_msm_pippenger(const u64 *bases, const u64 *scalars, long n,
                      int c, u64 *out) {
  g2_msm_pippenger_mt(bases, scalars, n, c, 1, out);
}

}  // extern "C"

// ===================================================================
// Fq6 / Fq12 and the optimal ate pairing: the Groth16 verification
// equation, whole.  The proving service verifies one sample proof a
// batch on the proving thread with the device empty; the Python pairing
// (pairing/pairing.py over field/tower.py) is ~0.47 s of big-int tower
// arithmetic there, this is a few milliseconds and the ctypes call
// releases the interpreter.  The Python function stays the oracle
// (snark/native_verify.py): every `0` from here is re-decided by it.
//
// Same tower as field/tower.py:  Fq6 = Fq2[v]/(v^3 - xi), xi = 9 + u;
// Fq12 = Fq6[w]/(w^2 - v).  Same Miller loop as pairing/pairing.py —
// affine points, lines through the untwisted psi(x, y) = (x w^2, y w^3)
// — so f is the SAME element of Fq12 before the final exponentiation,
// not one that differs by a subfield factor.  Written for obviousness,
// not speed: generic Fq12 products for the sparse lines, a plain
// square-and-multiply for the hard part of the exponent.
// ===================================================================

static inline void fp_neg(u64 out[4], const u64 a[4]) { sub_mod(out, ZERO, a); }
static inline void fp2_neg(Fp2 &r, const Fp2 &a) {
  fp_neg(r.c0, a.c0);
  fp_neg(r.c1, a.c1);
}
static inline void fp2_conj(Fp2 &r, const Fp2 &a) {
  memcpy(r.c0, a.c0, 32);
  fp_neg(r.c1, a.c1);
}
static inline void fp2_scale(Fp2 &r, const Fp2 &a, const u64 s[4]) {
  mont_mul(r.c0, a.c0, s);
  mont_mul(r.c1, a.c1, s);
}
static inline bool fp2_eq(const Fp2 &a, const Fp2 &b) {
  return !memcmp(a.c0, b.c0, 32) && !memcmp(a.c1, b.c1, 32);
}
// a * xi, xi = 9 + u:  (9 a0 - a1) + (a0 + 9 a1) u
static void fp2_mul_xi(Fp2 &r, const Fp2 &a) {
  Fp2 t;
  fp2_add(t, a, a);
  fp2_add(t, t, t);
  fp2_add(t, t, t);
  fp2_add(t, t, a);  // 9 a
  Fp2 out;
  sub_mod(out.c0, t.c0, a.c1);
  add_mod(out.c1, t.c1, a.c0);
  r = out;
}

struct Fp6 {
  Fp2 c0, c1, c2;
};
struct Fp12 {
  Fp6 c0, c1;
};

static inline void fp6_add(Fp6 &r, const Fp6 &a, const Fp6 &b) {
  fp2_add(r.c0, a.c0, b.c0);
  fp2_add(r.c1, a.c1, b.c1);
  fp2_add(r.c2, a.c2, b.c2);
}
static inline void fp6_sub(Fp6 &r, const Fp6 &a, const Fp6 &b) {
  fp2_sub(r.c0, a.c0, b.c0);
  fp2_sub(r.c1, a.c1, b.c1);
  fp2_sub(r.c2, a.c2, b.c2);
}
static inline void fp6_neg(Fp6 &r, const Fp6 &a) {
  fp2_neg(r.c0, a.c0);
  fp2_neg(r.c1, a.c1);
  fp2_neg(r.c2, a.c2);
}
// (c0, c1, c2) * v = (xi c2, c0, c1)
static inline void fp6_mul_v(Fp6 &r, const Fp6 &a) {
  Fp2 t;
  fp2_mul_xi(t, a.c2);
  r.c2 = a.c1;
  r.c1 = a.c0;
  r.c0 = t;
}
static void fp6_mul(Fp6 &r, const Fp6 &a, const Fp6 &b) {
  Fp2 t0, t1, t2, s, u, c0, c1, c2;
  fp2_mul(t0, a.c0, b.c0);
  fp2_mul(t1, a.c1, b.c1);
  fp2_mul(t2, a.c2, b.c2);
  fp2_add(s, a.c1, a.c2);
  fp2_add(u, b.c1, b.c2);
  fp2_mul(c0, s, u);
  fp2_sub(c0, c0, t1);
  fp2_sub(c0, c0, t2);
  fp2_mul_xi(c0, c0);
  fp2_add(c0, c0, t0);
  fp2_add(s, a.c0, a.c1);
  fp2_add(u, b.c0, b.c1);
  fp2_mul(c1, s, u);
  fp2_sub(c1, c1, t0);
  fp2_sub(c1, c1, t1);
  fp2_mul_xi(s, t2);
  fp2_add(c1, c1, s);
  fp2_add(s, a.c0, a.c2);
  fp2_add(u, b.c0, b.c2);
  fp2_mul(c2, s, u);
  fp2_sub(c2, c2, t0);
  fp2_sub(c2, c2, t2);
  fp2_add(c2, c2, t1);
  r.c0 = c0;
  r.c1 = c1;
  r.c2 = c2;
}
static void fp6_inv(Fp6 &r, const Fp6 &a) {
  Fp2 t0, t1, t2, s, d;
  fp2_sqr(t0, a.c0);
  fp2_mul(s, a.c1, a.c2);
  fp2_mul_xi(s, s);
  fp2_sub(t0, t0, s);
  fp2_sqr(t1, a.c2);
  fp2_mul_xi(t1, t1);
  fp2_mul(s, a.c0, a.c1);
  fp2_sub(t1, t1, s);
  fp2_sqr(t2, a.c1);
  fp2_mul(s, a.c0, a.c2);
  fp2_sub(t2, t2, s);
  fp2_mul(d, a.c0, t0);
  fp2_mul(s, a.c2, t1);
  fp2_mul_xi(s, s);
  fp2_add(d, d, s);
  fp2_mul(s, a.c1, t2);
  fp2_mul_xi(s, s);
  fp2_add(d, d, s);
  fp2_inv(d, d);
  fp2_mul(r.c0, t0, d);
  fp2_mul(r.c1, t1, d);
  fp2_mul(r.c2, t2, d);
}

static void fp12_mul(Fp12 &r, const Fp12 &a, const Fp12 &b) {
  Fp6 t0, t1, s, u, c1;
  fp6_mul(t0, a.c0, b.c0);
  fp6_mul(t1, a.c1, b.c1);
  fp6_add(s, a.c0, a.c1);
  fp6_add(u, b.c0, b.c1);
  fp6_mul(c1, s, u);
  fp6_sub(c1, c1, t0);
  fp6_sub(c1, c1, t1);
  fp6_mul_v(t1, t1);
  fp6_add(r.c0, t0, t1);
  r.c1 = c1;
}
static void fp12_sqr(Fp12 &r, const Fp12 &a) {
  // (a0 + a1 w)^2 = (a0 + a1)(a0 + v a1) - t - v t + 2 t w,  t = a0 a1
  Fp6 t, s, u, vt;
  fp6_mul(t, a.c0, a.c1);
  fp6_add(s, a.c0, a.c1);
  fp6_mul_v(u, a.c1);
  fp6_add(u, u, a.c0);
  fp6_mul(s, s, u);
  fp6_sub(s, s, t);
  fp6_mul_v(vt, t);
  fp6_sub(r.c0, s, vt);
  fp6_add(r.c1, t, t);
}
static void fp12_inv(Fp12 &r, const Fp12 &a) {
  Fp6 d, s;
  fp6_mul(d, a.c0, a.c0);
  fp6_mul(s, a.c1, a.c1);
  fp6_mul_v(s, s);
  fp6_sub(d, d, s);
  fp6_inv(d, d);
  fp6_mul(r.c0, a.c0, d);
  fp6_mul(s, a.c1, d);
  fp6_neg(r.c1, s);
}
static void fp12_one(Fp12 &r) {
  memset(&r, 0, sizeof(r));
  memcpy(r.c0.c0.c0, ONE_MONT, 32);
}
static bool fp12_is_one(const Fp12 &a) {
  Fp12 one;
  fp12_one(one);
  return !memcmp(&a, &one, sizeof(one));  // every limb is canonical (< p)
}

// What the curve fixes, computed (not transcribed) on first use.  An
// element of Fq12 is sum a_i w^i over Fq2 (a_i = c0.c0, c1.c0, c0.c1,
// c1.c1, c0.c2, c1.c2) and w^6 = xi, so x -> x^p sends a_i w^i to
// conj(a_i) g^i w^i with g = xi^((p-1)/6), and x -> x^(p^2) sends it to
// a_i N(g)^i w^i, N(g) = g conj(g) in Fq.
struct PairingConsts {
  u64 b[4];        // 3: the curve is y^2 = x^3 + 3
  Fp2 twist_b;     // 3 / xi: the twist is y^2 = x^3 + 3/xi
  Fp2 frob[6];     // g^i
  u64 frob2[6][4]; // N(g)^i
};
static PairingConsts make_pairing_consts() {
  PairingConsts k;
  Fp2 xi, three;
  memset(&xi, 0, sizeof(xi));
  memcpy(xi.c0, ONE_MONT, 32);
  fp2_mul_xi(xi, xi);
  add_mod(k.b, ONE_MONT, ONE_MONT);
  add_mod(k.b, k.b, ONE_MONT);
  memset(&three, 0, sizeof(three));
  memcpy(three.c0, k.b, 32);
  fp2_inv(k.twist_b, xi);
  fp2_mul(k.twist_b, k.twist_b, three);
  // e = (p - 1) / 6, by long division from the top limb
  u64 e[4];
  u128 rem = 0;
  for (int i = 3; i >= 0; --i) {
    u128 cur = (rem << 64) | (i ? P[i] : P[i] - 1);
    e[i] = (u64)(cur / 6);
    rem = cur % 6;
  }
  Fp2 g;
  memset(&g, 0, sizeof(g));
  memcpy(g.c0, ONE_MONT, 32);
  for (int i = 255; i >= 0; --i) {
    fp2_sqr(g, g);
    if ((e[i / 64] >> (i % 64)) & 1) fp2_mul(g, g, xi);
  }
  memset(&k.frob[0], 0, sizeof(Fp2));
  memcpy(k.frob[0].c0, ONE_MONT, 32);
  for (int i = 1; i < 6; ++i) fp2_mul(k.frob[i], k.frob[i - 1], g);
  for (int i = 0; i < 6; ++i) {
    Fp2 c, n;
    fp2_conj(c, k.frob[i]);
    fp2_mul(n, k.frob[i], c);
    memcpy(k.frob2[i], n.c0, 32);
  }
  return k;
}
static const PairingConsts &pairing_consts() {
  static const PairingConsts k = make_pairing_consts();  // thread-safe: C++11 static
  return k;
}

static void fp12_frobenius2(Fp12 &r, const Fp12 &a) {
  const PairingConsts &k = pairing_consts();
  fp2_scale(r.c0.c0, a.c0.c0, k.frob2[0]);
  fp2_scale(r.c1.c0, a.c1.c0, k.frob2[1]);
  fp2_scale(r.c0.c1, a.c0.c1, k.frob2[2]);
  fp2_scale(r.c1.c1, a.c1.c1, k.frob2[3]);
  fp2_scale(r.c0.c2, a.c0.c2, k.frob2[4]);
  fp2_scale(r.c1.c2, a.c1.c2, k.frob2[5]);
}

// (p^4 - p^2 + 1) / r, 761 bits, little-endian limbs: the hard part of
// the final exponent, as pairing.py's final_exponentiation computes it.
// A wrong limb makes every valid product read "not one": the load-time
// self-check of native/lib.py and tests/test_pairing.py hold it.
static const u64 FINAL_EXP_HARD[12] = {
    0xe81bb482ccdf42b1ULL, 0x5abf5cc4f49c36d4ULL, 0xf1154e7e1da014fdULL,
    0xdcc7b44c87cdbacfULL, 0xaaa441e3954bcf8aULL, 0x6b887d56d5095f23ULL,
    0x79581e16f3fd90c6ULL, 0x3b1b1355d189227dULL, 0x4e529a5861876f6bULL,
    0x6c0eb522d5b12278ULL, 0x331ec15183177fafULL, 0x01baaa710b0759adULL};

// f^((p^12 - 1) / r) == 1
static bool final_exponentiation_is_one(const Fp12 &f) {
  Fp12 f1, f2, t, acc;
  // easy: f^((p^6 - 1)(p^2 + 1)); x -> x^(p^6) negates the w coefficient
  fp12_inv(t, f);
  f1.c0 = f.c0;
  fp6_neg(f1.c1, f.c1);
  fp12_mul(f1, f1, t);
  fp12_frobenius2(f2, f1);
  fp12_mul(f2, f2, f1);
  fp12_one(acc);
  for (int i = 760; i >= 0; --i) {
    fp12_sqr(acc, acc);
    if ((FINAL_EXP_HARD[i / 64] >> (i % 64)) & 1) fp12_mul(acc, acc, f2);
  }
  return fp12_is_one(acc);
}

struct G2Aff {
  Fp2 x, y;  // Montgomery; all-zero = infinity
};
static inline bool g2aff_is_inf(const G2Aff &q) {
  return fp2_is_zero(q.x) && fp2_is_zero(q.y);
}

// Invert d[0..n) in place with one field inversion; false if any is 0.
static bool fp2_batch_inv(Fp2 *d, int n) {
  std::vector<Fp2> pre(n);
  Fp2 acc;
  memset(&acc, 0, sizeof(acc));
  memcpy(acc.c0, ONE_MONT, 32);
  for (int i = 0; i < n; ++i) {
    if (fp2_is_zero(d[i])) return false;
    pre[i] = acc;
    fp2_mul(acc, acc, d[i]);
  }
  fp2_inv(acc, acc);
  for (int i = n - 1; i >= 0; --i) {
    Fp2 inv;
    fp2_mul(inv, acc, pre[i]);
    fp2_mul(acc, acc, d[i]);
    d[i] = inv;
  }
  return true;
}

// One step of every pair's Miller loop: T_i <- T_i + S_i (or 2 T_i where
// S is null), and f <- f * prod_i line_i(P_i).  With lambda the slope in
// Fq2 of the chord (tangent) on the twist, the line through the
// untwisted points evaluated at P = (xP, yP) is
//     yP  -  lambda xP w  +  (lambda xT - yT) w^3
// (pairing.py's `_line`: (py - y1) - lam (px - x1) with x1 = xT w^2,
// y1 = yT w^3 and lam = lambda w).  False on a vertical line or a tangent
// at y = 0: no step of a loop over points of order r meets either, and
// pairing.py has no answer there either.
static bool miller_step(Fp12 &f, std::vector<G2Aff> &T, const G2Aff *S, const G1Aff *P,
                        bool update) {
  int n = (int)T.size();
  std::vector<Fp2> den(n);
  for (int i = 0; i < n; ++i) {
    if (S) {
      fp2_sub(den[i], S[i].x, T[i].x);
    } else {
      fp2_add(den[i], T[i].y, T[i].y);
    }
  }
  if (!fp2_batch_inv(den.data(), n)) return false;
  for (int i = 0; i < n; ++i) {
    Fp2 lam, t;
    if (S) {
      fp2_sub(lam, S[i].y, T[i].y);
    } else {
      fp2_sqr(t, T[i].x);
      fp2_add(lam, t, t);
      fp2_add(lam, lam, t);
    }
    fp2_mul(lam, lam, den[i]);
    Fp12 line;
    memset(&line, 0, sizeof(line));
    memcpy(line.c0.c0.c0, P[i].y, 32);
    fp2_scale(t, lam, P[i].x);
    fp2_neg(line.c1.c0, t);
    fp2_mul(t, lam, T[i].x);
    fp2_sub(line.c1.c1, t, T[i].y);
    fp12_mul(f, f, line);
    if (update) {
      Fp2 x3, y3;
      fp2_sqr(x3, lam);
      fp2_sub(x3, x3, T[i].x);
      fp2_sub(x3, x3, S ? S[i].x : T[i].x);
      fp2_sub(t, T[i].x, x3);
      fp2_mul(y3, lam, t);
      fp2_sub(y3, y3, T[i].y);
      T[i].x = x3;
      T[i].y = y3;
    }
  }
  return true;
}

// prod_i e(P_i, Q_i) == 1 over affine Montgomery points (all-zero =
// infinity: that pair contributes 1, as pairing.py's miller_loop has
// it), one final exponentiation for all.  Points are taken as given: the
// caller has checked what it needs of them.  1 = one, 0 = not one,
// -1 = a degenerate step (see miller_step).
static int pairing_product_is_one(const G1Aff *Ps, const G2Aff *Qs, int n) {
  const PairingConsts &k = pairing_consts();
  std::vector<G1Aff> P;
  std::vector<G2Aff> Q, Q1, Q2;
  for (int i = 0; i < n; ++i) {
    if ((is_zero4(Ps[i].x) && is_zero4(Ps[i].y)) || g2aff_is_inf(Qs[i])) continue;
    P.push_back(Ps[i]);
    Q.push_back(Qs[i]);
    // pi(Q) = (conj(x) g^2, conj(y) g^3);  -pi^2(Q) = (x N(g)^2, -y N(g)^3)
    G2Aff q1, q2;
    fp2_conj(q1.x, Qs[i].x);
    fp2_mul(q1.x, q1.x, k.frob[2]);
    fp2_conj(q1.y, Qs[i].y);
    fp2_mul(q1.y, q1.y, k.frob[3]);
    fp2_scale(q2.x, Qs[i].x, k.frob2[2]);
    fp2_scale(q2.y, Qs[i].y, k.frob2[3]);
    fp2_neg(q2.y, q2.y);
    Q1.push_back(q1);
    Q2.push_back(q2);
  }
  Fp12 f;
  fp12_one(f);
  if (Q.empty()) return 1;
  std::vector<G2Aff> T = Q;
  // 6u + 2 = 29793968203157093288 (65 bits), from the bit under the top
  static const u64 ATE_LOOP_LOW = 0x9d797039be763ba8ULL;
  for (int bit = 63; bit >= 0; --bit) {
    fp12_sqr(f, f);
    if (!miller_step(f, T, nullptr, P.data(), true)) return -1;
    if ((ATE_LOOP_LOW >> bit) & 1) {
      if (!miller_step(f, T, Q.data(), P.data(), true)) return -1;
    }
  }
  if (!miller_step(f, T, Q1.data(), P.data(), true)) return -1;
  if (!miller_step(f, T, Q2.data(), P.data(), false)) return -1;
  return final_exponentiation_is_one(f) ? 1 : 0;
}

// Standard-form limbs -> Montgomery, refusing a coordinate >= p.
static bool fp_load(u64 out[4], const u64 *in) {
  if (geq(in, P)) return false;
  mont_mul(out, in, R2P);
  return true;
}
static bool g1_load(G1Aff &p, const u64 *in) {
  return fp_load(p.x, in) && fp_load(p.y, in + 4);
}
static bool g2_load(G2Aff &q, const u64 *in) {
  return fp_load(q.x.c0, in) && fp_load(q.x.c1, in + 4) && fp_load(q.y.c0, in + 8) &&
         fp_load(q.y.c1, in + 12);
}
static bool g1_on_curve(const G1Aff &p) {
  if (is_zero4(p.x) && is_zero4(p.y)) return true;
  u64 l[4], r[4];
  mont_sqr(l, p.y);
  mont_sqr(r, p.x);
  mont_mul(r, r, p.x);
  add_mod(r, r, pairing_consts().b);
  return !memcmp(l, r, 32);
}
static bool g2_on_twist(const G2Aff &q) {
  if (g2aff_is_inf(q)) return true;
  Fp2 l, r;
  fp2_sqr(l, q.y);
  fp2_sqr(r, q.x);
  fp2_mul(r, r, q.x);
  fp2_add(r, r, pairing_consts().twist_b);
  return fp2_eq(l, r);
}
// k * p over all 256 bits of k (standard-form limbs, not reduced), a plain
// double-and-add: no table, nothing shared
static void g1_mul_plain(G1Jac &out, const G1Aff &p, const u64 k[4]) {
  G1Jac acc, t;
  memset(&acc, 0, sizeof(acc));
  for (int bit = 255; bit >= 0; --bit) {
    jac_double(t, acc);
    acc = t;
    if ((k[bit / 64] >> (bit % 64)) & 1) {
      jac_add_mixed(t, acc, p.x, p.y);
      acc = t;
    }
  }
  out = acc;
}
static void g2_mul_plain(G2Jac &out, const G2Aff &q, const u64 k[4]) {
  G2Jac acc, t;
  memset(&acc, 0, sizeof(acc));
  for (int bit = 255; bit >= 0; --bit) {
    g2_double(t, acc);
    acc = t;
    if ((k[bit / 64] >> (bit % 64)) & 1) {
      g2_add_mixed(t, acc, q.x, q.y);
      acc = t;
    }
  }
  out = acc;
}
// Jacobian -> affine, Montgomery; (0,0) = infinity
static void g1_to_aff(G1Aff &out, const G1Jac &p) {
  memset(&out, 0, sizeof(out));
  if (is_zero4(p.Z)) return;
  u64 zi[4], zi2[4], zi3[4];
  mont_inv(zi, p.Z);
  mont_sqr(zi2, zi);
  mont_mul(zi3, zi2, zi);
  mont_mul(out.x, p.X, zi2);
  mont_mul(out.y, p.Y, zi3);
}
// [r] Q == O: the twist's cofactor is large, so on the twist is not in G2
static bool g2_in_subgroup(const G2Aff &q) {
  G2Jac acc;
  g2_mul_plain(acc, q, R_MOD);
  return fp2_is_zero(acc.Z);
}

extern "C" {

// prod_i e(P_i, Q_i) == 1 (see pairing_product_is_one above) on
// standard-form affine points: g1s n * 8 u64 (x, y), g2s n * 16 u64
// (x.c0, x.c1, y.c0, y.c1), all-zero = infinity.  Nothing is checked of
// the points but that every coordinate is below p.  1 = one, 0 = not
// one, -1 = a coordinate >= p or a degenerate step.
int bn254_pairing_product_is_one(const u64 *g1s, const u64 *g2s, int n) {
  std::vector<G1Aff> Ps(n > 0 ? n : 0);
  std::vector<G2Aff> Qs(n > 0 ? n : 0);
  for (int i = 0; i < n; ++i) {
    if (!g1_load(Ps[i], g1s + 8 * i) || !g2_load(Qs[i], g2s + 16 * i)) return -1;
  }
  return pairing_product_is_one(Ps.data(), Qs.data(), n);
}

// The whole of snark/groth16.py's verify: 1 where
//   e(-A, B) e(alpha, beta) e(vk_x, gamma) e(C, delta) = 1
// with A, C on the curve, B on the twist and [r] B = O,
// vk_x = ic[0] + sum_i pub[i] ic[i + 1]; else 0.  0 also wherever this
// function does not decide — a wrong number of public inputs, a
// coordinate >= p, a key point off its curve, a degenerate Miller step —
// and the caller asks the Python function, whose answer (or exception)
// is the service's.  vk: alpha (8 u64), beta, gamma, delta (16 each),
// then n_ic ic points (8 each); proof: a (8), b (16), c (8); pub:
// n_pub * 4 u64, any 256-bit value.  Standard form, all-zero = infinity.
int groth16_verify_bn254(const u64 *vk, int n_public, int n_ic, const u64 *proof,
                         const u64 *pub, int n_pub) {
  if (n_pub != n_public || n_pub < 0 || n_ic < n_pub + 1) return 0;
  G1Aff A, C, alpha, vkx;
  G2Aff B, beta, gamma, delta;
  if (!g1_load(A, proof) || !g2_load(B, proof + 8) || !g1_load(C, proof + 24)) return 0;
  if (!g1_load(alpha, vk) || !g2_load(beta, vk + 8) || !g2_load(gamma, vk + 24) ||
      !g2_load(delta, vk + 40))
    return 0;
  const u64 *ic_std = vk + 56;
  std::vector<G1Aff> ic(n_pub + 1);
  for (int i = 0; i <= n_pub; ++i) {
    if (!g1_load(ic[i], ic_std + 8 * i) || !g1_on_curve(ic[i])) return 0;
  }
  if (!g1_on_curve(A) || !g1_on_curve(C) || !g1_on_curve(alpha)) return 0;
  if (!g2_on_twist(B) || !g2_in_subgroup(B)) return 0;
  if (!g2_on_twist(beta) || !g2_on_twist(gamma) || !g2_on_twist(delta)) return 0;
  // vk_x: one doubling chain for all the public inputs
  G1Jac acc, t;
  memset(&acc, 0, sizeof(acc));
  for (int bit = 255; bit >= 0; --bit) {
    jac_double(t, acc);
    acc = t;
    for (int i = 0; i < n_pub; ++i) {
      if ((pub[4 * i + bit / 64] >> (bit % 64)) & 1) {
        jac_add_mixed(t, acc, ic[i + 1].x, ic[i + 1].y);
        acc = t;
      }
    }
  }
  jac_add_mixed(t, acc, ic[0].x, ic[0].y);
  g1_to_aff(vkx, t);
  fp_neg(A.y, A.y);
  const G1Aff Ps[4] = {A, alpha, vkx, C};
  const G2Aff Qs[4] = {B, beta, gamma, delta};
  return pairing_product_is_one(Ps, Qs, 4) == 1 ? 1 : 0;
}

}  // extern "C"

// ===================================================================
// The proof's assembly: the ~10 group operations that blind the five
// MSM accumulators with (r, s) and make (A, B, C) — what
// prover/groth16_tpu.py::_assemble_host does on Python integers, 70-90
// ms a proof of which five sixths is one double-and-add over Fq2
// objects, on the proving thread with the device empty after every
// batch.  The same expression in the same order on the Jacobian
// primitives above, every addition complete (jac_add_mixed, g1_add_jac,
// g2_add_mixed and g2_add each take equal points, opposite points and
// infinity on either side), so the canonical affine bytes are the Python
// form's: group arithmetic is exact.  A plain double-and-add a scalar
// (g1_mul_plain, g2_mul_plain: by the 256 bits given, as the Python form
// multiplies by the integer it is given), no table, no lock, nothing
// shared or allocated: any number of threads may be in it at once.  The Python function stays the oracle
// (snark/native_assemble.py): every `0` from here is computed by it.
// ===================================================================

// p + q of two affine points, as a Jacobian point
static void g1_add_aff(G1Jac &out, const G1Aff &p, const G1Aff &q) {
  G1Jac inf, t;
  memset(&inf, 0, sizeof(inf));
  jac_add_mixed(t, inf, p.x, p.y);
  jac_add_mixed(out, t, q.x, q.y);
}
static void g1_store(u64 *out, const G1Aff &p) {
  fp_from_mont(p.x, out, 1);
  fp_from_mont(p.y, out + 4, 1);
}

extern "C" {

// pi_a  = alpha_1 + a  + r delta_1
// pi_b  = beta_2  + b2 + s delta_2
// pi_b1 = beta_1  + b1 + s delta_1
// pi_c  = c + h + s pi_a + r pi_b1 - (r s mod R) delta_1
// key: alpha_1, beta_1, delta_1 (8 u64 each), beta_2, delta_2 (16 each);
// acc: a, b1 (8 each), b2 (16), c, h (8 each), _assemble's order; rs: r
// then s, 4 u64 each, any 256-bit value; out: pi_a (8), pi_b (16), pi_c
// (8).  Standard form, canonical, all-zero = infinity, G2 as (x.c0,
// x.c1, y.c0, y.c1).  1 = out is written; 0 = this function does not
// decide (a coordinate >= p, a point off its curve) and the caller runs
// the Python form.
int groth16_assemble_bn254(const u64 *key, const u64 *acc, const u64 *rs, u64 *out) {
  G1Aff alpha1, beta1, delta1, a, b1, c, h;
  G2Aff beta2, delta2, b2;
  if (!g1_load(alpha1, key) || !g1_load(beta1, key + 8) || !g1_load(delta1, key + 16) ||
      !g2_load(beta2, key + 24) || !g2_load(delta2, key + 40))
    return 0;
  if (!g1_load(a, acc) || !g1_load(b1, acc + 8) || !g2_load(b2, acc + 16) ||
      !g1_load(c, acc + 32) || !g1_load(h, acc + 40))
    return 0;
  if (!g1_on_curve(alpha1) || !g1_on_curve(beta1) || !g1_on_curve(delta1) || !g1_on_curve(a) ||
      !g1_on_curve(b1) || !g1_on_curve(c) || !g1_on_curve(h))
    return 0;
  if (!g2_on_twist(beta2) || !g2_on_twist(delta2) || !g2_on_twist(b2)) return 0;
  const u64 *r = rs, *s = rs + 4;
  // r s mod R: both under R first (a 256-bit value is under 6 R), then
  // two Montgomery products, the first by 2^512 mod R
  u64 rr[4], sr[4], rsr[4];
  memcpy(rr, r, 32);
  memcpy(sr, s, 32);
  while (geq(rr, R_MOD)) sub_nored(rr, rr, R_MOD);
  while (geq(sr, R_MOD)) sub_nored(sr, sr, R_MOD);
  fr_mul(rr, rr, R2R);
  fr_mul(rsr, rr, sr);

  G1Jac t, m;
  G1Aff pi_a, pi_b1, pi_c;
  g1_add_aff(t, alpha1, a);
  g1_mul_plain(m, delta1, r);
  g1_add_jac(t, m);
  g1_to_aff(pi_a, t);

  g1_add_aff(t, beta1, b1);
  g1_mul_plain(m, delta1, s);
  g1_add_jac(t, m);
  g1_to_aff(pi_b1, t);

  g1_add_aff(t, c, h);
  g1_mul_plain(m, pi_a, s);
  g1_add_jac(t, m);
  g1_mul_plain(m, pi_b1, r);
  g1_add_jac(t, m);
  g1_mul_plain(m, delta1, rsr);
  fp_neg(m.Y, m.Y);
  g1_add_jac(t, m);
  g1_to_aff(pi_c, t);

  G2Jac t2, m2, inf2;
  memset(&inf2, 0, sizeof(inf2));
  g2_add_mixed(m2, inf2, beta2.x, beta2.y);
  g2_add_mixed(t2, m2, b2.x, b2.y);
  g2_mul_plain(m2, delta2, s);
  g2_add(t2, m2);

  g1_store(out, pi_a);
  g1_store(out + 24, pi_c);
  memset(out + 8, 0, 128);
  if (!fp2_is_zero(t2.Z)) {
    Fp2 zi, zi2, zi3, x, y;
    fp2_inv(zi, t2.Z);
    fp2_sqr(zi2, zi);
    fp2_mul(zi3, zi2, zi);
    fp2_mul(x, t2.X, zi2);
    fp2_mul(y, t2.Y, zi3);
    fp_from_mont(x.c0, out + 8, 1);
    fp_from_mont(x.c1, out + 12, 1);
    fp_from_mont(y.c0, out + 16, 1);
    fp_from_mont(y.c1, out + 20, 1);
  }
  return 1;
}

}  // extern "C"
