/* runtime_internal.h — shared internal structures of the native core.
 *
 * Split out of core.cpp so the communication engine (comm.cpp) and future
 * native subsystems (devices, tracing) can reach the runtime internals
 * without going through the public C ABI.  Everything here is
 * implementation detail; the public surface stays parsec_core.h.
 *
 * Reference analog: parsec/parsec_internal.h (task/taskpool/task-class
 * model) + parsec/remote_dep.h (comm seam) — see SURVEY.md §2.4/§2.5.
 */
#ifndef PTC_RUNTIME_INTERNAL_H
#define PTC_RUNTIME_INTERNAL_H

#include "parsec_core.h"

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <pthread.h>
#include <string>
#include <thread>
#include <unordered_map>
#include <unordered_set>
#include <vector>

/* ------------------------------------------------------------------ */
/* sanitizer-correct mutex                                             */
/* ------------------------------------------------------------------ */

/* glibc's std::mutex is zero-initialized and has a trivial destructor:
 * pthread_mutex_init/destroy are NEVER called, and ThreadSanitizer keys
 * mutex sync state by ADDRESS.  When sequential jobs in one process
 * heap-recycle a context/comm-engine address, the old object's free
 * marks the mutex at that offset "destroyed"; the next object's first
 * lock at the same address then reports "double lock of a mutex
 * (already destroyed)" and — with the lock's happens-before voided —
 * phantom data races on every field it guards (the 7 comm-fini
 * teardown warnings of the PR 2 TSan soak).  Explicit
 * pthread_mutex_init/destroy give each object's mutex a fresh TSan
 * identity.  Lockable, so std::lock_guard/std::unique_lock work;
 * condition variables on it use the ptc_condvar companion below. */
class ptc_mutex {
  pthread_mutex_t m_;

public:
  ptc_mutex() { pthread_mutex_init(&m_, nullptr); }
  ~ptc_mutex() { pthread_mutex_destroy(&m_); }
  ptc_mutex(const ptc_mutex &) = delete;
  ptc_mutex &operator=(const ptc_mutex &) = delete;
  void lock() { pthread_mutex_lock(&m_); }
  bool try_lock() { return pthread_mutex_trylock(&m_) == 0; }
  void unlock() { pthread_mutex_unlock(&m_); }
  pthread_mutex_t *native() { return &m_; }
};

/* Companion condvar: std::condition_variable_any is NOT a substitute —
 * it guards its own state with an internal make_shared<std::mutex>()
 * whose 56-byte block recycles across engines exactly like the outer
 * object, re-creating the aliasing the wrapper exists to kill.
 * pthread_cond_init/destroy are TSan-visible; timed waits run on
 * CLOCK_MONOTONIC so a wall-clock step cannot stretch a fence budget. */
class ptc_condvar {
  pthread_cond_t c_;

public:
  ptc_condvar() {
    pthread_condattr_t a;
    pthread_condattr_init(&a);
    pthread_condattr_setclock(&a, CLOCK_MONOTONIC);
    pthread_cond_init(&c_, &a);
    pthread_condattr_destroy(&a);
  }
  ~ptc_condvar() { pthread_cond_destroy(&c_); }
  ptc_condvar(const ptc_condvar &) = delete;
  ptc_condvar &operator=(const ptc_condvar &) = delete;
  void notify_one() { pthread_cond_signal(&c_); }
  void notify_all() { pthread_cond_broadcast(&c_); }
  template <class Pred>
  void wait(std::unique_lock<ptc_mutex> &lk, Pred pred) {
    while (!pred()) pthread_cond_wait(&c_, lk.mutex()->native());
  }
  template <class Rep, class Period, class Pred>
  bool wait_for(std::unique_lock<ptc_mutex> &lk,
                const std::chrono::duration<Rep, Period> &d, Pred pred) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    int64_t ns =
        (int64_t)ts.tv_nsec +
        std::chrono::duration_cast<std::chrono::nanoseconds>(d).count();
    ts.tv_sec += ns / 1000000000;
    ts.tv_nsec = ns % 1000000000;
    while (!pred()) {
      if (pthread_cond_timedwait(&c_, lk.mutex()->native(), &ts) ==
          ETIMEDOUT)
        return pred();
    }
    return true;
  }
};

/* ------------------------------------------------------------------ */
/* expressions                                                         */
/* ------------------------------------------------------------------ */

struct Expr {
  std::vector<int64_t> code; /* empty == constant 0 (or "true" for guards) */
  /* decode-time fast form (ptc_expr_finalize): almost every guard /
   * dep-param / range-bound expression in real JDFs is `atom` or
   * `atom op atom` (k==0, k-1, k<NB, ...).  Those evaluate here with
   * two loads and a switch instead of the VM's fetch-decode loop —
   * the dominant cost of the dispatch critical path before this.
   *   fast_op: 0 = none (run the VM), 1 = single atom, else the binop
   *   opcode; f*_kind: 1 imm, 2 local, 3 global. */
  int8_t fast_op = 0;
  int8_t fa_kind = 0, fb_kind = 0;
  int64_t fa = 0, fb = 0;
  bool empty() const { return code.empty(); }
};

/* populate Expr::fast_* from code (called once at spec decode) */
void ptc_expr_finalize(Expr &e);

struct ExprCb {
  ptc_expr_cb fn;
  void *user;
};

/* lock-free-read registry (the PR 6 arena-table idiom, templated):
 * readers acquire-load the published table and index it with ids
 * handed out by registration; writers (reg_lock held) publish
 * slot-then-count and grow by TABLE REPLACEMENT, retiring every old
 * table until teardown so a reader holding a stale pointer never
 * dangles.  Registration stays open for the context's life: the
 * serving stack registers pt.call lookup tables and KV-page
 * collections from submitter/pump threads while admitted pools
 * execute — a plain vector's push_back realloc would move the
 * elements under the OP_CALL / body-dispatch readers (TSan-caught
 * by the ptc-share prefix/speculation churn). */
template <typename T> struct PubReg {
  std::atomic<T *> tab{nullptr};
  std::atomic<int32_t> count{0};
  int32_t cap = 0;         /* writer-side, under reg_lock */
  std::vector<T *> tables; /* every table ever published */
  int32_t push(T v) {      /* caller holds reg_lock */
    int32_t n = count.load(std::memory_order_relaxed);
    if (n == cap) {
      int32_t nc = cap ? cap * 2 : 16;
      T *nt = new T[nc];
      T *ot = tab.load(std::memory_order_relaxed);
      for (int32_t i = 0; i < n; i++) nt[i] = ot[i];
      tables.push_back(nt);
      tab.store(nt, std::memory_order_release);
      cap = nc;
    }
    tab.load(std::memory_order_relaxed)[n] = v;
    count.store(n + 1, std::memory_order_release);
    return n;
  }
  T &operator[](size_t i) {
    return tab.load(std::memory_order_acquire)[i];
  }
  int32_t size() const { return count.load(std::memory_order_acquire); }
  ~PubReg() {
    for (T *t : tables) delete[] t;
  }
};

/* ------------------------------------------------------------------ */
/* data                                                                */
/* ------------------------------------------------------------------ */

struct ReshapeCache; /* below (needs DtypeDef) */

struct ptc_copy {
  ptc_data *data = nullptr;
  void *ptr = nullptr;
  int64_t size = 0;
  int64_t handle = 0; /* opaque Python-side id (e.g. jax buffer) */
  std::atomic<int32_t> refcount{1};
  std::atomic<int32_t> version{0};
  int32_t arena_id = -1; /* >=0: return to arena freelist on release */
  bool owns_ptr = false;
  /* local-reshape support (reference: parsec_reshape.c /
   * parsec_datacopy_future.c — the datacopy-future chain).  `shaped_as`
   * marks a copy that IS the product of a reshape through that datatype,
   * so forwarding it through a same-typed dep does not re-reshape
   * (reference: remote_no_re_reshape.jdf).  `reshape` memoizes this
   * copy's reshaped children per (datatype, version): every consumer of
   * the same (copy, type) shares one converted copy — the future's
   * trigger runs once. */
  int32_t shaped_as = -1;
  std::atomic<ReshapeCache *> reshape{nullptr};
};

struct ptc_data {
  int64_t key = 0;
  int64_t size = 0;
  ptc_copy *host_copy = nullptr;
};

/* ------------------------------------------------------------------ */
/* spec structures (decoded blobs)                                     */
/* ------------------------------------------------------------------ */

enum DepKind { DEP_NONE = 0, DEP_TASK = 1, DEP_MEM = 2 };

struct DepParam {
  bool is_range = false;
  Expr value;      /* when !is_range */
  Expr lo, hi, st; /* when is_range */
};

/* bound iterator of a bracketed dep (`-> [i = 0..n] A T(f(i))`): its
 * expressions may read earlier iterators; the iterator value lives in
 * scratch slot nb_locals + position during dep evaluation */
struct DepIter {
  Expr lo, hi, st;
};

struct Dep {
  int32_t direction = 0; /* 0 in, 1 out */
  Expr guard;            /* empty == always true */
  /* guard contains a Python escape (decode-time memo of expr_has_call:
   * the conservative counting path checks this per dep per instance) */
  bool guard_dyn = false;
  int32_t kind = DEP_NONE;
  /* DEP_TASK */
  int32_t peer_class = -1;
  int32_t peer_flow = -1;
  std::vector<DepParam> params;
  /* DEP_MEM */
  int32_t dc_id = -1;
  std::vector<Expr> idx;
  int32_t arena_id = -1;
  /* bracketed iterators (JDF local indices); guard and params may read
   * them via scratch slots */
  std::vector<DepIter> iters;
  /* wire datatype (JDF `[type_remote = ...]`): OUT deps pack the
   * producer's strided layout to contiguous wire bytes, IN deps scatter
   * wire bytes into the consumer's layout (reference: the MPI datatype
   * construction per dep, parsec/datatype/datatype_mpi.c) */
  int32_t dtype_id = -1;
  /* local reshape datatype (JDF `[type = ...]` / `[type_data = ...]`):
   * the dep's data is routed through a NEW datacopy holding only the
   * elements the type selects (and/or element-cast), memoized per
   * (source copy, type) — the reference's datacopy-future reshape,
   * parsec/parsec_reshape.c:771.  On a Mem OUT dep this selects which
   * region of the collection tile the write-back updates. */
  int32_t ltype_id = -1;
};

/* wire/reshape datatype.  Three forms:
 *  - strided vector: `count` blocks of `elem` bytes spaced `stride`
 *    bytes apart (contiguous when stride == elem);
 *  - indexed: explicit (offset, len) byte segments (`segs` non-empty;
 *    the MPI_Type_indexed analog — expresses triangles etc.);
 *  - element cast: src_kind/dst_kind >= 0, contiguous; `count` elements
 *    (count < 0 = the whole copy) converted element-wise.  Cast and
 *    segment selection do not combine (rejected at registration). */
struct DtypeDef {
  int64_t elem = 0, count = 0, stride = 0;
  std::vector<std::pair<int64_t, int64_t>> segs; /* (offset, len) bytes */
  int32_t src_kind = -1, dst_kind = -1;          /* PTC_ELEM_* */
  bool is_cast() const { return src_kind >= 0; }
  int64_t packed() const {
    if (!segs.empty()) {
      int64_t s = 0;
      for (const auto &p : segs) s += p.second;
      return s;
    }
    return elem * count;
  }
  int64_t extent() const {
    if (!segs.empty()) {
      int64_t e = 0;
      for (const auto &p : segs)
        if (p.first + p.second > e) e = p.first + p.second;
      return e;
    }
    return count > 0 ? (count - 1) * stride + elem : 0;
  }
};

/* memoized reshaped children of one source copy (datacopy-future role:
 * one conversion, shared by every consumer of the same (copy, type)) */
struct ReshapeCache {
  std::mutex lock;
  struct Entry {
    int32_t ltype_id;
    int32_t src_version;
    ptc_copy *shaped; /* one ref held by the cache */
  };
  std::vector<Entry> entries;
};

struct Flow {
  int32_t flags = 0; /* PTC_FLOW_* */
  int32_t arena_id = -1;
  std::vector<Dep> in_deps, out_deps;
};

struct Local {
  bool is_range = false;
  /* comprehension parameter (JDF local indices: `odd = [i = 0..4] 2*i+1`,
   * tests/dsl/ptg/local-indices): lo/hi/st bound the ITERATOR, and
   * `value` maps it to the parameter value — compiled to read the
   * local's own slot, which holds the iterator during evaluation and
   * the mapped value afterwards. */
  bool is_compr = false;
  Expr lo, hi, st; /* range bounds, or comprehension iterator bounds */
  Expr value;      /* derived value, or comprehension map expr */
};

struct Chore {
  int32_t device_type = PTC_DEV_CPU;
  int32_t body_kind = PTC_BODY_NOOP;
  int64_t body_arg = 0;
  std::atomic<bool> disabled{false};
  Chore() = default;
  Chore(const Chore &o)
      : device_type(o.device_type), body_kind(o.body_kind),
        body_arg(o.body_arg), disabled(o.disabled.load()) {}
};

struct TaskClass {
  std::string name;
  int32_t id = 0;
  std::vector<Local> locals;
  std::vector<int32_t> range_locals; /* indices of range locals, in order */
  int32_t aff_dc = -1;
  std::vector<Expr> aff_idx;
  Expr priority;
  std::vector<Flow> flows;
  std::vector<Chore> chores;
  /* domain-check fast path (task_params_in_domain): when every range
   * bound depends only on pool globals, [lo,hi,st] per range local are
   * cached here on first use (classes live per-taskpool, so globals are
   * fixed).  state: 0 unknown, 1 cached, 2 dynamic bounds. */
  mutable std::atomic<int> domain_cache_state{0};
  mutable std::vector<int64_t> domain_lo, domain_hi, domain_st;
  /* per range-local sorted value set for POOL-CONST comprehension
   * parameters (membership by binary search instead of an O(range)
   * re-evaluation walk); empty vector = plain range, use lo/hi/st */
  mutable std::vector<std::vector<int64_t>> domain_vals;
  /* any IN dep declares a local reshape type (checked per delivery only
   * when true — keeps ltype-free classes off the select_input_dep path) */
  bool has_in_ltype = false;
  /* any non-range (derived) local exists — fill_derived_locals runs 3x
   * per task on the dispatch path; derived-free classes skip the walk */
  bool has_derived = false;
  /* runtime-native collective step (class name starts with "ptc_coll"):
   * completions and cross-rank deliveries feed the ptc_coll_stats
   * counters and PROF_KEY_COLL trace spans */
  bool is_coll = false;
  /* always-on metrics: interned class-name id (context-wide, stable
   * across taskpools sharing a name); -1 past the interning cap */
  int32_t metric_id = -1;
  TaskClass() = default;
  TaskClass(const TaskClass &o)
      : name(o.name), id(o.id), locals(o.locals),
        range_locals(o.range_locals), aff_dc(o.aff_dc), aff_idx(o.aff_idx),
        priority(o.priority), flows(o.flows), chores(o.chores),
        has_in_ltype(o.has_in_ltype), has_derived(o.has_derived),
        is_coll(o.is_coll), metric_id(o.metric_id) {}
};

/* ------------------------------------------------------------------ */
/* registries                                                          */
/* ------------------------------------------------------------------ */

struct BodyCb {
  ptc_body_cb fn;
  void *user;
};

struct Collection {
  uint32_t nodes = 1, myrank = 0;
  ptc_rank_of_cb rank_of = nullptr;
  ptc_data_of_cb data_of = nullptr;
  void *user = nullptr;
  /* builtin linear collection */
  bool linear = false;
  char *base = nullptr;
  int64_t nb_elems = 0, elem_size = 0;
  std::vector<ptc_data *> linear_data; /* lazily created */
  std::mutex linear_lock;
};

/* Arena block allocator with per-worker magazines (reference:
 * parsec/mempool.c's per-thread mempools).  A worker thread allocates
 * and frees against its own magazine with no lock; magazines refill
 * from / spill to the shared freelist in PTC_MAG_BATCH-sized moves
 * under ONE lock acquisition, so the steady-state alloc/free pair
 * crosses no mutex.  Non-worker threads (slot < 0: main, comm, device
 * managers) take the locked shared path directly.
 *
 * hits/misses use single-writer relaxed atomics (plain add codegen on
 * x86, TSan-visible for the cross-thread stats read). */
constexpr int PTC_MAG_BATCH_DEFAULT = 64;

struct Arena {
  int64_t elem_size = 0;
  /* refill/spill move size, stamped from the owning context's
   * mag_batch (PTC_MCA_runtime_mag_batch) at registration and
   * immutable afterwards — the ptc-tune magazine-batch knob */
  int32_t mag_batch = PTC_MAG_BATCH_DEFAULT;
  std::vector<void *> freelist;
  std::mutex lock;
  struct alignas(64) Mag {
    std::vector<void *> items;
    std::atomic<int64_t> hits{0}, misses{0};
  };
  std::unique_ptr<Mag[]> mags; /* one per worker; owner-thread only */
  int32_t nb_mags = 0;
  std::atomic<int64_t> ext_hits{0}, ext_misses{0};
  void init_mags(int32_t n);
  /* slot = calling worker's index when the caller IS that worker
   * thread of the owning context, else -1 (locked shared path) */
  void *alloc(int32_t slot);
  void dealloc(int32_t slot, void *p);
  int64_t stat_hits() const;
  int64_t stat_misses() const;
  ~Arena();
};

/* ------------------------------------------------------------------ */
/* task                                                                */
/* ------------------------------------------------------------------ */

/* Dynamic-task extension (DTD): explicit successor lists instead of
 * expression-derived deps.  Reference: parsec/interfaces/dtd.  */
struct DynExt {
  std::mutex lock;
  std::vector<ptc_task *> succs;     /* registered, not yet released */
  std::atomic<int32_t> remaining{1}; /* +1 submission hold */
  std::atomic<int32_t> refs{1};      /* runtime ref; tiles add refs */
  bool completed = false;
  int32_t nb_flows = 0;
  int32_t body_kind = 0; /* PTC_BODY_* */
  int64_t body_arg = 0;
  int32_t modes[PTC_MAX_FLOWS] = {0}; /* PTC_DTD_* per flow */
  /* distributed DTD */
  uint64_t seq = 0;           /* global insertion sequence number */
  uint32_t rank = 0;          /* placement rank */
  bool shadow = false;        /* placed on another rank */
  ptc_dtile *tiles[PTC_MAX_FLOWS] = {nullptr}; /* arg tiles (borrowed) */
};

struct ptc_task {
  ptc_taskpool *tp = nullptr;
  int32_t class_id = 0;
  int32_t priority = 0;
  int32_t chore_idx = 0;
  int32_t status = 0;
  int64_t locals[PTC_MAX_LOCALS];
  ptc_copy *data[PTC_MAX_FLOWS];
  ptc_task *next = nullptr; /* freelist link */
  DynExt *dyn = nullptr;    /* non-null for DTD tasks */
};

/* Per-tile accessor chain (reference: parsec_dtd_tile_t last_user /
 * last_writer under per-tile locks, insert_function_internal.h:110-139) */
struct ptc_dtile {
  std::mutex lock;
  ptc_copy *copy = nullptr;
  ptc_task *last_writer = nullptr;
  std::vector<ptc_task *> readers;
  uint32_t owner = 0; /* owning rank (distributed DTD placement) */
  /* Distributed payload pull (the reference routes DTD data along actual
   * dependency edges instead of broadcasting written tiles to every rank,
   * insert_function_internal.h:110-139).  A remote writer's completion
   * above the eager limit carries a size-only marker; the local mirror is
   * then `stale` until a local consumer pulls the bytes on demand. */
  bool stale = false;
  bool fetch_inflight = false;
  uint64_t stale_seq = 0;   /* writer's insertion seq (the pull key) */
  int32_t stale_flow = 0;   /* writer's flow index holding the bytes */
  uint32_t stale_src = 0;   /* rank that executed the writer */
  std::vector<ptc_task *> fetch_waiters; /* +1 remaining each, retained */
  /* owner side: seq of this tile's live entry in tp->dtd_served */
  uint64_t served_seq = UINT64_MAX;
};

/* ------------------------------------------------------------------ */
/* dependency tracking                                                 */
/* ------------------------------------------------------------------ */

struct DepKey {
  int32_t class_id;
  uint64_t hash;
  std::vector<int64_t> params;
  bool operator==(const DepKey &o) const {
    return class_id == o.class_id && params == o.params;
  }
};
struct DepKeyHash {
  size_t operator()(const DepKey &k) const { return (size_t)k.hash; }
};

uint64_t ptc_fnv_hash(int32_t class_id, const std::vector<int64_t> &params);

/* sched.cpp: canonical module name a request resolves to */
const char *ptc_sched_canonical(const char *name);

/* A pending successor: data copies staged by producers until all task-input
 * dependencies are satisfied, then promoted to a ready task.  (Reference
 * analog: parsec_hashable_dependency_t entries + datarepo retention.)
 *
 * Per-flow expected-delivery counts give EXACT duplicate detection while
 * the entry is live (the reference's output-mask update semantics,
 * parsec/parsec_internal.h:355-365, generalized to control-gather counts):
 * a second delivery to an already-satisfied flow is dropped with a
 * warning instead of firing the task early.  Promoted instances leave no
 * tombstone — memory is flat in completed tasks, and a 64-bit hash
 * collision between two live instances can no longer swallow a
 * legitimate delivery (round-1 VERDICT weak #4). */
struct DepEntry {
  int32_t remaining = 0;
  bool initialized = false;
  int32_t flow_remaining[PTC_MAX_FLOWS] = {0};
  ptc_copy *staged[PTC_MAX_FLOWS] = {nullptr};
};

/* Dense dependency engine (reference: the per-task-class choice between
 * a dense multi-dim dependency array and a hash table,
 * parsec/parsec_internal.h:201-216 + parsec_default_find_deps:343):
 * when startup enumeration finds a class's instances fit a bounded box,
 * deliveries index an O(1) slot array instead of the sharded hash —
 * no key allocation, no hashing, no map rebalance on the hot path.
 * Slot values: nullptr (untouched) / live DepEntry* / PROMOTED sentinel
 * (exact duplicate detection for the WHOLE run, memory already paid by
 * the slot array).  Slots are guarded by the taskpool's shard mutexes,
 * striped by slot index. */
struct DepEntry;
struct DenseDeps {
  bool enabled = false;
  std::vector<int64_t> lo, span; /* per range-local bounding box */
  int64_t nb_slots = 0;
  std::unique_ptr<std::atomic<DepEntry *>[]> slots;
};

struct DepShard {
  std::mutex lock;
  std::unordered_map<DepKey, DepEntry, DepKeyHash> map;
  /* Recently-promoted instances, FULL key identity (a hash collision can
   * never be mistaken for a duplicate), bounded FIFO (memory stays flat
   * at any task count).  Catches the only plausible post-promotion
   * duplicates — near-in-time re-deliveries — without re-creating a
   * fresh entry that could double-fire the task. */
  std::unordered_set<DepKey, DepKeyHash> promoted_recent;
  std::deque<DepKey> promoted_fifo;
};
constexpr int NB_SHARDS = 64;
constexpr size_t PROMOTED_RECENT_CAP = 1024; /* per shard */

/* ------------------------------------------------------------------ */
/* schedulers                                                          */
/* ------------------------------------------------------------------ */

struct Scheduler {
  /* per-worker steal counters — a select() that served worker w from a
   * VICTIM's queue ticks steals[w].  Data source for the print_steals
   * observability role (reference: mca/pins/print_steals); global-queue
   * schedulers never tick.  Sized by the install caller (core.cpp). */
  std::vector<std::unique_ptr<std::atomic<int64_t>>> steals;
  /* external-producer inject traffic (lock-free MPSC modules tick these;
   * mutex/global modules leave them 0) — Context.sched_stats() rows */
  std::atomic<int64_t> inject_pushes{0}, inject_pops{0};
  /* per-pool QoS traffic (lanes implemented by lws; other modules rely
   * on the composed task priority alone and leave these 0).  preempt
   * off = a worker keeps draining the lane it last served until empty
   * instead of re-ranking by priority at every select (the
   * wave-boundary preemption control knob, PTC_MCA_sched_qos_preempt) */
  std::atomic<bool> qos_preempt{true};
  std::atomic<int64_t> qos_selects{0}, qos_preempts{0};
  void steals_init(int n) {
    steals.clear();
    for (int i = 0; i < (n < 1 ? 1 : n); i++)
      steals.emplace_back(new std::atomic<int64_t>(0));
  }
  void steal_tick(int w) {
    if (w >= 0 && w < (int)steals.size())
      steals[(size_t)w]->fetch_add(1, std::memory_order_relaxed);
  }
  virtual ~Scheduler() {}
  /* vp (virtual process / NUMA domain) per worker, given BEFORE
   * install; hierarchical modules (lhq) shape their steal order on it,
   * everyone else ignores it (reference: vpmap.c feeding sched init) */
  virtual void set_vpmap(const std::vector<int32_t> &) {}
  virtual void install(int nb_workers) = 0;
  virtual void schedule(int worker, ptc_task *t) = 0;
  virtual ptc_task *select(int worker) = 0;
};

/* optional introspection mixin for hierarchical schedulers: exposes
 * the computed steal order so tests can assert the hierarchy without
 * racing actual steals (consumed by ptc_sched_victim_order) */
struct SchedVictimOrder {
  virtual ~SchedVictimOrder() {}
  virtual int32_t victim_order(int32_t worker, int32_t *out,
                               int32_t cap) const = 0;
};

/* registered by name; see sched table in core.cpp */
Scheduler *ptc_sched_create(const std::string &name);

/* ------------------------------------------------------------------ */
/* device queues, profiling                                            */
/* ------------------------------------------------------------------ */

struct DeviceQueue {
  ptc_mutex lock;
  ptc_condvar cv;
  std::deque<ptc_task *> dq;
  /* load-balancing inputs (reference: parsec_get_best_device's
   * flop-rate weights + per-device load, parsec/mca/device/device.c:79;
   * weights device.h:137-140) */
  std::atomic<int64_t> depth{0};     /* tasks queued, not yet completed */
  std::atomic<double> weight{1.0};   /* relative device speed */
};

enum { PROF_WORDS_K = 8 }; /* words per event (== PROF_WORDS below) */

struct ProfBuf {
  /* spinlock, not a mutex: the push critical section is a ~16-word
   * append (amortized), paid once per task at trace level 1 — an
   * uncontended std::mutex costs ~3x the test_and_set pair.  Contention
   * is rare (owner worker + comm-thread instants on buffer 0 + take). */
  std::atomic_flag lock = ATOMIC_FLAG_INIT;
  std::vector<int64_t> words; /* PROF_WORDS words per event */
  /* flight-recorder ring (PTC_MCA_runtime_trace_ring): cap_words > 0
   * bounds the buffer; pushes wrap, overwriting oldest whole events
   * (dropped counts them), so long production runs always keep the
   * last-N-bytes tail instead of growing without bound.  head = next
   * write offset, count = live words; all fields are lock-guarded. */
  size_t cap_words = 0, head = 0, count = 0;
  int64_t dropped = 0; /* events overwritten before being taken */
  void acquire() {
    while (lock.test_and_set(std::memory_order_acquire))
      std::this_thread::yield();
  }
  void release() { lock.clear(std::memory_order_release); }
  /* append n words (a multiple of PROF_WORDS); lock held by caller */
  void append(const int64_t *w, size_t n) {
    if (cap_words == 0) {
      words.insert(words.end(), w, w + n);
      return;
    }
    if (n > cap_words) { /* degenerate cap: keep the newest tail */
      dropped += (int64_t)((n - cap_words) / PROF_WORDS_K);
      w += n - cap_words;
      n = cap_words;
    }
    if (words.size() != cap_words) words.resize(cap_words);
    if (count + n > cap_words)
      dropped += (int64_t)((count + n - cap_words) / PROF_WORDS_K);
    for (size_t i = 0; i < n; i++) {
      words[head] = w[i];
      head = head + 1 == cap_words ? 0 : head + 1;
    }
    count = std::min(cap_words, count + n);
  }
  /* copy the live contents oldest-first into out (<= cap_out words,
   * whole events only); lock held.  clear=true resets the buffer. */
  int64_t drain(int64_t *out, int64_t cap_out, bool clear) {
    int64_t n = cap_words ? (int64_t)count : (int64_t)words.size();
    int64_t take = std::min(n, cap_out);
    take -= take % PROF_WORDS_K;
    if (take > 0) {
      if (cap_words) {
        size_t start = (head + cap_words - count) % cap_words;
        for (int64_t i = 0; i < take; i++)
          out[i] = words[(start + (size_t)i) % cap_words];
      } else {
        std::memcpy(out, words.data(), (size_t)take * sizeof(int64_t));
      }
    }
    if (clear && take > 0) {
      if (cap_words) {
        count -= (size_t)take; /* newest `count` words stay */
      } else {
        words.erase(words.begin(), words.begin() + take);
      }
    }
    return take;
  }
};

/* RAII for ProfBuf::acquire/release */
struct ProfLockGuard {
  ProfBuf *b;
  explicit ProfLockGuard(ProfBuf *buf) : b(buf) { b->acquire(); }
  ~ProfLockGuard() { b->release(); }
};

/* Paired-event trace keys (reference: the profiling dictionary +
 * PINS event points, parsec/mca/pins/pins.h:26-54, SURVEY.md §5).
 * Event = (key, phase, class_id, l0, l1, worker, aux, t_ns); EDGE events
 * come in consecutive src(phase=0)/dst(phase=1) pairs.                  */
enum {
  PROF_KEY_EXEC = 0,      /* task body begin/end                      */
  PROF_KEY_RELEASE = 1,   /* release_deps begin/end                   */
  PROF_KEY_EDGE = 2,      /* dep edge src->dst (pair of events)       */
  PROF_KEY_COMM_SEND = 3, /* per-target activation send: instant span
                           * (begin+end, same t), aux = payload bytes */
  PROF_KEY_DEVICE = 5,    /* device dispatch call begin/end (emitted by
                             the device manager through ptc_prof_event;
                             l0 = lanes in the batched call)            */
  PROF_KEY_COMM_RECV = 4, /* per-target activation delivery: instant
                           * span, aux = payload bytes                */
  /* 6 (DEVICE_H2D), 7 (STREAM_D2H) and 11-13 (the device manager's
   * loop states: READY_WAIT, BATCH_WAIT, LOOP) are emitted by the Python
   * device layer through ptc_prof_event — keep this enum in sync with
   * profiling/trace.py when extending */
  PROF_KEY_COLL = 8,      /* collective-step traffic on a ptc_coll_*
                           * task class: instant span at delivery
                           * (l0 = src rank, l1 = corr, aux = bytes) —
                           * the evidence behind the coll_wait lost-time
                           * bucket (profiling/critpath.py)            */
  PROF_KEY_SCOPE = 9,     /* request-scope flow tag: instant span
                           * emitted ALONGSIDE COMM_SEND (producer) and
                           * COMM_RECV (consumer) when the sending
                           * taskpool carries a nonzero scope_id —
                           * (class = tp id, l0 = src rank, l1 = corr,
                           * aux = scope_id), so a merged trace maps
                           * each (src, corr) wire flow back to the
                           * request it served (profiling/scope.py)   */
  PROF_KEY_INFLIGHT = 10, /* crash-dump synthetic: one instant span per
                           * OPEN EXEC body at fatal-signal time, built
                           * from the MetWorker inflight slots inside
                           * the async-signal-safe crash writer —
                           * (class = mid, l0 = worker, l1 = 0,
                           * aux = scope_id, begin stamped at the body's
                           * cur_begin).  Never emitted on the normal
                           * path; ptc_postmortem reads these to name
                           * what a dead rank was executing.           */
};
enum { PROF_WORDS = 8 };

/* ------------------------------------------------------------------ */
/* always-on runtime metrics (reference role: the PINS counter modules
 * + aggregator_visu live streaming, made native and always-on)         */
/* ------------------------------------------------------------------ */

/* Metric kinds.  EXEC is per task class (one histogram per interned
 * class name per worker); the others are one histogram per worker. */
enum {
  PTC_MET_EXEC = 0,      /* task body duration (per class)             */
  PTC_MET_RELEASE = 1,   /* release_deps latency (1-in-N sampled)      */
  PTC_MET_H2D_STALL = 2, /* dispatch-time synchronous h2d stall        */
  PTC_MET_COMM_WAIT = 3, /* rendezvous pull window (GET -> delivered)  */
  PTC_MET_COLL_WAIT = 4, /* same, delivered to a ptc_coll_* class      */
  PTC_MET_NKINDS = 5,
};

/* log2 buckets with 3-bit linear sub-buckets (HDR-histogram style):
 * bucket width is 12.5% of the value, so a quantile read off the bucket
 * midpoint is within ~6% of exact — the "p50/p99 within 10% of a
 * level-2 trace" contract.  ns < 8 index exactly; octaves 3..44 get 8
 * linear sub-buckets each; >= 2^45 ns (~9.7 h) clamps to the last. */
constexpr int PTC_MET_SUBBITS = 3;
constexpr int PTC_MET_SUB = 1 << PTC_MET_SUBBITS;
constexpr int PTC_MET_MAX_OCT = 45;
constexpr int PTC_MET_BUCKETS =
    PTC_MET_SUB + (PTC_MET_MAX_OCT - PTC_MET_SUBBITS) * PTC_MET_SUB;
constexpr int PTC_MET_MAX_CLASSES = 256; /* interned class-name cap */

inline int ptc_met_bucket(int64_t ns) {
  if (ns < PTC_MET_SUB) return ns < 0 ? 0 : (int)ns;
  int oct = 63 - __builtin_clzll((uint64_t)ns);
  if (oct >= PTC_MET_MAX_OCT) return PTC_MET_BUCKETS - 1;
  int sub = (int)((ns >> (oct - PTC_MET_SUBBITS)) & (PTC_MET_SUB - 1));
  return PTC_MET_SUB + (oct - PTC_MET_SUBBITS) * PTC_MET_SUB + sub;
}

/* One histogram.  Writers are per-worker (single-writer in steady
 * state), readers snapshot cross-thread: relaxed atomics everywhere —
 * a torn snapshot only misclassifies in-flight events, never corrupts. */
struct MetHist {
  std::atomic<int64_t> count{0};
  std::atomic<int64_t> sum{0};
  std::atomic<int64_t> b[PTC_MET_BUCKETS] = {};
  void record(int64_t ns) {
    count.fetch_add(1, std::memory_order_relaxed);
    if (ns > 0) sum.fetch_add(ns, std::memory_order_relaxed);
    b[ptc_met_bucket(ns)].fetch_add(1, std::memory_order_relaxed);
  }
};

/* Per-worker metric state.  Index nb_workers is the shared EXTERNAL
 * slot (comm thread, device managers, main) — multi-writer there, the
 * relaxed atomics stay correct.  The inflight slot feeds the watchdog:
 * cur_begin != 0 means an EXEC body is open on this worker since then
 * (write order: mid then begin at open; begin=0 then mid=-1 at close). */
struct MetWorker {
  std::atomic<MetHist *> exec[PTC_MET_MAX_CLASSES] = {};
  MetHist kind[PTC_MET_NKINDS]; /* kind[EXEC] = unnamed-class overflow */
  std::atomic<int64_t> cur_begin{0};
  std::atomic<int32_t> cur_mid{-1};
  /* owning pool's request scope of the open EXEC body (0 = unscoped):
   * lets the watchdog's stuck-task event name the victim request */
  std::atomic<int64_t> cur_scope{0};
  std::atomic<int64_t> rel_tick{0}; /* release-latency sampling */
  ~MetWorker() {
    for (auto &h : exec) delete h.load(std::memory_order_relaxed);
  }
};

/* latest rank-wide merge input from one peer (rank 0 only; fed by the
 * fence-time MSG_METRICS frames, guarded by ctx->met_lock) */
struct MetRemote {
  int64_t rtt_ns = 0;    /* the peer's clock-sync min RTT to rank 0 */
  int64_t offset_ns = 0; /* the peer's clock offset estimate */
  struct Rec {
    int32_t kind;
    std::string name; /* empty = no class (non-EXEC kinds) */
    int64_t count, sum;
    std::vector<std::pair<int32_t, int64_t>> pairs; /* (bucket, count) */
  };
  std::vector<Rec> recs;
};

/* ------------------------------------------------------------------ */
/* taskpool + context                                                  */
/* ------------------------------------------------------------------ */

struct CommEngine; /* defined in comm.cpp */

struct ptc_taskpool {
  ptc_context *ctx = nullptr;
  int32_t id = -1; /* distributed taskpool id (SPMD creation order) */
  std::vector<int64_t> globals;
  std::vector<TaskClass> classes;
  std::atomic<int64_t> nb_tasks{0};  /* remaining local tasks */
  std::atomic<int64_t> nb_total{0};  /* counted at startup */
  std::atomic<int64_t> nb_errors{0}; /* failed/dropped tasks */
  std::atomic<bool> open{false};     /* DTD: dynamic insertion */
  std::atomic<bool> completed{false};
  std::atomic<bool> added{false};
  ptc_tp_complete_cb complete_cb = nullptr; /* compose/recursive seam */
  void *complete_user = nullptr;
  DepShard shards[NB_SHARDS];
  std::vector<DenseDeps> dense; /* per class; enabled by enumeration */
  /* ptc_mutex/ptc_condvar (not std::): explicit pthread init/destroy
   * give each pool's sync objects a fresh TSan identity across the
   * heap-recycled pool addresses of sequential jobs (the PR 3 fix),
   * and keep every core condvar out of libstdc++ — the TSan
   * suppressions may mute uninstrumented libstdc++ users (jax's
   * Eigen pool) without ever masking this runtime's own waits. */
  ptc_mutex done_lock;
  ptc_condvar done_cv;
  /* DTD insertion-window throttle; drain_waiters gates the notify in the
   * per-task completion hot path (ptc_tp_drain on a PTG pool would
   * otherwise miss its wakeup — only the DTD path notified window_cv) */
  ptc_mutex window_lock;
  ptc_condvar window_cv;
  std::atomic<int32_t> drain_waiters{0};
  /* completion-path guard: >0 while a completer may still touch this
   * pool AFTER a waiter-visible predicate (completed / nb_tasks==0)
   * flipped.  A waiter can return the instant the predicate is true
   * (spurious wakeup), so ptc_tp_destroy must wait for busy==0 before
   * freeing the condvars/mutexes the completer is about to notify. */
  std::atomic<int32_t> busy{0};
  /* DTD distributed: insertion sequence counter + remote completions that
   * arrived before their shadow task was inserted (seq → payload frame) */
  std::atomic<uint64_t> dtd_seq{0};
  std::mutex dtd_lock;
  std::unordered_map<uint64_t, ptc_task *> dtd_shadows; /* seq → waiting */
  std::unordered_map<uint64_t, std::vector<uint8_t>> dtd_early;
  /* payload pull server (writer side): seq → records a remote rank may
   * still fetch.  An entry is retired when the tile's NEXT writer
   * completes here (by then every fetch of the old seq has been served —
   * WAR ordering) or at pool teardown.  Copies are retained. */
  struct DtdServed {
    int32_t flow;
    ptc_copy *copy;
    ptc_dtile *tile;
  };
  std::unordered_map<uint64_t, std::vector<DtdServed>> dtd_served;
  /* requester side: outstanding pulls, (seq, flow) → destination tile */
  std::map<std::pair<uint64_t, int32_t>, ptc_dtile *> dtd_fetch_pending;

  /* ---- per-pool QoS (serving runtime; reference role: the priority
   * levels of __parsec_schedule generalized to whole taskpools).  A pool
   * with `qos` set routes its ready tasks through the scheduler's QoS
   * lanes (SchedLWS: one lane per (priority, weight) class, strict
   * priority tiers + stride-weighted sharing inside a tier, consulted at
   * every select() — the wave-boundary preemption point) and skips the
   * same-worker bypass so a higher-priority pool can win every boundary.
   * Counters: scheduled = tasks entering a lane, selected = lane pops,
   * executed = completed tasks (any scheduler), wait_ns = lane queue
   * time, preempts = selections that overtook a nonempty lower-priority
   * lane.  qos_prio is clamped to ±1023 so the composed task priority
   * (pool_prio << 20 + class priority) cannot overflow int32. */
  std::atomic<bool> qos{false};
  int32_t qos_prio = 0;
  int64_t qos_weight = 1;
  std::atomic<int64_t> q_scheduled{0}, q_selected{0}, q_executed{0};
  std::atomic<int64_t> q_wait_ns{0}, q_preempts{0};

  /* ---- request scope (observability; reference role: the PINS
   * task-attribution layer generalized to the serving work unit).  A
   * nonzero scope_id names the request/pool this taskpool serves: EXEC
   * and RELEASE trace spans stamp it in their aux word, outgoing
   * ACTIVATE frames carry it across the wire, and the watchdog's
   * inflight slot reports it so a stuck-task event names the victim
   * request.  0 = unscoped (every pre-serve workload). */
  std::atomic<int64_t> scope_id{0};
};

struct ptc_context {
  int nb_workers = 1;
  std::vector<std::thread> workers;
  std::atomic<bool> started{false};
  std::mutex start_lock; /* serializes lazy startup vs concurrent schedulers */
  std::atomic<bool> shutdown{false};
  Scheduler *sched = nullptr;
  std::string sched_name = "lfq";
  /* dense dep engine budget (slots per class); 0 disables.  Env:
   * PTC_MCA_deptable_dense_max */
  int64_t dense_max_slots = 1 << 22;

  /* idle-worker parking */
  ptc_mutex idle_lock;
  ptc_condvar idle_cv;
  std::atomic<int64_t> work_signal{0};

  /* registries: lock-free readers (OP_CALL evaluation, body dispatch,
   * collection vtable lookups on workers and the comm thread) against
   * registration that stays open for the context's life — grow-only
   * published tables, same discipline as the arena registry below */
  PubReg<ExprCb> expr_cbs;
  PubReg<BodyCb> body_cbs;
  PubReg<Collection *> collections;
  /* arena registry: lock-free reads on the copy-release / comm sizing
   * hot paths while registration stays OPEN for the context's life
   * (runtime-native collectives register one arena per op with comm
   * traffic still draining — a plain vector's push_back realloc would
   * move the data under a concurrent reader).  Writers (reg_lock held)
   * publish slot-then-count; growth installs a fresh table and retires
   * the old one until teardown, so a reader holding a stale table
   * pointer still indexes valid memory. */
  std::atomic<Arena **> arena_tab{nullptr};
  std::atomic<int32_t> arena_count{0};
  int32_t arena_cap = 0;              /* writer-side, under reg_lock */
  std::vector<Arena **> arena_tables; /* every table ever published */

  Arena *arena_at(int32_t id) {
    return arena_tab.load(std::memory_order_acquire)[(size_t)id];
  }
  int32_t arenas_n() const {
    return arena_count.load(std::memory_order_acquire);
  }
  std::vector<DtypeDef> dtypes; /* wire datatypes — ALWAYS read via
                                 * ptc_dtype_get (reg_lock-guarded) */
  std::atomic<bool> has_dtypes{false};
  std::vector<DeviceQueue *> dev_queues;
  /* data-affinity routing (reference: the owner_device/preferred_device
   * pass of parsec_get_best_device, device.c:100-117, which runs BEFORE
   * the load pass at :129-160): copy handle(uid) → packed
   * (qid<<32 | mirror version) of the device queue holding a current
   * mirror.  Maintained by the device layer (cache put / evict /
   * invalidate / copy death); read in execute_task's best-device pass.
   * A stale entry (version mismatch, or mirror evicted without the copy
   * dying) only costs a misroute — the consumer re-stages, exactly what
   * load-only routing would have done. */
  std::mutex owner_lock;
  std::unordered_map<int64_t, uint64_t> data_owner;
  /* spill guard: affinity yields when owner load > skew * best load
   * (<=0 disables the affinity pass).  MCA: device.affinity_skew. */
  std::atomic<double> affinity_skew{4.0};
  std::mutex reg_lock;

  uint32_t myrank = 0, nodes = 1;
  /* activation-broadcast topology: 0 star (direct sends), 1 chain,
   * 2 binomial (reference: runtime_comm_coll_bcast, remote_dep.c:39-47) */
  std::atomic<int32_t> comm_topo{0};

  /* ptc-topo rank remap (plan.remap_ranks / Taskpool.run(remap=)): a
   * permutation applied to EVERY ptc_collection_rank_of result, so task
   * affinity, successor placement, mem owners and the startup filter
   * move together — a pure relabeling of which physical rank plays
   * which logical role.  Published by atomic pointer swap; replaced
   * maps are retired (not freed) until context destroy so a concurrent
   * reader can never touch freed memory.  Rank_of is evaluated lazily
   * at/after pool startup, so setting the map between taskpool build
   * and run re-places the whole pool. */
  struct RankMap { std::vector<int32_t> map; };
  std::atomic<RankMap *> rank_map{nullptr};
  std::vector<RankMap *> rank_maps_retired; /* under reg_lock */

  /* runtime-native collective counters (ptc_coll_stats): steps = executed
   * ptc_coll_* task bodies; send/recv = cross-rank activation frames
   * whose (first) target is a ptc_coll_* class, with their payload bytes.
   * The Python coll layer adds op-level counters on top. */
  std::atomic<int64_t> coll_steps{0};
  std::atomic<int64_t> coll_send_msgs{0}, coll_send_bytes{0};
  std::atomic<int64_t> coll_recv_msgs{0}, coll_recv_bytes{0};

  /* active taskpools */
  std::atomic<int64_t> active_tps{0};
  ptc_mutex wait_lock;
  ptc_condvar wait_cv;

  /* distributed taskpool registry (id → pool) + parked early activations */
  std::mutex tp_reg_lock;
  int32_t next_tp_id = 0;
  std::unordered_map<int32_t, ptc_taskpool *> tp_registry;
  std::unordered_map<int32_t, std::vector<std::vector<uint8_t>>> tp_early;

  /* task freelist (mempool stand-in; reference parsec/mempool.c).
   * free_lock/free_list is the SHARED spill pool; each worker owns a
   * magazine (task_mags[w], owner-thread only) that refills from and
   * flushes to it in mag_batch-sized moves, so the steady-state
   * task alloc/free pair on a worker never takes free_lock.
   * mag_batch is read once from PTC_MCA_runtime_mag_batch at context
   * creation (immutable afterwards) — the ptc-tune knob. */
  int32_t mag_batch = PTC_MAG_BATCH_DEFAULT;
  std::mutex free_lock;
  ptc_task *free_list = nullptr;
  struct alignas(64) TaskMag {
    ptc_task *head = nullptr;
    int32_t count = 0;
    std::atomic<int64_t> hits{0}, misses{0}; /* single-writer relaxed */
  };
  std::vector<TaskMag *> task_mags; /* one per worker */
  std::atomic<int64_t> free_ext_hits{0}, free_ext_misses{0};

  /* same-worker ready-task bypass knob (PTC_MCA_sched_bypass /
   * ptc_context_set_sched_bypass; reference: keep_highest_priority_task,
   * parsec/scheduling.c:373-396).  worker_bypass[w] counts tasks worker
   * w executed straight from its thread-local slot — the proof the
   * schedule()+select() round trip was skipped. */
  std::atomic<bool> sched_bypass{true};
  /* per-pool QoS wave-boundary preemption (PTC_MCA_sched_qos_preempt /
   * ptc_context_set_qos_preempt): copied into the scheduler at install;
   * kept here too so pre-start sets survive.  Default on. */
  std::atomic<bool> qos_preempt{true};
  std::vector<std::atomic<int64_t> *> worker_bypass;
  /* tasks handed to the scheduler (ptc_schedule_task, bypass slot
   * included) and not yet back from execute_task, whatever its outcome
   * (ptc_tasks_in_flight), as two monotonic counts.  A worker counts
   * what it schedules and what it finishes in its own line, single
   * writer, so the hot path shares no line between workers; main, comm
   * and device-manager threads schedule through flight_ext.  A task's
   * successors are scheduled inside its complete_task, before its
   * worker counts it done: the difference reads 0 only with no release
   * chain alive. */
  struct alignas(64) FlightShard {
    std::atomic<int64_t> scheduled{0}, done{0};
  };
  std::vector<FlightShard *> flight; /* one per worker */
  FlightShard flight_ext;            /* .scheduled only */

  /* batched DTD insertion accounting (ptc_dtask_insert_batch) */
  std::atomic<int64_t> insert_batches{0};
  std::atomic<int64_t> insert_batched_tasks{0};

  /* device-layer hook: copy with handle released */
  ptc_copy_release_cb copy_release_cb = nullptr;
  void *copy_release_user = nullptr;

  /* device-layer hook: host bytes of a device-touched copy are about to be
   * read (comm serialization / collection memcpy) — the device module
   * writes back its dirty mirror so the host never reads stale memory
   * (reference: the CUDA epilog's OWNED→SHARED coherency flip,
   * device_cuda_module.c:2365-2420, made lazy + pull-based here) */
  ptc_copy_sync_cb copy_sync_cb = nullptr;
  void *copy_sync_user = nullptr;

  /* device-layer hook: host bytes of a device-touched copy were just
   * OVERWRITTEN by the runtime (collection write-back memcpy, remote
   * PUT) — the device module drops its now-stale mirror so a later
   * flush cannot write old device bytes over the newer host state */
  ptc_copy_invalidate_cb copy_invalidate_cb = nullptr;
  void *copy_invalidate_user = nullptr;

  /* device data plane (ICI seam; see parsec_core.h) */
  ptc_dp_register_cb dp_register = nullptr;
  ptc_dp_serve_cb dp_serve = nullptr;
  ptc_dp_serve_done_cb dp_serve_done = nullptr;
  ptc_dp_deliver_cb dp_deliver = nullptr;
  ptc_dp_bound_cb dp_bound = nullptr;
  /* progressive-serve offer (wire v4 streaming; see parsec_core.h) */
  ptc_dp_serve_stream_cb dp_serve_stream = nullptr;
  void *dp_user = nullptr;
  /* this rank's transfer-plane pull capability, stamped on GET frames */
  std::atomic<int32_t> dp_can_pull{0};

  /* profiling */
  std::atomic<int32_t> prof_level{0}; /* 0 off, 1 spans, 2 +edges */
  std::vector<ProfBuf *> prof;
  /* flight recorder: per-worker ring cap in bytes (0 = unbounded
   * buffers; PTC_MCA_runtime_trace_ring) and the dump-path prefix the
   * autodump writes "<prefix>.<rank>.ptt" to on taskpool abort / peer
   * loss (PTC_MCA_runtime_trace_dump; defaults to /tmp/ptc_flight when
   * ring mode is on) */
  std::atomic<int64_t> trace_ring_bytes{0};
  std::string flight_dump_path;
  std::atomic<bool> flight_dumped{false};
  /* PINS instrumentation sink (pins.h:26-54 analog; see pins_fire).
   * cb/user/mask live in one atomically-swapped block so a racing reader
   * can never pair an old callback with a new user pointer; retired
   * blocks are freed at context destroy (installs are rare). */
  struct PinsState { ptc_pins_cb cb; void *user; uint64_t mask; };
  std::atomic<PinsState *> pins_state{nullptr};
  std::vector<PinsState *> pins_retired;
  std::mutex pins_lock;
  /* per-worker selected-task counters (reference: the PAPI-SDE
   * scheduled/retired counters + per-thread rusage dumps,
   * parsec/scheduling.c:45-86,319-323) */
  std::vector<std::atomic<int64_t> *> worker_executed;
  /* vpmap (reference: parsec/vpmap.c virtual processes): vp id per
   * worker, set before start; empty = flat (single vp).  Consumed by
   * hierarchical schedulers (lhq steal order). */
  std::vector<int32_t> vp_of_worker;
  /* thread binding (hwloc analog): 0 = unbound, 1 = round-robin core
   * pinning; worker_cpu[w] = bound cpu id or -1 */
  int32_t bind_mode = 0;
  std::vector<std::atomic<int32_t> *> worker_cpu;
  /* per-subsystem debug verbosity (PTC_DBG_*; debug.c streams analog) */
  std::atomic<int32_t> verbose[PTC_DBG_NSUBSYS] = {};

  /* communication engine (nullptr when single-process) */
  CommEngine *comm = nullptr;

  /* local-reshape accounting (avoidable-reshape tests assert on these:
   * conversions = futures triggered, hits = memoized/identity reuses) */
  std::atomic<int64_t> reshape_conversions{0};
  std::atomic<int64_t> reshape_hits{0};

  /* always-on runtime metrics (PTC_MCA_runtime_metrics, default on):
   * per-worker log2-bucket latency histograms + the watchdog's inflight
   * slots.  met_workers has nb_workers + 1 entries; the last is the
   * shared slot for external threads (comm, device managers, main). */
  std::atomic<bool> metrics_on{true};
  /* release 1-in-N sampling as a power-of-two mask (N-1): the per-task
   * sampler is one fetch_add + AND — an integer modulo here costs more
   * than everything else in the level-0 metrics path combined */
  std::atomic<int32_t> met_rel_mask{63};
  std::vector<MetWorker *> met_workers;
  std::mutex met_lock; /* interning + peer snapshots */
  std::vector<std::string> met_names; /* mid -> class name */
  std::unordered_map<std::string, int32_t> met_ids;
  int32_t met_dtd_mid = -1; /* all DTD bodies share one class bucket */
  std::map<uint32_t, MetRemote> met_peers; /* rank 0: latest per peer */

  ~ptc_context();
};

/* ------------------------------------------------------------------ */
/* runtime internals shared across translation units                   */
/* ------------------------------------------------------------------ */

int64_t ptc_now_ns();

int64_t ptc_eval_expr(const Expr &e, ptc_context *ctx, const int64_t *locals,
                      int nb_locals, const int64_t *globals,
                      int64_t empty_value = 0);

void ptc_copy_retain(ptc_copy *c);
void ptc_copy_release_internal(ptc_context *ctx, ptc_copy *c);

/* The reshaped view of `src` through local datatype `ltype_id`
 * (reference: parsec_reshape.c reshape promises).  Returns `src` itself
 * when the type is the identity for this copy or the copy is already
 * shaped as the type; otherwise the memoized per-(copy, type, version)
 * converted child — created (and counted as a conversion) at most once.
 * The returned pointer is RETAINED (under the cache lock, so a racing
 * stale-version eviction cannot free it first): the caller owns one ref
 * and must release it after staging. */
ptc_copy *ptc_reshape_get(ptc_context *ctx, ptc_copy *src, int32_t ltype_id);

/* selective write-back of `src` into `dst` through a datatype: segments
 * copy only their byte ranges; cast types reverse-convert (the copy
 * holds dst_kind elements, the collection tile holds src_kind).  A
 * ltype < 0 (or unknown) falls back to a full memcpy. */
void ptc_typed_writeback(ptc_context *ctx, int32_t ltype_id, ptc_copy *src,
                         void *dst, int64_t dst_size);

/* element-cast primitives (PTC_ELEM_*; shared by the reshape engine and
 * the comm layer's pack/scatter) */
int64_t ptc_elem_size_of(int32_t kind);
bool ptc_convert_elems(int32_t src_kind, int32_t dst_kind, const void *src,
                       void *dst, int64_t n);

ptc_data *ptc_collection_data_of(ptc_context *ctx, int32_t dc_id,
                                 const int64_t *idx, int32_t n);
uint32_t ptc_collection_rank_of(ptc_context *ctx, int32_t dc_id,
                                const int64_t *idx, int32_t n);

/* schedule a ready task (wakes idle workers) */
void ptc_schedule_task(ptc_context *ctx, int worker, ptc_task *t);

/* abort a taskpool (body-error semantics: successors withheld, waiters
 * observe the error) — used by the comm layer for undeliverable by-ref
 * payloads */
void ptc_tp_abort_internal(ptc_context *ctx, ptc_taskpool *tp);

/* trace push (core.cpp): event = (key, phase, class, l0, l1, worker,
 * aux, t_ns); no-op unless profiling >= min_level (PINS callbacks fire
 * regardless of trace level — their mask is the gate).  RELEASE spans
 * ride min_level 2 so level-1 tracing keeps the dispatch path to two
 * locked pushes per task (the sp-perf lean-trace setting). */
void ptc_prof_push(ptc_context *ctx, int worker, int64_t key, int64_t phase,
                   int64_t class_id, int64_t l0, int64_t l1, int64_t aux,
                   int32_t min_level = 1);
/* instant span: begin+end with the SAME timestamp, one lock (comm thread
 * events; buffer 0 is shared with worker 0) */
void ptc_prof_instant(ptc_context *ctx, int64_t key, int64_t class_id,
                      int64_t l0, int64_t l1, int64_t aux);

/* always-on metrics internals (core.cpp).  ptc_met_intern returns -1
 * past PTC_MET_MAX_CLASSES; ptc_met_record routes (kind, mid) to the
 * calling worker's histogram set (worker < 0 or >= nb_workers = the
 * external slot).  serialize/absorb carry the fence-time rank-wide
 * merge: serialize writes this rank's aggregated records in the wire
 * form MSG_METRICS ships, absorb parses a peer's frame into
 * ctx->met_peers (rank 0). */
int32_t ptc_met_intern(ptc_context *ctx, const std::string &name);
MetWorker *ptc_met_worker(ptc_context *ctx, int worker);
void ptc_met_record(ptc_context *ctx, int worker, int kind, int32_t mid,
                    int64_t ns);
void ptc_met_serialize(ptc_context *ctx, std::vector<uint8_t> &out);
void ptc_met_absorb(ptc_context *ctx, uint32_t from, int64_t rtt_ns,
                    int64_t offset_ns, const uint8_t *body, size_t len);

/* flight-recorder autodump: writes the current (ring) trace contents to
 * "<flight_dump_path>.<rank>.ptt" at most once per context — called on
 * taskpool abort (core.cpp) and peer loss (comm.cpp) so production
 * failures always leave a last-N-seconds trace behind. */
void ptc_flight_autodump(ptc_context *ctx, const char *reason);

/* crash-path hook (core.cpp): when ptc_crash_arm has armed this context
 * and the crash file has not fired yet, write the crash-format dump
 * (ring tail + inflight-slot snapshot) to the armed path.  Called from
 * ptc_flight_autodump so peer-loss reaping on survivors leaves the same
 * artifact a fatal signal would. */
void ptc_crash_dump_if_armed(ptc_context *ctx);

/* deliver one dependency release to a local successor instance (the
 * incoming half of the remote ACTIVATE path calls this).
 * domain_checked = true skips the re-validation when the caller (the
 * local release path) already ran task_params_in_domain — wire arrivals
 * must leave it false (defense against malformed frames). */
void ptc_deliver_dep_local(ptc_context *ctx, int worker, ptc_taskpool *tp,
                           int32_t class_id, std::vector<int64_t> &&params,
                           int32_t flow_idx, ptc_copy *copy,
                           bool domain_checked = false);

/* the selecting IN dep's wire datatype for one consumer instance, or -1
 * (guard/domain-aware; comm receive-side scatter) */
int32_t ptc_consumer_recv_dtype(ptc_context *ctx, ptc_taskpool *tp,
                                int32_t class_id,
                                const std::vector<int64_t> &params,
                                int32_t flow_idx);

/* copy a datatype definition out under reg_lock (registration may
 * reallocate the vector concurrently); false when id is invalid */
bool ptc_dtype_get(ptc_context *ctx, int32_t id, DtypeDef *out);
/* true when any datatype is registered (cheap comm-path early-out) */
bool ptc_has_dtypes(ptc_context *ctx);

/* DTD: complete a shadow task whose remote original finished; `payload`
 * holds the serialized written-tile contents (comm.cpp framing:
 * [u32 flow][u64 len][bytes]*) */
void ptc_dtd_shadow_ready(ptc_context *ctx, ptc_taskpool *tp, uint64_t seq,
                          const uint8_t *payload, size_t len);
/* apply a completion payload to a known shadow task + drop its message hold */
void ptc_dtd_apply_complete(ptc_context *ctx, ptc_task *t,
                            const uint8_t *payload, size_t len);

/* ------------------------------------------------------------------ */
/* comm engine hooks (implemented in comm.cpp; safe no-ops when
 * ctx->comm == nullptr)                                               */
/* ------------------------------------------------------------------ */

/* outgoing PTG activation: deliver (class_id, params, flow, copy bytes) to
 * `rank`'s matching taskpool.  send_dtype >= 0 packs the producer copy's
 * strided layout (ctx->dtypes[send_dtype]) to contiguous wire bytes. */
void ptc_comm_send_activate(ptc_context *ctx, uint32_t rank, ptc_taskpool *tp,
                            int32_t class_id,
                            const std::vector<int64_t> &params,
                            int32_t flow_idx, ptc_copy *copy,
                            int32_t send_dtype = -1);

/* batched form: several successor instances sharing one payload copy
 * (reference: per-rank output bitmaps, parsec/remote_dep.h:143-177) */
/* one rank's targets within an activation broadcast */
struct PtcBcastRankGroup {
  uint32_t rank;
  std::vector<std::pair<int32_t, std::vector<int64_t>>> targets;
};
/* propagate one output copy's activations to several ranks along the
 * chain/binomial topology (topo 1/2); caller keeps ownership of copy */
void ptc_comm_send_activate_bcast(ptc_context *ctx, ptc_taskpool *tp,
                                  int32_t flow_idx, ptc_copy *copy,
                                  int32_t topo,
                                  std::vector<PtcBcastRankGroup> &&groups,
                                  int32_t send_dtype = -1);

void ptc_comm_send_activate_batch(
    ptc_context *ctx, uint32_t rank, ptc_taskpool *tp, int32_t flow_idx,
    ptc_copy *copy,
    const std::vector<std::pair<int32_t, std::vector<int64_t>>> &targets,
    int32_t send_dtype = -1);

/* replay activations that arrived before `tp` was registered locally */
void ptc_comm_drain_early(ptc_context *ctx, ptc_taskpool *tp);

/* stop the comm thread + close sockets (idempotent; no-op if never up) */
void ptc_comm_shutdown(ptc_context *ctx);

/* coherence pull before reading a copy's host bytes (core.cpp; see
 * ptc_set_copy_sync_cb) — safe from any thread, no-op without a handle.
 * (extern "C": defined inside core.cpp's public-API linkage block) */
extern "C" void ptc_copy_sync_for_host(ptc_context *ctx, ptc_copy *c);

/* stale-mirror drop after the runtime overwrote a copy's host bytes
 * (core.cpp; see ptc_set_copy_invalidate_cb) — safe from any thread,
 * no-op without a handle */
extern "C" void ptc_copy_host_written(ptc_context *ctx, ptc_copy *c);

/* outgoing memory write-back to a collection datum owned by `rank`.
 * ltype >= 0: selective write-back — the receiver applies only the
 * byte ranges (or reverse element cast) the datatype selects (SPMD
 * registration order makes the id meaningful on both sides). */
void ptc_comm_send_put_mem(ptc_context *ctx, uint32_t rank, int32_t dc_id,
                           const int64_t *idx, int32_t nidx, ptc_copy *copy,
                           int32_t ltype = -1);

/* outgoing DTD completion broadcast (real task finished; shadows on every
 * other rank release their successors + apply written-tile payloads).
 * Written flows at or under the eager limit ride inline
 * ([u32 flow][u64 len][bytes]); larger ones ship a size-only marker
 * ([u32 flow|MARKER][u64 len]) and consumers pull on demand. */
void ptc_comm_send_dtd_complete(ptc_context *ctx, ptc_taskpool *tp,
                                ptc_task *t);

/* marker bit in a DTD completion record's flow word */
constexpr uint32_t PTC_DTD_REC_MARKER = 0x80000000u;

/* pull one marked flow's bytes from the rank that ran writer `seq` */
void ptc_comm_send_dtd_fetch(ptc_context *ctx, uint32_t rank, int32_t tp_id,
                             uint64_t seq, int32_t flow);

/* requester side: fetched bytes landed (comm.cpp → core.cpp) */
void ptc_dtd_fetch_data(ptc_context *ctx, ptc_taskpool *tp, uint64_t seq,
                        int32_t flow, const uint8_t *payload, size_t len);

/* retire the pull-server entry a tile holds (next-writer completion or
 * teardown); caller must hold tp->dtd_lock */
void ptc_dtd_retire_served_locked(ptc_context *ctx, ptc_taskpool *tp,
                                  ptc_dtile *tile);

#endif /* PTC_RUNTIME_INTERNAL_H */
