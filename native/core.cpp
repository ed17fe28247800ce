/* core.cpp — native runtime core of tpu-parsec.
 *
 * Implements the C API in parsec_core.h (structs in runtime_internal.h):
 *   - expression VM (guards / ranges / indices / priorities as bytecode)
 *   - table-driven task classes (the interpreter replacing the reference's
 *     jdf2c code generator, parsec/interfaces/ptg/ptg-compiler/jdf2c.c)
 *   - sharded dependency table (reference: hash dep tracking,
 *     parsec/parsec_internal.h:224-229 + parsec.c release path)
 *   - ready-task schedulers (reference parsec/mca/sched)
 *   - worker threads + chore execution protocol (reference
 *     parsec/scheduling.c:124-203, 470-531)
 *   - local termination detection (counter; reference mca/termdet/local)
 *   - device queues: the ASYNC seam the (Python/JAX) TPU device manager
 *     drains (reference: CUDA manager thread, device_cuda_module.c:2537+)
 *   - minimal paired-event profiling buffers (reference: parsec/profiling.c)
 *
 * Remote successors are handed to the comm engine (comm.cpp) as batched
 * ACTIVATE sends; incoming activations re-enter through
 * ptc_deliver_dep_local.  (Reference: parsec/remote_dep.c:454 activation
 * fan-out + remote_dep_mpi.c incoming path — SURVEY.md §3.3.)
 *
 * Design note: behavior follows SURVEY.md §2/§3; the implementation is new
 * and intentionally different from the reference (interpreted specs instead
 * of generated C; push-based data delivery into successor dep entries
 * instead of repo lookups at prepare_input).
 */

#include "runtime_internal.h"

#include <algorithm>
#include <chrono>
#include <cstdio>
#if defined(__linux__)
#include <pthread.h>
#include <sched.h>
#endif
#include <cstdlib>
#include <cstring>
#include <csignal>
#include <fcntl.h>
#include <unistd.h>
#if defined(__x86_64__)
#include <x86intrin.h>
#endif

static inline int64_t chrono_now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

/* Trace timestamp source.  steady_clock::now costs ~33 ns/call on the
 * measurement host — one call per task at trace level 1 — so on x86-64
 * the hot path reads the invariant TSC (~8 ns) and converts through a
 * rate calibrated once per process against steady_clock (two ~5 ms
 * windows; if they disagree > 1% — non-invariant TSC, paused VM — the
 * chrono path is kept).  Timestamps stay on the steady_clock epoch, so
 * traces mix freely with pre-calibration events.  Each end of a window
 * is a steady_clock reading bracketed by two TSC reads, after one
 * untimed call: the process's first steady_clock call (lazy symbol
 * binding) runs microseconds long, and inside a 1 ms window that alone
 * set the rate 0.3-2% slow, so traces drifted that much from every
 * other clock. */
int64_t ptc_now_ns() {
#if defined(__x86_64__)
  struct Calib {
    double ns_per_tick = 0.0;
    int64_t base_ns = 0;
    uint64_t base_tsc = 0;
    bool ok = false;
    /* (TSC at a steady_clock reading, the reading): the narrowest
     * bracket of a few tries, so a preemption mid-read is not kept */
    static void sample(uint64_t &tsc, int64_t &ns) {
      uint64_t width = ~0ull;
      for (int i = 0; i < 8; i++) {
        uint64_t a = __rdtsc();
        int64_t n = chrono_now_ns();
        uint64_t b = __rdtsc();
        if (b - a < width) {
          width = b - a;
          tsc = a + width / 2;
          ns = n;
        }
      }
    }
    Calib() {
      const int64_t window_ns = 5000000;
      uint64_t c0, c1, c2;
      int64_t n0, n1, n2;
      (void)chrono_now_ns();
      sample(c0, n0);
      while (chrono_now_ns() - n0 < window_ns) { /* spin */ }
      sample(c1, n1);
      while (chrono_now_ns() - n1 < window_ns) { }
      sample(c2, n2);
      if (c1 == c0 || c2 == c1) return;
      double r1 = (double)(n1 - n0) / (double)(c1 - c0);
      double r2 = (double)(n2 - n1) / (double)(c2 - c1);
      if (r1 <= 0.0 || r2 <= 0.0 || r1 / r2 > 1.01 || r2 / r1 > 1.01)
        return;
      ns_per_tick = (double)(n2 - n0) / (double)(c2 - c0);
      base_ns = n2;
      base_tsc = c2;
      ok = true;
    }
  };
  static const Calib cal; /* magic-static: one calibration per process */
  if (cal.ok)
    return cal.base_ns +
           (int64_t)((double)(__rdtsc() - cal.base_tsc) * cal.ns_per_tick);
#endif
  return chrono_now_ns();
}

/* ------------------------------------------------------------------ */
/* worker-thread identity (magazine routing)                           */
/* ------------------------------------------------------------------ */

/* Which context's worker thread is this?  Set once in worker_main.
 * Non-worker threads (main, comm, device managers) and workers of OTHER
 * contexts in the same process resolve to slot -1 and take the locked
 * shared paths — magazines are touched only by their owning thread. */
static thread_local ptc_context *tl_mag_ctx = nullptr;
static thread_local int tl_mag_worker = -1;

static inline int mag_slot(ptc_context *ctx) {
  return tl_mag_ctx == ctx ? tl_mag_worker : -1;
}

/* single-writer counter bump: relaxed load+store (plain add codegen,
 * no lock prefix) — TSan-visible for the cross-thread stats read */
static inline void tick1(std::atomic<int64_t> &c) {
  c.store(c.load(std::memory_order_relaxed) + 1, std::memory_order_relaxed);
}

/* ------------------------------------------------------------------ */
/* arena                                                               */
/* ------------------------------------------------------------------ */

void Arena::init_mags(int32_t n) {
  nb_mags = n > 0 ? n : 0;
  if (nb_mags) mags.reset(new Mag[(size_t)nb_mags]);
}

void *Arena::alloc(int32_t slot) {
  if (slot >= 0 && slot < nb_mags) {
    Mag &m = mags[(size_t)slot];
    if (m.items.empty()) {
      /* refill: up to a batch from the shared pool, ONE lock */
      std::lock_guard<std::mutex> g(lock);
      int take = (int)std::min<size_t>(freelist.size(), (size_t)mag_batch);
      if (take > 0) {
        m.items.insert(m.items.end(), freelist.end() - take,
                       freelist.end());
        freelist.resize(freelist.size() - (size_t)take);
      }
    }
    if (!m.items.empty()) {
      void *p = m.items.back();
      m.items.pop_back();
      tick1(m.hits);
      return p;
    }
    tick1(m.misses);
    return std::malloc((size_t)elem_size);
  }
  {
    std::lock_guard<std::mutex> g(lock);
    if (!freelist.empty()) {
      void *p = freelist.back();
      freelist.pop_back();
      ext_hits.fetch_add(1, std::memory_order_relaxed);
      return p;
    }
  }
  ext_misses.fetch_add(1, std::memory_order_relaxed);
  return std::malloc((size_t)elem_size);
}

void Arena::dealloc(int32_t slot, void *p) {
  if (slot >= 0 && slot < nb_mags) {
    Mag &m = mags[(size_t)slot];
    m.items.push_back(p);
    if (m.items.size() >= 2 * (size_t)mag_batch) {
      /* spill one batch back so idle workers don't hoard blocks */
      std::lock_guard<std::mutex> g(lock);
      freelist.insert(freelist.end(), m.items.end() - mag_batch,
                      m.items.end());
      m.items.resize(m.items.size() - (size_t)mag_batch);
    }
    return;
  }
  std::lock_guard<std::mutex> g(lock);
  freelist.push_back(p);
}

int64_t Arena::stat_hits() const {
  int64_t s = ext_hits.load(std::memory_order_relaxed);
  for (int32_t i = 0; i < nb_mags; i++)
    s += mags[(size_t)i].hits.load(std::memory_order_relaxed);
  return s;
}

int64_t Arena::stat_misses() const {
  int64_t s = ext_misses.load(std::memory_order_relaxed);
  for (int32_t i = 0; i < nb_mags; i++)
    s += mags[(size_t)i].misses.load(std::memory_order_relaxed);
  return s;
}

Arena::~Arena() {
  for (void *p : freelist) std::free(p);
  for (int32_t i = 0; i < nb_mags; i++)
    for (void *p : mags[(size_t)i].items) std::free(p);
}

/* ------------------------------------------------------------------ */
/* context teardown                                                    */
/* ------------------------------------------------------------------ */

ptc_context::~ptc_context() {
  {
    Collection **t = collections.tab.load(std::memory_order_relaxed);
    int32_t n = collections.count.load(std::memory_order_relaxed);
    for (int32_t i = 0; i < n; i++) delete t[i];
  }
  {
    Arena **t = arena_tab.load(std::memory_order_relaxed);
    int32_t n = arena_count.load(std::memory_order_relaxed);
    for (int32_t i = 0; i < n; i++) delete t[i];
    for (Arena **tt : arena_tables) delete[] tt;
  }
  for (auto *q : dev_queues) delete q;
  for (auto *p : prof) delete p;
  for (auto *m : met_workers) delete m;
  for (auto *c : worker_executed) delete c;
  for (auto *c : worker_cpu) delete c;
  for (auto *c : worker_bypass) delete c;
  for (auto *s : flight) delete s;
  delete sched;
  ptc_task *t = free_list;
  while (t) {
    ptc_task *n = t->next;
    delete t;
    t = n;
  }
  for (TaskMag *m : task_mags) {
    ptc_task *mt = m ? m->head : nullptr;
    while (mt) {
      ptc_task *n = mt->next;
      delete mt;
      mt = n;
    }
    delete m;
  }
}

/* ------------------------------------------------------------------ */
/* expression evaluation                                               */
/* ------------------------------------------------------------------ */

namespace {

/* fast-form operand fetch (kinds: 1 imm, 2 local, 3 global) */
static inline int64_t fast_atom(int8_t kind, int64_t v,
                                const int64_t *locals,
                                const int64_t *globals) {
  if (kind == 2) return locals[v];
  if (kind == 3) return globals[v];
  return v;
}

} // namespace

void ptc_expr_finalize(Expr &e) {
  const std::vector<int64_t> &c = e.code;
  auto atom_of = [](int64_t op) -> int8_t {
    switch (op) {
    case PTC_OP_IMM: return 1;
    case PTC_OP_LOCAL: return 2;
    case PTC_OP_GLOBAL: return 3;
    default: return 0;
    }
  };
  auto binop_ok = [](int64_t op) {
    switch (op) {
    case PTC_OP_ADD: case PTC_OP_SUB: case PTC_OP_MUL: case PTC_OP_DIV:
    case PTC_OP_MOD: case PTC_OP_EQ: case PTC_OP_NE: case PTC_OP_LT:
    case PTC_OP_LE: case PTC_OP_GT: case PTC_OP_GE: case PTC_OP_AND:
    case PTC_OP_OR: case PTC_OP_MIN: case PTC_OP_MAX: case PTC_OP_SHL:
    case PTC_OP_SHR:
      return true;
    default:
      return false;
    }
  };
  e.fast_op = 0;
  if (c.size() == 2 && atom_of(c[0])) {
    e.fast_op = 1;
    e.fa_kind = atom_of(c[0]);
    e.fa = c[1];
  } else if (c.size() == 5 && atom_of(c[0]) && atom_of(c[2]) &&
             binop_ok(c[4])) {
    e.fast_op = (int8_t)c[4];
    e.fa_kind = atom_of(c[0]);
    e.fa = c[1];
    e.fb_kind = atom_of(c[2]);
    e.fb = c[3];
  }
}

int64_t ptc_eval_expr(const Expr &e, ptc_context *ctx, const int64_t *locals,
                      int nb_locals, const int64_t *globals,
                      int64_t empty_value) {
  if (e.empty()) return empty_value;
  if (e.fast_op) {
    int64_t a = fast_atom(e.fa_kind, e.fa, locals, globals);
    if (e.fast_op == 1) return a;
    int64_t b = fast_atom(e.fb_kind, e.fb, locals, globals);
    switch (e.fast_op) {
    case PTC_OP_ADD: return a + b;
    case PTC_OP_SUB: return a - b;
    case PTC_OP_MUL: return a * b;
    case PTC_OP_DIV: return b ? a / b : 0;
    case PTC_OP_MOD: return b ? a % b : 0;
    case PTC_OP_EQ: return a == b;
    case PTC_OP_NE: return a != b;
    case PTC_OP_LT: return a < b;
    case PTC_OP_LE: return a <= b;
    case PTC_OP_GT: return a > b;
    case PTC_OP_GE: return a >= b;
    case PTC_OP_AND: return a && b;
    case PTC_OP_OR: return a || b;
    case PTC_OP_MIN: return a < b ? a : b;
    case PTC_OP_MAX: return a > b ? a : b;
    case PTC_OP_SHL:
      return (int64_t)((uint64_t)a
                       << std::min<int64_t>(std::max<int64_t>(b, 0), 62));
    case PTC_OP_SHR:
      return a >> std::min<int64_t>(std::max<int64_t>(b, 0), 62);
    default: break; /* unreachable (binop_ok-filtered) */
    }
  }
  constexpr int STACK_MAX = 64;
  int64_t stack[STACK_MAX];
  int sp = 0;
  const auto &c = e.code;
  size_t n = c.size();
  for (size_t i = 0; i < n; i++) {
    if (sp >= STACK_MAX - 1) { /* pushes below stay in bounds */
      std::fprintf(stderr, "ptc: expression stack overflow (depth>%d)\n",
                   STACK_MAX);
      return 0;
    }
    switch (c[i]) {
    case PTC_OP_IMM: stack[sp++] = c[++i]; break;
    case PTC_OP_LOCAL: stack[sp++] = locals[c[++i]]; break;
    case PTC_OP_GLOBAL: stack[sp++] = globals[c[++i]]; break;
    case PTC_OP_ADD: sp--; stack[sp - 1] += stack[sp]; break;
    case PTC_OP_SUB: sp--; stack[sp - 1] -= stack[sp]; break;
    case PTC_OP_MUL: sp--; stack[sp - 1] *= stack[sp]; break;
    case PTC_OP_DIV: sp--; stack[sp - 1] = stack[sp] ? stack[sp - 1] / stack[sp] : 0; break;
    case PTC_OP_MOD: sp--; stack[sp - 1] = stack[sp] ? stack[sp - 1] % stack[sp] : 0; break;
    case PTC_OP_NEG: stack[sp - 1] = -stack[sp - 1]; break;
    case PTC_OP_EQ: sp--; stack[sp - 1] = stack[sp - 1] == stack[sp]; break;
    case PTC_OP_NE: sp--; stack[sp - 1] = stack[sp - 1] != stack[sp]; break;
    case PTC_OP_LT: sp--; stack[sp - 1] = stack[sp - 1] < stack[sp]; break;
    case PTC_OP_LE: sp--; stack[sp - 1] = stack[sp - 1] <= stack[sp]; break;
    case PTC_OP_GT: sp--; stack[sp - 1] = stack[sp - 1] > stack[sp]; break;
    case PTC_OP_GE: sp--; stack[sp - 1] = stack[sp - 1] >= stack[sp]; break;
    case PTC_OP_AND: sp--; stack[sp - 1] = stack[sp - 1] && stack[sp]; break;
    case PTC_OP_OR: sp--; stack[sp - 1] = stack[sp - 1] || stack[sp]; break;
    case PTC_OP_NOT: stack[sp - 1] = !stack[sp - 1]; break;
    case PTC_OP_SELECT: {
      int64_t b = stack[--sp], a = stack[--sp], cnd = stack[--sp];
      stack[sp++] = cnd ? a : b;
      break;
    }
    case PTC_OP_SHL: sp--; stack[sp - 1] = (int64_t)((uint64_t)stack[sp - 1] << std::min<int64_t>(std::max<int64_t>(stack[sp], 0), 62)); break;
    case PTC_OP_SHR: sp--; stack[sp - 1] = stack[sp - 1] >> std::min<int64_t>(std::max<int64_t>(stack[sp], 0), 62); break; /* arithmetic on gcc/clang */
    case PTC_OP_MIN: sp--; stack[sp - 1] = std::min(stack[sp - 1], stack[sp]); break;
    case PTC_OP_MAX: sp--; stack[sp - 1] = std::max(stack[sp - 1], stack[sp]); break;
    case PTC_OP_CALL: {
      int64_t id = c[++i];
      const ExprCb &cb = ctx->expr_cbs[(size_t)id];
      stack[sp++] = cb.fn(cb.user, locals, nb_locals, globals);
      break;
    }
    default:
      std::fprintf(stderr, "ptc: bad opcode %lld\n", (long long)c[i]);
      return 0;
    }
  }
  return sp > 0 ? stack[sp - 1] : 0;
}

namespace {

static inline int64_t eval_expr(const Expr &e, ptc_context *ctx,
                                const int64_t *locals, int nb_locals,
                                const int64_t *globals, int64_t ev = 0) {
  return ptc_eval_expr(e, ctx, locals, nb_locals, globals, ev);
}

static inline bool eval_guard(const Expr &e, ptc_context *ctx,
                              const int64_t *locals, int nb_locals,
                              const int64_t *globals) {
  return ptc_eval_expr(e, ctx, locals, nb_locals, globals, /*empty=*/1) != 0;
}

} // namespace

uint64_t ptc_fnv_hash(int32_t class_id, const std::vector<int64_t> &params) {
  /* PTC_DEBUG_WEAK_HASH collapses the hash space to 8 values: every dep
   * key collides, proving promotion/duplicate logic never depends on hash
   * uniqueness (PARANOID-style sanitizer mode, SURVEY §5).  Checked once. */
  static const bool weak = [] {
    const char *e = std::getenv("PTC_DEBUG_WEAK_HASH");
    return e && *e && *e != '0';
  }();
  uint64_t h = 1469598103934665603ull;
  auto mix = [&](int64_t v) {
    for (int i = 0; i < 8; i++) {
      h ^= (uint64_t)(v >> (8 * i)) & 0xff;
      h *= 1099511628211ull;
    }
  };
  mix(class_id);
  for (int64_t p : params) mix(p);
  return weak ? (h & 7) : h;
}

/* ------------------------------------------------------------------ */
/* spec decoding                                                       */
/* ------------------------------------------------------------------ */

namespace {

static bool expr_has_call(const Expr &e); /* defined below */

struct SpecReader {
  const int64_t *p;
  const int64_t *end;
  bool ok = true;
  int64_t next() {
    if (p >= end) { ok = false; return 0; }
    return *p++;
  }
  Expr expr() {
    Expr e;
    int64_t n = next();
    if (n < 0 || n > 4096) { ok = false; return e; }
    e.code.reserve((size_t)n);
    for (int64_t i = 0; i < n && ok; i++) e.code.push_back(next());
    if (ok) ptc_expr_finalize(e);
    return e;
  }
};

static bool decode_class(TaskClass &tc, const int64_t *spec, int64_t len) {
  SpecReader r{spec, spec + len};
  int64_t version = r.next();
  /* v2 adds a wire-datatype id per dep after the arena slot;
   * v3 adds comprehension locals (kind 2) + per-dep iterator lists */
  if (version < 1 || version > 4) return false;
  int64_t nb_locals = r.next();
  if (nb_locals < 0 || nb_locals > PTC_MAX_LOCALS) return false;
  for (int64_t i = 0; i < nb_locals; i++) {
    Local l;
    int64_t kind = r.next();
    if (kind == 1 || kind == 2) {
      l.is_range = true;
      l.lo = r.expr();
      l.hi = r.expr();
      l.st = r.expr();
      if (kind == 2) {
        l.is_compr = true;
        l.value = r.expr();
      }
      tc.range_locals.push_back((int32_t)i);
    } else {
      l.value = r.expr();
      tc.has_derived = true;
    }
    tc.locals.push_back(std::move(l));
  }
  tc.aff_dc = (int32_t)r.next();
  int64_t nb_aff = r.next();
  for (int64_t i = 0; i < nb_aff; i++) tc.aff_idx.push_back(r.expr());
  tc.priority = r.expr();
  int64_t nb_flows = r.next();
  if (nb_flows < 0 || nb_flows > PTC_MAX_FLOWS) return false;
  for (int64_t f = 0; f < nb_flows; f++) {
    Flow fl;
    fl.flags = (int32_t)r.next();
    fl.arena_id = (int32_t)r.next();
    int64_t nb_deps = r.next();
    for (int64_t d = 0; d < nb_deps && r.ok; d++) {
      Dep dep;
      dep.direction = (int32_t)r.next();
      dep.guard = r.expr();
      dep.guard_dyn = expr_has_call(dep.guard);
      dep.kind = (int32_t)r.next();
      if (dep.kind == DEP_TASK) {
        dep.peer_class = (int32_t)r.next();
        dep.peer_flow = (int32_t)r.next();
        int64_t np = r.next();
        for (int64_t k = 0; k < np && r.ok; k++) {
          DepParam pm;
          pm.is_range = r.next() != 0;
          if (pm.is_range) {
            pm.lo = r.expr();
            pm.hi = r.expr();
            pm.st = r.expr();
          } else {
            pm.value = r.expr();
          }
          dep.params.push_back(std::move(pm));
        }
      } else if (dep.kind == DEP_MEM) {
        dep.dc_id = (int32_t)r.next();
        int64_t ni = r.next();
        for (int64_t k = 0; k < ni && r.ok; k++) dep.idx.push_back(r.expr());
      }
      dep.arena_id = (int32_t)r.next();
      if (version >= 2) dep.dtype_id = (int32_t)r.next();
      if (version >= 3) {
        int64_t ni = r.next();
        if (ni < 0 || nb_locals + ni > PTC_MAX_LOCALS) return false;
        for (int64_t k = 0; k < ni && r.ok; k++) {
          DepIter di;
          di.lo = r.expr();
          di.hi = r.expr();
          di.st = r.expr();
          dep.iters.push_back(std::move(di));
        }
      }
      if (version >= 4) dep.ltype_id = (int32_t)r.next();
      if (dep.direction == 0) {
        if (dep.ltype_id >= 0) tc.has_in_ltype = true;
        fl.in_deps.push_back(std::move(dep));
      } else
        fl.out_deps.push_back(std::move(dep));
    }
    tc.flows.push_back(std::move(fl));
  }
  int64_t nb_chores = r.next();
  for (int64_t i = 0; i < nb_chores && r.ok; i++) {
    Chore ch;
    ch.device_type = (int32_t)r.next();
    ch.body_kind = (int32_t)r.next();
    ch.body_arg = r.next();
    tc.chores.push_back(ch);
  }
  return r.ok;
}

} // namespace

/* ------------------------------------------------------------------ */
/* data helpers                                                        */
/* ------------------------------------------------------------------ */

void ptc_copy_retain(ptc_copy *c) {
  if (c) c->refcount.fetch_add(1, std::memory_order_relaxed);
}

void ptc_copy_release_internal(ptc_context *ctx, ptc_copy *c) {
  if (!c) return;
  if (c->refcount.fetch_sub(1, std::memory_order_acq_rel) == 1) {
    if (c->handle != 0 && ctx->copy_release_cb)
      ctx->copy_release_cb(ctx->copy_release_user, c->handle);
    /* drop the memoized reshape children (each holds one cache ref);
     * consumers still running hold their own refs */
    ReshapeCache *rc = c->reshape.load(std::memory_order_acquire);
    if (rc) {
      for (ReshapeCache::Entry &e : rc->entries)
        ptc_copy_release_internal(ctx, e.shaped);
      delete rc;
    }
    if (c->arena_id >= 0 && c->ptr)
      ctx->arena_at(c->arena_id)->dealloc(mag_slot(ctx), c->ptr);
    else if (c->owns_ptr && c->ptr)
      std::free(c->ptr);
    delete c;
  }
}

namespace {
static inline void copy_retain(ptc_copy *c) { ptc_copy_retain(c); }
static inline void copy_release(ptc_context *ctx, ptc_copy *c) {
  ptc_copy_release_internal(ctx, c);
}

/* ---- local reshape (datacopy-future role; parsec_reshape.c) -------- */

template <typename S, typename D>
static void convert_loop(const void *src, void *dst, int64_t n) {
  const S *s = (const S *)src;
  D *d = (D *)dst;
  for (int64_t i = 0; i < n; i++) d[i] = (D)s[i];
}

template <typename S>
static bool convert_from(int32_t dk, const void *src, void *dst, int64_t n) {
  switch (dk) {
  case PTC_ELEM_F32: convert_loop<S, float>(src, dst, n); return true;
  case PTC_ELEM_F64: convert_loop<S, double>(src, dst, n); return true;
  case PTC_ELEM_I32: convert_loop<S, int32_t>(src, dst, n); return true;
  case PTC_ELEM_I64: convert_loop<S, int64_t>(src, dst, n); return true;
  case PTC_ELEM_U8: convert_loop<S, uint8_t>(src, dst, n); return true;
  default: return false;
  }
}

} // namespace

int64_t ptc_elem_size_of(int32_t kind) {
  switch (kind) {
  case PTC_ELEM_F32:
  case PTC_ELEM_I32:
    return 4;
  case PTC_ELEM_F64:
  case PTC_ELEM_I64:
    return 8;
  case PTC_ELEM_U8:
    return 1;
  default:
    return 0;
  }
}

bool ptc_convert_elems(int32_t sk, int32_t dk, const void *src, void *dst,
                       int64_t n) {
  switch (sk) {
  case PTC_ELEM_F32: return convert_from<float>(dk, src, dst, n);
  case PTC_ELEM_F64: return convert_from<double>(dk, src, dst, n);
  case PTC_ELEM_I32: return convert_from<int32_t>(dk, src, dst, n);
  case PTC_ELEM_I64: return convert_from<int64_t>(dk, src, dst, n);
  case PTC_ELEM_U8: return convert_from<uint8_t>(dk, src, dst, n);
  default: return false;
  }
}

ptc_copy *ptc_reshape_get(ptc_context *ctx, ptc_copy *src, int32_t ltype_id) {
  if (!src || !src->ptr || ltype_id < 0) {
    ptc_copy_retain(src);
    return src;
  }
  if (src->shaped_as == ltype_id) {
    /* already the product of this exact type (a reshaped copy forwarded
     * through a same-typed dep): no re-reshape (remote_no_re_reshape) */
    ctx->reshape_hits.fetch_add(1, std::memory_order_relaxed);
    ptc_copy_retain(src);
    return src;
  }
  DtypeDef dt;
  if (!ptc_dtype_get(ctx, ltype_id, &dt)) {
    ptc_copy_retain(src);
    return src;
  }
  if (!dt.is_cast()) {
    /* identity for this copy (full-extent contiguous selection): the
     * avoidable-reshape case — pass the original pointer through */
    bool identity;
    if (!dt.segs.empty())
      identity = dt.segs.size() == 1 && dt.segs[0].first == 0 &&
                 dt.segs[0].second >= src->size;
    else
      identity = dt.stride == dt.elem && dt.packed() >= src->size;
    if (identity) {
      ctx->reshape_hits.fetch_add(1, std::memory_order_relaxed);
      ptc_copy_retain(src);
      return src;
    }
  }
  ReshapeCache *rc = src->reshape.load(std::memory_order_acquire);
  if (!rc) {
    ReshapeCache *fresh = new ReshapeCache();
    ReshapeCache *expect = nullptr;
    if (src->reshape.compare_exchange_strong(expect, fresh,
                                             std::memory_order_acq_rel))
      rc = fresh;
    else {
      delete fresh;
      rc = expect; /* the racer's cache */
    }
  }
  int32_t ver = src->version.load(std::memory_order_acquire);
  std::lock_guard<std::mutex> g(rc->lock);
  for (auto it = rc->entries.begin(); it != rc->entries.end();) {
    if (it->ltype_id == ltype_id) {
      if (it->src_version == ver) {
        ctx->reshape_hits.fetch_add(1, std::memory_order_relaxed);
        /* retained under the cache lock: a concurrent stale-version
         * eviction cannot free it before the caller owns a ref */
        ptc_copy_retain(it->shaped);
        return it->shaped; /* the future already resolved: shared copy */
      }
      /* stale version: evict so an iteratively rewritten source does
       * not accumulate one retained child per version (running
       * consumers hold their own refs) */
      ptc_copy_release_internal(ctx, it->shaped);
      it = rc->entries.erase(it);
      continue;
    }
    ++it;
  }
  /* trigger the future: materialize the converted child exactly once */
  ptc_copy_sync_for_host(ctx, src);
  ptc_copy *out = new ptc_copy();
  if (dt.is_cast()) {
    int64_t ssz = ptc_elem_size_of(dt.src_kind);
    int64_t dsz = ptc_elem_size_of(dt.dst_kind);
    int64_t n = (dt.count > 0) ? dt.count : (ssz ? src->size / ssz : 0);
    if (ssz && n * ssz > src->size) n = src->size / ssz;
    out->size = n * dsz;
    out->ptr = std::calloc(1, (size_t)(out->size > 0 ? out->size : 1));
    ptc_convert_elems(dt.src_kind, dt.dst_kind, src->ptr, out->ptr, n);
  } else {
    out->size = src->size;
    out->ptr = std::calloc(1, (size_t)(out->size > 0 ? out->size : 1));
    auto copy_seg = [&](int64_t off, int64_t len) {
      if (off < 0 || off >= src->size || len <= 0) return;
      if (off + len > src->size) len = src->size - off;
      std::memcpy((char *)out->ptr + off, (const char *)src->ptr + off,
                  (size_t)len);
    };
    if (!dt.segs.empty())
      for (const auto &p : dt.segs) copy_seg(p.first, p.second);
    else
      for (int64_t i = 0; i < dt.count; i++) copy_seg(i * dt.stride, dt.elem);
  }
  out->owns_ptr = true;
  out->shaped_as = ltype_id;
  rc->entries.push_back(ReshapeCache::Entry{ltype_id, ver, out});
  ctx->reshape_conversions.fetch_add(1, std::memory_order_relaxed);
  ptc_copy_retain(out); /* one ref for the cache, one for the caller */
  return out;
}

void ptc_typed_writeback(ptc_context *ctx, int32_t ltype_id, ptc_copy *src,
                         void *dst, int64_t dst_size) {
  DtypeDef dt;
  if (ltype_id < 0 || !ptc_dtype_get(ctx, ltype_id, &dt)) {
    std::memcpy(dst, src->ptr,
                (size_t)std::min<int64_t>(dst_size, src->size));
    return;
  }
  if (dt.is_cast()) {
    /* the copy holds dst_kind elements; the collection holds src_kind */
    int64_t ssz = ptc_elem_size_of(dt.src_kind);
    int64_t dsz = ptc_elem_size_of(dt.dst_kind);
    if (!ssz || !dsz) return;
    int64_t n = src->size / dsz;
    if (n * ssz > dst_size) n = dst_size / ssz;
    ptc_convert_elems(dt.dst_kind, dt.src_kind, src->ptr, dst, n);
    return;
  }
  auto put_seg = [&](int64_t off, int64_t len) {
    if (off < 0 || off >= dst_size || len <= 0) return;
    if (off + len > dst_size) len = dst_size - off;
    if (off + len > src->size) len = src->size - off;
    if (len > 0)
      std::memcpy((char *)dst + off, (const char *)src->ptr + off,
                  (size_t)len);
  };
  if (!dt.segs.empty())
    for (const auto &p : dt.segs) put_seg(p.first, p.second);
  else
    for (int64_t i = 0; i < dt.count; i++) put_seg(i * dt.stride, dt.elem);
}

ptc_data *ptc_collection_data_of(ptc_context *ctx, int32_t dc_id,
                                 const int64_t *idx, int32_t n) {
  Collection *dc = ctx->collections[(size_t)dc_id];
  if (dc->linear) {
    int64_t k = n > 0 ? idx[0] : 0;
    if (k < 0 || k >= dc->nb_elems) return nullptr;
    std::lock_guard<std::mutex> g(dc->linear_lock);
    if (dc->linear_data.empty())
      dc->linear_data.assign((size_t)dc->nb_elems, nullptr);
    if (!dc->linear_data[(size_t)k])
      dc->linear_data[(size_t)k] =
          ptc_data_new(k, dc->base + k * dc->elem_size, dc->elem_size);
    return dc->linear_data[(size_t)k];
  }
  return dc->data_of ? dc->data_of(dc->user, idx, n) : nullptr;
}

uint32_t ptc_collection_rank_of(ptc_context *ctx, int32_t dc_id,
                                const int64_t *idx, int32_t n) {
  Collection *dc = ctx->collections[(size_t)dc_id];
  uint32_t r;
  if (dc->linear)
    r = dc->nodes ? (uint32_t)((n > 0 ? idx[0] : 0) % dc->nodes) : 0;
  else
    r = dc->rank_of ? dc->rank_of(dc->user, idx, n) : 0;
  /* ptc-topo rank remap: relabel the logical owner to its physical
   * rank.  Every rank_of consumer funnels through here, so affinity,
   * placement and mem owners move consistently. */
  ptc_context::RankMap *rm =
      ctx->rank_map.load(std::memory_order_acquire);
  if (rm && r < rm->map.size()) r = (uint32_t)rm->map[r];
  return r;
}

/* Install (or clear, map == NULL / n <= 0) the ptc-topo rank remap.
 * The permutation must be SPMD-identical across ranks — every rank
 * computes placement with it, so divergent maps would strand tasks.
 * Old maps are retired until destroy (lock-free readers in flight). */
extern "C" void ptc_context_set_rank_map(ptc_context_t *ctx,
                                         const int32_t *map, int32_t n) {
  ptc_context::RankMap *rm = nullptr;
  if (map && n > 0) {
    rm = new ptc_context::RankMap();
    rm->map.assign(map, map + n);
  }
  ptc_context::RankMap *old =
      ctx->rank_map.exchange(rm, std::memory_order_acq_rel);
  if (old) {
    std::lock_guard<std::mutex> g(ctx->reg_lock);
    ctx->rank_maps_retired.push_back(old);
  }
}

/* ------------------------------------------------------------------ */
/* runtime: creation, scheduling, execution, release                   */
/* ------------------------------------------------------------------ */

namespace {

/* Task alloc/free with per-worker magazines: the steady-state pair
 * (alloc in deliver → free in complete, both on the executing worker)
 * touches only the worker's own magazine — no lock.  Refill/flush move
 * ctx->mag_batch tasks per free_lock acquisition; external threads
 * (startup enumeration, comm deliveries) use the shared pool directly. */
static ptc_task *task_alloc(ptc_context *ctx) {
  int slot = mag_slot(ctx);
  if (slot >= 0 && slot < (int)ctx->task_mags.size()) {
    ptc_context::TaskMag &m = *ctx->task_mags[(size_t)slot];
    if (!m.head) {
      std::lock_guard<std::mutex> g(ctx->free_lock);
      for (int i = 0; i < ctx->mag_batch && ctx->free_list; i++) {
        ptc_task *t = ctx->free_list;
        ctx->free_list = t->next;
        t->next = m.head;
        m.head = t;
        m.count++;
      }
    }
    if (m.head) {
      ptc_task *t = m.head;
      m.head = t->next;
      m.count--;
      tick1(m.hits);
      return t;
    }
    tick1(m.misses);
    return new ptc_task();
  }
  {
    std::lock_guard<std::mutex> g(ctx->free_lock);
    if (ctx->free_list) {
      ptc_task *t = ctx->free_list;
      ctx->free_list = t->next;
      ctx->free_ext_hits.fetch_add(1, std::memory_order_relaxed);
      return t;
    }
  }
  ctx->free_ext_misses.fetch_add(1, std::memory_order_relaxed);
  return new ptc_task();
}

static void task_free(ptc_context *ctx, ptc_task *t) {
  int slot = mag_slot(ctx);
  if (slot >= 0 && slot < (int)ctx->task_mags.size()) {
    ptc_context::TaskMag &m = *ctx->task_mags[(size_t)slot];
    t->next = m.head;
    m.head = t;
    if (++m.count >= 2 * ctx->mag_batch) {
      /* spill one batch so idle workers don't hoard task memory */
      std::lock_guard<std::mutex> g(ctx->free_lock);
      for (int i = 0; i < ctx->mag_batch && m.head; i++) {
        ptc_task *s = m.head;
        m.head = s->next;
        m.count--;
        s->next = ctx->free_list;
        ctx->free_list = s;
      }
    }
    return;
  }
  std::lock_guard<std::mutex> g(ctx->free_lock);
  t->next = ctx->free_list;
  ctx->free_list = t;
}

static void complete_task(ptc_context *ctx, int worker, ptc_task *t);
static void execute_task(ptc_context *ctx, int worker, ptc_task *t);
static void prof_event(ptc_context *ctx, int worker, int64_t key,
                       int64_t phase, ptc_task *t, int32_t min_level = 1);
static void prof_edge(ptc_context *ctx, int worker, ptc_task *src,
                      int64_t dst_class, int64_t dl0, int64_t dl1);
static void prof_edge_params(ptc_context *ctx, int worker, ptc_task *src,
                             ptc_taskpool *tp, int32_t peer_class,
                             const int64_t *params, size_t nparams);

/* Fill derived locals given range-local values already in `locals`. */
static void fill_derived_locals(ptc_context *ctx, ptc_taskpool *tp,
                                const TaskClass &tc, int64_t *locals) {
  if (!tc.has_derived) return; /* decode-time memo: nothing to derive */
  for (size_t i = 0; i < tc.locals.size(); i++) {
    if (!tc.locals[i].is_range)
      locals[i] = eval_expr(tc.locals[i].value, ctx, locals,
                            (int)tc.locals.size(), tp->globals.data());
  }
}

/* True when the expression references no task locals and no Python
 * escapes — its value is fixed for the life of the taskpool. */
static bool expr_pool_const(const Expr &e) {
  const std::vector<int64_t> &c = e.code;
  for (size_t i = 0; i < c.size(); i++) {
    switch (c[i]) {
    case PTC_OP_LOCAL:
    case PTC_OP_CALL:
      return false;
    case PTC_OP_IMM:
    case PTC_OP_GLOBAL:
      i++; /* skip operand */
      break;
    default:
      break;
    }
  }
  return true;
}

/* stride-range membership: v in {lo, lo+st, ...} bounded by hi */
static inline bool in_range(int64_t v, int64_t lo, int64_t hi, int64_t st) {
  if (st > 0) return v >= lo && v <= hi && (v - lo) % st == 0;
  return v <= lo && v >= hi && (lo - v) % (-st) == 0;
}

/* True when the expression depends on nothing but pool globals and ONE
 * local slot (a comprehension value reading its iterator) — no escapes,
 * no other locals. */
static bool expr_const_except_slot(const Expr &e, int64_t slot) {
  const std::vector<int64_t> &c = e.code;
  for (size_t i = 0; i < c.size(); i++) {
    switch (c[i]) {
    case PTC_OP_LOCAL:
      if (i + 1 >= c.size() || c[i + 1] != slot) return false;
      i++;
      break;
    case PTC_OP_CALL:
      return false;
    case PTC_OP_IMM:
    case PTC_OP_GLOBAL:
      i++;
      break;
    default:
      break;
    }
  }
  return true;
}

/* Is `params` inside the class's enumerated parameter domain?  The
 * reference's generated iterate_successors/predecessors bound-check every
 * peer (jdf2c emits per-param min/max guards around each release), so an
 * unguarded JDF edge aimed at an out-of-range instance is DROPPED by
 * language semantics — tests/dsl/ptg/choice/choice.jdf's unguarded
 * `-> D Choice(k+1)` from TA(NT) relies on exactly this.  Classes whose
 * bounds depend only on pool globals take a cached-constant fast path
 * (state: 0 unknown, 3 being decided, 1 cached, 2 dynamic). */
static bool task_params_in_domain(ptc_context *ctx, ptc_taskpool *tp,
                                  const TaskClass &tc,
                                  const int64_t *params, size_t nparams) {
  size_t nb_range = tc.range_locals.size();
  if (nparams != nb_range) return false;
  int nb_locals = (int)tc.locals.size();
  const int64_t *g = tp->globals.data();
  int cs = tc.domain_cache_state.load(std::memory_order_acquire);
  if (cs == 0) {
    int expected = 0;
    if (tc.domain_cache_state.compare_exchange_strong(expected, 3)) {
      bool constb = true;
      for (size_t i = 0; constb && i < nb_range; i++) {
        const Local &l = tc.locals[(size_t)tc.range_locals[(size_t)i]];
        constb = expr_pool_const(l.lo) && expr_pool_const(l.hi) &&
                 expr_pool_const(l.st);
        if (constb && l.is_compr)
          /* cacheable when the value maps nothing but its own iterator
           * slot (+ globals): the whole value set is fixed per pool */
          constb = expr_const_except_slot(l.value, tc.range_locals[i]);
      }
      /* derived locals feeding nothing here: const bounds read none */
      if (constb) {
        int64_t zero[PTC_MAX_LOCALS] = {0};
        tc.domain_lo.resize(nb_range);
        tc.domain_hi.resize(nb_range);
        tc.domain_st.resize(nb_range);
        tc.domain_vals.assign(nb_range, {});
        for (size_t i = 0; constb && i < nb_range; i++) {
          const Local &l = tc.locals[(size_t)tc.range_locals[(size_t)i]];
          int64_t lo = eval_expr(l.lo, ctx, zero, nb_locals, g);
          int64_t hi = eval_expr(l.hi, ctx, zero, nb_locals, g);
          int64_t st = eval_expr(l.st, ctx, zero, nb_locals, g, 1);
          if (st == 0) st = 1;
          tc.domain_lo[i] = lo;
          tc.domain_hi[i] = hi;
          tc.domain_st[i] = st;
          if (l.is_compr) {
            int64_t n = (st > 0) ? (hi - lo) / st + 1 : (lo - hi) / (-st) + 1;
            if (n > 65536) { /* unreasonable value-set: stay dynamic */
              constb = false;
              break;
            }
            int32_t idx = tc.range_locals[(size_t)i];
            std::vector<int64_t> &vals = tc.domain_vals[i];
            for (int64_t it = lo; (st > 0) ? it <= hi : it >= hi; it += st) {
              zero[idx] = it;
              vals.push_back(eval_expr(l.value, ctx, zero, nb_locals, g));
            }
            zero[idx] = 0;
            std::sort(vals.begin(), vals.end());
            /* empty comprehension: in_range on lo>hi rejects everything,
             * matching the no-instances domain */
          }
        }
      }
      if (constb) {
        tc.domain_cache_state.store(1, std::memory_order_release);
        cs = 1;
      } else {
        tc.domain_cache_state.store(2, std::memory_order_release);
        cs = 2;
      }
    } else {
      cs = tc.domain_cache_state.load(std::memory_order_acquire);
    }
  }
  if (cs == 1) {
    for (size_t i = 0; i < nb_range; i++) {
      if (i < tc.domain_vals.size() && !tc.domain_vals[i].empty()) {
        const std::vector<int64_t> &vals = tc.domain_vals[i];
        if (!std::binary_search(vals.begin(), vals.end(), params[i]))
          return false;
      } else if (!in_range(params[i], tc.domain_lo[i], tc.domain_hi[i],
                           tc.domain_st[i])) {
        return false;
      }
    }
    return true;
  }
  /* dynamic bounds (triangular ranges etc.): evaluate in declaration
   * order with the candidate params bound */
  int64_t locals[PTC_MAX_LOCALS] = {0};
  for (size_t i = 0; i < nb_range; i++)
    locals[tc.range_locals[(size_t)i]] = params[i];
  fill_derived_locals(ctx, tp, tc, locals);
  for (size_t i = 0; i < nb_range; i++) {
    const Local &l = tc.locals[(size_t)tc.range_locals[(size_t)i]];
    int64_t lo = eval_expr(l.lo, ctx, locals, nb_locals, g);
    int64_t hi = eval_expr(l.hi, ctx, locals, nb_locals, g);
    int64_t st = eval_expr(l.st, ctx, locals, nb_locals, g, 1);
    if (st == 0) st = 1;
    if (l.is_compr) {
      /* membership = some iterator value maps to params[i] (no inverse
       * in general: walk the iterator range) */
      int32_t idx = tc.range_locals[(size_t)i];
      bool found = false;
      for (int64_t it = lo; (st > 0) ? it <= hi : it >= hi; it += st) {
        locals[idx] = it;
        if (eval_expr(l.value, ctx, locals, nb_locals, g) == params[i]) {
          found = true;
          break;
        }
      }
      locals[idx] = params[i]; /* restore for later range bounds */
      if (!found) return false;
      continue;
    }
    if (!in_range(params[i], lo, hi, st)) return false;
  }
  return true;
}

static inline bool task_params_in_domain(ptc_context *ctx, ptc_taskpool *tp,
                                         const TaskClass &tc,
                                         const std::vector<int64_t> &params) {
  return task_params_in_domain(ctx, tp, tc, params.data(), params.size());
}

/* Evaluate a DEP_TASK input dep's producer instance; true when that
 * producer exists (is in its class's domain).  Scalar-param fast path;
 * range params (CTL gathers) are checked per expanded instance by the
 * caller. */
static bool dep_producer_in_domain(ptc_context *ctx, ptc_taskpool *tp,
                                   const Dep &d, const int64_t *locals,
                                   int nb_locals, const int64_t *g) {
  if (d.peer_class < 0 || (size_t)d.peer_class >= tp->classes.size())
    return false;
  const TaskClass &peer = tp->classes[(size_t)d.peer_class];
  /* stack array, not a vector: this runs per DEP_TASK dep of every task
   * instance (counting + prepare_input hot paths) */
  int64_t pv[PTC_MAX_LOCALS];
  size_t np = d.params.size() < (size_t)PTC_MAX_LOCALS
                  ? d.params.size() : (size_t)PTC_MAX_LOCALS;
  for (size_t i = 0; i < np; i++) {
    if (d.params[i].is_range) return true; /* caller expands + checks */
    pv[i] = eval_expr(d.params[i].value, ctx, locals, nb_locals, g);
  }
  return task_params_in_domain(ctx, tp, peer, pv, np);
}

/* does the expression call into Python (an escape that may read state
 * written by task bodies — e.g. choice.jdf's `decision` array)? */
static bool expr_has_call(const Expr &e) {
  const std::vector<int64_t> &c = e.code;
  for (size_t i = 0; i < c.size(); i++) {
    switch (c[i]) {
    case PTC_OP_CALL:
      return true;
    case PTC_OP_IMM:
    case PTC_OP_LOCAL:
    case PTC_OP_GLOBAL:
      i++;
      break;
    default:
      break;
    }
  }
  return false;
}

/* The input dep selected for a non-CTL flow: the first dep that is
 * guard-true AND (for task sources) whose producer instance exists —
 * the reference's implicit range guard on every dep composes with the
 * explicit guard, so selection falls through to the next alternative.
 *
 * `conservative` (the COUNTING mode): a dynamic guard — one containing
 * a Python escape — may read state that task bodies write later
 * (choice.jdf's decision array), so its value at enumeration time is
 * meaningless.  A dynamic-guard TASK dep is then treated as a
 * potential source (the instance waits for a delivery instead of
 * startup-firing; if no producer ever chooses it, the count-correction
 * path retires it — the reference's choice contract).  Execution-time
 * resolution (prepare_input) evaluates guards for real: by then the
 * producers have run. */
static const Dep *select_input_dep(ptc_context *ctx, ptc_taskpool *tp,
                                   const Flow &fl, const int64_t *locals,
                                   int nb_locals, const int64_t *g,
                                   bool conservative = false) {
  for (const Dep &d : fl.in_deps) {
    if (conservative && d.guard_dyn) {
      if (d.kind != DEP_TASK)
        continue; /* dynamic memory source: cannot deliver; keep looking */
      if (!dep_producer_in_domain(ctx, tp, d, locals, nb_locals, g))
        continue;
      return &d;
    }
    if (!eval_guard(d.guard, ctx, locals, nb_locals, g)) continue;
    if (d.kind == DEP_TASK &&
        !dep_producer_in_domain(ctx, tp, d, locals, nb_locals, g))
      continue;
    return &d;
  }
  return nullptr;
}

/* Nested-loop walk over a dep's bracketed iterators (JDF local indices):
 * binds scratch slots nb_locals + k in declaration order — inner bounds
 * may read outer iterators and are re-evaluated per outer step — and
 * invokes fn() per combination.  Callers evaluate dep expressions with
 * count nb_locals + iters so Python escapes see the iterator slots. */
template <typename F>
static void walk_dep_iters(ptc_context *ctx, const Dep &d, int64_t *scratch,
                           int nb_locals, const int64_t *g, F &&fn,
                           size_t k = 0) {
  if (k == d.iters.size()) {
    fn();
    return;
  }
  int nb_eval = nb_locals + (int)k;
  const DepIter &di = d.iters[k];
  int64_t lo = eval_expr(di.lo, ctx, scratch, nb_eval, g);
  int64_t hi = eval_expr(di.hi, ctx, scratch, nb_eval, g);
  int64_t st = eval_expr(di.st, ctx, scratch, nb_eval, g, 1);
  if (st == 0) st = 1;
  for (int64_t v = lo; (st > 0) ? v <= hi : v >= hi; v += st) {
    scratch[nb_locals + (int)k] = v;
    walk_dep_iters(ctx, d, scratch, nb_locals, g, fn, k + 1);
  }
}

/* Count the task-input dependencies of one task instance: for every non-CTL
 * IN flow the *first* guard-true dep with an existing producer selects the
 * source (JDF alternative semantics); for CTL flows every guard-true input
 * dep counts, expanding ranges (control-gather) and skipping out-of-domain
 * producers.  Returns the total number of expected releases and, when
 * `per_flow` is non-null, the expected count per consumer flow (exact
 * duplicate-delivery accounting — see DepEntry). */
static int32_t count_task_inputs(ptc_context *ctx, ptc_taskpool *tp,
                                 const TaskClass &tc, const int64_t *locals,
                                 int32_t *per_flow = nullptr) {
  int nb_locals = (int)tc.locals.size();
  const int64_t *g = tp->globals.data();
  int32_t remaining = 0;
  for (size_t fi = 0; fi < tc.flows.size(); fi++) {
    const Flow &fl = tc.flows[fi];
    int32_t flow_count = 0;
    if (fl.flags & PTC_FLOW_CTL) {
      for (const Dep &d : fl.in_deps) {
        if (d.kind != DEP_TASK) continue;
        const TaskClass &peer = tp->classes[(size_t)d.peer_class];
        /* producers counted for one guard-true (dep-level) combination */
        auto count_for = [&](const int64_t *locs, int nb) {
          size_t np = d.params.size();
          std::vector<int64_t> vals(np, 0);
          std::vector<size_t> range_idx;
          for (size_t i = 0; i < np; i++) {
            if (d.params[i].is_range)
              range_idx.push_back(i);
            else
              vals[i] = eval_expr(d.params[i].value, ctx, locs, nb, g);
          }
          if (range_idx.empty()) {
            if (task_params_in_domain(ctx, tp, peer, vals)) flow_count += 1;
            return;
          }
          /* odometer over range params, domain-checking each producer */
          struct R { int64_t lo, hi, st, cur; };
          std::vector<R> rs;
          bool live = true;
          for (size_t ri : range_idx) {
            const DepParam &pm = d.params[ri];
            R r;
            r.lo = eval_expr(pm.lo, ctx, locs, nb, g);
            r.hi = eval_expr(pm.hi, ctx, locs, nb, g);
            r.st = eval_expr(pm.st, ctx, locs, nb, g, 1);
            if (r.st == 0) r.st = 1;
            r.cur = r.lo;
            if ((r.st > 0 && r.cur > r.hi) || (r.st < 0 && r.cur < r.hi))
              live = false;
            rs.push_back(r);
          }
          while (live) {
            for (size_t i = 0; i < rs.size(); i++)
              vals[range_idx[i]] = rs[i].cur;
            if (task_params_in_domain(ctx, tp, peer, vals)) flow_count += 1;
            size_t lvl = rs.size();
            while (lvl > 0) {
              R &r = rs[lvl - 1];
              r.cur += r.st;
              bool ok = (r.st > 0) ? r.cur <= r.hi : r.cur >= r.hi;
              if (ok) break;
              r.cur = r.lo;
              lvl--;
            }
            if (lvl == 0) live = false;
          }
        };
        if (d.iters.empty()) {
          if (!eval_guard(d.guard, ctx, locals, nb_locals, g)) continue;
          count_for(locals, nb_locals);
          continue;
        }
        /* bracketed iterators: guard per combination (it may read them) */
        int nb_ext = nb_locals + (int)d.iters.size();
        int64_t scratch[PTC_MAX_LOCALS] = {0};
        std::memcpy(scratch, locals,
                    sizeof(int64_t) * (size_t)nb_locals);
        walk_dep_iters(ctx, d, scratch, nb_locals, g, [&]() {
          if (eval_guard(d.guard, ctx, scratch, nb_ext, g))
            count_for(scratch, nb_ext);
        });
      }
    } else {
      const Dep *sel = select_input_dep(ctx, tp, fl, locals, nb_locals, g,
                                        /*conservative=*/true);
      if (sel && sel->kind == DEP_TASK) flow_count = 1;
    }
    if (per_flow && fi < PTC_MAX_FLOWS) per_flow[fi] = flow_count;
    remaining += flow_count;
  }
  return remaining;
}

/* Build a ready task from class + range-local params + staged copies.
 * Span form: the dispatch hot path hands params as a stack array — no
 * vector materialization between release_deps and the ready task. */
static ptc_task *make_task(ptc_context *ctx, ptc_taskpool *tp,
                           const TaskClass &tc, const int64_t *params,
                           size_t nparams,
                           ptc_copy *const staged[PTC_MAX_FLOWS]) {
  ptc_task *t = task_alloc(ctx);
  t->tp = tp;
  t->class_id = tc.id;
  t->chore_idx = 0;
  std::memset(t->locals, 0, sizeof(t->locals));
  std::memset(t->data, 0, sizeof(t->data));
  for (size_t i = 0; i < tc.range_locals.size() && i < nparams; i++)
    t->locals[tc.range_locals[(size_t)i]] = params[i];
  fill_derived_locals(ctx, tp, tc, t->locals);
  if (staged)
    for (size_t f = 0; f < tc.flows.size(); f++) t->data[f] = staged[f];
  t->priority = (int32_t)eval_expr(tc.priority, ctx, t->locals,
                                   (int)tc.locals.size(), tp->globals.data());
  /* pool-QoS priority bias: priority-ordered modules (ap/spq/ltq, and
   * the bypass slot) then order across pools too — the lane-less
   * fallback of the per-pool QoS contract.  qos_prio is clamped to
   * ±1023 at set time, so the composed value cannot overflow. */
  if (tp->qos.load(std::memory_order_relaxed))
    t->priority += tp->qos_prio * (1 << 20);
  return t;
}

static inline ptc_task *make_task(ptc_context *ctx, ptc_taskpool *tp,
                                  const TaskClass &tc,
                                  const std::vector<int64_t> &params,
                                  ptc_copy *const staged[PTC_MAX_FLOWS]) {
  return make_task(ctx, tp, tc, params.data(), params.size(), staged);
}

/* A batch of remote activations accumulated during one release_deps pass:
 * successors of the same output copy heading to the same rank share one
 * ACTIVATE message (reference: per-rank output bitmaps + forward mask,
 * parsec/remote_dep.h:143-177). */
struct RemoteSend {
  uint32_t rank;
  int32_t flow_idx;
  ptc_copy *copy;
  int32_t send_dtype; /* OUT dep's wire datatype, -1 = raw bytes */
  std::vector<std::pair<int32_t, std::vector<int64_t>>> targets;
};

/* Compute the placement rank of a successor instance (affinity expr over
 * its collection); myrank when the class has no affinity. */
static uint32_t successor_rank(ptc_context *ctx, ptc_taskpool *tp,
                               const TaskClass &tc, const int64_t *params,
                               size_t nparams) {
  if (tc.aff_dc < 0 || ctx->nodes <= 1) return ctx->myrank;
  int64_t locals[PTC_MAX_LOCALS] = {0};
  for (size_t i = 0; i < tc.range_locals.size() && i < nparams; i++)
    locals[tc.range_locals[(size_t)i]] = params[i];
  fill_derived_locals(ctx, tp, tc, locals);
  int64_t idx[PTC_MAX_LOCALS];
  int ni = (int)tc.aff_idx.size();
  for (int i = 0; i < ni; i++)
    idx[i] = eval_expr(tc.aff_idx[(size_t)i], ctx, locals,
                       (int)tc.locals.size(), tp->globals.data());
  return ptc_collection_rank_of(ctx, tc.aff_dc, idx, ni);
}

/* span-based local delivery core (defined below, after the dep-table
 * machinery).  `owned` non-null lets the hash path MOVE the caller's
 * vector instead of re-materializing one from the span. */
static void deliver_local_impl(ptc_context *ctx, int worker,
                               ptc_taskpool *tp, int32_t class_id,
                               const int64_t *params, size_t nparams,
                               std::vector<int64_t> *owned, int32_t flow_idx,
                               ptc_copy *copy, bool domain_checked);

/* Deliver one dependency release to a successor task instance: local
 * successors stage into the dep table; remote successors batch into an
 * ACTIVATE send (or go out immediately when batch == nullptr).  Params
 * arrive as a span — the local dense-engine path (the dispatch hot
 * path) never materializes a heap vector from them. */
static void deliver_dep(ptc_context *ctx, int worker, ptc_taskpool *tp,
                        int32_t class_id, const int64_t *params,
                        size_t nparams, int32_t flow_idx, ptc_copy *copy,
                        std::vector<RemoteSend> *batch,
                        int32_t send_dtype = -1) {
  const TaskClass &tc = tp->classes[(size_t)class_id];
  uint32_t rank = successor_rank(ctx, tp, tc, params, nparams);
  if (rank != ctx->myrank) {
    std::vector<int64_t> pv(params, params + nparams);
    if (batch) {
      for (RemoteSend &rs : *batch) {
        if (rs.rank == rank && rs.flow_idx == flow_idx && rs.copy == copy &&
            rs.send_dtype == send_dtype) {
          rs.targets.emplace_back(class_id, std::move(pv));
          return;
        }
      }
      batch->push_back(RemoteSend{rank, flow_idx, copy, send_dtype, {}});
      batch->back().targets.emplace_back(class_id, std::move(pv));
    } else {
      ptc_comm_send_activate(ctx, rank, tp, class_id, pv, flow_idx, copy,
                             send_dtype);
    }
    return;
  }
  /* local successors read the producer's copy directly: wire datatypes
   * apply only at the rank boundary (reference does the same — the
   * datatype engine sits in the remote-dep path).  release_deps already
   * domain-checked these params (domain_checked=true skips the re-check
   * — with dynamic bounds it would re-fire Python escape evaluations). */
  deliver_local_impl(ctx, worker, tp, class_id, params, nparams,
                     /*owned=*/nullptr, flow_idx, copy,
                     /*domain_checked=*/true);
}

} // namespace

namespace {

/* dense-engine promoted-slot sentinel (never a valid heap pointer) */
DepEntry *const DENSE_PROMOTED = reinterpret_cast<DepEntry *>(1);

/* first touch of a dependency entry: compute how many task-inputs this
 * instance expects, per consumer flow (exact over-delivery detection) */
static void init_dep_entry(ptc_context *ctx, ptc_taskpool *tp,
                           const TaskClass &tc, const int64_t *params,
                           size_t nparams, DepEntry &e) {
  int64_t locals[PTC_MAX_LOCALS] = {0};
  for (size_t i = 0; i < tc.range_locals.size() && i < nparams; i++)
    locals[tc.range_locals[(size_t)i]] = params[i];
  fill_derived_locals(ctx, tp, tc, locals);
  e.remaining = count_task_inputs(ctx, tp, tc, locals, e.flow_remaining);
  e.initialized = true;
}

/* one delivery applied to an entry (shared by both engines).  Returns
 * 0 = keep waiting, 1 = fire the task, -1 = duplicate (dropped). */
static int apply_delivery(ptc_context *ctx, const TaskClass &tc, DepEntry &e,
                          int32_t flow_idx, ptc_copy *copy) {
  if (flow_idx >= 0 && flow_idx < PTC_MAX_FLOWS) {
    if (e.flow_remaining[flow_idx] <= 0) {
      /* this flow already received every delivery it expects: duplicate
       * (over-delivering output dep, or a comm-layer re-delivery).
       * Dropping it instead of decrementing keeps the task from firing
       * with a missing input on another flow. */
      std::fprintf(stderr,
                   "ptc: duplicate dependency delivery to %s flow %d; "
                   "ignored\n", tc.name.c_str(), flow_idx);
      return -1;
    }
    e.flow_remaining[flow_idx] -= 1;
  }
  if (copy && flow_idx >= 0 && flow_idx < PTC_MAX_FLOWS) {
    copy_retain(copy);
    if (e.staged[flow_idx]) copy_release(ctx, e.staged[flow_idx]);
    e.staged[flow_idx] = copy;
  }
  e.remaining -= 1;
  return e.remaining == 0 ? 1 : 0;
}

/* linearized slot index within the class's bounding box, or -1 */
static int64_t dense_index(const DenseDeps &dd, const int64_t *params,
                           size_t nparams) {
  if (nparams != dd.lo.size()) return -1;
  int64_t idx = 0;
  for (size_t i = 0; i < nparams; i++) {
    int64_t d = params[i] - dd.lo[i];
    if (d < 0 || d >= dd.span[i]) return -1;
    idx = idx * dd.span[i] + d;
  }
  return idx;
}

} // namespace

/* locked copy-out of a datatype definition (registration may reallocate
 * the vector concurrently on another thread) */
bool ptc_dtype_get(ptc_context *ctx, int32_t id, DtypeDef *out) {
  if (id < 0) return false;
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  if ((size_t)id >= ctx->dtypes.size()) return false;
  *out = ctx->dtypes[(size_t)id];
  return true;
}

bool ptc_has_dtypes(ptc_context *ctx) {
  return ctx->has_dtypes.load(std::memory_order_acquire);
}

/* The wire datatype of the IN dep that selects this delivery for one
 * consumer instance (guard- and domain-aware, same selection rule as
 * count_task_inputs), or -1.  Used by the comm layer to scatter wire
 * bytes into the consumer's layout (reference: per-dep MPI datatype
 * selection on the receive side, remote_dep_mpi.c). */
/* The IN dep that selects deliveries for one consumer instance's flow
 * (guard- and domain-aware).  Real evaluation first: at delivery time
 * the producers have run, so a dynamic guard usually resolves (and
 * alternatives may declare DIFFERENT datatypes — picking conservatively
 * would pick the wrong layout); conservative fallback only when nothing
 * selects.  ONE rule shared by the wire-dtype scatter and the
 * local-reshape staging so the two cannot drift. */
static const Dep *ptc_select_consumer_in_dep(
    ptc_context *ctx, ptc_taskpool *tp, const TaskClass &tc,
    const std::vector<int64_t> &params, int32_t flow_idx) {
  int nb_locals = (int)tc.locals.size();
  int64_t locals[PTC_MAX_LOCALS] = {0};
  for (size_t i = 0; i < tc.range_locals.size() && i < params.size(); i++)
    locals[tc.range_locals[(size_t)i]] = params[i];
  fill_derived_locals(ctx, tp, tc, locals);
  const Flow &fl = tc.flows[(size_t)flow_idx];
  const Dep *sel = select_input_dep(ctx, tp, fl, locals, nb_locals,
                                    tp->globals.data());
  if (!sel)
    sel = select_input_dep(ctx, tp, fl, locals, nb_locals,
                           tp->globals.data(), /*conservative=*/true);
  return sel;
}

int32_t ptc_consumer_recv_dtype(ptc_context *ctx, ptc_taskpool *tp,
                                int32_t class_id,
                                const std::vector<int64_t> &params,
                                int32_t flow_idx) {
  if (class_id < 0 || (size_t)class_id >= tp->classes.size()) return -1;
  const TaskClass &tc = tp->classes[(size_t)class_id];
  if (flow_idx < 0 || (size_t)flow_idx >= tc.flows.size()) return -1;
  if (tc.flows[(size_t)flow_idx].flags & PTC_FLOW_CTL) return -1;
  const Dep *sel = ptc_select_consumer_in_dep(ctx, tp, tc, params, flow_idx);
  return sel ? sel->dtype_id : -1;
}

void ptc_deliver_dep_local(ptc_context *ctx, int worker, ptc_taskpool *tp,
                           int32_t class_id, std::vector<int64_t> &&params,
                           int32_t flow_idx, ptc_copy *copy,
                           bool domain_checked) {
  deliver_local_impl(ctx, worker, tp, class_id, params.data(), params.size(),
                     &params, flow_idx, copy, domain_checked);
}

namespace {

static void deliver_local_impl(ptc_context *ctx, int worker,
                               ptc_taskpool *tp, int32_t class_id,
                               const int64_t *params, size_t nparams,
                               std::vector<int64_t> *owned, int32_t flow_idx,
                               ptc_copy *copy, bool domain_checked) {
  const TaskClass &tc = tp->classes[(size_t)class_id];

  if (!domain_checked &&
      !task_params_in_domain(ctx, tp, tc, params, nparams)) {
    /* out-of-domain successor: dropped by JDF semantics (see
     * task_params_in_domain).  Not an error. */
    return;
  }

  /* consumer-side local reshape ([type = X] on the IN dep): stage the
   * memoized reshaped child instead of the delivered copy.  Same dep
   * selection rule as the recv-dtype path (ptc_consumer_recv_dtype);
   * gated per class so ltype-free programs never pay for it.  The hold
   * releases the caller-owned reshape ref once staging has retained. */
  struct LtypeHold {
    ptc_context *ctx;
    ptc_copy *c = nullptr;
    ~LtypeHold() {
      if (c) ptc_copy_release_internal(ctx, c);
    }
  } ltype_hold{ctx};
  if (copy && tc.has_in_ltype && flow_idx >= 0 &&
      (size_t)flow_idx < tc.flows.size()) {
    const Flow &fl = tc.flows[(size_t)flow_idx];
    if (!(fl.flags & PTC_FLOW_CTL)) {
      std::vector<int64_t> pvec(params, params + nparams);
      const Dep *sel = ptc_select_consumer_in_dep(ctx, tp, tc, pvec,
                                                  flow_idx);
      if (sel && sel->ltype_id >= 0)
        copy = ltype_hold.c = ptc_reshape_get(ctx, copy, sel->ltype_id);
    }
  }

  /* dense engine: O(1) slot in the class's bounding box (reference:
   * parsec_default_find_deps over the dense deps array vs
   * parsec_hash_find_deps, parsec_internal.h:343-346).
   *
   * Slot protocol: the null -> {entry | PROMOTED} transition is a CAS
   * (lock-free), so a first delivery that SATISFIES the instance — the
   * steady state of chains and every single-producer flow set — counts
   * its inputs on the stack, fires the task, and never touches a mutex
   * or the heap.  Only live multi-input entries serialize on the shard
   * stripe (their fields are plain); entry -> PROMOTED happens under
   * that stripe, and slots never return to null, so a CAS loser can
   * safely re-resolve under the lock. */
  if ((size_t)class_id < tp->dense.size() &&
      tp->dense[(size_t)class_id].enabled) {
    DenseDeps &dd = tp->dense[(size_t)class_id];
    int64_t sidx = dense_index(dd, params, nparams);
    if (sidx >= 0) {
      std::atomic<DepEntry *> &slot = dd.slots[sidx];
      DepEntry *e0 = slot.load(std::memory_order_acquire);
      if (e0 == DENSE_PROMOTED) {
        std::fprintf(stderr, "ptc: duplicate dependency delivery to "
                             "already-fired %s; ignored\n",
                     tc.name.c_str());
        return;
      }
      if (!e0) {
        /* first touch: count + apply on the STACK, publish by CAS */
        DepEntry se;
        init_dep_entry(ctx, tp, tc, params, nparams, se);
        int rc = apply_delivery(ctx, tc, se, flow_idx, copy);
        if (rc < 0) return; /* zero-expectation flow: nothing retained */
        DepEntry *expect = nullptr;
        if (rc > 0) {
          if (slot.compare_exchange_strong(expect, DENSE_PROMOTED,
                                           std::memory_order_acq_rel,
                                           std::memory_order_acquire)) {
            ptc_schedule_task(ctx, worker,
                              make_task(ctx, tp, tc, params, nparams,
                                        se.staged));
            return;
          }
        } else {
          DepEntry *he = new DepEntry(se);
          if (slot.compare_exchange_strong(expect, he,
                                           std::memory_order_acq_rel,
                                           std::memory_order_acquire))
            return;
          delete he;
        }
        /* lost the first-touch race: drop the stack stage refs and
         * re-deliver against the winner's slot state under the stripe */
        for (int f = 0; f < PTC_MAX_FLOWS; f++)
          if (se.staged[f]) copy_release(ctx, se.staged[f]);
      }
      DepShard &shard = tp->shards[(size_t)(sidx % NB_SHARDS)];
      ptc_task *ready = nullptr;
      {
        std::lock_guard<std::mutex> g(shard.lock);
        DepEntry *e = slot.load(std::memory_order_acquire);
        if (e == DENSE_PROMOTED) {
          std::fprintf(stderr, "ptc: duplicate dependency delivery to "
                               "already-fired %s; ignored\n",
                       tc.name.c_str());
          return;
        }
        if (!e) {
          /* cannot happen (slots never revert to null) — defensive */
          e = new DepEntry();
          init_dep_entry(ctx, tp, tc, params, nparams, *e);
          slot.store(e, std::memory_order_release);
        }
        int rc = apply_delivery(ctx, tc, *e, flow_idx, copy);
        if (rc < 0) return;
        if (rc > 0) {
          ready = make_task(ctx, tp, tc, params, nparams, e->staged);
          delete e;
          slot.store(DENSE_PROMOTED, std::memory_order_release);
        }
      }
      if (ready) ptc_schedule_task(ctx, worker, ready);
      return;
    }
    /* out-of-box instance (shouldn't happen): hash path below is exact */
  }

  std::vector<int64_t> pv = owned
                                ? std::move(*owned)
                                : std::vector<int64_t>(params,
                                                       params + nparams);
  DepKey key{class_id, ptc_fnv_hash(class_id, pv), std::move(pv)};
  DepShard &shard = tp->shards[key.hash % NB_SHARDS];

  ptc_task *ready = nullptr;
  {
    std::lock_guard<std::mutex> g(shard.lock);
    if (shard.promoted_recent.count(key)) {
      std::fprintf(stderr,
                   "ptc: duplicate dependency delivery to already-fired %s; "
                   "ignored\n", tc.name.c_str());
      return;
    }
    DepEntry &e = shard.map[key];
    if (!e.initialized)
      init_dep_entry(ctx, tp, tc, key.params.data(), key.params.size(), e);
    int rc = apply_delivery(ctx, tc, e, flow_idx, copy);
    if (rc < 0) return;
    if (rc > 0) {
      /* refs transfer to the task; the entry is erased and only a
       * bounded, full-key recent-promotions record remains */
      ready = make_task(ctx, tp, tc, key.params, e.staged);
      shard.map.erase(key);
      shard.promoted_fifo.push_back(key);
      shard.promoted_recent.insert(std::move(key));
      if (shard.promoted_fifo.size() > PROMOTED_RECENT_CAP) {
        shard.promoted_recent.erase(shard.promoted_fifo.front());
        shard.promoted_fifo.pop_front();
      }
    }
  }
  if (ready) ptc_schedule_task(ctx, worker, ready);
}

} // namespace

namespace {

/* prepare_input: resolve memory-input deps and allocate WRITE-only flows.
 * (Reference: data_lookup/prepare_input generated hooks.) */
static int prepare_input(ptc_context *ctx, ptc_task *t) {
  ptc_taskpool *tp = t->tp;
  const TaskClass &tc = tp->classes[(size_t)t->class_id];
  int nb_locals = (int)tc.locals.size();
  const int64_t *g = tp->globals.data();
  for (size_t f = 0; f < tc.flows.size(); f++) {
    const Flow &fl = tc.flows[f];
    if (fl.flags & PTC_FLOW_CTL) continue;
    if (t->data[f]) continue; /* staged by a producer */
    /* same selection rule as the counting side: first guard-true dep with
     * an existing producer (out-of-domain task sources fall through to
     * the next alternative — memory read or WRITE allocation) */
    const Dep *sel =
        select_input_dep(ctx, tp, fl, t->locals, nb_locals, g);
    if (sel && sel->kind == DEP_MEM) {
      int64_t idx[PTC_MAX_LOCALS];
      int ni = (int)sel->idx.size();
      for (int i = 0; i < ni; i++)
        idx[i] = eval_expr(sel->idx[(size_t)i], ctx, t->locals, nb_locals, g);
      if (ctx->nodes > 1 &&
          ptc_collection_rank_of(ctx, sel->dc_id, idx, ni) != ctx->myrank) {
        /* memory reads must be affine with task placement (DPLASMA-style
         * JDFs are; remote initial reads would need a GET protocol).
         * Proceeding would silently compute on whatever is in the local
         * mirror — hard-fail the task instead (VERDICT r1 weak #6). */
        std::fprintf(stderr,
                     "ptc: task %s reads remote collection data; place the "
                     "task at its data (affinity) instead — task failed\n",
                     tc.name.c_str());
        return -1;
      }
      ptc_data *d = ptc_collection_data_of(ctx, sel->dc_id, idx, ni);
      if (d && d->host_copy) {
        /* [type_data = X] on a matrix read: stage the reshaped child
         * (a new copy holding only the selected/converted elements) so
         * the body never aliases the collection tile.  reshape_get
         * returns retained; the plain path retains explicitly. */
        ptc_copy *c = d->host_copy;
        if (sel->ltype_id >= 0)
          c = ptc_reshape_get(ctx, c, sel->ltype_id);
        else
          copy_retain(c);
        t->data[f] = c;
      }
    } else if (!sel || sel->kind == DEP_NONE) {
      /* pure WRITE flow: allocate from its arena */
      if ((fl.flags & PTC_FLOW_WRITE) && fl.arena_id >= 0) {
        Arena *a = ctx->arena_at(fl.arena_id);
        ptc_copy *c = new ptc_copy();
        c->ptr = a->alloc(mag_slot(ctx));
        c->size = a->elem_size;
        c->arena_id = fl.arena_id;
        t->data[f] = c;
      }
    }
  }
  return 0;
}

/* release_deps: after a task body ran, walk every flow's output deps and
 * fan out: task targets get the flow's current copy delivered; memory
 * targets get written back (remote ones via comm PUT).  (Reference:
 * iterate_successors + parsec_release_dep_fct, parsec/parsec.c:1912.) */
static void release_deps(ptc_context *ctx, int worker, ptc_task *t) {
  ptc_taskpool *tp = t->tp;
  const TaskClass &tc = tp->classes[(size_t)t->class_id];
  int nb_locals = (int)tc.locals.size();
  const int64_t *g = tp->globals.data();
  std::vector<RemoteSend> batch;
  /* reshape refs owned by this pass (ptc_reshape_get returns retained);
   * released only after the remote batch flush — RemoteSend holds raw
   * copy pointers until then */
  std::vector<ptc_copy *> reshape_holds;

  for (size_t f = 0; f < tc.flows.size(); f++) {
    const Flow &fl = tc.flows[f];
    ptc_copy *copy = t->data[f];
    if (copy && (fl.flags & PTC_FLOW_WRITE))
      copy->version.fetch_add(1, std::memory_order_relaxed);
    for (const Dep &d : fl.out_deps) {
      /* one guard-true (dep-level) emission given the locals view `locs`
       * (the task's own locals, or a scratch copy extended with bracketed
       * iterator values in slots nb_locals..) */
      auto emit_task_dep = [&](const int64_t *locs, int nb) {
        /* local reshape ([type = X] on an OUT dep): successors of this
         * dep receive the memoized reshaped child instead of the
         * producer's copy — and remote sends ship it (the reference's
         * pre-send remote reshape, parsec_reshape.c:771).  Resolved
         * lazily on the first in-domain delivery so an all-out-of-domain
         * boundary dep never pays the conversion. */
        ptc_copy *ecopy_v = nullptr;
        bool ecopy_done = false;
        auto ecopy = [&]() -> ptc_copy * {
          if (!ecopy_done) {
            ecopy_done = true;
            ecopy_v = (fl.flags & PTC_FLOW_CTL) ? nullptr : copy;
            if (ecopy_v && d.ltype_id >= 0) {
              ecopy_v = ptc_reshape_get(ctx, ecopy_v, d.ltype_id);
              reshape_holds.push_back(ecopy_v); /* released post-flush */
            }
          }
          return ecopy_v;
        };
        /* expand range params (broadcast outputs).  All-stack storage:
         * the scalar case (every chain/chord successor) runs from here
         * through the dense dep engine to the ready task without one
         * heap allocation. */
        size_t np = d.params.size();
        if (np > (size_t)PTC_MAX_LOCALS)
          return; /* cannot be in any class's domain (> max range locals) */
        int64_t vals[PTC_MAX_LOCALS] = {0};
        size_t range_idx[PTC_MAX_LOCALS];
        size_t nri = 0;
        for (size_t i = 0; i < np; i++)
          if (d.params[i].is_range) range_idx[nri++] = i;
        /* evaluate scalar params once */
        for (size_t i = 0; i < np; i++)
          if (!d.params[i].is_range)
            vals[i] = eval_expr(d.params[i].value, ctx, locs, nb, g);
        /* out-of-domain successors are dropped HERE, before the edge is
         * traced or the successor's rank is computed: a negative param
         * through a modulo rank_of would index garbage, and a remote
         * send would serialize a frame the receiver immediately drops.
         * (Remote arrivals re-check in ptc_deliver_dep_local as wire
         * defense; local deliveries skip the re-check.) */
        const TaskClass &peer_tc = tp->classes[(size_t)d.peer_class];
        if (nri == 0) {
          if (!task_params_in_domain(ctx, tp, peer_tc, vals, np)) return;
          prof_edge_params(ctx, worker, t, tp, d.peer_class, vals, np);
          deliver_dep(ctx, worker, tp, d.peer_class, vals, np, d.peer_flow,
                      ecopy(), &batch, d.dtype_id);
          return;
        }
        /* nested iteration over up to a few range params */
        struct R { int64_t lo, hi, st, cur; };
        R rs[PTC_MAX_LOCALS];
        for (size_t i = 0; i < nri; i++) {
          const DepParam &pm = d.params[range_idx[i]];
          R &r = rs[i];
          r.lo = eval_expr(pm.lo, ctx, locs, nb, g);
          r.hi = eval_expr(pm.hi, ctx, locs, nb, g);
          r.st = eval_expr(pm.st, ctx, locs, nb, g, 1);
          if (r.st == 0) r.st = 1;
          r.cur = r.lo;
        }
        bool live = true;
        for (size_t i = 0; i < nri; i++)
          if ((rs[i].st > 0 && rs[i].cur > rs[i].hi) ||
              (rs[i].st < 0 && rs[i].cur < rs[i].hi))
            live = false;
        while (live) {
          for (size_t i = 0; i < nri; i++)
            vals[range_idx[i]] = rs[i].cur;
          if (task_params_in_domain(ctx, tp, peer_tc, vals, np)) {
            prof_edge_params(ctx, worker, t, tp, d.peer_class, vals, np);
            deliver_dep(ctx, worker, tp, d.peer_class, vals, np,
                        d.peer_flow, ecopy(), &batch, d.dtype_id);
          }
          /* advance odometer */
          size_t i = 0;
          for (; i < nri; i++) {
            rs[i].cur += rs[i].st;
            if ((rs[i].st > 0 && rs[i].cur <= rs[i].hi) ||
                (rs[i].st < 0 && rs[i].cur >= rs[i].hi))
              break;
            rs[i].cur = rs[i].lo;
          }
          if (i == nri) live = false;
        }
      };
      auto emit_mem_dep = [&](const int64_t *locs, int nb) {
        if (!copy || !(fl.flags & PTC_FLOW_WRITE)) return;
        int64_t idx[PTC_MAX_LOCALS];
        int ni = (int)d.idx.size();
        for (int i = 0; i < ni; i++)
          idx[i] = eval_expr(d.idx[(size_t)i], ctx, locs, nb, g);
        if (ctx->nodes > 1) {
          uint32_t r = ptc_collection_rank_of(ctx, d.dc_id, idx, ni);
          if (r != ctx->myrank) {
            ptc_copy_sync_for_host(ctx, copy); /* coherence: pull mirror */
            ptc_comm_send_put_mem(ctx, r, d.dc_id, idx, ni, copy,
                                  d.ltype_id);
            return;
          }
        }
        ptc_data *dst = ptc_collection_data_of(ctx, d.dc_id, idx, ni);
        if (dst && dst->host_copy && dst->host_copy->ptr != copy->ptr) {
          ptc_copy_sync_for_host(ctx, copy); /* coherence: pull mirror */
          /* [type_data = X] on the write-back: update only the region
           * the type selects (cast types reverse-convert) instead of
           * overwriting the whole tile */
          if (d.ltype_id >= 0)
            ptc_typed_writeback(ctx, d.ltype_id, copy, dst->host_copy->ptr,
                                dst->host_copy->size);
          else
            std::memcpy(dst->host_copy->ptr, copy->ptr,
                        (size_t)std::min(dst->host_copy->size, copy->size));
          /* the tile's host bytes are now authoritative: drop any stale
           * device mirror of dst (a Mem-rooted earlier task may have
           * left a dirty one bound to this very buffer — flushing it
           * later would clobber the bytes just written; the version
           * store below cannot catch that, it copies the SOURCE
           * version, which can collide with the mirror's) */
          ptc_copy_host_written(ctx, dst->host_copy);
        }
        if (dst && dst->host_copy)
          dst->host_copy->version.store(copy->version.load());
      };
      auto emit = [&](const int64_t *locs, int nb) {
        if (d.kind == DEP_TASK)
          emit_task_dep(locs, nb);
        else if (d.kind == DEP_MEM)
          emit_mem_dep(locs, nb);
      };
      if (d.iters.empty()) {
        if (!eval_guard(d.guard, ctx, t->locals, nb_locals, g)) continue;
        emit(t->locals, nb_locals);
        continue;
      }
      /* bracketed iterators (JDF local indices): nested loops binding
       * scratch slots nb_locals..; the guard is re-evaluated per
       * combination (it may read the iterators), and inner bounds may
       * read outer iterators (re-evaluated per outer step) */
      int nb_ext = nb_locals + (int)d.iters.size();
      int64_t scratch[PTC_MAX_LOCALS];
      std::memcpy(scratch, t->locals, sizeof(scratch));
      walk_dep_iters(ctx, d, scratch, nb_locals, g, [&]() {
        if (eval_guard(d.guard, ctx, scratch, nb_ext, g))
          emit(scratch, nb_ext);
      });
    }
  }
  int32_t topo = ctx->comm_topo.load(std::memory_order_relaxed);
  if (topo == 0) {
    for (RemoteSend &rs : batch)
      ptc_comm_send_activate_batch(ctx, rs.rank, tp, rs.flow_idx, rs.copy,
                                   rs.targets, rs.send_dtype);
  } else {
    /* chain/binomial propagation: sends of the SAME output copy to several
     * ranks become one broadcast the comm layer forwards along the
     * topology (reference: remote_dep_bcast_*_child, remote_dep.c:39-47) */
    for (size_t i = 0; i < batch.size(); i++) {
      if (batch[i].rank == UINT32_MAX) continue;
      std::vector<PtcBcastRankGroup> groups;
      groups.push_back(
          PtcBcastRankGroup{batch[i].rank, std::move(batch[i].targets)});
      for (size_t j = i + 1; j < batch.size(); j++) {
        if (batch[j].rank != UINT32_MAX &&
            batch[j].flow_idx == batch[i].flow_idx &&
            batch[j].copy == batch[i].copy &&
            batch[j].send_dtype == batch[i].send_dtype) {
          groups.push_back(
              PtcBcastRankGroup{batch[j].rank, std::move(batch[j].targets)});
          batch[j].rank = UINT32_MAX;
        }
      }
      if (groups.size() >= 2) {
        ptc_comm_send_activate_bcast(ctx, tp, batch[i].flow_idx,
                                     batch[i].copy, topo, std::move(groups),
                                     batch[i].send_dtype);
      } else {
        ptc_comm_send_activate_batch(ctx, batch[i].rank, tp,
                                     batch[i].flow_idx, batch[i].copy,
                                     groups[0].targets, batch[i].send_dtype);
      }
      batch[i].rank = UINT32_MAX;
    }
  }
  for (ptc_copy *h : reshape_holds) ptc_copy_release_internal(ctx, h);
}

static void wake_workers(ptc_context *ctx) {
  ctx->work_signal.fetch_add(1, std::memory_order_release);
  ctx->idle_cv.notify_all();
}

} // namespace

/* Hot-path scheduler bypass (reference: __parsec_schedule's
 * keep_highest_priority_task + es->next_task, parsec/scheduling.c:373-396):
 * a worker thread completing a task keeps the highest-priority ready
 * successor in a thread-local slot and executes it directly, skipping one
 * schedule/select round-trip per task.  Only worker threads opt in
 * (tl_bypass), so comm-thread, device-manager, and main-thread schedules
 * take the normal scheduler path. */
static thread_local ptc_task *tl_next_task = nullptr;
static thread_local bool tl_bypass = false;

void ptc_schedule_task(ptc_context *ctx, int worker, ptc_task *t) {
  /* comm-thread deliveries can precede/overlap the lazy start */
  if (!ctx->started.load(std::memory_order_acquire))
    ptc_context_start(ctx);
  int slot = mag_slot(ctx);
  if (slot >= 0)
    tick1(ctx->flight[(size_t)slot]->scheduled);
  else
    ctx->flight_ext.scheduled.fetch_add(1, std::memory_order_relaxed);
  if (tl_bypass && ctx->sched_bypass.load(std::memory_order_relaxed) &&
      !(t->tp && t->tp->qos.load(std::memory_order_relaxed))) {
    /* QoS pools never ride the thread-local bypass: every ready
     * successor must pass a select() boundary so a higher-priority
     * pool's lane can win the wave (see SchedLWS QoS lanes) */
    if (!tl_next_task) {
      tl_next_task = t;
      return;
    }
    if (t->priority > tl_next_task->priority) {
      ptc_task *lower = tl_next_task;
      tl_next_task = t;
      t = lower;
    }
  }
  ctx->sched->schedule(worker < 0 ? 0 : worker, t);
  wake_workers(ctx);
}

namespace {

static inline void schedule_task(ptc_context *ctx, int worker, ptc_task *t) {
  ptc_schedule_task(ctx, worker, t);
}

/* Mark a taskpool complete exactly once: notify tp waiters and, when it was
 * the last active pool, context waiters.  The empty lock_guard blocks
 * protect against the missed-wakeup race with waiters that have evaluated
 * the predicate but not yet blocked. */
static void notify_drain_waiters(ptc_taskpool *tp) {
  /* seq_cst pairs with ptc_tp_drain: completer stores nb_tasks then loads
   * drain_waiters; drainer stores drain_waiters then loads nb_tasks — the
   * seq_cst total order forbids both sides missing the other's store */
  if (tp->drain_waiters.load(std::memory_order_seq_cst) == 0) return;
  /* notify UNDER the lock: a waiter may return the instant the predicate
   * flips and destroy the pool — an after-unlock notify would then
   * broadcast on a dead condvar (ptc_tp_destroy serializes on this lock
   * before deleting; TSan-caught) */
  std::lock_guard<ptc_mutex> g(tp->window_lock);
  tp->window_cv.notify_all();
}

static void tp_mark_complete(ptc_context *ctx, ptc_taskpool *tp) {
  bool expected = false;
  if (!tp->completed.compare_exchange_strong(expected, true)) return;
  if (ptc_context_verbose(ctx, PTC_DBG_RUNTIME) >= 1)
    std::fprintf(stderr, "ptc [runtime]: taskpool %d complete (%lld "
                         "errors)\n", tp->id,
                 (long long)tp->nb_errors.load());
  /* composition callback first: if it adds a follow-up taskpool, active_tps
   * never hits 0 between the pools and ptc_context_wait stays blocked */
  if (tp->complete_cb) tp->complete_cb(tp->complete_user, tp);
  {
    /* under the lock: see notify_drain_waiters */
    std::lock_guard<ptc_mutex> g(tp->done_lock);
    tp->done_cv.notify_all();
  }
  notify_drain_waiters(tp);
  if (ctx->active_tps.fetch_sub(1, std::memory_order_acq_rel) == 1) {
    std::lock_guard<ptc_mutex> g(ctx->wait_lock);
    ctx->wait_cv.notify_all();
  }
}

static void tp_task_done(ptc_context *ctx, ptc_taskpool *tp) {
  if (tp->qos.load(std::memory_order_relaxed))
    tp->q_executed.fetch_add(1, std::memory_order_relaxed);
  /* seq_cst pairs with ptc_tp_set_open: forbids the store-buffer interleaving
   * where the closer misses nb_tasks==0 and the last worker misses open==false
   * (both would skip completion). */
  if (tp->nb_tasks.fetch_sub(1, std::memory_order_seq_cst) == 1) {
    if (!tp->open.load(std::memory_order_seq_cst))
      tp_mark_complete(ctx, tp);
  }
  notify_drain_waiters(tp); /* PTG path: ptc_tp_drain waits on window_cv */
}

/* Abort the taskpool after a task failure: successors are deliberately NOT
 * released (their inputs would be garbage), so the pool can never drain —
 * complete it with an error mark instead and let waiters observe it. */
static void tp_abort(ptc_context *ctx, ptc_taskpool *tp) {
  tp->nb_errors.fetch_add(1, std::memory_order_acq_rel);
  ptc_flight_autodump(ctx, "taskpool abort");
  tp_mark_complete(ctx, tp);
}

/* -------- DTD task lifetime + completion -------- */
} // namespace

/* comm-layer entry to the abort path: an undeliverable by-ref payload
 * (failed device placement / transfer pull) poisons the pool the same
 * way a body error does — waiters observe the error instead of garbage */
void ptc_tp_abort_internal(ptc_context *ctx, ptc_taskpool *tp) {
  tp_abort(ctx, tp);
}

/* ---- crash-durable flight recorder (ptc-blackbox) ----
 * On SIGSEGV/SIGABRT/SIGBUS an async-signal-safe handler write()s the
 * flight-recorder ring tail + an inflight-slots snapshot to the armed
 * path (<journal dir>/crash.<rank>.ptt) before re-raising, so a fatal
 * native fault leaves the same artifact the journal's peer-loss path
 * leaves on survivors.  The .ptt header is PREFORMATTED on the normal
 * path (arm / update_meta on the journal cadence) because snprintf and
 * malloc are off-limits in the handler. */
namespace {

struct CrashState {
  std::atomic<ptc_context *> ctx{nullptr};
  char path[512] = {0};
  /* handler reads hdr/hlen without a lock: a torn read during a racing
   * update_meta costs header fields in the artifact, never event words
   * (best-effort by design; meta_lock serializes the writers) */
  char hdr[512] = {0};
  std::atomic<int32_t> hlen{0};
  std::atomic<bool> fired{false};
  std::mutex meta_lock;
  bool installed = false;
  struct sigaction prev[3] = {};
};
CrashState g_crash;
const int k_crash_sigs[3] = {SIGSEGV, SIGABRT, SIGBUS};

/* (re)format the preformatted header; g_crash.meta_lock held */
void crash_format_header(ptc_context *ctx) {
  int64_t clock[4] = {0, 0, 0, 0};
  ptc_comm_clock_stats(ctx, clock);
  int n = std::snprintf(
      g_crash.hdr, sizeof g_crash.hdr,
      "{\"rank\": %u, \"dictionary\": {}, \"class_names\": [], "
      "\"meta\": {\"flight\": 1, \"crash\": 1, \"dropped_events\": %lld, "
      "\"ring_bytes\": %lld, \"clock_offset_ns\": %lld, "
      "\"clock_err_ns\": %lld}}",
      ctx->myrank, (long long)ptc_profile_dropped(ctx),
      (long long)ctx->trace_ring_bytes.load(std::memory_order_relaxed),
      (long long)clock[0], (long long)clock[1]);
  g_crash.hlen.store((n > 0 && n < (int)sizeof g_crash.hdr) ? n : 0,
                     std::memory_order_release);
}

/* The async-signal-safe writer: open/write/close only.  ProfBuf locks
 * are taken with a BOUNDED spin — the crashed thread may itself be the
 * lock holder — and on timeout the buffer is written anyway: records
 * are 8-word aligned, so a torn in-progress append costs at most one
 * garbage event, which readers drop by key range.  ptc_now_ns here is
 * a TSC read (calibration ran at the first trace event, long before). */
void crash_write(ptc_context *ctx) {
  int fd = ::open(g_crash.path, O_WRONLY | O_CREAT | O_TRUNC, 0644);
  if (fd < 0) return;
  const char magic[8] = {'#', 'P', 'T', 'C', 'P', 'R', 'O', 'F'};
  uint32_t ver = 2, h = (uint32_t)g_crash.hlen.load(std::memory_order_acquire);
  bool ok = ::write(fd, magic, 8) == 8 && ::write(fd, &ver, 4) == 4 &&
            ::write(fd, &h, 4) == 4 &&
            (h == 0 || ::write(fd, g_crash.hdr, h) == (ssize_t)h);
  for (size_t bi = 0; ok && bi < ctx->prof.size(); bi++) {
    ProfBuf *b = ctx->prof[bi];
    int64_t spins = 0;
    bool locked = true;
    while (b->lock.test_and_set(std::memory_order_acquire))
      if (++spins > 4000000) { locked = false; break; }
    size_t n = b->cap_words ? b->count : b->words.size();
    const int64_t *base = b->words.data();
    if (n && base) {
      if (b->cap_words && b->count <= b->cap_words) {
        size_t start = (b->head + b->cap_words - b->count) % b->cap_words;
        size_t first = std::min(n, b->cap_words - start);
        (void)!::write(fd, base + start, first * sizeof(int64_t));
        if (n > first)
          (void)!::write(fd, base, (n - first) * sizeof(int64_t));
      } else if (!b->cap_words) {
        (void)!::write(fd, base, n * sizeof(int64_t));
      }
    }
    if (locked) b->lock.clear(std::memory_order_release);
  }
  /* inflight-slots snapshot: each open EXEC body as a synthetic
   * PROF_KEY_INFLIGHT instant span (relaxed loads of the MetWorker
   * watchdog slots) — what this rank was executing when it died */
  int64_t now = ptc_now_ns();
  for (size_t w = 0; ok && w < ctx->met_workers.size(); w++) {
    MetWorker *mw = ctx->met_workers[w];
    int64_t begin = mw->cur_begin.load(std::memory_order_relaxed);
    if (!begin) continue;
    int64_t mid = (int64_t)mw->cur_mid.load(std::memory_order_relaxed);
    int64_t scope = mw->cur_scope.load(std::memory_order_relaxed);
    int64_t ev[2][PROF_WORDS] = {
        {PROF_KEY_INFLIGHT, 0, mid, (int64_t)w, 0, (int64_t)w, scope, begin},
        {PROF_KEY_INFLIGHT, 1, mid, (int64_t)w, 0, (int64_t)w, scope, now}};
    (void)!::write(fd, ev, sizeof ev);
  }
  ::close(fd);
}

void crash_handler(int sig, siginfo_t *, void *) {
  ptc_context *ctx = g_crash.ctx.load(std::memory_order_relaxed);
  if (ctx && !g_crash.fired.exchange(true)) crash_write(ctx);
  /* restore the pre-arm disposition and re-raise so the process still
   * dies with the original signal (core dump + wait status intact) */
  for (int i = 0; i < 3; i++)
    if (k_crash_sigs[i] == sig) ::sigaction(sig, &g_crash.prev[i], nullptr);
  ::raise(sig);
}

} // namespace

/* internal hook: peer-loss / abort reaping leaves the crash-format
 * artifact on survivors too (same one-shot as the signal path) */
void ptc_crash_dump_if_armed(ptc_context *ctx) {
  if (g_crash.ctx.load(std::memory_order_acquire) != ctx) return;
  if (g_crash.fired.exchange(true)) return;
  crash_write(ctx);
  std::fprintf(stderr, "ptc: crash-format dump written to %s\n",
               g_crash.path);
}

extern "C" int32_t ptc_crash_arm(ptc_context_t *ctx, const char *path) {
  if (!path || !*path) return -1;
  std::lock_guard<std::mutex> g(g_crash.meta_lock);
  std::snprintf(g_crash.path, sizeof g_crash.path, "%s", path);
  crash_format_header(ctx);
  g_crash.fired.store(false, std::memory_order_relaxed);
  g_crash.ctx.store(ctx, std::memory_order_release);
  if (!g_crash.installed) {
    struct sigaction sa;
    std::memset(&sa, 0, sizeof sa);
    sa.sa_sigaction = crash_handler;
    sa.sa_flags = SA_SIGINFO;
    sigemptyset(&sa.sa_mask);
    for (int i = 0; i < 3; i++)
      ::sigaction(k_crash_sigs[i], &sa, &g_crash.prev[i]);
    g_crash.installed = true;
  }
  return 0;
}

extern "C" void ptc_crash_update_meta(ptc_context_t *ctx) {
  std::lock_guard<std::mutex> g(g_crash.meta_lock);
  if (g_crash.ctx.load(std::memory_order_relaxed) != ctx) return;
  crash_format_header(ctx);
}

extern "C" void ptc_crash_disarm(ptc_context_t *ctx) {
  std::lock_guard<std::mutex> g(g_crash.meta_lock);
  if (g_crash.ctx.load(std::memory_order_relaxed) != ctx) return;
  g_crash.ctx.store(nullptr, std::memory_order_release);
  if (g_crash.installed) {
    for (int i = 0; i < 3; i++)
      ::sigaction(k_crash_sigs[i], &g_crash.prev[i], nullptr);
    g_crash.installed = false;
  }
}

extern "C" int32_t ptc_crash_dump_now(ptc_context_t *ctx) {
  if (g_crash.ctx.load(std::memory_order_acquire) != ctx) return -1;
  if (g_crash.fired.exchange(true)) return 1; /* already written */
  crash_write(ctx);
  return 0;
}

/* Flight-recorder autodump: at most ONE dump per context (the first
 * failure is the interesting one; later aborts of cascading pools would
 * overwrite it with a trace of the wreckage).  No-op when tracing is
 * off or no dump path is armed (ring mode arms the /tmp default). */
void ptc_flight_autodump(ptc_context *ctx, const char *reason) {
  ptc_crash_dump_if_armed(ctx); /* journal-armed ranks get the crash-
                                 * format artifact (inflight snapshot
                                 * included) even with tracing off */
  if (ctx->prof_level.load(std::memory_order_relaxed) <= 0) return;
  if (ctx->flight_dump_path.empty()) return;
  if (ctx->flight_dumped.exchange(true, std::memory_order_acq_rel)) return;
  char path[512];
  std::snprintf(path, sizeof path, "%s.%u.ptt",
                ctx->flight_dump_path.c_str(), ctx->myrank);
  if (ptc_flight_dump(ctx, path) == 0)
    std::fprintf(stderr, "ptc: flight-recorder trace dumped to %s (%s)\n",
                 path, reason);
  else
    std::fprintf(stderr, "ptc: flight-recorder dump to %s FAILED (%s)\n",
                 path, reason);
}

/* ---- always-on runtime metrics (log2-bucket latency histograms) ----
 * Reference role: the PINS counter modules + aggregator_visu's live
 * counter streaming, made native so the serving stack gets p50/p99
 * without tracing on.  Recording is lock-free (per-worker histograms,
 * relaxed atomics); interning and snapshotting take met_lock, both off
 * the hot path. */

int32_t ptc_met_intern(ptc_context *ctx, const std::string &name) {
  if (name.empty()) return -1;
  std::lock_guard<std::mutex> g(ctx->met_lock);
  auto it = ctx->met_ids.find(name);
  if (it != ctx->met_ids.end()) return it->second;
  if ((int32_t)ctx->met_names.size() >= PTC_MET_MAX_CLASSES) return -1;
  int32_t mid = (int32_t)ctx->met_names.size();
  ctx->met_names.push_back(name);
  ctx->met_ids.emplace(name, mid);
  return mid;
}

MetWorker *ptc_met_worker(ptc_context *ctx, int worker) {
  size_t i = (worker < 0 || worker >= ctx->nb_workers)
                 ? (size_t)ctx->nb_workers
                 : (size_t)worker;
  return ctx->met_workers[i];
}

/* get-or-create the per-class EXEC histogram (CAS install: losers free) */
static MetHist *met_exec_hist(MetWorker *mw, int32_t mid) {
  std::atomic<MetHist *> &slot = mw->exec[(size_t)mid];
  MetHist *h = slot.load(std::memory_order_acquire);
  if (!h) {
    MetHist *nh = new MetHist();
    if (slot.compare_exchange_strong(h, nh, std::memory_order_acq_rel))
      h = nh;
    else
      delete nh;
  }
  return h;
}

static void met_record_mw(MetWorker *mw, int kind, int32_t mid, int64_t ns) {
  if (kind == PTC_MET_EXEC && mid >= 0 && mid < PTC_MET_MAX_CLASSES)
    met_exec_hist(mw, mid)->record(ns);
  else if (kind >= 0 && kind < PTC_MET_NKINDS)
    mw->kind[kind].record(ns);
}

void ptc_met_record(ptc_context *ctx, int worker, int kind, int32_t mid,
                    int64_t ns) {
  if (!ctx->metrics_on.load(std::memory_order_relaxed)) return;
  met_record_mw(ptc_met_worker(ctx, worker), kind, mid, ns);
}

/* release-sampling stride -> power-of-two mask (stride rounds UP, so
 * the realized sampling rate never exceeds the requested one) */
static int32_t met_pow2_mask(int32_t n) {
  if (n <= 1) return 0;
  int32_t p = 1;
  while (p < n && p < (1 << 30)) p <<= 1;
  return p - 1;
}

/* one aggregated record: (kind, mid) summed across workers */
namespace {
struct MetAggRec {
  int32_t kind;
  int32_t mid; /* -1 = no class / unnamed overflow */
  int64_t count = 0, sum = 0;
  std::vector<int64_t> b;
  MetAggRec(int32_t k, int32_t m)
      : kind(k), mid(m), b((size_t)PTC_MET_BUCKETS, 0) {}
};

static void met_fold_hist(MetAggRec &r, const MetHist &h) {
  r.count += h.count.load(std::memory_order_relaxed);
  r.sum += h.sum.load(std::memory_order_relaxed);
  for (int i = 0; i < PTC_MET_BUCKETS; i++)
    r.b[(size_t)i] += h.b[i].load(std::memory_order_relaxed);
}

/* local per-worker histograms -> aggregated records (count > 0 only) */
static void met_aggregate_local(ptc_context *ctx,
                                std::vector<MetAggRec> &out) {
  for (int32_t mid = 0; mid < PTC_MET_MAX_CLASSES; mid++) {
    MetAggRec r(PTC_MET_EXEC, mid);
    for (MetWorker *mw : ctx->met_workers) {
      MetHist *h = mw->exec[(size_t)mid].load(std::memory_order_acquire);
      if (h) met_fold_hist(r, *h);
    }
    if (r.count > 0) out.push_back(std::move(r));
  }
  for (int kind = 0; kind < PTC_MET_NKINDS; kind++) {
    MetAggRec r((int32_t)kind, -1);
    for (MetWorker *mw : ctx->met_workers)
      met_fold_hist(r, mw->kind[kind]);
    if (r.count > 0) out.push_back(std::move(r));
  }
}

/* tiny native-endian byte writer/reader for the MSG_METRICS body (the
 * comm layer's Writer/Reader are file-local to comm.cpp) */
template <typename T>
static void met_put(std::vector<uint8_t> &v, T x) {
  const uint8_t *p = (const uint8_t *)&x;
  v.insert(v.end(), p, p + sizeof(T));
}
template <typename T>
static bool met_get(const uint8_t *&p, const uint8_t *end, T &x) {
  if ((size_t)(end - p) < sizeof(T)) return false;
  std::memcpy(&x, p, sizeof(T));
  p += sizeof(T);
  return true;
}
} // namespace

/* wire body: [u32 nrec] then per record [u8 kind][u16 nlen][name bytes]
 * [i64 count][i64 sum][u16 npairs][(u16 bucket, i64 count)*] — buckets
 * ship sparse (real workloads touch a handful of octaves). */
void ptc_met_serialize(ptc_context *ctx, std::vector<uint8_t> &out) {
  std::vector<MetAggRec> recs;
  std::vector<std::string> names;
  {
    std::lock_guard<std::mutex> g(ctx->met_lock);
    met_aggregate_local(ctx, recs);
    names = ctx->met_names;
  }
  met_put<uint32_t>(out, (uint32_t)recs.size());
  for (const MetAggRec &r : recs) {
    std::string name;
    if (r.kind == PTC_MET_EXEC && r.mid >= 0 &&
        (size_t)r.mid < names.size())
      name = names[(size_t)r.mid];
    met_put<uint8_t>(out, (uint8_t)r.kind);
    met_put<uint16_t>(out, (uint16_t)name.size());
    out.insert(out.end(), name.begin(), name.end());
    met_put<int64_t>(out, r.count);
    met_put<int64_t>(out, r.sum);
    uint16_t npairs = 0;
    for (int i = 0; i < PTC_MET_BUCKETS; i++)
      if (r.b[(size_t)i]) npairs++;
    met_put<uint16_t>(out, npairs);
    for (int i = 0; i < PTC_MET_BUCKETS; i++)
      if (r.b[(size_t)i]) {
        met_put<uint16_t>(out, (uint16_t)i);
        met_put<int64_t>(out, r.b[(size_t)i]);
      }
  }
}

void ptc_met_absorb(ptc_context *ctx, uint32_t from, int64_t rtt_ns,
                    int64_t offset_ns, const uint8_t *body, size_t len) {
  const uint8_t *p = body, *end = body + len;
  uint32_t nrec = 0;
  if (!met_get(p, end, nrec) || nrec > 4096) return;
  MetRemote rem;
  rem.rtt_ns = rtt_ns;
  rem.offset_ns = offset_ns;
  rem.recs.reserve(nrec);
  for (uint32_t i = 0; i < nrec; i++) {
    MetRemote::Rec rec;
    uint8_t kind;
    uint16_t nlen, npairs;
    if (!met_get(p, end, kind) || !met_get(p, end, nlen)) return;
    if ((size_t)(end - p) < nlen) return;
    rec.kind = kind;
    rec.name.assign((const char *)p, nlen);
    p += nlen;
    if (!met_get(p, end, rec.count) || !met_get(p, end, rec.sum) ||
        !met_get(p, end, npairs))
      return;
    rec.pairs.reserve(npairs);
    for (uint16_t j = 0; j < npairs; j++) {
      uint16_t idx;
      int64_t c;
      if (!met_get(p, end, idx) || !met_get(p, end, c)) return;
      if (idx < PTC_MET_BUCKETS) rec.pairs.emplace_back((int32_t)idx, c);
    }
    rem.recs.push_back(std::move(rec));
  }
  std::lock_guard<std::mutex> g(ctx->met_lock);
  ctx->met_peers[from] = std::move(rem);
}

/* ---- paired-event trace (reference: parsec/profiling.c + the PINS hook
 * points of parsec/mca/pins/pins.h:26-54; format doc at PROF_WORDS).    */
/* PINS: synchronous instrumentation callback chain at the event points
 * (reference: parsec/mca/pins/pins.h:26-54 — modules hook task
 * select/exec/complete; here one registered sink fans out to the Python
 * module chain).  Disabled = one relaxed load + branch. */
static inline void pins_fire(ptc_context *ctx, int64_t key,
                             const int64_t w[PROF_WORDS]) {
  /* acquire pairs with the exchange in ptc_set_pins_cb; cb+user+mask are
   * one immutable block, so no torn pairing across a swap */
  ptc_context::PinsState *st =
      ctx->pins_state.load(std::memory_order_acquire);
  if (st && ((st->mask >> key) & 1)) st->cb(st->user, w);
}

void ptc_set_pins_cb(ptc_context_t *ctx, ptc_pins_cb cb, void *user,
                     uint64_t key_mask) {
  /* Callers must keep the old cb's trampoline alive for the context's
   * lifetime: a reader that loaded the old block may still invoke it
   * briefly after the swap.  Old blocks are retired, not freed, for the
   * same reason (installs are rare; freed at context destroy). */
  ptc_context::PinsState *ns =
      cb ? new ptc_context::PinsState{cb, user, key_mask} : nullptr;
  ptc_context::PinsState *old =
      ctx->pins_state.exchange(ns, std::memory_order_acq_rel);
  if (old) {
    std::lock_guard<std::mutex> g(ctx->pins_lock);
    ctx->pins_retired.push_back(old);
  }
}

void ptc_prof_push(ptc_context *ctx, int worker, int64_t key, int64_t phase,
                   int64_t class_id, int64_t l0, int64_t l1, int64_t aux,
                   int32_t min_level) {
  bool trace = ctx->prof_level.load(std::memory_order_relaxed) >= min_level;
  bool pins = ctx->pins_state.load(std::memory_order_relaxed) != nullptr;
  if (!trace && !pins) return;
  int64_t w[PROF_WORDS] = {key,         phase, class_id, l0, l1,
                           (int64_t)worker, aux,   ptc_now_ns()};
  if (trace) {
    ProfBuf *b = ctx->prof[(size_t)(worker < 0 ? 0 : worker)];
    ProfLockGuard g(b);
    b->append(w, PROF_WORDS);
  }
  if (pins) pins_fire(ctx, key, w);
}

void ptc_prof_instant(ptc_context *ctx, int64_t key, int64_t class_id,
                      int64_t l0, int64_t l1, int64_t aux) {
  bool trace = ctx->prof_level.load(std::memory_order_relaxed) >= 1;
  bool pins = ctx->pins_state.load(std::memory_order_relaxed) != nullptr;
  if (!trace && !pins) return;
  int64_t now = ptc_now_ns();
  int64_t w[2 * PROF_WORDS] = {key, 0, class_id, l0, l1, -1, aux, now,
                               key, 1, class_id, l0, l1, -1, aux, now};
  if (pins) pins_fire(ctx, key, w); /* begin event only: instant span */
  if (!trace) return;
  ProfBuf *b = ctx->prof[0];
  ProfLockGuard g(b);
  b->append(w, 2 * PROF_WORDS);
}

namespace {

static void prof_event(ptc_context *ctx, int worker, int64_t key,
                       int64_t phase, ptc_task *t, int32_t min_level) {
  bool trace = ctx->prof_level.load(std::memory_order_relaxed) >= min_level;
  bool pins = ctx->pins_state.load(std::memory_order_relaxed) != nullptr;
  if (!trace && !pins) return;
  /* aux carries the owning pool's request scope (0 = unscoped): the
   * per-request timeline decomposition keys EXEC/RELEASE spans on it */
  int64_t scope = (t && t->tp)
                      ? t->tp->scope_id.load(std::memory_order_relaxed)
                      : 0;
  ptc_prof_push(ctx, worker, key, phase, t ? t->class_id : -1,
                t ? t->locals[0] : 0, t ? t->locals[1] : 0, scope,
                min_level);
}

/* begin+end of a zero-duration body as ONE buffer transaction (one lock,
 * one timestamp) — the noop-chore dispatch path; PINS still sees both
 * phases as separate callbacks */
static void prof_event_pair(ptc_context *ctx, int worker, int64_t key,
                            ptc_task *t) {
  bool trace = ctx->prof_level.load(std::memory_order_relaxed) >= 1;
  bool pins = ctx->pins_state.load(std::memory_order_relaxed) != nullptr;
  if (!trace && !pins) return;
  int64_t now = ptc_now_ns();
  int64_t cid = t ? t->class_id : -1;
  int64_t l0 = t ? t->locals[0] : 0, l1 = t ? t->locals[1] : 0;
  int64_t sc = (t && t->tp)
                   ? t->tp->scope_id.load(std::memory_order_relaxed)
                   : 0;
  int64_t w[2 * PROF_WORDS] = {key, 0, cid, l0, l1, (int64_t)worker, sc, now,
                               key, 1, cid, l0, l1, (int64_t)worker, sc, now};
  if (trace) {
    ProfBuf *b = ctx->prof[(size_t)(worker < 0 ? 0 : worker)];
    ProfLockGuard g(b);
    b->append(w, 2 * PROF_WORDS);
  }
  if (pins) {
    pins_fire(ctx, key, w);
    pins_fire(ctx, key, w + PROF_WORDS);
  }
}

/* dep edge = consecutive src/dst event pair, pushed under ONE lock so a
 * concurrent pusher on the same buffer cannot interleave them.  dst
 * identity is the peer task's declaration-order (locals[0], locals[1]) —
 * the same identity its own EXEC/src events carry. */
static void prof_edge(ptc_context *ctx, int worker, ptc_task *src,
                      int64_t dst_class, int64_t dl0, int64_t dl1) {
  if (ctx->prof_level.load(std::memory_order_relaxed) < 2) return;
  ProfBuf *b = ctx->prof[(size_t)(worker < 0 ? 0 : worker)];
  ProfLockGuard g(b);
  int64_t now = ptc_now_ns();
  int64_t w[2 * PROF_WORDS] = {
      PROF_KEY_EDGE, 0, src ? src->class_id : -1,
      src ? src->locals[0] : 0, src ? src->locals[1] : 0,
      (int64_t)worker, 0, now,
      PROF_KEY_EDGE, 1, dst_class, dl0, dl1,
      (int64_t)worker, 0, now};
  b->append(w, 2 * PROF_WORDS);
}

/* PTG-path edge: dep params arrive in range-param order; translate them
 * through the peer class's range_locals (+ derived locals) so the dst
 * node matches that task's EXEC identity in the captured DAG. */
static void prof_edge_params(ptc_context *ctx, int worker, ptc_task *src,
                             ptc_taskpool *tp, int32_t peer_class,
                             const int64_t *params, size_t nparams) {
  if (ctx->prof_level.load(std::memory_order_relaxed) < 2) return;
  const TaskClass &tc = tp->classes[(size_t)peer_class];
  int64_t locals[PTC_MAX_LOCALS] = {0};
  for (size_t i = 0; i < tc.range_locals.size() && i < nparams; i++)
    locals[tc.range_locals[(size_t)i]] = params[i];
  fill_derived_locals(ctx, tp, tc, locals);
  prof_edge(ctx, worker, src, peer_class, locals[0], locals[1]);
}

static void dyn_retain(ptc_task *t) {
  t->dyn->refs.fetch_add(1, std::memory_order_relaxed);
}

static void dyn_release(ptc_task *t) {
  if (t->dyn->refs.fetch_sub(1, std::memory_order_acq_rel) == 1) {
    delete t->dyn;
    delete t; /* dyn tasks never enter the freelist */
  }
}

static void dyn_complete_task(ptc_context *ctx, int worker, ptc_task *t) {
  ptc_taskpool *tp = t->tp;
  DynExt *dx = t->dyn;
  /* version bumps MUST precede successor release: the device layer keys
   * its copy mirrors by version (same order as the PTG path, which bumps
   * in release_deps before delivering) */
  for (int f = 0; f < dx->nb_flows; f++)
    if (t->data[f] && (dx->modes[f] & PTC_DTD_OUTPUT))
      t->data[f]->version.fetch_add(1, std::memory_order_release);
  /* distributed: tell every shadow of this task that it finished (carries
   * the written-tile payloads) before releasing local successors */
  if (!dx->shadow && ctx->nodes > 1)
    ptc_comm_send_dtd_complete(ctx, tp, t);
  std::vector<ptc_task *> succs;
  {
    std::lock_guard<std::mutex> g(dx->lock);
    dx->completed = true;
    succs.swap(dx->succs);
  }
  for (ptc_task *s : succs) {
    prof_edge(ctx, worker, t, s->class_id, s->locals[0], s->locals[1]);
    if (s->dyn->remaining.fetch_sub(1, std::memory_order_acq_rel) == 1)
      schedule_task(ctx, worker, s);
  }
  for (int f = 0; f < dx->nb_flows; f++)
    if (t->data[f]) copy_release(ctx, t->data[f]);
  dyn_release(t);
  tp->busy.fetch_add(1, std::memory_order_acquire);
  tp_task_done(ctx, tp); /* decrement before waking window waiters */
  {
    /* under the lock: see notify_drain_waiters */
    std::lock_guard<ptc_mutex> g(tp->window_lock);
    tp->window_cv.notify_all();
  }
  tp->busy.fetch_sub(1, std::memory_order_release); /* LAST tp access */
}

static void complete_task(ptc_context *ctx, int worker, ptc_task *t) {
  if (t->dyn) {
    dyn_complete_task(ctx, worker, t);
    return;
  }
  ptc_taskpool *tp = t->tp;
  const TaskClass &tc = tp->classes[(size_t)t->class_id];
  if (tc.is_coll)
    ctx->coll_steps.fetch_add(1, std::memory_order_relaxed);
  /* RELEASE spans are level-2 trace events: level 1 (the dispatch
   * bench's lean setting) pays two locked pushes per task, not four.
   * PINS sinks still see them at any level (mask-gated). */
  /* always-on metrics: release latency is 1-in-N SAMPLED (met_rel_mask)
   * — the full clock pair on every task would cost ~20 ns on the noop
   * dispatch path, which the level-0 <5% overhead contract forbids; the
   * steady-state cost is one relaxed fetch_add on the worker's own line */
  int64_t r0 = 0;
  MetWorker *mw = nullptr;
  if (ctx->metrics_on.load(std::memory_order_relaxed)) {
    mw = ptc_met_worker(ctx, worker);
    int32_t mask = ctx->met_rel_mask.load(std::memory_order_relaxed);
    /* load+store, not fetch_add: the tick is a sampling phase, not a
     * count — a lost increment when two external-slot writers collide
     * only shifts which task gets sampled, and the RMW's lock prefix
     * is the single biggest cost in the level-0 metrics path */
    int64_t tick = mw->rel_tick.load(std::memory_order_relaxed);
    mw->rel_tick.store(tick + 1, std::memory_order_relaxed);
    if ((tick & mask) == 0) r0 = ptc_now_ns();
  }
  prof_event(ctx, worker, PROF_KEY_RELEASE, 0, t, /*min_level=*/2);
  release_deps(ctx, worker, t);
  prof_event(ctx, worker, PROF_KEY_RELEASE, 1, t, /*min_level=*/2);
  if (r0) mw->kind[PTC_MET_RELEASE].record(ptc_now_ns() - r0);
  for (size_t f = 0; f < tc.flows.size(); f++)
    if (t->data[f]) copy_release(ctx, t->data[f]);
  task_free(ctx, t);
  tp->busy.fetch_add(1, std::memory_order_acquire);
  tp_task_done(ctx, tp);
  tp->busy.fetch_sub(1, std::memory_order_release); /* LAST tp access */
}

/* A task failed (body error / no runnable chore): do NOT release successors
 * — their inputs would be garbage — abort the whole taskpool instead. */
static void fail_task(ptc_context *ctx, ptc_task *t) {
  ptc_taskpool *tp = t->tp;
  const TaskClass &tc = tp->classes[(size_t)t->class_id];
  for (size_t f = 0; f < tc.flows.size(); f++)
    if (t->data[f]) copy_release(ctx, t->data[f]);
  task_free(ctx, t);
  tp->busy.fetch_add(1, std::memory_order_acquire);
  tp_abort(ctx, tp);
  tp->busy.fetch_sub(1, std::memory_order_release); /* LAST tp access */
}

/* (prof_event / ptc_prof_push defined above dyn_complete_task) */

/* DTD failure: same taskpool-abort semantics as fail_task */
static void dyn_fail_task(ptc_context *ctx, ptc_task *t) {
  ptc_taskpool *tp = t->tp;
  DynExt *dx = t->dyn;
  {
    std::lock_guard<std::mutex> g(dx->lock);
    dx->completed = true; /* successors are never released */
  }
  for (int f = 0; f < dx->nb_flows; f++)
    if (t->data[f]) copy_release(ctx, t->data[f]);
  dyn_release(t);
  tp->busy.fetch_add(1, std::memory_order_acquire);
  tp_abort(ctx, tp);
  {
    /* under the lock: see notify_drain_waiters */
    std::lock_guard<ptc_mutex> g(tp->window_lock);
    tp->window_cv.notify_all();
  }
  tp->busy.fetch_sub(1, std::memory_order_release); /* LAST tp access */
}

/* single-chore execution for dynamic tasks */
static void execute_dyn(ptc_context *ctx, int worker, ptc_task *t) {
  DynExt *dx = t->dyn;
  int32_t rc = PTC_HOOK_DONE;
  if (dx->shadow) {
    /* shadow of a remote task: its "body" is the arrival of the owner's
     * completion message.  All local predecessor deps are satisfied here;
     * the message dep was registered at insertion (comm.cpp releases it). */
    complete_task(ctx, worker, t);
    return;
  }
  switch (dx->body_kind) {
  case PTC_BODY_NOOP:
    prof_event_pair(ctx, worker, PROF_KEY_EXEC, t);
    break;
  case PTC_BODY_CB: {
    BodyCb &cb = ctx->body_cbs[(size_t)dx->body_arg];
    /* DTD bodies share one interned class ("dtd"); same inflight-slot
     * protocol as the PTG path so the watchdog sees them too */
    bool met = ctx->metrics_on.load(std::memory_order_relaxed);
    MetWorker *mw = nullptr;
    int64_t m0 = 0;
    if (met) {
      mw = ptc_met_worker(ctx, worker);
      m0 = ptc_now_ns();
      mw->cur_mid.store(ctx->met_dtd_mid, std::memory_order_relaxed);
      mw->cur_scope.store(
          t->tp ? t->tp->scope_id.load(std::memory_order_relaxed) : 0,
          std::memory_order_relaxed);
      mw->cur_begin.store(m0, std::memory_order_relaxed);
    }
    prof_event(ctx, worker, PROF_KEY_EXEC, 0, t);
    rc = cb.fn(cb.user, t);
    prof_event(ctx, worker, PROF_KEY_EXEC, 1, t);
    if (met) {
      mw->cur_begin.store(0, std::memory_order_relaxed);
      mw->cur_mid.store(-1, std::memory_order_relaxed);
      mw->cur_scope.store(0, std::memory_order_relaxed);
      met_record_mw(mw, PTC_MET_EXEC, ctx->met_dtd_mid,
                    ptc_now_ns() - m0);
    }
    break;
  }
  case PTC_BODY_DEVICE: {
    DeviceQueue *q = ctx->dev_queues[(size_t)dx->body_arg];
    q->depth.fetch_add(1, std::memory_order_relaxed);
    {
      std::lock_guard<ptc_mutex> g(q->lock);
      q->dq.push_back(t);
    }
    q->cv.notify_one();
    return; /* ASYNC */
  }
  default:
    rc = PTC_HOOK_ERROR;
  }
  switch (rc) {
  case PTC_HOOK_DONE:
    complete_task(ctx, worker, t);
    return;
  case PTC_HOOK_AGAIN: {
    /* same spin guard as the PTG AGAIN path: the bypass slot would
     * re-execute the task immediately, starving whatever it waits on */
    bool save = tl_bypass;
    tl_bypass = false;
    schedule_task(ctx, worker, t);
    tl_bypass = save;
    return;
  }
  case PTC_HOOK_ASYNC:
    return;
  default:
    std::fprintf(stderr, "ptc: dtd task body error (%d); aborting taskpool\n",
                 rc);
    dyn_fail_task(ctx, t);
    return;
  }
}

/* chore execution protocol (reference: __parsec_execute,
 * parsec/scheduling.c:124-203) */
static void execute_task(ptc_context *ctx, int worker, ptc_task *t) {
  if (t->dyn) {
    execute_dyn(ctx, worker, t);
    return;
  }
  ptc_taskpool *tp = t->tp;
  TaskClass &tc = tp->classes[(size_t)t->class_id];
  if (prepare_input(ctx, t) != 0) {
    fail_task(ctx, t);
    return;
  }
  /* best-device selection (reference: parsec_get_best_device,
   * parsec/mca/device/device.c:79-160): when a class offers several
   * enabled DEVICE chores and the first enabled chore is one of them,
   * route to the queue with the lowest load/weight instead of blindly
   * taking declaration order.  CPU-first classes are untouched. */
  if (t->chore_idx == 0) {
    int32_t best = -1, n_dev = 0;
    double best_load = 0.0;
    bool first_enabled_is_device = false;
    /* candidate table for the affinity pass (chores are few; >16 device
     * chores would only lose affinity for the overflow, never routing) */
    enum { MAX_CAND = 16 };
    int32_t cand_idx[MAX_CAND];
    int64_t cand_qid[MAX_CAND];
    double cand_load[MAX_CAND];
    for (int32_t i = 0; i < (int32_t)tc.chores.size(); i++) {
      Chore &ch = tc.chores[(size_t)i];
      if (ch.disabled.load(std::memory_order_relaxed)) continue;
      bool is_dev = (ch.body_kind == PTC_BODY_DEVICE);
      if (n_dev == 0 && best == -1 && !is_dev) break; /* CPU first: keep */
      if (!is_dev) continue;
      if (best == -1) first_enabled_is_device = true;
      DeviceQueue *q = ctx->dev_queues[(size_t)ch.body_arg];
      double w = q->weight.load(std::memory_order_relaxed);
      /* projected completion load INCLUDING this task (+1): an idle slow
       * device must not tie with a fast one (reference folds the task's
       * own weight in the same way, device.c:129-141) */
      double load = (1.0 + (double)q->depth.load(std::memory_order_relaxed))
                    / (w > 0.0 ? w : 1e-9);
      if (n_dev < MAX_CAND) {
        cand_idx[n_dev] = i;
        cand_qid[n_dev] = ch.body_arg;
        cand_load[n_dev] = load;
      }
      if (best == -1 || load < best_load) { best = i; best_load = load; }
      n_dev++;
    }
    if (first_enabled_is_device && n_dev >= 2) {
      t->chore_idx = best;
      /* data-affinity pass (reference: device.c:100-117): a queue that
       * already holds a current mirror of one of this task's flows —
       * write flows first, read flows as fallback — wins over pure
       * load, unless its load is skewed past the best candidate's. */
      double skew = ctx->affinity_skew.load(std::memory_order_relaxed);
      if (skew > 0.0) {
        int32_t aff = -1;
        double aff_load = 0.0;
        int cap = n_dev < (int)MAX_CAND ? n_dev : (int)MAX_CAND;
        for (int pass = 0; pass < 2 && aff == -1; pass++) {
          for (int32_t f = 0;
               f < (int32_t)tc.flows.size() && aff == -1; f++) {
            Flow &fl = tc.flows[(size_t)f];
            if (fl.flags & PTC_FLOW_CTL) continue;
            bool wr = (fl.flags & PTC_FLOW_WRITE) != 0;
            if (pass == 0 ? !wr : wr) continue;
            ptc_copy *c = t->data[f];
            if (!c || c->handle == 0) continue;
            uint64_t pack;
            {
              std::lock_guard<std::mutex> g(ctx->owner_lock);
              auto it = ctx->data_owner.find(c->handle);
              if (it == ctx->data_owner.end()) continue;
              pack = it->second;
            }
            if ((int32_t)(uint32_t)pack !=
                c->version.load(std::memory_order_relaxed))
              continue; /* stale mirror */
            for (int j = 0; j < cap; j++)
              if (cand_qid[j] == (int64_t)(int32_t)(pack >> 32)) {
                aff = cand_idx[j];
                aff_load = cand_load[j];
                break;
              }
          }
        }
        if (aff >= 0 && aff_load <= skew * best_load) t->chore_idx = aff;
      }
    }
  }
  while (t->chore_idx < (int32_t)tc.chores.size()) {
    Chore &ch = tc.chores[(size_t)t->chore_idx];
    if (ch.disabled.load(std::memory_order_relaxed)) { t->chore_idx++; continue; }
    int32_t rc = PTC_HOOK_DONE;
    switch (ch.body_kind) {
    case PTC_BODY_NOOP:
      rc = PTC_HOOK_DONE;
      break;
    case PTC_BODY_CB: {
      BodyCb &cb = ctx->body_cbs[(size_t)ch.body_arg];
      /* always-on metrics: bracket the body with its own clock pair
       * (~10 ns each, trivial against a real body) and publish the
       * inflight slot the watchdog's stuck-task scan reads.  Noop
       * chores stay unmetered — their "duration" is the dispatch
       * path itself, which the level-0 overhead contract protects. */
      bool met = ctx->metrics_on.load(std::memory_order_relaxed);
      MetWorker *mw = nullptr;
      int64_t m0 = 0;
      if (met) {
        mw = ptc_met_worker(ctx, worker);
        m0 = ptc_now_ns();
        mw->cur_mid.store(tc.metric_id, std::memory_order_relaxed);
        mw->cur_scope.store(
            t->tp ? t->tp->scope_id.load(std::memory_order_relaxed) : 0,
            std::memory_order_relaxed);
        mw->cur_begin.store(m0, std::memory_order_relaxed);
      }
      prof_event(ctx, worker, PROF_KEY_EXEC, 0, t);
      rc = cb.fn(cb.user, t);
      prof_event(ctx, worker, PROF_KEY_EXEC, 1, t);
      if (met) {
        mw->cur_begin.store(0, std::memory_order_relaxed);
        mw->cur_mid.store(-1, std::memory_order_relaxed);
        mw->cur_scope.store(0, std::memory_order_relaxed);
        met_record_mw(mw, PTC_MET_EXEC, tc.metric_id,
                      ptc_now_ns() - m0);
      }
      break;
    }
    case PTC_BODY_DEVICE: {
      DeviceQueue *q = ctx->dev_queues[(size_t)ch.body_arg];
      q->depth.fetch_add(1, std::memory_order_relaxed);
      {
        std::lock_guard<ptc_mutex> g(q->lock);
        q->dq.push_back(t);
      }
      q->cv.notify_one();
      rc = PTC_HOOK_ASYNC;
      break;
    }
    default:
      rc = PTC_HOOK_ERROR;
    }
    switch (rc) {
    case PTC_HOOK_DONE:
      if (ch.body_kind == PTC_BODY_NOOP)
        prof_event_pair(ctx, worker, PROF_KEY_EXEC, t);
      complete_task(ctx, worker, t);
      return;
    case PTC_HOOK_ASYNC:
      return; /* ownership transferred */
    case PTC_HOOK_AGAIN: {
      /* AGAIN means "requeue, try later" — the bypass slot would
       * re-execute it immediately and spin; force the scheduler path */
      bool save = tl_bypass;
      tl_bypass = false;
      schedule_task(ctx, worker, t);
      tl_bypass = save;
      return;
    }
    case PTC_HOOK_NEXT:
      t->chore_idx++;
      continue;
    case PTC_HOOK_DISABLE:
      ch.disabled.store(true, std::memory_order_relaxed);
      t->chore_idx++;
      continue;
    default:
      std::fprintf(stderr,
                   "ptc: task class %s body error (%d); aborting taskpool\n",
                   tc.name.c_str(), rc);
      fail_task(ctx, t);
      return;
    }
  }
  std::fprintf(stderr,
               "ptc: task class %s has no runnable chore; aborting taskpool\n",
               tc.name.c_str());
  fail_task(ctx, t);
}

/* Pin this thread to one core (reference: the hwloc thread-binding layer,
 * parsec/parsec_hwloc.c + bindthread.c — workers bound round-robin over
 * the allowed cpuset).  Returns the bound cpu or -1. */
static int bind_worker_thread(int worker) {
#if defined(__linux__)
  cpu_set_t allowed;
  CPU_ZERO(&allowed);
  if (sched_getaffinity(0, sizeof(allowed), &allowed) != 0) return -1;
  int ncpu = CPU_COUNT(&allowed);
  if (ncpu <= 0) return -1;
  int pick = worker % ncpu, seen = 0, cpu = -1;
  for (int c = 0; c < CPU_SETSIZE; c++) {
    if (!CPU_ISSET(c, &allowed)) continue;
    if (seen++ == pick) { cpu = c; break; }
  }
  if (cpu < 0) return -1;
  cpu_set_t one;
  CPU_ZERO(&one);
  CPU_SET(cpu, &one);
  if (pthread_setaffinity_np(pthread_self(), sizeof(one), &one) != 0)
    return -1;
  return cpu;
#else
  (void)worker;
  return -1;
#endif
}

/* worker main loop (reference: __parsec_context_wait,
 * parsec/scheduling.c:535-666) */
static void worker_main(ptc_context *ctx, int worker) {
  if (ctx->bind_mode == 1) {
    int cpu = bind_worker_thread(worker);
    ctx->worker_cpu[(size_t)worker]->store(cpu, std::memory_order_relaxed);
  }
  /* magazine routing: this thread now owns ctx's per-worker freelists */
  tl_mag_ctx = ctx;
  tl_mag_worker = worker;
  std::atomic<int64_t> *bypass_ctr = ctx->worker_bypass[(size_t)worker];
  std::atomic<int64_t> *exec_ctr = ctx->worker_executed[(size_t)worker];
  std::atomic<int64_t> *done_ctr = &ctx->flight[(size_t)worker]->done;
  int misses = 0;
  tl_bypass = true;
  while (!ctx->shutdown.load(std::memory_order_acquire)) {
    ptc_task *t = tl_next_task;
    if (t) {
      tl_next_task = nullptr; /* bypass hit: no scheduler round-trip */
      tick1(*bypass_ctr);
    } else {
      t = ctx->sched->select(worker);
    }
    if (t) {
      misses = 0;
      tick1(*exec_ctr); /* single writer: this worker */
      execute_task(ctx, worker, t);
      /* after the push to a device queue and after complete_task
       * scheduled the successors: a reader that sees 0 and then finds
       * a device queue empty has missed nothing this task routed */
      done_ctr->store(done_ctr->load(std::memory_order_relaxed) + 1,
                      std::memory_order_release);
      continue;
    }
    if (++misses < 64) {
      std::this_thread::yield();
      continue;
    }
    std::unique_lock<ptc_mutex> lk(ctx->idle_lock);
    int64_t sig = ctx->work_signal.load(std::memory_order_acquire);
    ctx->idle_cv.wait_for(lk, std::chrono::milliseconds(1), [&] {
      return ctx->shutdown.load(std::memory_order_acquire) ||
             ctx->work_signal.load(std::memory_order_acquire) != sig;
    });
    misses = 0;
  }
  /* a successor kept across the shutdown check must not leak: hand it
   * back so destroy-time accounting sees it */
  if (tl_next_task) {
    ctx->sched->schedule(worker, tl_next_task);
    tl_next_task = nullptr;
  }
  tl_bypass = false;
}

/* ------------------------------------------------------------------ */
/* startup enumeration (reference: generated startup tasks,
 * jdf2c startup generator — here: direct interpreted enumeration)     */
/* ------------------------------------------------------------------ */

struct StartupStats {
  int64_t nb_local = 0;
  std::vector<ptc_task *> ready;
};

static void enumerate_class(ptc_context *ctx, ptc_taskpool *tp,
                            const TaskClass &tc, StartupStats &st) {
  size_t nb_range = tc.range_locals.size();
  int nb_locals = (int)tc.locals.size();
  const int64_t *g = tp->globals.data();
  int64_t locals[PTC_MAX_LOCALS] = {0};
  /* bounding box over ALL instances (pre-affinity: remote deliveries
   * target local tasks, a superset box is always safe) — feeds the
   * dense dependency engine (parsec_internal.h:201-216 analog) */
  std::vector<int64_t> bmin(nb_range, INT64_MAX), bmax(nb_range, INT64_MIN);
  int64_t visited = 0;

  /* odometer over range locals, honoring declaration order so later ranges
   * may reference earlier locals (incl. derived ones in between).  For
   * comprehension locals `cur` walks the ITERATOR; the slot holds the
   * mapped value (the value expr reads the slot as the iterator). */
  struct R { int64_t lo, hi, st, cur; };
  std::vector<R> rs(nb_range);

  auto set_slot = [&](size_t i) {
    const Local &l = tc.locals[(size_t)tc.range_locals[i]];
    int32_t idx = tc.range_locals[i];
    locals[idx] = rs[i].cur;
    if (l.is_compr)
      locals[idx] = eval_expr(l.value, ctx, locals, nb_locals, g);
  };

  /* recompute range i bounds from current locals */
  auto init_range = [&](size_t i) -> bool {
    const Local &l = tc.locals[(size_t)tc.range_locals[i]];
    /* derived locals appearing before this range must be current */
    fill_derived_locals(ctx, tp, tc, locals);
    rs[i].lo = eval_expr(l.lo, ctx, locals, nb_locals, g);
    rs[i].hi = eval_expr(l.hi, ctx, locals, nb_locals, g);
    rs[i].st = eval_expr(l.st, ctx, locals, nb_locals, g, 1);
    if (rs[i].st == 0) rs[i].st = 1;
    rs[i].cur = rs[i].lo;
    bool live =
        (rs[i].st > 0) ? rs[i].cur <= rs[i].hi : rs[i].cur >= rs[i].hi;
    if (live) set_slot(i);
    return live;
  };

  auto visit = [&]() {
    fill_derived_locals(ctx, tp, tc, locals);
    visited++;
    for (size_t i = 0; i < nb_range; i++) {
      int64_t v = locals[tc.range_locals[i]];
      if (v < bmin[i]) bmin[i] = v;
      if (v > bmax[i]) bmax[i] = v;
    }
    /* affinity filter (owner-computes; reference ": desc(m,n)" placement) */
    if (tc.aff_dc >= 0 && ctx->nodes > 1) {
      int64_t idx[PTC_MAX_LOCALS];
      int ni = (int)tc.aff_idx.size();
      for (int i = 0; i < ni; i++)
        idx[i] = eval_expr(tc.aff_idx[(size_t)i], ctx, locals, nb_locals, g);
      if (ptc_collection_rank_of(ctx, tc.aff_dc, idx, ni) != ctx->myrank)
        return;
    }
    st.nb_local++;
    if (count_task_inputs(ctx, tp, tc, locals) == 0) {
      std::vector<int64_t> params(nb_range);
      for (size_t i = 0; i < nb_range; i++)
        params[i] = locals[tc.range_locals[i]];
      st.ready.push_back(make_task(ctx, tp, tc, params, nullptr));
    }
  };

  if (nb_range == 0) {
    visit();
    return;
  }
  auto walk = [&]() {
    /* init all ranges; empty range -> no tasks */
    size_t level = 0;
    if (!init_range(0)) return;
    while (true) {
      if (level + 1 < nb_range) {
        if (init_range(level + 1)) {
          level++;
          continue;
        }
        /* inner range empty for this outer value: fall through to advance */
      } else {
        visit();
      }
      /* advance deepest live level */
      while (true) {
        R &r = rs[level];
        r.cur += r.st;
        bool live = (r.st > 0) ? r.cur <= r.hi : r.cur >= r.hi;
        if (live) {
          set_slot(level); /* only live iterators reach the value expr */
          break;
        }
        if (level == 0) return;
        level--;
      }
    }
  };
  walk();
  /* enable the dense dependency engine when the class's instances fit a
   * bounded box (auto-chosen; PTC_MCA_deptable_dense_max=0 disables) */
  if (visited > 0 && (size_t)tc.id < tp->dense.size()) {
    DenseDeps &dd = tp->dense[(size_t)tc.id];
    int64_t prod = 1;
    bool ok = ctx->dense_max_slots > 0;
    std::vector<int64_t> span(nb_range);
    for (size_t i = 0; ok && i < nb_range; i++) {
      span[i] = bmax[i] - bmin[i] + 1;
      if (span[i] <= 0 || prod > ctx->dense_max_slots / span[i]) ok = false;
      else prod *= span[i];
    }
    if (ok && prod <= ctx->dense_max_slots) {
      dd.lo = std::move(bmin);
      dd.span = std::move(span);
      dd.nb_slots = prod;
      dd.slots.reset(new std::atomic<DepEntry *>[(size_t)prod]());
      dd.enabled = true;
    }
  }
}

} // namespace

/* ------------------------------------------------------------------ */
/* DTD distributed: shadow release (called from comm.cpp)              */
/* ------------------------------------------------------------------ */

/* Retire the pull-server entry for `tile` (caller holds tp->dtd_lock).
 * Safe once the tile's next writer completed locally or as a shadow:
 * WAR ordering means every pull of the old version was served first. */
void ptc_dtd_retire_served_locked(ptc_context *ctx, ptc_taskpool *tp,
                                  ptc_dtile *tile) {
  if (tile->served_seq == UINT64_MAX) return;
  auto it = tp->dtd_served.find(tile->served_seq);
  if (it != tp->dtd_served.end()) {
    auto &vec = it->second;
    for (size_t i = 0; i < vec.size(); i++)
      if (vec[i].tile == tile) {
        ptc_copy_release_internal(ctx, vec[i].copy);
        vec.erase(vec.begin() + (ptrdiff_t)i);
        break;
      }
    if (vec.empty()) tp->dtd_served.erase(it);
  }
  tile->served_seq = UINT64_MAX;
}

/* Register `task` as waiting for `tile`'s pulled bytes (+1 message-style
 * hold, dedup by pointer).  Returns true if this call must ALSO issue the
 * fetch (first waiter while no pull is in flight).  tile->lock held. */
static bool dtd_add_fetch_waiter_locked(ptc_dtile *tile, ptc_task *task) {
  for (ptc_task *w : tile->fetch_waiters)
    if (w == task) return false;
  task->dyn->remaining.fetch_add(1, std::memory_order_relaxed);
  dyn_retain(task);
  tile->fetch_waiters.push_back(task);
  if (!tile->fetch_inflight) {
    tile->fetch_inflight = true;
    return true;
  }
  return false;
}

/* Payload framing (see comm.cpp dtd_complete): sequence of
 * [u32 flow][u64 len][bytes] records for every OUTPUT-mode flow; a flow
 * word with PTC_DTD_REC_MARKER set carries no bytes — the writer's rank
 * serves them on demand (MSG_DTD_FETCH). */
void ptc_dtd_apply_complete(ptc_context *ctx, ptc_task *t,
                            const uint8_t *payload, size_t len) {
  ptc_taskpool *tp = t->tp;
  DynExt *dx = t->dyn;
  /* this (remote) writer supersedes any pull entry we served for its
   * tiles — those versions can no longer be fetched */
  for (int fi = 0; fi < dx->nb_flows; fi++) {
    ptc_dtile *tile = dx->tiles[fi];
    if ((dx->modes[fi] & PTC_DTD_OUTPUT) && tile &&
        tile->served_seq != UINT64_MAX) {
      std::lock_guard<std::mutex> g(tp->dtd_lock);
      ptc_dtd_retire_served_locked(ctx, tp, tile);
    }
  }
  /* apply written-tile payloads into the local copies */
  struct Fetch {
    ptc_dtile *tile;
    uint64_t seq;
    int32_t flow;
    uint32_t src;
  };
  std::vector<Fetch> fetches;
  size_t off = 0;
  while (off + 12 <= len) {
    uint32_t flow_word;
    uint64_t plen;
    std::memcpy(&flow_word, payload + off, 4);
    std::memcpy(&plen, payload + off + 4, 8);
    off += 12;
    uint32_t flow = flow_word & ~PTC_DTD_REC_MARKER;
    if (flow_word & PTC_DTD_REC_MARKER) {
      /* size-only marker: the local mirror is stale until pulled.  Local
       * successors already ordered after this shadow (its succs) must not
       * run on stale bytes — give each a pull hold now, BEFORE the
       * message hold below releases them.  Successors inserted later are
       * handled by the submit-time stale check. */
      ptc_dtile *tile = flow < PTC_MAX_FLOWS ? dx->tiles[flow] : nullptr;
      if (tile) {
        /* ORDER MATTERS: mark stale BEFORE snapshotting succs.  A reader
         * whose dep edge lands after the snapshot then observes stale at
         * its submit-time check; one that landed before is in the
         * snapshot; one in between is caught by both (waiter dedup). */
        {
          std::lock_guard<std::mutex> tg0(tile->lock);
          tile->stale = true;
          tile->stale_seq = dx->seq;
          tile->stale_flow = (int32_t)flow;
          tile->stale_src = dx->rank;
        }
        std::vector<ptc_task *> succs_snap;
        {
          std::lock_guard<std::mutex> g(dx->lock);
          succs_snap = dx->succs;
        }
        std::lock_guard<std::mutex> tg(tile->lock);
        bool need_fetch = false;
        for (ptc_task *s : succs_snap) {
          DynExt *sd = s->dyn;
          if (!sd || sd->shadow) continue;
          bool reads_tile = false;
          {
            std::lock_guard<std::mutex> sg(sd->lock);
            if (sd->completed) continue;
            for (int sf = 0; sf < sd->nb_flows; sf++)
              if (sd->tiles[sf] == tile && (sd->modes[sf] & PTC_DTD_INPUT)) {
                reads_tile = true;
                break;
              }
          }
          if (reads_tile)
            need_fetch |= dtd_add_fetch_waiter_locked(tile, s);
        }
        if (need_fetch)
          fetches.push_back(Fetch{tile, dx->seq, (int32_t)flow, dx->rank});
      }
      continue;
    }
    if (off + plen > len) break;
    if (flow < PTC_MAX_FLOWS && t->data[flow] && t->data[flow]->ptr)
      std::memcpy(t->data[flow]->ptr, payload + off,
                  (size_t)std::min<uint64_t>(plen, (uint64_t)t->data[flow]->size));
    off += plen;
  }
  for (const Fetch &f : fetches) {
    {
      std::lock_guard<std::mutex> g(tp->dtd_lock);
      tp->dtd_fetch_pending[{f.seq, f.flow}] = f.tile;
    }
    ptc_comm_send_dtd_fetch(ctx, f.src, tp->id, f.seq, f.flow);
  }
  /* drop the message hold; schedule if local predecessors are also done */
  if (t->dyn->remaining.fetch_sub(1, std::memory_order_acq_rel) == 1)
    ptc_schedule_task(ctx, -1, t);
}

/* requester side: pulled bytes landed — fill the mirror, release holds */
void ptc_dtd_fetch_data(ptc_context *ctx, ptc_taskpool *tp, uint64_t seq,
                        int32_t flow, const uint8_t *payload, size_t len) {
  ptc_dtile *tile = nullptr;
  {
    std::lock_guard<std::mutex> g(tp->dtd_lock);
    auto it = tp->dtd_fetch_pending.find({seq, flow});
    if (it == tp->dtd_fetch_pending.end()) {
      std::fprintf(stderr, "ptc: unexpected DTD_DATA (seq=%llu flow=%d)\n",
                   (unsigned long long)seq, flow);
      return;
    }
    tile = it->second;
    tp->dtd_fetch_pending.erase(it);
  }
  std::vector<ptc_task *> waiters;
  {
    std::lock_guard<std::mutex> g(tile->lock);
    if (len > 0 && tile->copy && tile->copy->ptr)
      std::memcpy(tile->copy->ptr, payload,
                  std::min(len, (size_t)tile->copy->size));
    /* only clear if no NEWER writer re-marked meanwhile (cannot happen
     * per WAR ordering, but the guard is cheap) */
    if (tile->stale && tile->stale_seq == seq) tile->stale = false;
    tile->fetch_inflight = false;
    waiters.swap(tile->fetch_waiters);
  }
  for (ptc_task *w : waiters) {
    if (w->dyn->remaining.fetch_sub(1, std::memory_order_acq_rel) == 1)
      ptc_schedule_task(ctx, -1, w);
    dyn_release(w);
  }
}

void ptc_dtd_shadow_ready(ptc_context *ctx, ptc_taskpool *tp, uint64_t seq,
                          const uint8_t *payload, size_t len) {
  ptc_task *t = nullptr;
  {
    std::lock_guard<std::mutex> g(tp->dtd_lock);
    auto it = tp->dtd_shadows.find(seq);
    if (it == tp->dtd_shadows.end()) {
      /* message beat the insertion: park the payload */
      tp->dtd_early[seq] = std::vector<uint8_t>(payload, payload + len);
      return;
    }
    t = it->second;
    tp->dtd_shadows.erase(it);
  }
  ptc_dtd_apply_complete(ctx, t, payload, len);
}

/* ------------------------------------------------------------------ */
/* C API                                                               */
/* ------------------------------------------------------------------ */

extern "C" {

const char *ptc_version(void) { return "tpu-parsec-core 0.2"; }

ptc_context_t *ptc_context_new(int32_t nb_workers) {
  /* calibrate the trace clock now (~10 ms, once per process), so no
   * timestamp the context takes later waits on it */
  (void)ptc_now_ns();
  ptc_context *ctx = new ptc_context();
  if (nb_workers <= 0) {
    unsigned hc = std::thread::hardware_concurrency();
    nb_workers = hc > 0 ? (int32_t)hc : 1;
  }
  ctx->nb_workers = nb_workers;
  /* magazine batch knob (ptc-tune): read once here, immutable for the
   * context's life — workers only ever see the settled value */
  if (const char *e = std::getenv("PTC_MCA_runtime_mag_batch")) {
    int32_t v = (int32_t)std::atoi(e);
    if (v >= 1 && v <= 8192) ctx->mag_batch = v;
  }
  for (int i = 0; i < nb_workers; i++) {
    ctx->prof.push_back(new ProfBuf());
    ctx->worker_executed.push_back(new std::atomic<int64_t>(0));
    ctx->worker_cpu.push_back(new std::atomic<int32_t>(-1));
    ctx->worker_bypass.push_back(new std::atomic<int64_t>(0));
    ctx->flight.push_back(new ptc_context::FlightShard());
    ctx->task_mags.push_back(new ptc_context::TaskMag());
  }
  /* always-on metrics: one histogram set per worker + the shared
   * external slot (comm thread, device managers, main thread) */
  for (int i = 0; i < nb_workers + 1; i++)
    ctx->met_workers.push_back(new MetWorker());
  ctx->met_dtd_mid = ptc_met_intern(ctx, "dtd");
  if (const char *e = std::getenv("PTC_MCA_runtime_metrics"))
    ctx->metrics_on.store(!(*e == '0' && e[1] == '\0'),
                          std::memory_order_relaxed);
  if (const char *e = std::getenv("PTC_MCA_runtime_metrics_relsample"))
    ctx->met_rel_mask.store(met_pow2_mask((int32_t)std::atoi(e)),
                            std::memory_order_relaxed);
  if (const char *e = std::getenv("PTC_MCA_deptable_dense_max"))
    ctx->dense_max_slots = std::atoll(e);
  /* flight recorder: bound per-worker trace buffers (overwrite-oldest)
   * and/or arm the failure autodump path.  The Python MCA layer
   * re-applies its resolved value via ptc_profile_set_ring, same
   * pattern as sched_bypass below. */
  if (const char *e = std::getenv("PTC_MCA_runtime_trace_dump"))
    if (*e) ctx->flight_dump_path = e;
  if (const char *e = std::getenv("PTC_MCA_runtime_trace_ring"))
    ptc_profile_set_ring(ctx, std::atoll(e));
  /* same-worker ready-task bypass: on unless PTC_MCA_sched_bypass=0
   * (the Python MCA layer re-applies its resolved value via
   * ptc_context_set_sched_bypass; this env read covers native-only
   * embeddings and keeps the two spellings consistent) */
  if (const char *e = std::getenv("PTC_MCA_sched_bypass"))
    ctx->sched_bypass.store(!(*e == '0' && e[1] == '\0'),
                            std::memory_order_relaxed);
  /* QoS wave-boundary preemption: on unless PTC_MCA_sched_qos_preempt=0
   * (same re-apply pattern via ptc_context_set_qos_preempt) */
  if (const char *e = std::getenv("PTC_MCA_sched_qos_preempt"))
    ctx->qos_preempt.store(!(*e == '0' && e[1] == '\0'),
                           std::memory_order_relaxed);
  /* the weak-hash sanitizer targets the HASH engine: force it (same
   * value parse as ptc_fnv_hash — "0" means off) */
  if (const char *wh = std::getenv("PTC_DEBUG_WEAK_HASH"))
    if (*wh && *wh != '0') ctx->dense_max_slots = 0;
  return ctx;
}

/* per-worker selected-task counters (scheduler pops; AGAIN re-schedules
 * tick once per pass, ASYNC device chores tick at dispatch); returns
 * workers written (<= cap).  (Reference: PAPI-SDE TASKS_SCHEDULED,
 * parsec/scheduling.c:319-323.) */
int64_t ptc_worker_stats(ptc_context_t *ctx, int64_t *out, int64_t cap) {
  int64_t n = 0;
  for (; n < (int64_t)ctx->worker_executed.size() && n < cap; n++)
    out[n] = ctx->worker_executed[(size_t)n]->load(std::memory_order_relaxed);
  return n;
}

/* externally-sourced trace event (device manager dispatch spans):
 * same buffer, dictionary, and PINS fan-out as native events */
void ptc_prof_event(ptc_context_t *ctx, int64_t key, int64_t phase,
                    int64_t class_id, int64_t l0, int64_t l1, int64_t aux) {
  ptc_prof_push(ctx, -1, key, phase, class_id, l0, l1, aux);
}

/* runtime-native collective counters (the ptc_coll_* task-class family):
 * out6 = [steps executed, coll frames sent, bytes sent, coll frames
 * received, bytes received, reserved] */
void ptc_coll_stats(ptc_context_t *ctx, int64_t *out6) {
  out6[0] = ctx->coll_steps.load(std::memory_order_relaxed);
  out6[1] = ctx->coll_send_msgs.load(std::memory_order_relaxed);
  out6[2] = ctx->coll_send_bytes.load(std::memory_order_relaxed);
  out6[3] = ctx->coll_recv_msgs.load(std::memory_order_relaxed);
  out6[4] = ctx->coll_recv_bytes.load(std::memory_order_relaxed);
  out6[5] = 0;
}

/* ---- always-on metrics ABI (ptc_metrics; see MetHist above) ---- */

void ptc_metrics_enable(ptc_context_t *ctx, int32_t on) {
  ctx->metrics_on.store(on != 0, std::memory_order_relaxed);
}

int32_t ptc_metrics_enabled(ptc_context_t *ctx) {
  return ctx->metrics_on.load(std::memory_order_relaxed) ? 1 : 0;
}

void ptc_metrics_set_release_sample(ptc_context_t *ctx, int32_t n) {
  ctx->met_rel_mask.store(met_pow2_mask(n), std::memory_order_relaxed);
}

/* external producers (device layer h2d stall, Python embeddings) feed
 * the same histograms the native span-close paths use */
void ptc_metrics_record(ptc_context_t *ctx, int32_t kind, int32_t mid,
                        int64_t ns) {
  ptc_met_record(ctx, -1, (int)kind, mid, ns);
}

int32_t ptc_metrics_intern(ptc_context_t *ctx, const char *name) {
  return ptc_met_intern(ctx, name ? name : "");
}

int32_t ptc_metrics_nclasses(ptc_context_t *ctx) {
  std::lock_guard<std::mutex> g(ctx->met_lock);
  return (int32_t)ctx->met_names.size();
}

/* copy-out (interned std::string data moves when the registry grows) */
int32_t ptc_metrics_class_name(ptc_context_t *ctx, int32_t mid, char *out,
                               int32_t cap) {
  std::lock_guard<std::mutex> g(ctx->met_lock);
  if (mid < 0 || (size_t)mid >= ctx->met_names.size() || cap <= 0)
    return -1;
  const std::string &s = ctx->met_names[(size_t)mid];
  int32_t n = (int32_t)std::min<size_t>(s.size(), (size_t)cap - 1);
  std::memcpy(out, s.data(), (size_t)n);
  out[n] = 0;
  return n;
}

/* bucket-scheme constants for the Python decoder:
 * [nkinds, max_classes, buckets, subbits] */
void ptc_metrics_layout(int64_t *out4) {
  out4[0] = PTC_MET_NKINDS;
  out4[1] = PTC_MET_MAX_CLASSES;
  out4[2] = PTC_MET_BUCKETS;
  out4[3] = PTC_MET_SUBBITS;
}

/* Flat histogram dump: per record [kind, mid, count, sum, b0..b<N-1>]
 * (stride 4 + buckets; records with count == 0 are omitted).  merged=1
 * folds in the latest fence-time peer snapshots (rank 0) — peer class
 * names intern into this rank's registry so mids stay meaningful. */
int64_t ptc_metrics_snapshot(ptc_context_t *ctx, int64_t *out, int64_t cap,
                             int32_t merged) {
  std::vector<MetAggRec> recs;
  std::map<uint32_t, MetRemote> peers;
  {
    std::lock_guard<std::mutex> g(ctx->met_lock);
    met_aggregate_local(ctx, recs);
    if (merged) peers = ctx->met_peers;
  }
  if (merged && !peers.empty()) {
    for (auto &kv : peers)
      for (auto &rr : kv.second.recs) {
        int32_t mid = -1;
        if (rr.kind == PTC_MET_EXEC && !rr.name.empty())
          mid = ptc_met_intern(ctx, rr.name);
        MetAggRec *r = nullptr;
        for (auto &cand : recs)
          if (cand.kind == rr.kind && cand.mid == mid) {
            r = &cand;
            break;
          }
        if (!r) {
          recs.emplace_back(rr.kind, mid);
          r = &recs.back();
        }
        r->count += rr.count;
        r->sum += rr.sum;
        for (auto &pr : rr.pairs) r->b[(size_t)pr.first] += pr.second;
      }
  }
  const int64_t stride = 4 + PTC_MET_BUCKETS;
  int64_t n = 0;
  for (auto &r : recs) {
    if (n + stride > cap) break;
    out[n] = r.kind;
    out[n + 1] = r.mid;
    out[n + 2] = r.count;
    out[n + 3] = r.sum;
    for (int i = 0; i < PTC_MET_BUCKETS; i++)
      out[n + 4 + i] = r.b[(size_t)i];
    n += stride;
  }
  return n;
}

/* open EXEC bodies: [worker, mid, begin_ns] triplets — the watchdog's
 * stuck-task scan (deadline = k * p99 of the class's histogram) */
int64_t ptc_metrics_inflight(ptc_context_t *ctx, int64_t *out, int64_t cap) {
  int64_t n = 0;
  for (size_t w = 0; w < ctx->met_workers.size() && n + 4 <= cap; w++) {
    MetWorker *mw = ctx->met_workers[w];
    int64_t b = mw->cur_begin.load(std::memory_order_relaxed);
    if (!b) continue;
    out[n] = (int64_t)w;
    out[n + 1] = mw->cur_mid.load(std::memory_order_relaxed);
    out[n + 2] = b;
    out[n + 3] = mw->cur_scope.load(std::memory_order_relaxed);
    n += 4;
  }
  return n;
}

/* per-peer fence-time clock-sync RTTs as seen by rank 0 (fed by the
 * MSG_METRICS frames; all-zero on other ranks / before the first
 * fence).  The watchdog's slow-rank outlier scan reads this. */
int32_t ptc_metrics_peer_rtts(ptc_context_t *ctx, int64_t *out,
                              int32_t cap) {
  int32_t n = (int32_t)ctx->nodes;
  if (n > cap) n = cap;
  for (int32_t i = 0; i < n; i++) out[i] = 0;
  std::lock_guard<std::mutex> g(ctx->met_lock);
  for (auto &kv : ctx->met_peers)
    if ((int32_t)kv.first < n) out[kv.first] = kv.second.rtt_ns;
  return n;
}

/* per-worker steal counters (selects served from a victim's queue);
 * 0 for global-queue schedulers.  (Reference observability role:
 * mca/pins/print_steals.) */
int64_t ptc_worker_steals(ptc_context_t *ctx, int64_t *out, int64_t cap) {
  /* gate on started (acquire), NOT on the plain ctx->sched pointer: a
   * monitor thread (watchdog tick, Prometheus scrape) can call this
   * while another thread's add_taskpool is inside the lazy
   * ptc_context_start — `started` is released only after the scheduler
   * is fully built, so this acquire pairs with it */
  if (!ctx->started.load(std::memory_order_acquire)) return 0;
  auto &st = ctx->sched->steals;
  int64_t n = 0;
  for (; n < (int64_t)st.size() && n < cap; n++)
    out[n] = st[(size_t)n]->load(std::memory_order_relaxed);
  return n;
}

/* Same-worker ready-task bypass knob (PTC_MCA_sched_bypass): when off,
 * every ready successor takes the full schedule()+select() round trip —
 * the control the dispatch bench measures the bypass against. */
void ptc_context_set_sched_bypass(ptc_context_t *ctx, int32_t on) {
  ctx->sched_bypass.store(on != 0, std::memory_order_relaxed);
}

int32_t ptc_context_get_sched_bypass(ptc_context_t *ctx) {
  return ctx->sched_bypass.load(std::memory_order_relaxed) ? 1 : 0;
}

/* ---- per-pool QoS (serving runtime) ---- */

/* Arm QoS on a taskpool: priority orders pools strictly (higher wins
 * every select boundary under lws; negative = background, served only
 * when the default path is dry), weight shares a priority tier by
 * stride scheduling.  Call BEFORE add_taskpool (tasks scheduled earlier
 * would miss the lane routing).  Priority clamps to ±1023 so the
 * composed task priority (prio << 20 + class priority) stays in int32. */
void ptc_tp_set_qos(ptc_taskpool_t *tp, int32_t priority, int64_t weight) {
  if (priority > 1023) priority = 1023;
  if (priority < -1023) priority = -1023;
  tp->qos_prio = priority;
  tp->qos_weight = weight < 1 ? 1 : weight;
  tp->qos.store(true, std::memory_order_release);
}

/* Per-pool QoS counters: out = [priority, weight, scheduled, selected,
 * executed, wait_ns, queued (scheduled - selected), preempts].  Returns
 * slots written (<= cap); 0 when the pool has no QoS armed. */
int64_t ptc_tp_qos_stats(ptc_taskpool_t *tp, int64_t *out, int64_t cap) {
  if (!tp->qos.load(std::memory_order_acquire)) return 0;
  int64_t sched = tp->q_scheduled.load(std::memory_order_relaxed);
  int64_t sel = tp->q_selected.load(std::memory_order_relaxed);
  int64_t v[8] = {
      tp->qos_prio,
      tp->qos_weight,
      sched,
      sel,
      tp->q_executed.load(std::memory_order_relaxed),
      tp->q_wait_ns.load(std::memory_order_relaxed),
      sched - sel < 0 ? 0 : sched - sel,
      tp->q_preempts.load(std::memory_order_relaxed),
  };
  int64_t n = cap < 8 ? (cap < 0 ? 0 : cap) : 8;
  for (int64_t i = 0; i < n; i++) out[i] = v[i];
  return n;
}

/* ---- request scope (observability) ---- */

/* Stamp the request/pool id this taskpool serves (0 = unscoped).  The
 * scope rides EXEC/RELEASE span aux, the watchdog's inflight slot, and
 * outgoing ACTIVATE frames (comm.cpp re-emits it on delivery as a
 * PROF_KEY_SCOPE flow tag).  Safe to call any time before run; spans
 * pushed earlier simply carry 0. */
void ptc_tp_set_scope(ptc_taskpool_t *tp, int64_t scope_id) {
  tp->scope_id.store(scope_id, std::memory_order_relaxed);
}

int64_t ptc_tp_scope(ptc_taskpool_t *tp) {
  return tp->scope_id.load(std::memory_order_relaxed);
}

int64_t ptc_task_scope(ptc_task_t *t) {
  if (!t || !t->tp) return 0;
  return t->tp->scope_id.load(std::memory_order_relaxed);
}

/* Wave-boundary preemption knob (PTC_MCA_sched_qos_preempt): off = a
 * worker drains the lane it last served until empty instead of
 * re-ranking lanes by priority at every select. */
void ptc_context_set_qos_preempt(ptc_context_t *ctx, int32_t on) {
  ctx->qos_preempt.store(on != 0, std::memory_order_relaxed);
  if (ctx->started.load(std::memory_order_acquire))
    ctx->sched->qos_preempt.store(on != 0, std::memory_order_relaxed);
}

int32_t ptc_context_get_qos_preempt(ptc_context_t *ctx) {
  return ctx->qos_preempt.load(std::memory_order_relaxed) ? 1 : 0;
}

/* Every done count first, then every scheduled count: a task read done
 * has its own and its successors' scheduled counts visible (release /
 * acquire), and the counts only grow, so the difference is never below
 * the number in flight between the two passes, and never negative. */
int64_t ptc_tasks_in_flight(ptc_context_t *ctx) {
  int64_t done = 0, scheduled = 0;
  for (auto *s : ctx->flight)
    done += s->done.load(std::memory_order_acquire);
  for (auto *s : ctx->flight)
    scheduled += s->scheduled.load(std::memory_order_acquire);
  scheduled += ctx->flight_ext.scheduled.load(std::memory_order_acquire);
  return scheduled - done;
}

/* Dispatch fast-path counters (Context.sched_stats()).  Layout:
 *  [0] bypass hits (sum over workers)   [1] bypass enabled (0/1)
 *  [2] task-freelist hits               [3] task-freelist misses
 *  [4] arena-freelist hits              [5] arena-freelist misses
 *  [6] DTD insert batches               [7] DTD batch-inserted tasks
 *  [8] scheduler inject pushes          [9] scheduler inject pops
 *  [10] QoS lane selects                [11] QoS wave preemptions
 * Returns the number of slots written (<= cap). */
int64_t ptc_sched_stats(ptc_context_t *ctx, int64_t *out, int64_t cap) {
  int64_t v[12] = {0};
  for (auto *c : ctx->worker_bypass)
    v[0] += c->load(std::memory_order_relaxed);
  v[1] = ctx->sched_bypass.load(std::memory_order_relaxed) ? 1 : 0;
  v[2] = ctx->free_ext_hits.load(std::memory_order_relaxed);
  v[3] = ctx->free_ext_misses.load(std::memory_order_relaxed);
  for (auto *m : ctx->task_mags) {
    v[2] += m->hits.load(std::memory_order_relaxed);
    v[3] += m->misses.load(std::memory_order_relaxed);
  }
  {
    std::lock_guard<std::mutex> g(ctx->reg_lock);
    Arena **t = ctx->arena_tab.load(std::memory_order_relaxed);
    int32_t n = ctx->arena_count.load(std::memory_order_relaxed);
    for (int32_t i = 0; i < n; i++) {
      v[4] += t[i]->stat_hits();
      v[5] += t[i]->stat_misses();
    }
  }
  v[6] = ctx->insert_batches.load(std::memory_order_relaxed);
  v[7] = ctx->insert_batched_tasks.load(std::memory_order_relaxed);
  /* started-gate, not a plain sched-pointer test: see ptc_worker_steals
   * (monitor threads race the lazy context start otherwise) */
  if (ctx->started.load(std::memory_order_acquire)) {
    v[8] = ctx->sched->inject_pushes.load(std::memory_order_relaxed);
    v[9] = ctx->sched->inject_pops.load(std::memory_order_relaxed);
    v[10] = ctx->sched->qos_selects.load(std::memory_order_relaxed);
    v[11] = ctx->sched->qos_preempts.load(std::memory_order_relaxed);
  }
  int64_t n = cap < 12 ? (cap < 0 ? 0 : cap) : 12;
  for (int64_t i = 0; i < n; i++) out[i] = v[i];
  return n;
}

int32_t ptc_context_nb_workers(ptc_context_t *ctx) { return ctx->nb_workers; }

int32_t ptc_context_set_scheduler(ptc_context_t *ctx, const char *name) {
  if (ctx->started.load()) return -1;
  ctx->sched_name = ptc_sched_canonical(name);
  return 0;
}

const char *ptc_context_get_scheduler(ptc_context_t *ctx) {
  return ctx->sched_name.c_str();
}

int32_t ptc_context_start(ptc_context_t *ctx) {
  /* fully-initialized-before-visible: the comm thread can race a lazy
   * start (early remote delivery while the user thread is inside
   * add_taskpool).  The mutex makes late starters BLOCK until install
   * finished; `started` is released only after the scheduler is usable,
   * so the fast path's acquire load sees a complete scheduler. */
  if (ctx->started.load(std::memory_order_acquire)) return 0;
  std::lock_guard<std::mutex> g(ctx->start_lock);
  if (ctx->started.load(std::memory_order_relaxed)) return 0;
  ctx->sched = ptc_sched_create(ctx->sched_name);
  if (!ctx->vp_of_worker.empty())
    ctx->sched->set_vpmap(ctx->vp_of_worker);
  ctx->sched->install(ctx->nb_workers);
  ctx->sched->steals_init(ctx->nb_workers);
  ctx->sched->qos_preempt.store(
      ctx->qos_preempt.load(std::memory_order_relaxed),
      std::memory_order_relaxed);
  for (int i = 0; i < ctx->nb_workers; i++)
    ctx->workers.emplace_back(worker_main, ctx, i);
  ctx->started.store(true, std::memory_order_release);
  return 0;
}

int32_t ptc_context_wait(ptc_context_t *ctx) {
  std::unique_lock<ptc_mutex> lk(ctx->wait_lock);
  ctx->wait_cv.wait(lk, [&] { return ctx->active_tps.load() == 0; });
  return 0;
}

int32_t ptc_context_test(ptc_context_t *ctx) {
  return ctx->active_tps.load() == 0 ? 1 : 0;
}

void ptc_context_destroy(ptc_context_t *ctx) {
  /* workers first: they may still call ptc_comm_send_* from release_deps,
   * so the comm engine must outlive them */
  ctx->shutdown.store(true, std::memory_order_release);
  ctx->idle_cv.notify_all();
  for (auto *q : ctx->dev_queues) q->cv.notify_all();
  for (auto &w : ctx->workers)
    if (w.joinable()) w.join();
  ptc_comm_shutdown(ctx); /* no-op when comm was never initialized */
  delete ctx->pins_state.load(std::memory_order_relaxed);
  for (auto *st : ctx->pins_retired) delete st;
  delete ctx->rank_map.load(std::memory_order_relaxed);
  for (auto *rm : ctx->rank_maps_retired) delete rm;
  delete ctx;
}

void ptc_context_set_rank(ptc_context_t *ctx, uint32_t myrank, uint32_t nodes) {
  ctx->myrank = myrank;
  ctx->nodes = nodes ? nodes : 1;
}

void ptc_context_set_binding(ptc_context_t *ctx, int32_t mode) {
  ctx->bind_mode = mode;
}

/* vpmap (reference: parsec/vpmap.c): vp id per worker, before start.
 * Returns -1 once the context started — the scheduler was installed
 * with the old map and will not re-read it (silent acceptance would
 * leave the caller believing the hierarchy changed). */
int32_t ptc_context_set_vpmap(ptc_context_t *ctx, const int32_t *vp,
                              int32_t n) {
  if (!ctx || !vp || n <= 0) return -1;
  std::lock_guard<std::mutex> g(ctx->start_lock);
  if (ctx->started.load(std::memory_order_acquire)) return -1;
  ctx->vp_of_worker.assign(vp, vp + n);
  return 0;
}

/* test/debug probe: the victim (steal) order a hierarchical scheduler
 * computed for `worker`.  Returns the count written (<= cap), or -1
 * when the active scheduler has no explicit order (flat modules). */
int32_t ptc_sched_victim_order(ptc_context_t *ctx, int32_t worker,
                               int32_t *out, int32_t cap) {
  if (!ctx || !ctx->started.load(std::memory_order_acquire)) return -1;
  auto *lhq = dynamic_cast<SchedVictimOrder *>(ctx->sched);
  if (!lhq) return -1;
  return lhq->victim_order(worker, out, cap);
}

void ptc_context_set_verbose(ptc_context_t *ctx, int32_t subsys,
                             int32_t level) {
  if (subsys >= 0 && subsys < PTC_DBG_NSUBSYS)
    ctx->verbose[subsys].store(level, std::memory_order_relaxed);
}

int32_t ptc_context_verbose(ptc_context_t *ctx, int32_t subsys) {
  if (subsys < 0 || subsys >= PTC_DBG_NSUBSYS) return 0;
  return ctx->verbose[subsys].load(std::memory_order_relaxed);
}

int32_t ptc_worker_binding(ptc_context_t *ctx, int32_t worker) {
  if (worker < 0 || (size_t)worker >= ctx->worker_cpu.size()) return -1;
  return ctx->worker_cpu[(size_t)worker]->load(std::memory_order_relaxed);
}

int32_t ptc_register_expr_cb(ptc_context_t *ctx, ptc_expr_cb cb, void *user) {
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  return ctx->expr_cbs.push({cb, user});
}

int32_t ptc_register_body(ptc_context_t *ctx, ptc_body_cb cb, void *user) {
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  return ctx->body_cbs.push({cb, user});
}

int32_t ptc_register_collection(ptc_context_t *ctx, uint32_t nodes,
                                uint32_t myrank, ptc_rank_of_cb rank_of,
                                ptc_data_of_cb data_of, void *user) {
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  Collection *dc = new Collection();
  dc->nodes = nodes;
  dc->myrank = myrank;
  dc->rank_of = rank_of;
  dc->data_of = data_of;
  dc->user = user;
  return ctx->collections.push(dc);
}

int32_t ptc_register_linear_collection(ptc_context_t *ctx, uint32_t nodes,
                                       uint32_t myrank, void *base,
                                       int64_t nb_elems, int64_t elem_size) {
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  Collection *dc = new Collection();
  dc->nodes = nodes ? nodes : 1;
  dc->myrank = myrank;
  dc->linear = true;
  dc->base = (char *)base;
  dc->nb_elems = nb_elems;
  dc->elem_size = elem_size;
  return ctx->collections.push(dc);
}

/* tool access to a registered collection's vtable (ptg_to_dtd, dumps) */
ptc_data_t *ptc_dc_data_of(ptc_context_t *ctx, int32_t dc_id,
                           const int64_t *idx, int32_t n) {
  if (!ctx || dc_id < 0 || dc_id >= ctx->collections.size())
    return nullptr;
  return ptc_collection_data_of(ctx, dc_id, idx, n);
}

int32_t ptc_dc_rank_of(ptc_context_t *ctx, int32_t dc_id,
                       const int64_t *idx, int32_t n) {
  if (!ctx || dc_id < 0 || dc_id >= ctx->collections.size())
    return 0;
  return (int32_t)ptc_collection_rank_of(ctx, dc_id, idx, n);
}

int32_t ptc_register_arena(ptc_context_t *ctx, int64_t elem_size) {
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  Arena *a = new Arena();
  a->elem_size = elem_size;
  a->mag_batch = ctx->mag_batch;
  a->init_mags(ctx->nb_workers);
  int32_t n = ctx->arena_count.load(std::memory_order_relaxed);
  if (n == ctx->arena_cap) {
    /* grow by table replacement: copy into a fresh table and retire
     * the old one until teardown — concurrent lock-free readers keep
     * indexing whichever table they loaded */
    int32_t nc = ctx->arena_cap ? ctx->arena_cap * 2 : 16;
    Arena **nt = new Arena *[nc];
    Arena **ot = ctx->arena_tab.load(std::memory_order_relaxed);
    for (int32_t i = 0; i < n; i++) nt[i] = ot[i];
    ctx->arena_tables.push_back(nt);
    ctx->arena_tab.store(nt, std::memory_order_release);
    ctx->arena_cap = nc;
  }
  ctx->arena_tab.load(std::memory_order_relaxed)[n] = a;
  ctx->arena_count.store(n + 1, std::memory_order_release);
  return n;
}

int32_t ptc_register_datatype(ptc_context_t *ctx, int64_t elem_bytes,
                              int64_t count, int64_t stride_bytes) {
  if (elem_bytes <= 0 || count <= 0 || stride_bytes < elem_bytes) return -1;
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  ctx->dtypes.push_back(DtypeDef{elem_bytes, count, stride_bytes});
  ctx->has_dtypes.store(true, std::memory_order_release);
  return (int32_t)ctx->dtypes.size() - 1;
}

int32_t ptc_register_datatype_indexed(ptc_context_t *ctx,
                                      const int64_t *offsets,
                                      const int64_t *lens, int32_t nseg) {
  if (nseg <= 0) return -1;
  DtypeDef dt;
  for (int32_t i = 0; i < nseg; i++) {
    if (offsets[i] < 0 || lens[i] <= 0) return -1;
    dt.segs.emplace_back(offsets[i], lens[i]);
  }
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  ctx->dtypes.push_back(std::move(dt));
  ctx->has_dtypes.store(true, std::memory_order_release);
  return (int32_t)ctx->dtypes.size() - 1;
}

int32_t ptc_register_datatype_cast(ptc_context_t *ctx, int32_t src_kind,
                                   int32_t dst_kind, int64_t count) {
  auto valid = [](int32_t k) {
    return k >= PTC_ELEM_F32 && k <= PTC_ELEM_U8;
  };
  if (!valid(src_kind) || !valid(dst_kind) || count == 0) return -1;
  DtypeDef dt;
  dt.src_kind = src_kind;
  dt.dst_kind = dst_kind;
  dt.count = count; /* < 0: whole copy, element count derived per copy */
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  ctx->dtypes.push_back(std::move(dt));
  ctx->has_dtypes.store(true, std::memory_order_release);
  return (int32_t)ctx->dtypes.size() - 1;
}

void ptc_ctx_reshape_stats(ptc_context_t *ctx, int64_t *conversions,
                           int64_t *hits) {
  if (conversions)
    *conversions = ctx->reshape_conversions.load(std::memory_order_relaxed);
  if (hits) *hits = ctx->reshape_hits.load(std::memory_order_relaxed);
}

ptc_taskpool_t *ptc_tp_new(ptc_context_t *ctx, int32_t nb_globals,
                           const int64_t *globals) {
  ptc_taskpool *tp = new ptc_taskpool();
  tp->ctx = ctx;
  tp->globals.assign(globals, globals + nb_globals);
  return tp;
}

void ptc_tp_destroy(ptc_taskpool_t *tp) {
  if (tp->id >= 0) {
    std::lock_guard<std::mutex> g(tp->ctx->tp_reg_lock);
    tp->ctx->tp_registry.erase(tp->id);
  }
  /* completion drain: a waiter can return the instant completed /
   * nb_tasks==0 flips, but the completer may still be on its way to the
   * notify locks (or inside them).  Every such path holds tp->busy for
   * its full tp lifetime-critical span, so spin it out before freeing
   * the condvars/mutexes.  (Acquire pairs with the completer's release
   * decrement: all its tp writes are visible before the delete.) */
  while (tp->busy.load(std::memory_order_acquire) != 0)
    std::this_thread::yield();
  for (auto &shard : tp->shards) {
    std::lock_guard<std::mutex> g(shard.lock);
    for (auto &kv : shard.map)
      for (int f = 0; f < PTC_MAX_FLOWS; f++)
        if (kv.second.staged[f]) copy_release(tp->ctx, kv.second.staged[f]);
    shard.map.clear();
  }
  for (DenseDeps &dd : tp->dense) {
    if (!dd.enabled) continue;
    for (int64_t i = 0; i < dd.nb_slots; i++) {
      DepEntry *e = dd.slots[i].load(std::memory_order_relaxed);
      if (!e || e == DENSE_PROMOTED) continue;
      for (int f = 0; f < PTC_MAX_FLOWS; f++)
        if (e->staged[f]) copy_release(tp->ctx, e->staged[f]);
      delete e;
    }
  }
  {
    /* never-refetched pull-server entries (chain-final tiles).  The tile
     * pointers are NOT touched: user tiles may already be destroyed, and
     * a stale served_seq on a surviving tile is harmless (the next
     * writer's retire just misses in the new pool's map). */
    std::lock_guard<std::mutex> g(tp->dtd_lock);
    for (auto &kv : tp->dtd_served)
      for (auto &rec : kv.second)
        ptc_copy_release_internal(tp->ctx, rec.copy);
    tp->dtd_served.clear();
    /* unanswered pulls (aborted pool / lost peer): drop the waiters'
     * retains so their task memory is reclaimed.  No scheduling — the
     * pool is dying; the +1 holds simply never release. */
    for (auto &kv : tp->dtd_fetch_pending) {
      ptc_dtile *tile = kv.second;
      std::vector<ptc_task *> waiters;
      {
        std::lock_guard<std::mutex> tg(tile->lock);
        waiters.swap(tile->fetch_waiters);
        tile->fetch_inflight = false;
      }
      for (ptc_task *w : waiters) dyn_release(w);
    }
    tp->dtd_fetch_pending.clear();
  }
  delete tp;
}

int32_t ptc_tp_add_class(ptc_taskpool_t *tp, const char *name,
                         const int64_t *spec, int64_t spec_len) {
  TaskClass tc;
  tc.name = name ? name : "";
  tc.id = (int32_t)tp->classes.size();
  /* the ptc_coll_* family (runtime-native collective steps) is detected
   * by name so the comm/trace layers can attribute its traffic without
   * a second registration call — the prefix IS the contract
   * (parsec_tpu/comm/coll.py names every class it builds this way) */
  tc.is_coll = tc.name.compare(0, 8, "ptc_coll") == 0;
  if (!decode_class(tc, spec, spec_len)) return -1;
  /* always-on metrics: intern the class name context-wide so same-named
   * classes across taskpools share one latency histogram */
  if (tp->ctx) tc.metric_id = ptc_met_intern(tp->ctx, tc.name);
  tp->classes.push_back(std::move(tc));
  return (int32_t)tp->classes.size() - 1;
}

int32_t ptc_tp_id(ptc_taskpool_t *tp) {
  /* the id is assigned inside add_taskpool under tp_reg_lock; a
   * monitor thread (Context.stats() pool rows) may ask while the
   * submitting thread is mid-registration — read under the same lock
   * (TSan-caught in the serve_churn stress) */
  std::lock_guard<std::mutex> g(tp->ctx->tp_reg_lock);
  return tp->id;
}

int32_t ptc_tp_dense_classes(ptc_taskpool_t *tp) {
  int32_t n = 0;
  for (const DenseDeps &dd : tp->dense)
    if (dd.enabled) n++;
  return n;
}

int32_t ptc_context_add_taskpool(ptc_context_t *ctx, ptc_taskpool_t *tp) {
  bool expected = false;
  if (!tp->added.compare_exchange_strong(expected, true)) return -1;
  ctx->active_tps.fetch_add(1);
  StartupStats st;
  tp->dense.resize(tp->classes.size());
  for (const TaskClass &tc : tp->classes) enumerate_class(ctx, tp, tc, st);
  tp->nb_total.store(st.nb_local);
  tp->nb_tasks.store(st.nb_local);
  /* distributed registration: ids follow SPMD creation order (reference:
   * taskpool id sync, parsec/runtime.h:480-491) */
  {
    std::lock_guard<std::mutex> g(ctx->tp_reg_lock);
    tp->id = ctx->next_tp_id++;
    ctx->tp_registry[tp->id] = tp;
  }
  if (ptc_context_verbose(ctx, PTC_DBG_RUNTIME) >= 1)
    std::fprintf(stderr,
                 "ptc [runtime]: taskpool %d: %lld local tasks across %zu "
                 "classes (%d on the dense engine), %zu startup-ready\n",
                 tp->id, (long long)st.nb_local, tp->classes.size(),
                 ptc_tp_dense_classes(tp), st.ready.size());
  if (st.nb_local == 0 && !tp->open.load()) {
    tp->busy.fetch_add(1, std::memory_order_acquire);
    tp_mark_complete(ctx, tp);
    ptc_comm_drain_early(ctx, tp);
    tp->busy.fetch_sub(1, std::memory_order_release);
    return 0;
  }
  ptc_context_start(ctx);
  /* activations that arrived before this pool existed */
  ptc_comm_drain_early(ctx, tp);
  for (ptc_task *t : st.ready) schedule_task(ctx, 0, t);
  return 0;
}

int32_t ptc_tp_wait(ptc_taskpool_t *tp) {
  std::unique_lock<ptc_mutex> lk(tp->done_lock);
  tp->done_cv.wait(lk, [&] { return tp->completed.load(); });
  return tp->nb_errors.load() > 0 ? -1 : 0;
}

int64_t ptc_tp_nb_tasks(ptc_taskpool_t *tp) { return tp->nb_tasks.load(); }

/* Body-driven task-count adjustment (reference: the termination-detection
 * module's taskpool_addto_nb_tasks, used by "choice"-style DAGs whose
 * bodies retire tasks that will never become ready —
 * tests/dsl/ptg/choice/choice.jdf — and by %option nb_local_tasks_fn
 * overrides, tests/dsl/ptg/user-defined-functions/udf.jdf). */
int64_t ptc_tp_addto_nb_tasks(ptc_taskpool_t *tp, int64_t delta) {
  tp->busy.fetch_add(1, std::memory_order_acquire);
  int64_t now =
      tp->nb_tasks.fetch_add(delta, std::memory_order_seq_cst) + delta;
  if (now == 0 && !tp->open.load(std::memory_order_seq_cst))
    tp_mark_complete(tp->ctx, tp);
  notify_drain_waiters(tp);
  tp->busy.fetch_sub(1, std::memory_order_release);
  return now;
}

/* Drain: block until every task inserted so far has completed, WITHOUT
 * closing the pool — insertion may continue afterwards.  (Reference:
 * parsec_dtd_data_flush's wait-for-writers semantics,
 * parsec/interfaces/dtd/parsec_dtd_data_flush.c — SURVEY.md §2.7.) */
int32_t ptc_tp_drain(ptc_taskpool_t *tp) {
  tp->drain_waiters.fetch_add(1, std::memory_order_seq_cst);
  {
    std::unique_lock<ptc_mutex> lk(tp->window_lock);
    tp->window_cv.wait(lk, [&] {
      return tp->nb_tasks.load(std::memory_order_seq_cst) == 0 ||
             tp->completed.load(std::memory_order_acquire) ||
             tp->ctx->shutdown.load(std::memory_order_acquire);
    });
  }
  tp->drain_waiters.fetch_sub(1, std::memory_order_acq_rel);
  return tp->completed.load(std::memory_order_acquire) ? -1 : 0;
}
int64_t ptc_tp_nb_total_tasks(ptc_taskpool_t *tp) { return tp->nb_total.load(); }
int64_t ptc_tp_nb_errors(ptc_taskpool_t *tp) { return tp->nb_errors.load(); }

void ptc_tp_set_open(ptc_taskpool_t *tp, int32_t open) {
  tp->open.store(open != 0, std::memory_order_seq_cst);
  /* closing after the count already drained must still complete the pool;
   * seq_cst pairs with tp_task_done (see comment there) */
  if (!open && tp->added.load(std::memory_order_acquire) &&
      tp->nb_tasks.load(std::memory_order_seq_cst) == 0) {
    tp->busy.fetch_add(1, std::memory_order_acquire);
    tp_mark_complete(tp->ctx, tp);
    tp->busy.fetch_sub(1, std::memory_order_release);
  }
}

void ptc_tp_set_on_complete(ptc_taskpool_t *tp, ptc_tp_complete_cb cb,
                            void *user) {
  tp->complete_cb = cb;
  tp->complete_user = user;
}

int64_t ptc_tp_global(ptc_taskpool_t *tp, int32_t i) {
  return (i >= 0 && (size_t)i < tp->globals.size()) ? tp->globals[(size_t)i] : 0;
}

/* data */
ptc_data_t *ptc_data_new(int64_t key, void *ptr, int64_t size) {
  ptc_data *d = new ptc_data();
  d->key = key;
  d->size = size;
  ptc_copy *c = new ptc_copy();
  c->data = d;
  c->ptr = ptr;
  c->size = size;
  d->host_copy = c;
  return d;
}

void ptc_data_destroy(ptc_data_t *d) {
  if (!d) return;
  if (d->host_copy) {
    /* context not available here; host copies never come from arenas */
    if (d->host_copy->refcount.fetch_sub(1) == 1) {
      if (d->host_copy->owns_ptr && d->host_copy->ptr)
        std::free(d->host_copy->ptr);
      delete d->host_copy;
    }
  }
  delete d;
}

ptc_copy_t *ptc_data_host_copy(ptc_data_t *d) {
  return d ? d->host_copy : nullptr;
}
void *ptc_copy_ptr(ptc_copy_t *c) { return c ? c->ptr : nullptr; }
int64_t ptc_copy_size(ptc_copy_t *c) { return c ? c->size : 0; }
int64_t ptc_copy_handle(ptc_copy_t *c) { return c ? c->handle : 0; }
void ptc_copy_set_handle(ptc_copy_t *c, int64_t h) { if (c) c->handle = h; }
int32_t ptc_copy_version(ptc_copy_t *c) { return c ? c->version.load() : 0; }
int32_t ptc_copy_is_persistent(ptc_copy_t *c) {
  return (c && c->data) ? 1 : 0;
}

void ptc_set_copy_release_cb(ptc_context_t *ctx, ptc_copy_release_cb cb,
                             void *user) {
  ctx->copy_release_cb = cb;
  ctx->copy_release_user = user;
}

void ptc_set_copy_sync_cb(ptc_context_t *ctx, ptc_copy_sync_cb cb,
                          void *user) {
  ctx->copy_sync_cb = cb;
  ctx->copy_sync_user = user;
}

void ptc_copy_sync_for_host(ptc_context *ctx, ptc_copy *c) {
  if (!c || c->handle == 0) return; /* never touched a device */
  ptc_copy_sync_cb cb = ctx->copy_sync_cb;
  if (cb) cb(ctx->copy_sync_user, c->handle);
}

void ptc_set_copy_invalidate_cb(ptc_context_t *ctx,
                                ptc_copy_invalidate_cb cb, void *user) {
  ctx->copy_invalidate_cb = cb;
  ctx->copy_invalidate_user = user;
}

void ptc_copy_host_written(ptc_context *ctx, ptc_copy *c) {
  if (!c || c->handle == 0) return; /* never touched a device */
  ptc_copy_invalidate_cb cb = ctx->copy_invalidate_cb;
  if (cb) cb(ctx->copy_invalidate_user, c->handle);
}

void ptc_set_dataplane(ptc_context_t *ctx, ptc_dp_register_cb reg,
                       ptc_dp_serve_cb serve, ptc_dp_serve_done_cb done,
                       ptc_dp_deliver_cb deliver, ptc_dp_bound_cb bound,
                       void *user) {
  ctx->dp_register = reg;
  ctx->dp_serve = serve;
  ctx->dp_serve_done = done;
  ctx->dp_deliver = deliver;
  ctx->dp_bound = bound;
  ctx->dp_user = user;
}

void ptc_set_dp_can_pull(ptc_context_t *ctx, int32_t ok) {
  if (ctx) ctx->dp_can_pull.store(ok, std::memory_order_relaxed);
}

void ptc_set_dp_stream(ptc_context_t *ctx, ptc_dp_serve_stream_cb cb) {
  if (ctx) ctx->dp_serve_stream = cb;
}

/* task accessors */
int64_t ptc_task_local(ptc_task_t *t, int32_t i) {
  return (t && i >= 0 && i < PTC_MAX_LOCALS) ? t->locals[i] : 0;
}
int32_t ptc_task_class(ptc_task_t *t) { return t ? t->class_id : -1; }
int32_t ptc_task_priority(ptc_task_t *t) { return t ? t->priority : 0; }
void *ptc_task_data_ptr(ptc_task_t *t, int32_t f) {
  if (!t || f < 0 || f >= PTC_MAX_FLOWS || !t->data[f]) return nullptr;
  return t->data[f]->ptr;
}
ptc_copy_t *ptc_task_copy(ptc_task_t *t, int32_t f) {
  return (t && f >= 0 && f < PTC_MAX_FLOWS) ? t->data[f] : nullptr;
}
ptc_taskpool_t *ptc_task_taskpool(ptc_task_t *t) { return t ? t->tp : nullptr; }
void ptc_task_set_tag(ptc_task_t *t, int64_t tag) {
  if (t) t->locals[PTC_MAX_LOCALS - 1] = tag;
}
int64_t ptc_task_get_tag(ptc_task_t *t) {
  return t ? t->locals[PTC_MAX_LOCALS - 1] : 0;
}
int32_t ptc_dtask_nb_flows(ptc_task_t *t) {
  return (t && t->dyn) ? t->dyn->nb_flows : 0;
}

/* device queues */
int32_t ptc_device_queue_new(ptc_context_t *ctx) {
  std::lock_guard<std::mutex> g(ctx->reg_lock);
  ctx->dev_queues.push_back(new DeviceQueue());
  return (int32_t)ctx->dev_queues.size() - 1;
}

void ptc_device_queue_set_weight(ptc_context_t *ctx, int32_t qid, double w) {
  if (qid < 0 || (size_t)qid >= ctx->dev_queues.size()) return;
  ctx->dev_queues[(size_t)qid]->weight.store(w, std::memory_order_relaxed);
}

int64_t ptc_device_queue_depth(ptc_context_t *ctx, int32_t qid) {
  if (qid < 0 || (size_t)qid >= ctx->dev_queues.size()) return -1;
  return ctx->dev_queues[(size_t)qid]->depth.load(std::memory_order_relaxed);
}

/* data-affinity map (see parsec_core.h; reference device.c:100-117) */
void ptc_device_set_data_owner(ptc_context_t *ctx, int64_t handle,
                               int32_t qid, int32_t version) {
  if (!ctx || handle == 0) return;
  std::lock_guard<std::mutex> g(ctx->owner_lock);
  if (qid < 0)
    ctx->data_owner.erase(handle);
  else
    ctx->data_owner[handle] =
        ((uint64_t)(uint32_t)qid << 32) | (uint32_t)version;
}

void ptc_device_clear_data_owner(ptc_context_t *ctx, int64_t handle,
                                 int32_t qid) {
  if (!ctx || handle == 0) return;
  std::lock_guard<std::mutex> g(ctx->owner_lock);
  auto it = ctx->data_owner.find(handle);
  if (it == ctx->data_owner.end()) return;
  if (qid < 0 || (int32_t)(it->second >> 32) == qid)
    ctx->data_owner.erase(it);
}

int32_t ptc_device_get_data_owner(ptc_context_t *ctx, int64_t handle,
                                  int32_t *version_out) {
  if (!ctx) return -1;
  std::lock_guard<std::mutex> g(ctx->owner_lock);
  auto it = ctx->data_owner.find(handle);
  if (it == ctx->data_owner.end()) return -1;
  if (version_out) *version_out = (int32_t)(uint32_t)it->second;
  return (int32_t)(it->second >> 32);
}

void ptc_device_set_affinity_skew(ptc_context_t *ctx, double skew) {
  if (!ctx) return;
  ctx->affinity_skew.store(skew, std::memory_order_relaxed);
}

ptc_task_t *ptc_device_pop(ptc_context_t *ctx, int32_t qid, int32_t timeout_ms) {
  DeviceQueue *q = ctx->dev_queues[(size_t)qid];
  std::unique_lock<ptc_mutex> lk(q->lock);
  if (q->dq.empty()) {
    q->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms), [&] {
      return !q->dq.empty() || ctx->shutdown.load();
    });
  }
  if (q->dq.empty()) return nullptr;
  ptc_task *t = q->dq.front();
  q->dq.pop_front();
  return t;
}

/* Ready-peek for the device prefetch lane (span-based like the
 * release->deliver path: flat caller buffer, no heap traffic).
 * Snapshots tasks still QUEUED on `qid` — ready, every input final —
 * WITHOUT popping, so the prefetch lane can stage the NEXT wave's h2d
 * while the manager computes the current one.  Per task:
 *   [task_ref, n_copies, (copy_ptr, data_ptr, size, version) * n]
 * task_ref is an opaque wave-grouping key — the task may be popped,
 * executed and recycled the moment the queue lock drops, so it must
 * never be dereferenced.  Each emitted copy is RETAINED under the
 * queue lock (its host bytes outlive the task even if the wave
 * completes mid-stage); the caller MUST ptc_copy_unpin every copy_ptr
 * exactly once.  Only READ data flows are emitted (CTL and write-only
 * flows stage nothing); DTD shadow tasks are skipped. */
int64_t ptc_peek_ready(ptc_context_t *ctx, int32_t qid, int64_t *out,
                       int64_t max_words, int32_t max_tasks) {
  if (!ctx || !out || qid < 0 || (size_t)qid >= ctx->dev_queues.size())
    return 0;
  DeviceQueue *q = ctx->dev_queues[(size_t)qid];
  int64_t w = 0;
  int32_t n = 0;
  std::lock_guard<ptc_mutex> g(q->lock);
  for (ptc_task *t : q->dq) {
    if (n >= max_tasks) break;
    if (w + 2 + 4 * PTC_MAX_FLOWS > max_words) break;
    if (t->dyn && t->dyn->shadow) continue;
    int64_t hdr = w;
    out[w++] = (int64_t)(intptr_t)t;
    out[w++] = 0;
    int64_t nc = 0;
    int32_t nflows = t->dyn ? t->dyn->nb_flows
                            : (int32_t)t->tp->classes[(size_t)t->class_id]
                                  .flows.size();
    for (int32_t f = 0; f < nflows; f++) {
      if (t->dyn) {
        if (!(t->dyn->modes[f] & PTC_DTD_INPUT)) continue;
      } else {
        const Flow &fl =
            t->tp->classes[(size_t)t->class_id].flows[(size_t)f];
        if (!(fl.flags & PTC_FLOW_READ) || (fl.flags & PTC_FLOW_CTL))
          continue;
      }
      ptc_copy *c = t->data[f];
      if (!c || !c->ptr || c->size <= 0) continue;
      ptc_copy_retain(c);
      out[w++] = (int64_t)(intptr_t)c;
      out[w++] = (int64_t)(intptr_t)c->ptr;
      out[w++] = c->size;
      out[w++] = c->version.load(std::memory_order_acquire);
      nc++;
    }
    out[hdr + 1] = nc;
    n++;
  }
  return w;
}

/* drop one ptc_peek_ready pin (the copy frees if this was the last ref) */
void ptc_copy_unpin(ptc_context_t *ctx, ptc_copy_t *c) {
  if (ctx && c) ptc_copy_release_internal(ctx, c);
}

/* Wave-granular ready-front census (the wave compiler's peek): class id
 * + taskpool of every task still queued on `qid`, under the queue lock,
 * with nothing popped or pinned.  The compiler uses it to see whether
 * the remainder of a certified wave is already queued before fusing a
 * partially-popped front. */
int64_t ptc_peek_ready_front(ptc_context_t *ctx, int32_t qid, int64_t *out,
                             int64_t max_tasks) {
  if (!ctx || !out || qid < 0 || (size_t)qid >= ctx->dev_queues.size())
    return 0;
  DeviceQueue *q = ctx->dev_queues[(size_t)qid];
  int64_t n = 0;
  std::lock_guard<ptc_mutex> g(q->lock);
  for (ptc_task *t : q->dq) {
    if (n >= max_tasks) break;
    out[2 * n] = t->dyn ? -1 : (int64_t)t->class_id;
    out[2 * n + 1] = (int64_t)(intptr_t)t->tp;
    n++;
  }
  return n;
}

/* depth bookkeeping for load balancing: resolve which device queue an
 * ASYNC task was routed to (PTG: its current chore; DTD: its body) */
static void device_task_done(ptc_context *ctx, ptc_task *t) {
  int64_t qid = -1;
  if (t->dyn) {
    if (t->dyn->body_kind == PTC_BODY_DEVICE) qid = t->dyn->body_arg;
  } else {
    const TaskClass &tc = t->tp->classes[(size_t)t->class_id];
    if (t->chore_idx < (int32_t)tc.chores.size()) {
      const Chore &ch = tc.chores[(size_t)t->chore_idx];
      if (ch.body_kind == PTC_BODY_DEVICE) qid = ch.body_arg;
    }
  }
  if (qid >= 0 && qid < (int64_t)ctx->dev_queues.size())
    ctx->dev_queues[(size_t)qid]->depth.fetch_sub(
        1, std::memory_order_relaxed);
}

void ptc_task_complete(ptc_context_t *ctx, ptc_task_t *task) {
  device_task_done(ctx, task);
  complete_task(ctx, -1, task);
}

void ptc_task_fail(ptc_context_t *ctx, ptc_task_t *task) {
  device_task_done(ctx, task);
  std::fprintf(stderr, "ptc: async task failed; aborting taskpool\n");
  if (task->dyn)
    dyn_fail_task(ctx, task);
  else
    fail_task(ctx, task);
}

/* ------------------------------------------------------------ DTD API */
ptc_dtile_t *ptc_dtile_new(ptc_context_t *ctx, ptc_data_t *d) {
  (void)ctx;
  if (!d || !d->host_copy) return nullptr;
  ptc_dtile *tile = new ptc_dtile();
  copy_retain(d->host_copy);
  tile->copy = d->host_copy;
  return tile;
}

void ptc_dtile_set_owner(ptc_dtile_t *tile, uint32_t rank) {
  if (tile) tile->owner = rank;
}

void ptc_dtile_destroy(ptc_context_t *ctx, ptc_dtile_t *tile) {
  if (!tile) return;
  {
    std::lock_guard<std::mutex> g(tile->lock);
    if (tile->last_writer) dyn_release(tile->last_writer);
    for (ptc_task *r : tile->readers) dyn_release(r);
    tile->readers.clear();
    tile->last_writer = nullptr;
  }
  copy_release(ctx, tile->copy);
  delete tile;
}

ptc_task_t *ptc_dtask_begin(ptc_taskpool_t *tp, int32_t body_kind,
                            int64_t body_arg, int32_t priority) {
  ptc_task *t = new ptc_task();
  t->tp = tp;
  t->class_id = -1;
  t->priority = priority;
  /* pool-QoS priority bias, as in make_task */
  if (tp->qos.load(std::memory_order_relaxed))
    t->priority += tp->qos_prio * (1 << 20);
  std::memset(t->locals, 0, sizeof(t->locals));
  std::memset(t->data, 0, sizeof(t->data));
  t->dyn = new DynExt();
  t->dyn->body_kind = body_kind;
  t->dyn->body_arg = body_arg;
  t->dyn->seq = tp->dtd_seq.fetch_add(1, std::memory_order_relaxed);
  t->dyn->rank = UINT32_MAX; /* unset: resolved at submit */
  return t;
}

int32_t ptc_dtask_arg(ptc_task_t *t, ptc_dtile_t *tile, int32_t mode) {
  DynExt *dx = t->dyn;
  if (!dx || dx->nb_flows >= PTC_MAX_FLOWS) return -1;
  int f = dx->nb_flows++;
  dx->modes[f] = mode;
  dx->tiles[f] = tile;
  std::lock_guard<std::mutex> g(tile->lock);
  copy_retain(tile->copy);
  t->data[f] = tile->copy;

  auto add_dep = [&](ptc_task *pred) {
    if (!pred || pred == t || !pred->dyn) return;
    std::lock_guard<std::mutex> pg(pred->dyn->lock);
    if (!pred->dyn->completed) {
      dx->remaining.fetch_add(1, std::memory_order_relaxed);
      pred->dyn->succs.push_back(t);
    }
  };

  /* RAW/WAW: everyone orders after the last writer */
  add_dep(tile->last_writer);
  if (mode & PTC_DTD_OUTPUT) {
    /* WAR: writers wait for all current readers, then take the chain */
    for (ptc_task *r : tile->readers) add_dep(r);
    if (tile->last_writer) dyn_release(tile->last_writer);
    for (ptc_task *r : tile->readers) dyn_release(r);
    tile->readers.clear();
    dyn_retain(t);
    tile->last_writer = t;
  } else {
    /* amortized pruning: drop already-completed readers so read-heavy
     * chains don't retain dead tasks (and writers scan fewer entries) */
    size_t w = 0;
    for (size_t i = 0; i < tile->readers.size(); i++) {
      ptc_task *r = tile->readers[i];
      bool done;
      {
        std::lock_guard<std::mutex> rg(r->dyn->lock);
        done = r->dyn->completed;
      }
      if (done)
        dyn_release(r);
      else
        tile->readers[w++] = r;
    }
    tile->readers.resize(w);
    dyn_retain(t);
    tile->readers.push_back(t);
  }
  return f;
}

/* Declare the placement rank of a dynamic task (default: first OUTPUT
 * tile's owner; fallback myrank).  Must be called before submit. */
void ptc_dtask_set_rank(ptc_task_t *t, int32_t rank) {
  if (t && t->dyn && rank >= 0) t->dyn->rank = (uint32_t)rank;
}

int32_t ptc_dtask_submit(ptc_context_t *ctx, ptc_task_t *t, int64_t window) {
  ptc_taskpool *tp = t->tp;
  DynExt *dx = t->dyn;
  /* distributed placement: explicit rank (ptc_dtask_set_rank), else the
   * first OUTPUT tile's owner, else this rank (reference: DTD remote-task
   * shadows, parsec/interfaces/dtd/insert_function.c) */
  std::vector<uint8_t> early_payload;
  bool have_early = false;
  if (ctx->nodes > 1) {
    uint32_t rank = dx->rank;
    if (rank == UINT32_MAX) {
      rank = ctx->myrank;
      for (int f = 0; f < dx->nb_flows; f++) {
        if (dx->modes[f] & PTC_DTD_OUTPUT) {
          rank = dx->tiles[f] ? dx->tiles[f]->owner : ctx->myrank;
          break;
        }
      }
    }
    dx->rank = rank;
    dx->shadow = rank != ctx->myrank;
    if (dx->shadow) {
      /* +1 message dep: released by the owner's completion broadcast */
      dx->remaining.fetch_add(1, std::memory_order_relaxed);
      std::lock_guard<std::mutex> g(tp->dtd_lock);
      auto it = tp->dtd_early.find(dx->seq);
      if (it != tp->dtd_early.end()) {
        /* the completion beat the insertion: apply after bookkeeping */
        early_payload = std::move(it->second);
        tp->dtd_early.erase(it);
        have_early = true;
      } else {
        tp->dtd_shadows[dx->seq] = t;
      }
    }
  } else {
    dx->rank = ctx->myrank;
  }
  if (window > 0) {
    std::unique_lock<ptc_mutex> lk(tp->window_lock);
    tp->window_cv.wait(lk, [&] {
      return tp->nb_tasks.load() < window ||
             tp->completed.load(std::memory_order_acquire) ||
             ctx->shutdown.load(std::memory_order_acquire);
    });
  }
  if (tp->completed.load(std::memory_order_acquire)) {
    /* pool aborted (a body failed): refuse the insertion */
    if (dx->shadow && !have_early) {
      std::lock_guard<std::mutex> g(tp->dtd_lock);
      tp->dtd_shadows.erase(dx->seq);
    }
    for (int f = 0; f < dx->nb_flows; f++)
      if (t->data[f]) copy_release(ctx, t->data[f]);
    dyn_release(t);
    return -1;
  }
  tp->nb_tasks.fetch_add(1, std::memory_order_acq_rel);
  tp->nb_total.fetch_add(1, std::memory_order_relaxed);
  ptc_context_start(ctx);
  /* stale-mirror pulls: a LOCAL task reading a tile whose bytes live on
   * the remote writer's rank (marker completion) must not run until the
   * pull lands; a local OUTPUT-only writer clears the mark instead (it
   * overwrites — nobody here ever needed the old bytes) */
  if (ctx->nodes > 1 && !dx->shadow) {
    struct PendingFetch {
      ptc_dtile *tile;
      uint64_t seq;
      int32_t flow;
      uint32_t src;
    };
    std::vector<PendingFetch> fetches;
    for (int f = 0; f < dx->nb_flows; f++) {
      ptc_dtile *tile = dx->tiles[f];
      if (!tile) continue;
      std::lock_guard<std::mutex> g(tile->lock);
      if (!tile->stale) continue;
      if (dx->modes[f] & PTC_DTD_INPUT) {
        if (dtd_add_fetch_waiter_locked(tile, t))
          fetches.push_back(PendingFetch{tile, tile->stale_seq,
                                         tile->stale_flow, tile->stale_src});
      } else if ((dx->modes[f] & PTC_DTD_OUTPUT) && !tile->fetch_inflight &&
                 tile->fetch_waiters.empty()) {
        tile->stale = false;
      }
    }
    for (const PendingFetch &pf : fetches) {
      {
        std::lock_guard<std::mutex> g(tp->dtd_lock);
        tp->dtd_fetch_pending[{pf.seq, pf.flow}] = pf.tile;
      }
      ptc_comm_send_dtd_fetch(ctx, pf.src, tp->id, pf.seq, pf.flow);
    }
  }
  /* drop the submission hold; schedule if all preds already done */
  if (dx->remaining.fetch_sub(1, std::memory_order_acq_rel) == 1)
    schedule_task(ctx, 0, t);
  /* apply an early-arrived completion now the counts are consistent
   * (drops the message hold; may schedule the shadow) */
  if (have_early)
    ptc_dtd_apply_complete(ctx, t, early_payload.data(), early_payload.size());
  return 0;
}

/* Batched DTD insertion: ONE native crossing (and one GIL release from
 * ctypes) inserts a whole window of dynamic tasks, instead of the
 * 2+nargs crossings per task the begin/arg/submit triple costs from
 * Python.  Spec stream, per task:
 *   [body_kind, body_arg, priority, rank(-1 = auto), nargs,
 *    (tile_ptr, mode) * nargs]
 * Window throttling applies per task, exactly as ptc_dtask_submit.
 * Returns the number of tasks inserted (== the whole stream), or
 * ~inserted when the pool refused an insertion (aborted) or the stream
 * is malformed — the first `inserted` tasks stay in. */
int64_t ptc_dtask_insert_batch(ptc_context_t *ctx, ptc_taskpool_t *tp,
                               const int64_t *spec, int64_t len,
                               int64_t window) {
  int64_t i = 0, inserted = 0;
  while (i < len) {
    if (i + 5 > len) return ~inserted;
    int32_t body_kind = (int32_t)spec[i];
    int64_t body_arg = spec[i + 1];
    int32_t prio = (int32_t)spec[i + 2];
    int64_t rank = spec[i + 3];
    int64_t nargs = spec[i + 4];
    i += 5;
    if (nargs < 0 || nargs > PTC_MAX_FLOWS || i + 2 * nargs > len)
      return ~inserted; /* validated BEFORE building the task */
    ptc_task *t = ptc_dtask_begin(tp, body_kind, body_arg, prio);
    for (int64_t a = 0; a < nargs; a++) {
      ptc_dtile *tile = (ptc_dtile *)(intptr_t)spec[i + 2 * a];
      ptc_dtask_arg(t, tile, (int32_t)spec[i + 2 * a + 1]);
    }
    i += 2 * nargs;
    if (rank >= 0) ptc_dtask_set_rank(t, (int32_t)rank);
    if (ptc_dtask_submit(ctx, t, window) != 0) return ~inserted;
    inserted++;
  }
  ctx->insert_batches.fetch_add(1, std::memory_order_relaxed);
  ctx->insert_batched_tasks.fetch_add(inserted, std::memory_order_relaxed);
  return inserted;
}

/* profiling */
void ptc_profile_enable(ptc_context_t *ctx, int32_t enable) {
  ctx->prof_level.store(enable, std::memory_order_release);
}

int64_t ptc_profile_take(ptc_context_t *ctx, int64_t *out, int64_t cap) {
  int64_t written = 0;
  for (auto *b : ctx->prof) {
    ProfLockGuard g(b);
    written += b->drain(out + written, cap - written, /*clear=*/true);
  }
  return written;
}

int32_t ptc_profile_level(ptc_context_t *ctx) {
  return ctx->prof_level.load(std::memory_order_relaxed);
}

/* the trace/metrics clock, exported so Python-side lifecycle
 * timestamps (profiling/scope.py) window trace spans exactly */
int64_t ptc_clock_ns(void) { return ptc_now_ns(); }

/* flight-recorder ring: bound each worker's trace buffer to `nbytes`,
 * overwriting oldest whole events when full (dropped counted).  0
 * restores unbounded buffers.  Reconfiguring clears buffered events —
 * arm it before the traced run, as the env form does. */
void ptc_profile_set_ring(ptc_context_t *ctx, int64_t nbytes) {
  size_t cap_words = 0;
  if (nbytes > 0) {
    cap_words = ((size_t)nbytes / sizeof(int64_t) / PROF_WORDS) * PROF_WORDS;
    if (cap_words == 0) cap_words = PROF_WORDS; /* at least one event */
  }
  ctx->trace_ring_bytes.store(
      cap_words ? (int64_t)(cap_words * sizeof(int64_t)) : 0,
      std::memory_order_relaxed);
  for (auto *b : ctx->prof) {
    ProfLockGuard g(b);
    b->cap_words = cap_words;
    b->head = b->count = 0;
    b->words.clear();
    if (cap_words) b->words.resize(cap_words);
  }
  /* ring mode arms the failure autodump even without an explicit path */
  if (cap_words && ctx->flight_dump_path.empty())
    ctx->flight_dump_path = "/tmp/ptc_flight";
}

int64_t ptc_profile_ring(ptc_context_t *ctx) {
  return ctx->trace_ring_bytes.load(std::memory_order_relaxed);
}

void ptc_flight_set_dump_path(ptc_context_t *ctx, const char *prefix) {
  ctx->flight_dump_path = prefix ? prefix : "";
}

int64_t ptc_profile_dropped(ptc_context_t *ctx) {
  int64_t total = 0;
  for (auto *b : ctx->prof) {
    ProfLockGuard g(b);
    total += b->dropped;
  }
  return total;
}

/* Dump the live trace buffers (WITHOUT draining) as a valid .ptt v2
 * container: magic + version + a minimal JSON header (the Python layer's
 * Trace.load fills in the default dictionary) + the raw event words.
 * The clock-sync meta rides along so a merged post-mortem is still
 * causally alignable. */
int32_t ptc_flight_dump(ptc_context_t *ctx, const char *path) {
  FILE *f = std::fopen(path, "wb");
  if (!f) return -1;
  int64_t clock[4] = {0, 0, 0, 0};
  ptc_comm_clock_stats(ctx, clock);
  char hdr[512];
  int hlen = std::snprintf(
      hdr, sizeof hdr,
      "{\"rank\": %u, \"dictionary\": {}, \"class_names\": [], "
      "\"meta\": {\"flight\": 1, \"dropped_events\": %lld, "
      "\"ring_bytes\": %lld, \"clock_offset_ns\": %lld, "
      "\"clock_err_ns\": %lld}}",
      ctx->myrank, (long long)ptc_profile_dropped(ctx),
      (long long)ctx->trace_ring_bytes.load(std::memory_order_relaxed),
      (long long)clock[0], (long long)clock[1]);
  if (hlen <= 0 || hlen >= (int)sizeof hdr) {
    std::fclose(f);
    return -1;
  }
  const char magic[8] = {'#', 'P', 'T', 'C', 'P', 'R', 'O', 'F'};
  uint32_t ver = 2, h = (uint32_t)hlen;
  bool ok = std::fwrite(magic, 1, 8, f) == 8 &&
            std::fwrite(&ver, 4, 1, f) == 1 &&
            std::fwrite(&h, 4, 1, f) == 1 &&
            std::fwrite(hdr, 1, (size_t)hlen, f) == (size_t)hlen;
  std::vector<int64_t> tmp;
  for (auto *b : ctx->prof) {
    if (!ok) break;
    ProfLockGuard g(b);
    int64_t n = b->cap_words ? (int64_t)b->count : (int64_t)b->words.size();
    tmp.resize((size_t)(n > 0 ? n : 1));
    int64_t got = b->drain(tmp.data(), n, /*clear=*/false);
    if (got > 0)
      ok = std::fwrite(tmp.data(), sizeof(int64_t), (size_t)got, f) ==
           (size_t)got;
  }
  ok = (std::fclose(f) == 0) && ok;
  return ok ? 0 : -1;
}

} /* extern "C" */
