/* parsec_core.h — C API of the native tpu-parsec core runtime.
 *
 * The native core owns the hot path of the framework: task-class
 * interpretation, dependency tracking, ready-task scheduling across worker
 * threads, the chore (incarnation) execution protocol, and local termination
 * detection.  It corresponds to the reference runtime's L0+L3 layers
 * (parsec/parsec.c, parsec/scheduling.c, parsec/parsec_internal.h — see
 * SURVEY.md §2.1/§2.4), re-designed: where the reference compiles each JDF
 * task class to C code (parsec/interfaces/ptg/ptg-compiler/jdf2c.c), this
 * core *interprets* a compact table-driven spec whose scalar expressions
 * (ranges, guards, indices, priorities) are bytecode for a tiny stack VM.
 * Python (or the JDF compiler) emits the spec; no codegen round-trip needed,
 * and the interpreter cost is O(tens of ns) per expression — far below the
 * per-task dispatch budget.
 *
 * Everything here is extern "C" and ctypes-friendly: opaque pointers +
 * int64 arrays only.
 */
#ifndef PTC_CORE_H
#define PTC_CORE_H

#include <stdint.h>
#include <stddef.h>

#ifdef __cplusplus
extern "C" {
#endif

/* ---------------------------------------------------------------- limits */
#define PTC_MAX_LOCALS 20   /* matches reference MAX_LOCAL_COUNT */
#define PTC_MAX_FLOWS  20   /* matches reference MAX_PARAM_COUNT */

/* ------------------------------------------------------- hook protocol
 * Return protocol of a task body (chore), mirroring the reference's
 * parsec_hook_return_t (parsec/scheduling.c:124-203 consumption):       */
enum {
  PTC_HOOK_DONE    = 0,   /* body executed, complete the task            */
  PTC_HOOK_AGAIN   = 1,   /* not executed, reschedule on same device     */
  PTC_HOOK_ASYNC   = 2,   /* ownership transferred (device queue); the
                             owner must call ptc_task_complete() later   */
  PTC_HOOK_NEXT    = 3,   /* try the next chore incarnation              */
  PTC_HOOK_DISABLE = 4,   /* disable this chore for the class; try next  */
  PTC_HOOK_ERROR   = -1
};

/* flow access flags */
enum {
  PTC_FLOW_READ  = 1,
  PTC_FLOW_WRITE = 2,
  PTC_FLOW_RW    = 3,
  PTC_FLOW_CTL   = 4
};

/* chore body kinds (spec "chore" entries) */
enum {
  PTC_BODY_NOOP     = 0,  /* arg ignored */
  PTC_BODY_CB       = 1,  /* arg = body-callback id (ptc_register_body) */
  PTC_BODY_DEVICE   = 2   /* arg = device queue id: push + return ASYNC */
};

/* device types for chores (scheduler picks first enabled/accepting) */
enum {
  PTC_DEV_CPU = 0,
  PTC_DEV_TPU = 1,
  PTC_DEV_RECURSIVE = 2
};

/* ------------------------------------------------------- expression VM
 * An expr is encoded in a spec as [nwords, w0, w1, ...]; nwords==0 means
 * the constant 0 (also used for "no guard" == always true, by convention
 * guards with nwords==0 evaluate to 1 — see PTC_EXPR_EMPTY_TRUE use).
 * Stack machine over int64.  Operand-carrying opcodes consume the next
 * word.                                                                 */
enum {
  PTC_OP_IMM    = 1,   /* push operand                                  */
  PTC_OP_LOCAL  = 2,   /* push locals[operand]                          */
  PTC_OP_GLOBAL = 3,   /* push taskpool globals[operand]                */
  PTC_OP_ADD    = 4,
  PTC_OP_SUB    = 5,
  PTC_OP_MUL    = 6,
  PTC_OP_DIV    = 7,
  PTC_OP_MOD    = 8,
  PTC_OP_NEG    = 9,
  PTC_OP_EQ     = 10,
  PTC_OP_NE     = 11,
  PTC_OP_LT     = 12,
  PTC_OP_LE     = 13,
  PTC_OP_GT     = 14,
  PTC_OP_GE     = 15,
  PTC_OP_AND    = 16,
  PTC_OP_OR     = 17,
  PTC_OP_NOT    = 18,
  PTC_OP_SELECT = 19,  /* pop b, a, c; push c ? a : b                   */
  PTC_OP_MIN    = 20,
  PTC_OP_MAX    = 21,
  PTC_OP_CALL   = 22,  /* push expr-callback(operand)(locals, globals)  */
  PTC_OP_SHL    = 23,  /* pop b, a; push a << b (b clamped to [0,62])   */
  PTC_OP_SHR    = 24   /* pop b, a; push a >> b (arithmetic)            */
};

/* ------------------------------------------------------- opaque types */
typedef struct ptc_context  ptc_context_t;
typedef struct ptc_taskpool ptc_taskpool_t;
typedef struct ptc_task     ptc_task_t;
typedef struct ptc_data     ptc_data_t;
typedef struct ptc_copy     ptc_copy_t;

/* ------------------------------------------------------- callbacks */
/* inline-expression escape hatch (JDF %{ ... %}) */
typedef int64_t (*ptc_expr_cb)(void *user, const int64_t *locals,
                               int32_t nb_locals, const int64_t *globals);
/* task body; runs on a worker thread */
typedef int32_t (*ptc_body_cb)(void *user, ptc_task_t *task);
/* data-collection vtable pieces (Python-defined collections) */
typedef uint32_t   (*ptc_rank_of_cb)(void *user, const int64_t *idx, int32_t n);
typedef ptc_data_t*(*ptc_data_of_cb)(void *user, const int64_t *idx, int32_t n);

/* ------------------------------------------------------- context */
ptc_context_t *ptc_context_new(int32_t nb_workers);
void ptc_context_destroy(ptc_context_t *ctx);
int32_t ptc_context_nb_workers(ptc_context_t *ctx);
/* start worker threads (idempotent) */
int32_t ptc_context_start(ptc_context_t *ctx);
/* block until every added taskpool has completed */
int32_t ptc_context_wait(ptc_context_t *ctx);
/* non-blocking: 1 if all taskpools complete, 0 otherwise */
int32_t ptc_context_test(ptc_context_t *ctx);
/* scheduler selection, by name ("lfq", "gd", "ap"); default lfq.
 * Unknown names fall back to lfq (with a one-shot stderr warning). */
int32_t ptc_context_set_scheduler(ptc_context_t *ctx, const char *name);
/* canonical name of the module that will run (valid until ctx destroy) */
const char *ptc_context_get_scheduler(ptc_context_t *ctx);
/* same-worker ready-task bypass (PTC_MCA_sched_bypass; reference:
 * keep_highest_priority_task, parsec/scheduling.c:373-396): a worker
 * completing a task executes its best ready successor directly instead
 * of round-tripping schedule()+select().  Default on. */
void ptc_context_set_sched_bypass(ptc_context_t *ctx, int32_t on);
int32_t ptc_context_get_sched_bypass(ptc_context_t *ctx);
/* tasks handed to the scheduler and not yet back from their execution
 * (routed to a device queue, completed, requeued or failed).  0 means no
 * task can still land on a device queue except through a completion
 * made outside the workers (device managers, writeback, comm). */
int64_t ptc_tasks_in_flight(ptc_context_t *ctx);
/* dispatch fast-path counters — [0] bypass hits, [1] bypass enabled,
 * [2]/[3] task-freelist hits/misses, [4]/[5] arena hits/misses,
 * [6]/[7] DTD insert batches / batch-inserted tasks, [8]/[9] scheduler
 * inject pushes/pops, [10]/[11] QoS lane selects / wave preemptions.
 * Returns slots written (<= cap). */
int64_t ptc_sched_stats(ptc_context_t *ctx, int64_t *out, int64_t cap);
/* Per-pool QoS (serving runtime): arm a taskpool with a scheduling
 * priority (strict across pools under the lws module: higher-priority
 * pools win every select boundary — the wave-boundary preemption point;
 * negative = background; clamped to +-1023) and a weight (stride-
 * scheduled sharing within one priority tier).  Priority-ordered
 * modules (ap/spq/ltq) see the pool priority through the composed task
 * priority instead.  Call before ptc_context_add_taskpool. */
void ptc_tp_set_qos(ptc_taskpool_t *tp, int32_t priority, int64_t weight);
/* out = [priority, weight, scheduled, selected, executed, wait_ns,
 * queued, preempts]; returns slots written, 0 when QoS is not armed. */
int64_t ptc_tp_qos_stats(ptc_taskpool_t *tp, int64_t *out, int64_t cap);
/* QoS wave-boundary preemption knob (PTC_MCA_sched_qos_preempt,
 * default on): off = a worker drains the lane it last served until
 * empty instead of re-ranking lanes by priority at every select. */
void ptc_context_set_qos_preempt(ptc_context_t *ctx, int32_t on);
int32_t ptc_context_get_qos_preempt(ptc_context_t *ctx);
/* Request scope (observability): stamp the request/pool id this
 * taskpool serves.  Nonzero scopes ride EXEC/RELEASE span aux words,
 * cross the wire on ACTIVATE frames (the delivery side re-emits them
 * as PROF_KEY_SCOPE flow tags), and surface in the watchdog's inflight
 * slots.  Stamp beside ptc_tp_set_qos, before the pool runs. */
void ptc_tp_set_scope(ptc_taskpool_t *tp, int64_t scope_id);
int64_t ptc_tp_scope(ptc_taskpool_t *tp);
/* the owning pool's scope of one task (0 = unscoped) — the device
 * layer stamps H2D/STREAM staging spans with it */
int64_t ptc_task_scope(ptc_task_t *t);
/* the runtime's trace/metrics clock (ptc_now_ns: TSC fast path
 * calibrated to steady_clock).  Request-lifecycle timestamps that must
 * window trace spans (profiling/scope.py) read THIS clock — the TSC
 * epoch drifts from CLOCK_MONOTONIC over a long process, so mixing the
 * two misaligns by milliseconds after minutes. */
int64_t ptc_clock_ns(void);

/* registries: return non-negative id, or -1 on error */
int32_t ptc_register_expr_cb(ptc_context_t *ctx, ptc_expr_cb cb, void *user);
int32_t ptc_register_body(ptc_context_t *ctx, ptc_body_cb cb, void *user);
int32_t ptc_register_collection(ptc_context_t *ctx, uint32_t nodes,
                                uint32_t myrank, ptc_rank_of_cb rank_of,
                                ptc_data_of_cb data_of, void *user);
/* built-in linear host collection: key k -> base + k*elem_size, rank k%nodes */
int32_t ptc_register_linear_collection(ptc_context_t *ctx, uint32_t nodes,
                                       uint32_t myrank, void *base,
                                       int64_t nb_elems, int64_t elem_size);
/* arena: size-class allocator for WRITE-only flow outputs */
int32_t ptc_register_arena(ptc_context_t *ctx, int64_t elem_size);
/* tool access to a registered collection's vtable (ptg_to_dtd, dumps):
 * the datum at idx[0..n-1] (lazily created for linear collections) and
 * its owning rank */
ptc_data_t *ptc_dc_data_of(ptc_context_t *ctx, int32_t dc_id,
                           const int64_t *idx, int32_t n);
int32_t ptc_dc_rank_of(ptc_context_t *ctx, int32_t dc_id,
                       const int64_t *idx, int32_t n);

/* wire datatype: `count` blocks of `elem_bytes` spaced `stride_bytes`
 * apart (contiguous when stride == elem).  Attached per dep (JDF
 * `[type = name]`): OUT deps pack to contiguous wire bytes, IN deps
 * scatter into the consumer layout — the MPI-datatype analog
 * (reference: parsec/datatype/datatype_mpi.c).  SPMD creation order
 * defines the id, like arenas/collections. */
int32_t ptc_register_datatype(ptc_context_t *ctx, int64_t elem_bytes,
                              int64_t count, int64_t stride_bytes);

/* indexed datatype: explicit (offset, len) byte segments — the
 * MPI_Type_indexed analog; expresses lower/upper triangles etc.  Used
 * as a wire type (pack/scatter the segments) or as a dep's LOCAL
 * reshape type (JDF `[type = name]`): the dep's data is routed through
 * a new datacopy holding only the selected bytes (other bytes zero),
 * memoized per (source copy, type) — the reference's datacopy-future
 * reshape chain (parsec/parsec_reshape.c, parsec_datacopy_future.c). */
int32_t ptc_register_datatype_indexed(ptc_context_t *ctx,
                                      const int64_t *offsets,
                                      const int64_t *lens, int32_t nseg);

/* element-cast datatype: contiguous `count` elements (count < 0 = the
 * whole copy) converted src_kind -> dst_kind element-wise.  As a local
 * reshape type this is the arbitrary type->type promise of the
 * reference's reshape machinery; on a Mem write-back dep the conversion
 * reverses (the copy holds dst_kind, the collection holds src_kind). */
enum {
  PTC_ELEM_F32 = 0,
  PTC_ELEM_F64 = 1,
  PTC_ELEM_I32 = 2,
  PTC_ELEM_I64 = 3,
  PTC_ELEM_U8 = 4
};
int32_t ptc_register_datatype_cast(ptc_context_t *ctx, int32_t src_kind,
                                   int32_t dst_kind, int64_t count);

/* local-reshape accounting: conversions = reshape futures triggered
 * (distinct (copy, type) pairs materialized), hits = memoized or
 * identity reuses.  The avoidable-reshape test matrix asserts these. */
void ptc_ctx_reshape_stats(ptc_context_t *ctx, int64_t *conversions,
                           int64_t *hits);

/* set my rank / world for affinity filtering (default 0/1) */
void ptc_context_set_rank(ptc_context_t *ctx, uint32_t myrank, uint32_t nodes);

/* worker thread binding (reference: parsec_hwloc.c + bindthread.c):
 * mode 0 = unbound (default), 1 = round-robin core pinning over the
 * process's allowed cpuset.  Call before the first taskpool runs. */
void ptc_context_set_binding(ptc_context_t *ctx, int32_t mode);

/* vpmap (reference: parsec/vpmap.c virtual processes): vp id per
 * worker, set before the context starts.  Hierarchical schedulers
 * (lhq) steal within a worker's vp before crossing vps.  Returns 0, or
 * -1 when the context already started (the map would be ignored). */
/* ptc-topo rank remap (plan.remap_ranks / Taskpool.run(remap=)): a
 * permutation applied to every collection rank_of result, relabeling
 * which physical rank plays which logical role.  Must be SPMD-identical
 * across ranks; NULL / n<=0 clears it.  Set between taskpool build and
 * run — rank_of is evaluated lazily at pool startup. */
void ptc_context_set_rank_map(ptc_context_t *ctx, const int32_t *map,
                              int32_t n);
int32_t ptc_context_set_vpmap(ptc_context_t *ctx, const int32_t *vp,
                              int32_t n);
/* test/debug probe: a hierarchical scheduler's computed steal order
 * for `worker` (count written, or -1 for flat modules) */
int32_t ptc_sched_victim_order(ptc_context_t *ctx, int32_t worker,
                               int32_t *out, int32_t cap);

/* per-subsystem debug verbosity (reference: the parsec output/debug
 * streams, parsec/utils/debug.c — one stream per subsystem with its own
 * verbosity).  Level 0 = warnings only (default); >=1 enables `ptc
 * [subsys]` informational diagnostics on stderr. */
enum {
  PTC_DBG_RUNTIME = 0,
  PTC_DBG_COMM = 1,
  PTC_DBG_DEVICE = 2,
  PTC_DBG_NSUBSYS = 3
};
void ptc_context_set_verbose(ptc_context_t *ctx, int32_t subsys,
                             int32_t level);
int32_t ptc_context_verbose(ptc_context_t *ctx, int32_t subsys);
/* the cpu worker w was bound to, or -1 (unbound / binding failed /
 * worker not started yet) */
int32_t ptc_worker_binding(ptc_context_t *ctx, int32_t worker);

/* ------------------------------------------------------- taskpool */
ptc_taskpool_t *ptc_tp_new(ptc_context_t *ctx, int32_t nb_globals,
                           const int64_t *globals);
void ptc_tp_destroy(ptc_taskpool_t *tp);
/* register a task class from its spec blob; returns class id */
int32_t ptc_tp_add_class(ptc_taskpool_t *tp, const char *name,
                         const int64_t *spec, int64_t spec_len);
/* enumerate startup tasks, install task counts, release to scheduler */
int32_t ptc_context_add_taskpool(ptc_context_t *ctx, ptc_taskpool_t *tp);
/* block until this taskpool completed */
int32_t ptc_tp_wait(ptc_taskpool_t *tp);
int64_t ptc_tp_nb_tasks(ptc_taskpool_t *tp);       /* remaining local tasks */
int64_t ptc_tp_addto_nb_tasks(ptc_taskpool_t *tp, int64_t delta);
int64_t ptc_tp_nb_total_tasks(ptc_taskpool_t *tp); /* as counted at startup */
int64_t ptc_tp_nb_errors(ptc_taskpool_t *tp);      /* failed/dropped tasks  */
/* classes whose dependency tracking runs on the dense-array engine
 * (auto-chosen at startup when instances fit a bounded box; reference:
 * parsec_internal.h:201-216 dense vs hash find_deps) */
int32_t ptc_tp_dense_classes(ptc_taskpool_t *tp);
/* keep a taskpool alive for dynamic insertion (DTD): while open, reaching
 * zero remaining tasks does not complete it */
void ptc_tp_set_open(ptc_taskpool_t *tp, int32_t open);
/* block until every task inserted so far completed, WITHOUT closing the
 * pool (the DTD data-flush quiescence point); -1 if the pool aborted */
int32_t ptc_tp_drain(ptc_taskpool_t *tp);

/* Completion callback, fired exactly once when the taskpool completes —
 * BEFORE the context's active-pool count drops, so a callback that adds a
 * follow-up taskpool keeps ptc_context_wait blocked across the seam.  This
 * is the sequential-composition seam (reference: tp->on_complete used by
 * parsec_compose, parsec/compound.c:25-95) and the recursive-task seam
 * (parsec/recursive.h).  Runs on whichever thread completes the pool; it
 * must not block on the pool itself. */
typedef void (*ptc_tp_complete_cb)(void *user, ptc_taskpool_t *tp);
void ptc_tp_set_on_complete(ptc_taskpool_t *tp, ptc_tp_complete_cb cb,
                            void *user);

/* ------------------------------------------------------- data */
/* create a host-backed datum with a single host copy */
ptc_data_t *ptc_data_new(int64_t key, void *ptr, int64_t size);
void ptc_data_destroy(ptc_data_t *d);
ptc_copy_t *ptc_data_host_copy(ptc_data_t *d);
void    *ptc_copy_ptr(ptc_copy_t *c);
int64_t  ptc_copy_size(ptc_copy_t *c);
int64_t  ptc_copy_handle(ptc_copy_t *c);
void     ptc_copy_set_handle(ptc_copy_t *c, int64_t handle);
int32_t  ptc_copy_version(ptc_copy_t *c);

/* ------------------------------------------------------- task accessors */
int64_t  ptc_task_local(ptc_task_t *t, int32_t i);
int32_t  ptc_task_class(ptc_task_t *t);
int32_t  ptc_task_priority(ptc_task_t *t);
void    *ptc_task_data_ptr(ptc_task_t *t, int32_t flow);
ptc_copy_t *ptc_task_copy(ptc_task_t *t, int32_t flow);
ptc_taskpool_t *ptc_task_taskpool(ptc_task_t *t);
int64_t  ptc_tp_global(ptc_taskpool_t *tp, int32_t i);

/* ------------------------------------------------------- device queues
 * A device queue decouples ASYNC chores from workers: the chore body
 * (PTC_BODY_DEVICE) pushes the task and returns ASYNC; a device manager
 * thread (Python/TPU side) pops, executes, then calls ptc_task_complete.
 * This is the seam the TPU device module plugs into (reference analog:
 * the CUDA manager thread + pending fifo, device_cuda_module.c:2563).  */
int32_t ptc_device_queue_new(ptc_context_t *ctx);
/* load balancing (reference: parsec_get_best_device, device.c:79): when a
 * task class offers several enabled device chores, the runtime routes each
 * task to the queue minimising depth/weight; weight = relative speed */
void ptc_device_queue_set_weight(ptc_context_t *ctx, int32_t qid, double w);
int64_t ptc_device_queue_depth(ptc_context_t *ctx, int32_t qid);
/* blocking pop with timeout (ms); NULL on timeout or shutdown */
ptc_task_t *ptc_device_pop(ptc_context_t *ctx, int32_t qid, int32_t timeout_ms);
/* Ready-peek span for the device prefetch lane: snapshot up to
 * `max_tasks` tasks still queued on `qid` WITHOUT popping.  Per task the
 * flat buffer receives
 *   [task_ref, n_copies, (copy_ptr, data_ptr, size, version) * n]
 * with one record per READ data flow.  task_ref is an opaque grouping
 * key — never dereference it (the task may be popped and recycled at
 * any moment).  Emitted copies are retained; the caller MUST
 * ptc_copy_unpin each copy_ptr exactly once.  Returns words written. */
int64_t ptc_peek_ready(ptc_context_t *ctx, int32_t qid, int64_t *out,
                       int64_t max_words, int32_t max_tasks);
void ptc_copy_unpin(ptc_context_t *ctx, ptc_copy_t *copy);
/* wave-granular ready-front census for the wave compiler: per queued
 * task on `qid`, [class_id (-1 for DTD), taskpool_ptr] — the compiler
 * sees the FULL ready front (is the rest of a certified wave already
 * queued?) without popping or pinning anything.  Returns task count. */
int64_t ptc_peek_ready_front(ptc_context_t *ctx, int32_t qid,
                             int64_t *out, int64_t max_tasks);
/* data-affinity routing (reference: parsec_get_best_device's
 * owner_device/preferred_device pass, device.c:100-117, before the load
 * pass at :129-160).  The device layer stamps which queue holds a
 * CURRENT mirror (version-checked) of the copy with this handle;
 * best-device selection then prefers a queue owning one of the task's
 * flows — write flows first, read flows as fallback — unless the
 * owner's projected load exceeds skew * the least-loaded candidate
 * (affinity must not defeat load balance; skew<=0 disables the pass). */
void ptc_device_set_data_owner(ptc_context_t *ctx, int64_t handle,
                               int32_t qid, int32_t version);
/* erase only if currently owned by qid (qid<0: erase unconditionally) */
void ptc_device_clear_data_owner(ptc_context_t *ctx, int64_t handle,
                                 int32_t qid);
/* returns owner qid or -1; *version_out = stamped mirror version */
int32_t ptc_device_get_data_owner(ptc_context_t *ctx, int64_t handle,
                                  int32_t *version_out);
void ptc_device_set_affinity_skew(ptc_context_t *ctx, double skew);
/* completion entry point for ASYNC owners (any thread) */
void ptc_task_complete(ptc_context_t *ctx, ptc_task_t *task);
/* failure entry point for ASYNC owners: aborts the task's taskpool
 * (successors are never released; waiters observe the error) */
void ptc_task_fail(ptc_context_t *ctx, ptc_task_t *task);

/* ------------------------------------------------------- profiling
 * Minimal paired-event trace: per-worker buffers of (key, begin/end,
 * class, taskhash, t_ns).  ptc_profile_take copies out and clears.      */
void ptc_profile_enable(ptc_context_t *ctx, int32_t enable);
/* per-worker SELECTED-task counters (scheduler pops; the PAPI-SDE
 * TASKS_SCHEDULED analog) -> out[0..cap); returns count */
int64_t ptc_worker_stats(ptc_context_t *ctx, int64_t *out, int64_t cap);
int64_t ptc_worker_steals(ptc_context_t *ctx, int64_t *out, int64_t cap);
/* externally-sourced trace event (device manager spans): same buffer,
 * dictionary, and PINS fan-out as native events; no-op when both
 * profiling and PINS are off */
void ptc_prof_event(ptc_context_t *ctx, int64_t key, int64_t phase,
                    int64_t class_id, int64_t l0, int64_t l1, int64_t aux);
/* runtime-native collective counters (the ptc_coll_* task-class family,
 * parsec_tpu/comm/coll.py): out6 = [steps executed, frames sent, bytes
 * sent, frames received, bytes received, reserved] */
void ptc_coll_stats(ptc_context_t *ctx, int64_t *out6);
/* returns number of int64 words written into out (5 per event), up to cap */
int64_t ptc_profile_take(ptc_context_t *ctx, int64_t *out, int64_t cap);
/* current trace level (0 off, 1 spans, 2 +edges) */
int32_t ptc_profile_level(ptc_context_t *ctx);
/* flight-recorder ring mode (PTC_MCA_runtime_trace_ring): bound each
 * worker's trace buffer to `nbytes` (rounded down to whole events),
 * overwriting OLDEST events when full; 0 restores unbounded buffers.
 * Reconfiguring clears buffered events (set it before the run). */
void ptc_profile_set_ring(ptc_context_t *ctx, int64_t nbytes);
int64_t ptc_profile_ring(ptc_context_t *ctx); /* configured bytes/worker */
/* events overwritten-before-taken across all workers (ring mode) */
int64_t ptc_profile_dropped(ptc_context_t *ctx);
/* Dump the current trace buffers (WITHOUT draining them) as a valid
 * .ptt v2 file at `path` — the flight-recorder sink.  Also fired
 * automatically (once, to PTC_MCA_runtime_trace_dump or
 * /tmp/ptc_flight.<rank>.ptt) on taskpool abort and peer loss when
 * tracing is on.  Returns 0, or -1 when the file cannot be written. */
int32_t ptc_flight_dump(ptc_context_t *ctx, const char *path);
/* arm/replace the autodump path prefix (NULL or "" disarms unless ring
 * mode re-arms the /tmp default); call before the traced run */
void ptc_flight_set_dump_path(ptc_context_t *ctx, const char *prefix);

/* ---- crash-durable flight recorder (ptc-blackbox) ----
 * Arm an async-signal-safe SIGSEGV/SIGABRT/SIGBUS handler that
 * write()s the flight-recorder ring tail + an inflight-slots snapshot
 * (synthetic PROF_KEY_INFLIGHT instant spans) to `path` as a .ptt v2
 * file before re-raising the signal.  The header is preformatted on
 * the normal path; refresh it (clock offsets drift between fences)
 * with ptc_crash_update_meta on the journal cadence.  One dump per
 * arming; peer-loss reaping fires the same dump on survivors.  Disarm
 * restores the previous signal dispositions (call at context destroy).
 * ptc_crash_dump_now writes the artifact without a signal (returns 0
 * written, 1 already fired, -1 not armed for this context). */
int32_t ptc_crash_arm(ptc_context_t *ctx, const char *path);
void ptc_crash_update_meta(ptc_context_t *ctx);
void ptc_crash_disarm(ptc_context_t *ctx);
int32_t ptc_crash_dump_now(ptc_context_t *ctx);

/* ------------------------------------------------------- ptc_metrics
 * Always-on, low-overhead latency metrics: per-worker lock-free
 * log2-bucket histograms (8 linear sub-buckets per octave) accumulated
 * on the span-close paths — task EXEC duration per class, sampled
 * release latency, dispatch-time h2d stall, comm/coll rendezvous wait.
 * Independent of tracing (works at trace level 0); disable with
 * PTC_MCA_runtime_metrics=0 or ptc_metrics_enable(ctx, 0).             */
void ptc_metrics_enable(ptc_context_t *ctx, int32_t on);
int32_t ptc_metrics_enabled(ptc_context_t *ctx);
/* release-latency sampling stride (1 = every task; default 64) */
void ptc_metrics_set_release_sample(ptc_context_t *ctx, int32_t n);
/* feed an external duration into a histogram (device layer h2d stall;
 * kind = PTC_MET_*, mid = interned class id or -1) */
void ptc_metrics_record(ptc_context_t *ctx, int32_t kind, int32_t mid,
                        int64_t ns);
/* intern / inspect the class-name registry (mid is stable per context) */
int32_t ptc_metrics_intern(ptc_context_t *ctx, const char *name);
int32_t ptc_metrics_nclasses(ptc_context_t *ctx);
int32_t ptc_metrics_class_name(ptc_context_t *ctx, int32_t mid, char *out,
                               int32_t cap);
/* decoder constants: [nkinds, max_classes, buckets, subbits] */
void ptc_metrics_layout(int64_t *out4);
/* flat dump, per record [kind, mid, count, sum, b0..]; stride =
 * 4 + buckets.  merged=1 folds the fence-time peer snapshots (rank 0).
 * Returns words written. */
int64_t ptc_metrics_snapshot(ptc_context_t *ctx, int64_t *out, int64_t cap,
                             int32_t merged);
/* open EXEC bodies: [worker, mid, begin_ns, scope_id] quads (watchdog
 * scan; scope_id = the owning pool's request scope, 0 = unscoped) */
int64_t ptc_metrics_inflight(ptc_context_t *ctx, int64_t *out, int64_t cap);
/* per-peer fence-time clock-sync RTTs (rank 0; watchdog slow-rank scan) */
int32_t ptc_metrics_peer_rtts(ptc_context_t *ctx, int64_t *out,
                              int32_t cap);

/* PINS: pluggable instrumentation callback at the trace event points
 * (reference: parsec/mca/pins/pins.h:26-54).  cb receives the 8-word
 * event record; key_mask selects event keys (bit k = PROF key k).
 * cb = NULL uninstalls.  Works with tracing off. */
typedef void (*ptc_pins_cb)(void *user, const int64_t *words);
void ptc_set_pins_cb(ptc_context_t *ctx, ptc_pins_cb cb, void *user,
                     uint64_t key_mask);

/* ------------------------------------------------------- DTD (dynamic)
 * Dynamic task discovery: tasks are inserted one by one with explicit
 * data arguments; dependencies derive from per-tile last-writer/reader
 * accessor chains (reference: parsec/interfaces/dtd/insert_function.c,
 * insert_function_internal.h:110-139 — SURVEY.md §2.7).  The taskpool
 * must be open (ptc_tp_set_open) while inserting.                       */
typedef struct ptc_dtile ptc_dtile_t;

enum { PTC_DTD_INPUT = 1, PTC_DTD_OUTPUT = 2, PTC_DTD_INOUT = 3 };

/* wrap a datum's host copy as a trackable tile */
ptc_dtile_t *ptc_dtile_new(ptc_context_t *ctx, ptc_data_t *d);
/* drop the tile tracker (does not free the datum) */
void ptc_dtile_destroy(ptc_context_t *ctx, ptc_dtile_t *tile);

/* begin a dynamic task: body as in chores (PTC_BODY_CB/NOOP/DEVICE) */
ptc_task_t *ptc_dtask_begin(ptc_taskpool_t *tp, int32_t body_kind,
                            int64_t body_arg, int32_t priority);
/* append a data argument (flow index = call order); mode PTC_DTD_*  */
int32_t ptc_dtask_arg(ptc_task_t *t, ptc_dtile_t *tile, int32_t mode);
/* submit; blocks while more than `window` tasks are in flight (0: no
 * throttle).  Returns 0, or -1 if the pool aborted (task refused). */
int32_t ptc_dtask_submit(ptc_context_t *ctx, ptc_task_t *t, int64_t window);
/* batched insertion: one crossing inserts a stream of task specs —
 * per task [body_kind, body_arg, priority, rank(-1 = auto), nargs,
 * (tile_ptr, mode) * nargs].  Same per-task window throttle as
 * ptc_dtask_submit.  Returns tasks inserted, or ~inserted on refusal /
 * malformed stream (the first `inserted` tasks stay in). */
int64_t ptc_dtask_insert_batch(ptc_context_t *ctx, ptc_taskpool_t *tp,
                               const int64_t *spec, int64_t len,
                               int64_t window);
int32_t ptc_dtask_nb_flows(ptc_task_t *t);
/* opaque user tag on a task (stored in the last local slot; used by the
 * device layer to key per-task DTD bodies without pointer-ABA issues) */
void ptc_task_set_tag(ptc_task_t *t, int64_t tag);
int64_t ptc_task_get_tag(ptc_task_t *t);

/* Notification when a copy with a nonzero handle reaches refcount 0: the
 * device layer drops its device-resident mirror (the handle is the device
 * layer's uid).  Called from whichever thread releases the last ref. */
typedef void (*ptc_copy_release_cb)(void *user, int64_t handle);
void ptc_set_copy_release_cb(ptc_context_t *ctx, ptc_copy_release_cb cb,
                             void *user);
/* Coherence pull: called (same thread) right before the runtime reads the
 * host bytes of a copy with a nonzero handle — comm payload serialization
 * and collection memory write-back.  The device layer writes its dirty
 * device mirror back to the host buffer, making CPU-after-TPU reads
 * automatic (no manual flush()). */
typedef void (*ptc_copy_sync_cb)(void *user, int64_t handle);
void ptc_set_copy_sync_cb(ptc_context_t *ctx, ptc_copy_sync_cb cb,
                          void *user);
/* Host-written invalidation: called right after the runtime OVERWRITES
 * the host bytes of a copy with a nonzero handle (collection write-back
 * memcpy — local release_deps or a remote PUT frame).  The host is now
 * authoritative, so the device layer must DROP any device mirror of the
 * copy: a stale dirty mirror left behind would be flushed over the newer
 * host bytes later (observed: a Mem-rooted chain's first-hop mirror
 * clobbering the final result at flush()).  A version check cannot
 * replace this — the write-back stores the SOURCE copy's version, which
 * can collide with the mirror's. */
typedef void (*ptc_copy_invalidate_cb)(void *user, int64_t handle);
void ptc_set_copy_invalidate_cb(ptc_context_t *ctx,
                                ptc_copy_invalidate_cb cb, void *user);

/* ---- device data plane (ICI seam) ----------------------------------
 * When registered, remote dependency payloads whose copy is device-
 * resident skip the host eager path: the ACTIVATE advertises a transfer
 * tag, the consumer pulls, and the payload is served from / delivered to
 * the device layer (reference seam: comm-engine put/get on registered
 * memory, parsec_comm_engine.h:139-160; on TPU pods the serve/deliver
 * pair rides ICI instead of host TCP).
 *   dp_register(copy_handle, size) -> tag>0 if a device mirror exists
 *                                     (the payload source), else 0
 *   dp_serve(tag, from, xfer_ok, &ptr, &real) -> wire byte size; ptr
 *       valid until dp_serve_done(tag).  `from` is the pulling rank: a
 *       colocated consumer (same process / same accelerator client) may
 *       be served a small by-reference token instead of the bytes — then
 *       `real` is set to the true payload size (the consumer-side copy
 *       is allocated at `real` and materialized lazily from the device
 *       mirror).  For byte serves, real == returned size.  `xfer_ok` is
 *       the PULLER's advertised transfer-plane capability (carried on
 *       the GET frame, set per-context via ptc_set_dp_can_pull after a
 *       successful consumer-side probe): serve a cross-process transfer
 *       token ONLY when it is nonzero — a token sent to a rank whose
 *       accelerator runtime cannot pull is unrecoverable (the real
 *       bytes were never sent).
 *   dp_deliver(ptr, size, tag) -> device-cache uid for the delivered
 *                                 payload (stamped on the new host copy)
 *   dp_bound(uid, ptr, size, host_valid) -> called after the consumer-
 *       side host copy exists, so the device layer can bind it as the
 *       mirror's writeback target.  host_valid=0 means the buffer was
 *       never written (by-reference delivery): the binding MUST mark the
 *       mirror dirty so host reads materialize it via the coherence pull.
 */
typedef int64_t (*ptc_dp_register_cb)(void *user, int64_t copy_handle,
                                      int64_t version, int64_t size);
typedef int64_t (*ptc_dp_serve_cb)(void *user, int64_t tag, int32_t from,
                                   int32_t xfer_ok, void **ptr_out,
                                   int64_t *real_out);
typedef void (*ptc_dp_serve_done_cb)(void *user, int64_t tag);
typedef int64_t (*ptc_dp_deliver_cb)(void *user, const void *ptr,
                                     int64_t size, int64_t tag);
typedef void (*ptc_dp_bound_cb)(void *user, int64_t uid, void *ptr,
                                int64_t size, int32_t host_valid);
void ptc_set_dataplane(ptc_context_t *ctx, ptc_dp_register_cb reg,
                       ptc_dp_serve_cb serve, ptc_dp_serve_done_cb done,
                       ptc_dp_deliver_cb deliver, ptc_dp_bound_cb bound,
                       void *user);
/* PROGRESSIVE SERVE (wire v4 streaming, PTC_MCA_comm_stream): when
 * registered, a chunked pull of a device payload is first OFFERED to
 * the device layer as a streaming session:
 *   dp_serve_stream(tag, from, xfer_ok, stream_id, total) -> 1 to
 *       accept (the device layer then d2h's the mirror in slices on its
 *       writeback lane, pushing each through ptc_dp_serve_progress), or
 *       0 to decline (the synchronous dp_serve path takes over — the
 *       right answer when a colocated/transfer token is the better
 *       serve).  Called on the comm thread; accept must only ENQUEUE
 *       the slicing work, never block on it.
 * ptc_dp_serve_progress returns 2 (absorbed, session completed: stop),
 * 1 (absorbed, keep streaming), 0 (session gone: stop), -1 (session
 * not installed yet: retry the same slice). */
typedef int32_t (*ptc_dp_serve_stream_cb)(void *user, int64_t tag,
                                          int32_t from, int32_t xfer_ok,
                                          uint64_t stream_id,
                                          int64_t total);
void ptc_set_dp_stream(ptc_context_t *ctx, ptc_dp_serve_stream_cb cb);
int32_t ptc_dp_serve_progress(ptc_context_t *ctx, uint64_t stream_id,
                              const void *bytes, uint64_t offset,
                              uint64_t len);
/* Advertise this rank's transfer-plane PULL capability on outgoing GET
 * frames (0 until the device layer's probe succeeds).  Producers serve
 * cross-process device tokens only to capable pullers; everyone else
 * gets real bytes over the host path. */
void ptc_set_dp_can_pull(ptc_context_t *ctx, int32_t ok);
/* nonzero if the copy is backed by persistent user data (ptc_data_new),
 * zero for transient arena-backed copies */
int32_t ptc_copy_is_persistent(ptc_copy_t *c);

/* ------------------------------------------------------- comm engine
 * Distributed control plane (reference: parsec_comm_engine.h vtable +
 * remote_dep protocol — SURVEY.md §2.5).  Ranks form a loopback/DCN TCP
 * full mesh; dependency activations, memory write-backs and DTD completion
 * broadcasts ride it.  Call ptc_context_set_rank first; then:            */
/* bring up the transport (rank r listens on base_port + r); no-op when
 * nodes <= 1.  Blocks until the full mesh is connected. */
int32_t ptc_comm_init(ptc_context_t *ctx, int32_t base_port);
/* flush queued sends + wait for every peer's matching fence: after this,
 * all messages sent before any rank's fence have been applied everywhere */
/* returns 0 on quiescence, -1 on timeout (PTC_MCA_comm_fence_timeout_s,
 * default 0 = wait forever; set seconds to arm) or peer loss */
int32_t ptc_comm_fence(ptc_context_t *ctx);
/* counting termination detection (fourcounter analog): double wave of
 * (app msgs sent, received, idle).  tp limits the idle predicate to one
 * pool (NULL = context-wide).  Same error contract as the fence. */
int32_t ptc_comm_quiesce(ptc_context_t *ctx, ptc_taskpool_t *tp);
/* activation-broadcast topology: 0 star (direct per-rank sends, default),
 * 1 chain pipeline, 2 binomial tree (reference: runtime_comm_coll_bcast) */
void ptc_comm_set_topology(ptc_context_t *ctx, int32_t topo);
/* fence + stop the comm thread (idempotent) */
int32_t ptc_comm_fini(ptc_context_t *ctx);
int32_t ptc_comm_enabled(ptc_context_t *ctx);
/* out4 = {msgs_sent, msgs_recv, bytes_sent, bytes_recv} */
void ptc_comm_stats(ptc_context_t *ctx, int64_t *out4);
/* rendezvous: [gets_sent, gets_served, registered_bytes, pending_pulls] */
void ptc_comm_rdv_stats(ptc_context_t *ctx, int64_t *out4);
/* transfer tuning: [eager_limit, chunk_size, inflight, rtt_ns,
 * memcpy_bps, chunks_sent, chunks_recv, eager_adaptive] */
void ptc_comm_tuning(ptc_context_t *ctx, int64_t *out8);
/* streaming pipeline: [sessions, parked_gets, overlap_ns, d2h_ns,
 * wire_ns, reaps, rails, stream_enabled] */
void ptc_comm_stream_stats(ptc_context_t *ctx, int64_t *out8);
/* ptc-topo per-peer counters: 6 int64 per peer [bytes_sent, bytes_recv,
 * msgs_sent, msgs_recv, parked_gets, rtt_ns]; returns peers written */
int32_t ptc_comm_peer_stats(ptc_context_t *ctx, int64_t *out,
                            int32_t max_peers);
/* PING every peer and wait (<= 2 s) for per-peer min RTTs — the
 * link-class auto-detect input; returns peers with a measured RTT */
int32_t ptc_comm_probe_rtts(ptc_context_t *ctx);
/* distributed clock sync (tracing v2): each rank estimates its
 * ptc_now_ns offset to RANK 0's clock from PING/PONG midpoints over the
 * existing wire (probed at comm bring-up and refreshed at each fence;
 * the minimum-RTT sample wins).  out4 = [offset_ns (rank0 - local),
 * err_ns (RTT of the winning sample — the uncertainty bound),
 * samples used, measured flag].  Rank 0 reports offset 0/measured 1. */
void ptc_comm_clock_stats(ptc_context_t *ctx, int64_t *out4);
/* re-probe now (blocks up to ~2s for at least one fresh sample);
 * returns samples accumulated so far */
int64_t ptc_comm_clock_sync(ptc_context_t *ctx);

/* inventory-blob replication (ptc-blackbox): share_blob pushes opaque
 * bytes to every live peer as a control frame (never dirties a fence);
 * each receiver keeps the LATEST blob per peer, so survivors still
 * hold a SIGKILLed rank's last checkpoint.  peer_blob copies the blob
 * from `rank` into out (returns the FULL length; 0 = none yet; -1 =
 * no comm / bad rank).  peers_lost exports the per-peer loss flags
 * (1 = connection died outside shutdown); returns entries written. */
int32_t ptc_comm_share_blob(ptc_context_t *ctx, const void *buf,
                            int64_t len);
int64_t ptc_comm_peer_blob(ptc_context_t *ctx, int32_t rank, void *out,
                           int64_t cap);
int32_t ptc_comm_peers_lost(ptc_context_t *ctx, int64_t *out, int32_t cap);

/* distributed taskpool id (SPMD creation order; assigned at add_taskpool) */
int32_t ptc_tp_id(ptc_taskpool_t *tp);

/* DTD distributed placement: a tile's owning rank (default 0) and an
 * explicit per-task rank override (default: first OUTPUT tile's owner) */
void ptc_dtile_set_owner(ptc_dtile_t *tile, uint32_t rank);
void ptc_dtask_set_rank(ptc_task_t *t, int32_t rank);

/* version / build info */
const char *ptc_version(void);

#ifdef __cplusplus
}
#endif
#endif /* PTC_CORE_H */
