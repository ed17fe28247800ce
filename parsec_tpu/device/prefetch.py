"""Device prefetch lane: ahead-of-schedule h2d staging.

The manager thread (tpu.py) drains ready device tasks into waves and
dispatches each wave as one (vmapped) executable call; every input tile
without a current device mirror costs a SYNCHRONOUS h2d at dispatch
time.  This lane removes that stall from the critical path: it walks the
runtime's ready-task lookahead through the native `ptc_peek_ready` span
API (tasks queued but not yet popped — ready, every input final) and
stages the NEXT wave's inputs while the current wave computes.  A wave
whose inputs were all prefetched dispatches with zero synchronous h2d.

Reference analog: the CUDA module's stage-in stream running ahead of the
exec stream (device_cuda_module.c:2197 ff); T3 (arXiv:2401.16677) makes
the case that this fine-grained transfer/compute overlap is where the
next integer factor lives once dispatch itself is fast.

Safety model:
  - `ptc_peek_ready` RETAINS every emitted copy under the queue lock, so
    host bytes stay valid even if the wave is popped, executed and its
    copies released mid-stage; the lane unpins every copy, exceptions
    included.
  - Tiles are staged as RAW flat-uint8 mirrors (`_cache_put_prefetch`),
    reinterpreted device-side at first stage-in — dtype/shape knowledge
    stays with the consumer, the lane needs none of it.
  - Prefetch inserts NEVER displace an existing cache entry (a dirty
    entry is newer truth; a clean one may be mid-read by the in-flight
    wave): the put is skip-if-present, which is what makes the staging
    slots collision-free without copying the double-buffer literally.
  - Budget is RESERVED before staging (`_prefetch_reserve`): the lane
    can evict clean non-lookahead tiles to make room but never dirty
    ones; a failed reservation skips the tile and the wave degrades to
    on-demand (out-of-core) staging instead of thrashing.

Staging slots: the lane stages at most `slots` waves (of batch_max
tasks each) beyond the one executing.  A slot is a set of staged uids;
it recycles when every uid has been consumed (pf flag cleared by the
first stage-in) or has left the cache.  Two slots (the default) give
classic double buffering: one wave in flight, one staged, one being
staged.
"""
from __future__ import annotations

import ctypes as C
import threading
import time

import numpy as np

from .. import _native as N
from ..profiling.trace import KEY_H2D

# span record layout (native ptc_peek_ready): per task
#   [task_ref, n_copies, (copy_ptr, data_ptr, size, version) * n_copies]
_REC_WORDS = 4
_HDR_WORDS = 2


class _PrefetchLane:
    def __init__(self, dev, depth: int = 64, slots: int = 2):
        self.dev = dev
        self.depth = max(1, depth)
        self.slots_max = max(1, slots)
        words = self.depth * (_HDR_WORDS + _REC_WORDS * N.MAX_FLOWS)
        self._buf = (C.c_int64 * words)()
        self._slots: list = []  # each: set of staged uids
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="ptc-tpu-prefetch")
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    # ------------------------------------------------------------ loop
    def _loop(self):
        """Sweep whenever there is lookahead to stage.  Idle waits ride
        the device's wake event instead of a fixed poll interval: a
        remote delivery (dp_deliver) sets it, so staging of tile k
        starts the moment its bytes land — while tile k+1 is still on
        the wire — instead of up to a poll period later (the sweep-poll
        latency the event-driven wakeup removes for remote tiles)."""
        dev = self.dev
        ctx = dev.ctx

        def wait(timeout: float) -> None:
            if dev._pf_wake.wait(timeout):
                dev._stats_add("prefetch_wakeups", 1)
            dev._pf_wake.clear()

        while not self._stop.is_set():
            try:
                if N.lib.ptc_device_queue_depth(ctx._ptr, dev.qid) <= 0:
                    if dev._pf_pin:
                        with dev._lock:
                            dev._pf_pin = set()
                    wait(0.001)
                    continue
                if not self._sweep():
                    wait(0.0005)
            except Exception:
                import traceback
                traceback.print_exc()
                time.sleep(0.01)

    def _free_slots(self) -> int:
        """Recycle slots whose every tile was consumed or dropped."""
        dev = self.dev
        with dev._lock:
            self._slots = [s for s in self._slots
                           if any((e := dev._cache.get(u)) is not None
                                  and e.pf for u in s)]
        return self.slots_max - len(self._slots)

    def _sweep(self) -> bool:
        """One lookahead pass: peek, stage what fits the free slots,
        update the lookahead pin set.  Returns True if anything was
        staged (the loop re-sweeps immediately)."""
        dev = self.dev
        ctx = dev.ctx
        free = self._free_slots()
        if free <= 0:
            return False
        words = N.lib.ptc_peek_ready(ctx._ptr, dev.qid, self._buf,
                                     len(self._buf), self.depth)
        if words <= 0:
            return False
        buf = self._buf
        # parse the span: tasks -> [(task_ref, [(cptr, dptr, size, ver)])]
        tasks, w = [], 0
        pins = []  # every emitted copy_ptr: MUST unpin exactly once
        while w + _HDR_WORDS <= words:
            tref, nc = buf[w], buf[w + 1]
            w += _HDR_WORDS
            recs = []
            for _ in range(nc):
                cptr, dptr, size, ver = (buf[w], buf[w + 1], buf[w + 2],
                                         buf[w + 3])
                w += _REC_WORDS
                recs.append((cptr, dptr, size, ver))
                pins.append(cptr)
            tasks.append((tref, recs))
        staged_any = False
        try:
            # lookahead pin set: everything the ready window will read.
            # Published BEFORE staging so eviction/spill decisions made
            # during this sweep already prefer non-lookahead tiles.
            pin = set()
            uid_of = {}
            for _, recs in tasks:
                for cptr, _, _, _ in recs:
                    uid = uid_of.get(cptr)
                    if uid is None:
                        uid_of[cptr] = uid = dev._copy_uid(cptr)
                    pin.add(uid)
            with dev._lock:
                dev._pf_pin = pin
            # stage up to `free` waves' worth of tasks (batch_max each)
            budget_tasks = free * max(1, dev.batch_max)
            slot_uids = set()
            inflight = set().union(*self._slots) if self._slots else set()
            for tref, recs in tasks[:budget_tasks]:
                if self._stop.is_set():
                    break
                for cptr, dptr, size, ver in recs:
                    uid = uid_of[cptr]
                    if uid in slot_uids or uid in inflight:
                        continue
                    # skip tiles with a CURRENT mirror anywhere in the
                    # context (affinity map check covers siblings): when
                    # a device holds the newest version, the host bytes
                    # may be stale — staging them would resurrect old
                    # data.  The mirror itself will serve the stage-in.
                    q, v = ctx.device_get_data_owner(uid)
                    if q >= 0 and v == ver:
                        continue
                    if not dev._prefetch_reserve(size):
                        continue  # over budget: on-demand staging wins
                    try:
                        raw = np.frombuffer(
                            (C.c_uint8 * size).from_address(dptr),
                            dtype=np.uint8, count=size).copy()
                        t0 = time.perf_counter_ns()
                        N.lib.ptc_prof_event(ctx._ptr, KEY_H2D, 0, -1,
                                             size, dev.qid, 1)
                        darr = dev._jax.device_put(raw, dev.device)
                        N.lib.ptc_prof_event(ctx._ptr, KEY_H2D, 1, -1,
                                             size, dev.qid, 1)
                        dev._stats_add("prefetch_h2d_ns",
                                       time.perf_counter_ns() - t0)
                    except Exception:
                        dev._prefetch_unreserve(size)
                        raise
                    if dev._cache_put_prefetch(uid, ver, darr, size):
                        dev._stats_add("h2d_bytes", size)
                        slot_uids.add(uid)
                        staged_any = True
            if slot_uids:
                self._slots.append(slot_uids)
        finally:
            for cptr in pins:
                N.lib.ptc_copy_unpin(ctx._ptr, cptr)
        return staged_any
