"""ptc-fuse: wave mega-kernelization — certified waves compile into one
cached XLA executable.

Dispatch p50 is a quarter microsecond (BENCH_dispatch) but every device
task group still pays its own XLA launch, and the rung-5 captures showed
launch overhead — not FLOPs — is the wall on real chips.  MPK
(arXiv:2512.22219) compiles whole task groups into one mega-kernel; this
module is the runtime half of that move, built on two in-tree artifacts:

  plan.certify()         per-(rank, wave) fusability certificates —
                         homogeneous class, table-driven/pure bodies,
                         no intra-wave conflict, one tile signature
  plan.certify_chains()  chain certificates — adjacent certified waves
                         where the producer wave feeds the consumer
                         wave rank-locally with matching tile
                         signatures, every consumer input either
                         in-program (from the producer wave) or a
                         statically-known collection tile

Two fusion levels:

  wave   a popped same-class group that passes the ONLINE certificate
         checks (the live re-validation of what the static certificate
         proves: homogeneity and one tile signature hold by
         construction of the per-class _DeviceBody, purity holds
         because the kernel IS the table, and independence — no member
         writing a copy another member touches — is checked against
         the live task copies) dispatches as ONE vmapped executable.
         That executable is the existing batched-dispatch program
         (`_get_fused` riding the fused-gather machinery), so this
         level is *observational*: it counts, and marks the DEVICE
         span's begin aux, without changing a single numeric.

  chain  when the static chain certificates link the popped wave to its
         consumer wave(s), the consumers' kernels run INSIDE the same
         jitted program — the producer wave's output stacks feed them
         without ever round-tripping the mirror cache — and the
         results are PARKED.  When the runtime later releases and pops
         the consumer tasks, they complete from the parked results with
         ZERO device launches, after a per-flow (uid, version) check of
         every real input copy against what the speculation consumed —
         the same discipline as the speculative epilogue (_try_spec),
         widened from one lane to whole waves.  Any mismatch (a tile
         written in between, an upstream miss, an unresolved pending
         link) discards the parked result and falls back to a normal
         dispatch: stale certificates cost a wasted speculation, never
         a wrong answer.

Executable cache: chain programs cache per (kernel chain, marshaling
structure); wave widths are padded to powers of two before they reach
XLA (the `_bucket` discipline), so compiles stay O(log W) per class.
Every refusal is COUNTED by reason (`fuse_refused`) — mirroring
certify()'s refuse records, never a silent fallback — and
`PTC_MCA_device_wave_fuse=0` removes this module from the dispatch path
entirely, reproducing the per-group batched dispatch bit-exactly.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .. import _native as N

# process-wide chain-executable cache: (kernel0, sig0, level structure)
# -> jitted callable.  Shapes respecialize inside jax.jit; the
# power-of-two width padding bounds those to O(log W) per class.
_CHAIN_CACHE: Dict[tuple, object] = {}

# hard bound on parked speculative results (each pins a stack row of
# HBM through its _StackRef): beyond it the oldest record drops and its
# task falls back to a normal dispatch (counted, never silent)
_PARKED_MAX = 8192


def _get_chained(jax_mod, kernel0, sig0: tuple, levels_struct: tuple):
    """One jitted program running the producer wave's (vmapped) kernel
    followed by each chained level's kernel, with level-l inputs
    gathered from level-(l-1)'s in-program outputs ("chain" specs) or
    marshaled like any wave flow ("idx"/"bcast").  Returns
    (callable, compiled_now)."""
    key = (kernel0, sig0, levels_struct)
    f = _CHAIN_CACHE.get(key)
    if f is not None:
        return f, False
    from .tpu import _sig_assemble, _sig_core
    jnp = jax_mod.numpy
    core0 = _sig_core(jax_mod, kernel0, sig0, False)

    def chained(*args):
        ins, ai = _sig_assemble(jnp, sig0, args)
        out = core0(*ins)
        prev = out if isinstance(out, tuple) else (out,)
        outs_all = list(prev)
        for kern, specs in levels_struct:
            lins, axes = [], []
            for spec in specs:
                k = spec[0]
                if k == "chain":
                    # producer-wave output row(s): the gather rides
                    # inside the program — the tile never leaves HBM
                    lins.append(jnp.take(prev[spec[1]], args[ai],
                                         axis=0))
                    ai += 1
                    axes.append(0)
                elif k == "idx":
                    lins.append(jnp.take(args[ai], args[ai + 1],
                                         axis=0))
                    ai += 2
                    axes.append(0)
                else:  # bcast
                    lins.append(args[ai])
                    ai += 1
                    axes.append(None)
            out = jax_mod.vmap(kern, in_axes=tuple(axes))(*lins)
            prev = out if isinstance(out, tuple) else (out,)
            outs_all.extend(prev)
        return tuple(outs_all)

    f = jax_mod.jit(chained)
    _CHAIN_CACHE[key] = f
    return f, True


class WaveFuser:
    """Per-device wave compiler.  All mutation happens on the device
    manager thread (the only dispatcher); counters are merged under the
    device lock so info()/device_stats() readers on other threads see
    consistent values."""

    def __init__(self, dev):
        self.dev = dev
        from ..utils import params as _mca
        self.depth = max(1, int(_mca.get("device.wave_fuse_depth")))
        # id(tp) -> {"failed": str|False, "links", "classes", "slots",
        #            "by_name"} — the consumed chain certificates
        self._tp_state: Dict[int, dict] = {}
        # (tp_id, class_id, params) -> parked speculative result
        self._parked: Dict[tuple, dict] = {}
        self._parked_classes: Dict[tuple, int] = {}
        # (tp_id, cls_name, params, flow) -> [(rec_key, flow_name)]:
        # chain checks waiting for an upstream consumption to learn its
        # concrete (uid, version); unresolved pendings read as a miss
        self._pending: Dict[tuple, list] = {}
        self._seen_exec: set = set()  # (structure key, widths) compiled
        self.stats = {"fused_waves": 0, "fused_tasks": 0,
                      "fused_chains": 0, "chain_waves": 0,
                      "chain_parked": 0, "chain_hits": 0,
                      "chain_misses": 0, "chain_drops": 0,
                      "cache_hits": 0, "cache_misses": 0}
        self.refused: Dict[str, int] = {}

    # ------------------------------------------------------------ stats
    def _bump(self, key: str, n: int = 1) -> None:
        with self.dev._lock:
            self.stats[key] += n

    def _refuse(self, reason: str, n: int = 1) -> None:
        """Count an explicit refusal by reason — the runtime mirror of
        certify()'s refuse records; there is no silent fallback."""
        with self.dev._lock:
            self.refused[reason] = self.refused.get(reason, 0) + n

    def snapshot(self) -> dict:
        with self.dev._lock:
            out = dict(self.stats)
            out["refused"] = dict(self.refused)
        out["enabled"] = True
        out["parked"] = len(self._parked)
        return out

    def clear(self) -> None:
        """Drop parked results and certificate state (device stop)."""
        self._parked.clear()
        self._parked_classes.clear()
        self._pending.clear()
        self._tp_state.clear()
        with self.dev._lock:
            self.dev._chain_pinned = 0

    # ----------------------------------------------------- certificates
    def _state_for(self, body) -> Optional[dict]:
        """Consume the static chain certificates for a taskpool, once.
        Extraction failures refuse with a reason and never retry (a
        pool that cannot certify cannot start certifying mid-run)."""
        tp = body.tp
        if tp is None or body.tc is None:
            return None
        key = id(tp)
        st = self._tp_state.get(key)
        if st is None:
            st = {"failed": False, "links": {}, "classes": {},
                  "slots": {}, "by_name": {}}
            try:
                from ..analysis.plan import chain_certificates
                plan = chain_certificates(tp)
                if plan is None:
                    st["failed"] = "enumeration-refused"
                else:
                    idx = plan.chain_index(
                        getattr(self.dev.ctx, "myrank", 0))
                    st["links"] = idx["links"]
                    st["classes"] = idx["classes"]
                    for nm, rec in idx["classes"].items():
                        st["slots"][rec["id"]] = rec["param_slots"]
                        st["by_name"][nm] = rec["id"]
            except Exception as e:  # analysis must never kill dispatch
                st["failed"] = f"certificate-error: {type(e).__name__}"
            self._tp_state[key] = st
        return st

    @staticmethod
    def _params(view, slots) -> tuple:
        return tuple(int(N.lib.ptc_task_local(view._ptr, s))
                     for s in slots)

    # ------------------------------------------------- parked consumption
    def consume_group(self, body, tasks: List) -> List:
        """Complete every task with a matching parked chain result;
        return the remainder for a real dispatch."""
        if not self._parked or body.tc is None:
            return tasks
        if (id(body.tp), body.tc.id) not in self._parked_classes:
            return tasks
        return [t for t in tasks if not self.consume(body, t)]

    def consume(self, body, task) -> bool:
        """Parked-result fast path (the chain analog of _try_spec,
        widened to every read flow): complete the task with ZERO device
        launches when every input copy matches the (uid, version) the
        speculation consumed.  Returns True when the task was DISPOSED
        (completed or failed)."""
        if not self._parked or body.tc is None:
            return False
        tp_id = id(body.tp)
        cid = body.tc.id
        if (tp_id, cid) not in self._parked_classes:
            return False
        st = self._tp_state.get(tp_id)
        slots = st["slots"].get(cid) if st else None
        if slots is None:
            return False
        dev = self.dev
        view = body.make_view(task)
        params = self._params(view, slots)
        key = (tp_id, cid, params)
        rec = self._parked.pop(key, None)
        if rec is None:
            return False
        self._unpark_class((tp_id, cid))
        with dev._lock:
            dev._chain_pinned = max(
                0, dev._chain_pinned - rec.get("pin", 0))
        ok = not rec["pending"]
        if ok:
            for fname, chk in rec["checks"].items():
                fi = body.flow_index(fname)
                cptr = N.lib.ptc_task_copy(view._ptr, fi)
                if N.lib.ptc_copy_handle(cptr) != chk[0] \
                        or N.lib.ptc_copy_version(cptr) != chk[1]:
                    ok = False
                    break
        if not ok:
            # stale speculation (an input changed underneath, or an
            # upstream lane itself missed and never resolved this
            # record's pending check): discard, dispatch normally
            self._bump("chain_misses")
            return False
        try:
            wb_uids = []
            for f in body.writes:
                uid, nv = dev._write_out(view, body, f, rec["outs"][f])
                if f in body.mem_out_flows:
                    wb_uids.append(uid)
                # downstream parked records waiting on this lane's
                # output learn its concrete (uid, version) now
                self._resolve(tp_id, body.tc.name, params, f, uid, nv)
        except Exception:
            import traceback
            traceback.print_exc()
            dev.ctx.task_fail(task)
            return True
        self._bump("chain_hits")
        self._bump("fused_tasks")
        with dev._lock:
            dev.stats["tasks"] += 1
        if wb_uids and dev._wb_thread is not None:
            dev._wb_q.put(("sync", [task], wb_uids))
            return True
        dev.ctx.task_complete(task)
        return True

    def _unpark_class(self, ckey: tuple) -> None:
        n = self._parked_classes.get(ckey, 0) - 1
        if n <= 0:
            self._parked_classes.pop(ckey, None)
        else:
            self._parked_classes[ckey] = n

    def _resolve(self, tp_id, cls_name, params, flow, uid, ver) -> None:
        lst = self._pending.pop((tp_id, cls_name, params, flow), None)
        if not lst:
            return
        for rec_key, fname in lst:
            rec = self._parked.get(rec_key)
            if rec is not None and fname in rec["pending"]:
                del rec["pending"][fname]
                rec["checks"][fname] = (uid, ver)

    # -------------------------------------------------- wave dispatch
    def dispatch_group(self, body, tasks: List) -> bool:
        """Online-certify a popped same-class group.  Returns True when
        the group (plus its certified chain) was dispatched here; False
        hands the group back to the normal batched path — with the
        DEVICE span's fused mark set when the wave certified."""
        dev = self.dev
        if body.tc is None:
            self._refuse("dtd-body")
            return False
        if not body.batch:
            # ptc_coll_* chain tasks are latency-bound relay hops, never
            # wave-fusable; a dedicated reason keeps tp benches able to
            # tell the embedded collective's expected refusals apart
            # from genuinely unbatchable compute bodies
            if body.tc.name.startswith("ptc_coll_"):
                self._refuse("coll-chain")
            else:
                self._refuse("unbatchable-body")
            return False
        views = [body.make_view(t) for t in tasks]
        # Independence, against the LIVE copies: no member may write a
        # copy another member touches — the engine's intra-wave order
        # is arbitrary, so such a pair inside one executable would be
        # a race (the structural half of certify(); V010 flags it
        # statically, this is the dispatch-time proof).
        readers: Dict[int, set] = {}
        writers: Dict[int, set] = {}
        for i, v in enumerate(views):
            for f in body.reads:
                c = N.lib.ptc_task_copy(v._ptr, body.flow_index(f))
                if c:
                    readers.setdefault(c, set()).add(i)
            for f in body.writes:
                c = N.lib.ptc_task_copy(v._ptr, body.flow_index(f))
                if c:
                    writers.setdefault(c, set()).add(i)
        for c, ws in writers.items():
            if len(ws | readers.get(c, set())) > 1:
                self._refuse("intra-wave-conflict")
                return False
        # certified: one wave -> one launch.  The normal batched path
        # IS the wave executable; mark its span and count it.
        self._bump("fused_waves")
        self._bump("fused_tasks", len(tasks))
        dev._disp_fused = 1
        try:
            return self._try_chain(body, tasks, views)
        except Exception:
            import traceback
            traceback.print_exc()
            self._refuse("chain:error")
            return False

    # -------------------------------------------------- chain dispatch
    def _try_chain(self, body, tasks: List, views: List) -> bool:
        dev = self.dev
        if self.depth < 2:
            return False
        epi = body.epilogue
        if epi is not None and any(epi.pick(v) is not None
                                   for v in views):
            # the speculative-epilogue lane is about to fire inside the
            # normal path; chaining on top would double-speculate
            self._refuse("chain:epilogue-active")
            return False
        st = self._state_for(body)
        if st is None:
            self._refuse("chain:no-certificate")
            return False
        if st["failed"]:
            self._refuse(f"chain:{st['failed']}")
            return False
        links = st["links"]
        slots = st["slots"].get(body.tc.id)
        if not links or slots is None:
            self._refuse("chain:no-link")
            return False
        lane_params = [self._params(v, slots) for v in views]
        levels = self._plan_levels(st, body, lane_params, len(tasks))
        if not levels:
            return False  # reason already counted
        return self._chain_exec(st, body, tasks, views, lane_params,
                                levels)

    def _plan_levels(self, st, body, lane_params, width0) -> List[dict]:
        """Walk the chain certificates forward from the popped lanes:
        one entry per fused consumer wave, bounded by the depth knob
        and the batched-dispatch byte cap."""
        dev = self.dev
        links = st["links"]
        tp_id = id(body.tp)

        def per_lane_bytes(b) -> int:
            total = 0
            for f in list(b.reads) + list(b.writes):
                shp = b.shapes.get(f)
                if shp:
                    total += int(np.prod(shp)) * np.dtype(
                        b.dtypes.get(f, np.float32)).itemsize
            return total

        # chain stacks live in HBM outside the LRU until consumed:
        # bound them by BOTH the batched-dispatch byte cap and the
        # device's free residency (budget - used - reservations).
        # Under pressure the chain refuses and the wave dispatches
        # normally — out-of-core spilling keeps its PR 12 semantics.
        with dev._lock:
            free = (dev._cache_bytes - dev._cache_used
                    - dev._pf_reserved - dev._chain_pinned)
        byte_budget = min(
            dev.batch_max_bytes - per_lane_bytes(body) * width0,
            free - per_lane_bytes(body) * width0)
        pressured = False
        levels: List[dict] = []
        prev_cls = body.tc.name
        prev_lanes = set(lane_params)
        prev_writes = list(body.writes)
        while 1 + len(levels) < self.depth:
            cons: Dict[tuple, dict] = {}
            for params in prev_lanes:
                for e in links.get((prev_cls, params), ()):
                    cons.setdefault(e["params"], e)
            if not cons:
                if not levels:
                    self._refuse("chain:no-link")
                break
            cnames = {e["cls"] for e in cons.values()}
            if len(cnames) != 1:
                self._refuse("chain:mixed-consumers")
                break
            cname = next(iter(cnames))
            cid = st["by_name"].get(cname)
            cbody = dev.bodies.get((tp_id, cid))
            if cbody is None or not cbody.batch:
                self._refuse("chain:consumer-not-attached")
                break
            if cbody.spec_src is not None or cbody.epilogue is not None:
                self._refuse("chain:epilogue-active")
                break
            # feasibility per consumer: every "wave" spec must point at
            # a lane this segment actually holds, with a flow the
            # producer body writes; "mem" specs need a collection that
            # can serve tiles at speculation time
            entries = []
            for params in sorted(cons):
                e = cons[params]
                ok = True
                for _fname, spec in e["ins"]:
                    if spec[0] == "wave":
                        if spec[1] not in prev_lanes \
                                or spec[2] not in prev_writes:
                            ok = False
                            break
                    elif spec[0] == "mem":
                        coll = dev.ctx.collection_objs.get(spec[1])
                        if coll is None or not hasattr(coll, "data_of"):
                            ok = False
                            break
                    else:
                        ok = False
                        break
                if ok:
                    entries.append(e)
            if not entries:
                self._refuse("chain:unresolvable-inputs")
                break
            byte_budget -= per_lane_bytes(cbody) * len(entries)
            if byte_budget < 0:
                pressured = True
                break  # byte cap / free-residency bound reached
            levels.append({"cls": cname, "cid": cid, "body": cbody,
                           "entries": entries})
            prev_cls = cname
            prev_lanes = {e["params"] for e in entries}
            prev_writes = list(cbody.writes)
        if not levels and pressured:
            self._refuse("chain:residency-pressure")
        return levels

    def _fetch_datum(self, cbody, fname: str, coll_name: str,
                     idx: tuple):
        """Device entry for an external collection tile a chained
        consumer reads: current mirror (here or a sibling, D2D), else a
        fresh h2d from the host tile.  Returns (entry, uid, version) —
        the (uid, version) is what consumption re-validates."""
        dev = self.dev
        coll = dev.ctx.collection_objs[coll_name]
        d = coll.data_of(*idx)
        cptr = N.lib.ptc_data_host_copy(d._ptr)
        uid = dev._copy_uid(cptr)
        ver = N.lib.ptc_copy_version(cptr)
        ent = dev._cache_ent(uid, ver)
        if ent is not None and not ent.raw:
            return ent.arr, uid, ver  # may be a _StackRef: gather-fusable
        dtype = cbody.dtypes[fname]
        shape = cbody.shapes.get(fname)
        arr = dev._cache_get_typed(uid, ver, dtype, shape)
        if arr is not None:
            return arr, uid, ver
        for sib in list(dev.ctx._devices):
            if sib is dev:
                continue
            sarr = sib._cache_get_typed(uid, ver, dtype, shape)
            if sarr is not None:
                darr = dev._jax.device_put(sarr, dev.device)
                dev._cache_put(uid, ver, darr, int(sarr.nbytes))
                dev._stats_add("d2d_bytes", int(sarr.nbytes))
                return darr, uid, ver
        host = np.array(coll.tile(*idx), copy=True)
        if shape is not None:
            host = host.reshape(shape)
        darr = dev._jax.device_put(host, dev.device)
        dev._cache_put(uid, ver, darr, int(host.nbytes))
        dev._stats_add("h2d_bytes", int(host.nbytes))
        return darr, uid, ver

    def _chain_exec(self, st, body, tasks, views, lane_params,
                    levels) -> bool:
        """Compile-and-run the chained program, write out the popped
        wave, park the speculated consumer waves.

        Ordering discipline: the chained-level marshaling (which can
        still refuse) runs BEFORE the DEVICE span opens and before any
        effect, so a refusal or marshaling error falls back to the
        normal batched dispatch with nothing written; once the
        executable has run, the effects below are the proven group-path
        code — an error there fails the tasks loudly (re-dispatching
        already-written lanes would double-write)."""
        dev = self.dev
        from .tpu import _StackRef, _bucket
        tp_id = id(body.tp)
        try:
            bucket0 = _bucket(len(tasks))
            extra_args: List[object] = []
            levels_struct: List[tuple] = []
            mem_checks: Dict[tuple, tuple] = {}
            prev_lane_of = {p: i for i, p in enumerate(lane_params)}
            prev_writes = list(body.writes)
            widths = [bucket0]
            for li, lvl in enumerate(levels):
                cbody = lvl["body"]
                entries = lvl["entries"]
                bucket_l = _bucket(len(entries))
                widths.append(bucket_l)
                ins_of = [dict(e["ins"]) for e in entries]
                specs: List[tuple] = []
                for fname in cbody.reads:
                    fspecs = [ins.get(fname) for ins in ins_of]
                    kinds = {s[0] if s else None for s in fspecs}
                    if kinds == {"wave"}:
                        pflows = {s[2] for s in fspecs}
                        if len(pflows) != 1:
                            self._refuse("chain:unresolvable-inputs")
                            return False
                        w_idx = prev_writes.index(next(iter(pflows)))
                        lanes = [prev_lane_of[s[1]] for s in fspecs]
                        lanes += [lanes[0]] * (bucket_l - len(lanes))
                        specs.append(("chain", w_idx))
                        extra_args.append(
                            np.asarray(lanes, dtype=np.int32))
                    elif kinds == {"mem"}:
                        ents = []
                        for j, s in enumerate(fspecs):
                            ent, uid, ver = self._fetch_datum(
                                cbody, fname, s[1], s[2])
                            ents.append(ent)
                            mem_checks[(li, j, fname)] = (uid, ver)
                        first = ents[0]
                        if all(e is first for e in ents):
                            if isinstance(first, _StackRef):
                                specs.append(("idx",))
                                extra_args += [
                                    first.stack,
                                    np.full((bucket_l,), first.idx,
                                            np.int32)]
                            else:
                                specs.append(("bcast",))
                                extra_args.append(first)
                        else:
                            stack, idxs = dev._fold(ents)
                            idxs += [idxs[0]] * (bucket_l - len(idxs))
                            specs.append(("idx",))
                            extra_args += [
                                stack, np.asarray(idxs, dtype=np.int32)]
                    else:
                        self._refuse("chain:unresolvable-inputs")
                        return False
                levels_struct.append((cbody.kernel, tuple(specs)))
                prev_lane_of = {e["params"]: i
                                for i, e in enumerate(entries)}
                prev_writes = list(cbody.writes)
        except Exception:
            import traceback
            traceback.print_exc()
            self._refuse("chain:error")
            return False

        dev._disp_fused = 1 + len(levels)
        dev._prof(0, body, len(tasks))
        try:
            try:
                sig0, call_args = dev._wave_sig_args(body, views,
                                                     bucket0)
                exe, compiled = _get_chained(dev._jax, body.kernel,
                                             tuple(sig0),
                                             tuple(levels_struct))
                wkey = (body.kernel, tuple(sig0),
                        tuple(levels_struct), tuple(widths))
                if compiled or wkey not in self._seen_exec:
                    self._seen_exec.add(wkey)
                    self._bump("cache_misses")
                else:
                    self._bump("cache_hits")
                out_all = exe(*call_args, *extra_args)
            except Exception:
                # nothing written yet (XLA enqueue failed): fall back
                # to the normal batched dispatch of the popped wave
                import traceback
                traceback.print_exc()
                self._refuse("chain:error")
                return False

            try:
                # ---- level-0 effects: the batched group path's code
                wb_stacks = []
                out_uid: Dict[tuple, tuple] = {}
                oi = 0
                outs0 = out_all[oi:oi + len(body.writes)]
                oi += len(body.writes)
                for f, ostack in zip(body.writes, outs0):
                    sync_host = f in body.mem_out_flows
                    uids = []
                    for i, view in enumerate(views):
                        uid, nv = dev._write_out(view, body, f,
                                                 _StackRef(ostack, i))
                        out_uid[(lane_params[i], f)] = (uid, nv)
                        if sync_host:
                            uids.append(uid)
                    if sync_host:
                        wb_stacks.append((ostack, uids))
                with dev._lock:
                    dev.stats["tasks"] += len(tasks)
                    dev.stats["batches"] += 1
                    dev.stats["batched_tasks"] += len(tasks)

                # ---- park the speculated consumer waves
                parked = 0
                prev_cls = body.tc.name
                for li, lvl in enumerate(levels):
                    cbody = lvl["body"]
                    entries = lvl["entries"]
                    ostacks = out_all[oi:oi + len(cbody.writes)]
                    oi += len(cbody.writes)
                    ckey = (tp_id, lvl["cid"])
                    # residency accounting of the parked stacks (one
                    # level's output stacks, split across its records;
                    # released as each record is consumed or dropped)
                    lvl_bytes = 0
                    for f in cbody.writes:
                        shp = cbody.shapes.get(f)
                        if shp:
                            lvl_bytes += _bucket(len(entries)) \
                                * int(np.prod(shp)) * np.dtype(
                                    cbody.dtypes.get(
                                        f, np.float32)).itemsize
                    share = lvl_bytes // max(1, len(entries))
                    with dev._lock:
                        dev._chain_pinned += share * len(entries)
                    for j, e in enumerate(entries):
                        rec_key = (tp_id, lvl["cid"], e["params"])
                        rec = {"outs": {f: _StackRef(ostacks[fi], j)
                                        for fi, f in
                                        enumerate(cbody.writes)},
                               "pin": share,
                               "checks": {}, "pending": {}}
                        for fname, spec in e["ins"]:
                            if spec[0] == "wave":
                                if li == 0:
                                    rec["checks"][fname] = \
                                        out_uid[(spec[1], spec[2])]
                                else:
                                    # resolved when the upstream lane
                                    # is consumed; unresolved reads as
                                    # a miss
                                    rec["pending"][fname] = True
                                    self._pending.setdefault(
                                        (tp_id, prev_cls, spec[1],
                                         spec[2]), []).append(
                                            (rec_key, fname))
                            else:
                                rec["checks"][fname] = \
                                    mem_checks[(li, j, fname)]
                        if rec_key in self._parked:
                            self._unpark_class(ckey)
                        self._parked[rec_key] = rec
                        self._parked_classes[ckey] = \
                            self._parked_classes.get(ckey, 0) + 1
                        parked += 1
                    prev_cls = lvl["cls"]
                while len(self._parked) > _PARKED_MAX:
                    old_key = next(iter(self._parked))
                    old = self._parked.pop(old_key)
                    self._unpark_class((old_key[0], old_key[1]))
                    with dev._lock:
                        dev._chain_pinned = max(
                            0, dev._chain_pinned
                            - old.get("pin", 0))
                    self._bump("chain_drops")
                self._bump("fused_chains")
                self._bump("chain_waves", len(levels))
                self._bump("chain_parked", parked)
                self._publish_hints(st, levels)
                # mem-out coherence + completions ride the writeback
                # lane, exactly like the batched group path
                if wb_stacks and dev._wb_thread is not None:
                    dev._wb_q.put(("stack", list(tasks), wb_stacks))
                else:
                    for t in tasks:
                        dev.ctx.task_complete(t)
            except Exception:
                # effects already started: failing the tasks is the
                # only sound exit (a retry would double-write)
                import traceback
                traceback.print_exc()
                for t in tasks:
                    dev.ctx.task_fail(t)
        finally:
            dev._prof(1, body, len(tasks))
        return True

    def _publish_hints(self, st, levels) -> None:
        """Predict the NEXT chain segment's external collection reads
        and hand them to the prefetch lane — the chain-granular
        lookahead: by the time the segment dispatches, its tiles are
        staged mirrors, not synchronous h2d stalls."""
        if not levels:
            return
        links = st["links"]
        last = levels[-1]
        hints: List[tuple] = []
        seen = set()
        for e in last["entries"]:
            for nxt in links.get((last["cls"], e["params"]), ()):
                for _fname, spec in nxt["ins"]:
                    if spec[0] == "mem" and spec[1:] not in seen:
                        seen.add(spec[1:])
                        hints.append((spec[1], spec[2]))
        if hints:
            self.dev._pf_chain_hints = hints
            self.dev._pf_wake.set()
