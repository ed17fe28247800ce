"""TPU device module: dispatches task bodies as cached XLA executables.

Reference analog: the CUDA device module (parsec/mca/device/cuda/
device_cuda_module.c — SURVEY.md §2.6/§3.4), re-designed for TPU/XLA:

  - the native core pushes device-chore tasks onto a device queue
    (PTC_BODY_DEVICE → ASYNC); a manager thread drains it — the analog of
    the CUDA manager-thread pattern (device_cuda_module.c:2563-2589)
  - task bodies are jax-traceable kernels; `jax.jit` gives the cached
    per-(kernel, shape, dtype) executable — the analog of the dyld'd
    cublas handle lookup (cuda_find_incarnation, :175)
  - **device-resident dataflow**: results of device tasks stay on the TPU
    (OWNED state); successors consume them straight from HBM.  The host
    copy is only materialized (a) synchronously when the flow writes back
    to collection memory (DEP_MEM output), (b) at `flush()`, or (c) never,
    if the copy dies first (the native copy-release hook drops dead
    mirrors).  This is the analog of the CUDA module's coherency
    OWNED→SHARED epilog (device_cuda_module.c:2365-2420) + LRU
    (parsec_gpu_data_reserve_device_space, :864).
  - XLA's async dispatch gives the execution pipelining the CUDA module
    builds manually from streams+events: the manager never blocks on
    results that only device-side consumers need.

Host coherence (round 2): CPU chores and comm sends pull a newer
device-resident copy automatically — TaskView.data() and the native
serialization/memcpy sites call back into sync_copy_handle(), which
writes the dirty mirror to the host buffer (the lazy, pull-based analog
of the CUDA epilog's OWNED→SHARED flip, device_cuda_module.c:2365-2420).
Manual flush() remains for bulk host reads (to_dense etc.).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import _native as N
from ..analysis.plan import PlanCheckError
from ..core.context import Context
from ..core.taskclass import Mem, TaskClass, TaskView
from ..core.taskpool import Taskpool


class _DeviceBody:
    def __init__(self, kernel: Callable, reads: Sequence,
                 writes: Sequence, shapes: Dict, dtypes: Dict,
                 tc: Optional[TaskClass], tp: Optional[Taskpool],
                 nb_flows: int = 0, batch: bool = False):
        self.kernel = kernel
        self.reads = list(reads)
        self.writes = list(writes)
        self.shapes = shapes
        self.dtypes = dtypes
        self.tc = tc
        self.tp = tp
        self.nb_flows = nb_flows
        self.epilogue = None  # _Epilogue on the SOURCE class
        self.spec_src = None  # _Epilogue on the DESTINATION class
        self.batch = batch  # kernel is elementwise over tiles: vmap-able
        # flows whose output deps include a memory writeback: their host
        # copy must be coherent at completion (release_deps may memcpy it)
        self.mem_out_flows = set()
        if tc is not None:
            for fl in tc.flows:
                if fl.name in self.writes:
                    for d in fl.deps:
                        if d.direction == 1 and isinstance(d.target, Mem):
                            self.mem_out_flows.add(fl.name)

    def flow_index(self, f) -> int:
        return f if isinstance(f, int) else self.tc.flow_index(f)

    def make_view(self, task_ptr):
        if self.tc is not None:
            return TaskView(task_ptr, self.tc, self.tp)
        from ..dsl.dtd import DtdView
        return DtdView(task_ptr, self.nb_flows)


# process-wide executable cache: kernel fn -> jax.jit wrapper.  Re-wrapping
# the same kernel in a new TpuDevice would re-trace and re-compile; keeping
# the wrapper global makes every (kernel, shape, dtype) compile exactly once
# per process (plus the on-disk jax compilation cache across processes).
_JIT_CACHE: Dict[object, Callable] = {}

# batched variants: kernel fn -> jit(vmap(kernel)).  One executable per
# (kernel, bucket size, tile shape/dtype); bucket padding (powers of two)
# keeps the number of compiles logarithmic in the max batch.
_VMAP_CACHE: Dict[object, Callable] = {}
_FUSED_CACHE: Dict[object, Callable] = {}

# live devices, for copy-handle coherence sync (handles are stamped only by
# devices, so a zero handle short-circuits before ever reaching this).
# Device-cache uids are allocated from ONE process-wide counter, so a uid
# identifies its device unambiguously even with several contexts/devices
# in one process (4-chip hosts, colocated-rank tests).
_ALL_DEVICES: List["TpuDevice"] = []
_UID_LOCK = threading.Lock()
_UID_STATE = {"next": 1}


def _next_uid() -> int:
    with _UID_LOCK:
        u = _UID_STATE["next"]
        _UID_STATE["next"] += 1
        return u


def sync_copy_handle(handle: int) -> None:
    """Write the dirty device mirror of `handle` (if any) back to its host
    buffer.  Called by CPU-chore data views and, via the native
    copy-sync callback, by comm serialization and collection memcpy."""
    for dev in list(_ALL_DEVICES):
        dev.sync_handle(handle)


def maybe_sync_copy(cptr) -> None:
    """Coherence entry point for host-side reads of a task flow: no-op for
    copies no device ever staged (zero handle), dirty-mirror writeback
    otherwise.  Shared by TaskView.data and DtdView.data."""
    from .. import _native as _N
    h = _N.lib.ptc_copy_handle(cptr)
    if h:
        sync_copy_handle(h)


# ---------------------------------------------------------------- data plane
# Device side of the comm engine's PK_DEVICE rendezvous (native seam:
# ptc_set_dataplane, reference: comm-engine put/get on registered memory,
# parsec_comm_engine.h:139-160).  A remote dep whose copy has a current
# device mirror is advertised as a transfer tag; at pull time the payload
# is served EITHER as bytes (d2h once, host transport carries them) OR —
# when the pulling rank is colocated (same process, devices of one
# accelerator client: a pod slice under a single controller, the 8-CPU
# test mesh) — as a 16-byte by-reference token, and the tile itself moves
# device-to-device over the fabric (jax.device_put == ICI DMA on TPU; see
# comm/ici.py).  Consumer-side host bytes then materialize lazily through
# the ordinary dirty-mirror coherence pull.

_DP_LOCK = threading.Lock()
_DP_STATE = {"next_tag": 1}
# tag -> [device array, refcount, key, raw, dev]; tags are shared per
# (copy_handle, version) across send batches so a fan-out pins ONE array;
# `raw` (flat-uint8 mirror) travels with by-ref handoffs so relayed
# payloads keep their reinterpret-at-stage-in semantics; `dev` is the
# owning TpuDevice (its writeback lane runs progressive-serve slicing)
_DP_REG: Dict[int, list] = {}
_DP_BY_KEY: Dict[tuple, int] = {}
# tag -> [pinned host-byte buffers], one entry per live serve: with the
# chunked rendezvous two pulls of one tag can be mid-serve at once, so a
# single slot would unpin the first buffer when the second serve lands
_DP_SERVING: Dict[int, list] = {}
# colocated by-reference handoff: tag -> device array (same process)
_DP_XFER: Dict[int, object] = {}
_DP_REF_MAGIC = b"PTCDPRF1"

# cross-PROCESS device transfer plane (jax.experimental.transfer): the
# producer serves a token naming a pull uuid + its transfer server's
# address; the consumer pulls the array device-to-device through the
# transfer service (TCP bulk transport between hosts, DCN/pinned paths
# on pods) — the payload bytes never exist on either HOST in this
# runtime's buffers.  Opt-in (PTC_MCA_device_dp_transfer=1); each rank
# probes its own pull path at device init (_xfer_can_pull) and
# advertises the verdict on GET frames, so producers serve tokens only
# to capable pullers — incapable ranks (PJRT plugins without async-h2d,
# or device.dp_pull=0) get real bytes.  Reference seam: transport-native
# payload movement end to end, parsec_comm_engine.h:139-160 (SURVEY §7 #2).
_DP_XFER_MAGIC = b"PTCDPXF1"
_XFER_LOCK = threading.Lock()
_XFER_STATE: Dict[str, object] = {"server": None, "failed": False,
                                  "sessions": None, "next_uuid": 1}


def _xfer_sessions():
    """Process-wide persistent per-peer transfer sessions (the pool in
    comm/ici.py): connections are established once per peer address and
    reused by every pull — the endpoint-setup cost is paid once, not
    per transfer."""
    with _XFER_LOCK:
        pool = _XFER_STATE["sessions"]
        if pool is None:
            from ..comm.ici import TransferSessionPool
            pool = _XFER_STATE["sessions"] = TransferSessionPool()
    return pool


def _xfer_enabled() -> bool:
    from ..utils import params as _mca
    try:
        return bool(_mca.get("device.dp_transfer"))
    except KeyError:
        return False


def _xfer_server(client):
    """Process-wide transfer server, lazily started for `client`; None
    when the backend does not support it (byte path takes over)."""
    with _XFER_LOCK:
        if _XFER_STATE["failed"]:
            return None
        if _XFER_STATE["server"] is None:
            try:
                import jax.experimental.transfer as jxt
                host = os.environ.get("PTC_DP_TRANSFER_HOST", "127.0.0.1")
                _XFER_STATE["server"] = jxt.start_transfer_server(
                    client, f"{host}:0", [f"{host}:0"])
            except Exception as e:
                import sys
                sys.stderr.write(f"ptc-dp: transfer server unavailable "
                                 f"({e!r}); device payloads fall back to "
                                 "host bytes\n")
                _XFER_STATE["failed"] = True
                return None
        return _XFER_STATE["server"]


def _xfer_can_pull(client, device) -> bool:
    """One-time consumer-side probe: can this process PULL through the
    transfer plane?  Serves a tiny array to itself and pulls it back —
    exercising the exact runtime path a remote token will need
    (start_transfer_server + connect + CreateBuffersForAsyncHostToDevice,
    which some PJRT plugins do not implement).  The verdict is advertised
    to producers on GET frames via ptc_set_dp_can_pull; a False keeps
    every payload on the always-safe byte path instead of aborting pools
    at delivery time."""
    from ..utils import params as _mca
    try:
        if not _mca.get("device.dp_pull"):
            return False  # ops override: this rank refuses pulls
    except KeyError:
        pass
    with _XFER_LOCK:
        cached = _XFER_STATE.get("can_pull")
    if cached is not None:
        return bool(cached)
    ok = False
    try:
        import jax
        from jax.sharding import SingleDeviceSharding
        srv = _xfer_server(client)
        if srv is not None:
            probe = jax.device_put(np.arange(4, dtype=np.float32), device)
            with _XFER_LOCK:
                uuid = _XFER_STATE["next_uuid"]
                _XFER_STATE["next_uuid"] += 1
            srv.await_pull(uuid, [probe])
            # session pool: tokens advertising this rank's own server
            # (loopback jobs) reuse the probe's connection forever
            conn = _xfer_sessions().get(srv, srv.address())
            sds = jax.ShapeDtypeStruct((4,), np.float32,
                                       sharding=SingleDeviceSharding(device))
            out = conn.pull(uuid, [sds])[0]
            ok = bool(np.array_equal(np.asarray(out), np.arange(4)))
    except Exception as e:
        import sys
        sys.stderr.write(f"ptc-dp: transfer-plane pull probe failed "
                         f"({e!r}); this rank will request host bytes\n")
        ok = False
    with _XFER_LOCK:
        _XFER_STATE["can_pull"] = ok
    return ok


def _xfer_token(arr, raw: bool):
    """Register `arr` for one pull and build the wire token, or None (the
    d2h byte path takes over on ANY transfer-plane problem here — once
    the token is on the wire there is no fallback, so failures must
    happen on this side).  Known limitation: a registered pull the
    consumer never completes (peer death between serve and pull) stays
    pinned in the transfer server for the process lifetime — the server
    API has no cancel; peer-loss reaping covers the comm-layer state
    only."""
    try:
        client = next(iter(arr.sharding.device_set)).client
        srv = _xfer_server(client)
        if srv is None:
            return None
        with _XFER_LOCK:
            uuid = _XFER_STATE["next_uuid"]
            _XFER_STATE["next_uuid"] += 1
        srv.await_pull(uuid, [arr])
        addr = srv.address().encode()
    except Exception as e:
        import sys
        sys.stderr.write(f"ptc-dp: transfer registration failed ({e!r}); "
                         "serving host bytes\n")
        return None
    dt = np.dtype(arr.dtype).str.encode()
    tok = (_DP_XFER_MAGIC + int(uuid).to_bytes(8, "little")
           + bytes([1 if raw else 0, len(dt), len(arr.shape)])
           + dt + b"".join(int(d).to_bytes(8, "little") for d in arr.shape)
           + len(addr).to_bytes(2, "little") + addr)
    return np.frombuffer(tok, dtype=np.uint8).copy()


def _xfer_pull(raw_tok: bytes, device):
    """Resolve a transfer token: pull the array onto `device`.  Returns
    (array, raw_flag) or raises."""
    import jax
    from jax.sharding import SingleDeviceSharding
    o = 8
    uuid = int.from_bytes(raw_tok[o:o + 8], "little"); o += 8
    rawf, dtlen, ndim = raw_tok[o], raw_tok[o + 1], raw_tok[o + 2]; o += 3
    dt = np.dtype(raw_tok[o:o + dtlen].decode()); o += dtlen
    shape = tuple(int.from_bytes(raw_tok[o + 8 * i:o + 8 * (i + 1)],
                                 "little") for i in range(ndim))
    o += 8 * ndim
    alen = int.from_bytes(raw_tok[o:o + 2], "little"); o += 2
    addr = raw_tok[o:o + alen].decode()
    srv = _xfer_server(device.client)
    if srv is None:
        raise RuntimeError("transfer plane unavailable on consumer")
    conn = _xfer_sessions().get(srv, addr)  # persistent per-peer session
    sds = jax.ShapeDtypeStruct(shape, dt,
                               sharding=SingleDeviceSharding(device))
    return conn.pull(uuid, [sds])[0], bool(rawf)


def _make_dp_callbacks(ctx):
    """Per-context data-plane callbacks (closing over ctx._devices and
    ctx._colocated — no cross-context scans)."""

    def dp_register(user, copy_handle, version, size) -> int:
        """A remote send asks: is there a current device mirror for this
        copy?  Returns a transfer tag (>0) or 0 for the host path.  The
        same (copy, version) advertised to several ranks/batches shares
        one tag (refcounted) — k-way fan-out pins one device array."""
        try:
            for dev in list(ctx._devices):
                with dev._lock:
                    ent = dev._cache.get(copy_handle)
                    if ent is not None and ent.version == version:
                        key = (copy_handle, version)
                        with _DP_LOCK:
                            tag = _DP_BY_KEY.get(key)
                            if tag is not None and tag in _DP_REG:
                                _DP_REG[tag][1] += 1
                            else:
                                tag = _DP_STATE["next_tag"]
                                _DP_STATE["next_tag"] += 1
                                _DP_REG[tag] = [_conc(ent), 1, key,
                                                ent.raw, dev]
                                _DP_BY_KEY[key] = tag
                        dev.stats["dp_sends"] += 1
                        return tag
            return 0
        except Exception:
            import traceback
            traceback.print_exc()
            return 0  # host path takes over

    def dp_serve(user, tag, from_rank, xfer_ok, ptr_out, real_out) -> int:
        """Produce one pull's wire bytes: the payload itself, or — for a
        colocated consumer — a by-reference token (the array is handed
        off in-process and the transfer rides the device fabric)."""
        try:
            with _DP_LOCK:
                rec = _DP_REG.get(tag)
            if rec is None:
                return -1
            arr = rec[0]
            if from_rank in ctx._colocated:
                # one handoff slot per PULL (not per tag): a fan-out to
                # several colocated consumers serves several tokens, each
                # resolving independently
                with _DP_LOCK:
                    pull_id = _DP_STATE["next_tag"]
                    _DP_STATE["next_tag"] += 1
                    _DP_XFER[pull_id] = (arr, rec[3])
                buf = np.frombuffer(
                    _DP_REF_MAGIC + int(pull_id).to_bytes(8, "little"),
                    dtype=np.uint8).copy()
            else:
                buf = None
                if _xfer_enabled() and xfer_ok:
                    # cross-process transfer plane: serve a token, the
                    # consumer pulls device-to-device — no d2h here.
                    # Gated on the PULLER's probed capability (GET frame
                    # bit): a token is unrecoverable if the pull fails
                    buf = _xfer_token(arr, bool(rec[3]))
                if buf is None:
                    buf = np.ascontiguousarray(np.asarray(arr))
            with _DP_LOCK:
                _DP_SERVING.setdefault(tag, []).append(buf)  # pin: serve_done
            ptr_out[0] = buf.ctypes.data
            real_out[0] = arr.nbytes
            return buf.nbytes
        except Exception:
            import traceback
            traceback.print_exc()
            return -1

    def dp_serve_stream(user, tag, from_rank, xfer_ok, stream_id,
                        total) -> int:
        """Progressive-serve offer (wire v4 streaming): accept by
        ENQUEUEING the sliced d2h onto the owning device's writeback
        lane (never block — this runs on the comm thread).  Decline
        whenever the synchronous dp_serve would produce a better
        answer: a colocated by-ref handoff or a transfer-plane token
        moves the tile over the device fabric, which no byte stream
        beats."""
        try:
            from ..utils import params as _mca
            if not _mca.get("device.stream_serve"):
                return 0
            if from_rank in ctx._colocated:
                return 0  # by-ref handoff wins
            if xfer_ok and _xfer_enabled():
                return 0  # device-fabric transfer token wins
            with _DP_LOCK:
                rec = _DP_REG.get(tag)
            if rec is None:
                return 0
            arr, dev = rec[0], rec[4]
            if dev is None or int(arr.nbytes) != int(total):
                return 0
            if dev._wb_thread is None or not dev._wb_thread.is_alive():
                return 0
            with _DP_LOCK:
                # placeholder pin: the engine calls dp_serve_done once
                # per serve, streaming or not — without a matching push
                # the retire would pop a CONCURRENT synchronous serve's
                # buffer pin early (use-after-free on the wire).  The
                # pin list only guarantees balanced counts, so a None
                # entry is enough.
                _DP_SERVING.setdefault(tag, []).append(None)
            dev._wb_q.put(("stream", [], (int(stream_id), int(tag))))
            return 1
        except Exception:
            import traceback
            traceback.print_exc()
            return 0

    def dp_serve_done(user, tag) -> None:
        with _DP_LOCK:
            pins = _DP_SERVING.get(tag)
            if pins:
                pins.pop()
                if not pins:
                    _DP_SERVING.pop(tag, None)
            rec = _DP_REG.get(tag)
            if rec is not None:
                rec[1] -= 1
                if rec[1] <= 0:
                    _DP_REG.pop(tag, None)
                    _DP_BY_KEY.pop(rec[2], None)

    def dp_deliver(user, ptr, size, tag) -> int:
        """Payload (or by-ref token) arrived for a device-plane dep:
        place it on this context's least-loaded device and return the
        cache uid stamped on the new host copy."""
        try:
            import ctypes as C
            devs = list(ctx._devices)
            if not devs or size <= 0:
                return 0
            # route to the least-loaded device (by native queue depth),
            # not devs[0]; sibling devices can still D2D-stage from it
            dev = min(devs, key=lambda d: ctx.device_queue_depth(d.qid))
            src = (C.c_uint8 * size).from_address(ptr)
            raw = bytes(src)
            if size == 16 and raw[:8] == _DP_REF_MAGIC:
                xtag = int.from_bytes(raw[8:], "little")
                with _DP_LOCK:
                    hand = _DP_XFER.pop(xtag, None)
                if hand is None:
                    return 0
                arr, was_raw = hand
                from ..comm.ici import device_transfer
                darr = device_transfer(arr, dev.device)
                uid = _next_uid()
                # rawness travels with the array: a relay's raw-bytes
                # mirror stays raw (consumers reinterpret at stage-in)
                dev._cache_put(uid, 0, darr, arr.nbytes, raw=was_raw)
                dev._stats_add("dp_d2d_bytes", arr.nbytes)
                dev._pf_wake.set()
                return uid
            if size > 21 and raw[:8] == _DP_XFER_MAGIC:
                # cross-process transfer token: pull device-to-device
                # through the transfer service; the payload never touches
                # this host's buffers
                darr, was_raw = _xfer_pull(raw, dev.device)
                uid = _next_uid()
                dev._cache_put(uid, 0, darr, darr.nbytes, raw=was_raw)
                dev._stats_add("dp_xfer_bytes", darr.nbytes)
                dev._pf_wake.set()
                return uid
            host = np.frombuffer(src, dtype=np.uint8, count=size).copy()
            darr = dev._jax.device_put(host, dev.device)
            uid = _next_uid()
            # version 0 matches the fresh wire-materialized ptc_copy;
            # raw=True: stage-in reinterprets to the consumer's dtype/shape
            dev._cache_put(uid, 0, darr, size, raw=True)
            dev._stats_add("dp_recv_bytes", size)
            # event-driven prefetch: a remote tile just landed — wake the
            # lane NOW instead of waiting out its poll interval, so h2d
            # staging of tile k starts while tile k+1 is on the wire
            dev._pf_wake.set()
            return uid
        except Exception:
            import traceback
            traceback.print_exc()
            return 0  # consumer falls back to staging the host bytes

    def dp_bound(user, uid, ptr, size, host_valid) -> None:
        """The consumer-side host copy now exists: bind it as the mirror's
        writeback target.  host_valid=0 (by-ref delivery: the host buffer
        was never written) marks the mirror dirty so any host read
        materializes it through the coherence pull."""
        try:
            import ctypes as C
            for dev in list(ctx._devices):
                with dev._lock:
                    ent = dev._cache.get(uid)
                    if ent is None:
                        continue
                    view = np.ctypeslib.as_array(
                        (C.c_uint8 * size).from_address(ptr))
                    ent.host = view
                    if not host_valid:
                        ent.dirty = True
                    ent.persistent = False  # wire copy, not user Data
                    return
        except Exception:
            import traceback
            traceback.print_exc()

    return (dp_register, dp_serve, dp_serve_done, dp_deliver, dp_bound,
            dp_serve_stream)


def _get_jitted(jax_mod, kernel: Callable) -> Callable:
    j = _JIT_CACHE.get(kernel)
    if j is None:
        j = jax_mod.jit(kernel)
        _JIT_CACHE[kernel] = j
    return j


def _get_vmapped(jax_mod, kernel: Callable) -> Callable:
    j = _VMAP_CACHE.get(kernel)
    if j is None:
        j = jax_mod.jit(jax_mod.vmap(kernel))
        _VMAP_CACHE[kernel] = j
    return j


def _sig_core(jax_mod, kernel: Callable, sig: tuple, single: bool):
    """The (possibly vmapped) kernel for a sig — one source of truth for
    the vmap axes shared by _get_fused and _get_fused_epi."""
    if single:
        return kernel
    axes = tuple(None if s in ("bcast", "bidx") else 0 for s in sig)
    return jax_mod.vmap(kernel, in_axes=axes)


def _sig_assemble(jnp, sig, args):
    """Marshal flat call args into kernel inputs per the sig — the
    idx/bidx gathers happen here, INSIDE the traced program.  Returns
    (inputs, args_consumed); shared by _get_fused and _get_fused_epi so
    the two can never marshal differently."""
    ins, ai = [], 0
    for s in sig:
        if s in ("idx", "bidx"):
            ins.append(jnp.take(args[ai], args[ai + 1], axis=0))
            ai += 2
        else:  # "bcast" / pre-stacked passthrough
            ins.append(args[ai])
            ai += 1
    return ins, ai


def _get_fused(jax_mod, kernel: Callable, sig: tuple, single: bool):
    """One jitted program fusing the per-flow gathers INTO the kernel
    call.  `sig[i]` says whether read flow i arrives as (stack, idx) —
    gathered inside the program — or as an already-shaped array.  A
    wave that used to cost one `take` per flow plus the exec collapses
    to ONE dispatch.  `single=True` wraps the unbatched kernel (scalar
    idx selects one row); False wraps vmap(kernel) over stacked rows.

    Per-flow sig entries (None = pre-stacked passthrough, vmap axis 0):
      "idx"   (stack, lane_idxs) — gathered inside, vmap axis 0
      "bcast" one shared array every lane consumes — vmap axis None,
              shipped ONCE instead of duplicated per lane by a gather
              (e.g. the panel inverse every TRSM lane reads)
      "bidx"  (stack, scalar_idx) — one shared row taken inside,
              vmap axis None

    A sig with nothing to fuse or broadcast reuses the plain
    jitted/vmapped program (same cache `warm()` pre-compiles into)."""
    if not any(sig):
        return (_get_jitted if single else _get_vmapped)(jax_mod, kernel)
    key = (kernel, sig, single)
    f = _FUSED_CACHE.get(key)
    if f is None:
        jnp = jax_mod.numpy
        core = _sig_core(jax_mod, kernel, sig, single)

        def fused(*args):
            ins, _ = _sig_assemble(jnp, sig, args)
            return core(*ins)

        f = jax_mod.jit(fused)
        _FUSED_CACHE[key] = f
    return f


def _get_fused_epi(jax_mod, kernel: Callable, sig: tuple, single: bool,
                   epi_kernel: Callable, w_idx: int, n_epi_ops: int):
    """_get_fused plus a SPECULATIVE EPILOGUE: after the (vmapped)
    kernel, one lane's output feeds a second kernel inside the SAME
    jitted program — the device-call answer to a critical-path
    consumer that the runtime has not released yet (it will, the moment
    this wave completes).  Panel factorizations are the shape this
    serves: the U(k, k+1) update's output is factored into F(k+1)'s
    result in the same call, halving calls on the factor chain.
    (Related art: cross-task kernel fusion in mega-kernel compilers,
    e.g. MPK, arXiv:2512.22219 — here done dynamically by the device
    module, scoped to a declared producer→consumer pair.)

    Batched form appends (lane:int32, *epi_ops) to the argument list
    and returns (*outs, *epi_outs); single form appends just the ops
    (the one lane IS the output)."""
    key = (kernel, sig, single, epi_kernel, w_idx, n_epi_ops)
    f = _FUSED_CACHE.get(key)
    if f is None:
        jnp = jax_mod.numpy
        core = _sig_core(jax_mod, kernel, sig, single)
        n_extra = n_epi_ops + (0 if single else 1)

        def fused(*args):
            base, extra = args[:len(args) - n_extra], \
                args[len(args) - n_extra:]
            ins, _ = _sig_assemble(jnp, sig, base)
            out = core(*ins)
            outs = out if isinstance(out, tuple) else (out,)
            if single:
                src = outs[w_idx]
                ops = extra
            else:
                src = jnp.take(outs[w_idx], extra[0], axis=0)
                ops = extra[1:]
            e = epi_kernel(src, *ops)
            eouts = e if isinstance(e, tuple) else (e,)
            return outs + eouts

        f = jax_mod.jit(fused)
        _FUSED_CACHE[key] = f
    return f


class _Epilogue:
    """Speculative cross-class fusion config, attached to the SOURCE
    body (see TpuDevice.attach_epilogue)."""
    __slots__ = ("dst_bkey", "kernel", "pick", "dst_params", "ops",
                 "src_flow", "dst_in_flow", "n_dst_writes")

    def __init__(self, dst_bkey, kernel, pick, dst_params, ops,
                 src_flow, dst_in_flow, n_dst_writes):
        self.dst_bkey = dst_bkey
        self.kernel = kernel
        self.pick = pick
        self.dst_params = dst_params
        self.ops = ops
        self.src_flow = src_flow
        self.dst_in_flow = dst_in_flow
        self.n_dst_writes = n_dst_writes


def _single_stack(ents):
    """(stack, row_idxs) when every entry is a lazy slice of ONE source
    stack — the gather can then ride inside the fused program — else
    None.  Shared by grouped_stack's eager fast path and the fused
    dispatcher so padding/identity semantics cannot diverge."""
    if not ents or not all(isinstance(e, _StackRef) for e in ents):
        return None
    if len({id(e.stack) for e in ents}) != 1:
        return None
    return ents[0].stack, [e.idx for e in ents]


# largest stacked d2h one flush transfer makes
_FLUSH_CHUNK_BYTES = 256 << 20


def _bucket(n: int) -> int:
    """Round a batch size up to a power of two: stacked shapes then come
    from a log-bounded set, so XLA compiles each batched kernel O(log B)
    times instead of once per distinct wave width."""
    b = 1
    while b < n:
        b <<= 1
    return b


class _StackRef:
    """Lazy slice of a stacked batch result.  Batched dispatch produces ONE
    device array for a whole task group; per-task cache entries reference
    (stack, index) so the common consumer — the next batched group — can
    gather straight from the stack with a single device op, and nothing is
    sliced out unless a host sync or an unbatched consumer asks for it."""
    __slots__ = ("stack", "idx")

    def __init__(self, stack, idx: int):
        self.stack = stack
        self.idx = idx

    def materialize(self):
        return self.stack[self.idx]


def local_tile_index(coll):
    """Row-major (m, n) list of this rank's stored local tiles."""
    out = []
    for m in range(coll.mt):
        for n in range(getattr(coll, "nt", 1)):
            if coll.rank_of(m, n) != coll.myrank:
                continue
            if hasattr(coll, "stored") and not coll.stored(m, n):
                continue
            out.append((m, n))
    return out


def grouped_stack(jnp, ents, bucket=None):
    """One stacked (bucket, *tile) device array from per-tile entries
    (concrete arrays or _StackRefs), in O(source stacks) device ops
    instead of O(tiles) slice ops.  Rows past len(ents) are padding (row 0
    repeated).  Shared by the batched dispatch gather and flush()."""
    bucket = bucket or len(ents)
    one = _single_stack(ents)
    if one is not None:
        stack, idxs = one
        idxs += [idxs[0]] * (bucket - len(idxs))
        return jnp.take(stack, jnp.asarray(idxs, dtype=jnp.int32),
                        axis=0)
    stacks = {id(e.stack) for e in ents if isinstance(e, _StackRef)}
    if stacks and len(ents) > len(stacks) + 2:
        by_stack = {}   # id -> (stack, [(orig_pos, row_idx)])
        loose = []      # [(orig_pos, array)]
        for pos, e in enumerate(ents):
            if isinstance(e, _StackRef):
                by_stack.setdefault(id(e.stack), (e.stack, []))[1] \
                    .append((pos, e.idx))
            else:
                loose.append((pos, e))
        parts, order = [], []
        for stack, rows in by_stack.values():
            parts.append(jnp.take(
                stack, jnp.asarray([r for _, r in rows],
                                   dtype=jnp.int32), axis=0))
            order.extend(p for p, _ in rows)
        if loose:
            parts.append(jnp.stack([a for _, a in loose]))
            order.extend(p for p, _ in loose)
        cat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        perm = [0] * len(ents)
        for cat_row, orig_pos in enumerate(order):
            perm[orig_pos] = cat_row
        perm += [perm[0]] * (bucket - len(perm))
        return jnp.take(cat, jnp.asarray(perm, dtype=jnp.int32), axis=0)
    mats = [e.materialize() if isinstance(e, _StackRef) else e
            for e in ents]
    mats += [mats[0]] * (bucket - len(mats))
    return jnp.stack(mats)


def _conc(ent: "_CacheEnt"):
    """Concrete device array for a cache entry, slicing a _StackRef out of
    its batch stack on first use (memoized; benign if raced)."""
    a = ent.arr
    if isinstance(a, _StackRef):
        a = a.materialize()
        ent.arr = a
    return a


def _host_write(ent: "_CacheEnt", res: np.ndarray) -> None:
    """Write a device result into the entry's bound host buffer.  The
    host binding may be a typed tile view or a flat uint8 view of a wire
    copy (dp_bound) — bytes are bytes either way."""
    if ent.host.dtype != res.dtype:
        ent.host[...] = np.ascontiguousarray(res).view(
            np.uint8).reshape(ent.host.shape)
    else:
        ent.host[...] = res.reshape(ent.host.shape)


class _CacheEnt:
    __slots__ = ("version", "arr", "nbytes", "dirty", "host", "persistent",
                 "raw", "stack", "pf", "spilling")

    def __init__(self, version, arr, nbytes, dirty=False, host=None,
                 persistent=True, raw=False):
        # pf: staged ahead of time by the prefetch lane, not consumed yet
        # (cleared — and counted as a prefetch hit — at first stage-in)
        self.pf = False
        # spilling: picked by the residency planner for an out-of-core
        # writeback+evict riding the writeback lane; the lane drops the
        # entry only if it is still THIS object when the d2h lands
        self.spilling = False
        self.version = version
        self.arr = arr
        self.nbytes = nbytes
        # batch-stack pin: entries born as _StackRef keep the whole stack
        # alive (and accounted) until the entry itself dies — HBM
        # accounting charges the stack once, per stack, not per slice
        self.stack = arr.stack if isinstance(arr, _StackRef) else None
        self.dirty = dirty  # device newer than host; host view kept to flush
        self.host = host
        # persistent: backed by user Data (host buffer cannot be freed
        # mid-flush); transient arena copies are never host-flushed
        self.persistent = persistent
        # raw: data-plane arrival as flat uint8; stage-in reinterprets to
        # the consumer's dtype/shape (device-side bitcast, no h2d)
        self.raw = raw


class TpuDevice:
    """One TPU device (one jax device) with a manager thread."""

    def __init__(self, ctx: Context, jax_device=None, pipeline_depth: int = 16,
                 cache_bytes: Optional[int] = None, autostart: bool = True,
                 prefetch: Optional[bool] = None):
        import jax  # deferred: tests may pin the platform first
        from collections import OrderedDict
        from ..utils.compile_cache import place_compile_cache
        self._jax = jax
        place_compile_cache()
        self.ctx = ctx
        self.device = jax_device or jax.devices()[0]
        self.qid = ctx.device_queue_new()
        self.pipeline_depth = pipeline_depth
        # max tasks fused into one vmapped dispatch (power-of-two padded)
        self.batch_max = int(os.environ.get("PTC_DEVICE_BATCH", "128"))
        # opt-in accumulate window: after a MULTI-task drain, keep
        # sweeping for up to this long so a wave being released
        # concurrently by workers lands in ONE dispatch — worth paying
        # when per-dispatch cost dominates (bench sets it for spotrf;
        # 0 = off, and single-task pops never wait, so latency-bound
        # chains are unaffected)
        self.batch_wait_ms = float(
            os.environ.get("PTC_DEVICE_BATCH_WAIT_MS", "0"))
        # byte cap on one vmapped call's stacked operands (see
        # _dispatch_group); count cap alone is blind to tile size
        self.batch_max_bytes = int(
            os.environ.get("PTC_DEVICE_BATCH_BYTES", str(2 << 30)))
        self.bodies: Dict[Tuple[int, int], _DeviceBody] = {}
        self._dtd_bodies: Dict[int, _DeviceBody] = {}
        self._tp_by_ptr: Dict[int, Taskpool] = {}
        # device-copy LRU keyed by uid (stamped into the native copy handle,
        # so freed/reused ptc_copy addresses can't alias — ABA guard)
        self._cache: "OrderedDict[int, _CacheEnt]" = OrderedDict()
        if cache_bytes is None:
            # the ptc-tune cache-budget knob: an explicit constructor
            # argument always wins; otherwise device.cache_bytes > 0
            # overrides the 4 GiB default
            from ..utils import params as _knobs
            cache_bytes = int(_knobs.get("device.cache_bytes")) or 4 << 30
        self._cache_bytes = cache_bytes
        self._cache_used = 0
        # id(stack) -> [refcount, stack]; the strong ref keeps id() stable
        self._stacks: Dict[int, list] = {}
        # speculative epilogue results: (dst body key, dst params) ->
        # (arrays, src_uid, src_version); consumed by the dst task's
        # dispatch, version-checked (see attach_epilogue)
        self._spec: Dict[tuple, tuple] = {}
        self._lock = threading.Lock()
        # ---- device pipeline (prefetch lane + residency planner) ----
        from ..utils import params as _mca
        if prefetch is None:
            prefetch = bool(_mca.get("device.prefetch"))
        self._pf_enabled = prefetch
        self._pf_depth = max(1, int(_mca.get("device.prefetch_depth")))
        self._pf_slots_max = max(1, int(_mca.get("device.staging_slots")))
        self._ooc = bool(_mca.get("device.out_of_core"))
        self._overcommit = max(1.0, float(_mca.get("device.overcommit")))
        # uids in the current ready-task lookahead: eviction under
        # pressure prefers tiles OUTSIDE this set (they are not about to
        # be consumed), and the planner never spills into it
        self._pf_pin: set = set()
        # bytes the prefetch lane has reserved but not yet installed:
        # reservations keep the lane from staging the cache past budget
        # and thrashing tiles the executing wave still needs
        self._pf_reserved = 0
        self._pf_lane = None  # _PrefetchLane once started
        # event-driven prefetch wakeup: remote deliveries (dp_deliver)
        # set it so the lane sweeps NOW instead of waiting out its poll
        # interval — within a wave, tile k h2d-stages while tile k+1 is
        # still on the wire
        self._pf_wake = threading.Event()
        # dispatch-time h2d stall accumulator for the CURRENT dispatch
        # call (manager thread only); emitted as the DEVICE span's aux,
        # so the bench can tell prefetch-hit waves (aux == 0) from
        # staged ones without a second event
        self._disp_stall_ns = 0
        # fused-dispatch mark for the NEXT DEVICE span's begin aux
        # (manager thread only): 0 plain, n >= 1 a certified wave
        # executable covering n wave(s) — set by the wave compiler
        self._disp_fused = 0
        # HBM pinned by parked chain speculations (ptc-fuse): the
        # output stacks of speculated waves live outside the cache
        # accounting until their tasks consume them, so the wave
        # compiler charges them here and refuses to chain under
        # residency pressure — out-of-core pools keep the PR 12
        # spill behavior instead of pinning unaccounted stacks
        self._chain_pinned = 0
        # ptc-fuse wave compiler (device.wave_fuse knob; None = off
        # reproduces the per-group batched dispatch path bit-exactly)
        self._fuser = None
        if bool(_mca.get("device.wave_fuse")):
            from .fuse import WaveFuser
            self._fuser = WaveFuser(self)
        # chain prefetch hints: [(collection name, idx tuple)] the wave
        # compiler predicts the NEXT chain segment will read; the
        # prefetch lane stages them alongside the peeked lookahead
        self._pf_chain_hints: list = []
        self._dbg(f"device up: {self.device} queue={self.qid} "
                  f"cache={cache_bytes >> 20}MiB batch<= {self.batch_max}")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # every key pre-populated: the dict never resizes after init, so
        # a concurrent info()/stats_dump() copy cannot hit a
        # changed-size-during-iteration error
        self.stats = {"tasks": 0, "h2d_bytes": 0, "d2h_bytes": 0,
                      "h2d_hits": 0, "evictions": 0, "dead_drops": 0,
                      "batches": 0, "batched_tasks": 0, "d2d_bytes": 0,
                      "dp_sends": 0, "dp_d2d_bytes": 0, "dp_xfer_bytes": 0,
                      "dp_recv_bytes": 0, "invalidations": 0,
                      "eager_gathers": 0, "fused_flows": 0,
                      "wb_tasks": 0, "f64_refused": 0,
                      "spec_store": 0, "spec_hits": 0, "spec_misses": 0,
                      # device pipeline (prefetch lane + residency planner)
                      "prefetch_staged": 0, "prefetch_bytes": 0,
                      "prefetch_hits": 0, "prefetch_misses": 0,
                      "prefetch_wasted": 0, "reserve_fails": 0,
                      "spills": 0, "spill_bytes": 0,
                      # waves demoted to per-task dispatch because their
                      # kernel has no batching rule (trace-time only)
                      "batch_fallbacks": 0,
                      "h2d_stall_ns": 0, "prefetch_h2d_ns": 0,
                      "ooc_waits": 0,
                      # cross-rank streaming (progressive serve + event-
                      # driven prefetch wakeups on remote delivery)
                      "stream_serves": 0, "stream_slices": 0,
                      "stream_d2h_ns": 0, "stream_bytes": 0,
                      "prefetch_wakeups": 0,
                      # high-water mark of the accounted device bytes —
                      # the measured side of the ptc-plan peak-residency
                      # bound (plan-vs-measured tests)
                      "cache_peak_bytes": 0}
        # native hook: copies dying with a device mirror drop it (a dead
        # dirty mirror is garbage by definition — no consumer remains).
        # ONE callback per context fanning out to all its devices — a
        # per-device registration would overwrite the slot and leak every
        # earlier device's entries.
        if getattr(ctx, "_copy_release_cb", None) is None:
            def _ctx_release(user, handle, _ctx=ctx):
                for d in list(_ctx._devices):
                    d._on_copy_released(user, handle)
            ctx._copy_release_cb = N.COPY_RELEASE_CB_T(_ctx_release)
            N.lib.ptc_set_copy_release_cb(ctx._ptr, ctx._copy_release_cb,
                                          None)
        # native coherence pull: comm sends / collection memcpys of a
        # device-dirty copy write the mirror back first.  Uids are
        # process-unique, so scanning this context's devices suffices.
        if getattr(ctx, "_copy_sync_cb", None) is None:
            def _ctx_sync(user, handle, _ctx=ctx):
                for d in list(_ctx._devices):
                    d.sync_handle(handle)
            ctx._copy_sync_cb = N.COPY_SYNC_CB_T(_ctx_sync)
            N.lib.ptc_set_copy_sync_cb(ctx._ptr, ctx._copy_sync_cb, None)
        # host-written invalidation: the runtime just OVERWROTE a copy's
        # host bytes (collection write-back memcpy, remote PUT) — every
        # device mirror of it is now stale and must drop, or a later
        # flush writes old device bytes over the newer host state
        # (observed: a Mem-rooted chain's hop-0 mirror clobbering the
        # final result at flush)
        if getattr(ctx, "_copy_invalidate_cb", None) is None:
            def _ctx_inval(user, handle, _ctx=ctx):
                for d in list(_ctx._devices):
                    d._drop_mirror(handle)
                N.lib.ptc_device_clear_data_owner(_ctx._ptr, handle, -1)
            ctx._copy_invalidate_cb = N.COPY_INVALIDATE_CB_T(_ctx_inval)
            N.lib.ptc_set_copy_invalidate_cb(ctx._ptr,
                                             ctx._copy_invalidate_cb, None)
        # device data plane: remote deps with a current device mirror ride
        # PK_DEVICE rendezvous instead of the host eager/GET paths
        if not hasattr(ctx, "_colocated"):
            ctx._colocated = set()
        if getattr(ctx, "_dp_cbs", None) is None:
            reg, srv, done, dlv, bnd, strm = _make_dp_callbacks(ctx)
            ctx._dp_cbs = (N.DP_REGISTER_CB_T(reg),
                           N.DP_SERVE_CB_T(srv),
                           N.DP_SERVE_DONE_CB_T(done),
                           N.DP_DELIVER_CB_T(dlv),
                           N.DP_BOUND_CB_T(bnd))
            N.lib.ptc_set_dataplane(ctx._ptr, *ctx._dp_cbs, None)
            # progressive-serve offer hook (kept alive alongside the
            # dataplane tuple — ctypes thunks die with their last ref)
            ctx._dp_stream_cb = N.DP_STREAM_CB_T(strm)
            N.lib.ptc_set_dp_stream(ctx._ptr, ctx._dp_stream_cb)
            if _xfer_enabled():
                # advertise pull capability to producers (GET-frame bit);
                # probe once per process, stamp per context
                ok = _xfer_can_pull(self.device.client, self.device)
                N.lib.ptc_set_dp_can_pull(ctx._ptr, 1 if ok else 0)
        ctx._devices.append(self)  # stopped before the native ctx dies
        _ALL_DEVICES.append(self)
        # mem-out writeback lane (reference: the CUDA stage-out/pop
        # stream, device_cuda_module.c:2197): d2h materialization of
        # sync-mem-out flows runs here, NOT in the dispatch loop, so one
        # slow d2h cannot serialize the waves behind it.  The task
        # completes from this lane AFTER its host bytes are coherent
        # (release_deps may memcpy them).
        import queue as _queue
        self._wb_q: "_queue.Queue" = _queue.Queue()
        self._wb_thread: Optional[threading.Thread] = None
        if autostart:
            self.start()

    # ------------------------------------------------------------ cache
    def _stats_add(self, key: str, n: int = 1) -> None:
        """Merge a counter delta under self._lock.  Stats are written
        from the manager thread, the writeback lane AND the comm
        thread's data-plane callbacks; a bare `+=` is a read-modify-
        write that loses updates across threads — and these counters
        feed bench evidence, so losses corrupt the harness too."""
        with self._lock:
            self.stats[key] += n

    def _copy_uid(self, cptr) -> int:
        with self._lock:  # races: manager vs stage_collection/gather
            h = N.lib.ptc_copy_handle(cptr)
            if h == 0:
                h = _next_uid()
                N.lib.ptc_copy_set_handle(cptr, h)
            return h

    def _charge(self, ent: _CacheEnt):
        """Account an entry's device bytes.  Slices of a batch stack charge
        the WHOLE stack exactly once (per-stack refcount): evicting one
        slice of a live stack frees nothing, and the accounting must say
        so or the LRU believes it is under budget while HBM is not."""
        if ent.stack is not None:
            rec = self._stacks.get(id(ent.stack))
            if rec is None:
                self._stacks[id(ent.stack)] = [1, ent.stack]
                self._cache_used += ent.stack.nbytes
            else:
                rec[0] += 1
        else:
            self._cache_used += ent.nbytes
        if self._cache_used > self.stats["cache_peak_bytes"]:
            self.stats["cache_peak_bytes"] = self._cache_used

    def _uncharge(self, ent: _CacheEnt):
        if ent.stack is not None:
            key = id(ent.stack)
            rec = self._stacks.get(key)
            if rec is not None:
                rec[0] -= 1
                if rec[0] == 0:
                    del self._stacks[key]
                    self._cache_used -= ent.stack.nbytes
        else:
            self._cache_used -= ent.nbytes

    def _drop_mirror(self, uid: int) -> None:
        """Drop a mirror whose HOST bytes were just overwritten by the
        runtime (the host is authoritative now; dirty or not, the device
        bytes are stale).  Owner clearing is done once by the context-
        level fan-out, not per device."""
        with self._lock:
            ent = self._cache.pop(uid, None)
            if ent is not None:
                self._uncharge(ent)
                self.stats["invalidations"] += 1

    def _on_copy_released(self, user, handle):
        with self._lock:
            ent = self._cache.pop(handle, None)
            if ent is not None:
                self._uncharge(ent)
                self.stats["dead_drops"] += 1
        # the copy is dying: its affinity stamp must not route anyone
        N.lib.ptc_device_clear_data_owner(self.ctx._ptr, handle, -1)

    def _cache_put(self, uid, version, arr, nbytes, dirty=False, host=None,
                   persistent=True, raw=False):
        spill = []
        with self._lock:
            old = self._cache.pop(uid, None)
            if old is not None:
                self._uncharge(old)
            ent = _CacheEnt(version, arr, nbytes, dirty, host,
                            persistent, raw)
            self._cache[uid] = ent
            self._charge(ent)
            # affinity stamp (reference: the owner_device routing pass,
            # device.c:100-117): consumers of this copy at this version
            # route here instead of staging on a cold sibling
            N.lib.ptc_device_set_data_owner(self.ctx._ptr, uid,
                                            self.qid, version)
            # evict-under-pressure, preference order (reference: the
            # clean-first reserve protocol of
            # parsec_gpu_data_reserve_device_space, :864): clean tiles
            # OUTSIDE the prefetch lookahead first — a pinned tile is
            # about to be consumed and would be re-staged immediately —
            # then clean lookahead tiles; dirty tiles never evict here
            # (their device bytes are the only truth).
            for only_unpinned in (True, False):
                if self._cache_used <= self._cache_bytes:
                    break
                evict = []
                for k, e in self._cache.items():
                    if self._cache_used <= self._cache_bytes:
                        break
                    if e.dirty or k == uid:
                        continue  # dirty entries are pinned until flushed
                    if only_unpinned and k in self._pf_pin:
                        continue
                    evict.append((k, e))
                    self._uncharge(e)
                for k, e in evict:
                    del self._cache[k]
                    self.stats["evictions"] += 1
                    N.lib.ptc_device_clear_data_owner(self.ctx._ptr, k,
                                                      self.qid)
            if self._ooc and self._cache_used > self._cache_bytes:
                spill = self._spill_pick_locked(uid)
        if spill:
            # out-of-core degrade: write the dirty mirrors back through
            # the writeback lane (host becomes authoritative, entry
            # evicted, re-staged on demand) instead of pinning HBM past
            # budget until the pool OOMs — the panel-cyclic residency of
            # the TPU distributed-LA paper (arXiv:2112.09017)
            self._wb_q.put(("spill", [], spill))

    def _spill_pick_locked(self, new_uid: int) -> list:
        """Residency planner, out-of-core leg (caller holds self._lock):
        pick dirty mirrors to spill through the writeback lane until the
        projected usage is back under budget.  Only persistent
        (collection-backed) entries qualify — a transient arena host
        buffer can be freed by its last consumer while the d2h is in
        flight — and lookahead-pinned tiles are skipped (they are about
        to be consumed).  Entries are marked `spilling` so one pressure
        wave cannot enqueue them twice."""
        picked, projected = [], self._cache_used
        for k, e in self._cache.items():
            if projected <= self._cache_bytes:
                break
            if (not e.dirty or e.spilling or not e.persistent
                    or e.host is None or k == new_uid
                    or k in self._pf_pin):
                continue
            e.spilling = True
            picked.append(k)
            projected -= e.nbytes if e.stack is None else 0
        return picked

    def _spill_one(self, uid: int) -> None:
        """Writeback-lane half of the spill: d2h the dirty mirror into
        its host buffer, then evict — IF the entry is still the one the
        planner picked (a re-put at a newer version since then must not
        be dropped; its own pressure wave will handle it)."""
        with self._lock:
            ent = self._cache.get(uid)
            if ent is None or not ent.spilling:
                return
        res = np.asarray(_conc(ent)) if ent.dirty else None  # blocking d2h
        with self._lock:
            cur = self._cache.get(uid)
            if cur is not ent:
                return
            if res is not None and ent.dirty:
                _host_write(ent, res)
                ent.dirty = False
                self.stats["d2h_bytes"] += int(res.nbytes)
            del self._cache[uid]
            self._uncharge(ent)
            self.stats["spills"] += 1
            self.stats["spill_bytes"] += int(ent.nbytes)
            N.lib.ptc_device_clear_data_owner(self.ctx._ptr, uid, self.qid)

    # ------------------------------------------------- prefetch lane seam
    def _prefetch_reserve(self, nbytes: int) -> bool:
        """Reserve byte budget BEFORE staging a lookahead tile (the
        reserve half of the reserve/evict protocol): evicts clean
        non-lookahead tiles if needed, never dirty ones and never the
        lookahead itself.  A False means the working set does not fit —
        the lane skips the tile and execution degrades to on-demand
        (out-of-core) staging instead of thrashing."""
        with self._lock:
            budget = self._cache_bytes - self._pf_reserved - nbytes
            if self._cache_used <= budget:
                self._pf_reserved += nbytes
                return True
            evict = []
            for k, e in self._cache.items():
                if self._cache_used <= budget:
                    break
                if e.dirty or e.pf or k in self._pf_pin:
                    continue
                evict.append((k, e))
                self._uncharge(e)
            for k, e in evict:
                del self._cache[k]
                self.stats["evictions"] += 1
                N.lib.ptc_device_clear_data_owner(self.ctx._ptr, k,
                                                  self.qid)
            if self._cache_used <= budget:
                self._pf_reserved += nbytes
                return True
            self.stats["reserve_fails"] += 1
            return False

    def _prefetch_unreserve(self, nbytes: int) -> None:
        with self._lock:
            self._pf_reserved = max(0, self._pf_reserved - nbytes)

    def _cache_put_prefetch(self, uid, version, arr, nbytes) -> bool:
        """Install a prefetched raw (flat uint8) mirror and release its
        reservation.  NEVER displaces an existing entry — the in-flight
        wave may be mid-read, and a dirty entry is newer truth than the
        host bytes this was staged from (the double-buffer discipline:
        prefetch writes land only in empty slots).  Returns False when
        the slot was taken since the peek (wasted stage, counted)."""
        with self._lock:
            self._pf_reserved = max(0, self._pf_reserved - nbytes)
            if uid in self._cache:
                self.stats["prefetch_wasted"] += 1
                return False
            ent = _CacheEnt(version, arr, nbytes, persistent=False,
                            raw=True)
            ent.pf = True
            self._cache[uid] = ent
            self._charge(ent)
            self.stats["prefetch_staged"] += 1
            self.stats["prefetch_bytes"] += int(nbytes)
            N.lib.ptc_device_set_data_owner(self.ctx._ptr, uid,
                                            self.qid, version)
        return True

    def _consume_pf(self, uid: int) -> bool:
        """First stage-in of a prefetched tile: clear the flag (so the
        hit counts once and the staging slot can recycle) and report."""
        with self._lock:
            ent = self._cache.get(uid)
            if ent is not None and ent.pf:
                ent.pf = False
                return True
        return False

    def _invalidate_siblings(self, uid: int) -> None:
        """Writer-side invalidation (MOESI 'owned' takeover): after this
        device produces a new version of `uid`, sibling mirrors hold a
        stale version — drop them so a later flush/sync cannot write
        stale bytes over the newer host state.  In-flight readers are
        unaffected (jax arrays are immutable; only the cache entry dies).
        Reference: coherency version/ownership flips,
        device_cuda_module.c:2365-2420."""
        for sib in list(getattr(self.ctx, "_devices", [])):
            if sib is self:
                continue
            with sib._lock:
                ent = sib._cache.pop(uid, None)
                if ent is not None:
                    sib._uncharge(ent)
                    sib.stats["invalidations"] += 1
                    N.lib.ptc_device_clear_data_owner(self.ctx._ptr, uid,
                                                      sib.qid)

    def set_cache_budget(self, nbytes: int) -> None:
        """Retarget the device byte budget at runtime (ops lever for
        multi-tenant hosts; tests use it to re-run one DAG resident vs
        out-of-core).  The residency planner reacts at the next insert —
        an over-budget cache evicts/spills then, not here."""
        with self._lock:
            self._cache_bytes = int(nbytes)

    def plan_check(self, tp, mode: Optional[str] = None, plan=None):
        """Pre-run residency check (ptc-plan): compare the pool's
        predicted per-rank DEVICE working set against this device's
        byte budget before anything schedules.

          fits            -> silent (counters only)
          over budget,
          out_of_core=0   -> warn to stderr, or raise PlanCheckError
                             with mode="error" — the run would pin HBM
                             until it OOMs
          over budget,
          out_of_core=1   -> warn with the PREDICTED SPILL COUNT (the
                             run completes out-of-core; the number is
                             the d2h write-back traffic to expect)

        `mode` defaults to the device.plan_check MCA param; Taskpool.run
        calls this automatically when the knob is armed.  Analysis
        failures never block a run (warned, counted as a skipped
        check).  Returns the (possibly supplied) Plan, or None when the
        pool has no device-chore classes or analysis failed."""
        import sys as _sys
        from ..utils import params as _mca
        if mode is None:
            mode = _mca.get("device.plan_check")
        if not mode or mode == "off":
            return None
        try:
            if plan is None:
                plan = tp.plan()
        except Exception as e:  # analysis must never kill a run
            _sys.stderr.write(f"ptc [plan]: plan_check skipped: {e}\n")
            return None
        if not plan.has_device_classes:
            return None
        rank = getattr(self.ctx, "myrank", 0)
        peak = plan.peak_bytes(rank=rank if rank in plan.per_rank
                               else None, device_only=True)
        ps = self.ctx._plan_stats
        with self._lock:
            budget = self._cache_bytes
        ps["checks"] += 1
        ps["last_peak_bytes"] = int(peak or 0)
        ps["last_budget_bytes"] = int(budget)
        if plan.bounded and peak is None:
            _sys.stderr.write(
                "ptc [plan]: plan_check inconclusive (symbolic bound "
                "unavailable); proceeding\n")
            return plan
        if peak <= budget:
            return plan
        ps["over_budget"] += 1
        if self._ooc:
            spills = plan.predict_spills(budget, rank=rank,
                                         device_only=True)
            ps["predicted_spills"] += spills
            _sys.stderr.write(
                f"ptc [plan]: predicted device working set {peak} B "
                f"exceeds cache budget {budget} B; out-of-core will "
                f"spill (~{spills} predicted write-backs)\n")
            return plan
        msg = (f"predicted device working set {peak} B exceeds the "
               f"cache budget {budget} B with device.out_of_core=0: "
               "the run would pin HBM past budget (raise the budget, "
               "re-enable out-of-core, or shrink the tiling)")
        if mode == "error":
            raise PlanCheckError(msg)
        _sys.stderr.write(f"ptc [plan]: {msg}\n")
        return plan

    def _cache_ent(self, uid, version) -> Optional["_CacheEnt"]:
        """Entry lookup without materializing _StackRefs (batched stage-in
        gathers straight from the underlying stacks)."""
        with self._lock:
            ent = self._cache.get(uid)
            if ent is not None and ent.version == version:
                self._cache.move_to_end(uid)
                return ent
        return None

    def _cache_get_typed(self, uid, version, dtype, shape):
        """Cache lookup that reinterprets raw data-plane arrivals (flat
        uint8) to the consumer's dtype/shape — a device-side bitcast, so
        a pulled payload is consumed with no h2d at all."""
        with self._lock:
            ent = self._cache.get(uid)
            if ent is None or ent.version != version:
                return None
            self._cache.move_to_end(uid)
            arr, raw = _conc(ent), ent.raw
        if not raw:
            return arr
        conv = self._reinterpret(arr, dtype, shape)
        with self._lock:
            ent2 = self._cache.get(uid)
            if ent2 is not None and ent2.version == version and ent2.raw:
                ent2.arr = conv  # memoize the typed view
                ent2.raw = False
        return conv

    def _reinterpret(self, arr_u8, dtype, shape):
        import jax
        dt = np.dtype(dtype)
        out = arr_u8
        if dt.itemsize > 1:
            out = jax.lax.bitcast_convert_type(
                arr_u8.reshape(-1, dt.itemsize), dt)
        return out.reshape(shape) if shape is not None else out

    def sync_handle(self, uid: int) -> None:
        """Coherence pull for ONE copy: if its device mirror is dirty,
        write it back to the host buffer and clear the dirty bit.

        Unlike flush(), non-persistent (arena-backed) copies are synced
        too: every caller is actively holding the copy it is about to
        read, so the host buffer cannot be freed concurrently here."""
        with self._lock:
            ent = self._cache.get(uid)
            if ent is None or not ent.dirty:
                return
        res = np.asarray(_conc(ent))  # blocks until the XLA result is ready
        _host_write(ent, res)
        with self._lock:  # d2h_bytes merge: callers span three threads
            self.stats["d2h_bytes"] += res.nbytes
            ent.dirty = False

    def info(self) -> dict:
        """Device info object (reference: the per-device info dictionaries,
        parsec/mca/device/device.h device_info) — identity, capacity, and
        live cache/kernel state for tooling and stats dumps."""
        with self._lock:
            cache_n = len(self._cache)
            cache_b = self._cache_used
            # copied under the lock: the manager thread inserts stats
            # keys lazily, and dict iteration during an insert raises
            stats = dict(self.stats)
            attached = len(self.bodies)
        fz = self._fuser
        return {
            "device": str(self.device),
            "kind": getattr(self.device, "device_kind", "?"),
            "platform": getattr(self.device, "platform", "?"),
            "queue": self.qid,
            "cache_tiles": cache_n,
            "cache_bytes": cache_b,
            "cache_capacity": self._cache_bytes,
            "attached_classes": attached,
            # the executable cache is process-wide (shared across device
            # instances of one client), hence the name
            "process_jit_kernels": len(_JIT_CACHE),
            "stats": stats,
            # ptc-fuse wave-compiler counters (schema-stable when off)
            "fuse": (fz.snapshot() if fz is not None
                     else {"enabled": False, "fused_waves": 0,
                           "fused_tasks": 0, "fused_chains": 0,
                           "chain_waves": 0, "chain_parked": 0,
                           "chain_hits": 0, "chain_misses": 0,
                           "chain_drops": 0, "cache_hits": 0,
                           "cache_misses": 0, "parked": 0,
                           "refused": {}}),
        }

    def _dbg(self, msg: str):
        """Device-subsystem debug stream (PTC_MCA_debug_device >= 1;
        reference: the per-subsystem output streams, parsec/utils/debug.c)."""
        if N.lib.ptc_context_verbose(self.ctx._ptr, N.DBG_DEVICE) >= 1:
            import sys
            print(f"ptc [device]: {msg}", file=sys.stderr)

    def flush(self):
        """Write every dirty device mirror back to its host copy.  Call
        before bulk host reads (to_dense etc.); per-copy coherence for CPU
        chores and comm sends is automatic via sync_handle().
        Same-shape mirrors are batched into stacked d2h transfers of at
        most _FLUSH_CHUNK_BYTES each: a whole-matrix stack would need the
        matrix's size again in HBM (N=32768 fp32 on a v5e: 4 GiB more
        than it has)."""
        import jax.numpy as jnp
        # coherence point: deferred mem-out writebacks must retire first
        self._wb_barrier()
        with self._lock:
            # only persistent (user-Data-backed) hosts are written: arena
            # buffers can be freed concurrently by the last consumer
            dirty = [(k, e) for k, e in self._cache.items()
                     if e.dirty and e.persistent]
        if dirty:
            self._dbg(f"flush: {len(dirty)} dirty mirrors")
        by_shape: Dict[tuple, list] = {}
        for uid, ent in dirty:
            by_shape.setdefault(tuple(ent.host.shape), []).append(ent)
        for shape, ents in by_shape.items():
            # grouped takes, not per-tile slices: flushing N tiles costs
            # O(source stacks) device ops per chunk, not N eager slices
            step = max(1, _FLUSH_CHUNK_BYTES // max(1, ents[0].nbytes))
            for i in range(0, len(ents), step):
                chunk = ents[i:i + step]
                stacked = np.asarray(
                    grouped_stack(jnp, [e.arr for e in chunk]))
                for e, res in zip(chunk, stacked):
                    _host_write(e, res)
                    with self._lock:
                        self.stats["d2h_bytes"] += res.nbytes
                        e.dirty = False

    # ------------------------------------------------------------ attach
    def attach(self, tc: TaskClass, tp: Taskpool, kernel: Callable,
               reads: Sequence[str], writes: Sequence[str],
               shapes: Dict[str, tuple], dtype=np.float32,
               dtypes: Optional[Dict[str, np.dtype]] = None,
               sync_mem_out: bool = False, batch: bool = True):
        """Attach a TPU chore: kernel(*read_arrays) -> write_array(s).

        sync_mem_out=True forces a blocking d2h before task completion for
        flows with memory-output deps — required only when the DAG writes a
        flow into a *different* collection tile (cross-collection memcpy at
        release); same-tile pass-through writebacks are no-ops natively and
        are satisfied lazily by flush().

        batch=True (default) lets the manager fuse a group of ready tasks
        of this class into ONE vmapped executable call — the TPU answer to
        µs-grained MIMD dispatch (SURVEY §7 hard-part 1: batch same-class
        ready tasks).  Requires the kernel to be elementwise over tiles
        (true for map-style bodies and all dense-LA update kernels); set
        False for kernels with cross-tile semantics."""
        if dtypes is None:
            dtypes = {f: np.dtype(dtype) for f in set(reads) | set(writes)}
        # float64 without jax x64: device_put silently downcasts to
        # float32 and the writeback would reinterpret mismatched bytes
        # (observed: corrupted f64 host tiles).  TPUs have no f64 compute
        # anyway — leave the class on its host chore, loudly.
        if any(np.dtype(d) == np.float64 for d in dtypes.values()) \
                and not self._jax.config.jax_enable_x64:
            import sys as _sys
            _sys.stderr.write(
                f"ptc [device]: not attaching {getattr(tc, 'name', '?')}: "
                "float64 flows need JAX_ENABLE_X64=1 (device would "
                "silently downcast); host chore carries it\n")
            # programmatic signal alongside the stderr line (DTD's
            # insert_tpu_task raises for the same hazard): tests/benches
            # assert the refusal without parsing stderr
            self.stats["f64_refused"] += 1
            return
        tc.body_device(self.qid, device="tpu")
        body = _DeviceBody(kernel, reads, writes, shapes, dtypes, tc, tp,
                           batch=batch)
        if not sync_mem_out:
            body.mem_out_flows = set()
        self.bodies[(id(tp), tc.id)] = body
        self._tp_by_ptr[tp._ptr] = tp

    def attach_epilogue(self, src_tc: TaskClass, dst_tc: TaskClass, tp,
                        src_flow: str, dst_in_flow: str, pick, dst_params,
                        kernel: Callable, ops,
                        const_flows: Sequence[str] = ()) -> None:
        """Speculative cross-class fusion (the dispatch-economics lever
        for factor chains): when a wave of `src_tc` contains the lane
        whose output is `dst_tc`'s next input, compute `kernel` (the
        dst-class device kernel) on that lane INSIDE the wave's program
        and park the result; when the dst task arrives, it completes
        from the parked result with ZERO device calls (version-checked
        against its actual input copy — any mismatch falls back to a
        normal dispatch).

          pick(src_view)  -> dst key tuple if this lane feeds the next
                             dst task, else None
          dst_params(view)-> the same key computed on the dst side
          ops(key)        -> extra host operands for `kernel` (tiny)

        SINGLE-VARYING-INPUT CONTRACT: the parked result was computed
        from the src lane's output plus `ops(key)` ONLY — the hit path
        version-checks just the `dst_in_flow` copy.  Every OTHER read
        flow of `dst_tc` must therefore be constant over the fused
        pair's lifetime and folded into `ops` (e.g. potrf/getrf's pivot
        index flow), and must be DECLARED in `const_flows`; an
        undeclared varying read flow would let a dst task complete from
        a result computed without that input — silent wrong answers.
        Raises ValueError for any dst read flow that is neither
        `dst_in_flow` nor declared.

        Both classes must already be attach()ed to this device.
        Disable via PTC_DEVICE_EPILOGUE=0 (bench comparison)."""
        if os.environ.get("PTC_DEVICE_EPILOGUE", "1") == "0":
            return
        src = self.bodies.get((id(tp), src_tc.id))
        dst = self.bodies.get((id(tp), dst_tc.id))
        if src is None or dst is None:
            return  # not device-attached (e.g. f64 refusal): no fusion
        uncovered = [f for f in dst.reads
                     if f != dst_in_flow and f not in const_flows]
        if uncovered:
            raise ValueError(
                f"attach_epilogue({getattr(src_tc, 'name', '?')} -> "
                f"{getattr(dst_tc, 'name', '?')}): dst read flow(s) "
                f"{uncovered} are neither dst_in_flow nor declared in "
                "const_flows.  The parked result is computed from the "
                "src lane + ops alone; a varying undeclared input would "
                "complete dst tasks with stale data (single-varying-"
                "input contract — see docstring)")
        epi = _Epilogue((id(tp), dst_tc.id), kernel, pick, dst_params,
                        ops, src_flow, dst_in_flow, len(dst.writes))
        src.epilogue = epi
        dst.spec_src = epi

    def stage_collection(self, coll):
        """Bulk-prestage every local tile of a TwoDimBlockCyclic-like
        collection: ONE h2d transfer of a stacked array, then per-tile
        device views.  Amortizes per-transfer latency (critical on
        high-latency links; on any link it beats per-tile puts)."""
        tiles = []
        uids = []
        for m, n in local_tile_index(coll):
            d = coll.data_of(m, n)
            cptr = N.lib.ptc_data_host_copy(d._ptr)
            uids.append((self._copy_uid(cptr),
                         N.lib.ptc_copy_version(cptr)))
            tiles.append(coll.tile(m, n))
        if not tiles:
            return
        stacked = self._jax.device_put(np.stack(tiles), self.device)
        for i, (uid, ver) in enumerate(uids):
            self._cache_put(uid, ver, stacked[i], tiles[i].nbytes)
        self._stats_add("h2d_bytes", stacked.nbytes)  # user thread

    def warm(self, kernel: Callable, example_args) -> None:
        """Pre-compile a kernel for given example shapes (optional)."""
        _get_jitted(self._jax, kernel).lower(*example_args).compile()

    # ------------------------------------------------------------ manager
    def start(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._manager, daemon=True,
                                        name="ptc-tpu-manager")
        self._thread.start()
        self._wb_thread = threading.Thread(target=self._wb_loop,
                                           daemon=True,
                                           name="ptc-tpu-writeback")
        self._wb_thread.start()
        if self._pf_enabled:
            from .prefetch import _PrefetchLane
            self._pf_lane = _PrefetchLane(self, depth=self._pf_depth,
                                          slots=self._pf_slots_max)
            self._pf_lane.start()

    def _wb_loop(self):
        """Writeback lane: materialize deferred mem-out d2h, then
        complete the tasks (coherence before release_deps).  A batched
        wave's whole output stack transfers as ONE stacked d2h ("stack"
        items); single-task dispatches sync per copy ("sync")."""
        while True:
            item = self._wb_q.get()
            if item is None:
                return
            if item[0] == "barrier":
                item[1].set()
                continue
            kind, tasks, payload = item
            try:
                if kind == "stack":
                    for ostack, uids in payload:
                        res = np.asarray(ostack[:len(uids)])  # one d2h
                        for i, uid in enumerate(uids):
                            self._wb_write(uid, ostack, i, res[i])
                elif kind == "spill":
                    # out-of-core residency: d2h + evict (see _spill_one)
                    for uid in payload:
                        self._spill_one(uid)
                elif kind == "stream":
                    # progressive serve: slice the remote-pulled mirror's
                    # d2h through the comm engine's watermark
                    self._stream_serve(*payload)
                else:
                    for uid in payload:
                        self.sync_handle(uid)
            except Exception:
                import traceback
                traceback.print_exc()
                for t in tasks:
                    self.ctx.task_fail(t)
                continue
            self._stats_add("wb_tasks", len(tasks))
            for t in tasks:
                self.ctx.task_complete(t)

    def _stream_serve(self, stream_id: int, tag: int) -> None:
        """Progressive-serve slicer (writeback lane): d2h the registered
        device array in comm.chunk_size slices, pushing each through
        ptc_dp_serve_progress so the comm engine's watermark advances —
        the wire starts moving after the FIRST slice instead of the
        whole-tile snapshot.  The engine answers 0 when the session is
        gone (retired early / puller lost): stop, the _DP_REG pin is
        dropped by the engine's dp_serve_done."""
        with _DP_LOCK:
            rec = _DP_REG.get(tag)
        if rec is None:
            return  # raced a release; the engine reaps on peer loss
        arr = rec[0]
        total = int(arr.nbytes)
        itemsize = int(np.dtype(arr.dtype).itemsize)
        chunk = int(self.ctx.comm_tuning().get("chunk_size") or (1 << 20))
        chunk_elems = max(1, chunk // itemsize)
        if getattr(self.device, "platform", "") == "cpu":
            # CPU backend: the mirror IS host memory — np.asarray is a
            # (near-)zero-copy view, so slices are plain views with no
            # per-slice dispatch.  The watermark protocol is identical;
            # the serialized path's whole-tile snapshot copy is what
            # this skips.
            host = np.ascontiguousarray(np.asarray(arr))
            hb = host.reshape(-1).view(np.uint8)

            def get_slice(ei):
                a = ei * itemsize
                return hb[a:a + chunk_elems * itemsize]
        else:
            # accelerator: slice ON DEVICE, d2h one slice at a time —
            # the wire starts after the first slice instead of the last
            flat = arr.reshape(-1)

            def get_slice(ei):
                sl = np.ascontiguousarray(
                    np.asarray(flat[ei:ei + chunk_elems]))  # blocking d2h
                return sl.view(np.uint8).reshape(-1)

        n = total // itemsize
        from ..profiling.trace import KEY_STREAM
        N.lib.ptc_prof_event(self.ctx._ptr, KEY_STREAM, 0, -1, total,
                             self.qid, 0)
        t0 = time.perf_counter_ns()
        slices = 0
        off = 0
        ei = 0
        try:
            while ei < n:
                b = get_slice(ei)
                while True:
                    rc = N.lib.ptc_dp_serve_progress(
                        self.ctx._ptr, stream_id, b.ctypes.data, off,
                        b.nbytes)
                    if rc != -1:
                        break
                    # session install races the accept callback: retry
                    time.sleep(0.0002)
                if rc == 0:
                    return  # session reaped (puller lost): stop slicing
                slices += 1
                off += int(b.nbytes)
                ei += chunk_elems
                if rc == 2:
                    return  # absorbed and the session completed with it
        finally:
            dt = time.perf_counter_ns() - t0
            N.lib.ptc_prof_event(self.ctx._ptr, KEY_STREAM, 1, -1, total,
                                 self.qid, 0)
            with self._lock:
                self.stats["stream_serves"] += 1
                self.stats["stream_slices"] += slices
                self.stats["stream_d2h_ns"] += dt
                self.stats["stream_bytes"] += off

    def _wb_write(self, uid, ostack, i, res) -> None:
        """Host-write one stack row's result if the cache entry is still
        the dispatch-time slice; anything re-put/evicted since falls back
        to the generic per-copy sync."""
        with self._lock:
            ent = self._cache.get(uid)
            hit = (ent is not None and ent.dirty
                   and isinstance(ent.arr, _StackRef)
                   and ent.arr.stack is ostack and ent.arr.idx == i)
        if not hit:
            self.sync_handle(uid)
            return
        _host_write(ent, res)
        with self._lock:  # writeback lane vs manager: merge under lock
            self.stats["d2h_bytes"] += res.nbytes
            ent.dirty = False

    def _wb_barrier(self, timeout: float = 300.0):
        """Coherence point: block until every queued writeback retired.
        A timeout is a hard error: proceeding would snapshot/clear dirty
        mirrors the writeback lane may still be writing (silent
        corruption of the host tiles a flush claims to make coherent)."""
        if self._wb_thread is None or not self._wb_thread.is_alive():
            return
        ev = threading.Event()
        self._wb_q.put(("barrier", ev))
        if not ev.wait(timeout=timeout):
            raise RuntimeError(
                f"ptc [device]: writeback barrier timed out after "
                f"{timeout:.0f}s — the writeback lane is wedged or still "
                "draining; dirty mirrors are NOT coherent")

    def stop(self):
        """Flush dirty mirrors and stop the manager (idempotent)."""
        if self._stop.is_set():
            return
        # prefetch lane first: it peeks the native queue and pins copies,
        # so it must be quiesced before the context can tear down
        if self._pf_lane is not None:
            self._pf_lane.stop()
            self._pf_lane = None
        # a failed flush still stops the threads (and re-raises below):
        # a live manager under a destroyed context crashes the process
        try:
            self.flush()
            err = None
        except Exception as e:
            err = e
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=30)
            self._thread = None
        # second flush AFTER the join: a task completing between the
        # first flush's dirty snapshot and manager exit would otherwise
        # be discarded by the clear below (cheap when nothing new)
        if err is None:
            self.flush()
        if self._wb_thread is not None:
            self._wb_q.put(None)
            self._wb_thread.join(timeout=30)
            self._wb_thread = None
        if self in _ALL_DEVICES:
            _ALL_DEVICES.remove(self)
        # release the HBM now: the device object itself often survives in
        # ctx/callback reference cycles until a GC pass, and a stopped
        # device's mirrors are dead weight (the flushes made the host
        # authoritative).  _stacks holds the strong refs to the batch
        # stacks — the multi-GiB allocations — so it must clear too.
        # Back-to-back runs on one chip otherwise OOM on the previous
        # run's stacks (r4 N=32768 rep-2).
        with self._lock:
            for k in self._cache:
                N.lib.ptc_device_clear_data_owner(self.ctx._ptr, k,
                                                  self.qid)
            self._cache.clear()
            self._stacks.clear()
            self._spec.clear()
            self._cache_used = 0
        if self._fuser is not None:
            self._fuser.clear()
        if err is not None:
            raise err

    def _manager(self):
        """Dispatch loop.  XLA queues kernels asynchronously, so completing
        a task here only means 'enqueued after its inputs' — device-side
        consumers chain correctly, and host coherence points (mem-out
        flows / flush) block on the actual results.

        The loop drains every ready task before dispatching, then fuses
        same-class groups into one vmapped call each — per-wave dispatch
        cost is O(classes), not O(tasks)."""
        while not self._stop.is_set():
            task = self.ctx.device_pop(self.qid, timeout_ms=50)
            if not task:
                continue
            if self._ooc and self._cache_used > \
                    self._cache_bytes * self._overcommit:
                # out-of-core hard cap: spills ride the writeback lane,
                # so usage can transiently overshoot budget; past
                # overcommit * budget the pipeline drains the lane
                # between waves — bounded residency, the panel-cyclic
                # throttle point (racy read: an approximate trigger is
                # fine, the barrier itself is exact)
                self._stats_add("ooc_waits", 1)
                self._wb_barrier()
            batch = [task]
            while len(batch) < self.batch_max:
                t2 = self.ctx.device_pop(self.qid, timeout_ms=0)
                if not t2:
                    break
                batch.append(t2)
            if (len(batch) > 1 and self.batch_wait_ms > 0
                    and len(batch) < self.batch_max):
                deadline = time.monotonic() + self.batch_wait_ms / 1e3
                while (len(batch) < self.batch_max
                       and time.monotonic() < deadline):
                    t2 = self.ctx.device_pop(self.qid, timeout_ms=1)
                    if t2:
                        batch.append(t2)
            if len(batch) == 1:
                self._dispatch(task)
                continue
            # group by body, preserving pop order within each group
            groups: List[Tuple[Optional[_DeviceBody], List]] = []
            index: Dict[int, int] = {}
            for t in batch:
                body = self._body_for(t)
                key = id(body)
                gi = index.get(key)
                if gi is None or body is None or not body.batch:
                    if gi is not None and body is not None \
                            and not body.batch \
                            and self._fuser is not None:
                        # >= 2 ready tasks of a vmap-incompatible
                        # class: the wave exists but cannot fuse —
                        # recorded, mirroring certify()'s opaque-body
                        # refusals (no silent fallback)
                        self._fuser._refuse("unbatchable-body")
                    index[key] = len(groups)
                    groups.append((body, [t]))
                else:
                    groups[gi][1].append(t)
            if self._fuser is not None and len(
                    {id(b) for b, _ in groups if b is not None}) > 1:
                # mixed ready front: each group still certifies on its
                # own, but the front as popped was not ONE wave —
                # recorded like certify()'s heterogeneous refusals
                self._fuser._refuse("heterogeneous-front")
            for body, ts in groups:
                if body is None:
                    for t in ts:
                        self.ctx.task_complete(t)
                elif len(ts) == 1 or not body.batch:
                    for t in ts:
                        self._dispatch_one(body, t)
                else:
                    self._dispatch_group(body, ts)

    def register_dtd_task(self, task_ptr, kernel, reads, writes, shapes,
                          dtype, nb_flows):
        """Per-task body for a DTD device task (consumed at dispatch).
        Keyed by a unique tag stamped on the task — raw heap addresses can
        be reused by later tasks (same ABA issue the copy cache guards)."""
        dtypes = {i: np.dtype(dtype) for i in range(nb_flows)}
        with self._lock:
            tag = _next_uid()
            N.lib.ptc_task_set_tag(task_ptr, tag)
            self._dtd_bodies[tag] = _DeviceBody(
                kernel, reads, writes, shapes, dtypes, None, None, nb_flows)

    def _body_for(self, task) -> Optional[_DeviceBody]:
        tag = N.lib.ptc_task_get_tag(task)
        if tag:
            with self._lock:
                b = self._dtd_bodies.pop(tag, None)
            if b is not None:
                return b
        tp_ptr = N.lib.ptc_task_taskpool(task)
        tp = self._tp_by_ptr.get(tp_ptr)
        if tp is None:
            return None
        cid = N.lib.ptc_task_class(task)
        return self.bodies.get((id(tp), cid))

    def _stage_in(self, view, body: _DeviceBody, flow):
        fi = body.flow_index(flow)
        cptr = N.lib.ptc_task_copy(view._ptr, fi)
        uid = self._copy_uid(cptr)
        ver = N.lib.ptc_copy_version(cptr)
        arr = self._cache_get_typed(uid, ver, body.dtypes[flow],
                                    body.shapes.get(flow))
        if arr is not None:
            self.stats["h2d_hits"] += 1
            if self._consume_pf(uid):
                self.stats["prefetch_hits"] += 1
            return arr
        # D2D: a sibling device of this context may hold the current
        # mirror — stage device-to-device over the fabric instead of
        # round-tripping the host (reference: CUDA peer stage-in,
        # device_cuda_module.c:1261)
        for sib in list(self.ctx._devices):
            if sib is self:
                continue
            sarr = sib._cache_get_typed(uid, ver, body.dtypes[flow],
                                        body.shapes.get(flow))
            if sarr is not None:
                darr = self._jax.device_put(sarr, self.device)
                self._cache_put(uid, ver, darr, sarr.nbytes)
                self.stats["d2d_bytes"] += sarr.nbytes
                return darr
        host = view.data(flow, dtype=body.dtypes[flow],
                         shape=body.shapes.get(flow), sync=False)
        # cold staging: a synchronous h2d ON the dispatch critical path —
        # exactly the stall the prefetch lane exists to absorb.  Timed
        # (h2d_stall_ns + the wave's DEVICE-span aux) and traced as a
        # dispatch-lane H2D span so the bench can pair it against
        # compute spans for the overlap fraction.
        from ..profiling.trace import KEY_H2D
        t0 = time.perf_counter_ns()
        # ptc-scope: the dispatching task is live in hand — stamp its
        # pool's request scope into the span's (otherwise unused) class
        # slot, so per-request timelines attribute this stall.  -1 when
        # unscoped (prefetch-lane spans stay -1: their tasks may retire
        # while the lane stages, and overlapped h2d is not lost time).
        scope = int(N.lib.ptc_task_scope(view._ptr)) or -1
        N.lib.ptc_prof_event(self.ctx._ptr, KEY_H2D, 0, scope,
                             host.nbytes, self.qid, 0)
        # OWNED snapshot, not the raw view: jax may read the h2d source
        # AFTER device_put returns (async dispatch), and `host` is a view
        # over native-owned memory — a wire-arrival copy dies at its last
        # consumer's completion, which the async kernel can overtake.
        # Observed failure: the first 16 bytes of a consumed panel turn
        # into freed-chunk heap metadata (tests/comm potrf device runs).
        darr = self._jax.device_put(np.array(host, copy=True), self.device)
        N.lib.ptc_prof_event(self.ctx._ptr, KEY_H2D, 1, scope,
                             host.nbytes, self.qid, 0)
        stall = time.perf_counter_ns() - t0
        self._disp_stall_ns += stall
        self.stats["h2d_stall_ns"] += stall
        # always-on metrics: the stall joins the native h2d_stall
        # histogram (same span-close instant as the H2D trace event),
        # so serving dashboards see its p99 without tracing on
        N.lib.ptc_metrics_record(self.ctx._ptr, N.MET_H2D_STALL, -1,
                                 stall)
        if self._pf_lane is not None:
            self.stats["prefetch_misses"] += 1
        self._cache_put(uid, ver, darr, host.nbytes)
        self._stats_add("h2d_bytes", host.nbytes)  # vs stage_collection
        return darr

    def _dispatch(self, task):
        body = self._body_for(task)
        if body is None:
            self.ctx.task_complete(task)
            return
        self._dispatch_one(body, task)

    def _flow_uid_ver(self, view, body, flow):
        fi = body.flow_index(flow)
        cptr = N.lib.ptc_task_copy(view._ptr, fi)
        return cptr, self._copy_uid(cptr), N.lib.ptc_copy_version(cptr)

    def _flow_entries(self, views, body, flow):
        """Per-task device entries for one read flow: concrete arrays or
        lazy _StackRefs (left unresolved so the dispatcher can fuse the
        gather into the kernel program)."""
        ents = []
        for view in views:
            cptr, uid, ver = self._flow_uid_ver(view, body, flow)
            ent = self._cache_ent(uid, ver)
            if ent is None or ent.raw:
                # host stage-in / raw reinterpret: same path as unbatched
                ents.append(self._stage_in(view, body, flow))
            else:
                self.stats["h2d_hits"] += 1
                ents.append(ent.arr)  # may be a _StackRef
        return ents

    def _write_out(self, view, body: _DeviceBody, flow, arr):
        """Install one task's output in the cache as a dirty mirror and
        return its uid.  Host coherence is lazy: flush()/sync_handle()
        pull it, and sync-mem-out flows ride the writeback lane, which
        syncs the host copy BEFORE completing the task (release_deps may
        memcpy it into another collection tile).  Shared by batched and
        per-task dispatch."""
        cptr, uid, ver = self._flow_uid_ver(view, body, flow)
        host = view.data(flow, dtype=body.dtypes[flow],
                         shape=body.shapes.get(flow), sync=False)
        persistent = bool(N.lib.ptc_copy_is_persistent(cptr))
        self._cache_put(uid, ver + 1, arr, host.nbytes,
                        dirty=True, host=host, persistent=persistent)
        self._invalidate_siblings(uid)
        return uid, ver + 1

    def _dispatch_group(self, body: _DeviceBody, tasks: List):
        """One vmapped executable call for a group of ready tasks of the
        same class.  Inputs are gathered per flow into (bucket, *tile)
        stacks; outputs stay stacked, with per-task cache entries holding
        lazy slices — the next batched consumer gathers from them without
        any intermediate slicing.

        Groups are split so one call's stacked operands stay under
        PTC_DEVICE_BATCH_BYTES (default 2 GiB): a wave of wide tiles
        (panel-granular dense LA) must not stack itself out of HBM."""
        per_task = 0
        # reads + writes separately: an RW flow's gathered input stack
        # and produced output stack coexist during the call, so it costs
        # two stacks' worth.  (Wave-shared broadcast flows are counted
        # per lane though shipped once — conservative over-splitting.)
        for f in list(body.reads) + list(body.writes):
            shp = body.shapes.get(f)
            if shp:
                per_task += int(np.prod(shp)) * np.dtype(
                    body.dtypes.get(f, np.float32)).itemsize
        if per_task > 0 and len(tasks) * per_task > self.batch_max_bytes:
            chunk = max(1, self.batch_max_bytes // per_task)
            # floor to a power of two: _bucket rounds the lane count UP,
            # so a non-power chunk would pad its stacks past the cap
            chunk = 1 << (chunk.bit_length() - 1)
            for i in range(0, len(tasks), chunk):
                self._dispatch_group_chunk(body, tasks[i:i + chunk])
            return
        self._dispatch_group_chunk(body, tasks)

    def _prof(self, phase: int, body: "_DeviceBody", lanes: int) -> None:
        """DEVICE_DISPATCH trace span: begin at gather/dispatch start,
        end after the async enqueue.  Same native buffer, dictionary,
        and PINS fan-out as worker events; no-op when both are off.
        l1 carries the device's queue id so concurrent same-class spans
        from sibling devices pair and render distinctly.  The END
        event's aux carries the wave's dispatch-time h2d stall in ns
        (0 == every input was resident/prefetched: a prefetch-hit
        wave), so the bench reads staged-vs-prefetched latency straight
        off paired spans.  The BEGIN event's aux marks FUSED dispatches
        (ptc-fuse): 0 = plain, n >= 1 = a certified wave executable
        covering n wave(s) — the bench-device fused-vs-unfused section
        counts launches straight off these spans."""
        from ..profiling.trace import KEY_DEVICE
        cid = body.tc.id if body.tc is not None else -1
        if phase == 0:
            self._disp_stall_ns = 0
            aux = self._disp_fused
        else:
            aux = self._disp_stall_ns
            self._disp_fused = 0
        N.lib.ptc_prof_event(self.ctx._ptr, KEY_DEVICE, phase, cid,
                             lanes, self.qid, aux)

    def _dispatch_group_chunk(self, body: _DeviceBody, tasks: List):
        fz = self._fuser
        if fz is not None:
            # ptc-fuse: parked chain results complete first (zero
            # launches), then the wave compiler certifies the remainder
            # online — a certified wave marks its DEVICE span, and a
            # certified CHAIN dispatches entirely inside the compiler
            tasks = fz.consume_group(body, tasks)
            if not tasks:
                return
            if len(tasks) == 1:
                self._dispatch_one(body, tasks[0])
                return
            if fz.dispatch_group(body, tasks):
                return
        self._prof(0, body, len(tasks))
        try:
            self._dispatch_group_run(body, tasks)
        finally:
            self._prof(1, body, len(tasks))

    def _wave_sig_args(self, body: _DeviceBody, views: List, bucket: int):
        """Fused-gather marshaling for one wave: per read flow, decide
        how the lanes' inputs enter the jitted program (in-program
        gather / shared broadcast / pre-stacked) and build the flat
        call args.  Shared by the batched group dispatch and the wave
        compiler (fuse.py) so the two can never marshal differently —
        the chain executable's level 0 IS the group dispatch's
        program."""
        sig, call_args = [], []
        for f in body.reads:
            ents = self._flow_entries(views, body, f)
            first = ents[0]
            if all(e is first for e in ents):
                # wave-wide shared operand: ship once, vmap axis None
                self.stats["fused_flows"] += 1
                if isinstance(first, _StackRef):
                    sig.append("bidx")
                    call_args += [first.stack, np.int32(first.idx)]
                else:
                    sig.append("bcast")
                    call_args.append(first)
                continue
            one = _single_stack(ents)
            if one is not None:
                stack, idxs = one
                if len(set(idxs)) == 1:
                    # shared row of one stack: same broadcast case
                    self.stats["fused_flows"] += 1
                    sig.append("bidx")
                    call_args += [stack, np.int32(idxs[0])]
                    continue
                idxs += [idxs[0]] * (bucket - len(idxs))
                sig.append("idx")
                self.stats["fused_flows"] += 1
                call_args += [stack,
                              np.asarray(idxs, dtype=np.int32)]
            else:
                sig.append(None)
                self.stats["eager_gathers"] += 1
                call_args.append(grouped_stack(
                    self._jax.numpy, ents, bucket))
        if sig and all(s in ("bcast", "bidx") for s in sig):
            # degenerate wave (every flow shared): vmap needs one
            # mapped axis — demote flow 0 to a per-lane form
            if sig[0] == "bidx":
                sig[0] = "idx"
                call_args[1] = np.full((bucket,),
                                       int(call_args[1]), np.int32)
            else:
                sig[0] = None
                call_args[0] = self._jax.numpy.stack(
                    [call_args[0]] * bucket)
        return sig, call_args

    def _dispatch_group_run(self, body: _DeviceBody, tasks: List):
        if body.spec_src is not None:
            # batched destination class: consume parked results here too
            # (potrf's factor chain never batches, but the mechanism must
            # not silently waste stores for classes that do)
            rest = []
            for t in tasks:
                if not self._try_spec(body, t, body.make_view(t)):
                    rest.append(t)
            if not rest:
                return
            tasks = rest
        views = [body.make_view(t) for t in tasks]
        bucket = _bucket(len(tasks))
        try:
            # Per flow: if every entry is a slice of ONE source stack,
            # ship (stack, idx) and gather inside the fused program;
            # otherwise pre-gather eagerly (mixed sources).  The whole
            # wave is then a single device dispatch.
            sig, call_args = self._wave_sig_args(body, views, bucket)
            # speculative epilogue: if one lane feeds the next dst-class
            # task, compute the dst kernel on it inside the same program
            epi = body.epilogue
            epi_lane = epi_key = None
            if epi is not None:
                for i, view in enumerate(views):
                    kk = epi.pick(view)
                    if kk is not None:
                        epi_lane, epi_key = i, kk
                        break
            if epi_lane is not None:
                epi_ops = epi.ops(epi_key)
                w_idx = body.writes.index(epi.src_flow)
                out_all = _get_fused_epi(
                    self._jax, body.kernel, tuple(sig), False,
                    epi.kernel, w_idx, len(epi_ops))(
                        *call_args, np.int32(epi_lane), *epi_ops)
                outs = tuple(out_all[:len(body.writes)])
                eouts = tuple(out_all[len(body.writes):])
            else:
                out = _get_fused(self._jax, body.kernel, tuple(sig),
                                 single=False)(*call_args)
                outs = out if isinstance(out, tuple) else (out,)
                eouts = ()
            wb_stacks = []
            epi_src = None
            for f, ostack in zip(body.writes, outs):
                sync_host = f in body.mem_out_flows
                uids = []
                for i, view in enumerate(views):
                    uid, nv = self._write_out(view, body, f,
                                              _StackRef(ostack, i))
                    if sync_host:
                        uids.append(uid)
                    if epi_lane is not None and i == epi_lane \
                            and f == epi.src_flow:
                        epi_src = (uid, nv)
                if sync_host:
                    wb_stacks.append((ostack, uids))
            if eouts and epi_src is not None:
                self._spec_put((epi.dst_bkey, epi_key), eouts, epi_src)
            self.stats["tasks"] += len(tasks)
            self.stats["batches"] += 1
            self.stats["batched_tasks"] += len(tasks)
        except self._jax.errors.JaxRuntimeError:
            # XLA refused or failed the wave (compile error, OOM, ...):
            # the tasks fail and the pool aborts — per-task dispatch
            # would only hide the device's answer
            import traceback
            traceback.print_exc()
            for t in tasks:
                self.ctx.task_fail(t)
            return
        except Exception:
            # a trace-time error: the kernel has no batching rule (or a
            # shape-dependent callback).  Demote the class to per-task
            # dispatch, where genuine kernel errors still fail the task,
            # and count it
            import traceback
            traceback.print_exc()
            import sys as _sys
            _sys.stderr.write("ptc: batched dispatch failed for "
                              f"{getattr(body.tc, 'name', '?')}; "
                              "falling back to per-task dispatch\n")
            self.stats["batch_fallbacks"] += 1
            body.batch = False
            for t in tasks:
                self._dispatch_one(body, t)
            return
        if wb_stacks and self._wb_thread is not None:
            # mem-out flows: host coherence (the blocking d2h) and the
            # completions ride the writeback lane; the dispatch loop
            # moves straight on to the next wave.  The whole output
            # stack ships as ONE stacked d2h there, not per-tile pulls.
            self._wb_q.put(("stack", list(tasks), wb_stacks))
            return
        for t in tasks:
            self.ctx.task_complete(t)

    def _dispatch_one(self, body, task):
        fz = self._fuser
        if fz is not None and fz.consume(body, task):
            return  # completed from a parked chain result: no launch
        self._prof(0, body, 1)
        try:
            self._dispatch_one_run(body, task)
        finally:
            self._prof(1, body, 1)

    def _spec_put(self, key, eouts, src) -> None:
        """Park a speculative result.  Bounded: an unconsumed entry
        (the dst task routed to a sibling device) pins a whole panel of
        HBM, so only a handful may linger."""
        self._spec[key] = (eouts, src[0], src[1])
        self.stats["spec_store"] += 1
        while len(self._spec) > 4:
            self._spec.pop(next(iter(self._spec)))

    def _try_spec(self, body, task, view) -> bool:
        """Destination-side epilogue fast path: complete the task from a
        parked speculative result (ZERO device calls) when its input
        copy matches the version the source wave produced.  Returns True
        when the task was DISPOSED (completed or failed) — a raising
        user callback must not kill the manager thread, it fails the
        task like every other body-error path."""
        spec = body.spec_src
        if spec is None:
            return False
        try:
            rec = self._spec.pop((spec.dst_bkey, spec.dst_params(view)),
                                 None)
            if rec is None:
                return False
            arrs, suid, sver = rec
            if len(arrs) != len(body.writes):
                # misconfigured epilogue kernel (wrong output arity): a
                # silent partial write would corrupt downstream flows
                self.stats["spec_misses"] += 1
                import sys as _sys
                _sys.stderr.write(
                    "ptc [device]: epilogue kernel returned "
                    f"{len(arrs)} output(s), dst class writes "
                    f"{len(body.writes)}; ignoring parked result\n")
                return False
            cptr = N.lib.ptc_task_copy(
                view._ptr, body.flow_index(spec.dst_in_flow))
            if N.lib.ptc_copy_handle(cptr) != suid \
                    or N.lib.ptc_copy_version(cptr) != sver:
                self.stats["spec_misses"] += 1
                return False
            wb_uids = []
            for f, arr in zip(body.writes, arrs):
                uid, _ = self._write_out(view, body, f, arr)
                if f in body.mem_out_flows:
                    wb_uids.append(uid)
        except Exception:
            import traceback
            traceback.print_exc()
            self.ctx.task_fail(task)
            return True
        self.stats["spec_hits"] += 1
        self.stats["tasks"] += 1
        if wb_uids and self._wb_thread is not None:
            self._wb_q.put(("sync", [task], wb_uids))
            return True
        self.ctx.task_complete(task)
        return True

    def _dispatch_one_run(self, body, task):
        view = body.make_view(task)
        if self._try_spec(body, task, view):
            return
        try:
            # Inputs still living as stack slices are selected INSIDE the
            # jitted program (scalar-index take) — a single-task dispatch
            # whose inputs are batch-stack rows costs one device call,
            # not one slice op per flow plus the exec.
            sig, call_args = [], []
            for f in body.reads:
                ent = self._flow_entries([view], body, f)[0]
                if isinstance(ent, _StackRef):
                    sig.append("idx")
                    call_args += [ent.stack,
                                  np.int32(ent.idx)]
                else:
                    sig.append(None)
                    call_args.append(ent)
            epi = body.epilogue
            epi_key = epi.pick(view) if epi is not None else None
            if epi_key is not None:
                epi_ops = epi.ops(epi_key)
                w_idx = body.writes.index(epi.src_flow)
                out_all = _get_fused_epi(
                    self._jax, body.kernel, tuple(sig), True,
                    epi.kernel, w_idx, len(epi_ops))(*call_args,
                                                     *epi_ops)
                outs = tuple(out_all[:len(body.writes)])
                eouts = tuple(out_all[len(body.writes):])
            else:
                out = _get_fused(self._jax, body.kernel, tuple(sig),
                                 single=True)(*call_args)  # async
                outs = out if isinstance(out, tuple) else (out,)
                eouts = ()
            wb_uids = []
            epi_src = None
            for f, arr in zip(body.writes, outs):
                uid, nv = self._write_out(view, body, f, arr)
                if f in body.mem_out_flows:
                    wb_uids.append(uid)
                if epi_key is not None and f == epi.src_flow:
                    epi_src = (uid, nv)
            if eouts and epi_src is not None:
                self._spec_put((epi.dst_bkey, epi_key), eouts, epi_src)
            self.stats["tasks"] += 1
        except Exception:
            # A failed kernel must NOT complete the task — successors
            # would consume stale/garbage data and the pool would
            # "succeed".  Abort the pool (reference: ptc_task_fail /
            # chore ERROR protocol; VERDICT r1 weak #2).
            import traceback
            traceback.print_exc()
            self.ctx.task_fail(task)
            return
        if wb_uids and self._wb_thread is not None:
            self._wb_q.put(("sync", [task], wb_uids))
            return
        self.ctx.task_complete(task)
