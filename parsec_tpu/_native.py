"""ctypes bindings to the native core (build/libparsec_core.so).

Auto-builds via `make` when the shared library is missing or was built from
other sources: freshness is a hash of native/* stored beside the library, not
mtimes, so a build/ copied from elsewhere rebuilds from the committed sources.
All Python→native traffic goes through this module; keep the ABI in sync with
native/parsec_core.h.
"""
from __future__ import annotations

import ctypes as C
import fcntl
import hashlib
import os
import subprocess

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# PTC_NATIVE_LIB points at an alternate build of the core (ASan/TSan
# instrumented, debug, ...) without touching the default build tree.
_LIB_PATH = os.environ.get("PTC_NATIVE_LIB") or \
    os.path.join(_REPO, "build", "libparsec_core.so")
_SRC_DIR = os.path.join(_REPO, "native")

# hook protocol (parsec_core.h)
HOOK_DONE = 0
HOOK_AGAIN = 1
HOOK_ASYNC = 2
HOOK_NEXT = 3
HOOK_DISABLE = 4
HOOK_ERROR = -1

FLOW_READ = 1
FLOW_WRITE = 2
FLOW_RW = 3
FLOW_CTL = 4

# must mirror PTC_MAX_LOCALS / PTC_MAX_FLOWS (native/parsec_core.h:30-31)
MAX_LOCALS = 20
MAX_FLOWS = 20

# debug-stream subsystem ids (must mirror PTC_DBG_* in parsec_core.h)
DBG_RUNTIME = 0
DBG_COMM = 1
DBG_DEVICE = 2
DBG_SUBSYSTEMS = ("runtime", "comm", "device")  # index == id

BODY_NOOP = 0
BODY_CB = 1
BODY_DEVICE = 2

# element kinds for cast datatypes (must mirror PTC_ELEM_* in parsec_core.h)
ELEM_KINDS = {"float32": 0, "float64": 1, "int32": 2, "int64": 3, "uint8": 4}

# always-on metrics kinds (must mirror PTC_MET_* in runtime_internal.h)
MET_EXEC = 0
MET_RELEASE = 1
MET_H2D_STALL = 2
MET_COMM_WAIT = 3
MET_COLL_WAIT = 4
MET_KIND_NAMES = ("exec", "release", "h2d_stall", "comm_wait", "coll_wait")

DEV_CPU = 0
DEV_TPU = 1
DEV_RECURSIVE = 2

# expression VM opcodes
OP_IMM = 1
OP_LOCAL = 2
OP_GLOBAL = 3
OP_ADD = 4
OP_SUB = 5
OP_MUL = 6
OP_DIV = 7
OP_MOD = 8
OP_NEG = 9
OP_EQ = 10
OP_NE = 11
OP_LT = 12
OP_LE = 13
OP_GT = 14
OP_GE = 15
OP_AND = 16
OP_OR = 17
OP_NOT = 18
OP_SELECT = 19
OP_MIN = 20
OP_MAX = 21
OP_CALL = 22
OP_SHL = 23
OP_SHR = 24


def source_hash() -> str:
    """sha256 over the names and bytes of every file in native/."""
    h = hashlib.sha256()
    for name in sorted(os.listdir(_SRC_DIR)):
        path = os.path.join(_SRC_DIR, name)
        if os.path.isfile(path):
            with open(path, "rb") as f:
                h.update(name.encode() + b"\0" + f.read())
    return h.hexdigest()


SOURCE_HASH = source_hash()


def _ensure_built() -> None:
    """(Re)build the core unless the library beside its hash file was
    built from exactly these sources.  Under a file lock: concurrent
    importers (test workers) build once, and none loads a half-written
    library."""
    if os.environ.get("PTC_NATIVE_LIB"):
        return  # instrumented override: its builder owns freshness
    stamp = _LIB_PATH + ".srchash"
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    with open(_LIB_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            with open(stamp) as f:
                built = f.read().strip()
        except OSError:
            built = None
        if built == SOURCE_HASH and os.path.exists(_LIB_PATH):
            return
        # -B: make's own mtime test would trust a copied library
        subprocess.run(["make", "-s", "-B"], cwd=_REPO, check=True)
        with open(stamp, "w") as f:
            f.write(SOURCE_HASH + "\n")


_ensure_built()

lib = C.CDLL(_LIB_PATH)

# callback signatures
EXPR_CB_T = C.CFUNCTYPE(C.c_int64, C.c_void_p, C.POINTER(C.c_int64), C.c_int32,
                        C.POINTER(C.c_int64))
BODY_CB_T = C.CFUNCTYPE(C.c_int32, C.c_void_p, C.c_void_p)
RANK_OF_CB_T = C.CFUNCTYPE(C.c_uint32, C.c_void_p, C.POINTER(C.c_int64), C.c_int32)
DATA_OF_CB_T = C.CFUNCTYPE(C.c_void_p, C.c_void_p, C.POINTER(C.c_int64), C.c_int32)
COPY_RELEASE_CB_T = C.CFUNCTYPE(None, C.c_void_p, C.c_int64)
COPY_SYNC_CB_T = C.CFUNCTYPE(None, C.c_void_p, C.c_int64)
COPY_INVALIDATE_CB_T = C.CFUNCTYPE(None, C.c_void_p, C.c_int64)
DP_REGISTER_CB_T = C.CFUNCTYPE(C.c_int64, C.c_void_p, C.c_int64, C.c_int64,
                               C.c_int64)
DP_SERVE_CB_T = C.CFUNCTYPE(C.c_int64, C.c_void_p, C.c_int64, C.c_int32,
                            C.c_int32, C.POINTER(C.c_void_p),
                            C.POINTER(C.c_int64))
DP_SERVE_DONE_CB_T = C.CFUNCTYPE(None, C.c_void_p, C.c_int64)
DP_DELIVER_CB_T = C.CFUNCTYPE(C.c_int64, C.c_void_p, C.c_void_p, C.c_int64,
                              C.c_int64)
DP_BOUND_CB_T = C.CFUNCTYPE(None, C.c_void_p, C.c_int64, C.c_void_p,
                            C.c_int64, C.c_int32)
# progressive-serve offer (wire v4 streaming): (user, tag, from, xfer_ok,
# stream_id, total) -> 1 accept / 0 decline
DP_STREAM_CB_T = C.CFUNCTYPE(C.c_int32, C.c_void_p, C.c_int64, C.c_int32,
                             C.c_int32, C.c_uint64, C.c_int64)
TP_COMPLETE_CB_T = C.CFUNCTYPE(None, C.c_void_p, C.c_void_p)
PINS_CB_T = C.CFUNCTYPE(None, C.c_void_p, C.POINTER(C.c_int64))

_sigs = {
    "ptc_version": (C.c_char_p, []),
    "ptc_context_new": (C.c_void_p, [C.c_int32]),
    "ptc_context_destroy": (None, [C.c_void_p]),
    "ptc_context_nb_workers": (C.c_int32, [C.c_void_p]),
    "ptc_context_start": (C.c_int32, [C.c_void_p]),
    "ptc_context_wait": (C.c_int32, [C.c_void_p]),
    "ptc_context_test": (C.c_int32, [C.c_void_p]),
    "ptc_context_set_scheduler": (C.c_int32, [C.c_void_p, C.c_char_p]),
    "ptc_context_set_sched_bypass": (None, [C.c_void_p, C.c_int32]),
    "ptc_context_get_sched_bypass": (C.c_int32, [C.c_void_p]),
    "ptc_sched_stats": (C.c_int64, [C.c_void_p, C.POINTER(C.c_int64),
                                    C.c_int64]),
    "ptc_tp_set_qos": (None, [C.c_void_p, C.c_int32, C.c_int64]),
    "ptc_tp_qos_stats": (C.c_int64, [C.c_void_p, C.POINTER(C.c_int64),
                                     C.c_int64]),
    "ptc_tp_set_scope": (None, [C.c_void_p, C.c_int64]),
    "ptc_tp_scope": (C.c_int64, [C.c_void_p]),
    "ptc_task_scope": (C.c_int64, [C.c_void_p]),
    "ptc_clock_ns": (C.c_int64, []),
    "ptc_context_set_qos_preempt": (None, [C.c_void_p, C.c_int32]),
    "ptc_context_get_qos_preempt": (C.c_int32, [C.c_void_p]),
    "ptc_context_set_rank": (None, [C.c_void_p, C.c_uint32, C.c_uint32]),
    "ptc_context_set_binding": (None, [C.c_void_p, C.c_int32]),
    "ptc_worker_binding": (C.c_int32, [C.c_void_p, C.c_int32]),
    "ptc_context_set_verbose": (None, [C.c_void_p, C.c_int32, C.c_int32]),
    "ptc_context_verbose": (C.c_int32, [C.c_void_p, C.c_int32]),
    "ptc_register_expr_cb": (C.c_int32, [C.c_void_p, EXPR_CB_T, C.c_void_p]),
    "ptc_register_body": (C.c_int32, [C.c_void_p, BODY_CB_T, C.c_void_p]),
    "ptc_register_collection": (C.c_int32, [C.c_void_p, C.c_uint32, C.c_uint32,
                                            RANK_OF_CB_T, DATA_OF_CB_T, C.c_void_p]),
    "ptc_context_set_vpmap": (C.c_int32, [C.c_void_p,
                                          C.POINTER(C.c_int32),
                                          C.c_int32]),
    "ptc_sched_victim_order": (C.c_int32, [C.c_void_p, C.c_int32,
                                           C.POINTER(C.c_int32),
                                           C.c_int32]),
    "ptc_dc_data_of": (C.c_void_p, [C.c_void_p, C.c_int32,
                                    C.POINTER(C.c_int64), C.c_int32]),
    "ptc_dc_rank_of": (C.c_int32, [C.c_void_p, C.c_int32,
                                   C.POINTER(C.c_int64), C.c_int32]),
    "ptc_register_linear_collection": (C.c_int32, [C.c_void_p, C.c_uint32,
                                                   C.c_uint32, C.c_void_p,
                                                   C.c_int64, C.c_int64]),
    "ptc_register_arena": (C.c_int32, [C.c_void_p, C.c_int64]),
    "ptc_register_datatype": (C.c_int32, [C.c_void_p, C.c_int64, C.c_int64,
                                          C.c_int64]),
    "ptc_register_datatype_indexed": (C.c_int32, [C.c_void_p,
                                                  C.POINTER(C.c_int64),
                                                  C.POINTER(C.c_int64),
                                                  C.c_int32]),
    "ptc_register_datatype_cast": (C.c_int32, [C.c_void_p, C.c_int32,
                                               C.c_int32, C.c_int64]),
    "ptc_ctx_reshape_stats": (None, [C.c_void_p, C.POINTER(C.c_int64),
                                     C.POINTER(C.c_int64)]),
    "ptc_tp_new": (C.c_void_p, [C.c_void_p, C.c_int32, C.POINTER(C.c_int64)]),
    "ptc_tp_destroy": (None, [C.c_void_p]),
    "ptc_tp_add_class": (C.c_int32, [C.c_void_p, C.c_char_p,
                                     C.POINTER(C.c_int64), C.c_int64]),
    "ptc_context_add_taskpool": (C.c_int32, [C.c_void_p, C.c_void_p]),
    "ptc_tp_wait": (C.c_int32, [C.c_void_p]),
    "ptc_tp_nb_tasks": (C.c_int64, [C.c_void_p]),
    "ptc_tp_addto_nb_tasks": (C.c_int64, [C.c_void_p, C.c_int64]),
    "ptc_tp_nb_total_tasks": (C.c_int64, [C.c_void_p]),
    "ptc_tp_nb_errors": (C.c_int64, [C.c_void_p]),
    "ptc_tp_dense_classes": (C.c_int32, [C.c_void_p]),
    "ptc_task_fail": (None, [C.c_void_p, C.c_void_p]),
    "ptc_tp_set_open": (None, [C.c_void_p, C.c_int32]),
    "ptc_tp_drain": (C.c_int32, [C.c_void_p]),
    "ptc_tp_set_on_complete": (None, [C.c_void_p, TP_COMPLETE_CB_T,
                                      C.c_void_p]),
    "ptc_set_pins_cb": (None, [C.c_void_p, PINS_CB_T, C.c_void_p,
                               C.c_uint64]),
    "ptc_tp_global": (C.c_int64, [C.c_void_p, C.c_int32]),
    "ptc_data_new": (C.c_void_p, [C.c_int64, C.c_void_p, C.c_int64]),
    "ptc_data_destroy": (None, [C.c_void_p]),
    "ptc_data_host_copy": (C.c_void_p, [C.c_void_p]),
    "ptc_copy_ptr": (C.c_void_p, [C.c_void_p]),
    "ptc_copy_size": (C.c_int64, [C.c_void_p]),
    "ptc_copy_handle": (C.c_int64, [C.c_void_p]),
    "ptc_copy_set_handle": (None, [C.c_void_p, C.c_int64]),
    "ptc_copy_version": (C.c_int32, [C.c_void_p]),
    "ptc_copy_is_persistent": (C.c_int32, [C.c_void_p]),
    "ptc_set_copy_release_cb": (None, [C.c_void_p, COPY_RELEASE_CB_T,
                                       C.c_void_p]),
    "ptc_set_copy_sync_cb": (None, [C.c_void_p, COPY_SYNC_CB_T,
                                    C.c_void_p]),
    "ptc_set_copy_invalidate_cb": (None, [C.c_void_p, COPY_INVALIDATE_CB_T,
                                          C.c_void_p]),
    "ptc_set_dataplane": (None, [C.c_void_p, DP_REGISTER_CB_T, DP_SERVE_CB_T,
                                 DP_SERVE_DONE_CB_T, DP_DELIVER_CB_T,
                                 DP_BOUND_CB_T, C.c_void_p]),
    "ptc_set_dp_can_pull": (None, [C.c_void_p, C.c_int32]),
    "ptc_set_dp_stream": (None, [C.c_void_p, DP_STREAM_CB_T]),
    "ptc_dp_serve_progress": (C.c_int32, [C.c_void_p, C.c_uint64,
                                          C.c_void_p, C.c_uint64,
                                          C.c_uint64]),
    "ptc_task_local": (C.c_int64, [C.c_void_p, C.c_int32]),
    "ptc_task_class": (C.c_int32, [C.c_void_p]),
    "ptc_task_priority": (C.c_int32, [C.c_void_p]),
    "ptc_task_data_ptr": (C.c_void_p, [C.c_void_p, C.c_int32]),
    "ptc_task_copy": (C.c_void_p, [C.c_void_p, C.c_int32]),
    "ptc_task_taskpool": (C.c_void_p, [C.c_void_p]),
    "ptc_device_queue_new": (C.c_int32, [C.c_void_p]),
    "ptc_device_queue_set_weight": (None, [C.c_void_p, C.c_int32, C.c_double]),
    "ptc_device_queue_depth": (C.c_int64, [C.c_void_p, C.c_int32]),
    "ptc_device_pop": (C.c_void_p, [C.c_void_p, C.c_int32, C.c_int32]),
    "ptc_peek_ready": (C.c_int64, [C.c_void_p, C.c_int32,
                                   C.POINTER(C.c_int64), C.c_int64,
                                   C.c_int32]),
    "ptc_peek_ready_front": (C.c_int64, [C.c_void_p, C.c_int32,
                                         C.POINTER(C.c_int64),
                                         C.c_int64]),
    "ptc_copy_unpin": (None, [C.c_void_p, C.c_void_p]),
    "ptc_device_set_data_owner": (None, [C.c_void_p, C.c_int64, C.c_int32,
                                         C.c_int32]),
    "ptc_device_clear_data_owner": (None, [C.c_void_p, C.c_int64,
                                           C.c_int32]),
    "ptc_device_get_data_owner": (C.c_int32, [C.c_void_p, C.c_int64,
                                              C.POINTER(C.c_int32)]),
    "ptc_device_set_affinity_skew": (None, [C.c_void_p, C.c_double]),
    "ptc_task_complete": (None, [C.c_void_p, C.c_void_p]),
    "ptc_dtile_new": (C.c_void_p, [C.c_void_p, C.c_void_p]),
    "ptc_dtile_destroy": (None, [C.c_void_p, C.c_void_p]),
    "ptc_dtask_begin": (C.c_void_p, [C.c_void_p, C.c_int32, C.c_int64,
                                     C.c_int32]),
    "ptc_dtask_arg": (C.c_int32, [C.c_void_p, C.c_void_p, C.c_int32]),
    "ptc_dtask_submit": (C.c_int32, [C.c_void_p, C.c_void_p, C.c_int64]),
    "ptc_dtask_insert_batch": (C.c_int64, [C.c_void_p, C.c_void_p,
                                           C.POINTER(C.c_int64), C.c_int64,
                                           C.c_int64]),
    "ptc_dtask_nb_flows": (C.c_int32, [C.c_void_p]),
    "ptc_task_set_tag": (None, [C.c_void_p, C.c_int64]),
    "ptc_task_get_tag": (C.c_int64, [C.c_void_p]),
    "ptc_profile_enable": (None, [C.c_void_p, C.c_int32]),
    "ptc_profile_take": (C.c_int64, [C.c_void_p, C.POINTER(C.c_int64), C.c_int64]),
    "ptc_profile_level": (C.c_int32, [C.c_void_p]),
    "ptc_profile_set_ring": (None, [C.c_void_p, C.c_int64]),
    "ptc_profile_ring": (C.c_int64, [C.c_void_p]),
    "ptc_profile_dropped": (C.c_int64, [C.c_void_p]),
    "ptc_flight_dump": (C.c_int32, [C.c_void_p, C.c_char_p]),
    "ptc_flight_set_dump_path": (None, [C.c_void_p, C.c_char_p]),
    "ptc_crash_arm": (C.c_int32, [C.c_void_p, C.c_char_p]),
    "ptc_crash_update_meta": (None, [C.c_void_p]),
    "ptc_crash_disarm": (None, [C.c_void_p]),
    "ptc_crash_dump_now": (C.c_int32, [C.c_void_p]),
    "ptc_worker_stats": (C.c_int64, [C.c_void_p, C.POINTER(C.c_int64), C.c_int64]),
    "ptc_worker_steals": (C.c_int64, [C.c_void_p, C.POINTER(C.c_int64), C.c_int64]),
    "ptc_prof_event": (None, [C.c_void_p, C.c_int64, C.c_int64, C.c_int64,
                              C.c_int64, C.c_int64, C.c_int64]),
    "ptc_coll_stats": (None, [C.c_void_p, C.POINTER(C.c_int64)]),
    "ptc_metrics_enable": (None, [C.c_void_p, C.c_int32]),
    "ptc_metrics_enabled": (C.c_int32, [C.c_void_p]),
    "ptc_metrics_set_release_sample": (None, [C.c_void_p, C.c_int32]),
    "ptc_metrics_record": (None, [C.c_void_p, C.c_int32, C.c_int32,
                                  C.c_int64]),
    "ptc_metrics_intern": (C.c_int32, [C.c_void_p, C.c_char_p]),
    "ptc_metrics_nclasses": (C.c_int32, [C.c_void_p]),
    "ptc_metrics_class_name": (C.c_int32, [C.c_void_p, C.c_int32,
                                           C.c_char_p, C.c_int32]),
    "ptc_metrics_layout": (None, [C.POINTER(C.c_int64)]),
    "ptc_metrics_snapshot": (C.c_int64, [C.c_void_p, C.POINTER(C.c_int64),
                                         C.c_int64, C.c_int32]),
    "ptc_metrics_inflight": (C.c_int64, [C.c_void_p, C.POINTER(C.c_int64),
                                         C.c_int64]),
    "ptc_metrics_peer_rtts": (C.c_int32, [C.c_void_p, C.POINTER(C.c_int64),
                                          C.c_int32]),
    "ptc_context_get_scheduler": (C.c_char_p, [C.c_void_p]),
    "ptc_comm_init": (C.c_int32, [C.c_void_p, C.c_int32]),
    "ptc_comm_fence": (C.c_int32, [C.c_void_p]),
    "ptc_comm_quiesce": (C.c_int32, [C.c_void_p, C.c_void_p]),
    "ptc_comm_set_topology": (None, [C.c_void_p, C.c_int32]),
    "ptc_comm_fini": (C.c_int32, [C.c_void_p]),
    "ptc_comm_enabled": (C.c_int32, [C.c_void_p]),
    "ptc_comm_stats": (None, [C.c_void_p, C.POINTER(C.c_int64)]),
    "ptc_comm_rdv_stats": (None, [C.c_void_p, C.POINTER(C.c_int64)]),
    "ptc_comm_tuning": (None, [C.c_void_p, C.POINTER(C.c_int64)]),
    "ptc_comm_stream_stats": (None, [C.c_void_p, C.POINTER(C.c_int64)]),
    "ptc_comm_clock_stats": (None, [C.c_void_p, C.POINTER(C.c_int64)]),
    "ptc_comm_clock_sync": (C.c_int64, [C.c_void_p]),
    "ptc_comm_share_blob": (C.c_int32, [C.c_void_p, C.c_char_p, C.c_int64]),
    "ptc_comm_peer_blob": (C.c_int64, [C.c_void_p, C.c_int32, C.c_void_p,
                                       C.c_int64]),
    "ptc_comm_peers_lost": (C.c_int32, [C.c_void_p, C.POINTER(C.c_int64),
                                        C.c_int32]),
    "ptc_comm_peer_stats": (C.c_int32, [C.c_void_p, C.POINTER(C.c_int64),
                                        C.c_int32]),
    "ptc_comm_probe_rtts": (C.c_int32, [C.c_void_p]),
    "ptc_context_set_rank_map": (None, [C.c_void_p,
                                        C.POINTER(C.c_int32), C.c_int32]),
    "ptc_tp_id": (C.c_int32, [C.c_void_p]),
    "ptc_dtile_set_owner": (None, [C.c_void_p, C.c_uint32]),
    "ptc_dtask_set_rank": (None, [C.c_void_p, C.c_int32]),
}

for _name, (_res, _args) in _sigs.items():
    fn = getattr(lib, _name)
    fn.restype = _res
    fn.argtypes = _args
