"""Observability: paired-event binary traces, trace tables, DOT grapher.

The reference's L7 stack (SURVEY.md §5): per-thread event buffers flushed
to a per-rank binary profile ("dbp", parsec/parsec_binary_profile.h:45)
whose events are paired begin/end keys from a global dictionary
(parsec/profiling.c:580,791), converted offline to pandas trace tables by
the Cython pbt2ptt (tools/profiling/python/pbt2ptt.pyx), plus a DOT DAG
grapher (parsec/parsec_prof_grapher.c:86-135).  This package is the
TPU-native equivalent over the native core's 8-word event stream
(native/runtime_internal.h PROF_WORDS):

  Dictionary     event-key registry with names/colors
  Trace          take/save/load/merge + to_pandas() trace tables +
                 to_perfetto() standard-tool sink (the OTF2-writer analog)
                 — merge applies cross-rank CLOCK SYNC, detects
                 dictionary conflicts and matches send/recv FLOW ids
                 (tracing v2); flows()/wire_latency() expose the
                 per-message pairs; shift() moves it onto a profiler
                 trace's clock (profiler_offset_ns)
  xplane_spans   the program's `ptc.*` spans in a jax.profiler trace (the
                 device manager's loop states, ManagerSpans)
  critpath       critical_path() / lost_time() over the executed DAG
  to_dot         executed-DAG capture from EDGE event pairs
  pins           pluggable instrumentation-module chain at the event
                 points (parsec/mca/pins/pins.h analog), MCA-selected
  Journal        crash-durable per-rank JSONL flight journal + native
                 fatal-signal dump arming (ptc-blackbox)
  FleetView      cross-replica /stats.json federation -> /fleet.json
"""
from .trace import (KEY_EXEC, KEY_RELEASE, KEY_EDGE,
                    KEY_COMM_SEND, KEY_COMM_RECV, KEY_DEVICE, KEY_H2D,
                    KEY_STREAM, KEY_COLL, KEY_SCOPE, KEY_INFLIGHT,
                    KEY_READY_WAIT, KEY_BATCH_WAIT, KEY_DEVICE_LOOP,
                    Dictionary, Trace, take_trace, to_dot, xplane_spans,
                    profiler_offset_ns)
from .critpath import critical_path, lost_time
from .pins import (PinsModule, PinsChain, TaskCounter, TaskProfiler,
                   CommVolume, DeviceActivity, StragglerLog, REGISTRY,
                   enable_pins)
from .metrics import (Hist, MetricsRegistry, MetricsExporter, Watchdog,
                      snapshot_histograms)
from .scope import ScopeRegistry, request_timeline
from .blackbox import Journal, FleetView

__all__ = ["KEY_EXEC", "KEY_RELEASE", "KEY_EDGE",
           "KEY_COMM_SEND", "KEY_COMM_RECV", "KEY_DEVICE", "KEY_H2D",
           "KEY_STREAM", "KEY_COLL", "KEY_SCOPE", "KEY_INFLIGHT",
           "KEY_READY_WAIT", "KEY_BATCH_WAIT", "KEY_DEVICE_LOOP",
           "Dictionary", "Trace",
           "take_trace", "to_dot", "xplane_spans", "profiler_offset_ns",
           "critical_path", "lost_time",
           "PinsModule", "PinsChain", "TaskCounter", "TaskProfiler",
           "CommVolume", "DeviceActivity", "StragglerLog", "REGISTRY",
           "enable_pins",
           "Hist", "MetricsRegistry", "MetricsExporter", "Watchdog",
           "snapshot_histograms",
           "ScopeRegistry", "request_timeline",
           "Journal", "FleetView"]
