"""Static analysis over PTG/DTD graphs (the parsec_ptgpp sanity-check
role, SURVEY §L1: the reference compiler rejects dangling flows and
malformed dep targets before any task runs).

`flowgraph` extracts a symbolic flow graph from compiled task-class
tables — one extractor shared by the verifier, the resource planner
and tools/jdf2dot.py — `verify` runs the V001–V009 rule engine over
it, using affine/interval reasoning where index expressions allow and
bounded concrete enumeration of the execution space as the exact
fallback, and `plan` (ptc-plan) computes the quantitative bounds:
per-rank peak tile residency, wave decomposition, comm volume and
makespan lower bounds.  `dtdlint` is the insertion-time linter for the
dynamic (DTD) path.
"""
from .flowgraph import (ConcreteGraph, FlowGraph, collection_tile_bytes,
                        extract_flowgraph, flowgraph_to_dot)
from .verify import (RULES, Finding, Report, VerifyError, verify_graph,
                     verify_taskpool)
from .plan import (CostModel, Plan, PlanCheckError, certify_waves,
                   compare_critpath, plan_graph, plan_taskpool)
from .tune import (ScheduleSimulator, TuneStore, apply_knobs, autotune,
                   graph_signature, hold_knobs, host_fingerprint)
from .control import Controller, SimClock
from .dtdlint import DtdLintError, DtdLinter

__all__ = [
    "FlowGraph", "ConcreteGraph", "extract_flowgraph", "flowgraph_to_dot",
    "collection_tile_bytes",
    "Finding", "Report", "RULES", "VerifyError", "verify_graph",
    "verify_taskpool",
    "CostModel", "Plan", "PlanCheckError", "plan_graph", "plan_taskpool",
    "compare_critpath", "certify_waves",
    "ScheduleSimulator", "TuneStore", "apply_knobs", "hold_knobs",
    "autotune", "graph_signature", "host_fingerprint",
    "Controller", "SimClock",
    "DtdLinter", "DtdLintError",
]
