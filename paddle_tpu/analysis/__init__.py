"""Static program analysis: verify a Program before the first XLA compile.

The reference framework validated programs only while interpreting them
op-by-op (operator.cc enforce macros, executor.cc:94 run loop) -- a
malformed program died mid-run with a C++ stack. Here the whole static
Program is linted *ahead of time*, the way tensor-IR compilers legalize
before codegen:

    import paddle_tpu.analysis as analysis
    diags = analysis.verify(main_program, fetch_names=["loss"])
    errors = [d for d in diags if d.severity == "error"]

Findings carry stable ``PT0xx`` codes (diagnostics.CODES is the table),
severities (error/warn/info), and the op's user-code creation stack
(Operator._creation_stack) so every finding points at the model line that
built the offending op.

Three doors in:

- library: ``analysis.verify(program) -> [Diagnostic]`` (this module);
- CLI: ``python -m paddle_tpu.analysis program.json --format json`` /
  ``tools/lint_program.py`` over a serialized Program;
- executor gate: ``PADDLE_TPU_VALIDATE=off|warn|raise`` verifies once per
  compile-cache miss and journals findings through observability.

Passes (pass_base registry, the ir::Pass analog): ``wellformed``
(undefined/use-before-def vars, unregistered ops, block-graph sanity),
``dataflow`` (dead ops, WAW hazards, fetch reachability), ``typecheck``
(shape/dtype propagation vs declarations), ``recompile`` (compile-cache
churn risks), ``distributed`` (collective/mesh consistency, SPMD deadlock
shapes, sharding legality vs a DistributedStrategy), the opt-in
``memplan`` (static liveness-based peak-memory planner, engaged by
``mem_budget=`` / ``--mem-budget`` or by naming the pass), and the opt-in
``shardplan`` (static auto-sharding planner, engaged by ``auto_shard=True``
/ ``--auto-shard``: PT04x-pruned, cost-priced shard-plan search, PT07x).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from ..framework import Program
from . import dataflow  # noqa: F401  (registers the pass)
from . import distributed  # noqa: F401
from . import layout_churn  # noqa: F401
from . import memplan  # noqa: F401
from . import recompile  # noqa: F401
from . import shardplan  # noqa: F401
from . import typecheck  # noqa: F401
from . import wellformed  # noqa: F401
from .diagnostics import (CODES, Diagnostic, Severity,  # noqa: F401
                          apply_baseline, codes_table, count_by_severity,
                          format_diagnostics, load_baseline,
                          sort_diagnostics, write_baseline)
from .distributed import strategy_from_dict  # noqa: F401
from .memplan import (MemEstimate, estimate_program_memory,  # noqa: F401
                      format_bytes, infer_batch, parse_bytes)
from .pass_base import (AnalysisPass, PassContext,  # noqa: F401
                        default_passes, get_pass, register_pass,
                        registered_passes, run_passes, split_strategy)
from .shardplan import (SearchResult, ShardPlan,  # noqa: F401
                        search_plans)


class VerificationError(RuntimeError):
    """Raised by verify_or_raise / PADDLE_TPU_VALIDATE=raise: the program
    has error-severity findings. ``diagnostics`` holds every finding."""

    def __init__(self, message: str, diagnostics: List[Diagnostic]):
        super().__init__(message)
        self.diagnostics = diagnostics


def verify(program: Program,
           feed_names: Optional[Sequence[str]] = None,
           fetch_names: Optional[Sequence[str]] = None,
           passes: Optional[Sequence[str]] = None,
           strategy=None, mem_budget: Optional[int] = None,
           batch: Optional[int] = None,
           auto_shard: bool = False,
           top_k: Optional[int] = None) -> List[Diagnostic]:
    """Run the analysis pipeline over ``program``; return sorted findings.

    ``feed_names``/``fetch_names`` sharpen the analysis when the run intent
    is known (Executor.run passes both): fetch targets switch on dead-op
    liveness and fetch-reachability, feeds tighten the unread-feed check.
    Without them the checks degrade gracefully (is_data vars are assumed
    feedable, liveness is skipped).

    ``strategy`` (a DistributedStrategy or a CompiledProgram) switches on
    the PT04x distributed checks -- collective/mesh consistency, sharding
    legality, re-gather cost -- and scales the memory planner's byte
    accounting by the sharding divisors. ``mem_budget`` (bytes) adds the
    PT05x static peak-memory planner to the pipeline and errors (PT051)
    when the estimate exceeds it; ``batch`` resolves dynamic (-1) dims for
    that accounting (without it the planner assumes batch 1 and says so,
    PT052).

    ``auto_shard=True`` engages the static auto-sharding planner (PT07x):
    it enumerates PT04x-legal per-tensor shard assignments over the
    strategy's mesh, prices them with the comm wire-byte model and the
    PT05x peak estimate, and reports the chosen plan (PT070), a budget
    infeasibility (PT071), or a near-tie measurement advisory (PT072).
    Requires a ``strategy`` with a concrete ``mesh_shape``; ``top_k``
    bounds the ranked plans kept (default 3).
    """
    if auto_shard:
        ds, _ = split_strategy(strategy)
        if ds is None or not getattr(ds, "mesh_shape", None):
            raise ValueError(
                "auto_shard=True needs a strategy with a concrete "
                "mesh_shape: the planner prices candidates against real "
                "axis sizes (pass DistributedStrategy(mesh_shape="
                "{'dp': ..., 'mp': ...}))")
        passes = list(passes) if passes is not None else default_passes()
        if "shardplan" not in passes:
            passes = passes + ["shardplan"]
    # supplying a budget or a strategy means the caller wants that check's
    # verdict: engage the owning pass even under an explicit --passes
    # subset (a CI gate narrowing passes must not silently lose the PT051
    # OOM check or the PT04x deadlock/sharding checks it asked for)
    if mem_budget is not None:
        passes = list(passes) if passes is not None else default_passes()
        if "memplan" not in passes:
            passes = passes + ["memplan"]
    if strategy is not None and passes is not None \
            and "distributed" not in passes:
        passes = list(passes) + ["distributed"]
    return sort_diagnostics(run_passes(program, passes=passes,
                                       feed_names=feed_names,
                                       fetch_names=fetch_names,
                                       strategy=strategy,
                                       mem_budget=mem_budget, batch=batch,
                                       auto_shard=auto_shard, top_k=top_k))


def verify_or_raise(program: Program,
                    feed_names: Optional[Sequence[str]] = None,
                    fetch_names: Optional[Sequence[str]] = None,
                    passes: Optional[Sequence[str]] = None,
                    strategy=None, mem_budget: Optional[int] = None,
                    batch: Optional[int] = None) -> List[Diagnostic]:
    """verify(), raising VerificationError if any error-severity finding."""
    diags = verify(program, feed_names=feed_names, fetch_names=fetch_names,
                   passes=passes, strategy=strategy, mem_budget=mem_budget,
                   batch=batch)
    errors = [d for d in diags if d.severity == Severity.ERROR]
    if errors:
        raise VerificationError(
            "program verification failed:\n" +
            format_diagnostics(errors, with_stack=True), diags)
    return diags
