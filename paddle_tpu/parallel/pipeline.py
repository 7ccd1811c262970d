"""Explicit GPipe schedule over a "pp" mesh axis via shard_map + ppermute.

Reference: PipelineTrainer/SectionWorker (framework/trainer.h:115,
section_worker.cc:85,141) stream Scopes between per-device section threads.
TPU-native: the schedule is *compiled* -- each device holds one stage's
parameters (the stage axis of a stacked pytree is sharded over "pp"),
activations flow to the next device with lax.ppermute, and the classic GPipe
skew fills/drains the pipeline over M + S - 1 ticks inside one lax.scan.
GSPMD cannot infer temporal schedules like this, hence shard_map.

Requires homogeneous stages (activation structure preserved), the natural
shape for transformer/BERT layer stacks. For the general heterogeneous-program
microbatch path use fluid.optimizer.PipelineOptimizer (a program rewrite);
PipelineOptimizer(schedule="temporal") lowers device_guard-annotated programs
onto this schedule through ops/pipeline_op.py.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

# incremented each time the GPipe schedule is traced -- the dryrun's proof
# that pp actually lowered to the temporal schedule (same pattern as
# ring_attention.TRACE_COUNT)
TRACE_COUNT = 0


def pipeline_spmd(stage_fn: Callable, stacked_params: Any, x, mesh,
                  axis: str = "pp", consts: Any = None,
                  mb_axis: Optional[str] = None):
    """Run a homogeneous S-stage pipeline over microbatches.

    stage_fn(params_one_stage, x_mb) -> y_mb, where x_mb/y_mb are pytrees of
        identical structure and shapes (per-example side inputs -- attention
        mask slices -- ride the pytree through the pipe untouched); called as
        stage_fn(params, x_mb, consts) when ``consts`` is given.
    stacked_params: pytree whose leaves have a leading stage axis S
        (sharded over ``axis`` on ``mesh``).
    x: pytree of [M, mb, ...] microbatched arrays.
    consts: optional pytree of stage-invariant values replicated everywhere.
    mb_axis: optional mesh axis to shard the microbatch (dim 1) over -- the
        data-parallel axis when pipelining composes with dp.
    Returns the pytree of [M, mb, ...] outputs after all S stages.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    global TRACE_COUNT
    TRACE_COUNT += 1
    tree = jax.tree_util
    if axis not in mesh.shape:
        # same failure class the static analyzer flags as PT040: off-mesh
        # the schedule's ppermute/psum would silently no-op or die mid-trace
        raise ValueError(
            f"pipeline axis {axis!r} is not an axis of the mesh "
            f"{dict(mesh.shape)}; add it to the DistributedStrategy "
            f"mesh_shape (the verifier flags this statically as PT040)")
    if mb_axis is not None and mb_axis not in mesh.shape:
        raise ValueError(
            f"microbatch axis {mb_axis!r} is not an axis of the mesh "
            f"{dict(mesh.shape)}")
    S = mesh.shape[axis]
    leaves = tree.tree_leaves(x)
    M = leaves[0].shape[0]

    # Schedule-shape telemetry (trace-time: the schedule itself is compiled,
    # so per-tick runtime counters would just be traced constants). Each
    # trace contributes its S*(M+S-1) stage spans -- the SectionWorker span
    # count a host-side profiler would have seen.
    from ..observability.metrics import REGISTRY as _OBS
    _OBS.counter("pipeline_traces_total",
                 "GPipe schedule traces by pipe axis", axis=axis).inc()
    _OBS.counter("pipeline_stage_spans_total",
                 "stage executions scheduled (S per tick, M+S-1 ticks)",
                 axis=axis).inc(S * (M + S - 1))
    _OBS.gauge("pipeline_schedule_ticks",
               "ticks (fill+steady+drain) of the last traced schedule",
               axis=axis).set(M + S - 1)
    _OBS.gauge("pipeline_bubble_fraction",
               "(S-1)/(M+S-1), the GPipe fill/drain overhead of the last "
               "traced schedule", axis=axis).set((S - 1) / (M + S - 1))
    have_consts = consts is not None
    if consts is None:
        consts = ()

    def per_device(params, xs, cs):
        # params leaves: [1, ...] local stage slice; xs leaves: [M, mb, ...]
        idx = jax.lax.axis_index(axis)
        local = tree.tree_map(lambda p: p[0], params)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def run_stage(inp):
            if have_consts:
                return stage_fn(local, inp, cs)
            return stage_fn(local, inp)

        state0 = tree.tree_map(lambda b: jnp.zeros_like(b[0]), xs)
        outbuf0 = tree.tree_map(jnp.zeros_like, xs)

        def tick(carry, t):
            state, outbuf = carry
            # stage 0 consumes microbatch t while t < M; later stages consume
            # what arrived from the previous device
            feed_idx = jnp.clip(t, 0, M - 1)
            inp = tree.tree_map(
                lambda b, st: jnp.where(idx == 0, b[feed_idx], st), xs, state)
            y = run_stage(inp)
            # last stage emits microbatch t-(S-1) once the pipe is full
            out_t = t - (S - 1)
            emit = jnp.logical_and(idx == S - 1, out_t >= 0)
            outbuf = jax.lax.cond(
                emit,
                lambda ob: tree.tree_map(
                    lambda b, yv: jax.lax.dynamic_update_index_in_dim(
                        b, yv, jnp.maximum(out_t, 0), 0), ob, y),
                lambda ob: ob, outbuf)
            state = tree.tree_map(
                lambda yv: jax.lax.ppermute(yv, axis, perm), y)
            return (state, outbuf), None

        (_, outbuf), _ = jax.lax.scan(tick, (state0, outbuf0),
                                      jnp.arange(M + S - 1))
        # replicate the last stage's buffer to every device along the pipe
        return tree.tree_map(
            lambda b: jax.lax.psum(b * (idx == S - 1).astype(b.dtype), axis),
            outbuf)

    pspec = tree.tree_map(lambda _: P(axis), stacked_params)
    xspec = tree.tree_map(
        lambda _: P(None, mb_axis) if mb_axis else P(), x)
    cspec = tree.tree_map(lambda _: P(), consts) if have_consts else P()
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(pspec, xspec, cspec), out_specs=xspec,
                   check_vma=False)
    # one flight-recorder span per schedule trace+dispatch: the compiled
    # schedule has no per-tick host visibility, so the span carries the
    # shape (S stages, M microbatches, M+S-1 ticks) instead
    from ..observability import timeline as _timeline
    with _timeline.phase("pipeline_schedule", cat="pipeline", axis=axis,
                         stages=S, microbatches=M, ticks=M + S - 1):
        return fn(stacked_params, x, consts)
