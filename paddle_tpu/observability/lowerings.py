"""What the op lowerings of a compiled program chose, as labelled metrics
published once per compile.

A lowering reports while the executor traces it (``LowerCtx.report(family,
amount, **labels)``, core/registry.py): the metric family, its labels by
name, an amount. The report is kept on the Program under (family, the op's
salt, the labels), so the forward a grad op lowers again under ``jax.vjp``
lands on its forward op's entry and counts once. The executor hands the
reports of the compile it just made to ``publish``. ``FAMILIES`` is the one
place a family is declared and documented; a lowering that reports another
is refused at trace time. A new kernel family costs its op file and one row
here.
"""
from __future__ import annotations

import time
from collections import Counter
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry

#: the amounts of a program's ops are added into a counter (``count``: ops
#: compiled so far under that label set) or summed and set as a gauge
#: (``gauge``: what the newest compile of the program holds)
COUNT, GAUGE = "count", "gauge"

#: family -> (COUNT or GAUGE, the labels a lowering gives, help). ``publish``
#: adds the label ``program``. benchmark/layer_metrics/*.json read these by
#: name: a family's name and labels are a contract with them.
FAMILIES = {
    # ops/pallas_attention.py. impl: pallas / xla / ring / ulysses; the
    # kernels' Q block and K tile (0 where no kernel ran; block_k = s is one
    # tile a row); kv_heads fewer than heads under grouped-query attention;
    # window: the sliding window the lowering applies (0: none, also for one
    # no shorter than s)
    # value_dim: the value head's width where it is not head_dim (latent
    # attention with v narrower than q / k), else 0
    # mesh (here and on the families below that have it): island where the
    # kernels ran on each device's own rows inside a shard_map island under a
    # GSPMD mesh (LowerCtx.island), else none
    "attention_lowering_total": (
        COUNT, ("impl", "s", "block_q", "block_k", "kv_heads", "window",
                "heads", "head_dim", "value_dim", "mesh"),
        "fused_attention ops compiled, by the lowering each took"),
    # amount: the K tiles the forward kernel passes over for one (batch,
    # head) (state=visited) and those a causal op leaves out because they lie
    # wholly above the diagonal or behind the window (skipped), from static
    # shapes (k_tiles); kernels only
    "attention_k_tiles_total": (
        COUNT, ("state", "window"),
        "K tiles a (batch, head) of the compiled flash-attention "
        "ops' forward kernels"),
    # stats: saved (the backward kernel read the forward op's Lse; no forward
    # lowered in the grad op -- or, for an op in a sub-block differentiated
    # as a whole, a scan op's or a remat_segment's, the statistic the
    # kernels' own custom_vjp kept: reported by the forward op, once an op
    # however often the sub-block is traced) / recomputed (the kernels on a desc without
    # Lse: the generic grad lowers the forward kernel again for them) /
    # generic (jax.vjp over another lowering)
    "attention_backward_total": (
        COUNT, ("stats",),
        "fused_attention_grad ops compiled, by where the softmax "
        "statistics came from"),
    # ops/math_ops.py. form: written (the closed form written once over the
    # logits' own buffer) / fused (the same expression left to XLA: logits
    # under WRITTEN_GRAD_MIN_BYTES, a mesh) / generic (jax.vjp over the
    # forward: float32 logits, soft labels, another axis, an ignore_index, a
    # desc without Lse)
    "loss_backward_total": (
        COUNT, ("form",),
        "softmax_with_cross_entropy_grad ops compiled, by the form "
        "of the logits' gradient"),
    # ops/decoder_ops.py. direction: forward / backward; form: kernel (the
    # one-pass kernel of ops/pallas_rope.py; the backward is the same pass on
    # the cotangent and lowers no forward) / composed (the rotation left to
    # XLA) / generic (a grad op without a cotangent); impl: pallas where the
    # form is the kernel, else composed (the label the other kernel families
    # say it with)
    "rotary_lowering_total": (
        COUNT, ("direction", "form", "mesh", "impl"),
        "rotary_embedding ops and grad ops compiled, by form"),
    # core/registry.py:bernoulli_mask. draw: shard (each of the data axis'
    # `shards` devices drew its own part of the batch in a shard_map island)
    # / global (one draw at the whole shape, shards=1); the flash kernels'
    # in-kernel dropout is not counted
    "mask_draw_total": (
        COUNT, ("draw", "shards"),
        "ops that drew a dropout mask, by how the mask was drawn"),
    # ops/tensor_ops.py. layout: shard (the gathered rows left the op's
    # island laid over the data axis) / global (a plain take)
    "gather_layout_total": (
        COUNT, ("layout", "shards"),
        "gather ops along axis 0, by the layout of their rows"),
    # impl: pallas (ops/pallas_ssd.py) / composed (the chunked form in
    # jax.numpy)
    "ssd_lowering_total": (
        COUNT, ("impl", "chunk", "heads", "state"),
        "ssd_scan ops compiled, by the lowering each took"),
    # form: gated (LFM2's) / plain (a Mamba mixer's)
    "short_conv_lowering_total": (
        COUNT, ("impl", "form", "activation", "taps"),
        "short_conv ops compiled, by form and the lowering each took"),
    # operands: packed (the kernels read q, k and v in place in the one array
    # the op was given) / split (three operands, given or cut out of it);
    # decay: head (one scalar a token and value head: Gated DeltaNet) /
    # channel (a vector over the key channels: Kimi Delta Attention); a
    # series without the label is a parent's, and is read as head;
    # step_heads: the key heads a grid step of the kernels takes, each with
    # all its value heads (pallas_delta.step_heads of the op's head counts;
    # 1 for the composed form and a series without the label)
    "delta_lowering_total": (
        COUNT, ("impl", "chunk", "heads", "key_dim", "value_dim",
                "operands", "decay", "step_heads"),
        "gated_delta_rule ops compiled, by the lowering and the "
        "operand form each took"),
    # ops/decoder_ops.py:rms_norm given a Gate, and its grad op (an rms_norm
    # without one reports nothing). impl: pallas (the one-pass kernels of
    # ops/pallas_norm.py; the backward reads X, Gate and the cotangent and
    # lowers no forward) / composed (the same closed forms in jax.numpy);
    # head_dim: the normed axis; activation: the gate's, silu / sigmoid
    "rms_norm_gated_lowering_total": (
        COUNT, ("impl", "direction", "head_dim", "activation"),
        "gated rms_norm ops and grad ops compiled, by the lowering each "
        "took"),
    # ops/decoder_ops.py: an expert layer's token sums, moe_combine's
    # forward (op=combine) and moe_dispatch's registered grad lowering
    # (dispatch_grad). impl: pallas (the one kernel of
    # ops/pallas_moe_rows.py, which reads the held groups' rows and no
    # other) / composed (the scatter-add under a row budget, the gathered
    # reduce without); bound: held (the layer holds a part of its experts:
    # the rows behind theirs are padding, not read) / all
    "moe_rows_lowering_total": (
        COUNT, ("impl", "op", "bound", "mesh"),
        "token sums of the expert layers compiled, by the lowering each "
        "took"),
    # ops/decoder_ops.py:moe_expert_matmul. impl: pallas (megablox's grouped
    # kernels) / composed (ragged_dot); mesh: island where the op ran on the
    # device's own experts and the rows it received (attr expert_axis)
    "moe_expert_matmul_lowering_total": (
        COUNT, ("impl", "mesh"),
        "grouped products of the expert layers compiled, by the lowering "
        "each took"),
    # ops/decoder_ops.py: a crossing of an expert layer's exchange (attr
    # expert_axis: the rows to the devices that hold their experts, or the
    # results back), forward or in a grad op. axis: the mesh axis the
    # experts are split over; impl: ragged (jax.lax.ragged_all_to_all) /
    # padded (all_to_all of a fixed part a pair of devices) / none (one
    # device on that axis: nothing crosses); crossing: dispatch / combine /
    # dispatch_grad / combine_grad, the four of a layer and step
    "moe_exchange_lowering_total": (
        COUNT, ("axis", "impl", "crossing"),
        "crossings of the expert layers' exchange compiled, by the wire "
        "each took"),
    # ops/decoder_ops.py:_rows_kernel, once an op that crosses the exchange:
    # how the rows it received change order (source by source <-> expert by
    # expert, RowExchange.by_expert / by_source). impl: pallas (the kernel
    # of ops/pallas_exchange_rows.py over the plan's segment tables) /
    # composed (an index a row and a gather); mesh: island where the kernel
    # ran, inside the exchange's own shard_map; way: out (rows to their
    # experts: moe_dispatch, moe_combine's backward) / back
    "moe_exchange_rows_lowering_total": (
        COUNT, ("impl", "mesh", "way"),
        "crossings of the expert layers' exchange compiled, by how the "
        "received rows change order"),
    # amount: the rows a device sends away at one crossing of an expert
    # layer's exchange IF THE ROUTER IS EVEN -- its assignments times
    # (n - 1) / n over n devices: a constant of the shapes, set at the
    # compile, which does not move with what a run's router sends --, all
    # layers and the step's crossings of that direction (out: rows to their
    # experts, in moe_dispatch and in moe_combine's backward; back: the
    # results, in moe_combine and in moe_dispatch's backward). What a run's
    # router sent is moe_dispatch's SendCount, which no job fetches
    # (tools/mellum2_probe.py load does)
    "moe_exchange_even_rows": (
        GAUGE, ("direction",),
        "rows a device would send away a step through the expert layers' "
        "exchange under an even router (from shapes)"),
    # ops/decoder_ops.py:latent_qkv, the forward op. rotated: 1 where q_r and
    # the one key head k_r are rotated, 0 under rotate=False (positions
    # left to the linear layers); head_dim: the q / k head's width as
    # written (zero columns behind nope + rope included); value_dim: v's
    # frequencies: default (theta^(-2i/d)) or yarn (yarn_inv_freq's blend)
    "latent_qkv_lowering_total": (
        COUNT, ("rotated", "heads", "head_dim", "value_dim", "frequencies"),
        "latent_qkv ops compiled, by rotation, head widths and the form of "
        "the rotation's frequencies"),
    # ops/decoder_ops.py:hyper_connection_pre / _post and their registered
    # grads, each lowering once. part: pre (the coefficients and the read) /
    # post (the write); streams: the residual streams a token; iters: the
    # Sinkhorn-Knopp iterations; product: how a pre lowering multiplies the
    # state by Phi, forward and in its grad: pieces (a bfloat16 state: the
    # float32 operand's bfloat16 pieces side by side, one MXU pass a
    # product) / highest (any other state: float32 at precision highest),
    # none on post, which has no product. All composed jax.numpy: no impl
    # label
    "hyper_connection_lowering_total": (
        COUNT, ("part", "direction", "streams", "iters", "product"),
        "hyper-connection ops and grad ops compiled, by side and direction"),
    # amount: a moe_dispatch op's row budget (attr rows), its assignments
    # without one; the sort's output, the grouped products, swiglu and the
    # combine are sized by it
    "moe_row_budget": (
        GAUGE, (),
        "sorted rows the expert layers keep a step, all layers: the "
        "assignments without a row budget, the budgets with one"),
    # ops/control_flow.py:scan_op (layers.Scan: recurrences over sequences, a
    # looped model's stack under ``steps``). amount: how often the op's lowering traced its
    # sub-block: 1, it is one lax.scan whatever the trip count; scan_grad
    # traces none. role: the program's, given by ``publish``
    "loop_stack_lowerings_total": (
        COUNT, ("role",),
        "times the scan ops' lowerings traced their sub-blocks"),
    # amount: the bytes of the leaves of the pullback a scan op left for its
    # grad op (what the forward keeps for the backward: JAX's residuals of
    # the body as lowered, stacked over the iterations), without the op's
    # own inputs (the weights are kept by whoever holds them)
    "loop_kept_bytes": (
        GAUGE, ("role",),
        "bytes the scan ops keep from forward to backward"),
    # core/executor.py:trace_block, every op: amount = the seconds its
    # lowering call took while the compile traced it (self time: what a
    # control-flow op's sub-block took is its ops'; a grad op keeps the
    # forward it lowers again under jax.vjp). family: kernel (the lowering
    # asked pallas_mode.lowers_kernels, whatever the answer) / xla; role:
    # the program's (startup / eval / train), given by ``publish``
    "lowering_seconds_total": (
        COUNT, ("role", "op_type", "family"),
        "seconds the compiles spent in each op type's lowering call"),
}

_SECONDS = "lowering_seconds_total"
#: key of the seconds ``lowering_ended`` has booked since the notes were
#: emptied, nested calls included: how an enclosing call knows its self time
_BOOKED = ("", 0, ())


#: labels a family gained after its first readers were written, with what a
#: report without them means: such a report is kept under the default
LATER_LABELS = {
    "delta_lowering_total": {"decay": "head", "step_heads": "1"},
    "rms_norm_gated_lowering_total": {"activation": "silu"},
    "attention_lowering_total": {"value_dim": 0, "mesh": "none"},
    "rotary_lowering_total": {"mesh": "none", "impl": "composed"},
    "moe_rows_lowering_total": {"mesh": "none"},
    "latent_qkv_lowering_total": {"frequencies": "default"},
}


def note(notes: dict, salt: int, family: str, amount, labels: dict) -> None:
    """Keep one lowering's report in ``notes`` (``LowerCtx.report``); a
    family or a set of labels that ``FAMILIES`` does not declare is
    refused."""
    if family not in FAMILIES:
        raise KeyError(
            f"lowering metric {family!r} is not declared in "
            f"observability/lowerings.py:FAMILIES ({', '.join(FAMILIES)})")
    names = FAMILIES[family][1]
    labels = {**LATER_LABELS.get(family, {}), **labels}
    if "role" in names:         # ``publish``'s to give: a lowering need not
        labels.setdefault("role", "")
    if set(labels) != set(names):
        raise KeyError(f"lowering metric {family!r} takes the labels "
                       f"{names}, not {tuple(labels)}")
    notes[family, salt, tuple(sorted(labels.items()))] = amount


def lowering_began(notes: dict) -> tuple:
    """What ``lowering_ended`` wants of the moment before a lowering call:
    the clock, and the seconds booked so far."""
    return time.perf_counter(), notes.get(_BOOKED, 0.0)


def lowering_ended(notes: dict, began: tuple, op_type: str,
                   kernel: bool) -> None:
    """Add the seconds since ``began`` to the op type's, less what the calls
    nested in this one booked meanwhile: self time."""
    t0, booked = began
    secs = time.perf_counter() - t0
    nested = notes.get(_BOOKED, 0.0) - booked
    key = (_SECONDS, 0, (("family", "kernel" if kernel else "xla"),
                         ("op_type", op_type)))
    notes[key] = notes.get(key, 0.0) + max(secs - nested, 0.0)
    notes[_BOOKED] = booked + secs


def publish(notes: dict, program: str,
            registry: Optional[MetricsRegistry] = None,
            role: str = "") -> None:
    """Add the reports of one compile to the registry and empty them.
    ``notes`` maps ``(family, op salt, ((label, value), ...))`` to an amount
    (a Program's ``_lowering_notes``); label values go through ``str``.
    Nothing is added for a family no op of the program reported. ``role``
    is the label of that name, for the families that declare it."""
    registry = registry or REGISTRY
    totals = Counter()
    notes.pop(_BOOKED, None)
    for (family, _, labels), amount in notes.items():
        totals[family, labels] += amount
    notes.clear()
    for (family, labels), amount in totals.items():
        kind, names, help = FAMILIES[family]
        labels = {name: str(value) for name, value in labels}
        if "role" in names:
            labels["role"] = role
        if kind == COUNT:
            registry.counter(family, help, program=program,
                             **labels).inc(amount)
        else:
            registry.gauge(family, help, program=program,
                           **labels).set(float(amount))
