"""Static FLOP accounting over a Program + TPU peak-FLOPs table (for MFU).

Analog of the reference's host-side program introspection utilities
(reference: python/paddle/fluid/contrib/memory_usage_calc.py:1,
contrib/op_frequence.py:1 — the reference estimates memory from var shapes; here we
estimate arithmetic cost from op shapes, which on TPU is the number that matters:
MFU = sustained FLOP/s / MXU peak).

Only matmul-class ops are counted (mul/matmul/conv*); elementwise and reduction
FLOPs are <1% on the BASELINE workloads and are ignored, so reported MFU is a
slight *underestimate* — safe direction for a performance claim.
"""
from __future__ import annotations

from typing import Dict, Optional

# bf16 peak FLOP/s per *JAX device* (v2/v3 report per-core devices; v4+ per chip).
_PEAK_BF16 = {
    "TPU v2": 22.5e12,
    "TPU v3": 61.25e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _peak(table: Dict[str, float], what: str,
          device_kind: str) -> Optional[float]:
    """Table lookup by ``device_kind``. A TPU that is not in the table is an
    error naming the kind, never a default and never a silent None: every
    MFU / roofline number divides by this. Off TPU (``cpu``, GPUs) there is
    no peak to claim and the answer is None -- callers then publish no
    utilization at all."""
    peak = table.get(device_kind)
    if peak is None and device_kind.startswith("TPU"):
        raise ValueError(
            f"no {what} for device_kind {device_kind!r} in "
            f"paddle_tpu/utils/flops.py (known: {sorted(table)}); add the "
            f"published figure with its source")
    return peak


def device_peak_flops(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOP/s for a jax device kind string; None off TPU, raises
    for a TPU kind the table does not have."""
    return _peak(_PEAK_BF16, "peak bf16 FLOP/s", device_kind)


# HBM bandwidth peaks, bytes/s per *JAX device* (v2/v3 report per-core
# devices -> half the chip's HBM). Public spec-sheet numbers.
_PEAK_HBM = {
    "TPU v2": 350e9,
    "TPU v3": 450e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}

# ICI egress per chip, bytes/s (one-way link bandwidth x link count on the
# torus; the scaling-book numbers). Upper bounds for sanity checks -- an
# allreduce bus bandwidth over ICI cannot exceed this.
_PEAK_ICI = {
    "TPU v2": 200e9,
    "TPU v3": 280e9,
    "TPU v4": 270e9,
    "TPU v5 lite": 180e9,
    "TPU v5e": 180e9,
    "TPU v5": 540e9,
    "TPU v5p": 540e9,
    "TPU v6 lite": 360e9,
    "TPU v6e": 360e9,
}


def device_peak_hbm_bw(device_kind: str) -> Optional[float]:
    """Peak HBM bytes/s for a jax device kind; None off TPU, raises for a
    TPU kind the table does not have."""
    return _peak(_PEAK_HBM, "peak HBM bytes/s", device_kind)


def device_peak_ici_bw(device_kind: str) -> Optional[float]:
    """Peak per-chip ICI egress bytes/s; None off TPU, raises for a TPU
    kind the table does not have."""
    return _peak(_PEAK_ICI, "peak ICI bytes/s", device_kind)


def bandwidth_sanity(value_gbps: float, device_kind: str, domain: str):
    """Clamp a measured bandwidth against the chip's physical peak.

    domain: "hbm" or "ici". Returns (reported_gbps, suspect, bound_gbps).
    A timing-differencing estimator fed noisy segment times can produce a
    tiny positive delta and an impossible bandwidth (round-4 postmortem:
    5,832 GB/s "HBM" on a chip whose HBM peaks at 819); any estimate above
    the physical peak is reported AS the peak with suspect=True so an
    impossible number can never be recorded as a measurement.
    """
    peak = (device_peak_hbm_bw if domain == "hbm"
            else device_peak_ici_bw)(device_kind)
    if peak is None:
        return value_gbps, False, None
    bound = peak / 1e9
    if value_gbps > bound:
        return bound, True, bound
    return value_gbps, False, bound


def _subst(shape, batch):
    return tuple(batch if d == -1 else int(d) for d in shape)


def _prod(xs):
    p = 1
    for x in xs:
        p *= int(x)
    return p


def _matmul_flops(xs, ys, trans_x, trans_y):
    if len(xs) < 2 or len(ys) < 2:
        return 0
    m = xs[-1] if trans_x else xs[-2]
    k = xs[-2] if trans_x else xs[-1]
    n = ys[-2] if trans_y else ys[-1]
    batch = _prod(max(xs[:-2], ys[:-2], key=len) or (1,))
    return 2 * batch * m * k * n


def _op_flops(op, shape_of, batch) -> int:
    """MACs*2 for one forward op desc; 0 for non-matmul ops."""
    t = op.type

    def shp(slot, i=0):
        names = op.inputs.get(slot) or ()
        if i >= len(names):
            return None
        s = shape_of(names[i])
        return None if s is None else _subst(s, batch)

    def oshp(slot, i=0):
        names = op.outputs.get(slot) or ()
        if i >= len(names):
            return None
        s = shape_of(names[i])
        return None if s is None else _subst(s, batch)

    if t == "mul":
        xs, ys = shp("X"), shp("Y")
        if xs is None or ys is None:
            return 0
        ncol = op.attr("x_num_col_dims") or 1
        m = _prod(xs[:ncol])
        k = _prod(xs[ncol:])
        n = _prod(ys[1:]) if len(ys) > 1 else 1
        return 2 * m * k * n
    if t == "matmul":
        xs, ys = shp("X"), shp("Y")
        if xs is None or ys is None:
            return 0
        return _matmul_flops(xs, ys, bool(op.attr("transpose_X")),
                             bool(op.attr("transpose_Y")))
    if t in ("conv2d", "depthwise_conv2d", "conv3d"):
        ws, outs = shp("Filter"), oshp("Output")
        if ws is None or outs is None:
            return 0
        # out elements x (Cin/groups * prod(kernel)) MACs each
        return 2 * _prod(outs) * _prod(ws[1:])
    if t == "conv2d_transpose":
        ws, xs = shp("Filter"), shp("Input")
        if ws is None or xs is None:
            return 0
        return 2 * _prod(xs) * _prod(ws[1:])
    if t == "fused_attention":
        qs = shp("Q")  # [B, H, S, D]
        if qs is None or len(qs) != 4:
            return 0
        B_, H_, S_, D_ = qs
        return 2 * 2 * B_ * H_ * S_ * S_ * D_  # QK^T and PV matmuls
    return 0


def program_flops(program, batch: int) -> Dict[str, int]:
    """Total matmul-class FLOPs for one run of ``program`` with -1 dims = batch.

    Grad ops count 2x their forward op (dX and dW are each one matmul-class op of
    the forward's cost). Sub-blocks (scan bodies) are counted once per op — callers
    with iterated sub-blocks should scale externally.
    Returns {"total": n, "forward": n_fwd, "backward": n_bwd}.
    """
    fwd = bwd = 0
    for block in program.blocks:
        def shape_of(name, _b=block):
            v = _b.find_var_recursive(name)
            return None if v is None else v.shape
        for op in block.ops:
            if op.type.endswith("_grad"):
                base = _clone_as_forward(op)
                if base is not None:
                    bwd += 2 * _op_flops(base, shape_of, batch)
            else:
                fwd += _op_flops(op, shape_of, batch)
    return {"total": fwd + bwd, "forward": fwd, "backward": bwd}


class _FwdView:
    """View of a grad op desc with the forward op's slots (inputs carry the
    forward inputs verbatim per make_grad_op_descs)."""

    def __init__(self, op):
        self.type = op.type[:-5]
        self.inputs = {s: n for s, n in op.inputs.items()
                       if not s.endswith("@GRAD")}
        fwd_outs = op.attr("__fwd_out_slots__") or ()
        self.outputs = {s: n for s, n in op.inputs.items() if s in fwd_outs}
        self._attrs = op.attrs

    def attr(self, name):
        return self._attrs.get(name)


def _clone_as_forward(op):
    try:
        return _FwdView(op)
    except Exception:
        return None
