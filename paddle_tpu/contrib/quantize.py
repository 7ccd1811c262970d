"""Post-training quantization (reference: python/paddle/fluid/contrib/slim/
quantization/quantization_pass.py + contrib/quantize/quantize_transpiler.py).

TPU-native design: the reference inserts fake_quantize/fake_dequantize op
pairs to simulate int8 on fp32 hardware. On TPU the useful serving form is
WEIGHT-ONLY int8: weights are stored int8 with per-output-channel symmetric
scales (4x less HBM and checkpoint size -- the TPU bottleneck), and the
lowering dequantizes to bf16 right at the consuming matmul, where XLA fuses
the multiply into the MXU feed. Accuracy loss is the int8 rounding only
(~1e-2 relative), no activation quantization error. Full int8xint8 MXU
compute (activations quantized dynamically per row) is ``int8_compute=True``
— the fused Pallas kernel (ops/pallas_int8.py) makes it faster than bf16 on
TPU-supported shapes.

API::

    quantize_weights(program, scope)           # rewrite in place, returns
                                               # {param: (bits, scale_name)}
    # then run / save_inference_model as usual -- the checkpoint stores int8
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..core.registry import register
from ..framework import Program

# ops whose weight input can be quantized: slot holding the weight
_WEIGHT_SLOTS = {"mul": "Y", "matmul": "Y", "conv2d": "Filter",
                 "conv3d": "Filter", "conv2d_transpose": "Filter"}


@register("quantized_mul", grad=None, nondiff_inputs=("Y", "YScale"))
def quantized_mul(ctx, ins):
    """Full int8 x int8 -> int32 matmul. The activation is quantized
    DYNAMICALLY per row (abs-max/127), the weight statically
    per-output-channel; the int32 accumulator is rescaled by
    (row_scale * w_scale). This is the compute mode the reference's slim
    stack simulates with fake-quant pairs -- here it is the real kernel.

    Kernel choice: on TPU-supported shapes this lowers to the FUSED Pallas
    kernel (ops/pallas_int8.py: quantize-to-VMEM-once + int8 MXU dot +
    fused rescale; MEASURED v5e 4096^3: 1.04x bf16, vs 0.73x for the
    unfused XLA path taken on other platforms/shapes — CPU/GPU serving
    stays compiled; tests/test_pallas_int8.py drives the kernel in
    interpret mode directly)."""
    from ..ops import pallas_int8
    x, w8, wscale = ins["X"][0], ins["Y"][0], ins["YScale"][0]
    ncol = ctx.attr("x_num_col_dims", 1) or 1
    xshape = x.shape
    m = 1
    for d in xshape[:ncol]:
        m *= d
    x2 = x.reshape(m, -1)
    N = w8.shape[1]
    # fused kernel on TPU only; elsewhere the XLA path compiles (interpret
    # mode is a test-only tool — tests/test_pallas_int8.py drives it
    # directly, so CPU/GPU serving keeps compiled speed)
    from ..ops import pallas_mode
    if (not ctx.abstract and pallas_mode.on_tpu()
            and pallas_int8.supports_fused(m, x2.shape[1],
                                           x2.dtype.itemsize)):
        out = pallas_int8.fused_int8_matmul(x2, w8, wscale)
    else:
        out = int8_matmul_xla(x2, w8, wscale)
    return {"Out": [out.reshape(tuple(xshape[:ncol]) + (N,))]}


def int8_matmul_xla(x2, w8, wscale):
    """quantized_mul's unfused XLA formulation ([M,K] float x [K,N] int8 ->
    [M,N] x2.dtype): the lowering off TPU and outside the fused kernel's
    shape gate, and the reference chip_smoke.py holds the kernel to."""
    import jax
    import jax.numpy as jnp
    a_scale = jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=1,
                      keepdims=True) / 127.0
    a_scale = jnp.maximum(a_scale, 1e-12)
    xq = jnp.clip(jnp.round(x2.astype(jnp.float32) / a_scale),
                  -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, w8, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) *
            (a_scale * wscale[None, :])).astype(x2.dtype)


@register("dequantize_weight", grad=None,
          nondiff_inputs=("X", "Scale"))
def dequantize_weight(ctx, ins):
    """int8 weight + per-channel scale -> compute dtype. XLA fuses this into
    the consuming matmul/conv (one multiply on the MXU feed path)."""
    import jax.numpy as jnp
    w8, scale = ins["X"][0], ins["Scale"][0]
    axis = int(ctx.attr("channel_axis", -1))
    dtype = ctx.attr("out_dtype", "float32")
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.dtype(dtype)
    shape = [1] * w8.ndim
    shape[axis] = w8.shape[axis]
    return {"Out": [(w8.astype(jnp.float32) *
                     scale.reshape(shape)).astype(dt)]}


def _quantize_array(w: np.ndarray, channel_axis: int, bits: int):
    qmax = 2 ** (bits - 1) - 1
    red = tuple(i for i in range(w.ndim) if i != channel_axis)
    scale = np.max(np.abs(w), axis=red).astype("float32") / qmax
    scale = np.maximum(scale, 1e-12)
    shape = [1] * w.ndim
    shape[channel_axis] = w.shape[channel_axis]
    q = np.clip(np.round(w / scale.reshape(shape)), -qmax - 1, qmax)
    return q.astype("int8"), scale


def quantize_weights(program: Program, scope, weight_bits: int = 8,
                     quantizable_op_type: Optional[Sequence[str]] = None,
                     min_elements: int = 1024,
                     int8_compute: bool = False) -> Dict[str, Tuple[int, str]]:
    """Weight-only PTQ rewrite (the quant_transpiler analog).

    For each weight input of a quantizable op: store the int8 array +
    per-output-channel scale in the scope, and insert a dequantize_weight op
    ahead of the consumer. Params smaller than ``min_elements`` are skipped
    (no memory win, pure accuracy cost). Returns {param_name: (bits,
    scale_var_name)}. Run on an inference program (clone(for_test=True) or a
    loaded inference model); training through quantized weights is QAT,
    which this pass does not do.

    ``int8_compute=True`` additionally swaps ``mul`` ops whose weight was
    quantized to the real int8xint8 kernel (quantized_mul) with dynamic
    per-ROW activation scales. On TPU-supported shapes this runs the fused
    Pallas kernel (ops/pallas_int8.py, measured 1.04x bf16 on v5e) — int8
    serving is now the faster mode there; other backends fall back to the
    unfused XLA path (slower than bf16, fine for accuracy studies).
    """
    ops = set(quantizable_op_type or _WEIGHT_SLOTS)
    block = program.global_block()
    done: Dict[str, Tuple[int, str]] = {}
    insertions = []   # (op_index, weight_name, deq_name)

    for idx, op in enumerate(block.ops):
        slot = _WEIGHT_SLOTS.get(op.type)
        if op.type not in ops or slot is None:
            continue
        for i, name in enumerate(op.inputs.get(slot, [])):
            v = block.find_var_recursive(name)
            w = scope.find_var(name)
            if v is None or w is None or not getattr(v, "persistable", False):
                continue
            w = np.asarray(w)
            # ml_dtypes.bfloat16 reports kind 'V'; it is a float for our
            # purposes (quantize from its f32 view)
            is_bf16 = w.dtype.name == "bfloat16"
            if w.size < min_elements or (w.dtype.kind != "f" and not is_bf16):
                continue
            if is_bf16:
                w = w.astype("float32")
            # output channels: matmul weights last dim; conv filters dim 0;
            # transpose-conv filters [C_in, C_out, ...] -> dim 1
            if "transpose" in op.type:
                ch = 1
            elif "conv" in op.type:
                ch = 0
            else:
                ch = w.ndim - 1
            deq_name = name + "@deq"
            if name not in done:
                q, scale = _quantize_array(w, ch, weight_bits)
                scope.set_var(name, q)
                scope.set_var(name + "@scale", scale)
                v.dtype = "int8"
                sv = block.create_var(name + "@scale", tuple(scale.shape),
                                      "float32")
                sv.persistable = True
                dv = block.create_var(deq_name, tuple(w.shape),
                                      "bfloat16" if is_bf16
                                      else str(w.dtype))
                dv.stop_gradient = True
                done[name] = (weight_bits, name + "@scale")
                insertions.append((idx, name, ch, str(dv.dtype)))
            if (int8_compute and op.type == "mul" and weight_bits == 8
                    and w.ndim == 2):
                # real int8 MXU path: the op consumes the int8 weight +
                # scale directly, no dequant op needed for this consumer
                op.type = "quantized_mul"
                op.inputs["YScale"] = [name + "@scale"]
            else:
                op.inputs[slot][i] = deq_name

    # Every OTHER consumer of a quantized weight (any op outside
    # _WEIGHT_SLOTS, e.g. a tied-embedding lookup) must read the dequantized
    # view too -- the original name now holds raw int8 codes.
    deq_ops = {"dequantize_weight", "quantized_mul"}
    for op in block.ops:
        if op.type in deq_ops:
            continue
        for slot, names in op.inputs.items():
            for i, n in enumerate(names):
                if n in done and not (
                        _WEIGHT_SLOTS.get(op.type) == slot):
                    names[i] = n + "@deq"

    # insert dequantize ops (reverse order keeps indices valid) for any
    # consumer still reading the dequantized view
    needed = {n for op in block.ops for n in op.input_arg_names()}
    for idx, name, ch, dtype in sorted(insertions, reverse=True):
        if name + "@deq" not in needed:
            continue
        block.insert_op(
            idx, "dequantize_weight",
            inputs={"X": [name], "Scale": [name + "@scale"]},
            outputs={"Out": [name + "@deq"]},
            attrs={"channel_axis": ch, "out_dtype": dtype},
            infer_shape=False)
    program._bump()
    return done


class QuantizeTranspiler:
    """Facade matching the reference's contrib.quantize.QuantizeTranspiler."""

    def __init__(self, weight_bits=8, activation_bits=8,
                 activation_quantize_type="abs_max",
                 weight_quantize_type="abs_max", window_size=10000):
        if activation_quantize_type not in (None, "abs_max"):
            raise NotImplementedError(
                "activation quantization: TPU PTQ here is weight-only "
                "(SCOPE.md open gap #4); activations stay bf16")
        self.weight_bits = weight_bits

    def training_transpile(self, program=None, startup_program=None):
        raise NotImplementedError(
            "QAT fake-quant training is not built (SCOPE.md); use bf16 AMP "
            "for training and quantize_weights() for serving")

    def freeze_program(self, program, place=None, scope=None):
        from ..core.executor import global_scope
        return quantize_weights(program, scope or global_scope(),
                                self.weight_bits)
