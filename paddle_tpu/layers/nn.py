"""Core NN layers DSL (reference: python/paddle/fluid/layers/nn.py, ~193 functions).

Each function builds ops into the default main program and parameters into the default
startup program, exactly like the reference's DSL; the difference is everything lowers
to XLA later instead of dispatching CUDA kernels.
"""
from __future__ import annotations


import numpy as np

from ..framework import convert_dtype, default_main_program
# Variable is re-exported (star-import into paddle_tpu.layers; reference
# user code reaches it as fluid.layers.Variable -- tests/api_spec.txt)
from ..framework import Variable  # noqa: F401
from ..layer_helper import LayerHelper


def _blk():
    return default_main_program().current_block()


def _out(helper, dtype="float32", stop_gradient=False):
    return helper.create_variable_for_type_inference(dtype, stop_gradient)


def _var(helper, v):
    return helper.main_program.current_block().var(v.name)


# --------------------------------------------------------------------------------------
# fully connected / embedding
# --------------------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Reference nn.py:233. y = act(sum_i(x_i @ W_i) + b)."""
    helper = LayerHelper("fc", param_attr=param_attr, bias_attr=bias_attr, act=act,
                         name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for x in inputs:
        tail = tuple(x.shape[num_flatten_dims:])
        if any(d < 0 for d in tail):
            raise ValueError(
                f"fc: input {getattr(x, 'name', '?')} has a dynamic dim in "
                f"the flattened tail {tail} (num_flatten_dims="
                f"{num_flatten_dims}); the weight shape would be wrong -- "
                f"only dims before num_flatten_dims may be -1 (reference "
                f"fc infer_shape enforces the same)")
        in_features = int(np.prod(tail))
        w = helper.create_parameter(param_attr, [in_features, size], x.dtype)
        out = _out(helper, x.dtype)
        helper.append_op("mul", inputs={"X": [x], "Y": [w]},
                         outputs={"Out": [out]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = _out(helper, inputs[0].dtype)
        helper.append_op("sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(_var(helper, pre_bias),
                                    dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """Reference nn.py:491. On TPU, is_sparse selects nothing special single-chip
    (grads are fused dense scatter-adds); sharded tables are layers in parallel/."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(param_attr, list(size), dtype)
    out = _out(helper, dtype)
    helper.append_op("lookup_table_v2", inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"padding_idx": -1 if padding_idx is None
                            else padding_idx,
                            "is_sparse": is_sparse,
                            "is_distributed": is_distributed})
    return _var(helper, out)


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    out = _out(helper, "float32")
    helper.append_op("one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"depth": depth})
    return _var(helper, out)


# --------------------------------------------------------------------------------------
# conv / pool / norm
# --------------------------------------------------------------------------------------

def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True, act=None,
           name=None, data_format="NCHW"):
    """Reference nn.py:2543 (use_cudnn accepted and ignored: XLA targets the MXU).
    data_format='NHWC' runs the channels-last TPU-preferred layout; the Filter
    parameter stays [O, I/g, kh, kw] in both layouts (checkpoint-compatible)."""
    helper = LayerHelper("conv2d", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    c_in = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    fh, fw = (filter_size if isinstance(filter_size, (list, tuple))
              else (filter_size, filter_size))
    groups = groups or 1
    w = helper.create_parameter(
        param_attr, [num_filters, c_in // groups, fh, fw], input.dtype,
        default_initializer=None)
    out = _out(helper, input.dtype)
    helper.append_op(
        "conv2d", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(stride) if isinstance(stride, (list, tuple))
               else [stride, stride],
               "paddings": list(padding) if isinstance(padding, (list, tuple))
               else [padding, padding],
               "dilations": list(dilation) if isinstance(dilation, (list, tuple))
               else [dilation, dilation],
               "groups": groups,
               "data_format": data_format})
    pre_act = _var(helper, out)
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        out2 = _out(helper, input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [pre_act], "Y": [b]},
                         outputs={"Out": [out2]},
                         attrs={"axis": 1 if data_format == "NCHW" else -1})
        pre_act = _var(helper, out2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1, param_attr=None,
                     bias_attr=None, use_cudnn=True, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c_in = input.shape[1]
    fh, fw = (filter_size if isinstance(filter_size, (list, tuple))
              else (filter_size, filter_size))
    w = helper.create_parameter(param_attr,
                                [c_in, num_filters // (groups or 1), fh, fw],
                                input.dtype)
    out = _out(helper, input.dtype)
    helper.append_op(
        "conv2d_transpose", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": [stride, stride] if isinstance(stride, int)
               else list(stride),
               "paddings": [padding, padding] if isinstance(padding, int)
               else list(padding),
               "dilations": [dilation, dilation] if isinstance(dilation, int)
               else list(dilation),
               "groups": groups or 1})
    pre_act = _var(helper, out)
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        out2 = _out(helper, input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [pre_act], "Y": [b]},
                         outputs={"Out": [out2]}, attrs={"axis": 1})
        pre_act = _var(helper, out2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False, name=None,
           exclusive=True, adaptive=False, data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    out = _out(helper, input.dtype)
    helper.append_op(
        "pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type,
               "ksize": [pool_size, pool_size] if isinstance(pool_size, int)
               else list(pool_size),
               "strides": [pool_stride, pool_stride]
               if isinstance(pool_stride, int) else list(pool_stride),
               "paddings": [pool_padding, pool_padding]
               if isinstance(pool_padding, int) else list(pool_padding),
               "global_pooling": global_pooling, "exclusive": exclusive,
               "adaptive": adaptive, "data_format": data_format})
    return _var(helper, out)


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    return pool2d(input, pool_size=pool_size, pool_type=pool_type, adaptive=True,
                  name=name)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False, fuse_stats=False):
    """Reference nn.py:4104.

    fuse_stats=True marks this BN for contrib.fuse_conv_bn_stats (the
    ir/conv_bn_fuse_pass.cc analog): when its input is a 1x1/s1 NHWC conv,
    the pass swaps the pair for the Pallas conv2d_bn_fused op whose epilogue
    accumulates the statistics. Off by default -- on v5e the measured XLA
    fusion is at least as fast (ops/pallas_conv_bn.py docstring)."""
    from ..initializer import Constant
    helper = LayerHelper("batch_norm", act=act, name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    dtype = input.dtype if input.dtype != "float16" else "float32"
    scale = helper.create_parameter(param_attr, [c], dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, [c], dtype, is_bias=True)
    mean = helper.create_global_variable(
        [c], "float32", persistable=True, name=moving_mean_name,
        initializer=Constant(0.0))
    variance = helper.create_global_variable(
        [c], "float32", persistable=True, name=moving_variance_name,
        initializer=Constant(1.0))
    y = _out(helper, input.dtype)
    saved_mean = _out(helper, "float32", stop_gradient=True)
    saved_var = _out(helper, "float32", stop_gradient=True)
    helper.append_op(
        "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [y], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout,
               "use_global_stats": use_global_stats,
               "fuse_stats": fuse_stats})
    return helper.append_activation(_var(helper, y))


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    """Reference nn.py:4567."""
    from ..initializer import Constant
    helper = LayerHelper("layer_norm", act=act, name=name)
    nshape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, nshape, input.dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, nshape, input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    y = _out(helper, input.dtype)
    mean = _out(helper, "float32", stop_gradient=True)
    var = _out(helper, "float32", stop_gradient=True)
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [y], "Mean": [mean], "Variance": [var]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(_var(helper, y))


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    from ..initializer import Constant
    helper = LayerHelper("group_norm", act=act, name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(
            param_attr, [c], input.dtype, default_initializer=Constant(1.0))]
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(bias_attr, [c], input.dtype,
                                                  is_bias=True)]
    y = _out(helper, input.dtype)
    mean = _out(helper, "float32", stop_gradient=True)
    var = _out(helper, "float32", stop_gradient=True)
    helper.append_op("group_norm", inputs=inputs,
                     outputs={"Y": [y], "Mean": [mean], "Variance": [var]},
                     attrs={"groups": groups, "epsilon": epsilon})
    return helper.append_activation(_var(helper, y))


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    from ..initializer import Constant
    helper = LayerHelper("instance_norm", name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(
            param_attr, [c], input.dtype, default_initializer=Constant(1.0))]
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(bias_attr, [c], input.dtype,
                                                  is_bias=True)]
    y = _out(helper, input.dtype)
    sm = _out(helper, "float32", stop_gradient=True)
    sv = _out(helper, "float32", stop_gradient=True)
    helper.append_op("instance_norm", inputs=inputs,
                     outputs={"Y": [y], "SavedMean": [sm], "SavedVariance": [sv]},
                     attrs={"epsilon": epsilon})
    return _var(helper, y)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = _out(helper, x.dtype)
    mask = _out(helper, x.dtype, stop_gradient=True)
    helper.append_op("dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": seed if seed is not None else 0,
                            "dropout_implementation": dropout_implementation})
    return _var(helper, out)


# --------------------------------------------------------------------------------------
# math layers
# --------------------------------------------------------------------------------------

def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
                            "alpha": float(alpha)})
    return _var(helper, out)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("mul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return _var(helper, out)


def _elementwise(op_type):
    def layer(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, act=act, name=name)
        out = _out(helper, x.dtype)
        helper.append_op(op_type, inputs={"X": [x], "Y": [y]},
                         outputs={"Out": [out]}, attrs={"axis": axis})
        return helper.append_activation(_var(helper, out))
    layer.__name__ = op_type
    return layer


elementwise_add = _elementwise("elementwise_add")
elementwise_sub = _elementwise("elementwise_sub")
elementwise_mul = _elementwise("elementwise_mul")
elementwise_div = _elementwise("elementwise_div")
elementwise_max = _elementwise("elementwise_max")
elementwise_min = _elementwise("elementwise_min")
elementwise_pow = _elementwise("elementwise_pow")
elementwise_mod = _elementwise("elementwise_mod")
elementwise_floordiv = _elementwise("elementwise_floordiv")


def _unary(op_type, out_dtype=None, **extra):
    def layer(x, name=None, **kw):
        helper = LayerHelper(op_type, name=name)
        out = _out(helper, out_dtype or x.dtype)
        attrs = dict(extra)
        attrs.update({k: v for k, v in kw.items() if v is not None})
        helper.append_op(op_type, inputs={"X": [x]}, outputs={"Out": [out]},
                         attrs=attrs)
        return _var(helper, out)
    layer.__name__ = op_type
    return layer


relu = _unary("relu")
sigmoid = _unary("sigmoid")
logsigmoid = _unary("logsigmoid")
tanh = _unary("tanh")
tanh_shrink = _unary("tanh_shrink")
exp = _unary("exp")
log = _unary("log")
square = _unary("square")
sqrt = _unary("sqrt")
rsqrt = _unary("rsqrt")
abs = _unary("abs")
reciprocal = _unary("reciprocal")
softplus = _unary("softplus")
softsign = _unary("softsign")
ceil = _unary("ceil")
floor = _unary("floor")
round = _unary("round")
sign = _unary("sign")
erf = _unary("erf")
cos = _unary("cos")
sin = _unary("sin")
acos = _unary("acos")
asin = _unary("asin")
atan = _unary("atan")
cosh = _unary("cosh")
sinh = _unary("sinh")
gelu = _unary("gelu")
mish = _unary("mish")
hard_swish = _unary("hard_swish")
hard_sigmoid = _unary("hard_sigmoid")
relu6 = _unary("relu6")
soft_relu = _unary("soft_relu")
stanh = _unary("stanh")
hard_shrink = _unary("hard_shrink")
softshrink = _unary("softshrink")
thresholded_relu = _unary("thresholded_relu")
brelu = _unary("brelu")


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("leaky_relu", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"alpha": alpha})
    return _var(helper, out)


def elu(x, alpha=1.0, name=None):
    helper = LayerHelper("elu", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("elu", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"alpha": alpha})
    return _var(helper, out)


def swish(x, beta=1.0, name=None):
    helper = LayerHelper("swish", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("swish", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"beta": beta})
    return _var(helper, out)


def pow(x, factor=1.0, name=None):
    helper = LayerHelper("pow", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("pow", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"factor": factor})
    return _var(helper, out)


def prelu(x, mode, param_attr=None, name=None):
    from ..initializer import Constant
    helper = LayerHelper("prelu", name=name)
    alpha_shape = [1]
    if mode == "channel":
        alpha_shape = [x.shape[1]]
    elif mode == "element":
        alpha_shape = [int(np.prod(x.shape[1:]))]
    alpha = helper.create_parameter(param_attr, alpha_shape, x.dtype,
                                    default_initializer=Constant(0.25))
    out = _out(helper, x.dtype)
    helper.append_op("prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return _var(helper, out)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = _out(helper, x.dtype)
    helper.append_op("scale", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"scale": float(scale), "bias": float(bias),
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(_var(helper, out))


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": float(min), "max": float(max)})
    return _var(helper, out)


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("clip_by_norm", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"max_norm": float(max_norm)})
    return _var(helper, out)


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("softmax", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return _var(helper, out)


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("log_softmax", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return _var(helper, out)


# -- losses ----------------------------------------------------------------------------

def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False,
                               axis=-1):
    """Reference nn.py:8223. The op has a third output, ``Lse`` [N..., 1]:
    the rows' ``logsumexp``, which its own grad lowering reads (no gradient
    flows through it). Logits narrower than float32 (hard labels, the last
    axis, no ``ignore_index``) give a float32 ``Loss``, computed in float32
    inside without a float32 copy of the logits (ops/math_ops.py)."""
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = _out(helper, logits.dtype)
    loss = _out(helper, logits.dtype)
    lse = _out(helper, "float32", stop_gradient=True)
    helper.append_op("softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [softmax_out], "Loss": [loss],
                              "Lse": [lse]},
                     attrs={"soft_label": soft_label, "ignore_index": ignore_index,
                            "axis": axis})
    if return_softmax:
        return _var(helper, loss), _var(helper, softmax_out)
    return _var(helper, loss)


def cross_entropy2(input, label, ignore_index=-100):
    """Reference nn.py:1917 -- hard-label CE variant whose kernel saves the
    matched probability (MatchX) for its grad."""
    helper = LayerHelper("cross_entropy2")
    out = _out(helper, input.dtype)
    match_x = _out(helper, input.dtype, stop_gradient=True)
    helper.append_op("cross_entropy2",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out], "MatchX": [match_x]},
                     attrs={"ignore_index": ignore_index})
    return _var(helper, out)


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = _out(helper, input.dtype)
    helper.append_op("cross_entropy", inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return _var(helper, out)


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]}, outputs={"Out": [out]},
                     attrs={"ignore_index": ignore_index, "normalize": normalize})
    return _var(helper, out)


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = _out(helper, input.dtype)
    helper.append_op("square_error_cost",
                     inputs={"X": [input], "Y": [label]}, outputs={"Out": [out]})
    return _var(helper, out)


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = _out(helper, input.dtype)
    residual = _out(helper, input.dtype, stop_gradient=True)
    helper.append_op("huber_loss", inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out], "Residual": [residual]},
                     attrs={"delta": delta})
    return _var(helper, out)


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    out = _out(helper, x.dtype)
    diff = _out(helper, x.dtype, stop_gradient=True)
    helper.append_op("smooth_l1_loss", inputs=inputs,
                     outputs={"Out": [out], "Diff": [diff]},
                     attrs={"sigma": sigma if sigma is not None else 1.0})
    return _var(helper, out)


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("log_loss", inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [out]}, attrs={"epsilon": epsilon})
    return _var(helper, out)


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return _var(helper, out)


# -- reductions ------------------------------------------------------------------------

def _reduce(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = _out(helper, input.dtype)
        if dim is None:
            attrs = {"dim": [0], "keep_dim": keep_dim, "reduce_all": True}
        else:
            attrs = {"dim": dim if isinstance(dim, (list, tuple)) else [dim],
                     "keep_dim": keep_dim, "reduce_all": False}
        helper.append_op(op_type, inputs={"X": [input]}, outputs={"Out": [out]},
                         attrs=attrs)
        return _var(helper, out)
    layer.__name__ = op_type
    return layer


reduce_sum = _reduce("reduce_sum")
reduce_mean = _reduce("reduce_mean")
reduce_max = _reduce("reduce_max")
reduce_min = _reduce("reduce_min")
reduce_prod = _reduce("reduce_prod")
reduce_all = _reduce("reduce_all")
reduce_any = _reduce("reduce_any")


# -- shape manipulation ----------------------------------------------------------------

def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", act=act, name=name)
    out = _out(helper, x.dtype)
    helper.append_op("reshape2", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": [int(s) for s in shape]})
    return helper.append_activation(_var(helper, out))


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("transpose2", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": list(perm)})
    return _var(helper, out)


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("flatten2", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return _var(helper, out)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("squeeze2", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes)})
    return _var(helper, out)


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("unsqueeze2", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes)})
    return _var(helper, out)


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    axis = dim % len(input.shape)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": axis}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": axis}
    outs = [_out(helper, input.dtype) for _ in range(n)]
    helper.append_op("split", inputs={"X": [input]}, outputs={"Out": outs},
                     attrs=attrs)
    blk = helper.main_program.current_block()
    return [blk.var(o.name) for o in outs]


def stack(x, axis=0):
    helper = LayerHelper("stack")
    xs = x if isinstance(x, (list, tuple)) else [x]
    out = _out(helper, xs[0].dtype)
    helper.append_op("stack", inputs={"X": list(xs)}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    return _var(helper, out)


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    n = num if num is not None else x.shape[axis]
    outs = [_out(helper, x.dtype) for _ in range(n)]
    helper.append_op("unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis})
    blk = helper.main_program.current_block()
    return [blk.var(o.name) for o in outs]


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = _out(helper, input.dtype)
    helper.append_op("slice", inputs={"Input": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return _var(helper, out)


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return _var(helper, out)


def gather(input, index, overwrite=True, axis=0):
    """``input``'s slices along ``axis`` at ``index``. Under a mesh
    (``CompiledProgram.with_strategy``) the rows gathered along axis 0 leave
    the op laid over the strategy's data axis where its size divides both
    ``input``'s rows and the index count, whatever the layout of ``index``
    (BERT's flat ``mask_pos`` is replicated): each device runs what consumes
    them on its own part."""
    helper = LayerHelper("gather")
    out = _out(helper, input.dtype)
    helper.append_op("gather", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]}, attrs={"axis": int(axis)})
    return _var(helper, out)


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("gather_nd", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return _var(helper, out)


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("scatter",
                     inputs={"X": [input], "Ids": [index], "Updates": [updates]},
                     outputs={"Out": [out]}, attrs={"overwrite": overwrite})
    return _var(helper, out)


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "pad_value": pad_value})
    return _var(helper, out)


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    out = _out(helper, input.dtype)
    helper.append_op("pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": pad_value, "data_format": data_format})
    return _var(helper, out)


def shape(input):
    helper = LayerHelper("shape")
    out = _out(helper, "int32", stop_gradient=True)
    helper.append_op("shape", inputs={"Input": [input]}, outputs={"Out": [out]})
    return _var(helper, out)


def cast(x, dtype):
    from .tensor import cast as _cast
    return _cast(x, dtype)


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = _out(helper, input.dtype)
    indices = _out(helper, "int64", stop_gradient=True)
    helper.append_op("top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    blk = helper.main_program.current_block()
    return blk.var(values.name), blk.var(indices.name)


def accuracy(input, label, k=1, correct=None, total=None):
    """Reference layers/metric_op.py:accuracy — topk + accuracy op."""
    helper = LayerHelper("accuracy")
    _, indices = topk(input, k)
    acc = _out(helper, "float32", stop_gradient=True)
    correct = correct or _out(helper, "int32", stop_gradient=True)
    total = total or _out(helper, "int32", stop_gradient=True)
    helper.append_op("accuracy",
                     inputs={"Indices": [indices], "Label": [label]},
                     outputs={"Accuracy": [acc], "Correct": [correct],
                              "Total": [total]})
    return _var(helper, acc)


def deformable_conv(input, offset, mask, num_filters, filter_size,
                    stride=1, padding=0, dilation=1, groups=None,
                    deformable_groups=None, im2col_step=None,
                    param_attr=None, bias_attr=None, modulated=True,
                    name=None):
    """Reference nn.py:16751 — deformable convolution (v2 when modulated,
    v1 otherwise). im2col_step is accepted for parity and ignored: the
    lowering vectorizes the whole batch (ops/tail_ops.py)."""
    helper = LayerHelper("deformable_conv", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    c_in = input.shape[1]
    groups = groups or 1
    deformable_groups = deformable_groups or 1
    fh, fw = (filter_size if isinstance(filter_size, (list, tuple))
              else (filter_size, filter_size))
    w = helper.create_parameter(param_attr,
                                [num_filters, c_in // groups, fh, fw],
                                input.dtype)
    out = _out(helper, input.dtype)
    inputs = {"Input": [input], "Offset": [offset], "Filter": [w]}
    op_type = "deformable_conv" if modulated else "deformable_conv_v1"
    if modulated:
        if mask is None:
            raise ValueError("deformable_conv(modulated=True) needs a mask "
                             "(pass modulated=False for the v1 form)")
        inputs["Mask"] = [mask]
    elif mask is not None:
        raise ValueError("deformable_conv(modulated=False) is the v1 form "
                         "and takes no mask (the reference asserts the "
                         "same); pass mask=None")
    helper.append_op(
        op_type, inputs=inputs, outputs={"Output": [out]},
        attrs={"strides": [stride, stride] if isinstance(stride, int)
               else list(stride),
               "paddings": [padding, padding] if isinstance(padding, int)
               else list(padding),
               "dilations": [dilation, dilation] if isinstance(dilation, int)
               else list(dilation),
               "groups": groups, "deformable_groups": deformable_groups})
    pre_act = _var(helper, out)
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        out2 = _out(helper, input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [pre_act], "Y": [b]},
                         outputs={"Out": [out2]}, attrs={"axis": 1})
        pre_act = _var(helper, out2)
    return pre_act


def similarity_focus(input, axis, indexes, name=None):
    """Reference nn.py:9217 — similarity-focus mask: greedy row/column
    selection over the 2-D slices at ``indexes`` along ``axis``, broadcast
    over the axis dim (ops/tail_ops.py mirrors the reference kernel's walk
    exactly)."""
    helper = LayerHelper("similarity_focus", name=name)
    out = _out(helper, input.dtype, stop_gradient=True)
    helper.append_op("similarity_focus", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"axis": axis, "indexes": list(indexes)})
    return _var(helper, out)


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    """Reference nn.py:2051 — chunk-level precision/recall/F1 for sequence
    tagging (NER-style). input/label: padded [B, T] tag ids with the
    optional seq_length [B] giving true lengths (this repo's length-aware
    replacement for the reference's LoD input). Returns the reference's
    6-tuple (precision, recall, f1, num_infer, num_label, num_correct)."""
    helper = LayerHelper("chunk_eval")
    outs = {n: _out(helper, dt, stop_gradient=True)
            for n, dt in (("Precision", "float32"), ("Recall", "float32"),
                          ("F1-Score", "float32"),
                          ("NumInferChunks", "int32"),
                          ("NumLabelChunks", "int32"),
                          ("NumCorrectChunks", "int32"))}
    inputs = {"Inference": [input], "Label": [label]}
    if seq_length is not None:
        inputs["SeqLength"] = [seq_length]
    helper.append_op(
        "chunk_eval", inputs=inputs,
        outputs={k: [v] for k, v in outs.items()},
        attrs={"chunk_scheme": chunk_scheme,
               "num_chunk_types": num_chunk_types,
               "excluded_chunk_types": list(excluded_chunk_types or [])})
    return tuple(_var(helper, outs[k]) for k in
                 ("Precision", "Recall", "F1-Score", "NumInferChunks",
                  "NumLabelChunks", "NumCorrectChunks"))


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1, slide_steps=1):
    from ..initializer import Constant
    helper = LayerHelper("auc")
    stat_pos = helper.create_global_variable([num_thresholds + 1], "float32",
                                             initializer=Constant(0.0))
    stat_neg = helper.create_global_variable([num_thresholds + 1], "float32",
                                             initializer=Constant(0.0))
    auc_out = _out(helper, "float64", stop_gradient=True)
    helper.append_op("auc",
                     inputs={"Predict": [input], "Label": [label],
                             "StatPos": [stat_pos], "StatNeg": [stat_neg]},
                     outputs={"AUC": [auc_out], "StatPosOut": [stat_pos],
                              "StatNegOut": [stat_neg]},
                     attrs={"num_thresholds": num_thresholds})
    return _var(helper, auc_out), None, [stat_pos, stat_neg]


def where(condition, x=None, y=None):
    helper = LayerHelper("where")
    out = _out(helper, x.dtype)
    helper.append_op("where", inputs={"Condition": [condition], "X": [x],
                                      "Y": [y]}, outputs={"Out": [out]})
    return _var(helper, out)


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    out = _out(helper, dtype)
    helper.append_op("label_smooth", inputs=inputs, outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    return _var(helper, out)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = _out(helper, x.dtype)
    norm = _out(helper, x.dtype, stop_gradient=True)
    helper.append_op("l2_normalize", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return _var(helper, out)


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    out = _out(helper, X.dtype)
    xn = _out(helper, X.dtype, stop_gradient=True)
    yn = _out(helper, X.dtype, stop_gradient=True)
    helper.append_op("cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return _var(helper, out)


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    helper = LayerHelper("sequence_mask", name=name)
    out = _out(helper, dtype, stop_gradient=True)
    helper.append_op("sequence_mask", inputs={"X": [x]}, outputs={"Y": [out]},
                     attrs={"maxlen": maxlen if maxlen is not None else -1,
                            "out_dtype": convert_dtype(dtype)})
    return _var(helper, out)


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random")
    out = _out(helper, dtype, stop_gradient=True)
    helper.append_op("uniform_random", outputs={"Out": [out]},
                     attrs={"shape": [int(s) for s in shape],
                            "dtype": convert_dtype(dtype), "min": min,
                            "max": max, "seed": seed})
    return _var(helper, out)


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = _out(helper, dtype, stop_gradient=True)
    helper.append_op("gaussian_random", outputs={"Out": [out]},
                     attrs={"shape": [int(s) for s in shape],
                            "dtype": convert_dtype(dtype), "mean": mean,
                            "std": std, "seed": seed})
    return _var(helper, out)


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1):
    helper = LayerHelper("interpolate", name=name)
    out = _out(helper, input.dtype)
    method = {"BILINEAR": "bilinear", "NEAREST": "nearest"}[resample]
    attrs = {"interp_method": method, "scale": float(scale or 0.0)}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), int(out_shape[1])
    helper.append_op("interpolate", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return _var(helper, out)


def resize_bilinear(input, out_shape=None, scale=None, name=None, **kw):
    return image_resize(input, out_shape, scale, name, "BILINEAR")


def resize_nearest(input, out_shape=None, scale=None, name=None, **kw):
    return image_resize(input, out_shape, scale, name, "NEAREST")


# --------------------------------------------------------------------------------------
# beam search (reference nn.py:5852 beam_search, beam_search_decode; dense TPU
# redesign in ops/beam_ops.py)
# --------------------------------------------------------------------------------------

def beam_search(pre_ids, pre_scores, scores, finished, beam_size, end_id,
                name=None):
    """One dense beam step over [B,K] beams; ``scores`` are per-step log-probs
    [B,K,V]. Returns (selected_ids, selected_scores, parent_idx, finished)."""
    helper = LayerHelper("beam_search", name=name)
    sel_ids = _out(helper, "int64", stop_gradient=True)
    sel_scores = _out(helper, scores.dtype, stop_gradient=True)
    parent = _out(helper, "int32", stop_gradient=True)
    fin = _out(helper, "bool", stop_gradient=True)
    helper.append_op("beam_search",
                     inputs={"PreIds": [pre_ids], "PreScores": [pre_scores],
                             "Scores": [scores], "Finished": [finished]},
                     outputs={"SelectedIds": [sel_ids],
                              "SelectedScores": [sel_scores],
                              "ParentIdx": [parent], "FinishedOut": [fin]},
                     attrs={"beam_size": int(beam_size), "end_id": int(end_id)})
    blk = helper.main_program.current_block()
    return (blk.var(sel_ids.name), blk.var(sel_scores.name),
            blk.var(parent.name), blk.var(fin.name))


def beam_append(ids_buf, parent, new_ids, step_idx, name=None):
    """Reorder the [B,K,T] token buffer by parent pointers and write new_ids at
    column step_idx."""
    helper = LayerHelper("beam_append", name=name)
    out = _out(helper, ids_buf.dtype, stop_gradient=True)
    helper.append_op("beam_append",
                     inputs={"IdsBuf": [ids_buf], "Parent": [parent],
                             "NewIds": [new_ids], "StepIdx": [step_idx]},
                     outputs={"Out": [out]})
    return _var(helper, out)


def beam_search_decode(ids, parents, scores, beam_size=None, end_id=1,
                       name=None):
    """Backtrack per-step selections [B,T,K] into sentences [B,K,T] sorted
    best-first (reference beam_search_decode_op)."""
    helper = LayerHelper("beam_search_decode", name=name)
    sent = _out(helper, "int64", stop_gradient=True)
    sscores = _out(helper, scores.dtype, stop_gradient=True)
    helper.append_op("beam_search_decode",
                     inputs={"Ids": [ids], "Parents": [parents],
                             "Scores": [scores]},
                     outputs={"SentenceIds": [sent],
                              "SentenceScores": [sscores]},
                     attrs={"end_id": int(end_id)})
    blk = helper.main_program.current_block()
    return blk.var(sent.name), blk.var(sscores.name)


def fused_attention(q, k, v, bias=None, scale=None, dropout_prob=0.0,
                    causal=False, is_test=False, impl="auto", name=None,
                    window=None):
    """Fused scaled-dot-product attention over head-split tensors.

    q: [B, heads, S, D], k: [B, kv_heads, S, D], v: [B, kv_heads, S, Dv] (Dv
    = D but for latent attention whose values are narrower than its keys;
    the result is [B, heads, S, Dv]) with kv_heads dividing heads
    (grouped-query attention; heads x D need not be the model's hidden size);
    bias: optional [B, 1, 1, S] additive mask. ``window`` (with ``causal``):
    a sliding window, query i sees the keys i - window < j <= i (HF's
    ``sliding_window``); None, or a window of S or more, is plain causal
    attention. Lowers
    to one flash-attention Pallas kernel on TPU (ops/pallas_attention.py),
    which under a window visits only the K tiles the window reaches; the
    composed softmax(QK^T)V path otherwise. Reference analog: the subgraph that
    multihead_matmul_fuse_pass.cc:1 pattern-matches, exposed as one op.
    The op has a second output, ``Lse`` [B, heads, 1, S] float32: the rows'
    softmax statistics, which the flash kernels write for the op's own
    backward (no gradient flows through it; the layer returns ``Out``).
    """
    helper = LayerHelper("fused_attention", name=name)
    out = _out(helper, q.dtype)
    lse = _out(helper, "float32", stop_gradient=True)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    attrs = {"scale": float(scale) if scale else 0.0,
             "dropout_prob": float(dropout_prob),
             "causal": bool(causal), "is_test": bool(is_test), "impl": impl}
    if window:
        if not causal:
            raise ValueError("fused_attention: a sliding window needs causal")
        attrs["window"] = int(window)
    helper.append_op("fused_attention", inputs=inputs,
                     outputs={"Out": [out], "Lse": [lse]},   # Out first: the op's salt
                     attrs=attrs)
    return _var(helper, out)


# -- decoder-LM vocabulary (ops/decoder_ops.py) ------------------------------------------

def rms_norm(input, epsilon=1e-5, param_attr=None, name=None,
             zero_centered=False, gate=None, impl="auto",
             gate_activation="silu"):
    """RMSNorm over the last axis with a learned scale (initialised to 1):
    ``x / sqrt(mean(x^2) + epsilon) * scale``, float32 inside the op. Under
    ``zero_centered`` the scale is ``1 + w`` with ``w`` initialised to 0
    (Qwen3-Next's, Gemma's): the same function at the start, another under a
    weight decay, which pulls ``w`` to 0 and so the scale to 1. With
    ``gate`` (``input``'s element count: its shape, or ``[T, heads * D]``
    beside ``[T, heads, D]``) the result times ``silu(gate)`` inside the
    same op, one pass over both (a Gated DeltaNet mixer's output norm), or
    under ``gate_activation="sigmoid"`` times ``sigmoid(gate)`` (a Kimi
    Delta Attention mixer's); ``impl`` is that form's lowering, ``auto`` /
    ``pallas`` / ``composed`` (``ops/pallas_norm.py``)."""
    from ..initializer import Constant
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, [int(input.shape[-1])], input.dtype,
        default_initializer=Constant(0.0 if zero_centered else 1.0))
    y = _out(helper, input.dtype)
    attrs = {"epsilon": float(epsilon)}
    if zero_centered:
        attrs["zero_centered"] = True
    inputs = {"X": [input], "Scale": [scale]}
    if gate is not None:
        inputs["Gate"] = [gate]
        if impl != "auto":
            attrs["impl"] = impl
        if gate_activation != "silu":
            if gate_activation not in ("silu", "sigmoid"):
                raise ValueError(f"rms_norm: gate_activation="
                                 f"{gate_activation!r} (silu or sigmoid)")
            attrs["gate_activation"] = gate_activation
    helper.append_op("rms_norm", inputs=inputs, outputs={"Y": [y]},
                     attrs=attrs)
    return _var(helper, y)


def _rope_scaling_attrs(scaling) -> dict:
    """The attrs a rotating op (``rotary_embedding``, ``latent_qkv``) reads
    its frequencies from, out of a dict in HF's ``rope_scaling`` keys (None:
    none). ``rope_type`` (also spelt ``type``) ``"yarn"``: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast`` 32, ``beta_slow`` 1
    and the factor on cos and sin, ``attention_factor``; by default, as HF's
    ``_compute_yarn_parameters`` has it, ``m(mscale) / m(mscale_all_dim)``
    where the dict has both, else ``m(1)``, with ``m(s) = 0.1 s ln(factor) +
    1``."""
    import math
    kind = (scaling or {}).get("rope_type", (scaling or {}).get("type"))
    if kind in (None, "default"):
        return {}
    if kind != "yarn":
        raise NotImplementedError(
            f"rope_type {kind!r} is not built (only 'default' and 'yarn')")
    factor = float(scaling["factor"])

    def m(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0
    both = scaling.get("mscale") and scaling.get("mscale_all_dim")
    return {"scaling": "yarn", "factor": factor,
            "original_max_position": float(
                scaling["original_max_position_embeddings"]),
            "beta_fast": float(scaling.get("beta_fast") or 32.0),
            "beta_slow": float(scaling.get("beta_slow") or 1.0),
            "attention_factor": float(
                scaling.get("attention_factor")
                or (m(scaling["mscale"]) / m(scaling["mscale_all_dim"])
                    if both else m(1.0)))}


def rotary_embedding(x, theta=10000.0, name=None, rotary_dim=None,
                     scaling=None):
    """Rotary position embedding (rotate-half convention) over ``x [..., S,
    D]``, positions 0..S-1 along axis -2. ``rotary_dim`` (default D): the
    first ``rotary_dim`` values of a head are rotated among themselves and
    the rest pass through (HF's ``partial_rotary_factor`` x D). ``scaling``:
    None, or a dict in HF's ``rope_scaling`` keys with ``rope_type`` ``"yarn"``
    (``factor``, ``original_max_position_embeddings``, ``beta_fast`` 32,
    ``beta_slow`` 1, ``attention_factor`` default ``0.1 ln(factor) + 1``):
    the frequencies blended as HF's ``_compute_yarn_parameters`` blends them
    over ``rotary_dim``, cos and sin times ``attention_factor``
    (``ops/decoder_ops.py:yarn_inv_freq``)."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = _out(helper, x.dtype)
    attrs = {"theta": float(theta)}
    if rotary_dim and int(rotary_dim) != int(x.shape[-1]):
        attrs["rotary_dim"] = int(rotary_dim)
    attrs.update(_rope_scaling_attrs(scaling))
    helper.append_op("rotary_embedding", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs=attrs)
    return _var(helper, out)


def latent_qkv(q, kv, k_rope, batch, seq, heads, nope_dim, rope_dim,
               theta=10000.0, name=None, rotate=True, value_dim=None,
               head_dim=None, scaling=None):
    """Latent attention's q, k and v ``[batch, heads, seq, nope_dim +
    rope_dim]`` for ``fused_attention``, in one op, from its three
    up-projections over ``batch x seq`` tokens: ``q [T, heads x (nope_dim +
    rope_dim)]`` laid out ``[every head's q_n | every head's q_r]``, ``kv
    [T, heads x (nope_dim + v)]`` laid out ``[every head's k_n | every
    head's v]`` with ``v`` as wide as a q head, ``k_rope [T, rope_dim]`` the
    one rotary key head that every head shares. The rotary parts are rotated
    (rotate-half, base ``theta``, positions 0..seq-1) and follow their
    head's other part; the op's registered grad puts the cotangents' parts
    back and sums the key head's over the heads
    (``ops/decoder_ops.py:latent_qkv``). ``value_dim``: v's head width where
    it is not a q head's (v is then ``[batch, heads, seq, value_dim]``);
    ``rotate=False``: the rotary parts stay as projected (no positions);
    ``head_dim``: the q / k head written that wide, zero columns behind its
    two parts; ``scaling``: ``rotary_embedding``'s (a YaRN dict: the rotary
    parts turn at the blended frequencies; the factor on the softmax scale
    is the caller's)."""
    helper = LayerHelper("latent_qkv", name=name)
    outs = [_out(helper, x.dtype) for x in (q, kv, kv)]
    attrs = {"batch": int(batch), "seq": int(seq), "heads": int(heads),
             "nope_dim": int(nope_dim), "rope_dim": int(rope_dim),
             "theta": float(theta), **_rope_scaling_attrs(scaling)}
    if not rotate:
        attrs["rotate"] = False
    for key, width in (("value_dim", value_dim), ("head_dim", head_dim)):
        if width and int(width) != int(nope_dim) + int(rope_dim):
            attrs[key] = int(width)
    helper.append_op(
        "latent_qkv", inputs={"Q": [q], "KV": [kv], "KRope": [k_rope]},
        outputs={"OutQ": [outs[0]], "OutK": [outs[1]], "OutV": [outs[2]]},
        attrs=attrs)
    return tuple(_var(helper, o) for o in outs)


def hyper_connection_pre(x, streams, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
                         phi_attr=None, b_attr=None, alpha_attr=None,
                         name=None):
    """The read side of a manifold-constrained hyper-connection (mHC,
    arXiv:2512.24880; ``ops/decoder_ops.py:hyper_connection_pre``) over a
    residual state of ``streams`` streams a token, ``x [T, streams * C]``
    (stream j the columns ``[j C, (j + 1) C)``). Creates the float32
    parameters ``Phi [streams * C, 2 streams + streams^2]``, ``B [2 streams
    + streams^2]`` and ``Alpha [3]``. Returns ``(u, coef)``: ``u [T, C] =
    H_pre X``, the input of the sub-layer's branch, in x's dtype, and the
    token's float32 coefficients ``[T, 2 streams + streams^2]`` = ``[H_pre |
    H_post | H_res row-major]``, which ``hyper_connection_post`` takes.
    ``H_pre = sigmoid``, ``H_post = 2 sigmoid``, ``H_res`` ``iters``
    Sinkhorn-Knopp iterations over ``exp`` of the logits held to ``clamp``,
    each of ``Alpha x (RMSNorm_eps(x) Phi) + B``. The op's registered grad
    keeps x and computes the coefficients again."""
    helper = LayerHelper("hyper_connection_pre", name=name)
    n = int(streams)
    wide, k = int(x.shape[-1]), 2 * n + n * n
    if wide % n:
        raise ValueError(f"hyper_connection_pre: x {tuple(x.shape)} is not "
                         f"{n} streams side by side")
    phi = helper.create_parameter(phi_attr, [wide, k], "float32")
    b = helper.create_parameter(b_attr, [k], "float32", is_bias=True)
    alpha = helper.create_parameter(alpha_attr, [3], "float32")
    u, coef = _out(helper, x.dtype), _out(helper, "float32")
    helper.append_op(
        "hyper_connection_pre",
        inputs={"X": [x], "Phi": [phi], "B": [b], "Alpha": [alpha]},
        outputs={"U": [u], "Coef": [coef]},
        attrs={"streams": n, "iters": int(iters), "eps": float(eps),
               "clamp_min": float(clamp[0]), "clamp_max": float(clamp[1])})
    return _var(helper, u), _var(helper, coef)


def hyper_connection_post(x, y, coef, streams, iters=20, name=None):
    """The write side of a hyper-connection
    (``ops/decoder_ops.py:hyper_connection_post``): the next residual state
    ``H_res X + H_post^T y`` ``[T, streams * C]`` from the state ``x``, the
    branch's output ``y [T, C]`` and ``hyper_connection_pre``'s ``coef``
    (``iters``: the same op's, for the lowering's counter). The op's
    registered grad keeps x, y and coef."""
    helper = LayerHelper("hyper_connection_post", name=name)
    out = _out(helper, x.dtype)
    helper.append_op(
        "hyper_connection_post", inputs={"X": [x], "Y": [y], "Coef": [coef]},
        outputs={"Out": [out]},
        attrs={"streams": int(streams), "iters": int(iters)})
    return _var(helper, out)


def attention_gate(x, gate, name=None):
    """Attention's output gate: ``x [B, heads, S, D]``, the heads' outputs
    as ``fused_attention`` returns them, times ``sigmoid(gate)``, before the
    output projection: ``gate [B * S, heads]`` is one gate a token and head,
    ``gate [B * S, heads * D]`` one a token, head and channel
    (``ops/decoder_ops.py:attention_gate``, float32 inside)."""
    helper = LayerHelper("attention_gate", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("attention_gate", inputs={"X": [x], "Gate": [gate]},
                     outputs={"Out": [out]})
    return _var(helper, out)


def exit_gate_loss(x, ce, steps, entropy_coef=0.0, param_attr=None,
                   bias_attr=None, name=None):
    """A looped model's exit gate and the expected loss over its exit
    distribution (``ops/decoder_ops.py:exit_gate_loss``): ``x [steps * T,
    H]`` the state after every pass, pass-major, ``ce [steps * T, 1]`` each
    pass's per-position cross-entropy; one float32 gate ``Linear(H, 1)``
    shared by the passes. Returns ``(loss [1], expected_ce [1], p [steps *
    T, 1])``: the mean over positions of ``sum_r p_r ce_r - entropy_coef
    H(p)``, of ``sum_r p_r ce_r`` alone, and the exit probabilities."""
    helper = LayerHelper("exit_gate_loss", name=name)
    w = helper.create_parameter(param_attr, [int(x.shape[-1]), 1], "float32")
    b = helper.create_parameter(bias_attr, [1], "float32", is_bias=True)
    loss, expected, p = (_out(helper, "float32") for _ in range(3))
    helper.append_op("exit_gate_loss",
                     inputs={"X": [x], "W": [w], "B": [b], "CE": [ce]},
                     outputs={"Loss": [loss], "ExpectedCE": [expected],
                              "P": [p]},
                     attrs={"steps": int(steps),
                            "entropy_coef": float(entropy_coef)})
    return _var(helper, loss), _var(helper, expected), _var(helper, p)


def swiglu(gate, up, row_scale=None, name=None):
    """``silu(gate) * up``: the gated product of a gated feed-forward layer;
    with ``row_scale [rows]``, each row of it times its scale."""
    helper = LayerHelper("swiglu", name=name)
    out = _out(helper, gate.dtype)
    inputs = {"X": [gate], "Y": [up]}
    if row_scale is not None:
        inputs["Scale"] = [row_scale]
    helper.append_op("swiglu", inputs=inputs, outputs={"Out": [out]})
    return _var(helper, out)


def short_conv(x, seq, kernel_size=3, param_attr=None, name=None,
               bias_attr=False, gated=True, activation=None):
    """The short causal convolution of a hybrid decoder layer, between its
    two projections, one depthwise filter of ``kernel_size`` taps a channel
    (parameter ``[C, kernel_size]``) over sequences of ``seq`` consecutive
    rows, zeros before each sequence's start. ``gated`` (LFM2's): ``x [T,
    3C]`` holds the gates ``B | C`` and the signal ``u`` side by side;
    returns ``C * conv(B * u) [T, C]``. Not gated (a Mamba mixer's): ``x [T,
    C]``, returns ``conv(x)``. ``bias_attr`` (not False) adds a bias ``[C]``
    to the filter's output and ``activation`` (``"silu"``) follows it
    (``ops/decoder_ops.py:short_conv``, which lowers the Pallas kernels on a
    TPU and the composed form elsewhere)."""
    helper = LayerHelper("short_conv", name=name)
    chan = int(x.shape[-1]) // 3 if gated else int(x.shape[-1])
    w = helper.create_parameter(param_attr, [chan, int(kernel_size)], x.dtype)
    inputs = {"X": [x], "W": [w]}
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(
            bias_attr, [chan], x.dtype, is_bias=True)]
    out = _out(helper, x.dtype)
    helper.append_op("short_conv", inputs=inputs, outputs={"Out": [out]},
                     attrs={"seq": int(seq), "gated": bool(gated),
                            "activation": activation or ""})
    return _var(helper, out)


def ssd_scan(x, dt, a, b, c, d, chunk=256, impl="auto", name=None):
    """The state-space scan of a Mamba-2 layer, a head at a time with state
    ``h [N, P]``: ``h_t = exp(dt_t a) h_{t-1} + b_t (x) (dt_t x_t)``, ``y_t =
    c_t h_t + d x_t``, from a zero state at each sequence's start. ``x [B, S,
    heads, P]``, ``dt [B, S, heads]`` (positive), ``a [heads]`` (negative),
    ``b`` / ``c [B, S, N]`` shared by the heads, ``d [heads]``; returns ``y``
    like ``x``. Computed in chunks of ``chunk`` positions
    (``ops/decoder_ops.py:ssd_scan``: under ``impl="auto"`` the Pallas
    kernels on a TPU where they take the shapes, the composed chunked form
    elsewhere; ``"pallas"`` / ``"composed"`` force one)."""
    helper = LayerHelper("ssd_scan", name=name)
    out = _out(helper, x.dtype)
    helper.append_op("ssd_scan",
                     inputs={"X": [x], "Dt": [dt], "A": [a], "B": [b],
                             "C": [c], "D": [d]},
                     outputs={"Y": [out]},
                     attrs={"chunk": int(chunk), "impl": impl})
    return _var(helper, out)


def gated_delta_rule(q, k, v, g, beta, chunk=64, impl="auto", name=None):
    """The gated delta rule of a Gated DeltaNet layer, a value head at a time
    with state ``S [d_k, d_v]`` from zero at each sequence's start: ``S' =
    exp(g_t) S_{t-1}``, ``u_t = beta_t (v_t - S'^T k_t)``, ``S_t = S' + k_t
    u_t^T``, ``o_t = S_t^T q_t``, k and q each over its l2 norm (``x /
    sqrt(sum(x^2) + 1e-6)``) and q over ``sqrt(d_k)`` inside the op. ``q`` /
    ``k [B, S, key heads, d_k]``, ``v [B, S, heads, d_v]`` (value head j reads
    key head ``j // (heads / key heads)``), ``g`` (<= 0) and ``beta [B, S,
    heads]``; returns ``o`` like ``v``. Computed in chunks of ``chunk``
    positions (``ops/decoder_ops.py:gated_delta_rule``: under ``impl="auto"``
    the Pallas kernels on a TPU where they take the shapes, the composed
    chunked form elsewhere; ``"pallas"`` / ``"composed"`` force one). The op
    has a second output, ``States``: the state entering each chunk, for its
    own backward (no gradient flows through it; the layer returns ``Out``)."""
    return _gated_delta_rule_op(
        {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}, v.dtype,
        {"chunk": int(chunk), "impl": impl}, name)


def _gated_delta_rule_op(inputs, dtype, attrs, name):
    helper = LayerHelper("gated_delta_rule", name=name)
    out = _out(helper, dtype)
    states = _out(helper, "float32", stop_gradient=True)
    helper.append_op("gated_delta_rule", inputs=inputs,
                     outputs={"Out": [out], "States": [states]},  # Out first
                     attrs=attrs)
    return _var(helper, out)


def gated_delta_rule_packed(qkv, g, beta, key_heads, key_dim, chunk=64,
                            impl="auto", name=None):
    """``gated_delta_rule`` over q, k and v as one array ``qkv [B, S, 2 *
    key_heads * key_dim + values]`` (q | k | v along the columns, as one
    projection and a short convolution over it write them); the value heads
    are ``g``'s and share what is left of the columns. The same op with one
    operand in the three's place: the Pallas kernels read the array where
    it lies, each head by its column offset, so no q, k or v is cut out of
    it (``ops/pallas_delta.py``); the composed form reads column ranges.
    Returns ``o [B, S, heads, value dim]``."""
    return _gated_delta_rule_op(
        {"QKV": [qkv], "G": [g], "Beta": [beta]}, qkv.dtype,
        {"chunk": int(chunk), "impl": impl, "key_heads": int(key_heads),
         "key_dim": int(key_dim)}, name)


def moe_bias_update(bias, load, rate, name=None):
    """``bias += rate * sign(mean(load) - load)``, written into ``bias``
    itself: the router's selection bias follows the step's expert load
    (``layers.moe_ffn``'s ``aux["bias"]`` / ``aux["load"]``). Append it
    after ``minimize``: a grad op lowers its forward again from the op's
    inputs, so the bias must not change before the backward has run."""
    helper = LayerHelper("moe_bias_update", name=name)
    helper.append_op("moe_bias_update", inputs={"Bias": [bias],
                                                "Load": [load]},
                     outputs={"BiasOut": [bias]}, attrs={"rate": float(rate)})
    return bias


def moe_ffn(x, num_experts, experts_per_token, expert_width, param_attr=None,
            name="moe", experts_held=None, scoring="softmax", norm_topk=False,
            routed_scale=1.0, expert_bias=False, row_budget=None,
            shared_width=None, shared_gate=False, expert_axis=None,
            matmul_tiling=None):
    """A dropless mixture-of-experts feed-forward layer over tokens
    ``x [T, H]``: a float32 router (softmax over the experts, top-k values
    used as they are, or under ``norm_topk`` over their sum, times
    ``routed_scale``), every one of the T x k assignments sent to its expert
    (sort by expert -> grouped matmuls over three stacked weights -> sum of
    each token's k rows; no capacity, nothing dropped), each expert
    ``W_down (silu(W_gate x) * (W_up x))``, its router weight applied to the
    gated product before the down projection.

    ``scoring="sigmoid"``: independent sigmoid scores; with ``expert_bias``
    the k experts are chosen by score + a bias (a float32 state variable
    ``<name>_router_bias [E]``, zero at start, no gradient; ``aux["bias"]``,
    for ``layers.moe_bias_update``) and weighed by the bare score, over the
    chosen scores' sum under ``norm_topk``, times ``routed_scale``.

    ``experts_held=(first, count)``: this layer holds ``count`` of the
    ``num_experts`` experts, from expert ``first`` on -- one chip's share of
    a layer whose experts are split over several. The router keeps all
    ``num_experts`` outputs and the weights of an assignment are what the
    whole layer would give it; the stacked weights hold the held experts
    only, and the output is the held experts' part of each token's sum (an
    assignment to an expert held elsewhere adds nothing here: what an
    exchange would bring is not stood in for; ``expert_axis`` below is the
    layer with its exchange). The row buffers keep all
    T x k rows, so nothing can overflow whatever the routing -- unless the
    layer states a ``row_budget`` R (with ``experts_held`` only): the sort's
    output, the grouped products, the gated product and the combine are then
    sized for R rows, of which an even router fills ``T x k x count /
    num_experts``; the held experts' rows beyond R are dropped (they add
    nothing to their tokens) and counted in ``<name>_dropped_rows [1]``, an
    int32 state variable summed over the steps (``aux["dropped"]``). A
    budget of T x k rows is the layer without one.

    ``expert_axis`` (the counterpart of ``experts_held``: the exchange
    itself, not one device's share without it): the layer holds all its
    experts, split over the mesh axis of that name -- device c of its n
    holds experts ``[c E / n, (c + 1) E / n)``; the three stacked weights
    declare that split on their first dimension
    (``Variable.declare_sharding``: ``CompiledProgram.state_sharding``
    honours it ahead of any ``param_rules``, for the weights and their
    optimizer state) -- with the tokens laid over the same axis. Run under
    ``DistributedStrategy(mesh_shape={axis: n, ...})`` each device routes
    its own tokens over all E experts and sorts their T / n x k rows by
    expert, sends each row to the device that holds its expert
    (``moe_dispatch``), runs the grouped products over the rows it received
    for its E / n experts, sends the results back and sums each token's k
    rows (``moe_combine``); the backward crosses twice more. Shapes are
    static, so a device receives into a buffer of ``row_budget`` rows
    (allowed here: it is the receive buffer's; an even router delivers T x
    k / n; without one the buffer holds all T x k rows and nothing can
    overflow); rows over it are dropped and counted in
    ``<name>_dropped_rows`` (all devices'). The expert weights' gradients
    come through the exchange's transpose and are not summed over the axis;
    the router's, like every replicated parameter's, are. On one device, or
    under a mesh without that axis, nothing crosses and the layer is the
    one without ``expert_axis``, bit for bit; the Program's intermediate
    variables keep the shapes of that layer (under the mesh the sorted
    buffers are n x ``row_budget`` rows).

    ``shared_width``: one shared expert beside the routed ones, a dense
    SwiGLU of that width over every token (``<name>_shared_gate_w`` /
    ``_shared_up_w [H, shared_width]``, ``_shared_down_w [shared_width,
    H]``), added to the routed experts' sum: ungated, or under
    ``shared_gate`` times ``sigmoid(x . w)``, one gate a token
    (``<name>_shared_expert_gate_w [H, 1]``). Under ``experts_held`` every
    share computes it alike: over the shares it counts once.

    Parameters, by name: ``<name>_router_w [H, E]`` float32 and
    ``<name>_gate_w`` / ``<name>_up_w [held, H, width]``, ``<name>_down_w
    [held, width, H]`` in x's dtype; ``param_attr`` supplies the initializer.

    ``matmul_tiling`` ``(m, k, n)``: the rows, contraction and columns a
    grid step of the grouped products' megablox kernels takes, in
    ``ops.decoder_ops.GMM_TILING``'s place (each held to its dimension); the
    composed form does not read it.

    Returns ``(out [T, H], aux)`` with ``aux`` the router's variables:
    ``prob [T, E]`` (the scores), ``logz [T]`` (logsumexp of the logits;
    softmax scoring only), ``index [T, k]`` and ``load [E]`` (assignments
    received by each of the E experts, held here or not, int32), the last
    two without gradient, for the router losses and to be fetched;
    ``dropped`` under a ``row_budget``; ``routed [T, H]``, the routed
    experts' part of ``out`` (all of it without a shared expert).
    """
    from ..initializer import Constant
    from ..layer_helper import ParamAttr
    helper = LayerHelper("moe_ffn", name=name)
    H = int(x.shape[-1])
    E, k, width = int(num_experts), int(experts_per_token), int(expert_width)
    first, held = (0, E) if experts_held is None else map(int, experts_held)
    if not (0 <= first and held >= 1 and first + held <= E):
        raise ValueError(f"moe_ffn: experts_held={experts_held!r} is not a "
                         f"range of the {E} experts")
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"moe_ffn: scoring={scoring!r}")
    if scoring == "softmax" and expert_bias:
        raise NotImplementedError(
            "moe_ffn: expert_bias is built for scoring='sigmoid' only")
    if expert_axis is not None and held != E:
        raise ValueError("moe_ffn: expert_axis splits all the layer's "
                         "experts over a mesh axis; experts_held is one "
                         "device's share without the exchange")
    if row_budget is not None and held == E and expert_axis is None:
        raise ValueError("moe_ffn: a row_budget is for a layer that holds a "
                         "part of its experts (experts_held) or receives "
                         "rows through an exchange (expert_axis)")
    init = ParamAttr._to_attr(param_attr).initializer
    crossed = {} if expert_axis is None else {"expert_axis": str(expert_axis)}

    def param(suffix, shape, dtype, sharding=None):
        return helper.create_parameter(
            ParamAttr(name=f"{name}_{suffix}", initializer=init,
                      sharding=sharding), shape, dtype)

    def op(type, inputs, outputs, attrs=None):
        helper.append_op(type, inputs=inputs, outputs=outputs,
                         attrs=attrs or {})

    weight, prob, logz = (_out(helper, "float32") for _ in range(3))
    index, order, slot, load = (_out(helper, "int32", stop_gradient=True)
                                for _ in range(4))
    aux = {"prob": prob, "index": index, "load": load}
    router_in = {"X": [x], "W": [param("router_w", [H, E], "float32")]}
    routed = {"Weight": [weight], "Index": [index], "Prob": [prob]}
    router_attrs = {"k": k}
    if scoring == "softmax":
        routed["LogZ"] = [logz]
        aux["logz"] = logz
        if norm_topk or routed_scale != 1.0:
            router_attrs.update(norm_topk=bool(norm_topk),
                                scale=float(routed_scale))
    else:
        router_attrs.update(scoring="sigmoid", norm_topk=bool(norm_topk),
                            scale=float(routed_scale))
        if expert_bias:
            aux["bias"] = helper.create_global_variable(
                [E], "float32", persistable=True,
                name=f"{name}_router_bias", initializer=Constant(0.0))
            router_in["Bias"] = [aux["bias"]]
    op("moe_router", router_in, routed, router_attrs)
    rows, row_weight = _out(helper, x.dtype), _out(helper, "float32")
    sorted_to = {"Out": [rows], "RowWeight": [row_weight], "Order": [order],
                 "Slot": [slot], "Count": [load]}
    sort_attrs = {"num_experts": E}
    groups = load           # rows a group of the sorted buffer, in its order
    sum_attrs = {}          # moe_combine's: where the held rows end
    back_with = {}          # moe_combine's further inputs
    if held < E:            # the sort starts at the first held expert
        groups = _out(helper, "int32", stop_gradient=True)
        sorted_to["GroupCount"] = [groups]
        sort_attrs.update(first_expert=first, held=held)
        sum_attrs.update(held=held)
    if crossed:             # the rows each expert received, every device's
        groups, sent = (_out(helper, "int32", stop_gradient=True)
                        for _ in range(2))
        sorted_to.update(GroupCount=[groups], SendCount=[sent])
        back_with["SendCount"] = [sent]
        sort_attrs.update(crossed, recv_rows=int(row_budget or 0))
        sum_attrs.update(crossed, recv_rows=int(row_budget or 0),
                         num_experts=E)
    if row_budget is not None:
        dropped = _out(helper, "int32", stop_gradient=True)
        sorted_to["Dropped"] = [dropped]
        if not crossed:
            sort_attrs.update(rows=int(row_budget))
            sum_attrs.update(rows=int(row_budget))
    op("moe_dispatch", {"X": [x], "Index": [index], "Weight": [weight]},
       sorted_to, sort_attrs)
    if row_budget is not None:
        aux["dropped"] = helper.create_global_variable(
            [1], "int32", persistable=True, name=f"{name}_dropped_rows",
            initializer=Constant(0))
        op("sum", {"X": [aux["dropped"], dropped]},
           {"Out": [aux["dropped"]]})

    def experts(inp, suffix, shape):
        out = _out(helper, x.dtype)
        op("moe_expert_matmul",
           {"X": [inp], "W": [param(suffix, shape, x.dtype,
                                    (expert_axis, None, None)
                                    if crossed else None)],
            "Count": [groups]}, {"Out": [out]},
           dict(crossed, **({"tiling": [int(t) for t in matmul_tiling]}
                            if matmul_tiling else {})))
        return out

    gated = swiglu(experts(rows, "gate_w", [held, H, width]),
                   experts(rows, "up_w", [held, H, width]), row_weight)
    down = experts(gated, "down_w", [held, width, H])
    out = _out(helper, x.dtype)
    op("moe_combine", {"X": [down], "Order": [order], "Slot": [slot],
                       "GroupCount": [groups], **back_with}, {"Out": [out]},
       sum_attrs)
    aux["routed"] = out
    if shared_width:
        def dense(inp, suffix, size):
            return fc(inp, size, bias_attr=False, param_attr=ParamAttr(
                name=f"{name}_shared_{suffix}", initializer=init))
        shared = dense(swiglu(dense(x, "gate_w", int(shared_width)),
                              dense(x, "up_w", int(shared_width))),
                       "down_w", H)
        if shared_gate:
            shared = elementwise_mul(shared, sigmoid(fc(
                x, 1, bias_attr=False, param_attr=ParamAttr(
                    name=f"{name}_shared_expert_gate_w", initializer=init))))
        out = elementwise_add(_var(helper, out), shared)
    blk = helper.main_program.current_block()
    return blk.var(out.name), {n: blk.var(v.name) for n, v in aux.items()}
