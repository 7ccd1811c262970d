"""Per-op numpy-oracle tests (the reference's workhorse OpTest pattern,
python/paddle/fluid/tests/unittests/op_test.py:732,907 — 553 test files).

Table-driven: each case declares op_type / inputs / attrs / expected outputs
computed with numpy, runs through the real executor pipeline via the OpTest
harness, and (for differentiable float ops) checks analytic grads against
central finite differences. Keep tensors tiny: every case compiles a fresh
XLA program.
"""
import numpy as np
import pytest
from scipy import special

from op_test import OpTest


class _T(OpTest):
    def runTest(self):  # pragma: no cover - required by unittest ctor
        pass


def _mk():
    t = _T()
    t.setUp()
    return t


CASES = {}


def case(name, op, inputs, outputs, attrs=None, grad=(), grad_out=None,
         atol=1e-5, rtol=1e-5, max_rel=0.01, no_check=None):
    assert name not in CASES, name
    CASES[name] = dict(op=op, inputs=inputs, attrs=attrs or {},
                       outputs=outputs, grad=list(grad), grad_out=grad_out,
                       atol=atol, rtol=rtol, max_rel=max_rel,
                       no_check=no_check)


R = np.random.RandomState(7)


def f32(*shape, lo=-1.0, hi=1.0):
    return R.uniform(lo, hi, shape).astype("float32")


# ---------------------------------------------------------------------------------
# activations: unary x -> f(x)
# ---------------------------------------------------------------------------------
_XS = f32(2, 3, lo=0.3, hi=0.9)           # positive, away from kinks
_XM = f32(2, 3, lo=-0.9, hi=0.9)          # mixed sign
_XK = np.array([[-0.8, -0.3, 0.4], [0.7, -0.6, 0.9]], "float32")  # no kink pts

_sigmoid = lambda x: 1.0 / (1.0 + np.exp(-x))
_softplus = lambda x: np.log1p(np.exp(x))

ACT = [
    ("relu", _XK, {}, np.maximum(_XK, 0), True),
    ("sigmoid", _XM, {}, _sigmoid(_XM), True),
    ("logsigmoid", _XM, {}, np.log(_sigmoid(_XM)), True),
    ("tanh", _XM, {}, np.tanh(_XM), True),
    ("tanh_shrink", _XM, {}, _XM - np.tanh(_XM), True),
    ("exp", _XM, {}, np.exp(_XM), True),
    ("log", _XS, {}, np.log(_XS), True),
    ("log1p", _XS, {}, np.log1p(_XS), True),
    ("square", _XM, {}, _XM * _XM, True),
    ("sqrt", _XS, {}, np.sqrt(_XS), True),
    ("rsqrt", _XS, {}, 1.0 / np.sqrt(_XS), True),
    ("abs", _XK, {}, np.abs(_XK), True),
    ("reciprocal", _XS, {}, 1.0 / _XS, True),
    ("softplus", _XM, {}, _softplus(_XM), True),
    ("softsign", _XM, {}, _XM / (1 + np.abs(_XM)), True),
    ("softshrink", _XK, {"lambda": 0.2},
     np.where(_XK > 0.2, _XK - 0.2, np.where(_XK < -0.2, _XK + 0.2, 0)), False),
    ("hard_shrink", _XK, {"threshold": 0.2},
     np.where(np.abs(_XK) > 0.2, _XK, 0), False),
    ("thresholded_relu", _XK, {"threshold": 0.5},
     np.where(_XK > 0.5, _XK, 0), False),
    ("relu6", 8 * _XK, {}, np.clip(8 * _XK, 0, 6.0), False),
    ("brelu", 8 * _XK, {"t_min": 0.0, "t_max": 5.0},
     np.clip(8 * _XK, 0.0, 5.0), False),
    ("leaky_relu", _XK, {"alpha": 0.1},
     np.where(_XK >= 0, _XK, 0.1 * _XK), True),
    ("elu", _XK, {"alpha": 1.0},
     np.where(_XK > 0, _XK, np.exp(_XK) - 1), False),
    ("gelu", _XM, {}, 0.5 * _XM * (1 + special.erf(_XM / np.sqrt(2))), True),
    ("swish", _XM, {"beta": 1.0}, _XM * _sigmoid(_XM), True),
    ("hard_swish", _XM, {}, _XM * np.clip(_XM / 6.0 + 0.5, 0, 1), False),
    ("hard_sigmoid", _XM, {}, np.clip(0.2 * _XM + 0.5, 0, 1), False),
    ("mish", _XM, {}, _XM * np.tanh(_softplus(_XM)), True),
    ("stanh", _XM, {"scale_a": 0.67, "scale_b": 1.7159},
     1.7159 * np.tanh(0.67 * _XM), True),
    ("soft_relu", _XM, {}, np.log1p(np.exp(_XM)), True),
    ("pow", _XS, {"factor": 2.0}, _XS ** 2.0, True),
    ("cos", _XM, {}, np.cos(_XM), True),
    ("sin", _XM, {}, np.sin(_XM), True),
    ("acos", _XM, {}, np.arccos(_XM), False),
    ("asin", _XM, {}, np.arcsin(_XM), False),
    ("atan", _XM, {}, np.arctan(_XM), True),
    ("cosh", _XM, {}, np.cosh(_XM), True),
    ("sinh", _XM, {}, np.sinh(_XM), True),
    ("erf", _XM, {}, special.erf(_XM), True),
    ("ceil", _XM * 3, {}, np.ceil(_XM * 3), False),
    ("floor", _XM * 3, {}, np.floor(_XM * 3), False),
    ("round", _XM * 3, {}, np.round(_XM * 3), False),
    ("sign", _XK, {}, np.sign(_XK), False),
]
for op, x, attrs, want, do_grad in ACT:
    case(f"act_{op}", op, {"X": x}, {"Out": want.astype("float32")}, attrs,
         grad=["X"] if do_grad else [])

# ---------------------------------------------------------------------------------
# elementwise binary (+ fluid axis broadcasting)
# ---------------------------------------------------------------------------------
_EX = f32(2, 3, lo=0.5, hi=1.5)
_EY = f32(2, 3, lo=0.5, hi=1.5)
ELEM = [
    ("elementwise_add", _EX + _EY, True),
    ("elementwise_sub", _EX - _EY, True),
    ("elementwise_mul", _EX * _EY, True),
    ("elementwise_div", _EX / _EY, True),
    ("elementwise_min", np.minimum(_EX, _EY), True),
    ("elementwise_max", np.maximum(_EX, _EY), True),
    ("elementwise_pow", _EX ** _EY, True),
    ("elementwise_mod", np.mod(_EX, _EY), False),
    ("elementwise_floordiv", np.floor_divide(_EX, _EY), False),
]
for op, want, do_grad in ELEM:
    case(f"ew_{op[12:]}", op, {"X": _EX, "Y": _EY}, {"Out": want},
         grad=["X", "Y"] if do_grad else [])

# fluid axis-broadcast: X [2,3,4] + Y [3] at axis=1
_BX, _BY = f32(2, 3, 4), f32(3)
case("ew_add_axis_bcast", "elementwise_add", {"X": _BX, "Y": _BY},
     {"Out": _BX + _BY[None, :, None]}, {"axis": 1}, grad=["X", "Y"])
# trailing singleton run: Y [3,1] at axis=1 behaves like [3]
case("ew_mul_trailing1", "elementwise_mul",
     {"X": _BX, "Y": _BY.reshape(3, 1)},
     {"Out": _BX * _BY[None, :, None]}, {"axis": 1})

# ---------------------------------------------------------------------------------
# reductions / cumsum
# ---------------------------------------------------------------------------------
_RX = f32(2, 3, 4, lo=0.5, hi=1.5)
RED = [
    ("reduce_sum", np.sum, True),
    ("reduce_mean", np.mean, True),
    ("reduce_max", np.max, False),
    ("reduce_min", np.min, False),
    ("reduce_prod", np.prod, True),
]
for op, fn, do_grad in RED:
    case(f"red_{op[7:]}", op, {"X": _RX}, {"Out": fn(_RX, axis=1)},
         {"dim": [1]}, grad=["X"] if do_grad else [], max_rel=0.02)
    case(f"red_{op[7:]}_keepall", op, {"X": _RX},
         {"Out": fn(_RX, keepdims=True).astype("float32")},
         {"reduce_all": True, "keep_dim": True})
_BOOL = np.array([[True, False], [True, True]])
case("red_all", "reduce_all", {"X": _BOOL}, {"Out": np.all(_BOOL, axis=1)},
     {"dim": [1]})
case("red_any", "reduce_any", {"X": _BOOL}, {"Out": np.any(_BOOL, axis=1)},
     {"dim": [1]})
case("logsumexp", "logsumexp", {"X": _RX},
     {"Out": special.logsumexp(_RX, axis=(0, 1, 2)).astype("float32")},
     {"reduce_all": True}, grad=["X"])

_CX = f32(2, 5)
case("cumsum", "cumsum", {"X": _CX}, {"Out": np.cumsum(_CX, axis=1)},
     {"axis": 1}, grad=["X"])
_ex = np.concatenate([np.zeros((2, 1), "float32"),
                      np.cumsum(_CX, axis=1)[:, :-1]], axis=1)
case("cumsum_exclusive", "cumsum", {"X": _CX}, {"Out": _ex},
     {"axis": 1, "exclusive": True})
case("cumsum_reverse", "cumsum", {"X": _CX},
     {"Out": np.cumsum(_CX[:, ::-1], axis=1)[:, ::-1]},
     {"axis": 1, "reverse": True}, grad=["X"])
# regression (ADVICE r1): exclusive+reverse must compose
_rev = _CX[:, ::-1]
_exr = np.concatenate([np.zeros((2, 1), "float32"),
                       np.cumsum(_rev, axis=1)[:, :-1]], axis=1)[:, ::-1]
case("cumsum_excl_rev", "cumsum", {"X": _CX}, {"Out": _exr},
     {"axis": 1, "exclusive": True, "reverse": True})

# ---------------------------------------------------------------------------------
# matmul family / losses / norms
# ---------------------------------------------------------------------------------
_MA, _MB = f32(2, 3), f32(3, 4)
case("matmul", "matmul", {"X": _MA, "Y": _MB}, {"Out": _MA @ _MB},
     grad=["X", "Y"])
case("matmul_transpose", "matmul", {"X": _MA.T.copy(), "Y": _MB.T.copy()},
     {"Out": _MA @ _MB}, {"transpose_X": True, "transpose_Y": True})
_M3 = f32(2, 2, 3)
case("matmul_alpha", "matmul", {"X": _MA, "Y": _MB},
     {"Out": 2.5 * (_MA @ _MB)}, {"alpha": 2.5})
case("bmm", "bmm", {"X": _M3, "Y": f32(2, 3, 2)},
     {"Out": np.matmul(_M3, CASES and f32(0))} if False else
     {"Out": None}, grad=[])
del CASES["bmm"]
_B1, _B2 = f32(2, 2, 3), f32(2, 3, 2)
case("bmm", "bmm", {"X": _B1, "Y": _B2}, {"Out": np.matmul(_B1, _B2)},
     grad=["X", "Y"])
case("dot", "dot", {"X": _MA, "Y": _MA + 1},
     {"Out": np.sum(_MA * (_MA + 1), axis=-1, keepdims=True)}, grad=["X", "Y"])
_MU = f32(2, 3, 4)
_MW = f32(12, 5)
case("mul", "mul", {"X": _MU, "Y": _MW},
     {"Out": (_MU.reshape(2, 12) @ _MW).reshape(2, 5)},
     {"x_num_col_dims": 1, "y_num_col_dims": 1}, grad=["X", "Y"])

_LG = f32(3, 5)


def _np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


# (no grad check for softmax: mean(softmax) is constant 1/V per row, so the
#  true gradient is identically zero -- degenerate objective; the softmax grad
#  path is exercised through softmax_xent and log_softmax below.)
case("softmax", "softmax", {"X": _LG}, {"Out": _np_softmax(_LG)})
case("log_softmax", "log_softmax", {"X": _LG},
     {"Out": np.log(_np_softmax(_LG))}, grad=["X"])

_LAB = np.array([[1], [0], [4]], "int64")
_sm = _np_softmax(_LG)
_loss = -np.log(_sm[np.arange(3), _LAB[:, 0]])[:, None]
case("softmax_xent", "softmax_with_cross_entropy",
     {"Logits": _LG, "Label": _LAB},
     {"Softmax": _sm, "Loss": _loss}, grad=["Logits"], grad_out="Loss")
_SOFTL = _np_softmax(f32(3, 5))
case("softmax_xent_soft", "softmax_with_cross_entropy",
     {"Logits": _LG, "Label": _SOFTL},
     {"Softmax": _sm, "Loss": -np.sum(_SOFTL * np.log(_sm), axis=1,
                                      keepdims=True)},
     {"soft_label": True}, grad=["Logits"], grad_out="Loss")

_PROB = _np_softmax(f32(3, 4))
_LAB2 = np.array([[0], [2], [3]], "int64")
case("cross_entropy", "cross_entropy", {"X": _PROB, "Label": _LAB2},
     {"Y": -np.log(_PROB[np.arange(3), _LAB2[:, 0]])[:, None]},
     grad=["X"], grad_out="Y")

_SX, _SL = f32(2, 3), (R.rand(2, 3) > 0.5).astype("float32")
case("sigmoid_ce", "sigmoid_cross_entropy_with_logits",
     {"X": _SX, "Label": _SL},
     {"Out": np.maximum(_SX, 0) - _SX * _SL + np.log1p(np.exp(-np.abs(_SX)))},
     grad=["X"])

case("mean_op", "mean", {"X": _RX}, {"Out": np.mean(_RX).reshape(1)},
     grad=["X"])
_HA, _HB = f32(2, 3), f32(2, 3) + 2.0  # |r| ~ 2 > delta=1, away from kink
_hr = _HB - _HA
case("huber", "huber_loss", {"X": _HA, "Y": _HB},
     {"Out": np.where(np.abs(_hr) <= 1.0, 0.5 * _hr * _hr,
                      np.abs(_hr) - 0.5),
      "Residual": _hr}, {"delta": 1.0}, grad=["X"], grad_out="Out")
case("sqerr", "square_error_cost", {"X": _HA, "Y": _HB},
     {"Out": (_HA - _HB) ** 2}, grad=["X", "Y"])
case("log_loss", "log_loss",
     {"Predicted": _PROB[:, :1].copy(), "Labels": _LAB2[:, :1].astype("float32") / 3},
     {"Loss": -(_LAB2[:, :1] / 3) * np.log(_PROB[:, :1] + 1e-4) -
      (1 - _LAB2[:, :1] / 3) * np.log(1 - _PROB[:, :1] + 1e-4)},
     {"epsilon": 1e-4}, grad=["Predicted"], grad_out="Loss")

_CA, _CB = f32(3, 4, lo=0.2), f32(3, 4, lo=0.2)
_can = np.sqrt((_CA ** 2).sum(-1, keepdims=True))
_cbn = np.sqrt((_CB ** 2).sum(-1, keepdims=True))
case("cos_sim", "cos_sim", {"X": _CA, "Y": _CB},
     {"Out": (_CA * _CB).sum(-1, keepdims=True) / (_can * _cbn),
      "XNorm": _can, "YNorm": _cbn}, grad=["X", "Y"], grad_out="Out")
case("l2_normalize", "l2_normalize", {"X": _CA},
     {"Out": _CA / np.sqrt((_CA ** 2).sum(-1, keepdims=True) + 1e-12),
      "Norm": np.sqrt((_CA ** 2).sum(-1, keepdims=True) + 1e-12)},
     {"axis": -1}, grad=["X"], grad_out="Out")
case("p_norm", "p_norm", {"X": _CA},
     {"Out": (np.abs(_CA) ** 2).sum(-1) ** 0.5}, {"porder": 2.0, "axis": -1},
     grad=["X"])
case("squared_l2_norm", "squared_l2_norm", {"X": _CA},
     {"Out": (_CA ** 2).sum().reshape(1)}, grad=["X"])

# ---------------------------------------------------------------------------------
# tensor manipulation
# ---------------------------------------------------------------------------------
_TX = f32(2, 3, 4)
case("reshape", "reshape", {"X": _TX}, {"Out": _TX.reshape(2, 12)},
     {"shape": [2, 12]}, grad=["X"])
case("reshape_infer", "reshape2", {"X": _TX}, {"Out": _TX.reshape(8, 3)},
     {"shape": [-1, 0]})  # 0 copies dim 1 (=3), -1 infers 24/3=8
case("transpose", "transpose", {"X": _TX},
     {"Out": _TX.transpose(1, 0, 2)}, {"axis": [1, 0, 2]}, grad=["X"])
case("flatten", "flatten", {"X": _TX}, {"Out": _TX.reshape(2, 12)},
     {"axis": 1})
case("squeeze", "squeeze", {"X": _TX[:, :1]}, {"Out": _TX[:, 0]},
     {"axes": [1]}, grad=["X"])
case("unsqueeze", "unsqueeze", {"X": _TX}, {"Out": _TX[:, None]},
     {"axes": [1]}, grad=["X"])
case("concat", "concat",
     {"X": [("cc_a", _TX), ("cc_b", _TX + 1)]},
     {"Out": np.concatenate([_TX, _TX + 1], axis=1)}, {"axis": 1},
     grad=["cc_a", "cc_b"])
case("split", "split", {"X": _TX},
     {"Out": [("sp_a", _TX[:, :1]), ("sp_b", _TX[:, 1:])]},
     {"axis": 1, "sections": [1, 2]}, grad=["X"], grad_out="sp_b")
case("stack", "stack", {"X": [("st_a", _TX), ("st_b", _TX + 1)]},
     {"Y": np.stack([_TX, _TX + 1], axis=0)}, {"axis": 0},
     grad=["st_a"], grad_out="Y")
case("unstack", "unstack", {"X": _TX[:2]},
     {"Y": [("us_a", _TX[0]), ("us_b", _TX[1])]}, {"axis": 0})
case("slice", "slice", {"Input": _TX}, {"Out": _TX[:, 1:3]},
     {"axes": [1], "starts": [1], "ends": [3]}, grad=["Input"])
case("slice_neg", "slice", {"Input": _TX}, {"Out": _TX[:, -2:]},
     {"axes": [1], "starts": [-2], "ends": [1000]})
case("strided_slice", "strided_slice", {"Input": _TX},
     {"Out": _TX[:, ::2]}, {"axes": [1], "starts": [0], "ends": [3],
                            "strides": [2]}, grad=["Input"])
_IDX = np.array([1, 0, 1, 0], "int64")
case("gather", "gather", {"X": _TX, "Index": _IDX},
     {"Out": _TX[_IDX]}, grad=["X"])
_NDI = np.array([[0, 1], [1, 2]], "int64")
case("gather_nd", "gather_nd", {"X": _TX, "Index": _NDI},
     {"Out": _TX[[0, 1], [1, 2]]}, grad=["X"])
_SCX = f32(4, 3)
_SCU = f32(2, 3)
_SCI = np.array([1, 3], "int64")
_scw = _SCX.copy()
_scw[_SCI] = _SCU
case("scatter_overwrite", "scatter",
     {"X": _SCX, "Ids": _SCI, "Updates": _SCU}, {"Out": _scw},
     {"overwrite": True}, grad=["Updates"])
_sca = _SCX.copy()
np.add.at(_sca, _SCI, _SCU)
case("scatter_add", "scatter", {"X": _SCX, "Ids": _SCI, "Updates": _SCU},
     {"Out": _sca}, {"overwrite": False}, grad=["X", "Updates"])
_snd = _SCX.copy()
np.add.at(_snd, ([0, 2],), _SCU)
case("scatter_nd_add", "scatter_nd_add",
     {"X": _SCX, "Index": np.array([[0], [2]], "int64"), "Updates": _SCU},
     {"Out": _snd}, grad=["X", "Updates"])
_W = f32(10, 4)
_WI = np.array([[1], [3], [9]], "int64")
case("lookup_table", "lookup_table", {"W": _W, "Ids": _WI},
     {"Out": _W[_WI[:, 0]]}, grad=["W"])
case("lookup_table_pad", "lookup_table", {"W": _W, "Ids": _WI},
     {"Out": _W[_WI[:, 0]] * (np.asarray(_WI) != 3)},
     {"padding_idx": 3})
case("embedding_bag", "embedding_bag",
     {"W": _W, "Ids": np.array([[1, 2], [3, 4]], "int64")},
     {"Out": _W[[1, 2]].sum(0)[None].repeat(2, 0) * 0 +
      np.stack([_W[[1, 2]].sum(0), _W[[3, 4]].sum(0)])},
     {"mode": "sum"}, grad=["W"])
case("expand", "expand", {"X": _TX}, {"Out": np.tile(_TX, (2, 1, 1))},
     {"expand_times": [2, 1, 1]}, grad=["X"])
case("expand_as", "expand_as",
     {"X": _TX[:1], "target_tensor": _TX},
     {"Out": np.tile(_TX[:1], (2, 1, 1))})
case("tile", "tile", {"X": _TX}, {"Out": np.tile(_TX, (1, 2, 1))},
     {"repeat_times": [1, 2, 1]}, grad=["X"])
case("pad", "pad", {"X": _MA},
     {"Out": np.pad(_MA, [(1, 0), (0, 2)], constant_values=0.5)},
     {"paddings": [1, 0, 0, 2], "pad_value": 0.5}, grad=["X"])
_P4 = f32(1, 2, 3, 3)
case("pad2d", "pad2d", {"X": _P4},
     {"Out": np.pad(_P4, [(0, 0), (0, 0), (1, 1), (2, 0)])},
     {"paddings": [1, 1, 2, 0], "mode": "constant"}, grad=["X"])
case("pad2d_reflect", "pad2d", {"X": _P4},
     {"Out": np.pad(_P4, [(0, 0), (0, 0), (1, 1), (1, 1)], mode="reflect")},
     {"paddings": [1, 1, 1, 1], "mode": "reflect"})
_TKX = np.array([[0.1, 0.9, 0.5], [0.7, 0.2, 0.4]], "float32")
case("top_k", "top_k", {"X": _TKX},
     {"Out": np.sort(_TKX, axis=-1)[:, ::-1][:, :2],
      "Indices": np.argsort(-_TKX, axis=-1)[:, :2]}, {"k": 2})
case("arg_max", "arg_max", {"X": _TKX}, {"Out": np.argmax(_TKX, -1)},
     {"axis": -1})
case("arg_min", "arg_min", {"X": _TKX}, {"Out": np.argmin(_TKX, -1)},
     {"axis": -1})
case("argsort", "argsort", {"X": _TKX},
     {"Out": np.sort(_TKX, -1), "Indices": np.argsort(_TKX, -1)},
     {"axis": -1})
case("argsort_desc", "argsort", {"X": _TKX},
     {"Out": -np.sort(-_TKX, -1), "Indices": np.argsort(-_TKX, -1)},
     {"axis": -1, "descending": True})
case("index_select", "index_select",
     {"X": _TX, "Index": np.array([0, 2], "int64")},
     {"Out": _TX[:, [0, 2]]}, {"dim": 1}, grad=["X"])
case("roll", "roll", {"X": _MA}, {"Out": np.roll(_MA, 1, axis=1)},
     {"shifts": [1], "axis": [1]}, grad=["X"])
case("flip", "flip", {"X": _MA}, {"Out": _MA[:, ::-1]}, {"axis": [1]},
     grad=["X"])
case("reverse", "reverse", {"X": _MA}, {"Out": _MA[::-1]}, {"axis": [0]})
case("label_smooth", "label_smooth", {"X": _SOFTL},
     {"Out": 0.9 * _SOFTL + 0.1 / 5}, {"epsilon": 0.1}, grad=["X"])
case("diag", "diag", {"Diagonal": f32(3)}, {"Out": None})
CASES["diag"]["outputs"] = {"Out": np.diag(CASES["diag"]["inputs"]["Diagonal"])}
case("eye", "eye", {}, {"Out": np.eye(3, 4, dtype="float32")},
     {"num_rows": 3, "num_columns": 4, "dtype": "float32"})
case("shard_index", "shard_index",
     {"X": np.array([[1], [6], [12], [19]], "int64")},
     {"Out": np.array([[-1], [-1], [2], [-1]], "int64")},
     {"index_num": 20, "nshards": 4, "shard_id": 2, "ignore_value": -1})

# ---------------------------------------------------------------------------------
# creation / cast / clip / logic / compare
# ---------------------------------------------------------------------------------
case("fill_constant", "fill_constant", {},
     {"Out": np.full((2, 3), 2.5, "float32")},
     {"shape": [2, 3], "value": 2.5, "dtype": "float32"})
case("fill_any_like", "fill_any_like", {"X": _MA},
     {"Out": np.full_like(_MA, 7.0)}, {"value": 7.0})
case("fill_zeros_like", "fill_zeros_like", {"X": _MA},
     {"Out": np.zeros_like(_MA)})
case("fill_bsl", "fill_constant_batch_size_like", {"Input": _TX},
     {"Out": np.full((2, 5), 1.5, "float32")},
     {"shape": [-1, 5], "value": 1.5, "dtype": "float32",
      "input_dim_idx": 0, "output_dim_idx": 0})
case("assign", "assign", {"X": _MA}, {"Out": _MA})
case("assign_value", "assign_value", {},
     {"Out": np.arange(6, dtype="float32").reshape(2, 3)},
     {"values": list(range(6)), "shape": [2, 3], "dtype": "float32"})
case("cast", "cast", {"X": _MA}, {"Out": _MA.astype("int32")},
     {"out_dtype": "int32"})
case("scale_op", "scale", {"X": _MA}, {"Out": _MA * 3 + 1},
     {"scale": 3.0, "bias": 1.0}, grad=["X"])
case("scale_bias_first", "scale", {"X": _MA}, {"Out": (_MA + 1) * 3},
     {"scale": 3.0, "bias": 1.0, "bias_after_scale": False})
case("sum3", "sum",
     {"X": [("sm_a", _MA), ("sm_b", _MA + 1), ("sm_c", _MA * 2)]},
     {"Out": _MA + _MA + 1 + _MA * 2}, grad=["sm_a", "sm_c"])
case("increment", "increment", {"X": np.array([3.0], "float32")},
     {"Out": np.array([4.5], "float32")}, {"step": 1.5})
case("clip_op", "clip", {"X": _MA}, {"Out": np.clip(_MA, -0.4, 0.4)},
     {"min": -0.4, "max": 0.4})
_CN = f32(3, 3)
_cnn = np.sqrt((_CN ** 2).sum())
case("clip_by_norm", "clip_by_norm", {"X": _CN},
     {"Out": _CN * (0.5 / _cnn) if _cnn > 0.5 else _CN}, {"max_norm": 0.5})
case("shape_op", "shape", {"Input": _TX},
     {"Out": np.array([2, 3, 4], "int32")})
case("range_op", "range", {},
     {"Out": np.arange(1.0, 7.0, 2.0, dtype="float32")},
     {"start": 1.0, "end": 7.0, "step": 2.0, "dtype": "float32"})
case("linspace", "linspace", {},
     {"Out": np.linspace(0, 1, 5).astype("float32")},
     {"start": 0.0, "stop": 1.0, "num": 5})
_OH = np.array([[1], [3]], "int64")
case("one_hot", "one_hot", {"X": _OH},
     {"Out": np.eye(5, dtype="float32")[[1, 3]]}, {"depth": 5})
case("one_hot_v2", "one_hot_v2", {"X": _OH[:, 0]},
     {"Out": np.eye(5, dtype="float32")[[1, 3]]}, {"depth": 5})
_CPA, _CPB = f32(2, 3), f32(2, 3)
for op, fn in [("less_than", np.less), ("less_equal", np.less_equal),
               ("greater_than", np.greater),
               ("greater_equal", np.greater_equal),
               ("equal", np.equal), ("not_equal", np.not_equal)]:
    case(f"cmp_{op}", op, {"X": _CPA, "Y": _CPB}, {"Out": fn(_CPA, _CPB)})
_LA = np.array([True, False, True])
_LB = np.array([True, True, False])
case("logical_and", "logical_and", {"X": _LA, "Y": _LB},
     {"Out": _LA & _LB})
case("logical_or", "logical_or", {"X": _LA, "Y": _LB}, {"Out": _LA | _LB})
case("logical_xor", "logical_xor", {"X": _LA, "Y": _LB}, {"Out": _LA ^ _LB})
case("logical_not", "logical_not", {"X": _LA}, {"Out": ~_LA})
case("isfinite", "isfinite",
     {"X": np.array([1.0, np.inf], "float32")},
     {"Out": np.array([False])})
case("where_op", "where",
     {"Condition": _LA[:3], "X": f32(3), "Y": f32(3)}, {"Out": None},
     grad=["X", "Y"])
CASES["where_op"]["outputs"] = {"Out": np.where(
    _LA[:3], CASES["where_op"]["inputs"]["X"],
    CASES["where_op"]["inputs"]["Y"])}

# ---------------------------------------------------------------------------------
# nn ops
# ---------------------------------------------------------------------------------
_CI = f32(1, 2, 5, 5)
_CF = f32(3, 2, 3, 3)


def _np_conv2d(x, w, stride=1, pad=0):
    n, ci, h, wd = x.shape
    co, _, kh, kw = w.shape
    xp = np.pad(x, [(0, 0), (0, 0), (pad, pad), (pad, pad)])
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wd + 2 * pad - kw) // stride + 1
    out = np.zeros((n, co, oh, ow), "float32")
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride:i * stride + kh,
                       j * stride:j * stride + kw]
            out[:, :, i, j] = np.einsum("ncij,ocij->no", patch, w)
    return out


case("conv2d", "conv2d", {"Input": _CI, "Filter": _CF},
     {"Output": _np_conv2d(_CI, _CF, 1, 1)},
     {"strides": [1, 1], "paddings": [1, 1]}, grad=["Input", "Filter"],
     grad_out="Output", atol=1e-4, rtol=1e-4, max_rel=0.02)
case("conv2d_stride2", "conv2d", {"Input": _CI, "Filter": _CF},
     {"Output": _np_conv2d(_CI, _CF, 2, 0)},
     {"strides": [2, 2], "paddings": [0, 0]}, atol=1e-4, rtol=1e-4)

_PX = f32(1, 2, 4, 4)
case("pool2d_max", "pool2d", {"X": _PX},
     {"Out": _PX.reshape(1, 2, 2, 2, 2, 2).max(axis=(3, 5))},
     {"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2]})
case("pool2d_avg", "pool2d", {"X": _PX},
     {"Out": _PX.reshape(1, 2, 2, 2, 2, 2).mean(axis=(3, 5))},
     {"pooling_type": "avg", "ksize": [2, 2], "strides": [2, 2]},
     grad=["X"])
case("pool2d_global", "pool2d", {"X": _PX},
     {"Out": _PX.max(axis=(2, 3), keepdims=True)},
     {"pooling_type": "max", "global_pooling": True})

_BNX = f32(2, 3, 2, 2)
_BNM = np.array([0.1, -0.2, 0.3], "float32")
_BNV = np.array([1.1, 0.9, 1.3], "float32")
_BNS = np.array([1.5, 0.8, 1.0], "float32")
_BNB = np.array([0.0, 0.1, -0.1], "float32")
_bny = ((_BNX - _BNM[None, :, None, None]) /
        np.sqrt(_BNV[None, :, None, None] + 1e-5) *
        _BNS[None, :, None, None] + _BNB[None, :, None, None])
case("batch_norm_infer", "batch_norm",
     {"X": _BNX, "Scale": _BNS, "Bias": _BNB, "Mean": _BNM,
      "Variance": _BNV},
     {"Y": _bny, "MeanOut": _BNM, "VarianceOut": _BNV},
     {"is_test": True, "epsilon": 1e-5}, grad=["X"], grad_out="Y")

_LNX = f32(2, 6)
_lnm = _LNX.mean(1, keepdims=True)
_lnv = ((_LNX - _lnm) ** 2).mean(1, keepdims=True)
_LNS, _LNB = f32(6), f32(6)
case("layer_norm", "layer_norm",
     {"X": _LNX, "Scale": _LNS, "Bias": _LNB},
     {"Y": (_LNX - _lnm) / np.sqrt(_lnv + 1e-5) * _LNS + _LNB,
      "Mean": _lnm.reshape(2), "Variance": _lnv.reshape(2)},
     {"begin_norm_axis": 1, "epsilon": 1e-5}, grad=["X", "Scale", "Bias"],
     grad_out="Y", max_rel=0.02)

_GNX = f32(1, 4, 2, 2)
_gng = _GNX.reshape(1, 2, 2, 2, 2)
_gnm = _gng.mean(axis=(2, 3, 4), keepdims=True)
_gnv = ((_gng - _gnm) ** 2).mean(axis=(2, 3, 4), keepdims=True)
_gny = ((_gng - _gnm) / np.sqrt(_gnv + 1e-5)).reshape(1, 4, 2, 2)
case("group_norm", "group_norm",
     {"X": _GNX, "Scale": np.ones(4, "float32"),
      "Bias": np.zeros(4, "float32")},
     {"Y": _gny}, {"groups": 2, "epsilon": 1e-5},
     no_check=["Mean", "Variance"])

_INX = f32(2, 3, 4, 4)
_inm = _INX.mean(axis=(2, 3), keepdims=True)
_inv = ((_INX - _inm) ** 2).mean(axis=(2, 3), keepdims=True)
case("instance_norm", "instance_norm",
     {"X": _INX, "Scale": np.ones(3, "float32"),
      "Bias": np.zeros(3, "float32")},
     {"Y": (_INX - _inm) / np.sqrt(_inv + 1e-5)}, {"epsilon": 1e-5},
     no_check=["SavedMean", "SavedVariance"])

case("dropout_infer", "dropout", {"X": _MA},
     {"Out": _MA * 0.6}, {"dropout_prob": 0.4, "is_test": True},
     no_check=["Mask"])
case("dropout_infer_upscale", "dropout", {"X": _MA},
     {"Out": _MA},
     {"dropout_prob": 0.4, "is_test": True,
      "dropout_implementation": "upscale_in_train"},
     no_check=["Mask"], grad=["X"])
_PRX = _XK
case("prelu_all", "prelu",
     {"X": _PRX, "Alpha": np.array([0.25], "float32")},
     {"Out": np.where(_PRX > 0, _PRX, 0.25 * _PRX)}, {"mode": "all"},
     grad=["X", "Alpha"])
_NIX = f32(1, 1, 2, 2)
case("nearest_interp", "nearest_interp", {"X": _NIX},
     {"Out": _NIX.repeat(2, axis=2).repeat(2, axis=3)},
     {"out_h": 4, "out_w": 4})

# ---------------------------------------------------------------------------------
# sequence ops (padded + Length convention)
# ---------------------------------------------------------------------------------
_SQX = f32(2, 4, 3)
_SQL = np.array([2, 4], "int64")
_sqm = (np.arange(4)[None, :] < _SQL[:, None]).astype("float32")
case("seq_mask", "sequence_mask", {"X": _SQL},
     {"Y": (np.arange(5)[None, :] < _SQL[:, None]).astype("int64")},
     {"maxlen": 5})
case("seq_pool_sum", "sequence_pool", {"X": _SQX, "Length": _SQL},
     {"Out": (_SQX * _sqm[:, :, None]).sum(1)}, {"pooltype": "SUM"},
     grad=["X"])
case("seq_pool_avg", "sequence_pool", {"X": _SQX, "Length": _SQL},
     {"Out": (_SQX * _sqm[:, :, None]).sum(1) / _SQL[:, None]},
     {"pooltype": "AVERAGE"})
_sqmax = np.where(_sqm[:, :, None] > 0, _SQX, -1e9).max(1)
case("seq_pool_max", "sequence_pool", {"X": _SQX, "Length": _SQL},
     {"Out": _sqmax}, {"pooltype": "MAX"})
_sqrev = _SQX.copy()
_sqrev[0, :2] = _SQX[0, 1::-1]
_sqrev[1] = _SQX[1, ::-1]
case("seq_reverse", "sequence_reverse", {"X": _SQX, "Length": _SQL},
     {"Y": _sqrev})
_sqsx = f32(2, 4)
_sqsm = np.where(_sqm > 0, _sqsx, -1e9)
case("seq_softmax", "sequence_softmax", {"X": _sqsx, "Length": _SQL},
     {"Out": _np_softmax(_sqsm) * _sqm})
case("seq_concat", "sequence_concat",
     {"X": [("sq_a", _SQX), ("sq_b", _SQX + 1)]},
     {"Out": np.concatenate([_SQX, _SQX + 1], axis=-1)})
case("seq_expand", "sequence_expand",
     {"X": _MA, "Length": np.array([2, 1], "int64")},
     {"Out": _MA[[0, 0, 1]]}, {"ref_lengths": [2, 1]})
case("seq_expand_times", "sequence_expand",
     {"X": _MA, "Length": np.array([2, 2], "int64")},
     {"Out": _MA.repeat(2, axis=0)}, {"expand_times": 2})

# ---------------------------------------------------------------------------------
# detection ops
# ---------------------------------------------------------------------------------
_BOXA = np.array([[0, 0, 2, 2], [1, 1, 3, 3]], "float32")
_BOXB = np.array([[0, 0, 2, 2], [2, 2, 4, 4]], "float32")
_iou = np.array([[1.0, 0.0], [1.0 / 7.0, 1.0 / 7.0]], "float32")
case("iou_similarity", "iou_similarity", {"X": _BOXA, "Y": _BOXB},
     {"Out": _iou}, atol=1e-4)
_prior = np.array([[0, 0, 2, 2], [1, 1, 3, 3]], "float32")
_target = np.array([[0.5, 0.5, 1.5, 2.0], [1, 1, 2, 3]], "float32")
_pw = _prior[:, 2] - _prior[:, 0]
_ph = _prior[:, 3] - _prior[:, 1]
_pcx = _prior[:, 0] + 0.5 * _pw
_pcy = _prior[:, 1] + 0.5 * _ph
_tw = _target[:, 2] - _target[:, 0]
_th = _target[:, 3] - _target[:, 1]
_tcx = _target[:, 0] + 0.5 * _tw
_tcy = _target[:, 1] + 0.5 * _th
_enc = np.stack([(_tcx - _pcx) / _pw, (_tcy - _pcy) / _ph,
                 np.log(_tw / _pw), np.log(_th / _ph)], axis=1)
case("box_coder_encode", "box_coder",
     {"PriorBox": _prior, "TargetBox": _target},
     {"OutputBox": _enc.astype("float32")},
     {"code_type": "encode_center_size"})

# ---------------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(CASES))
def test_op_output(name):
    c = CASES[name]
    t = _mk()
    t.op_type = c["op"]
    t.inputs = c["inputs"]
    t.outputs = c["outputs"]
    t.attrs = c["attrs"]
    t.check_output(atol=c["atol"], rtol=c["rtol"], no_check_set=c["no_check"])


GRAD_CASES = sorted(n for n, c in CASES.items() if c["grad"])


@pytest.mark.parametrize("name", GRAD_CASES)
def test_op_grad(name):
    c = CASES[name]
    t = _mk()
    t.op_type = c["op"]
    t.inputs = c["inputs"]
    t.outputs = c["outputs"]
    t.attrs = c["attrs"]
    out = c["grad_out"]
    if out is None:
        out = next(iter(c["outputs"]))
    t.check_grad(c["grad"], out, max_relative_error=c["max_rel"])


# ---------------------------------------------------------------------------------
# ops that need custom checks (random, stateful, multi-output indices)
# ---------------------------------------------------------------------------------


def _run_single_op(op_type, inputs, attrs, out_slots):
    import paddle_tpu as fluid
    main = fluid.Program()
    main.random_seed = 42
    with fluid.program_guard(main, fluid.Program()):
        block = main.global_block()
        in_io, feed = {}, {}
        for slot, arr in inputs.items():
            arr = np.asarray(arr)
            block.create_var(slot, arr.shape, str(arr.dtype), is_data=True)
            in_io[slot] = [slot]
            feed[slot] = arr
        out_io = {s: [s + "@O"] for s in out_slots}
        block.append_op(op_type, inputs=in_io, outputs=out_io, attrs=attrs)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        return exe.run(main, feed=feed,
                       fetch_list=[s + "@O" for s in out_slots])


def test_gaussian_random_moments():
    out, = _run_single_op("gaussian_random", {},
                          {"shape": [2000], "mean": 1.0, "std": 2.0,
                           "dtype": "float32"}, ["Out"])
    assert abs(out.mean() - 1.0) < 0.2 and abs(out.std() - 2.0) < 0.2


def test_uniform_random_range():
    out, = _run_single_op("uniform_random", {},
                          {"shape": [1000], "min": -3.0, "max": 5.0,
                           "dtype": "float32"}, ["Out"])
    assert out.min() >= -3.0 and out.max() <= 5.0
    assert abs(out.mean() - 1.0) < 0.5


def test_truncated_gaussian_bounds():
    out, = _run_single_op("truncated_gaussian_random", {},
                          {"shape": [1000], "mean": 0.0, "std": 1.0,
                           "dtype": "float32"}, ["Out"])
    assert np.abs(out).max() <= 2.01


def test_randint_range():
    out, = _run_single_op("randint", {},
                          {"shape": [500], "low": 2, "high": 9,
                           "dtype": "int32"}, ["Out"])
    assert out.min() >= 2 and out.max() < 9


def test_accuracy_op():
    idx = np.array([[1, 2], [0, 3], [4, 5]], "int64")
    lab = np.array([[2], [1], [4]], "int64")
    acc, correct, total = _run_single_op(
        "accuracy", {"Indices": idx, "Label": lab}, {},
        ["Accuracy", "Correct", "Total"])
    np.testing.assert_allclose(acc, [2.0 / 3.0], rtol=1e-6)
    assert correct[0] == 2 and total[0] == 3


def test_auc_op():
    pred = np.array([[0.2, 0.8], [0.9, 0.1], [0.4, 0.6], [0.7, 0.3]],
                    "float32")
    label = np.array([[1], [0], [1], [0]], "int64")
    nt = 255
    auc, pos, neg = _run_single_op(
        "auc", {"Predict": pred, "Label": label,
                "StatPos": np.zeros(nt + 1, "float32"),
                "StatNeg": np.zeros(nt + 1, "float32")},
        {"num_thresholds": nt}, ["AUC", "StatPosOut", "StatNegOut"])
    np.testing.assert_allclose(float(auc[0]), 1.0, atol=1e-3)
    assert pos.sum() == 2 and neg.sum() == 2


def _optimizer_case(op, ins, attrs, outs_expected, out_slots):
    got = _run_single_op(op, ins, attrs, out_slots)
    for g, (slot, want) in zip(got, outs_expected.items()):
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{op}: {slot}")


def test_sgd_op():
    p, g = f32(4), f32(4)
    lr = np.array([0.1], "float32")
    _optimizer_case("sgd", {"Param": p, "Grad": g, "LearningRate": lr}, {},
                    {"ParamOut": p - 0.1 * g}, ["ParamOut"])


def test_momentum_op():
    p, g, v = f32(4), f32(4), f32(4)
    lr = np.array([0.1], "float32")
    v_out = 0.9 * v + g
    _optimizer_case("momentum",
                    {"Param": p, "Grad": g, "Velocity": v,
                     "LearningRate": lr}, {"mu": 0.9},
                    {"ParamOut": p - 0.1 * v_out, "VelocityOut": v_out},
                    ["ParamOut", "VelocityOut"])


def test_adam_op():
    p, g = f32(4), f32(4)
    m, v = f32(4, lo=0, hi=0.1), f32(4, lo=0, hi=0.1)
    lr = np.array([0.01], "float32")
    b1p = np.array([0.9], "float32")
    b2p = np.array([0.999], "float32")
    m_out = 0.9 * m + 0.1 * g
    v_out = 0.999 * v + 0.001 * g * g
    lr_t = 0.01 * np.sqrt(1 - b2p) / (1 - b1p)
    p_out = p - lr_t * m_out / (np.sqrt(v_out) + 1e-8)
    _optimizer_case("adam",
                    {"Param": p, "Grad": g, "Moment1": m, "Moment2": v,
                     "Beta1Pow": b1p, "Beta2Pow": b2p, "LearningRate": lr},
                    {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
                    {"ParamOut": p_out, "Moment1Out": m_out,
                     "Moment2Out": v_out},
                    ["ParamOut", "Moment1Out", "Moment2Out"])


def test_rmsprop_op():
    p, g = f32(4), f32(4)
    ms, mom = f32(4, lo=0.01, hi=0.1), f32(4, lo=0, hi=0.1)
    lr = np.array([0.01], "float32")
    ms_out = 0.95 * ms + 0.05 * g * g
    mom_out = 0.9 * mom + 0.01 * g / np.sqrt(ms_out + 1e-6)
    got = _run_single_op(
        "rmsprop",
        {"Param": p, "Grad": g, "MeanSquare": ms, "Moment": mom,
         "LearningRate": lr},
        {"decay": 0.95, "momentum": 0.9, "epsilon": 1e-6},
        ["ParamOut", "MeanSquareOut", "MomentOut"])
    np.testing.assert_allclose(got[1], ms_out, rtol=1e-5)
    np.testing.assert_allclose(got[2], mom_out, rtol=1e-5)
    np.testing.assert_allclose(got[0], p - mom_out, rtol=1e-5)


def test_collective_prod_is_product():
    """Regression (ADVICE r1): c_allreduce_prod must compute a product, not a
    sum. Run under shard_map over 8 CPU devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("dp",))
    x = np.arange(1, 9, dtype="float32")  # one value per device

    from paddle_tpu.core import registry
    d = registry.get("c_allreduce_prod")

    def f(xs):
        ctx = registry.LowerCtx({"axis_name": "dp"})
        return d.lower(ctx, {"X": [xs]})["Out"][0]

    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.full(8, np.prod(x), "float32"))


def test_cross_entropy2_matches_cross_entropy():
    """cross_entropy2 (reference nn.py:1917): same loss as hard-label
    cross_entropy, plus the saved MatchX."""
    import paddle_tpu as fluid
    rng = np.random.RandomState(0)
    probs = rng.dirichlet(np.ones(5), size=6).astype("float32")
    label = rng.randint(0, 5, (6, 1)).astype("int64")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        x = fluid.data("x", [6, 5], "float32", **A)
        y = fluid.data("y", [6, 1], "int64", **A)
        l2 = fluid.layers.cross_entropy2(x, y)
        l1 = fluid.layers.cross_entropy(x, y)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        a, b = exe.run(main, feed={"x": probs, "y": label},
                       fetch_list=[l2, l1])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_gelu_approximate_attr():
    """gelu approximate=True must compute the tanh form (the BERT/bench
    fast path), approximate=False the erf form."""
    import paddle_tpu as fluid
    x_np = np.linspace(-3, 3, 31).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [31], "float32", append_batch_size=False)
        tanh_form = fluid.layers.gelu(x, approximate=True)
        erf_form = fluid.layers.gelu(x, approximate=False)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        a, b = exe.run(main, feed={"x": x_np}, fetch_list=[tanh_form,
                                                           erf_form])
    want_tanh = 0.5 * x_np * (1 + np.tanh(
        np.sqrt(2 / np.pi) * (x_np + 0.044715 * x_np ** 3)))
    want_erf = 0.5 * x_np * (1 + special.erf(x_np / np.sqrt(2)))
    np.testing.assert_allclose(np.asarray(a), want_tanh, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(b), want_erf, rtol=1e-5,
                               atol=1e-6)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-6  # distinct
