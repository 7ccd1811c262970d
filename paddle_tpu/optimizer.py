"""Optimizers (reference: python/paddle/fluid/optimizer.py, 19 classes, ~3.7k LoC).

``Optimizer.minimize(loss)`` = append_backward + regularization + clipping + one
update op per parameter, all inside the same Program -- so the whole training step
compiles to a single XLA program (reference splits this across executors/op handles).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from . import unique_name
from .clip import append_gradient_clip_ops
from .core.backward import append_backward
from .framework import (Parameter, Program, Variable, default_main_program,
                        default_startup_program)
from .initializer import Constant
from .layer_helper import LayerHelper
from .observability.timeline import spanned as _spanned
from .regularizer import append_regularization_ops


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._name = name
        self._accumulators = {}
        self._lr_var = None

    # -- learning rate -----------------------------------------------------------------
    def _create_lr_var(self):
        if isinstance(self._learning_rate, Variable):
            self._lr_var = self._learning_rate
            return
        helper = LayerHelper("learning_rate")
        self._lr_var = helper.create_global_variable(
            [1], "float32", persistable=True,
            name=unique_name.generate("learning_rate"),
            initializer=Constant(float(self._learning_rate)))

    def _lr(self, param=None):
        lr = self._lr_var
        mult = getattr(param, "optimize_attr", {}).get("learning_rate", 1.0) \
            if param is not None else 1.0
        if mult == 1.0:
            return lr
        block = default_main_program().global_block()
        out = block.create_var(unique_name.generate("lr_scaled"), (1,), "float32")
        block.append_op("scale", inputs={"X": [lr]}, outputs={"Out": [out]},
                        attrs={"scale": float(mult)})
        return block.var(out.name)

    # -- accumulators ------------------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, shape=None,
                         dtype=None) -> Variable:
        key = (name, param.name)
        if key in self._accumulators:
            return self._accumulators[key]
        helper = LayerHelper(name)
        # an accumulator of the parameter's shape is split as the parameter
        # declares itself split (framework.Variable.declare_sharding)
        v = helper.create_global_variable(
            list(shape if shape is not None else param.shape),
            dtype or "float32", persistable=True,
            name=unique_name.generate(f"{param.name}_{name}"),
            initializer=Constant(float(fill_value)),
            sharding=None if shape is not None else getattr(
                param, "sharding", None))
        self._accumulators[key] = v
        return v

    # -- to be implemented by subclasses ----------------------------------------------
    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    # -- public API --------------------------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set, callbacks)

    def apply_gradients(self, params_grads) -> List:
        params_grads = append_gradient_clip_ops(params_grads)
        params_grads = append_regularization_ops(params_grads,
                                                 self.regularization)
        self._create_lr_var()
        block = default_main_program().global_block()
        ops = []
        for p, g in params_grads:
            if g is None:
                continue
            ops.append(self._append_optimize_op(block, (p, g)))
        return ops

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    @_spanned("minimize", cat="build", nested=False)
    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None
                 ) -> Tuple[List, List[Tuple[Parameter, Variable]]]:
        # All ops (backward, clip, regularization, update) must land in the
        # *loss's* program, which may not be the current default (the reference
        # passes programs explicitly; we scope the defaults for the duration).
        from .framework import program_guard, default_startup_program
        with program_guard(loss.block.program,
                           startup_program or default_startup_program()):
            params_grads = self.backward(loss, startup_program, parameter_list,
                                         no_grad_set)
            if grad_clip is not None:
                # explicit clip instance (the dygraph_grad_clip.py surface):
                # applied to every gradient BEFORE any per-param
                # set_gradient_clip attrs run in apply_gradients -- the two
                # compose, so don't mix them on the same params
                from .clip import apply_clip_to_all
                params_grads = apply_clip_to_all(grad_clip, params_grads)
            ops = self.apply_gradients(params_grads)
        return ops, params_grads


class SGDOptimizer(Optimizer):
    """Reference optimizer.py:690."""

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "sgd", inputs={"Param": [p], "Grad": [g],
                           "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p]})


class MomentumOptimizer(Optimizer):
    """Reference optimizer.py:758."""

    def __init__(self, learning_rate, momentum, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _append_optimize_op(self, block, pg):
        p, g = pg
        vel = self._add_accumulator("velocity", p)
        return block.append_op(
            "momentum",
            inputs={"Param": [p], "Grad": [g], "Velocity": [vel],
                    "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p], "VelocityOut": [vel]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov})


class LarsMomentumOptimizer(Optimizer):
    """Reference optimizer.py:1686 (LARS)."""

    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _append_optimize_op(self, block, pg):
        p, g = pg
        vel = self._add_accumulator("velocity", p)
        return block.append_op(
            "lars_momentum",
            inputs={"Param": [p], "Grad": [g], "Velocity": [vel],
                    "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p], "VelocityOut": [vel]},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay})


class AdamOptimizer(Optimizer):
    """Reference optimizer.py:1108."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_mode=False, **kw):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m1 = self._add_accumulator("moment1", p)
        m2 = self._add_accumulator("moment2", p)
        b1p = self._add_accumulator("beta1_pow_acc", p, self._beta1, shape=[1])
        b2p = self._add_accumulator("beta2_pow_acc", p, self._beta2, shape=[1])
        return block.append_op(
            "adam",
            inputs={"Param": [p], "Grad": [g], "LearningRate": [self._lr(p)],
                    "Moment1": [m1], "Moment2": [m2], "Beta1Pow": [b1p],
                    "Beta2Pow": [b2p]},
            outputs={"ParamOut": [p], "Moment1Out": [m1], "Moment2Out": [m2],
                     "Beta1PowOut": [b1p], "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})


class AdamWOptimizer(AdamOptimizer):
    """Decoupled weight decay."""

    def __init__(self, learning_rate=0.001, weight_decay=0.01, **kw):
        super().__init__(learning_rate, **kw)
        self._coeff = weight_decay

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m1 = self._add_accumulator("moment1", p)
        m2 = self._add_accumulator("moment2", p)
        b1p = self._add_accumulator("beta1_pow_acc", p, self._beta1, shape=[1])
        b2p = self._add_accumulator("beta2_pow_acc", p, self._beta2, shape=[1])
        return block.append_op(
            "adamw",
            inputs={"Param": [p], "Grad": [g], "LearningRate": [self._lr(p)],
                    "Moment1": [m1], "Moment2": [m2], "Beta1Pow": [b1p],
                    "Beta2Pow": [b2p]},
            outputs={"ParamOut": [p], "Moment1Out": [m1], "Moment2Out": [m2],
                     "Beta1PowOut": [b1p], "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "coeff": self._coeff})


class AdagradOptimizer(Optimizer):
    """Reference optimizer.py:1010."""

    def __init__(self, learning_rate, epsilon=1e-6, initial_accumulator_value=0.0,
                 **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _append_optimize_op(self, block, pg):
        p, g = pg
        mom = self._add_accumulator("moment", p, self._initial)
        return block.append_op(
            "adagrad",
            inputs={"Param": [p], "Grad": [g], "Moment": [mom],
                    "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p], "MomentOut": [mom]},
            attrs={"epsilon": self._epsilon})


class AdamaxOptimizer(Optimizer):
    """Reference optimizer.py:1300."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 **kw):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _append_optimize_op(self, block, pg):
        p, g = pg
        mom = self._add_accumulator("moment", p)
        inf = self._add_accumulator("inf_norm", p)
        b1p = self._add_accumulator("beta1_pow_acc", p, self._beta1, shape=[1])
        op = block.append_op(
            "adamax",
            inputs={"Param": [p], "Grad": [g], "Moment": [mom], "InfNorm": [inf],
                    "Beta1Pow": [b1p], "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p], "MomentOut": [mom], "InfNormOut": [inf]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})
        block.append_op("scale", inputs={"X": [b1p]}, outputs={"Out": [b1p]},
                        attrs={"scale": self._beta1})
        return op


class AdadeltaOptimizer(Optimizer):
    """Reference optimizer.py:1480."""

    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon, self._rho = epsilon, rho

    def _append_optimize_op(self, block, pg):
        p, g = pg
        asg = self._add_accumulator("avg_squared_grad", p)
        asu = self._add_accumulator("avg_squared_update", p)
        return block.append_op(
            "adadelta",
            inputs={"Param": [p], "Grad": [g], "AvgSquaredGrad": [asg],
                    "AvgSquaredUpdate": [asu]},
            outputs={"ParamOut": [p], "AvgSquaredGradOut": [asg],
                     "AvgSquaredUpdateOut": [asu]},
            attrs={"epsilon": self._epsilon, "rho": self._rho})


class RMSPropOptimizer(Optimizer):
    """Reference optimizer.py:1554."""

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _append_optimize_op(self, block, pg):
        p, g = pg
        ms = self._add_accumulator("mean_square", p)
        mom = self._add_accumulator("momentum", p)
        inputs = {"Param": [p], "Grad": [g], "MeanSquare": [ms], "Moment": [mom],
                  "LearningRate": [self._lr(p)]}
        outputs = {"ParamOut": [p], "MeanSquareOut": [ms], "MomentOut": [mom]}
        if self._centered:
            mg = self._add_accumulator("mean_grad", p)
            inputs["MeanGrad"] = [mg]
            outputs["MeanGradOut"] = [mg]
        return block.append_op(
            "rmsprop", inputs=inputs, outputs=outputs,
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered})


class FtrlOptimizer(Optimizer):
    """Reference optimizer.py:1803."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _append_optimize_op(self, block, pg):
        p, g = pg
        sq = self._add_accumulator("squared", p)
        lin = self._add_accumulator("linear", p)
        return block.append_op(
            "ftrl",
            inputs={"Param": [p], "Grad": [g], "SquaredAccumulator": [sq],
                    "LinearAccumulator": [lin], "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p], "SquaredAccumOut": [sq],
                     "LinearAccumOut": [lin]},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power})


class LambOptimizer(Optimizer):
    """Reference optimizer.py:2291 (large-batch BERT training)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, exclude_from_weight_decay_fn=None,
                 **kw):
        super().__init__(learning_rate, **kw)
        self._weight_decay = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m1 = self._add_accumulator("moment1", p)
        m2 = self._add_accumulator("moment2", p)
        b1p = self._add_accumulator("beta1_pow_acc", p, self._beta1, shape=[1])
        b2p = self._add_accumulator("beta2_pow_acc", p, self._beta2, shape=[1])
        wd = self._weight_decay
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        return block.append_op(
            "lamb",
            inputs={"Param": [p], "Grad": [g], "LearningRate": [self._lr(p)],
                    "Moment1": [m1], "Moment2": [m2], "Beta1Pow": [b1p],
                    "Beta2Pow": [b2p]},
            outputs={"ParamOut": [p], "Moment1Out": [m1], "Moment2Out": [m2],
                     "Beta1PowOut": [b1p], "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "weight_decay": wd})


class DecayedAdagradOptimizer(Optimizer):
    """Reference optimizer.py:1399."""

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self._decay, self._epsilon = decay, epsilon

    def _append_optimize_op(self, block, pg):
        p, g = pg
        mom = self._add_accumulator("moment", p)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": [p], "Grad": [g], "Moment": [mom],
                    "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p], "MomentOut": [mom]},
            attrs={"decay": self._decay, "epsilon": self._epsilon})


class DpsgdOptimizer(Optimizer):
    """Differentially-private SGD (reference optimizer.py:952)."""

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16.0,
                 sigma=1.0, **kw):
        super().__init__(learning_rate, **kw)
        self._clip, self._batch_size, self._sigma = clip, batch_size, sigma

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "dpsgd", inputs={"Param": [p], "Grad": [g],
                             "LearningRate": [self._lr(p)]},
            outputs={"ParamOut": [p]},
            attrs={"clip": self._clip, "batch_size": self._batch_size,
                   "sigma": self._sigma})


class RecomputeOptimizer(Optimizer):
    """Activation rematerialization (reference optimizer.py:3278).

    ``_set_checkpoints([vars])`` marks segment boundaries; minimize() moves each
    inter-checkpoint forward segment into a sub-block executed under
    jax.checkpoint (see ops/control_flow.py remat_segment), then delegates to the
    inner optimizer. Backward recomputes segment intermediates instead of
    storing them. Note: vars internal to a segment can no longer be fetched.
    """

    def __init__(self, optimizer):
        self._optimizer = optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = checkpoints
        return self

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return self._optimizer.backward(loss, startup_program, parameter_list,
                                        no_grad_set, callbacks)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        if not self._checkpoints:
            raise ValueError("call _set_checkpoints() before minimize()")
        program = loss.block.program
        _rewrite_recompute(program,
                           [c.name if isinstance(c, Variable) else str(c)
                            for c in self._checkpoints])
        loss = program.global_block().var(loss.name)
        return self._optimizer.minimize(loss, startup_program, parameter_list,
                                        no_grad_set)


def _rewrite_recompute(program: Program, checkpoint_names):
    """Partition forward ops at checkpoint producers into remat_segment ops,
    in every block of the program: a checkpoint written inside a
    control-flow op's sub-block (the layers of a looped stack) cuts that
    sub-block. A checkpoint that no op of the program writes raises by name,
    and so do checkpoints among which no segment of two ops forms: either
    would be no recomputation, in silence. (Checkpoints written back to
    back, or right after a block's start, end no segment of their own and
    are fine beside ones that do.)"""
    ckpts = set(checkpoint_names)
    written = {n for block in program.blocks for op in block.ops
               for n in op.output_arg_names()}
    if ckpts - written:
        raise ValueError(
            f"RecomputeOptimizer: no op of the program writes the "
            f"checkpoint(s) {sorted(ckpts - written)} (of {len(ckpts)})")
    cut = False
    for block in list(program.blocks):      # not the sub-blocks made here
        cut |= _rewrite_recompute_block(program, block, ckpts)
    if not cut:
        raise ValueError(
            f"RecomputeOptimizer: no segment of at least two ops ends at "
            f"any of the checkpoints {sorted(ckpts)}: each is written right "
            f"after its block's start or another checkpoint; nothing would "
            f"be recomputed")


def _exported(program: Program, block) -> set:
    """Names of ``block`` that the op holding it as its sub-block reads out
    of it by attribute (a scan's ``carry_names`` / ``next_names`` /
    ``out_names``): used after the block's last op."""
    names = set()
    for b in program.blocks:
        for op in b.ops:
            if op.attr("sub_block", -1) == block.idx and b is not block:
                for value in op.attrs.values():
                    if isinstance(value, (list, tuple)):
                        names.update(n for n in value if isinstance(n, str))
    return names


def _rewrite_recompute_block(program: Program, block, ckpts) -> bool:
    """``_rewrite_recompute`` of one block; whether a segment formed."""
    ops = block.ops

    # segment boundaries: index just after an op that produces a checkpoint var
    boundaries = [0]
    for i, op in enumerate(ops):
        if any(n in ckpts for n in op.output_arg_names()):
            boundaries.append(i + 1)
    segments = [(a, b) for a, b in zip(boundaries, boundaries[1:]) if b - a >= 2]
    if not segments:
        return False

    exported = _exported(program, block) if block.idx else set()
    new_ops = []
    cursor = 0
    for (a, b) in segments:
        new_ops.extend(ops[cursor:a])
        seg_ops = ops[a:b]
        # io analysis
        produced = set()
        read = []
        for op in seg_ops:
            for n in op.input_arg_names():
                if n not in produced and n not in read:
                    read.append(n)
            produced.update(op.output_arg_names())
        used_later = set(exported)
        for op in ops[b:]:
            used_later.update(op.input_arg_names())
        out_names = []
        for op in seg_ops:
            for n in op.output_arg_names():
                v = block.find_var_recursive(n)
                if n in out_names:
                    continue
                if n in used_later or n in ckpts or (v is not None and
                                                     v.persistable):
                    out_names.append(n)
        in_names = [n for n in read
                    if block.find_var_recursive(n) is not None]
        sub = program._create_block(parent_idx=block.idx)
        sub.ops = list(seg_ops)
        program._rollback()
        from .framework import Operator
        seg_op = Operator(block, "remat_segment",
                          {"X": in_names}, {"Out": out_names},
                          {"sub_block": sub.idx, "in_names": in_names,
                           "out_names": out_names})
        new_ops.append(seg_op)
        cursor = b
    new_ops.extend(ops[cursor:])
    block.ops = new_ops
    program._bump()
    return True


class PipelineOptimizer:
    """GPipe-style pipeline trainer (reference optimizer.py:2985
    PipelineOptimizer, framework/trainer.h:115 PipelineTrainer,
    section_worker.cc:85 SectionWorker).

    TPU-native redesign: the reference cuts the program into per-device
    sections and streams Scopes between SectionWorker threads over NCCL. Here
    ``minimize`` rewrites the program into a **microbatch scan**: the feed
    batch splits into ``num_microbatches`` slices, one ``lax.scan`` runs
    forward+backward per slice accumulating gradients functionally, and the
    wrapped optimizer applies the averaged gradient once -- the same math as
    the reference's grad-merged pipeline schedule, in one XLA program.
    Cross-stage placement over a "pp" mesh axis is expressed separately with
    DistributedStrategy sharding rules (and parallel/pipeline.py carries the
    explicit shard_map/ppermute schedule for homogeneous layer stacks).

    Feed batch sizes must be divisible by num_microbatches.
    """

    def __init__(self, optimizer, num_microbatches=1, cut_list=None,
                 place_list=None, concurrency_list=None, queue_size=None,
                 sync_steps=None, start_cpu_core_id=0, schedule="auto",
                 pipeline_axis="pp"):
        self._optimizer = optimizer
        self._m = int(num_microbatches)
        # cut/place/concurrency/queue knobs are the reference's thread-section
        # tuning surface; scheduling is XLA's job here.
        # schedule: "auto" lowers device_guard("stage:i")-annotated homogeneous
        # stage stacks into the compiled temporal GPipe schedule
        # (ops/pipeline_op.py + parallel/pipeline.py) and falls back to the
        # microbatch scan otherwise; "scan" forces the scan; "temporal"
        # requires stage annotations and raises when they cannot lower.
        if schedule not in ("auto", "scan", "temporal"):
            raise ValueError(f"schedule must be auto|scan|temporal, "
                             f"got {schedule!r}")
        self._schedule = schedule
        self._axis = pipeline_axis

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return self._optimizer.backward(loss, startup_program, parameter_list,
                                        no_grad_set, callbacks)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .framework import program_guard
        program = loss.block.program
        block = program.global_block()
        startup = startup_program or default_startup_program()
        if self._schedule in ("auto", "temporal"):
            rewrote = _rewrite_temporal_pipeline(
                program, startup, self._m, self._axis,
                required=self._schedule == "temporal")
            if rewrote:
                with program_guard(program, startup):
                    params_grads = self._optimizer.backward(
                        loss, startup_program, parameter_list, no_grad_set)
                    pg = [(p, g) for p, g in params_grads if g is not None]
                    ops = self._optimizer.apply_gradients(pg)
                return ops, params_grads
        with program_guard(program, startup):
            params_grads = self._optimizer.backward(
                loss, startup_program, parameter_list, no_grad_set)
            if self._m <= 1:
                ops = self._optimizer.apply_gradients(params_grads)
                return ops, params_grads
            mean_grads = _rewrite_microbatch_scan(program, loss, params_grads,
                                                  self._m)
            pg = [(p, mean_grads[p.name]) for p, g in params_grads
                  if g is not None]
            ops = self._optimizer.apply_gradients(pg)
        return ops, params_grads

    @staticmethod
    def pp_param_rules(axis="pp"):
        """DistributedStrategy param_rules sharding the stage-stacked
        parameters (and their stage-stacked optimizer accumulators) over the
        pipeline axis. Scalar accumulators derived from stacked params
        (Adam's beta-pow) stay replicated -- first match wins."""
        return [(r"@pp_stacked.*_pow_acc", ()),
                (r"@pp_stacked", (axis,))]


def _rewrite_temporal_pipeline(program: Program, startup, M, axis="pp",
                               required=False):
    """Lower device_guard("stage:i")-annotated ops into one temporal_pipeline
    op (the compiled GPipe schedule; reference PipelineTrainer/SectionWorker,
    trainer.h:115, section_worker.cc:85).

    Requirements (the homogeneous-stage contract of parallel/pipeline.py):
      - annotated ops are contiguous and stage ids increase monotonically;
      - every stage has the same op-type/attr sequence with positionally
        matching parameter shapes (a transformer layer stack);
      - consecutive stages are linked by exactly one activation (cut) var of
        a shape shared by all cuts; other stage inputs must come from the
        prologue (stage-invariant consts, e.g. the attention mask bias).

    On success: per-stage parameters are replaced by [S, ...] stacks (named
    <stage0 param>@pp_stacked, initialized in the startup program by stacking
    the per-stage inits), the stage ops move into a template sub-block, and
    the main block gets one temporal_pipeline op. Returns True. On any
    violated requirement: returns False (schedule="auto") or raises
    (schedule="temporal").
    """
    from .framework import Parameter

    block = program.global_block()
    ops = list(block.ops)

    def stage_of(op):
        d = op.attr("op_device", None)
        if isinstance(d, str) and d.startswith("stage:"):
            return int(d.split(":", 1)[1])
        return None

    tagged = [i for i, o in enumerate(ops) if stage_of(o) is not None]

    def bail(msg):
        if required:
            raise ValueError(f"PipelineOptimizer(schedule='temporal'): {msg}")
        return False

    if not tagged:
        return bail("no device_guard('stage:i') annotations found")
    first, last = tagged[0], tagged[-1]
    prologue, staged, epilogue = ops[:first], ops[first:last + 1], ops[last + 1:]

    stages, cur = [], None
    for o in staged:
        s = stage_of(o)
        if s is None:
            return bail(f"un-annotated op {o.type!r} inside the stage region")
        if s != cur:
            if cur is not None and s != cur + 1:
                return bail(f"stage ids must increase by 1 (saw {cur} -> {s})")
            if cur is None and s != 0:
                return bail(f"stages must start at 0 (saw stage:{s} first)")
            stages.append([])
            cur = s
        stages[-1].append(o)
    S = len(stages)
    if S < 2:
        return bail("need at least 2 stages")

    # homogeneity: identical op type + attr sequences (modulo the stage tag)
    def sig(sops):
        out = []
        for o in sops:
            attrs = {k: v for k, v in o.attrs.items() if k != "op_device"}
            out.append((o.type, tuple(sorted(
                (k, repr(v)) for k, v in attrs.items()))))
        return out
    template_sig = sig(stages[0])
    for i, sops in enumerate(stages[1:], 1):
        if sig(sops) != template_sig:
            return bail(f"stage {i} op sequence differs from stage 0 "
                        f"(homogeneous stacks only; use schedule='scan' for "
                        f"heterogeneous sections)")

    produced = [set(n for o in sops for ns in o.outputs.values() for n in ns)
                for sops in stages]
    consumed = [set(n for o in sops for ns in o.inputs.values() for n in ns)
                for sops in stages]
    epi_consumed = set(n for o in epilogue for ns in o.inputs.values()
                       for n in ns)

    def params_of(sops):
        seen, out = set(), []
        for o in sops:
            for slot in sorted(o.inputs):
                for n in o.inputs[slot]:
                    v = block.find_var_recursive(n)
                    if isinstance(v, Parameter) and n not in seen:
                        seen.add(n)
                        out.append(n)
        return out

    stage_params = [params_of(sops) for sops in stages]
    K = len(stage_params[0])
    for i, ps in enumerate(stage_params[1:], 1):
        if len(ps) != K:
            return bail(f"stage {i} has {len(ps)} params, stage 0 has {K}")
        for a, b in zip(stage_params[0], ps):
            va, vb = block.var(a), block.var(b)
            if tuple(va.shape) != tuple(vb.shape) or va.dtype != vb.dtype:
                return bail(f"param {b!r} ({vb.shape}) does not match stage-0 "
                            f"{a!r} ({va.shape})")

    # cut vars: single activation handed stage i -> i+1 (and last -> epilogue)
    cuts = []
    for i in range(1, S):
        link = consumed[i] & produced[i - 1]
        if len(link) != 1:
            return bail(f"stages {i-1}->{i} must be linked by exactly one "
                        f"activation var (found {sorted(link)})")
        cuts.append(next(iter(link)))
    out_link = epi_consumed & produced[S - 1]
    if len(out_link) != 1:
        return bail(f"last stage must hand exactly one var to the epilogue "
                    f"(found {sorted(out_link)})")
    out_var = next(iter(out_link))
    # no skip connections across stages: stage i's outputs may only be read
    # by stage i+1 (the cut) -- or the epilogue for the last stage
    for i in range(S - 1):
        later = set().union(*consumed[i + 2:]) if i + 2 < S else set()
        later |= epi_consumed
        leak = produced[i] & later
        if leak:
            return bail(f"stage {i} outputs {sorted(leak)} consumed beyond "
                        f"stage {i+1} (single-cut chains only)")

    # stage inputs that are neither params nor the cut: stage-invariant consts
    pro_avail = set(n for o in prologue for ns in o.outputs.values()
                    for n in ns)
    pro_avail |= {n for n, v in block.vars.items() if v.is_data}
    for i in range(S):
        cut_in = cuts[i - 1] if i > 0 else None
        for n in sorted(consumed[i]):
            if n in stage_params[i] or n == cut_in or n in produced[i]:
                continue
            if n not in pro_avail:
                return bail(f"stage {i} reads {n!r} which is neither a "
                            f"param, the cut activation, nor a prologue "
                            f"output")
    # classify stage-0 non-param inputs: consts are read by stage >= 1 too
    later_consumed = set().union(*consumed[1:]) if S > 1 else set()
    cand = [n for n in sorted(consumed[0])
            if n not in stage_params[0] and n not in produced[0]]
    const_vars = [n for n in cand if n in later_consumed]
    ins0 = [n for n in cand if n not in later_consumed]
    if len(ins0) != 1:
        return bail(f"stage 0 must consume exactly one activation from the "
                    f"prologue (found {ins0}); stage-invariant inputs must "
                    f"also be read by later stages to classify as consts")
    in_var = ins0[0]

    # cut shapes must all match (homogeneous activation)
    shapes = {tuple(block.var(n).shape) for n in cuts + [in_var, out_var]}
    if len(shapes) != 1:
        return bail(f"cut activations must share one shape, found {shapes}")

    # classify consts statically: per-example (batch-riding, microbatched by
    # the op) vs stage-invariant (replicated). Recording this as an op attr
    # here -- where declared shapes are known -- avoids the runtime
    # shape-coincidence trap (a stage-invariant const whose dim 0 happens to
    # equal the batch). Three-way result:
    #   batch:  leading dim is the dynamic batch mark (-1) like the
    #           activation's, or concretely equals the activation's concrete
    #           batch dim;
    #   static: concrete leading dim that differs from the batch dim;
    #   defer:  declared shapes can't decide (one side -1, the other
    #           concrete) -- the op falls back to its runtime heuristic for
    #           just that var.
    act_lead = tuple(block.var(in_var).shape)[0] if block.var(in_var).shape \
        else None

    def _classify(n):
        shp = tuple(block.var(n).shape)
        if not shp:
            return "static"
        if shp[0] == -1:
            return "batch" if act_lead == -1 else "defer"
        if act_lead == -1:
            return "defer"
        return "batch" if shp[0] == act_lead else "static"

    batch_const_vars = [n for n in const_vars if _classify(n) == "batch"]
    defer_const_vars = [n for n in const_vars if _classify(n) == "defer"]

    # ---- build: template sub-block + stacked params + the pipeline op ------
    sub = program._create_block(parent_idx=0)
    program._rollback()
    sub.ops = stages[0]

    stacked_names = []
    sblock = startup.global_block()
    for k in range(K):
        base = stage_params[0][k]
        v0 = block.var(base)
        sname = f"{base}@pp_stacked"
        block.create_parameter(sname, (S,) + tuple(v0.shape), v0.dtype)
        stacked_names.append(sname)
        per_stage = [stage_params[i][k] for i in range(S)]
        sv = sblock.create_var(sname, (S,) + tuple(v0.shape), v0.dtype)
        sv.persistable = True
        sblock.append_op("stack", inputs={"X": per_stage},
                         outputs={"Y": [sname]}, attrs={"axis": 0},
                         infer_shape=False)
        # the per-stage params become startup-internal temporaries: only the
        # stack persists (keeps checkpoints and executor state stack-only)
        for i in range(S):
            block.var(per_stage[i]).persistable = False
            block.var(per_stage[i]).trainable = False
            su = sblock.find_var_recursive(per_stage[i])
            if su is not None:
                su.persistable = False

    block.ops = list(prologue)
    block.append_op(
        "temporal_pipeline",
        inputs={"X": [in_var], "Params": stacked_names,
                "Consts": const_vars},
        outputs={"Out": [out_var]},
        attrs={"sub_block": sub.idx, "num_stages": S,
               "num_microbatches": max(M, 1), "axis": axis,
               "in_var": in_var, "template_out": cuts[0],
               "param_vars": list(stage_params[0]),
               "const_vars": const_vars,
               "batch_const_vars": batch_const_vars,
               "defer_const_vars": defer_const_vars},
        infer_shape=False)
    block.ops.extend(epilogue)
    return True


def _rewrite_microbatch_scan(program: Program, loss, params_grads, M):
    """Move all ops built so far (forward + backward) into a sub-block scanned
    over M microbatch slices; return {param_name: mean-grad Variable}."""
    block = program.global_block()
    fwd_bwd_ops = list(block.ops)
    block.ops = []

    # data vars the step consumes (is_data) become scanned sequences. Only
    # TOP-LEVEL op inputs can be sliced: the executor's block_runner resolves
    # nested-block names through the top-level env, so a feed read inside a
    # sub-block WITHOUT being lifted into the enclosing op's inputs (the DSL
    # lifts reads; hand-wired blocks may not) would silently see the full
    # batch every microbatch -- refuse instead of corrupting gradients.
    data_names = []
    for op in fwd_bwd_ops:
        for n in op.input_arg_names():
            v = block.find_var_recursive(n)
            if v is not None and v.is_data and n not in data_names:
                data_names.append(n)

    def check_nested(ops, seen_blocks):
        for op in ops:
            for a in ("sub_block", "else_block"):
                si = op.attr(a, -1)
                if not (isinstance(si, int) and 0 <= si < len(program.blocks)
                        and si not in seen_blocks):
                    continue
                seen_blocks.add(si)
                sub_ops = program.blocks[si].ops
                local = set(program.blocks[si].vars)
                for sop in sub_ops:
                    for n in sop.input_arg_names():
                        v = block.find_var_recursive(n)
                        if (v is not None and v.is_data and n not in local
                                and n not in data_names):
                            raise ValueError(
                                f"PipelineOptimizer: feed var {n!r} is read "
                                f"inside sub-block {si} but is not an input "
                                f"of the enclosing control-flow op, so the "
                                f"microbatch slice cannot reach it; declare "
                                f"it in the op's inputs (the While/Scan DSL "
                                f"does this automatically)")
                check_nested(sub_ops, seen_blocks)

    check_nested(fwd_bwd_ops, set())

    sub = program._create_block(parent_idx=0)
    sub.ops = fwd_bwd_ops
    program._rollback()

    carry_names, init_names, final_names = [], [], []

    def add_carry(inner_name, shape, dtype, add_name, zero_like=None):
        """Accumulator carried across microbatches: inner += add_name."""
        sub.create_var(inner_name, tuple(shape), dtype).stop_gradient = True
        sub.append_op("sum", inputs={"X": [inner_name, add_name]},
                      outputs={"Out": [inner_name]}, infer_shape=False)
        zname = inner_name + "@zero"
        zv = block.create_var(zname, tuple(shape), dtype)
        zv.stop_gradient = True
        if zero_like is not None:
            block.append_op("fill_zeros_like", inputs={"X": [zero_like]},
                            outputs={"Out": [zname]}, infer_shape=False)
        else:
            block.append_op("fill_constant", outputs={"Out": [zname]},
                            attrs={"shape": [int(s) for s in shape],
                                   "value": 0.0, "dtype": dtype},
                            infer_shape=False)
        fname = inner_name + "@final"
        block.create_var(fname, tuple(shape), dtype).stop_gradient = True
        carry_names.append(inner_name)
        init_names.append(zname)
        final_names.append(fname)
        return fname

    grad_finals = {}
    for p, g in params_grads:
        if g is None:
            continue
        gd = getattr(g, "dtype", "float32")
        grad_finals[p.name] = add_carry(g.name + "@mb_acc", p.shape, gd,
                                        g.name, zero_like=p.name)
    loss_final = add_carry(loss.name + "@mb_acc", (1,), "float32", loss.name)

    mb_names = []
    for dn in data_names:
        v = block.var(dn)
        tail = [int(s) for s in v.shape[1:]]
        out = block.create_var(dn + "@mb", tuple([M, -1] + tail), v.dtype)
        out.stop_gradient = True
        block.append_op("reshape", inputs={"X": [dn]},
                        outputs={"Out": [out.name]},
                        attrs={"shape": [M, -1] + tail}, infer_shape=False)
        mb_names.append(out.name)

    block.append_op("scan",
                    inputs={"Init": init_names, "X": mb_names},
                    outputs={"Out": [], "FinalCarry": final_names},
                    attrs={"sub_block": sub.idx, "carry_names": carry_names,
                           "x_names": data_names, "out_names": [],
                           "time_major": True},
                    infer_shape=False)

    mean_grads = {}
    for p, g in params_grads:
        if g is None:
            continue
        mname = g.name + "@mb_mean"
        mv = block.create_var(mname, tuple(p.shape),
                              getattr(g, "dtype", "float32"))
        mv.stop_gradient = True
        block.append_op("scale", inputs={"X": [grad_finals[p.name]]},
                        outputs={"Out": [mname]},
                        attrs={"scale": 1.0 / M}, infer_shape=False)
        mean_grads[p.name] = block.var(mname)
    # the user-facing loss var becomes the microbatch-mean loss
    block.append_op("scale", inputs={"X": [loss_final]},
                    outputs={"Out": [loss.name]},
                    attrs={"scale": 1.0 / M}, infer_shape=False)
    return mean_grads


class ExponentialMovingAverage:
    """EMA shadow parameters (reference optimizer.py:2449).

    ``update()`` appends in-graph EMA ops (call after minimize); ``apply()`` /
    ``restore()`` swap param values in the scope host-side.
    """

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._shadow = {}
        self._backup = {}
        self._decay_pow_name = None

    def update(self):
        from .framework import default_main_program
        from .initializer import Constant
        block = default_main_program().global_block()
        helper = LayerHelper("ema")
        # decay^t accumulator for zero-debias in apply() (the reference divides
        # by (1 - decay^t), optimizer.py:2449 region).
        dp = helper.create_global_variable(
            [1], "float32", persistable=True,
            name=unique_name.generate("ema_decay_pow"),
            initializer=Constant(1.0))
        self._decay_pow_name = dp.name
        block.append_op("scale", inputs={"X": [dp.name]},
                        outputs={"Out": [dp.name]},
                        attrs={"scale": self._decay})
        for p in block.all_parameters():
            if not p.trainable:
                continue
            shadow = helper.create_global_variable(
                list(p.shape), "float32", persistable=True,
                name=unique_name.generate(p.name + "_ema"),
                initializer=Constant(0.0))
            self._shadow[p.name] = shadow.name
            tmp = block.create_var(unique_name.generate("ema_t"), p.shape,
                                   "float32")
            block.append_op("scale", inputs={"X": [shadow.name]},
                            outputs={"Out": [tmp]},
                            attrs={"scale": self._decay})
            tmp2 = block.create_var(unique_name.generate("ema_t"), p.shape,
                                    "float32")
            block.append_op("scale", inputs={"X": [p.name]},
                            outputs={"Out": [tmp2]},
                            attrs={"scale": 1.0 - self._decay})
            block.append_op("sum", inputs={"X": [tmp, tmp2]},
                            outputs={"Out": [shadow.name]})

    def apply(self, executor=None, need_restore=True):
        import numpy as np
        from .core.executor import global_scope
        scope = global_scope()
        debias = 1.0
        if self._decay_pow_name is not None:
            pow_val = scope.find_var(self._decay_pow_name)
            if pow_val is not None:
                pw = float(np.asarray(pow_val).reshape(-1)[0])
                if pw < 1.0:
                    debias = 1.0 - pw  # shadow seeded at 0 => divide by 1-decay^t
        for pname, sname in self._shadow.items():
            self._backup[pname] = scope.find_var(pname)
            val = scope.find_var(sname)
            if val is not None:
                arr = np.asarray(val, dtype="float32") / debias
                scope.set_var(pname, arr.astype(np.asarray(val).dtype))
        ema = self

        class _Guard:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                if need_restore:
                    ema.restore()
                return False

        return _Guard()

    def restore(self, executor=None):
        from .core.executor import global_scope
        scope = global_scope()
        for pname, val in self._backup.items():
            scope.set_var(pname, val)
        self._backup = {}


class ModelAverage:
    """Sliding-window parameter averaging (reference optimizer.py:2751).

    Simplification vs the reference's 3-tier sum buffers: one running sum +
    count per param with the same apply/restore surface; the window knobs bound
    when the accumulator restarts.
    """

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000000):
        self._max_window = max_average_window
        self._sums = {}
        self._backup = {}

    def _build(self):
        from .framework import default_main_program
        from .initializer import Constant
        block = default_main_program().global_block()
        helper = LayerHelper("model_average")
        count = helper.create_global_variable(
            [1], "float32", persistable=True,
            name=unique_name.generate("ma_count"), initializer=Constant(0.0))
        block.append_op("increment", inputs={"X": [count.name]},
                        outputs={"Out": [count.name]}, attrs={"step": 1.0})
        self._count = count.name
        for p in block.all_parameters():
            if not p.trainable:
                continue
            s = helper.create_global_variable(
                list(p.shape), "float32", persistable=True,
                name=unique_name.generate(p.name + "_ma_sum"),
                initializer=Constant(0.0))
            self._sums[p.name] = s.name
            block.append_op("sum", inputs={"X": [s.name, p.name]},
                            outputs={"Out": [s.name]})

    def update(self):
        if not self._sums:
            self._build()

    def apply(self, executor=None, need_restore=True):
        import numpy as np
        from .core.executor import global_scope
        scope = global_scope()
        cnt = float(np.asarray(scope.find_var(self._count)).reshape(-1)[0])
        for pname, sname in self._sums.items():
            self._backup[pname] = scope.find_var(pname)
            s = scope.find_var(sname)
            if s is not None and cnt > 0:
                scope.set_var(pname, s / cnt)
        ma = self

        class _Guard:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                if need_restore:
                    ma.restore()
                return False

        return _Guard()

    def restore(self, executor=None):
        from .core.executor import global_scope
        scope = global_scope()
        for pname, val in self._backup.items():
            scope.set_var(pname, val)
        self._backup = {}


class LookaheadOptimizer:
    """Lookahead k-step slow/fast weights (reference optimizer.py:3571)."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k

    def minimize(self, loss, startup_program=None):
        from .framework import program_guard, default_startup_program
        from .initializer import Constant
        from .layers import nn, tensor

        ops, pg = self.inner_optimizer.minimize(loss, startup_program)
        program = loss.block.program
        with program_guard(program, startup_program or
                           default_startup_program()):
            helper = LayerHelper("lookahead")
            block = program.global_block()
            step = helper.create_global_variable(
                [1], "float32", persistable=True,
                name=unique_name.generate("la_step"),
                initializer=Constant(0.0))
            block.append_op("increment", inputs={"X": [step.name]},
                            outputs={"Out": [step.name]}, attrs={"step": 1.0})
            kconst = tensor.fill_constant([1], "float32", float(self.k))
            mod = nn.elementwise_mod(block.var(step.name), kconst)
            sync = tensor.cast(nn.elementwise_mul(
                tensor.cast(mod < 0.5, "float32"),
                tensor.cast(block.var(step.name) >= 0.5, "float32")),
                "float32")
            keep = nn.scale(sync, scale=-1.0, bias=1.0)
            for p, g in pg:
                if g is None:
                    continue
                slow = helper.create_global_variable(
                    list(p.shape), "float32", persistable=True,
                    name=unique_name.generate(p.name + "_slow"),
                    initializer=Constant(0.0))
                init_flag = helper.create_global_variable(
                    [1], "float32", persistable=True,
                    name=unique_name.generate(p.name + "_slow_init"),
                    initializer=Constant(0.0))
                # first update: slow <- p
                fresh = nn.scale(block.var(init_flag.name), scale=-1.0,
                                 bias=1.0)
                slow_seeded = nn.elementwise_add(
                    nn.elementwise_mul(block.var(slow.name),
                                       block.var(init_flag.name)),
                    nn.elementwise_mul(block.var(p.name), fresh))
                block.append_op("fill_constant",
                                outputs={"Out": [init_flag.name]},
                                attrs={"shape": [1], "dtype": "float32",
                                       "value": 1.0})
                new_slow = nn.elementwise_add(
                    slow_seeded,
                    nn.elementwise_mul(
                        nn.elementwise_sub(block.var(p.name), slow_seeded),
                        nn.elementwise_mul(sync, tensor.fill_constant(
                            [1], "float32", self.alpha))))
                block.append_op("assign", inputs={"X": [new_slow]},
                                outputs={"Out": [slow.name]})
                new_fast = nn.elementwise_add(
                    nn.elementwise_mul(new_slow, sync),
                    nn.elementwise_mul(block.var(p.name), keep))
                block.append_op("assign", inputs={"X": [new_fast]},
                                outputs={"Out": [p.name]})
        return ops, pg


# Short aliases matching fluid.optimizer public names.
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adamax = AdamaxOptimizer
Adadelta = AdadeltaOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
LarsMomentum = LarsMomentumOptimizer
Lamb = LambOptimizer
Dpsgd = DpsgdOptimizer


class DGCMomentumOptimizer:
    """Reference optimizer.py:870. Not built -- deep gradient compression
    trades MXU cycles for interconnect bandwidth TPUs are not short of; see
    SCOPE.md (DGC row). Use Momentum, with BuildStrategy.ReduceStrategy.
    Reduce for ZeRO-style state sharding when memory is the constraint."""

    def __init__(self, *a, **kw):
        raise NotImplementedError(self.__doc__)
