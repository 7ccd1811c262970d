"""CompiledProgram + strategies: the multi-device front door.

Reference analog: python/paddle/fluid/compiler.py (CompiledProgram:138,
with_data_parallel), framework/parallel_executor.cc:393 (ParallelExecutor),
details/build_strategy.h:38 (BuildStrategy/ExecutionStrategy knobs).

TPU-native design: where the reference clones the graph per GPU and inserts
AllReduceOpHandles over NCCL rings, here a ``DistributedStrategy`` picks a
``jax.sharding.Mesh`` and sharding specs; the executor jits the whole program with
those shardings and XLA/GSPMD inserts the collectives (compiled onto ICI/DCN).
Data parallelism is the batch dim sharded over the "dp" axis -- gradient summation
over devices *is* the global-batch reduction, no explicit allreduce op needed.
Tensor/EP parallelism are PartitionSpec rules matched against parameter names.
sync_batch_norm falls out for free: batch-stat means over a sharded batch dim
compile to cross-replica reductions.
"""
from __future__ import annotations

import re
import warnings
from typing import Dict, List, Optional, Tuple

from .framework import Program
from .observability.timeline import spanned as _spanned

_warned_knobs = set()


def _warn_noop_knob(name: str, why: str):
    """Warn once when a reference-parity knob with no TPU effect is changed, so
    ported user code gets a signal instead of silent different behavior
    (VERDICT weak #10)."""
    if name in _warned_knobs:
        return
    _warned_knobs.add(name)
    warnings.warn(f"paddle_tpu: {name!r} has no effect on TPU ({why})",
                  UserWarning, stacklevel=3)


class ExecutionStrategy:
    """Knob parity with the reference (details/execution_strategy.h); most knobs are
    no-ops under XLA's static schedule and exist so user code ports unchanged."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = False


class BuildStrategy:
    """Reference details/build_strategy.h:38. The fusion/memory knobs are
    subsumed by XLA (fusion and buffer reuse are always on; changing them
    warns once). ``reduce_strategy=Reduce`` is real: optimizer-state
    accumulators that would be replicated are ZeRO-sharded over the "dp" mesh
    axis instead (the sharding analog of the reference's per-device param
    ownership, details/reduce_op_handle.*)."""

    class ReduceStrategy:
        AllReduce = 0   # replicated params (default)
        Reduce = 1      # shard optimizer states/params over dp (ZeRO-like)

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    # Knobs subsumed by XLA (fusion/buffer-reuse always on) — changing them
    # warns once instead of silently diverging from reference behavior.
    _NOOP_KNOBS = {
        "enable_sequential_execution": "XLA's schedule is already deterministic",
        "fuse_all_reduce_ops": "XLA fuses collectives",
        "fuse_elewise_add_act_ops": "XLA elementwise fusion is always on",
        "fuse_all_optimizer_ops": "the whole step is one fused XLA program",
        "memory_optimize": "buffer reuse is XLA's job",
        "enable_inplace": "donation makes updates in-place",
        "sync_batch_norm": "batch stats over a sharded batch dim sync for free",
    }

    def __init__(self):
        object.__setattr__(self, "_init_done", False)
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        # Reduce mode shards optimizer state over dp. reduce_params=True
        # additionally shards the Parameters themselves (the reference
        # ReduceOpHandle's per-device ownership + broadcast-on-use, ZeRO-3
        # style: GSPMD inserts the all-gather at each use). Opt-in: the
        # all-gather trades step latency for per-chip parameter memory.
        self.reduce_params = False
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""
        self.enable_sequential_execution = False
        self.fuse_all_reduce_ops = True      # XLA fuses; accepted for parity
        self.fuse_elewise_add_act_ops = True
        self.fuse_all_optimizer_ops = True
        self.memory_optimize = True
        self.enable_inplace = True
        self.sync_batch_norm = True          # free under GSPMD
        object.__setattr__(self, "_init_done", True)

    def __setattr__(self, name, value):
        if getattr(self, "_init_done", False) and name in self._NOOP_KNOBS \
                and value != getattr(self, name, value):
            _warn_noop_knob(f"BuildStrategy.{name}", self._NOOP_KNOBS[name])
        object.__setattr__(self, name, value)


class DistributedStrategy:
    """The mesh + sharding configuration (the TPU analog of the reference's
    DistributedStrategy, incubate/fleet/collective/__init__.py:94).

    mesh_shape: ordered {axis_name: size}; product must divide available devices.
      Conventional axes: "dp" (data), "mp" (tensor/model), "pp" (pipeline),
      "sp" (sequence/context), "ep" (expert/embedding).
    param_rules: [(regex, PartitionSpec-like tuple)] matched against parameter
      names, first match wins; unmatched params are replicated. A split the
      program declares on a variable (``Variable.declare_sharding``, set by
      the layer that knows which axis its state is split over: an expert
      layer's stacked weights under ``expert_axis``, a vocabulary's rows)
      outranks these rules, for the parameter and the optimizer's
      accumulators of its shape alike; a rule of the variable's rank that
      disagrees is an error (``CompiledProgram.state_sharding``).
    data_rules: [(regex, spec)] for feed vars; default shards dim 0 over "dp".
    comm_compression: 'off'|'bf16'|'int8' -- compress the dp-axis gradient
      allreduce (quantize -> psum -> dequantize with a per-tensor
      error-feedback residual persistable; see paddle_tpu/comm/).  world 1
      and tensors under ``comm_compress_min_bytes`` short-circuit to the
      uncompressed path; per-tensor on/off above the floor is the
      ``comm.compress`` TunableChoice.
    auto_shard: 'off'|'static'|'measure' -- the static auto-sharding
      planner (analysis/shardplan.py). 'off' (default) does zero planner
      work; 'static' searches PT04x-legal, cost-priced shard plans over
      ``mesh_shape`` at compile time and splices the top plan's
      param_rules in; 'measure' hands the top-k plans to the tuning
      harness (``shardplan.plan`` choice, decisions cached under
      tuning/cache.py keys). Needs a concrete ``mesh_shape``.
    """

    AUTO_SHARD_MODES = ("off", "static", "measure")

    def __init__(self, mesh_shape: Optional[Dict[str, int]] = None,
                 param_rules: Optional[List[Tuple[str, Tuple]]] = None,
                 data_rules: Optional[List[Tuple[str, Tuple]]] = None,
                 data_axis: str = "dp",
                 comm_compression: str = "off",
                 auto_shard: str = "off"):
        self.mesh_shape = dict(mesh_shape or {})
        self.param_rules = list(param_rules or [])
        self.data_rules = list(data_rules or [])
        self.data_axis = data_axis
        self.comm_compression = comm_compression
        self.auto_shard = auto_shard
        # hard floor in bytes below which a tensor never compresses (the
        # quantize arithmetic costs more than a small message saves)
        from .comm.compress import MIN_COMPRESS_BYTES
        self.comm_compress_min_bytes = MIN_COMPRESS_BYTES
        # multi-host/hierarchical knobs (parity with reference fleet strategy)
        self.use_hierarchical_allreduce = False
        self.nccl_comm_num = 1  # no-op: ICI has no rings to tune

    def __setattr__(self, name, value):
        if name == "comm_compression":
            from .comm.compress import MODES
            if value not in MODES:
                raise ValueError(
                    f"comm_compression must be one of {MODES}, "
                    f"got {value!r}")
        if name == "auto_shard" and value not in self.AUTO_SHARD_MODES:
            raise ValueError(
                f"auto_shard must be one of {self.AUTO_SHARD_MODES}, "
                f"got {value!r}")
        if name == "use_hierarchical_allreduce" and value:
            _warn_noop_knob(
                "DistributedStrategy.use_hierarchical_allreduce",
                "mesh-axis-factored reduction over (ICI, DCN) replaces "
                "2-level NCCL rings; add a 'host' axis to mesh_shape instead")
        if name == "nccl_comm_num" and value not in (None, 1):
            _warn_noop_knob("DistributedStrategy.nccl_comm_num",
                            "ICI has no rings to tune")
        object.__setattr__(self, name, value)

    # -- serialization (analysis CLI --strategy files, tooling) ------------------------
    def to_dict(self) -> dict:
        return {"mesh_shape": dict(self.mesh_shape),
                "param_rules": [[p, list(s)] for p, s in self.param_rules],
                "data_rules": [[p, list(s)] for p, s in self.data_rules],
                "data_axis": self.data_axis,
                "comm_compression": self.comm_compression,
                "comm_compress_min_bytes": self.comm_compress_min_bytes,
                "auto_shard": self.auto_shard}

    @staticmethod
    def from_dict(d: dict) -> "DistributedStrategy":
        """Build a strategy from the JSON shape ``to_dict`` emits. Spec
        entries may be axis names, null (replicated dim), or lists of axis
        names (a dim sharded over multiple axes)."""

        def spec(entries):
            return tuple(tuple(e) if isinstance(e, list) else e
                         for e in entries)

        ds = DistributedStrategy(
            mesh_shape=dict(d.get("mesh_shape") or {}),
            param_rules=[(p, spec(s)) for p, s in d.get("param_rules") or []],
            data_rules=[(p, spec(s)) for p, s in d.get("data_rules") or []],
            data_axis=d.get("data_axis", "dp"),
            comm_compression=d.get("comm_compression", "off"),
            auto_shard=d.get("auto_shard", "off"))
        if "comm_compress_min_bytes" in d:
            ds.comm_compress_min_bytes = int(d["comm_compress_min_bytes"])
        return ds

    # -- mesh --------------------------------------------------------------------------
    def build_mesh(self, devices=None):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        devices = list(devices if devices is not None else jax.devices())
        if not self.mesh_shape:
            self.mesh_shape = {"dp": len(devices)}
        sizes = list(self.mesh_shape.values())
        n = int(np.prod(sizes))
        if n > len(devices):
            raise ValueError(f"mesh {self.mesh_shape} needs {n} devices, "
                             f"have {len(devices)}")
        arr = np.array(devices[:n]).reshape(sizes)
        return Mesh(arr, tuple(self.mesh_shape))

    # -- sharding specs ----------------------------------------------------------------
    def param_spec(self, name: str):
        from jax.sharding import PartitionSpec as P
        for pat, spec in self.param_rules:
            if re.search(pat, name):
                return P(*spec)
        return P()

    def data_spec(self, name: str, ndim: int):
        from jax.sharding import PartitionSpec as P
        for pat, spec in self.data_rules:
            if re.search(pat, name):
                return P(*spec)
        if ndim == 0:
            return P()
        return P(self.data_axis, *([None] * (ndim - 1)))


class CompiledProgram:
    """Wrap a Program with a distribution strategy (reference compiler.py:138).

    ``with_data_parallel`` preserves the reference's signature;
    ``with_strategy`` is the native door for arbitrary meshes (dp/mp/pp/sp/ep).
    """

    def __init__(self, program: Program, build_strategy: Optional[BuildStrategy] = None):
        self.program = program
        self.build_strategy = build_strategy or BuildStrategy()
        self.exec_strategy = ExecutionStrategy()
        self.dist_strategy: Optional[DistributedStrategy] = None
        self._mesh = None

    def with_data_parallel(self, loss_name: Optional[str] = None,
                           build_strategy: Optional[BuildStrategy] = None,
                           exec_strategy: Optional[ExecutionStrategy] = None,
                           share_vars_from=None, places=None):
        if build_strategy is not None:
            self.build_strategy = build_strategy
        if exec_strategy is not None:
            self.exec_strategy = exec_strategy
        self.dist_strategy = DistributedStrategy()  # pure DP over all devices
        if places is not None:
            self.dist_strategy.mesh_shape = {"dp": len(places)}
        self._mesh = None
        return self

    @_spanned("with_strategy", cat="build", nested=False)
    def with_strategy(self, dist_strategy: DistributedStrategy):
        self.dist_strategy = dist_strategy
        self._mesh = None
        return self

    def strategy_signature(self) -> tuple:
        """Content-based signature for the executor's compile cache (mutating the
        strategy between runs must recompile, not serve a stale executable)."""
        ds = self.dist_strategy
        if ds is None:
            return ()
        return (tuple(sorted(ds.mesh_shape.items())),
                tuple((p, tuple(s)) for p, s in ds.param_rules),
                tuple((p, tuple(s)) for p, s in ds.data_rules),
                ds.data_axis, self.build_strategy.reduce_strategy,
                getattr(self.build_strategy, "reduce_params", False),
                getattr(ds, "comm_compression", "off"),
                getattr(ds, "comm_compress_min_bytes", None),
                getattr(ds, "auto_shard", "off"))

    @property
    def mesh(self):
        if self._mesh is None and self.dist_strategy is not None:
            self._mesh = self.dist_strategy.build_mesh()
        return self._mesh

    def state_sharding(self, name: str):
        """The NamedSharding the executor compiles for persistable var ``name``
        (None when no strategy). Single source of truth shared by the compile
        path (core/executor.py:_compile) and checkpoint reshard-on-load
        (io.py:load_vars) so a loaded array's sharding always matches what the
        jitted step expects."""
        ds = self.dist_strategy
        if ds is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .framework import Parameter
        mesh = self.mesh
        from .comm.compress import is_residual
        if is_residual(name):
            # error-feedback residual (comm/rewrite.py): per-DEVICE state of
            # shape (ndp, *grad.shape), sharded over dp on its leading dim --
            # one source of truth for compile and checkpoint stitching
            v = self.program.global_block().find_var_recursive(name)
            ndim = len(v.shape) if v is not None else 1
            return NamedSharding(mesh, P(ds.data_axis,
                                         *([None] * (ndim - 1))))
        v = self.program.global_block().find_var_recursive(name)
        spec = ds.param_spec(name) if v is not None else P()
        declared = self._declared_spec(v, spec)
        if declared is not None:
            return NamedSharding(mesh, declared)
        if v is not None and len(spec) > len(v.shape):
            # a param rule matched a lower-rank derived var (e.g. Adam's
            # beta_pow accumulator sharing the param's name prefix): replicate
            spec = P()
        bs = self.build_strategy
        reduce_mode = (bs.reduce_strategy == BuildStrategy.ReduceStrategy.Reduce
                       and "dp" in mesh.shape and mesh.shape["dp"] > 1)
        shardable = (v is not None and spec == P() and
                     (not isinstance(v, Parameter) or
                      getattr(bs, "reduce_params", False)))
        if reduce_mode and shardable:
            # ZeRO-style sharding over dp (details/reduce_op_handle.* analog):
            # optimizer accumulators always; Parameters too when
            # reduce_params is set (GSPMD all-gathers them at each use)
            ndp = mesh.shape["dp"]
            for dim, s in enumerate(v.shape):
                if isinstance(s, int) and s > 0 and s % ndp == 0:
                    spec = P(*([None] * dim), "dp")
                    break
            else:
                if (any(isinstance(s, int) and s > ndp for s in v.shape)
                        and name not in _warned_knobs):
                    # big but unevenly-shaped: replication costs real memory,
                    # tell the user instead of silently diverging from the
                    # expected 1/dp footprint (once per var; NOT the no-op
                    # knob wording -- the strategy IS active elsewhere)
                    _warned_knobs.add(name)
                    warnings.warn(
                        f"paddle_tpu: ReduceStrategy.Reduce keeps {name!r} "
                        f"replicated: no dim of shape {tuple(v.shape)} "
                        f"divides dp={ndp} (pad the dim or change dp for "
                        f"the full ZeRO memory win; other state still "
                        f"shards)")
        return NamedSharding(mesh, spec)

    def _declared_spec(self, v, ruled):
        """The PartitionSpec of the split variable ``v`` declares
        (``Variable.declare_sharding``), which outranks the strategy's
        ``param_rules``; None where it declares none. An axis the mesh does
        not have holds the dimension whole. ``ruled`` is what the first
        matching rule gives the name: a rule of the variable's rank that
        says otherwise is an error that names both."""
        from jax.sharding import PartitionSpec as P
        declared = getattr(v, "sharding", None) if v is not None else None
        if declared is None:
            return None
        spec = P(*(a if a in self.mesh.shape else None for a in declared))
        if len(ruled) == len(v.shape) and tuple(ruled) != tuple(declared):
            raise ValueError(
                f"{v.name} declares the split {tuple(declared)} "
                f"(Variable.declare_sharding) and the strategy's param_rules "
                f"give it {tuple(ruled)}: take the rule out, or make them "
                f"agree")
        return spec

    # Program-API passthroughs used by Executor
    def global_block(self):
        return self.program.global_block()

    @property
    def blocks(self):
        return self.program.blocks

    @property
    def random_seed(self):
        return self.program.random_seed

    @property
    def _version(self):
        return self.program._version
