"""Host-resident embedding tables: the parameter-server analog for beyond-HBM
sparse models.

Reference analog: the pserver distributed lookup table
(`python/paddle/fluid/transpiler/distribute_transpiler.py:1594`
`_replace_lookup_table_op_with_prefetch`, `operators/distributed_ops/
distributed_lookup_table_op.cc`) and the Hogwild/Downpour CPU workers
(`framework/device_worker.h:151,180`, `framework/fleet/fleet_wrapper.h:55`):
tables too large for accelerator memory live on parameter servers; workers
pull rows for the minibatch and push sparse gradients, and the *server*
applies the optimizer update.

TPU-native design (not a port): there is no RPC fleet. The table lives in
host RAM (optionally a disk-backed ``np.memmap`` for tables beyond RAM) on
the single controller process. The jitted XLA program reaches it through
host callbacks:

  * forward  — ``host_lookup_table`` op: ``jax.pure_callback`` gathers the
    minibatch rows (the "pull"); only ``B×F×dim`` floats cross PCIe, never
    the table.
  * backward — a custom grad maker emits ``host_push_grad``:
    ``jax.experimental.io_callback`` ships the sparse row grads back (the
    "push") and the host applies SGD/Adagrad immediately (synchronous PS)
    or on a background thread (``async_updates=True`` — the
    AsyncCommunicator/Hogwild analog: bounded queue, lock-free reads,
    locked row updates).

To ride the Program-autodiff machinery (which only appends grad ops for ops
with at least one differentiable input), every table gets a device-side
``[1]``-float *anchor* parameter. The forward ignores it; the push op's
io_callback returns the anchor's (zero) gradient so the callback is
data-depended-on and never DCE'd by XLA.

Multi-host, two topologies:
  * default — the classic single-pserver with no extra code: under
    multi-host GSPMD, jax gathers callback operands to process 0, runs the
    callback there alone, and broadcasts the result, so process 0's host
    RAM/memmap is the parameter server (2-process loss parity and
    pserver-rank push accounting in tests/test_multihost.py). Checkpoint
    from process 0 (the only rank whose table advances).
  * ``row_shard_axis`` — ROWS partitioned across processes (the reference
    pserver param blocks, distribute_transpiler.py:990): each process
    stores only rows [lo, hi) so capacity scales with hosts; lookups/pushes
    run through a shard_map island over the axis (one callback per device,
    per PROCESS under multi-host, against the local shard; non-shard mesh
    axes are replica-gated to zero grads so each row updates once) and a
    psum reassembles the minibatch rows. Checkpoint every rank (save/load
    write per-shard files).
On-chip tables that fit HBM should use EP sharding
(``models/deepfm.py:ep_param_rules``) instead.
"""
from __future__ import annotations

import os
import queue
import threading
from typing import Dict, Optional

import numpy as np

from ..framework import grad_var_name
from ..core.registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


class HostTable:
    """A host-RAM (or memmapped) embedding table with a server-side optimizer.

    The table is float32 on host regardless of the compute dtype: the push
    applies high-precision updates (the reference pserver does the same;
    bf16 grads are upcast on arrival).
    """

    @staticmethod
    def shard_bounds(vocab_size: int, n_shards: int, shard: int):
        """Contiguous row range [lo, hi) owned by ``shard`` of n_shards."""
        lo = (vocab_size * shard) // n_shards
        hi = (vocab_size * (shard + 1)) // n_shards
        return lo, hi

    def __init__(self, name: str, vocab_size: int, dim: int, *,
                 optimizer: str = "adagrad", lr: float = 0.05,
                 initializer=None, seed: int = 0, mmap_dir: Optional[str] = None,
                 async_updates: bool = False, queue_size: int = 64,
                 row_shard=None):
        if optimizer not in ("sgd", "adagrad"):
            raise ValueError(f"host table optimizer must be sgd|adagrad, "
                             f"got {optimizer!r}")
        self.name = name
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.optimizer = optimizer
        self.lr = float(lr)
        self.mmap_dir = mmap_dir
        self._seed = seed
        self._queue_size = queue_size
        self._initializer = initializer
        # row_shard=(shard_id, n_shards): this process stores ONLY rows
        # [lo, hi) -- the cross-process pserver row partition (reference
        # distribute_transpiler.py:990 param blocks). Ids stay global;
        # gather_shard/push_shard translate and filter by ownership.
        self.row_shard = tuple(row_shard) if row_shard else None
        if self.row_shard:
            k, nsh = self.row_shard
            if not (0 <= k < nsh):
                raise ValueError(f"row_shard {self.row_shard}: shard id out "
                                 f"of range")
            self.row_lo, self.row_hi = self.shard_bounds(
                self.vocab_size, nsh, k)
        else:
            self.row_lo, self.row_hi = 0, self.vocab_size
        shape = (self.row_hi - self.row_lo, self.dim)
        if mmap_dir is not None:
            os.makedirs(mmap_dir, exist_ok=True)
            # shard suffix: ranks sharing a filesystem must not open the
            # same backing file (same reason as _ckpt_path)
            sfx = (f".shard{self.row_shard[0]}of{self.row_shard[1]}"
                   if self.row_shard else "")
            self.table = np.lib.format.open_memmap(
                os.path.join(mmap_dir, f"{name}{sfx}.table.npy"), mode="w+",
                dtype=np.float32, shape=shape)
            self._accum = np.lib.format.open_memmap(
                os.path.join(mmap_dir, f"{name}{sfx}.accum.npy"), mode="w+",
                dtype=np.float32, shape=shape)
            self._accum[:] = 0.0
        else:
            self.table = np.empty(shape, np.float32)
            self._accum = np.zeros(shape, np.float32)
        rng = np.random.RandomState(seed)
        full_shape = (self.vocab_size, self.dim)
        if initializer is None:
            # draw the FULL table deterministically and keep the local rows:
            # every shard layout yields the same global values for a seed
            scale = 1.0 / np.sqrt(self.dim)
            full = rng.uniform(-scale, scale, full_shape).astype(np.float32)
            self.table[:] = full[self.row_lo:self.row_hi]
        elif callable(initializer):
            self.table[:] = np.asarray(initializer(full_shape),
                                       np.float32)[self.row_lo:self.row_hi]
        else:
            self.table[:] = np.asarray(initializer, np.float32).reshape(
                full_shape)[self.row_lo:self.row_hi]
        self._lock = threading.Lock()
        self.push_count = 0
        # online-publisher dirty tracking: None while disarmed so the push
        # hot path pays exactly one attribute read (spy-guard-tested).  When
        # armed, maps LOCAL row index -> table version (push_count) of its
        # last update; bounded -- on overflow the map is dropped and
        # _dirty_floor rises, forcing the next export to ship the full table.
        self._dirty: Optional[Dict[int, int]] = None
        self._dirty_bound = 0
        self._dirty_floor = 0
        self._closed = False
        self._worker_error: Optional[BaseException] = None
        self._async = bool(async_updates)
        self._queue: Optional[queue.Queue] = None
        self._worker: Optional[threading.Thread] = None
        if self._async:
            self._queue = queue.Queue(maxsize=queue_size)
            self._worker = threading.Thread(target=self._drain, daemon=True,
                                            name=f"host_table[{name}]")
            self._worker.start()

    def _check_ids(self, ids: np.ndarray, where: str) -> np.ndarray:
        """Host-side id validation (free of XLA constraints): out-of-range
        ids raise instead of silently reading/training row vocab_size-1 --
        that clamp corrupted data untraceably in a beyond-HBM table."""
        ids = np.asarray(ids, np.int64)
        bad = (ids < 0) | (ids >= self.vocab_size)
        if bad.any():
            examples = np.unique(ids[bad])[:8].tolist()
            raise IndexError(
                f"host table {self.name!r}: {int(bad.sum())} id(s) out of "
                f"range [0, {self.vocab_size}) in {where}, e.g. {examples} "
                f"-- check the feed's hashing/vocab")
        return ids

    # ---- pull ------------------------------------------------------------
    def gather(self, ids: np.ndarray) -> np.ndarray:
        """Lock-free read (Hogwild-style: concurrent async pushes may be
        partially visible; exact under sync mode)."""
        idx = self._check_ids(ids, "gather")
        if self.row_shard:
            raise RuntimeError(
                f"host table {self.name!r} is row-sharded "
                f"{self.row_shard}; use gather_shard (the sharded lookup "
                f"op does) -- a plain gather cannot see remote rows")
        return self.table[idx.reshape(-1)].reshape(idx.shape + (self.dim,))

    def gather_shard(self, ids: np.ndarray, shard: int,
                     n_shards: int) -> np.ndarray:
        """Rows for ids owned by ``shard``, zeros elsewhere; summing the
        n_shards results reconstructs the full gather (the psum in the
        sharded lookup op)."""
        idx = self._check_ids(ids, "gather_shard")
        if self.row_shard:
            if (shard, n_shards) != self.row_shard:
                raise RuntimeError(
                    f"host table {self.name!r} holds row shard "
                    f"{self.row_shard} but the mesh routed shard "
                    f"({shard}, {n_shards}) here -- host-axis device order "
                    f"and table row_shard disagree")
            lo, hi = self.row_lo, self.row_hi
        else:
            lo, hi = self.shard_bounds(self.vocab_size, n_shards, shard)
        flat = idx.reshape(-1)
        owned = (flat >= lo) & (flat < hi)
        local = np.where(owned, flat - self.row_lo
                         if self.row_shard else flat, 0)
        rows = self.table[local] * owned[:, None]
        return rows.reshape(idx.shape + (self.dim,))

    def push_shard(self, ids: np.ndarray, grads: np.ndarray, shard: int,
                   n_shards: int):
        """Apply only the grads whose rows ``shard`` owns."""
        idx = self._check_ids(np.asarray(ids).reshape(-1), "push_shard")
        g = np.asarray(grads, np.float32).reshape(len(idx), self.dim)
        if self.row_shard:
            if (shard, n_shards) != self.row_shard:
                raise RuntimeError(
                    f"host table {self.name!r} holds row shard "
                    f"{self.row_shard} but got push for ({shard}, "
                    f"{n_shards})")
            lo, hi = self.row_lo, self.row_hi
        else:
            lo, hi = self.shard_bounds(self.vocab_size, n_shards, shard)
        owned = (idx >= lo) & (idx < hi)
        if not owned.any():
            return
        g = g[owned]
        if not g.any():
            # replica-gated zero pushes (see _host_push) and genuinely zero
            # grads are no-op updates for sgd/adagrad: skip the host work
            return
        self.push(idx[owned], g)

    # ---- push ------------------------------------------------------------
    def push(self, ids: np.ndarray, grads: np.ndarray):
        if self._closed:
            raise RuntimeError(
                f"host table {self.name!r} is closed; no more pushes accepted")
        if self._worker_error is not None:
            raise RuntimeError(
                f"host table {self.name!r} async worker died: "
                f"{self._worker_error!r}") from self._worker_error
        if self._async:
            self._queue.put((np.asarray(ids).copy(),
                             np.asarray(grads, np.float32).copy()))
        else:
            self._apply(ids, grads)

    def _drain(self):
        while True:
            item = self._queue.get()
            try:
                if item is None:
                    return
                self._apply(*item)
            except BaseException as e:  # poison, surface on next push/flush
                self._worker_error = e
                return
            finally:
                self._queue.task_done()

    def _drain_wait(self):
        """Wait for the queue to drain, polling worker liveness so a worker
        that dies mid-wait cannot hang the caller (queue.join() would block
        forever on the never-consumed remainder)."""
        import time as _time
        while self._queue.unfinished_tasks:
            if self._worker_error is not None or self._worker is None \
                    or not self._worker.is_alive():
                break
            _time.sleep(0.001)

    def flush(self):
        """Barrier: wait until all queued async pushes are applied."""
        if self._async:
            self._drain_wait()
        if self._worker_error is not None:
            raise RuntimeError(
                f"host table {self.name!r} async worker died: "
                f"{self._worker_error!r}") from self._worker_error

    def close(self):
        if self._async and self._worker is not None:
            self._drain_wait()
            try:  # a dead worker never drains; don't block on a full queue
                self._queue.put_nowait(None)
            except queue.Full:
                pass
            self._worker.join(timeout=5)
            self._worker = None
        self._closed = True

    def _apply(self, ids, grads):
        ids = self._check_ids(np.asarray(ids).reshape(-1), "push")
        if self.row_shard:
            out = (ids < self.row_lo) | (ids >= self.row_hi)
            if out.any():
                raise IndexError(
                    f"host table {self.name!r} (row shard {self.row_shard},"
                    f" rows [{self.row_lo}, {self.row_hi})) got a push for "
                    f"non-owned ids, e.g. "
                    f"{np.unique(ids[out])[:4].tolist()}; route pushes "
                    f"through push_shard")
            ids = ids - self.row_lo
        g = np.asarray(grads, np.float32).reshape(len(ids), self.dim)
        # Duplicate ids in one minibatch sum their contributions first (the
        # SelectedRows merge-add semantic) so the update matches the dense
        # scatter-add a device-side table would apply.
        uniq, inv = np.unique(ids, return_inverse=True)
        acc = np.zeros((len(uniq), self.dim), np.float32)
        np.add.at(acc, inv, g)
        with self._lock:
            if self.optimizer == "adagrad":
                self._accum[uniq] += acc * acc
                self.table[uniq] -= self.lr * acc / np.sqrt(
                    self._accum[uniq] + 1e-10)
            else:
                self.table[uniq] -= self.lr * acc
            self.push_count += 1
            if self._dirty is not None:
                self._note_dirty(uniq)

    # ---- online publishing ------------------------------------------------
    @property
    def version(self) -> int:
        """Monotone table version: the number of applied pushes (survives
        checkpoint save/load via the npz meta)."""
        return self.push_count

    def arm_publisher(self, bound: int = 1_000_000):
        """Start dirty-row tracking so ``export_delta`` can ship only the
        rows touched since a version.  ``bound`` caps the tracked-id map;
        overflowing it degrades the NEXT export to a full-table publish
        (correct, just not incremental) rather than growing without limit."""
        with self._lock:
            if self._dirty is None:
                self._dirty = {}
                # rows dirtied before arming are unknown: exports reaching
                # below this floor must ship the full table
                self._dirty_floor = self.push_count
            self._dirty_bound = int(bound)

    def disarm_publisher(self):
        """Stop dirty tracking and drop the map (push hot path back to the
        single ``_dirty is None`` attribute read)."""
        with self._lock:
            self._dirty = None

    def _note_dirty(self, uniq):
        """Record locally-indexed rows ``uniq`` as dirty at the current
        version.  Caller holds ``self._lock`` (called from ``_apply``)."""
        d = self._dirty
        v = self.push_count
        for i in uniq.tolist():
            d[int(i)] = v
        if len(d) > self._dirty_bound:
            # bounded set overflow: forget row granularity, remember only
            # that everything up to v may be dirty (next export goes full)
            d.clear()
            self._dirty_floor = v

    def export_delta(self, since_version: int = 0, *, encoding: str = "off",
                     watermark=None, chunk_rows: int = 65536) -> dict:
        """Atomic snapshot of the rows changed after ``since_version`` as a
        ``host_table_delta_v1`` doc: chunked ids + rows (optionally
        int8/bf16-encoded via ``comm/compress``), per-chunk crc32, the
        stream ``watermark`` the rows were trained through, and the table
        version the delta advances to.  Requires ``arm_publisher()``; see
        ``paddle_tpu.online.delta`` for the format and the apply side."""
        from ..online.delta import export_table_delta
        return export_table_delta(self, since_version, encoding=encoding,
                                  watermark=watermark, chunk_rows=chunk_rows)

    # ---- persistence -----------------------------------------------------
    def _ckpt_path(self, dirname: str) -> str:
        # row-sharded tables checkpoint per shard (every rank saves/loads
        # its own slice; no filename collision on a shared filesystem)
        suffix = (f".shard{self.row_shard[0]}of{self.row_shard[1]}"
                  if self.row_shard else "")
        return os.path.join(dirname, f"host_table.{self.name}{suffix}.npz")

    def save(self, dirname: str):
        # snapshot consistency: flush() drains pending async pushes first
        # (a queued push applied mid-save would otherwise write a
        # half-updated row), then the apply lock is held across the whole
        # savez so no concurrent _apply can interleave table/accum/meta
        self.flush()
        os.makedirs(dirname, exist_ok=True)
        with self._lock:
            np.savez(self._ckpt_path(dirname),
                     table=np.asarray(self.table),
                     accum=np.asarray(self._accum),
                     meta=np.array([self.lr, self.push_count]))

    def load(self, dirname: str):
        data = np.load(self._ckpt_path(dirname))
        want = (self.row_hi - self.row_lo, self.dim)
        if data["table"].shape != want:
            raise ValueError(
                f"host table {self.name!r}: checkpoint shape "
                f"{data['table'].shape} != declared {want} "
                f"(row_shard={self.row_shard})")
        with self._lock:
            self.table[:] = data["table"]
            self._accum[:] = data["accum"]
            self.push_count = int(data["meta"][1])


def _same_init(a, b) -> bool:
    if a is b:
        return True
    if isinstance(a, np.ndarray) and isinstance(b, np.ndarray):
        return a.shape == b.shape and np.array_equal(a, b)
    return False


_TABLES: Dict[str, HostTable] = {}


def create_table(name: str, vocab_size: int, dim: int, **kwargs) -> HostTable:
    """Create (or fetch, with config check) the process-global table ``name``."""
    t = _TABLES.get(name)
    if t is not None:
        if (t.vocab_size, t.dim) != (int(vocab_size), int(dim)):
            raise ValueError(
                f"host table {name!r} already exists with shape "
                f"{(t.vocab_size, t.dim)}, requested {(vocab_size, dim)}")
        existing = {"optimizer": t.optimizer, "lr": t.lr,
                    "mmap_dir": t.mmap_dir, "async_updates": t._async,
                    "seed": t._seed, "queue_size": t._queue_size,
                    "row_shard": t.row_shard}
        for k, v in kwargs.items():
            if k == "initializer":
                if v is not None and not _same_init(v, t._initializer):
                    raise ValueError(
                        f"host table {name!r} already exists with a "
                        f"different initializer; drop_table({name!r}) first "
                        f"to rebuild it (its current weights would otherwise "
                        f"silently survive)")
            elif k in existing and existing[k] != (
                    float(v) if k == "lr" else
                    (tuple(v) if k == "row_shard" and v else v)):
                raise ValueError(
                    f"host table {name!r} already exists with {k}="
                    f"{existing[k]!r}; requested {v!r}. drop_table({name!r}) "
                    f"first to rebuild it with a different config")
        return t
    t = HostTable(name, vocab_size, dim, **kwargs)
    _TABLES[name] = t
    return t


def get_table(name: str) -> HostTable:
    try:
        return _TABLES[name]
    except KeyError:
        raise KeyError(
            f"host table {name!r} does not exist in this process; create it "
            f"with layers.host_embedding(...) / host_table.create_table() "
            f"before building or deserializing the program") from None


def drop_table(name: str):
    t = _TABLES.pop(name, None)
    if t is not None:
        t.close()


def save_all(dirname: str):
    for t in _TABLES.values():
        t.save(dirname)


def load_all(dirname: str):
    for t in _TABLES.values():
        t.load(dirname)


# --------------------------------------------------------------------------
# ops
# --------------------------------------------------------------------------

# desc-level custom grad maker (reference GradOpDescMakerBase analog)
def _host_lookup_grad_maker(op, grad_out_map):
    out_name = op.outputs["Out"][0]
    g = grad_out_map.get(out_name)
    if g is None:
        return []
    return [{"type": "host_push_grad",
             "inputs": {"Ids": list(op.inputs["Ids"]), "OutGrad": [g]},
             "outputs": {"Anchor@GRAD": [grad_var_name(op.inputs["Anchor"][0])]},
             "attrs": {"table_name": op.attrs["table_name"],
                       "shard_axis": op.attrs.get("shard_axis")}}]


def _shard_map(fn, mesh, in_specs, out_specs):
    from jax import shard_map
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _shard_axis_size(ctx):
    """(axis, n) when the sharded row-partition path applies, else None."""
    ax = ctx.attr("shard_axis", None)
    mesh = ctx.gspmd_mesh
    if ax and mesh is not None and mesh.shape.get(ax, 1) > 1 \
            and not ctx.abstract:
        return ax, mesh.shape[ax]
    return None


@register("host_lookup_table", grad=_host_lookup_grad_maker,
          nondiff_inputs=("Ids",))
def _host_lookup(ctx, ins):
    """Pull: gather minibatch rows from the host table via pure_callback.

    Anchor (a [1] device parameter) is ignored by the math; it exists so the
    backward pass has a differentiable input to hang ``host_push_grad`` on.

    With attr shard_axis=<mesh axis>, the table is row-partitioned across
    that axis (the cross-process pserver sharding, reference
    distribute_transpiler.py:990 param blocks): a shard_map island runs one
    callback per device -- under multi-host, per PROCESS against its local
    row shard -- each returning its owned rows (zeros elsewhere), and a psum
    over the axis reassembles the full minibatch.
    """
    import jax
    jnp = _jnp()
    from jax.sharding import PartitionSpec as P
    ids = ins["Ids"][0]
    if ids.ndim > 1 and ids.shape[-1] == 1:  # lookup_table squeeze parity
        ids = ids.squeeze(-1)
    name = ctx.attr("table_name")
    dim = get_table(name).dim  # shape is config, safe to bind at trace time
    dtype = ctx.attr("dtype", "float32")
    out_struct = jax.ShapeDtypeStruct(tuple(ids.shape) + (dim,),
                                      jnp.dtype(dtype))
    sharded = _shard_axis_size(ctx)
    if sharded:
        ax, n = sharded

        def per_device(i):
            sidx = jax.lax.axis_index(ax)
            rows = jax.pure_callback(
                lambda ii, ss: get_table(name).gather_shard(
                    ii, int(ss), n).astype(dtype), out_struct, i, sidx)
            return jax.lax.psum(rows, ax)

        rows = _shard_map(per_device, ctx.gspmd_mesh, (P(),), P())(ids)
        return {"Out": [rows]}
    # re-resolve by name inside the callback: a cached compiled program must
    # see the table registered at RUN time (drop_table+create_table safe)
    rows = jax.pure_callback(
        lambda i: get_table(name).gather(i).astype(dtype), out_struct, ids)
    return {"Out": [rows]}


@register("host_push_grad", grad=None, nondiff_inputs=("Ids", "OutGrad"))
def _host_push(ctx, ins):
    """Push: ship sparse row grads to the host table; the host applies the
    optimizer update (synchronous by default). Returns the anchor's zero
    gradient *from the callback* so XLA cannot dead-code-eliminate the push.
    """
    import jax
    from jax.experimental import io_callback
    from jax.sharding import PartitionSpec as P
    jnp = _jnp()
    ids, g = ins["Ids"][0], ins["OutGrad"][0]
    if ids.ndim > 1 and ids.shape[-1] == 1:
        ids = ids.squeeze(-1)
    name = ctx.attr("table_name")
    get_table(name)  # fail at trace time if missing
    sharded = _shard_axis_size(ctx)
    if sharded:
        ax, n = sharded
        mesh = ctx.gspmd_mesh
        other_axes = [a for a in mesh.axis_names if a != ax]

        def per_device(i, grad):
            sidx = jax.lax.axis_index(ax)
            # the island replicates over every NON-shard axis too; only the
            # first replica along each pushes (the rest skip the callback
            # entirely -- no device->host grad transfer) so each shard
            # applies the gradient exactly once
            primary = jnp.asarray(True)
            for a in other_axes:
                primary = primary & (jax.lax.axis_index(a) == 0)

            def push_cb(ii, gg, ss):
                get_table(name).push_shard(ii, gg, int(ss), n)
                return np.zeros((1,), np.float32)

            def do_push(operand):
                ii, gg, ss = operand
                return io_callback(push_cb,
                                   jax.ShapeDtypeStruct((1,), jnp.float32),
                                   ii, gg, ss, ordered=False)

            token = jax.lax.cond(primary, do_push,
                                 lambda _: jnp.zeros((1,), jnp.float32),
                                 (i, grad, sidx))
            return jax.lax.psum(token, ax)

        token = _shard_map(per_device, ctx.gspmd_mesh, (P(), P()), P())(
            ids, g)
        return {"Anchor@GRAD": [token]}

    def push_cb(i, grad):
        # late-bound by name (see _host_lookup)
        get_table(name).push(i, grad)
        return np.zeros((1,), np.float32)

    token = io_callback(push_cb,
                        jax.ShapeDtypeStruct((1,), jnp.float32),
                        ids, g, ordered=False)
    return {"Anchor@GRAD": [token]}


# --------------------------------------------------------------------------------------
# Pull/push hoisting: the PS schedule without in-graph callbacks
# --------------------------------------------------------------------------------------

def hoist_host_pulls(program):
    """Rewrite eligible host-table ops OUT of the compiled program: the pull
    becomes a host-side gather whose rows enter as a feed, the push becomes
    a fetch of the row gradients applied to the table after the step. This
    is the reference PS schedule itself (pull -> device step -> push,
    distribute_transpiler.py:1594) and removes jax callbacks from the hot
    path: a host gather before the step and a host update after it cost
    less per step than a callback that stalls the device mid-program.

    Eligible: non-row-sharded lookups whose Ids come straight from a feed
    (the CTR DataFeed pattern). Sharded (shard_axis) lookups keep the
    in-graph per-process callbacks.

    Returns (program_copy, pulls, pushes) -- or (program, [], []) when
    nothing is eligible. pulls: [(table, ids_feed, out_var)];
    pushes: [(table, ids_feed, grad_var, anchor_grad_var)].
    """
    from ..framework import Program

    if not any(op.type == "host_lookup_table"
               for op in program.global_block().ops):
        return program, [], []

    p2 = Program.from_dict(program.to_dict())
    b2 = p2.global_block()
    pulls, pushes, drop = [], [], set()
    # single eligibility filter, applied once over the copy (op order is
    # preserved by the dict round-trip)
    for op in list(b2.ops):
        if op.type == "host_lookup_table" and not op.attr("shard_axis",
                                                          None):
            ids_name = op.inputs["Ids"][0]
            iv = b2.find_var_recursive(ids_name)
            if iv is None or not iv.is_data:
                continue
            out = op.outputs["Out"][0]
            b2.find_var_recursive(out).is_data = True
            pulls.append((op.attr("table_name"), ids_name, out))
            drop.add(id(op))
    if not pulls:
        return program, [], []
    pull_keys = {(t, i) for t, i, _ in pulls}
    for idx, op in enumerate(list(b2.ops)):
        if op.type == "host_push_grad":
            key = (op.attr("table_name"), op.inputs["Ids"][0])
            if key not in pull_keys:
                continue
            anchor_grad = op.outputs["Anchor@GRAD"][0]
            pushes.append((op.attr("table_name"), op.inputs["Ids"][0],
                           op.inputs["OutGrad"][0], anchor_grad))
            drop.add(id(op))
            # the anchor's optimizer update still consumes Anchor@GRAD:
            # it is identically zero (the anchor never receives real
            # gradient), so materialize the zeros the push op used to emit
            av = b2.find_var_recursive(anchor_grad[:-5])
            zop = type(op)(
                b2, "fill_constant", inputs={},
                outputs={"Out": [anchor_grad]},
                attrs={"shape": list(av.shape) if av is not None else [1],
                       "dtype": "float32", "value": 0.0})
            b2.ops[idx] = zop
            drop.discard(id(zop))
    b2.ops = [o for o in b2.ops if id(o) not in drop]
    return p2, pulls, pushes


def run_pulls(pulls, feed):
    """Host-side gathers for hoisted pulls: extend ``feed`` with the rows."""
    for table_name, ids_name, out_name in pulls:
        if ids_name not in feed:
            raise KeyError(
                f"host_lookup_table over {table_name!r}: hoisted pull needs "
                f"ids {ids_name!r} in the feed. If this is an eval-style "
                f"run that only fetches a sub-graph not using this lookup, "
                f"pass use_prune=True to Executor.run so unused pulls are "
                f"pruned away instead of demanding their ids; otherwise "
                f"feed {ids_name!r}.")
        ids = np.asarray(feed[ids_name])
        if ids.ndim > 1 and ids.shape[-1] == 1:
            ids = ids[..., 0]            # lookup_table squeeze parity
        feed[out_name] = get_table(table_name).gather(ids)
    return feed


def run_pushes(pushes, fetched):
    """Apply hoisted pushes: fetched maps grad var name -> host array."""
    for table_name, ids_name, grad_name, _ in pushes:
        g = fetched.get(grad_name)
        if g is None:
            continue   # lookup output had no gradient this run (eval)
        get_table(table_name).push(fetched[ids_name],
                                   np.asarray(g))
