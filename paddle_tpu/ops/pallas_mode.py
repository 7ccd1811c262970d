"""Where a Pallas TPU kernel may run in this process.

On platform ``tpu`` a kernel is compiled by Mosaic, always. Off TPU it does
not run at all -- ``impl='auto'`` lowers the composed XLA path,
``impl='pallas'`` raises, and a tuning candidate that needs the kernel is
unmeasurable -- unless the test harness has asked for the Pallas interpreter
by setting ``TEST_INTERPRET`` (``tests/conftest.py`` does; nothing else
may). A number timed in the interpreter says nothing about the chip, so no
production path reaches it.

``lowers_kernels`` is the one rule by which an op with a kernel and a
composed form chooses between them. The lowerings that ask it:
``fused_attention`` and its grad op (``pallas_attention._plan``),
``rotary_embedding`` and its grad op, ``short_conv``, ``ssd_scan``,
``gated_delta_rule`` and its grad op, ``moe_expert_matmul`` (megablox's
``gmm``), ``rms_norm`` given a gate and its grad op, the token sums of
``moe_combine`` and of ``moe_dispatch``'s grad op, and the change of order
of the rows an expert layer's exchange received (``_rows_kernel``), those
eight in ``decoder_ops``. ``conv2d_bn_fused`` (``pallas_conv_bn``)
and the int8 matmul (``contrib/quantize``) still test the platform
themselves (ROADMAP D2).
"""
from __future__ import annotations

#: set True only by the test harness: off TPU, run kernels in the Pallas
#: interpreter so the CPU suite exercises the kernel bodies
TEST_INTERPRET = False


def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """The ``interpret=`` argument for a ``pallas_call`` issued here: never
    True on platform ``tpu``."""
    return TEST_INTERPRET and not on_tpu()


def available() -> bool:
    """Whether a Pallas TPU kernel can run here at all."""
    return on_tpu() or TEST_INTERPRET


def lowers_kernels(ctx, impl: str, fits: bool, what: str = "",
                   needs: str = "", shards: int = 1) -> bool:
    """Whether the op being lowered under ``ctx`` (a ``LowerCtx``), with attr
    ``impl`` (``auto`` / ``pallas`` / another lowering's name), lowers its
    Pallas kernels here; ``fits`` is the op's own answer to whether the
    kernels take its shapes. Never under shape inference (``ctx.abstract``),
    where every lowering gives the same shapes. ``pallas``: yes, and what
    cannot run raises -- no kernel can run here (``require``), or the shapes
    do not fit (a ``ValueError`` of ``what`` and ``needs``, the op's name and
    its own sentence of what the kernels need and what they got). ``auto``:
    where the shapes fit, a kernel can run, and the jit being traced spans
    one device. A Mosaic call has no partitioning rule: a jit over more than
    one device refuses to lower one outside a ``shard_map`` ("Mosaic kernels
    cannot be automatically partitioned"; seen on the chip, PR 27), so under
    a GSPMD mesh of several devices ``auto`` is the kernels only where the
    op calls them inside an island -- ``shards`` > 1: the devices of the
    data axis over which the op will lay its batch rows through
    ``ctx.island`` (``ctx.data_shards`` of those dimensions; ``fits`` is
    then the op's answer for one device's rows), or the lowering is inside
    a ``shard_map`` already (another op's island, ``ctx.mesh``) -- and the
    composed form, which GSPMD partitions, otherwise: an op whose batch
    does not divide over the data axis, or a family that has no island yet.
    Asking marks the op (``ctx.asked_kernels``) as one this rule decides
    for."""
    ctx.asked_kernels = True
    if ctx.abstract:
        return False
    if impl == "pallas":
        require(f"{what} impl='pallas'")
        if not fits:
            raise ValueError(f"{what} impl='pallas' {needs}")
        return True
    gm = ctx.gspmd_mesh
    return (impl == "auto" and fits and available()
            and (gm is None or gm.size == 1 or shards > 1 or in_island()))


def in_island() -> bool:
    """Whether what is being traced is inside a ``shard_map`` (some mesh
    axis is manual): a Mosaic call is legal there."""
    import jax
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def require(what: str) -> None:
    if not available():
        import jax
        raise RuntimeError(
            f"{what} is a Pallas TPU kernel and the platform here is "
            f"{jax.default_backend()!r}: it runs only on a TPU (the Pallas "
            f"interpreter is for the test harness, see ops/pallas_mode.py)")
