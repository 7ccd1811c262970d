"""Where a Pallas TPU kernel may run in this process.

One rule for every kernel (``pallas_attention``, ``pallas_int8``,
``pallas_conv_bn``) and for the autotuner's candidates: on platform ``tpu``
the kernel is compiled by Mosaic, always. Off TPU it does not run at all --
``impl='auto'`` lowers the composed XLA path, ``impl='pallas'`` raises, and a
tuning candidate that needs the kernel is unmeasurable -- unless the test
harness has asked for the Pallas interpreter by setting ``TEST_INTERPRET``
(``tests/conftest.py`` does; nothing else may). A number timed in the
interpreter says nothing about the chip, so no production path reaches it.
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


def lowers_kernels(impl: str, fits: bool, abstract: bool) -> bool:
    """Whether an op with attr ``impl`` (``auto`` / ``pallas`` / another
    lowering's name) lowers its Pallas kernels here: asked for by name, or
    ``auto`` where the shapes fit and a kernel can run; never under shape
    inference (``abstract``), where every lowering gives the same shapes."""
    return not abstract and (impl == "pallas" or (
        impl == "auto" and fits and available()))


def require(what: str) -> None:
    if not available():
        import jax
        raise RuntimeError(
            f"{what} is a Pallas TPU kernel and the platform here is "
            f"{jax.default_backend()!r}: it runs only on a TPU (the Pallas "
            f"interpreter is for the test harness, see ops/pallas_mode.py)")
