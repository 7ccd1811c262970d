"""The flash kernels through Mosaic, at the benchmark's shapes, for a TPU v5e
that is described and not attached: what the interpreter cannot see (VMEM
budgets, tiling, the in-kernel PRNG). Nothing runs, so this says nothing about
results or times. All such compiles live in this one file: the worker that
gets it loads libtpu, and keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import pallas_attention as pa


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernels(compiled):
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# (batch, S, dtype, dropout, causal, bias): bert_base.pretrain_s2048's
# attention; the longest S the whole-row kernel takes at BLK_Q; f32 inputs
CASES = [(8, 2048, jnp.bfloat16, 0.1, False, True),
         (4, 4096, jnp.bfloat16, 0.1, False, True),
         (8, 2048, jnp.float32, 0.0, True, False)]


@pytest.mark.parametrize("B,S,dtype,dropout,causal,use_bias", CASES)
def test_flash_compiles_for_v5e(one_chip, B, S, dtype, dropout, causal,
                                use_bias):
    x = jax.ShapeDtypeStruct((B, 12, S, 64), dtype, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32, sharding=one_chip)

    def attend(q, k, v, bias):
        return pa._flash(q, k, v, bias if use_bias else None, jnp.int32(3),
                         0.125, dropout, causal, False)

    def grads(q, k, v, bias, g):
        return jax.vjp(lambda q, k, v: attend(q, k, v, bias), q, k, v)[1](g)

    assert _kernels(jax.jit(attend).lower(x, x, x, bias).compile()) == 1
    # What a Program's grad op lowers (core/registry.py: the forward again
    # under jax.vjp, its output unused): the backward kernel alone. A
    # residual written by the forward kernel would keep a second forward.
    assert _kernels(jax.jit(grads).lower(x, x, x, bias, x).compile()) == 1
