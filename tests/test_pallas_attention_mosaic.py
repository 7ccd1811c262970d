"""The Pallas kernels through Mosaic, at the benchmark's shapes, for a TPU v5e
that is described and not attached: what the interpreter cannot see (VMEM
budgets, tiling, the in-kernel PRNG). The flash kernels at BERT's and the
decoder's shapes, the grouped expert matmuls at OLMoE's, and a small expert
layer's whole train step (what a Program's grad ops leave in it). Nothing
runs, so this says nothing about results or times. All such compiles live in
this one file: the worker that gets it loads libtpu, and keeps it until it
exits.
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


# (batch, heads, S, head dim, dtype, dropout, causal, bias):
# bert_base.pretrain_s2048's attention; the longest S the whole-row kernel
# takes at BLK_Q; f32 inputs; olmoe_1b_7b.pretrain_s4096's (its second
# shape: causal, no bias, no dropout, d=128) at 2 and at 4 sequences
CASES = [(8, 12, 2048, 64, jnp.bfloat16, 0.1, False, True),
         (4, 12, 4096, 64, jnp.bfloat16, 0.1, False, True),
         (8, 12, 2048, 64, jnp.float32, 0.0, True, False),
         (2, 16, 4096, 128, jnp.bfloat16, 0.0, True, False),
         (4, 16, 4096, 128, jnp.bfloat16, 0.0, True, False)]


@pytest.mark.parametrize("B,H,S,D,dtype,dropout,causal,use_bias", CASES)
def test_flash_compiles_for_v5e(one_chip, B, H, S, D, dtype, dropout, causal,
                                use_bias):
    x = jax.ShapeDtypeStruct((B, H, S, D), dtype, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32, sharding=one_chip)

    def attend(q, k, v, bias):
        return pa._flash(q, k, v, bias if use_bias else None, jnp.int32(3),
                         D ** -0.5, dropout, causal, False)

    def grads(q, k, v, bias, g):
        return jax.vjp(lambda q, k, v: attend(q, k, v, bias), q, k, v)[1](g)

    assert _kernels(jax.jit(attend).lower(x, x, x, bias).compile()) == 1
    # What a Program's grad op lowers (core/registry.py: the forward again
    # under jax.vjp, its output unused): the backward kernel alone. A
    # residual written by the forward kernel would keep a second forward.
    assert _kernels(jax.jit(grads).lower(x, x, x, bias, x).compile()) == 1


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``pallas_mode.on_tpu`` says what it will say on the chip (the backend
    here is the CPU; the compile is for the described TPU)."""
    from paddle_tpu.ops import pallas_mode
    monkeypatch.setattr(pallas_mode, "on_tpu", lambda: True)


@pytest.mark.parametrize("rows,k,n", [(131072, 2048, 1024),
                                      (131072, 1024, 2048),
                                      (65536, 2048, 1024)])
def test_grouped_expert_matmul_compiles_for_v5e(one_chip, as_on_the_chip,
                                                rows, k, n):
    """OLMoE's expert products (64 experts, 4 x 4096 and 2 x 4096 tokens x
    top-8 rows) through the megablox kernels at ``GMM_TILING``: one kernel
    forward; and what a Program's grad op lowers -- the forward again under
    jax.vjp, its output unused -- holds the rows' and the weights' gradient
    kernels and nothing of the forward."""
    from paddle_tpu.ops import decoder_ops
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16, sharding=one_chip)
    count = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16, sharding=one_chip)

    def grads(x, w, count, g):
        return jax.vjp(lambda x, w: decoder_ops.grouped_matmul(x, w, count),
                       x, w)[1](g)

    fwd = jax.jit(decoder_ops.grouped_matmul).lower(x, w, count).compile()
    assert _kernels(fwd) == 1
    assert _kernels(jax.jit(grads).lower(x, w, count, g).compile()) == 2


def test_expert_layer_step_holds_nine_grouped_matmuls_and_one_sort_by_expert(
        one_chip, as_on_the_chip):
    """D11 on the decoder: every grad op re-lowers its forward under
    jax.vjp. A small decoder_lm train step (one layer, widths Mosaic takes,
    S below the flash kernel's) compiled for the v5e must hold, a layer,
    3 forward + 6 backward grouped-matmul kernels and one sort, and no more:
    the copies the grad ops trace are dropped or merged."""
    import re

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Executor
    from paddle_tpu.models import decoder_lm

    model = {"hidden_size": 256, "num_hidden_layers": 1,
             "num_attention_heads": 2, "num_experts": 4,
             "num_experts_per_tok": 2, "intermediate_size": 128,
             "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000,
             "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001,
             "dtype": "bfloat16"}
    batch, seq = 2, 128
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        fluid.optimizer.AdamW(4e-4, weight_decay=0.1).minimize(out["loss"])
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    taken = {}

    class Captured(Exception):
        pass

    def capture(self, key, compiled, args):
        taken["fn"], taken["args"] = compiled.fn, args
        raise Captured()

    real = Executor._aot_compile
    Executor._aot_compile = capture
    try:
        with pytest.raises(Captured):
            exe.run(main, feed={
                "ids": np.zeros((batch, seq), np.int32),
                "labels": np.zeros((batch * seq, 1), np.int32)},
                fetch_list=[out["loss"]], scope=scope)
    finally:
        Executor._aot_compile = real
        exe.close()

    def spec(x):
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    text = taken["fn"].lower(
        *jax.tree_util.tree_map(spec, taken["args"])).compile().as_text()
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    in_scope = lambda ln, scope: re.search(                 # noqa: E731
        r'op_name="[^"]*/' + scope + r'#\d+/', ln) is not None
    assert sum(in_scope(ln, "moe_expert_matmul") for ln in kernels) == 3
    assert sum(in_scope(ln, "moe_expert_matmul_grad") for ln in kernels) == 6
    assert len(kernels) == 9            # S=128: attention is XLA's here
    # the stable sort by expert, once; XLA's TPU top_k is a sort too (the
    # router's), and neither is traced a second time into the step by the
    # grad ops
    sorts = [ln for ln in text.splitlines() if re.search(r"\ssort\(", ln)]
    assert sum(in_scope(ln, "moe_dispatch") for ln in sorts) == 1
    assert sum(in_scope(ln, "moe_router") for ln in sorts) == 1
    assert not [ln for ln in sorts if in_scope(ln, "moe_dispatch_grad")
                or in_scope(ln, "moe_router_grad")]
