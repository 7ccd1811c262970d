"""The state-space scan's kernels as they stood before PR 60 (``paddle_tpu/ops/
pallas_ssd.py`` at ef147c4, its kernels, specs and calls, nothing edited but
this text): a grid step walks its ``HEADS // 2`` lane tiles, and each tile's
two heads, in Python loops, one whole chain behind the other, and a head's
scalars travel twice, ``[.., Q, HEADS]`` and ``[.., HEADS, Q]``. Kept outside
the package for two readers: ``tools/granite_probe.py kernels`` times this
form beside the package's on the chip and compares their outputs' bits, and
``tests/test_ssd_scan.py`` holds the package's kernels to this form's bits in
the interpreter. ``_fwd_call`` / ``_bwd_call`` take what the package's take.
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

HEADS = 8           # heads a grid step
HEAD_DIM = 64       # two heads a 128-lane tile
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def supports(seq: int, heads: int, head_dim: int, state: int,
             chunk: int) -> bool:
    """Whether the kernels take these shapes (else the composed form)."""
    return (head_dim == HEAD_DIM and heads % HEADS == 0 and state % 128 == 0
            and chunk % 128 == 0 and seq % chunk == 0)


def _pl():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def _dot(a, b, dims):
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):      # a @ b
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):      # a @ b^T
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):      # a^T @ b
    return _dot(a, b, ((0,), (0,)))


def _spread(first, cols, k):
    """Heads k and k + 1 of ``cols [Q, HEADS]`` along the lanes of a tile:
    head k's value in the first 64 lanes, head k + 1's in the others."""
    import jax.numpy as jnp
    return jnp.where(first, cols[:, k:k + 1], cols[:, k + 1:k + 2])


def _decay(lower, cumc, cumr, k):
    """``L`` of head k: exp(cum_i - cum_j) on and under the diagonal."""
    import jax.numpy as jnp
    return jnp.exp(jnp.where(lower, cumc[:, k:k + 1] - cumr[k:k + 1, :],
                             -jnp.inf))


def _iotas(q):
    import jax
    import jax.numpy as jnp
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (q, 128), 1) < HEAD_DIM
    return rows >= cols, first


def _fwd_kernel(emit, x_ref, bm_ref, cm_ref, bt_ref, dtc_ref, cumc_ref,
                cumr_ref, d_ref, o_ref, h_ref):
    """``emit="y"``: the output; ``"states"``: the state entering each chunk
    (the recurrence alone, for the backward)."""
    import jax.numpy as jnp
    pl, _ = _pl()
    bf = x_ref.dtype            # the products' operand type

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    q = x_ref.shape[1]
    lower, first = _iotas(q)
    cm, bt = cm_ref[0], bt_ref[0]
    dtc, cumc, cumr = dtc_ref[0, 0], cumc_ref[0, 0], cumr_ref[0, 0]
    if emit == "states":
        o_ref[0, 0] = h_ref[...]
    else:
        g = _nn(cm, bt)                                     # C B^T [Q, Q]
    for t in range(HEADS // 2):
        sl = slice(128 * t, 128 * (t + 1))
        xs = x_ref[0, :, sl].astype(jnp.float32)
        cum = _spread(first, cumc, 2 * t)
        xd = xs * _spread(first, dtc, 2 * t)
        h = h_ref[:, sl]                                    # [N, 2 P]
        end = cum[q - 1:q, :]
        if emit == "y":
            y = jnp.exp(cum) * _nn(cm, h.astype(bf)) + xs * d_ref[:, sl]
            for k, mine in ((2 * t, first), (2 * t + 1, ~first)):
                m = (g * _decay(lower, cumc, cumr, k)).astype(bf)
                y += _nn(m, jnp.where(mine, xd, 0.0).astype(bf))
            o_ref[0, :, sl] = y.astype(o_ref.dtype)
        h_ref[:, sl] = jnp.exp(end) * h + _nn(
            bt, (xd * jnp.exp(end - cum)).astype(bf))


def _halves(first, z):
    """The sums of ``z [R, 128]`` over the first and the other 64 lanes."""
    import jax.numpy as jnp
    return (jnp.sum(jnp.where(first, z, 0.0), axis=1, keepdims=True),
            jnp.sum(jnp.where(first, 0.0, z), axis=1, keepdims=True))


def _bwd_kernel(x_ref, dy_ref, bm_ref, cm_ref, bt_ref, ct_ref, dtc_ref,
                cumc_ref, cumr_ref, d_ref, st_ref,
                dx_ref, ddt_ref, dcum_ref, dbm_ref, dcm_ref, dd_ref, dh_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)     # the last chunk: nothing follows it
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    q = x_ref.shape[1]
    lower, first = _iotas(q)
    first_n = jax.lax.broadcasted_iota(
        jnp.int32, st_ref.shape[2:3] + (128,), 1) < HEAD_DIM
    bm, cm, bt, ct = bm_ref[0], cm_ref[0], bt_ref[0], ct_ref[0]
    dtc, cumc, cumr = dtc_ref[0, 0], cumc_ref[0, 0], cumr_ref[0, 0]
    g = _nn(cm, bt)
    head = jax.lax.broadcasted_iota(jnp.int32, (q, HEADS), 1)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dg = jnp.zeros((q, q), jnp.float32)
    dcm = jnp.zeros(dcm_ref.shape[2:], jnp.float32)
    dbm = jnp.zeros(dbm_ref.shape[2:], jnp.float32)
    ddt = jnp.zeros((q, HEADS), jnp.float32)
    dcum = jnp.zeros((q, HEADS), jnp.float32)
    for t in range(HEADS // 2):
        sl = slice(128 * t, 128 * (t + 1))
        xs = x_ref[0, :, sl].astype(jnp.float32)
        dys = dy_ref[0, :, sl].astype(jnp.float32)
        cum = _spread(first, cumc, 2 * t)
        dts = _spread(first, dtc, 2 * t)
        xd = xs * dts
        xdb = xd.astype(bf)
        h, dh = st_ref[0, 0, :, sl], dh_ref[:, sl]
        end = cum[q - 1:q, :]
        e, f, e_end = jnp.exp(cum), jnp.exp(end - cum), jnp.exp(end)
        ynd = e * _nn(cm, h.astype(bf))            # y without the D term
        dxd_state = f * _nn(bm, dh.astype(bf))
        dxd_chunk = jnp.zeros_like(dxd_state)
        for k, mine in ((2 * t, first), (2 * t + 1, ~first)):
            decay = _decay(lower, cumc, cumr, k)
            m = (g * decay).astype(bf)
            dy_k = jnp.where(mine, dys, 0.0).astype(bf)
            ynd += _nn(m, jnp.where(mine, xd, 0.0).astype(bf))
            dxd_chunk += _tn(m, dy_k)
            dg += _nt(dy_k, xdb) * decay
        dxd = dxd_state + dxd_chunk
        dx_ref[0, :, sl] = (dxd * dts + dys * d_ref[:, sl]).astype(
            dx_ref.dtype)
        dd_ref[0, :, sl] += jnp.sum(dys * xs, axis=0, keepdims=True)
        ddt_k = _halves(first, dxd * xs)
        from_y = _halves(first, dys * ynd)
        # sum_j M_ij dy_i xd_j over the chunk's pairs, from the same rounded
        # operands as ynd's: in a position's running sum of dcum the pairs
        # on both sides of it cancel, and must do so exactly (with xd
        # unrounded here the residue of the triangle drowned the few pairs
        # that remain: A_log's and dt_bias' gradients read 12-33% off on the
        # chip, PERF.md section 6, PR 35)
        from_chunk = _halves(first, xdb.astype(jnp.float32) * dxd_chunk)
        moved = _halves(first, xd * dxd_state)
        kept = _halves(first_n, h * dh * e_end)
        for i, k in enumerate((2 * t, 2 * t + 1)):
            # <H_next, dH_next> of the head, at the chunk's last position
            at_last = jnp.sum(moved[i], axis=0, keepdims=True) + jnp.sum(
                kept[i], axis=0, keepdims=True)
            dcum_k = from_y[i] - from_chunk[i] - moved[i] + jnp.where(
                at_end, at_last, 0.0)
            ddt = jnp.where(head == k, ddt_k[i], ddt)
            dcum = jnp.where(head == k, dcum_k, dcum)
        dye = (dys * e).astype(bf)
        dcm += _nt(dye, h.astype(bf))
        dbm += _nt((xd * f).astype(bf), dh.astype(bf))
        dh_ref[:, sl] = e_end * dh + _nn(ct, dye)
    ddt_ref[0, 0] = ddt
    dcum_ref[0, 0] = dcum
    dgb = dg.astype(bf)
    dcm_ref[0, 0] = dcm + _nn(dgb, bm)
    dbm_ref[0, 0] = dbm + _tn(dgb, cm)


def _params(interpret):
    if interpret:
        return {}
    _, pltpu = _pl()
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def _by_head(v):
    """``[B, S, heads]`` -> ``[B, heads / HEADS, S, HEADS]`` (to spread along
    lanes) and ``[B, heads / HEADS, HEADS, S]`` (along sublanes)."""
    b, s, h = v.shape
    v = v.reshape(b, s, h // HEADS, HEADS)
    return v.transpose(0, 2, 1, 3), v.transpose(0, 2, 3, 1)


def _specs(q, n, chunk_of):
    """Block specs of the operands both passes read, the chunk a grid step
    works on given by ``chunk_of(c)``."""
    pl, pltpu = _pl()
    wide = HEADS * HEAD_DIM

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)
    x = spec((1, q, wide), lambda b, j, c: (b, chunk_of(c), j))
    rows = spec((1, q, n), lambda b, j, c: (b, chunk_of(c), 0))
    cols = spec((1, n, q), lambda b, j, c: (b, 0, chunk_of(c)))
    lanes = spec((1, 1, q, HEADS), lambda b, j, c: (b, j, chunk_of(c), 0))
    subl = spec((1, 1, HEADS, q), lambda b, j, c: (b, j, 0, chunk_of(c)))
    d = spec((1, wide), lambda b, j, c: (0, j))
    state = spec((1, 1, n, wide), lambda b, j, c: (b, chunk_of(c), 0, j))
    return x, rows, cols, lanes, subl, d, state


# behind a jit of its own, like the flash kernels: the layers of a model (and
# the forward a grad op traces again) share one trace and one lowering
@functools.partial(_jax.jit, static_argnames=("chunk", "interpret", "emit"))
def _fwd_call(x, dt, cum, bm, cm, drow, chunk, interpret, emit="y"):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, wide = x.shape
    n, chunks = bm.shape[2], seq // chunk
    x_s, rows, cols, lanes, subl, d_s, state = _specs(chunk, n, lambda c: c)
    dtc, _ = _by_head(dt)
    cumc, cumr = _by_head(cum)
    if emit == "y":
        out_spec, out_shape = x_s, jax.ShapeDtypeStruct(x.shape, x.dtype)
    else:
        out_spec, out_shape = state, jax.ShapeDtypeStruct(
            (batch, chunks, n, wide), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, emit),
        grid=(batch, wide // (HEADS * HEAD_DIM), chunks),
        in_specs=[x_s, rows, rows, cols, lanes, lanes, subl, d_s],
        out_specs=out_spec, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, HEADS * HEAD_DIM), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(x, bm, cm, bm.transpose(0, 2, 1), dtc, cumc, cumr, drow)


@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(x, dt, cum, bm, cm, drow, dy, chunk, interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, wide = x.shape
    n, chunks = bm.shape[2], seq // chunk
    blocks = wide // (HEADS * HEAD_DIM)
    states = _fwd_call(x, dt, cum, bm, cm, drow, chunk, interpret, "states")
    x_s, rows, cols, lanes, subl, d_s, state = _specs(
        chunk, n, lambda c: chunks - 1 - c)
    dtc, _ = _by_head(dt)
    cumc, cumr = _by_head(cum)
    pl_spec = pl.BlockSpec
    shared = pl_spec((1, 1, chunk, n),
                     lambda b, j, c: (b, j, chunks - 1 - c, 0),
                     memory_space=pltpu.VMEM)
    f32 = jnp.float32
    by_lanes = jax.ShapeDtypeStruct((batch, blocks, seq, HEADS), f32)
    by_block = jax.ShapeDtypeStruct((batch, blocks, seq, n), f32)
    dx, ddt, dcum, dbm, dcm, dd = pl.pallas_call(
        _bwd_kernel, grid=(batch, blocks, chunks),
        in_specs=[x_s, x_s, rows, rows, cols, cols, lanes, lanes, subl, d_s,
                  state],
        out_specs=[x_s, lanes, lanes, shared, shared,
                   pl_spec((1, 1, HEADS * HEAD_DIM),
                           lambda b, j, c: (b, 0, j),
                           memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), by_lanes,
                   by_lanes, by_block, by_block,
                   jax.ShapeDtypeStruct((batch, 1, wide), f32)],
        scratch_shapes=[pltpu.VMEM((n, HEADS * HEAD_DIM), f32)],
        interpret=interpret, **_params(interpret),
    )(x, dy, bm, cm, bm.transpose(0, 2, 1), cm.transpose(0, 2, 1), dtc, cumc,
      cumr, drow, states)

    def heads_last(v):      # [B, blocks, S, HEADS] -> [B, S, heads]
        return v.transpose(0, 2, 1, 3).reshape(batch, seq, blocks * HEADS)
    return (dx, heads_last(ddt), heads_last(dcum),
            jnp.sum(dbm, axis=1).astype(bm.dtype),
            jnp.sum(dcm, axis=1).astype(cm.dtype),
            jnp.sum(dd, axis=0).astype(drow.dtype))
