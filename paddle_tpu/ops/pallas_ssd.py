"""The chunked state-space scan of a Mamba-2 layer (``ops/ssm_ops.py:
ssd_scan``; Dao & Gu, arXiv:2405.21060) as Pallas TPU kernels, forward and
backward.

Per head, with state ``h [N, P]``, ``a_t = dt_t A`` and ``xd_t = dt_t x_t``:
``h_t = exp(a_t) h_{t-1} + B_t (x) xd_t``, ``y_t = C_t h_t + D x_t``. In chunks
of Q positions, ``cum`` the running sum of ``a`` inside a chunk:
``Y = (L * (C B^T)) Xd + exp(cum) * (C H_prev) + D X`` with ``L[i, j] =
exp(cum_i - cum_j)`` for i >= j, and ``H_next = exp(cum_Q) H_prev + B^T
(exp(cum_Q - cum) * Xd)``. The composed form builds ``L`` as a ``[batch,
heads, chunks, Q, Q]`` float32 array in HBM; here it lives in VMEM, a head
and a chunk at a time.

A grid step is (batch, a block of ``step_heads`` heads, chunk), the chunks
in order and the state carried in VMEM scratch as ``[N, heads a step * P]``
float32. ``x`` stays ``[B, S, heads * P]`` as the projection wrote it: two
heads fill the 128 lanes of a tile, the products of one head take the tile
with the other head's lanes zeroed (an MXU pass is 128 wide either way), and
what is summed over heads (``dB``, ``dC``, both shared by the heads)
contracts over both at once. A step walks its lane tiles, and each tile's two
heads, one behind the other, and that order is the point here as its opposite
is in ``pallas_delta.py`` (its module docstring): there a head's chunk is a
chain of small dependent products that waits, and the heads' chains side by
side fill the waits; here a head's chunk is a few wide passes over ``[Q, Q]``
float32 blocks, 64 vector registers each, the kernels are bound by what a
bundle issues (stores, the lane unit, the MXU) and not by waits, and the same
work mapped over a leading head axis keeps eight such blocks alive at once
and spills them (chip, PR 60: 1.26 -> 1.43 ms a layer's backward). What
bought time instead: the matrices that are zero above the diagonal (``L``,
``C B^T`` under it, its gradient) are held as row strips of 128 rows that end
at their diagonal block, so the blocks above it are neither made nor
multiplied (``_rows``, ``_masks``); ``dcum`` takes one sum over a head's lanes, of the
products' difference, where it took three (a lane sum runs on the one unit
that also spreads the scalars along the lanes); the forward's recurrence
reads no ``C``. ``C B^T`` is made once a step for its heads. A head's scalars
(``dt``, ``cum`` in, ``ddt``, ``dcum`` out) travel in one layout, ``[B,
heads / step, 2 * step, S]``: a step's block is ``dt`` a head a row over
``cum`` a head a row, 2 MB an array at 64 heads of 4096 positions (a ``[..,
S, heads a step]`` array, eight or sixteen lanes of a tile's 128, takes 16
MB, and the kernels read three and wrote two of those). The ``[Q, 1]``
columns that the lanes are scaled by come from one transposition of the
block in VMEM, and ``ddt`` / ``dcum`` leave through one. The decay, its
running sums, the exps and the state are float32; the products take bfloat16
operands and accumulate in float32.

The backward recomputes: a first kernel runs the state recurrence alone and
writes the state entering each chunk (``[B, chunks, N, heads * P]`` float32,
about as large as ``x``), a second walks the chunks in reverse with the
state's gradient in scratch. Nothing the forward kernel wrote is kept (a
Program's grad op lowers its forward again under ``jax.vjp``; XLA drops that
copy only while none of its outputs is read: pallas_attention.py). The
gradient of ``cum`` needs no ``[Q, Q]`` reduction: ``dcum_i = dy_i . (y_i -
D x_i) - xd_i . dxd_i``, plus at a chunk's last position ``<H_next,
dH_next>``; the two products are formed from the same rounded operands,
because the running sum that turns ``dcum`` into the decay's gradient
cancels nearly all of them against each other. From ``dcum`` and the direct
``ddt`` back to ``dt`` and ``A`` is plain ``jax.numpy`` around the kernels,
differentiated by JAX.
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

HEADS = 16          # heads a grid step takes at most (``step_heads``)
HEAD_BLOCK = 8      # ... in whole blocks of these
HEAD_DIM = 64       # two heads a 128-lane tile
BLOCK = 128         # side of the blocks a ``[Q, Q]`` matrix is skipped by
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def supports(seq: int, heads: int, head_dim: int, state: int,
             chunk: int) -> bool:
    """Whether the kernels take these shapes (else the composed form)."""
    return (head_dim == HEAD_DIM and heads % HEAD_BLOCK == 0
            and state % 128 == 0 and chunk % BLOCK == 0 and seq % chunk == 0)


def step_heads(heads: int) -> int:
    """The heads a grid step takes: the most whole ``HEAD_BLOCK``s that
    divide ``heads`` and do not exceed ``HEADS``."""
    return max(h for h in range(HEAD_BLOCK, HEADS + 1, HEAD_BLOCK)
               if heads % h == 0)


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


# A [Q, Q] matrix that is zero above its diagonal -- L, C B^T under it, the
# gradient of that -- is held as its row strips: strip r is rows [128 r,
# 128 (r + 1)) by columns [0, 128 (r + 1)), and the blocks above the
# diagonal's (a quarter of the matrix at Q = 256) are neither made nor
# multiplied. A strip is still contracted whole, its column blocks in order,
# so a product's sums are the whole matrix's without their zero terms.

def _rows(r):
    """Strip r's rows (and block r's columns)."""
    return slice(BLOCK * r, BLOCK * (r + 1))


def _masks(q):
    """Where strip r is on or under the diagonal, a strip each; and which
    lanes of a tile are its first head's."""
    import jax
    import jax.numpy as jnp
    lower = []
    for r in range(q // BLOCK):
        shape = (BLOCK, BLOCK * (r + 1))
        lower.append(jax.lax.broadcasted_iota(jnp.int32, shape, 0) + BLOCK * r
                     >= jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    first = jax.lax.broadcasted_iota(jnp.int32, (q, 128), 1) < HEAD_DIM
    return lower, first


def _cb(cm, bt):
    """``C B^T``'s strips, float32."""
    return [_nn(cm[_rows(r)], bt[:, :BLOCK * (r + 1)])
            for r in range(cm.shape[0] // BLOCK)]


def _decay(lower, cumc, cumr):
    """``L`` of a head, exp(cum_i - cum_j) on and under the diagonal, from
    its running sums as a column ``[Q, 1]`` and as a row ``[1, Q]``: its
    strips."""
    import jax.numpy as jnp
    return [jnp.exp(jnp.where(mask, cumc[_rows(r)]
                              - cumr[:, :mask.shape[1]], -jnp.inf))
            for r, mask in enumerate(lower)]


def _lower_nn(strips, x):
    """``M @ x`` of a lower ``M``'s strips."""
    import jax.numpy as jnp
    return jnp.concatenate([_nn(m, x[:m.shape[1]]) for m in strips], axis=0)


def _lower_tn(strips, x):
    """``M^T @ x``: a column block of ``M`` reaches from its diagonal block
    down."""
    import jax.numpy as jnp
    out = []
    for c in range(len(strips)):
        out.append(_tn(jnp.concatenate([m[:, _rows(c)] for m in strips[c:]],
                                       axis=0), x[BLOCK * c:]))
    return jnp.concatenate(out, axis=0)


def _scalars(ref):
    """A step's scalars, ``[2 * heads a step, Q]`` (``_by_head``) -> ``dt``
    and ``cum`` a head a column ``[Q, heads a step]`` (one transposition of
    the block in VMEM) and ``cum`` a head a row."""
    rows = ref[0, 0]
    n = rows.shape[0] // 2
    cols = rows.T
    return cols[:, :n], cols[:, n:], rows[n:]


def _spread(first, cols, k):
    """Heads k and k + 1 of ``cols [Q, heads a step]`` along the lanes of a
    tile: head k's value in the first 64 lanes, head k + 1's in the
    others."""
    import jax.numpy as jnp
    return jnp.where(first, cols[:, k:k + 1], cols[:, k + 1:k + 2])


def _fwd_kernel(emit, x_ref, *refs):
    """``emit="y"``: the output; ``"states"``: the state entering each chunk
    (the recurrence alone, for the backward: it reads no C)."""
    import jax.numpy as jnp
    pl, _ = _pl()
    cm_ref, bt_ref, sc_ref, d_ref, o_ref, h_ref = (
        refs if emit == "y" else (None, *refs))
    bf = x_ref.dtype            # the products' operand type

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    q = x_ref.shape[1]
    lower, first = _masks(q)
    bt = bt_ref[0]
    dtc, cumc, cumr = _scalars(sc_ref)
    if emit == "states":
        o_ref[0, 0] = h_ref[...]
    else:
        cm = cm_ref[0]
        g = _cb(cm, bt)
    for t in range(x_ref.shape[2] // 128):
        sl = slice(128 * t, 128 * (t + 1))
        xs = x_ref[0, :, sl].astype(jnp.float32)
        cum = _spread(first, cumc, 2 * t)
        xd = xs * _spread(first, dtc, 2 * t)
        h = h_ref[:, sl]                                    # [N, 2 P]
        end = cum[q - 1:q, :]
        if emit == "y":
            y = jnp.exp(cum) * _nn(cm, h.astype(bf)) + xs * d_ref[:, sl]
            for k, mine in ((2 * t, first), (2 * t + 1, ~first)):
                decay = _decay(lower, cumc[:, k:k + 1], cumr[k:k + 1, :])
                y += _lower_nn([(a * b).astype(bf) for a, b in zip(g, decay)],
                               jnp.where(mine, xd, 0.0).astype(bf))
            o_ref[0, :, sl] = y.astype(o_ref.dtype)
        h_ref[:, sl] = jnp.exp(end) * h + _nn(
            bt, (xd * jnp.exp(end - cum)).astype(bf))


def _halves(first, z):
    """The sums of ``z [R, 128]`` over the first and the other 64 lanes."""
    import jax.numpy as jnp
    return (jnp.sum(jnp.where(first, z, 0.0), axis=1, keepdims=True),
            jnp.sum(jnp.where(first, 0.0, z), axis=1, keepdims=True))


def _bwd_kernel(x_ref, dy_ref, bm_ref, cm_ref, bt_ref, ct_ref, sc_ref, d_ref,
                st_ref, dx_ref, dsc_ref, dbm_ref, dcm_ref, dd_ref, dh_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf, f32 = x_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)     # the last chunk: nothing follows it
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    q = x_ref.shape[1]
    lower, first = _masks(q)
    bm, cm, ct = bm_ref[0], cm_ref[0], ct_ref[0]
    dtc, cumc, cumr = _scalars(sc_ref)
    heads = dtc.shape[1]
    g = _cb(cm, bt_ref[0])
    head = jax.lax.broadcasted_iota(jnp.int32, (q, 2 * heads), 1)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dg = [jnp.zeros(a.shape, f32) for a in g]
    dcm = jnp.zeros(dcm_ref.shape[2:], f32)
    dbm = jnp.zeros(dbm_ref.shape[2:], f32)
    dsc = jnp.zeros((q, 2 * heads), f32)    # ddt | dcum, a head a column
    for t in range(heads // 2):
        sl = slice(128 * t, 128 * (t + 1))
        xs = x_ref[0, :, sl].astype(f32)
        dys = dy_ref[0, :, sl].astype(f32)
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
            decay = _decay(lower, cumc[:, k:k + 1], cumr[k:k + 1, :])
            m = [(a * b).astype(bf) for a, b in zip(g, decay)]
            dy_k = jnp.where(mine, dys, 0.0).astype(bf)
            ynd += _lower_nn(m, jnp.where(mine, xd, 0.0).astype(bf))
            dxd_chunk += _lower_tn(m, dy_k)
            dg = [a + _nt(dy_k[_rows(r)], xdb[:b.shape[1]]) * b
                  for r, (a, b) in enumerate(zip(dg, decay))]
        dxd = dxd_state + dxd_chunk
        dx_ref[0, :, sl] = (dxd * dts + dys * d_ref[:, sl]).astype(
            dx_ref.dtype)
        dd_ref[0, :, sl] += jnp.sum(dys * xs, axis=0, keepdims=True)
        ddt_k = _halves(first, dxd * xs)
        # dcum_i = dy_i . ynd_i - xd_i . dxd_i, one sum over a head's lanes
        # of the three products' difference (a lane sum each cost the
        # reverse kernel a fifth of its time). sum_j M_ij dy_i xd_j over the
        # chunk's pairs is formed from the same rounded operands as ynd's:
        # in a position's running sum of dcum the pairs on both sides of it
        # cancel, and must do so exactly (with xd unrounded here the residue
        # of the triangle drowned the few pairs that remain: A_log's and
        # dt_bias' gradients read 12-33% off on the chip, PERF.md section 6,
        # PR 35)
        moved = xd * dxd_state
        dcum_k = _halves(first, dys * ynd - xdb.astype(f32) * dxd_chunk
                         - moved)
        # <H_next, dH_next> of a head, at the chunk's last position
        at_last = _halves(first[:1], jnp.sum(moved, axis=0, keepdims=True)
                          + jnp.sum(h * dh * e_end, axis=0, keepdims=True))
        for i, k in enumerate((2 * t, 2 * t + 1)):
            dsc = jnp.where(head == k, ddt_k[i], dsc)
            dsc = jnp.where(head == heads + k, dcum_k[i] + jnp.where(
                at_end, at_last[i], 0.0), dsc)
        dye = (dys * e).astype(bf)
        dcm += _nt(dye, h.astype(bf))
        dbm += _nt((xd * f).astype(bf), dh.astype(bf))
        dh_ref[:, sl] = e_end * dh + _nn(ct, dye)
    dsc_ref[0, 0] = dsc.T
    dgb = [a.astype(bf) for a in dg]
    dcm_ref[0, 0] = dcm + _lower_nn(dgb, bm)
    dbm_ref[0, 0] = dbm + _lower_tn(dgb, cm)


def _params(interpret):
    if interpret:
        return {}
    _, pltpu = _pl()
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def _by_head(dt, cum):
    """``dt`` and ``cum [B, S, heads]`` -> ``[B, heads / step, 2 * step, S]``
    (``step = step_heads(heads)``): a step's scalars as one block, ``dt`` a
    head a row and ``cum`` a head a row under them, the one layout the
    scalars travel in."""
    import jax.numpy as jnp
    b, s, h = dt.shape
    step = step_heads(h)
    v = jnp.stack([dt, cum], axis=2).reshape(b, s, 2, h // step, step)
    return v.transpose(0, 3, 2, 4, 1).reshape(b, h // step, 2 * step, s)


def _heads_last(v, which):
    """``_by_head``'s inverse, ``[B, heads / step, 2 * step, S]`` -> ``[B, S,
    heads]``: ``which`` 0 the rows in ``dt``'s place, 1 those in ``cum``'s."""
    b, blocks, rows, s = v.shape
    v = v.reshape(b, blocks, 2, rows // 2, s)[:, :, which]
    return v.transpose(0, 3, 1, 2).reshape(b, s, blocks * rows // 2)


def _specs(q, n, step, chunk_of):
    """Block specs of the operands both passes read, the chunk a grid step
    works on given by ``chunk_of(c)``."""
    pl, pltpu = _pl()
    wide = step * HEAD_DIM

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)
    x = spec((1, q, wide), lambda b, j, c: (b, chunk_of(c), j))
    rows = spec((1, q, n), lambda b, j, c: (b, chunk_of(c), 0))
    cols = spec((1, n, q), lambda b, j, c: (b, 0, chunk_of(c)))
    scalars = spec((1, 1, 2 * step, q), lambda b, j, c: (b, j, 0, chunk_of(c)))
    d = spec((1, wide), lambda b, j, c: (0, j))
    state = spec((1, 1, n, wide), lambda b, j, c: (b, chunk_of(c), 0, j))
    return x, rows, cols, scalars, d, state


# behind a jit of its own, like the flash kernels: the layers of a model (and
# the forward a grad op traces again) share one trace and one lowering
@functools.partial(_jax.jit, static_argnames=("chunk", "interpret", "emit"))
def _fwd_call(x, dt, cum, bm, cm, drow, chunk, interpret, emit="y"):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, wide = x.shape
    n, chunks = bm.shape[2], seq // chunk
    step = step_heads(wide // HEAD_DIM)
    x_s, rows, cols, scalars, d_s, state = _specs(chunk, n, step, lambda c: c)
    bt = bm.transpose(0, 2, 1)
    if emit == "y":
        operands, specs = (x, cm, bt), [x_s, rows, cols]
        out_spec, out_shape = x_s, jax.ShapeDtypeStruct(x.shape, x.dtype)
    else:
        operands, specs = (x, bt), [x_s, cols]
        out_spec, out_shape = state, jax.ShapeDtypeStruct(
            (batch, chunks, n, wide), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, emit),
        grid=(batch, wide // (step * HEAD_DIM), chunks),
        in_specs=[*specs, scalars, d_s],
        out_specs=out_spec, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, step * HEAD_DIM), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(*operands, _by_head(dt, cum), drow)


@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(x, dt, cum, bm, cm, drow, dy, chunk, interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, wide = x.shape
    n, chunks = bm.shape[2], seq // chunk
    step = step_heads(wide // HEAD_DIM)
    blocks = wide // (step * HEAD_DIM)
    states = _fwd_call(x, dt, cum, bm, cm, drow, chunk, interpret, "states")
    x_s, rows, cols, scalars, d_s, state = _specs(
        chunk, n, step, lambda c: chunks - 1 - c)
    pl_spec = pl.BlockSpec
    shared = pl_spec((1, 1, chunk, n),
                     lambda b, j, c: (b, j, chunks - 1 - c, 0),
                     memory_space=pltpu.VMEM)
    f32 = jnp.float32
    by_block = jax.ShapeDtypeStruct((batch, blocks, seq, n), f32)
    dx, dsc, dbm, dcm, dd = pl.pallas_call(
        _bwd_kernel, grid=(batch, blocks, chunks),
        in_specs=[x_s, x_s, rows, rows, cols, cols, scalars, d_s, state],
        out_specs=[x_s, scalars, shared, shared,
                   pl_spec((1, 1, step * HEAD_DIM),
                           lambda b, j, c: (b, 0, j),
                           memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((batch, blocks, 2 * step, seq), f32),
                   by_block, by_block,
                   jax.ShapeDtypeStruct((batch, 1, wide), f32)],
        scratch_shapes=[pltpu.VMEM((n, step * HEAD_DIM), f32)],
        interpret=interpret, **_params(interpret),
    )(x, dy, bm, cm, bm.transpose(0, 2, 1), cm.transpose(0, 2, 1),
      _by_head(dt, cum), drow, states)
    return (dx, _heads_last(dsc, 0), _heads_last(dsc, 1),
            jnp.sum(dbm, axis=1).astype(bm.dtype),
            jnp.sum(dcm, axis=1).astype(cm.dtype),
            jnp.sum(dd, axis=0).astype(drow.dtype))


@functools.partial(_jax.custom_vjp, nondiff_argnums=(6, 7))
def _core(x, dt, cum, bm, cm, drow, chunk, interpret):
    return _fwd_call(x, dt, cum, bm, cm, drow, chunk, interpret)


def _vjp_fwd(x, dt, cum, bm, cm, drow, chunk, interpret):
    # inputs only: see the module docstring
    return (_fwd_call(x, dt, cum, bm, cm, drow, chunk, interpret),
            (x, dt, cum, bm, cm, drow))


def _vjp_bwd(chunk, interpret, res, dy):
    return _bwd_call(*res, dy, chunk, interpret)


_core.defvjp(_vjp_fwd, _vjp_bwd)


def ssd_scan(x, dt, a, bm, cm, d, chunk, interpret):
    """``x [B, S, heads, P]``, ``dt [B, S, heads]`` float32 (after its
    softplus), ``a [heads]`` (negative), ``bm`` / ``cm [B, S, N]``, ``d
    [heads]`` -> ``y`` like ``x``; differentiable in all six."""
    import jax.numpy as jnp
    batch, seq, heads, p = x.shape
    dt = dt.astype(jnp.float32)
    steps = dt * a.astype(jnp.float32)
    cum = jnp.cumsum(steps.reshape(batch, seq // chunk, chunk, heads),
                     axis=2).reshape(batch, seq, heads)
    drow = jnp.repeat(d.astype(jnp.float32), p)[None]
    y = _core(x.reshape(batch, seq, heads * p), dt, cum, bm.astype(x.dtype),
              cm.astype(x.dtype), drow, chunk, interpret)
    return y.reshape(x.shape)
