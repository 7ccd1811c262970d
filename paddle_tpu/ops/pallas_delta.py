"""The chunked gated delta rule of a Gated DeltaNet layer (``ops/decoder_ops.
py:gated_delta_rule``; Yang et al., arXiv:2412.06464, as HF's
``torch_chunk_gated_delta_rule`` computes it) as Pallas TPU kernels, forward
and backward.

Per value head, with state ``S [d_k, d_v]``, unit keys ``k_t``, scaled unit
queries ``q_t``, a decay ``g_t <= 0`` and a step ``beta_t``:
``S' = exp(g_t) S_{t-1}``; ``u_t = beta_t (v_t - S'^T k_t)``; ``S_t = S' + k_t
u_t^T``; ``o_t = S_t^T q_t``. The update reads the state it writes, so a chunk
of C positions needs the inverse of a unit lower-triangular ``[C, C]`` matrix
before any product. With ``G`` the running sum of ``g`` inside the chunk and
``D[i, j] = exp(G_i - G_j)`` for i >= j (0 above):

    M  = strict_tril(beta_i (k k^T) * D)        T = (I + M)^-1
    V' = T (beta * (v - exp(G) * (k S)))        (the chunk's u_t, all at once)
    O  = exp(G) * (q S) + ((q k^T) * D) V'
    S <- exp(G_C) S + (k * exp(G_C - G))^T V'

``T`` is the product ``(I - M)(I + M^2)(I + M^4)...``: M is nilpotent (``M^C
= 0``), so ``log2 C`` factors are the whole series and each costs two ``[C,
C]`` products. The composed form holds ``T`` and ``D`` as ``[batch, chunks,
heads, C, C]`` float32 arrays in HBM; here they live in VMEM, a key head and
a chunk at a time.

A grid step is (batch, key head, chunk), the chunks in order and the state
of the key head's ``rep`` value heads (value head j reads key head ``j //
rep``) carried in VMEM scratch as ``[rep, d_k, d_v]`` float32: ``k k^T`` and
``q k^T`` are made once a step for its value heads.

The kernels read what the projection and the short convolution wrote, as
they wrote it; everything between that and the products happens in VMEM.
``q``, ``k`` and ``v`` come either as three arrays ``[B, S, heads * 128]``
or as the one packed array ``[B, S, (2 key heads + value heads) * 128]`` (q |
k | v along the columns), which is then handed to the call three times with
three index maps: a head is one 128-lane tile, so key head j of q is lane
block ``j``, of k ``key heads + j``, and its value heads one block of ``rep *
128`` lanes behind both (``packs`` says when that offset is whole blocks).
No copy of v is cut out. q and k are raw: a step forms ``x * rsqrt(sum(x^2)
+ 1e-6)`` (q also over ``sqrt(d_k)``: ``unit``) in float32 and rounds it to
the inputs' dtype, which is what the products read; no unit q or k exists in
HBM. A head's scalars (``G``, ``beta``) come in once, ``[.., rep, C]`` along
the lanes; the ``[C, 1]`` columns the decay block and the row scalings want
are made from the rows through the identity's mask (a masked ``[C, C]`` sum,
exact). The decays, their running sums, the exps, ``T`` and the state are
float32; the products take operands in the inputs' dtype and accumulate in
float32.

The forward kernel also writes the state entering each chunk (``[B, chunks,
heads, d_k, d_v]`` float32), which the backward reads: it walks the chunks in
reverse with the state's gradient in scratch, recomputes a chunk's ``T`` and
``V'`` from the same rounded operands as the forward, and needs no second
forward pass. The gradient of ``G`` is a head's column of row terms less the
column sums of ``E = dM * M + dP * P`` (whose row sums cancel them pair by
pair in the running sum that turns ``dG`` into ``dg``: both are sums of the
one float32 array), summed in VMEM and written with ``dbeta`` in the
scalars' own ``[.., rep, C]`` layout. The gradients of the unit q and k
never leave VMEM either: the norm's own vjp (``jax.vjp`` of ``unit``, traced
into the kernel) is applied to them while they are float32, and dq, dk, dv
are written once (three outputs; the packed form's one gradient is their
concatenation). Only the running sums of ``g`` (1 MB) are plain ``jax.numpy``
around the kernels, differentiated by JAX (``ops/decoder_ops.py``).
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

from .pallas_ssd import _nn, _nt, _params, _pl, _tn

HEAD_DIM = 128          # key and value head size: one 128-lane tile
CHUNKS = (64, 128)      # chunk lengths the kernels take
QUERY_SCALE = HEAD_DIM ** -0.5


def supports(seq: int, key_heads: int, value_heads: int, key_dim: int,
             value_dim: int, chunk: int) -> bool:
    """Whether the kernels take these shapes (else the composed form)."""
    return (key_dim == HEAD_DIM and value_dim == HEAD_DIM
            and value_heads % key_heads == 0 and chunk in CHUNKS
            and seq % chunk == 0)


def packs(key_heads: int, value_heads: int) -> bool:
    """Whether the kernels read a packed ``q | k | v`` array in place: v
    starts ``2 * key heads`` tiles in, which must be a whole number of the
    ``rep``-tile blocks a key head's value heads are read by."""
    return (2 * key_heads) % (value_heads // key_heads) == 0


def unit(x, scale=None):
    """float32 ``x [.., d]`` over its l2 norm, ``x * rsqrt(sum(x^2) + 1e-6)``,
    then times ``scale``: what the delta rule's products read of q and k once
    rounded. One expression for the kernels (traced into their bodies, with
    its ``jax.vjp`` in the backward's) and for the composed form's operands
    (``ops/decoder_ops.py:_delta_operands``), so both round the same bits."""
    import jax
    import jax.numpy as jnp
    y = x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    return y if scale is None else y * scale


def _masks(c):
    """(i >= j, i > j, i == j) over a ``[c, c]`` block."""
    import jax
    import jax.numpy as jnp
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return rows >= cols, rows > cols, rows == cols


def _inverse(m, eye, bf):
    """``(I + m)^-1`` of a strictly lower-triangular ``m [c, c]``: ``(I -
    m)(I + m^2)(I + m^4)...``, ``log2 c`` factors."""
    x = -m
    t = eye + x
    for _ in range(m.shape[0].bit_length() - 2):
        xb = x.astype(bf)
        x = _nn(xb, xb)
        t = t + _nn(t.astype(bf), x.astype(bf))
    return t


def _column(row, diag):
    """``[1, C]`` along the lanes -> ``[C, 1]`` along the sublanes: the
    diagonal of the row spread over a ``[C, C]`` block, summed (exact)."""
    import jax.numpy as jnp
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


def _row(column, diag):
    """``_column``'s inverse."""
    import jax.numpy as jnp
    return jnp.sum(jnp.where(diag, column, 0.0), axis=0, keepdims=True)


def _head(r, g_ref, b_ref, lower, diag):
    """Value head r of the step: ``beta`` as a column, the decay block
    ``D``, ``exp(G)``, ``exp(G_C)`` (along a tile's lanes: Mosaic spreads a
    ``[1, 1]`` value over one axis at a time) and ``exp(G_C - G)``."""
    import jax.numpy as jnp
    gr = g_ref[0, 0, 0][r:r + 1, :]                     # [1, C]
    gc = _column(gr, diag)                              # [C, 1]
    bc = _column(b_ref[0, 0, 0][r:r + 1, :], diag)
    d = jnp.exp(jnp.where(lower, gc - gr, -jnp.inf))
    end = gc[gc.shape[0] - 1:, :]
    return (bc, d, jnp.exp(gc),
            jnp.exp(jnp.broadcast_to(end, (1, HEAD_DIM))), jnp.exp(end - gc))


def _fwd_kernel(rep, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, s_ref):
    import jax.numpy as jnp
    pl, _ = _pl()
    bf = q_ref.dtype            # the products' operand type
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    lower, strict, diag = _masks(q_ref.shape[1])
    eye = diag.astype(f32)
    qn = unit(q_ref[0].astype(f32), QUERY_SCALE).astype(bf)
    kn = unit(k_ref[0].astype(f32)).astype(bf)
    knf = kn.astype(f32)
    kk, qk = _nt(kn, kn), _nt(qn, kn)                   # [C, C], shared
    for r in range(rep):
        sl = slice(HEAD_DIM * r, HEAD_DIM * (r + 1))
        bc, d, eg, e_end, f = _head(r, g_ref, b_ref, lower, diag)
        t = _inverse(jnp.where(strict, kk * d, 0.0) * bc, eye, bf)
        s = s_ref[r]
        st_ref[0, 0, r] = s
        sb = s.astype(bf)
        z = v_ref[0, :, sl].astype(f32) - eg * _nn(kn, sb)
        vpb = _nn(t.astype(bf), (bc * z).astype(bf)).astype(bf)
        o = eg * _nn(qn, sb) + _nn((qk * d).astype(bf), vpb)
        o_ref[0, :, sl] = o.astype(o_ref.dtype)
        s_ref[r] = e_end * s + _tn((knf * f).astype(bf), vpb)


def _bwd_kernel(rep, q_ref, k_ref, v_ref, do_ref, g_ref, b_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf = q_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)     # the last chunk: nothing follows it
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    c = q_ref.shape[1]
    lower, strict, diag = _masks(c)
    eye = diag.astype(f32)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    head = jax.lax.broadcasted_iota(jnp.int32, (rep, c), 0)
    # the unit q and k the forward read, and the way back through the norms
    qu, q_back = jax.vjp(lambda x: unit(x, QUERY_SCALE), q_ref[0].astype(f32))
    ku, k_back = jax.vjp(unit, k_ref[0].astype(f32))
    qn, kn = qu.astype(bf), ku.astype(bf)
    knf = kn.astype(f32)
    kk, qk = _nt(kn, kn), _nt(qn, kn)

    def rows(x):
        return jnp.sum(x, axis=1, keepdims=True)

    dqn = jnp.zeros((c, HEAD_DIM), f32)
    dkn = jnp.zeros((c, HEAD_DIM), f32)
    dkk = jnp.zeros((c, c), f32)
    dqk = jnp.zeros((c, c), f32)
    dgs = jnp.zeros((rep, c), f32)
    dbs = jnp.zeros((rep, c), f32)
    for r in range(rep):
        sl = slice(HEAD_DIM * r, HEAD_DIM * (r + 1))
        bc, d, eg, e_end, f = _head(r, g_ref, b_ref, lower, diag)
        md = jnp.where(strict, kk * d, 0.0)
        m = md * bc
        tb = _inverse(m, eye, bf).astype(bf)
        s, dsn = st_ref[0, 0, r], ds_ref[r]
        sb, dsnb = s.astype(bf), dsn.astype(bf)
        # the forward again, from the same rounded operands
        ks = _nn(kn, sb)
        z = v_ref[0, :, sl].astype(f32) - eg * ks
        vpb = _nn(tb, (bc * z).astype(bf)).astype(bf)
        qs = _nn(qn, sb)
        p = qk * d
        dof = do_ref[0, :, sl].astype(f32)
        dob = dof.astype(bf)
        # O = exp(G) (q S) + P V';  S_next = exp(G_C) S + (k f)^T V'
        dvp = _tn(p.astype(bf), dob) + _nn((knf * f).astype(bf), dsnb)
        dp = jnp.where(lower, _nt(dob, vpb), 0.0)
        dqs = eg * dof
        dqsb = dqs.astype(bf)
        dqn += _nt(dqsb, sb)
        ds = e_end * dsn + _tn(qn, dqsb)
        dkf = _nt(vpb, dsnb)
        dkn += dkf * f
        moved = rows(dkf * knf) * f                     # dF_i F_i
        at_last = jnp.sum(moved, axis=0, keepdims=True) + jnp.sum(
            rows(e_end * dsn * s), axis=0, keepdims=True)
        # V' = T R, R = beta (v - exp(G) (k S));  dM = -dR V'^T
        dr = _tn(tb, dvp.astype(bf))
        dm = jnp.where(strict, -_nt(dr.astype(bf), vpb), 0.0)
        dz = bc * dr
        dv_ref[0, :, sl] = dz.astype(dv_ref.dtype)
        dks = -eg * dz
        dksb = dks.astype(bf)
        dkn += _nt(dksb, sb)
        ds_ref[r] = ds + _tn(kn, dksb)
        dkk += dm * bc * d
        dqk += dp * d
        # D[i, j] = exp(G_i - G_j): E's row sums at i, its column sums at j
        e = dm * m + dp * p
        dg = (rows(dqs * qs) - moved + rows(dks * ks) + rows(e)
              + jnp.where(at_end, at_last, 0.0))
        dgs = jnp.where(head == r, _row(dg, diag)
                        - jnp.sum(e, axis=0, keepdims=True), dgs)
        dbs = jnp.where(head == r, _row(rows(dr * z) + rows(dm * md), diag),
                        dbs)
    dqkb, dkkb = dqk.astype(bf), dkk.astype(bf)
    dq_ref[0] = q_back(dqn + _nn(dqkb, kn))[0].astype(dq_ref.dtype)
    dk_ref[0] = k_back(dkn + _tn(dqkb, qn) + _nn(dkkb, kn)
                       + _tn(dkkb, kn))[0].astype(dk_ref.dtype)
    dg_ref[0, 0, 0] = dgs
    db_ref[0, 0, 0] = dbs


def _by_head(v, key_heads, chunk):
    """``[B, S, heads]`` -> ``[B, key heads, chunks, rep, C]``: a grid
    step's scalars, a value head a row."""
    b, s, h = v.shape
    return (v.reshape(b, s // chunk, chunk, key_heads, h // key_heads)
            .transpose(0, 3, 1, 4, 2))


def _from_heads(rows):
    """``_by_head``'s layout back to ``[B, S, heads]``."""
    b, n_k, chunks, rep, c = rows.shape
    return rows.transpose(0, 2, 4, 1, 3).reshape(b, chunks * c, n_k * rep)


def _laid_out(qkv, value_heads):
    """(the q, k and v operands, the lane block each one's first head is at
    -- q's and k's in tiles of 128, v's in a key head's ``rep`` tiles --,
    the key heads) of ``(q, k, v)`` or of one packed ``q | k | v`` array."""
    if isinstance(qkv, (tuple, list)):
        q, k, v = qkv
        return (q, k, v), (0, 0, 0), q.shape[2] // HEAD_DIM
    key_heads = (qkv.shape[2] // HEAD_DIM - value_heads) // 2
    return (qkv, qkv, qkv), (
        0, key_heads, 2 * key_heads // (value_heads // key_heads)), key_heads


def _specs(c, rep, chunk_of):
    """Block specs of what both passes read or write, the chunk a grid step
    works on given by ``chunk_of(i)``: a key head's tile and its value
    heads' ``rep`` tiles, each from the lane block ``first`` its array's
    first head is at (``_laid_out``), a head's scalars, its states."""
    pl, pltpu = _pl()

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def key(first=0):
        return spec((1, c, HEAD_DIM),
                    lambda b, j, i: (b, chunk_of(i), first + j))

    def value(first=0):
        return spec((1, c, rep * HEAD_DIM),
                    lambda b, j, i: (b, chunk_of(i), first + j))
    scalars = spec((1, 1, 1, rep, c),
                   lambda b, j, i: (b, j, chunk_of(i), 0, 0))
    state = spec((1, 1, rep, HEAD_DIM, HEAD_DIM),
                 lambda b, j, i: (b, chunk_of(i), j, 0, 0))
    return key, value, scalars, state


# behind a jit of its own, like the flash kernels: the layers of a model
# share one trace and one lowering
@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _fwd_call(qkv, gcum, beta, chunk, interpret):
    """``qkv``: raw ``(q, k [B, S, key heads * 128], v [B, S, value heads *
    128])`` or one packed ``[B, S, (2 key heads + value heads) * 128]``;
    ``gcum`` / ``beta [B, S, value heads]`` float32 -> ``o [B, S, value heads
    * 128]`` and the state entering each chunk ``[B, chunks, value heads,
    128, 128]`` float32."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, n_v = gcum.shape
    (q, k, v), at, n_k = _laid_out(qkv, n_v)
    rep, chunks = n_v // n_k, seq // chunk
    key, value, scalars, state = _specs(chunk, rep, lambda i: i)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rep), grid=(batch, n_k, chunks),
        in_specs=[key(at[0]), key(at[1]), value(at[2]), scalars, scalars],
        out_specs=[value(), state],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, n_v * HEAD_DIM), v.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, chunks, n_v, HEAD_DIM, HEAD_DIM), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep, HEAD_DIM, HEAD_DIM), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(q, k, v, _by_head(gcum, n_k, chunk), _by_head(beta, n_k, chunk))


@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(qkv, gcum, beta, states, do, chunk, interpret):
    """The gradients of ``_fwd_call``'s three arguments (``qkv``'s in its
    own form: three arrays, or their concatenation for the packed one),
    given the states it wrote and ``do``."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, n_v = gcum.shape
    (q, k, v), at, n_k = _laid_out(qkv, n_v)
    rep, chunks = n_v // n_k, seq // chunk
    key, value, scalars, state = _specs(
        chunk, rep, lambda i: chunks - 1 - i)
    gr = _by_head(gcum, n_k, chunk)
    f32 = jnp.float32
    keys = jax.ShapeDtypeStruct((batch, seq, n_k * HEAD_DIM), v.dtype)
    by_head = jax.ShapeDtypeStruct(gr.shape, f32)
    dq, dk, dv, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, rep), grid=(batch, n_k, chunks),
        in_specs=[key(at[0]), key(at[1]), value(at[2]), value(), scalars,
                  scalars, state],
        out_specs=[key(), key(), value(), scalars, scalars],
        out_shape=[keys, keys, jax.ShapeDtypeStruct(do.shape, v.dtype),
                   by_head, by_head],
        scratch_shapes=[pltpu.VMEM((rep, HEAD_DIM, HEAD_DIM), f32)],
        interpret=interpret, **_params(interpret),
    )(q, k, v, do, gr, _by_head(beta, n_k, chunk), states)
    dqkv = (dq, dk, dv) if isinstance(qkv, (tuple, list)) else \
        jnp.concatenate([dq, dk, dv], axis=-1)
    return dqkv, _from_heads(dg), _from_heads(db)


@functools.partial(_jax.custom_vjp, nondiff_argnums=(3, 4))
def chunked(qkv, gcum, beta, chunk, interpret):
    """``_fwd_call``, differentiable in its three arguments (no gradient
    flows through the states it returns beside ``o``)."""
    return _fwd_call(qkv, gcum, beta, chunk, interpret)


def _vjp_fwd(qkv, gcum, beta, chunk, interpret):
    o, states = _fwd_call(qkv, gcum, beta, chunk, interpret)
    return (o, states), (qkv, gcum, beta, states)


def _vjp_bwd(chunk, interpret, res, cotangents):
    return _bwd_call(*res, cotangents[0], chunk, interpret)


chunked.defvjp(_vjp_fwd, _vjp_bwd)
