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
``q k^T`` are made once a step for its value heads. ``q``, ``k`` and ``v``
stay ``[B, S, heads * 128]`` as the projection wrote them; a head is one
128-lane tile. A head's scalars (``G``, ``beta``) come in twice, ``[.., C,
rep]`` to be spread along lanes and ``[.., rep, C]`` along sublanes. The
decays, their running sums, the exps, ``T`` and the state are float32; the
products take operands in the inputs' dtype and accumulate in float32.

The forward kernel also writes the state entering each chunk (``[B, chunks,
heads, d_k, d_v]`` float32), which the backward reads: it walks the chunks in
reverse with the state's gradient in scratch, recomputes a chunk's ``T`` and
``V'`` from the same rounded operands as the forward, and needs no second
forward pass. The gradient of ``G`` comes out in two parts, one spread along
lanes and one along sublanes (the column sums of ``E = dM * M + dP * P``,
whose row sums cancel them pair by pair in the running sum that turns ``dG``
into ``dg``: both are sums of the one float32 array). The l2 norms, the query
scale and the running sums are plain ``jax.numpy`` around the kernels,
differentiated by JAX (``ops/decoder_ops.py``).
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

from .pallas_ssd import _nn, _nt, _params, _pl, _tn

HEAD_DIM = 128          # key and value head size: one 128-lane tile
CHUNKS = (64, 128)      # chunk lengths the kernels take


def supports(seq: int, key_heads: int, value_heads: int, key_dim: int,
             value_dim: int, chunk: int) -> bool:
    """Whether the kernels take these shapes (else the composed form)."""
    return (key_dim == HEAD_DIM and value_dim == HEAD_DIM
            and value_heads % key_heads == 0 and chunk in CHUNKS
            and seq % chunk == 0)


def _masks(c):
    """(i >= j, i > j, the identity) over a ``[c, c]`` block."""
    import jax
    import jax.numpy as jnp
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return rows >= cols, rows > cols, (rows == cols).astype(jnp.float32)


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


def _head(r, gc_ref, gr_ref, bc_ref, lower):
    """Value head r of the step: ``G`` along sublanes, ``beta``, the decay
    block ``D``, ``exp(G)``, ``exp(G_C)`` (along a tile's lanes: Mosaic
    spreads a ``[1, 1]`` value over one axis at a time) and ``exp(G_C -
    G)``."""
    import jax.numpy as jnp
    gc = gc_ref[0, 0][:, r:r + 1]                       # [C, 1]
    gr = gr_ref[0, 0, 0][r:r + 1, :]                    # [1, C]
    bc = bc_ref[0, 0][:, r:r + 1]
    d = jnp.exp(jnp.where(lower, gc - gr, -jnp.inf))
    end = gc[gc.shape[0] - 1:, :]
    return (bc, d, jnp.exp(gc),
            jnp.exp(jnp.broadcast_to(end, (1, HEAD_DIM))), jnp.exp(end - gc))


def _fwd_kernel(rep, q_ref, k_ref, v_ref, gc_ref, gr_ref, bc_ref,
                o_ref, st_ref, s_ref):
    import jax.numpy as jnp
    pl, _ = _pl()
    bf = q_ref.dtype            # the products' operand type

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    lower, strict, eye = _masks(q_ref.shape[1])
    qn, kn = q_ref[0], k_ref[0]
    knf = kn.astype(jnp.float32)
    kk, qk = _nt(kn, kn), _nt(qn, kn)                   # [C, C], shared
    for r in range(rep):
        sl = slice(HEAD_DIM * r, HEAD_DIM * (r + 1))
        bc, d, eg, e_end, f = _head(r, gc_ref, gr_ref, bc_ref, lower)
        t = _inverse(jnp.where(strict, kk * d, 0.0) * bc, eye, bf)
        s = s_ref[r]
        st_ref[0, 0, r] = s
        sb = s.astype(bf)
        z = v_ref[0, :, sl].astype(jnp.float32) - eg * _nn(kn, sb)
        vpb = _nn(t.astype(bf), (bc * z).astype(bf)).astype(bf)
        o = eg * _nn(qn, sb) + _nn((qk * d).astype(bf), vpb)
        o_ref[0, :, sl] = o.astype(o_ref.dtype)
        s_ref[r] = e_end * s + _tn((knf * f).astype(bf), vpb)


def _bwd_kernel(rep, q_ref, k_ref, v_ref, do_ref, gc_ref, gr_ref, bc_ref,
                st_ref, dq_ref, dk_ref, dv_ref, dgc_ref, dgr_ref, db_ref,
                ds_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf = q_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)     # the last chunk: nothing follows it
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    c = q_ref.shape[1]
    lower, strict, eye = _masks(c)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    head_c = jax.lax.broadcasted_iota(jnp.int32, (c, rep), 1)
    head_r = jax.lax.broadcasted_iota(jnp.int32, (rep, c), 0)
    qn, kn = q_ref[0], k_ref[0]
    knf = kn.astype(f32)
    kk, qk = _nt(kn, kn), _nt(qn, kn)

    def rows(x):
        return jnp.sum(x, axis=1, keepdims=True)

    dqn = jnp.zeros((c, HEAD_DIM), f32)
    dkn = jnp.zeros((c, HEAD_DIM), f32)
    dkk = jnp.zeros((c, c), f32)
    dqk = jnp.zeros((c, c), f32)
    dgc = jnp.zeros((c, rep), f32)
    dgr = jnp.zeros((rep, c), f32)
    dbeta = jnp.zeros((c, rep), f32)
    for r in range(rep):
        sl = slice(HEAD_DIM * r, HEAD_DIM * (r + 1))
        bc, d, eg, e_end, f = _head(r, gc_ref, gr_ref, bc_ref, lower)
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
        dgc = jnp.where(head_c == r, dg, dgc)
        dgr = jnp.where(head_r == r, -jnp.sum(e, axis=0, keepdims=True), dgr)
        dbeta = jnp.where(head_c == r, rows(dr * z) + rows(dm * md), dbeta)
    dqkb, dkkb = dqk.astype(bf), dkk.astype(bf)
    dq_ref[0] = (dqn + _nn(dqkb, kn)).astype(dq_ref.dtype)
    dk_ref[0] = (dkn + _tn(dqkb, qn) + _nn(dkkb, kn)
                 + _tn(dkkb, kn)).astype(dk_ref.dtype)
    dgc_ref[0, 0] = dgc
    dgr_ref[0, 0, 0] = dgr
    db_ref[0, 0] = dbeta


def _by_head(v, key_heads, chunk):
    """``[B, S, heads]`` -> ``[B, key heads, S, rep]`` (to spread along
    lanes) and ``[B, key heads, chunks, rep, C]`` (along sublanes)."""
    b, s, h = v.shape
    rep = h // key_heads
    return (v.reshape(b, s, key_heads, rep).transpose(0, 2, 1, 3),
            v.reshape(b, s // chunk, chunk, key_heads, rep)
            .transpose(0, 3, 1, 4, 2))


def _from_lanes(cols):
    """``_by_head``'s first layout back to ``[B, S, heads]``."""
    b, n_k, s, rep = cols.shape
    return cols.transpose(0, 2, 1, 3).reshape(b, s, n_k * rep)


def _from_sublanes(rows):
    """``_by_head``'s second layout back to ``[B, S, heads]``."""
    b, n_k, chunks, rep, c = rows.shape
    return rows.transpose(0, 2, 4, 1, 3).reshape(b, chunks * c, n_k * rep)


def _specs(c, rep, chunk_of):
    """Block specs of what both passes read or write, the chunk a grid step
    works on given by ``chunk_of(c)``."""
    pl, pltpu = _pl()

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)
    key = spec((1, c, HEAD_DIM), lambda b, j, i: (b, chunk_of(i), j))
    value = spec((1, c, rep * HEAD_DIM), lambda b, j, i: (b, chunk_of(i), j))
    lanes = spec((1, 1, c, rep), lambda b, j, i: (b, j, chunk_of(i), 0))
    subl = spec((1, 1, 1, rep, c), lambda b, j, i: (b, j, chunk_of(i), 0, 0))
    state = spec((1, 1, rep, HEAD_DIM, HEAD_DIM),
                 lambda b, j, i: (b, chunk_of(i), j, 0, 0))
    return key, value, lanes, subl, state


# behind a jit of its own, like the flash kernels: the layers of a model
# share one trace and one lowering
@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _fwd_call(qn, kn, v, gcum, beta, chunk, interpret):
    """``qn`` / ``kn [B, S, key heads * 128]`` (normalised, q scaled), ``v
    [B, S, value heads * 128]``, ``gcum`` / ``beta [B, S, value heads]``
    float32 -> ``o`` like ``v`` and the state entering each chunk ``[B,
    chunks, value heads, 128, 128]`` float32."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, wide = v.shape
    n_k, n_v, chunks = qn.shape[2] // HEAD_DIM, wide // HEAD_DIM, seq // chunk
    rep = n_v // n_k
    key, value, lanes, subl, state = _specs(chunk, rep, lambda i: i)
    gc, gr = _by_head(gcum, n_k, chunk)
    bc, _ = _by_head(beta, n_k, chunk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rep), grid=(batch, n_k, chunks),
        in_specs=[key, key, value, lanes, subl, lanes],
        out_specs=[value, state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, chunks, n_v, HEAD_DIM, HEAD_DIM), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep, HEAD_DIM, HEAD_DIM), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(qn, kn, v, gc, gr, bc)


@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(qn, kn, v, gcum, beta, states, do, chunk, interpret):
    """The gradients of ``_fwd_call``'s first five arguments, given the
    states it wrote and ``do``."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, wide = v.shape
    n_k, n_v, chunks = qn.shape[2] // HEAD_DIM, wide // HEAD_DIM, seq // chunk
    rep = n_v // n_k
    key, value, lanes, subl, state = _specs(
        chunk, rep, lambda i: chunks - 1 - i)
    gc, gr = _by_head(gcum, n_k, chunk)
    bc, _ = _by_head(beta, n_k, chunk)
    f32 = jnp.float32
    by_lanes = jax.ShapeDtypeStruct(gc.shape, f32)
    dqn, dkn, dv, dgc, dgr, db = pl.pallas_call(
        functools.partial(_bwd_kernel, rep), grid=(batch, n_k, chunks),
        in_specs=[key, key, value, value, lanes, subl, lanes, state],
        out_specs=[key, key, value, lanes, subl, lanes],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), by_lanes,
                   jax.ShapeDtypeStruct(gr.shape, f32), by_lanes],
        scratch_shapes=[pltpu.VMEM((rep, HEAD_DIM, HEAD_DIM), f32)],
        interpret=interpret, **_params(interpret),
    )(qn, kn, v, do, gc, gr, bc, states)
    return (dqn, dkn, dv, _from_lanes(dgc) + _from_sublanes(dgr),
            _from_lanes(db))


@functools.partial(_jax.custom_vjp, nondiff_argnums=(5, 6))
def chunked(qn, kn, v, gcum, beta, chunk, interpret):
    """``_fwd_call``, differentiable in its five arrays (no gradient flows
    through the states it returns beside ``o``)."""
    return _fwd_call(qn, kn, v, gcum, beta, chunk, interpret)


def _vjp_fwd(qn, kn, v, gcum, beta, chunk, interpret):
    o, states = _fwd_call(qn, kn, v, gcum, beta, chunk, interpret)
    return (o, states), (qn, kn, v, gcum, beta, states)


def _vjp_bwd(chunk, interpret, res, cotangents):
    return _bwd_call(*res, cotangents[0], chunk, interpret)


chunked.defvjp(_vjp_fwd, _vjp_bwd)
