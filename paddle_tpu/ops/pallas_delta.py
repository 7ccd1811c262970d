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

**A decay a key channel** (Kimi Delta Attention; Kimi Linear, arXiv:
2510.26692): ``g_t`` is a vector over the ``d_k`` key channels, ``S' =
diag(exp(g_t)) S_{t-1}`` scales the state's rows, and ``G [C, d_k]`` puts the
decay inside the contraction over the channels:

    M[i, j] = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])      (i > j)
    P[i, j] =        sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])      (i >= j)
    V' = T (beta * (v - (k * exp(G)) S))     O = (q * exp(G)) S + P V'
    S <- diag(exp(G_C)) S + (k * exp(G_C - G))^T V'

``(k * exp(G)) (k * exp(-G))^T`` would overflow float32 (``exp(-G)`` passes
3e38 once a channel has fallen by 88 inside a chunk), so no exponent is ever
taken of a positive number: the chunk is cut into sub-blocks of ``SUB``
positions; a sub-block's rows against the sub-blocks before it are one
product of ``x * exp(G - G_ref)`` with ``k * exp(G_ref - G)``, ``G_ref`` the
sub-block's first row (both exponents <= 0; a factor that underflows has a
product that underflows); a sub-block against itself is formed pair by
pair, ``SUB`` passes of ``exp(min(G_i - G_j, 0))`` over the ``[C, d_k]``
tile, each giving one column of every diagonal block. ``T`` comes block by
block, 8-row blocks merged pair by pair (``_inverse_blocked`` says why not
by the doubling over the whole chunk). These are ``_intra``
and its transpose ``_intra_bwd``: plain functions of a chunk's values, which
the kernels' bodies trace and the composed form maps over batch, head and
chunk (``channel_chunk``). The kernels (``_fwd_kernel_channel``,
``_bwd_kernel_channel``) take one value head a key head, share the block
specs, the packed operand and the states' layout with the scalar pair, and
read ``G`` and write ``dG`` as ``[B, S, heads * 128]`` float32, a head a
lane tile. In the backward every product that holds a decay is
differentiated as the rounded operand the forward's product read (``dG +=
L * dL`` for ``L = (x * exp(..)).astype(bf)``), so what cancels pair by pair
in the running sum that turns ``dG`` into ``dg`` is a sum of the same
products on both sides.
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

from .pallas_ssd import _nn, _nt, _params, _pl, _tn

HEAD_DIM = 128          # key and value head size: one 128-lane tile
CHUNKS = (64, 128)      # chunk lengths the kernels take
SUB = 16                # sub-block of a chunk under a channel decay: one
#                         packed bfloat16 vreg of rows
QUERY_SCALE = HEAD_DIM ** -0.5


def supports(seq: int, key_heads: int, value_heads: int, key_dim: int,
             value_dim: int, chunk: int, channel: bool = False) -> bool:
    """Whether the kernels take these shapes (else the composed form);
    ``channel``: a decay a key channel, whose kernels take one value head a
    key head."""
    return (key_dim == HEAD_DIM and value_dim == HEAD_DIM
            and value_heads % key_heads == 0 and chunk in CHUNKS
            and seq % chunk == 0
            and not (channel and value_heads != key_heads))


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


# -- a decay a key channel ---------------------------------------------------

def _pick(x, jj, sub):
    """Each sub-block's row ``jj`` of ``x [C, d]`` over that sub-block's
    rows."""
    import jax.numpy as jnp
    return jnp.concatenate([
        jnp.broadcast_to(x[b + jj:b + jj + 1], (sub, x.shape[1]))
        for b in range(0, x.shape[0], sub)], axis=0)


def _block_sums(x, jj, sub):
    """Each sub-block's sum over its rows of ``x [C, d]``, at the
    sub-block's row ``jj``; zero elsewhere."""
    import jax
    import jax.numpy as jnp
    at = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0) == jj
    return jnp.concatenate([
        jnp.where(at, jnp.sum(x[b:b + sub], axis=0, keepdims=True), 0.0)
        for b in range(0, x.shape[0], sub)], axis=0)


def _blocks_of(c, sub):
    """Over ``[c, c]``: (row i's sub-block lies after column j's, the column
    a row's own sub-block starts at)."""
    import jax
    import jax.numpy as jnp
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return rows // sub > cols // sub, cols - rows // sub * sub


def _strip(kn, g, a, sub, bf):
    """Sub-block ``a``'s rows against every earlier position: (its rows, the
    decay of its rows from its first, the growth of every position's key
    back to that row -- ``min``: the positions from there on are masked out
    of the product --, ``kn`` times that growth as the product's operand)."""
    import jax.numpy as jnp
    at = slice(a * sub, (a + 1) * sub)
    ref = g[a * sub:a * sub + 1]
    near, far = jnp.exp(g[at] - ref), jnp.exp(jnp.minimum(ref - g, 0.0))
    return at, near, far, (kn * far).astype(bf)


INVERSE_BLOCK = 8       # rows of the blocks the blocked inverse starts from


def _inverse_blocked(m, eye, bf):
    """``(I + m)^-1`` of a strictly lower-triangular ``m [c, c]``, block by
    block: the diagonal blocks of ``INVERSE_BLOCK`` rows by ``_inverse``'s
    doubling (``(I - d)(I + d^2)(I + d^4)``, all blocks in one product: ``d``
    is ``m`` masked to them), then pairs of neighbouring blocks merged,
    ``log2(c / INVERSE_BLOCK)`` times: with ``T`` the inverse of the
    block-diagonal part so far and ``e`` the part of ``m`` that joins each
    pair's lower block to its upper one, the pairs' inverse is ``T - T e T``
    (``[[A, 0], [E, B]]^-1 = [[A^-1, 0], [-B^-1 E A^-1, B^-1]]``), every pair
    in the same two ``[c, c]`` products.

    ``_inverse``'s doubling over the whole chunk forms ``m^2, m^4, ..,
    m^(c/2)``, whose entries grow with the number of paths between two
    positions (``C(c, c/2)`` of them) before nilpotency ends them. With keys
    that resemble their neighbours' (a layer that reads a gated norm's
    output) under a decay that some channels hardly apply, ``m`` holds 0.5
    to 0.9 over long ranges, and the series loses every digit at c = 128
    (chip, PR 51: not-a-number from the second KDA layer on; chunks of 64
    read 2e-3 off in the fourth). Here no power beyond an 8-row block's
    fourth is formed (entries up to ``C(7, 3) 0.9^4 = 23``, typically about
    1), and every ``T`` on the way is an inverse whose entries are tame."""
    import jax
    import jax.numpy as jnp
    c = m.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    size = min(INVERSE_BLOCK, c)
    x = jnp.where(rows // size == cols // size, -m, 0.0)
    t = eye + x
    for _ in range(size.bit_length() - 2):
        xb = x.astype(bf)
        x = _nn(xb, xb)
        t = t + _nn(t.astype(bf), x.astype(bf))
    while size < c:
        # the lower block of each pair against the pair's upper block
        joins = (rows // (2 * size) == cols // (2 * size)) & (
            rows // size > cols // size)
        tb = t.astype(bf)
        t = t - _nn(tb, _nn(jnp.where(joins, m, 0.0).astype(bf), tb)
                    .astype(bf))
        size *= 2
    return t


def _intra(qn, kn, g, sub, bf):
    """``sum_c x_i[c] kn_j[c] exp(G_i[c] - G_j[c])`` over a chunk for ``x``
    = ``kn`` and ``qn`` (float32 values ``[C, d]``; ``g [C, d]`` the running
    sums): two ``[C, C]`` float32 blocks, right at and under the diagonal
    (above it the diagonal sub-blocks hold clamped values: the caller
    masks)."""
    import jax.numpy as jnp
    c = g.shape[0]
    kk, qk = ([jnp.zeros((sub, c), jnp.float32)] for _ in range(2))
    for a in range(1, c // sub):
        at, near, _, right = _strip(kn, g, a, sub, bf)
        kk.append(_nt((kn[at] * near).astype(bf), right))
        qk.append(_nt((qn[at] * near).astype(bf), right))
    before, offset = _blocks_of(c, sub)
    kk = jnp.where(before, jnp.concatenate(kk, axis=0), 0.0)
    qk = jnp.where(before, jnp.concatenate(qk, axis=0), 0.0)
    for jj in range(sub):       # a sub-block against itself, a column a pass
        w = _pick(kn, jj, sub) * jnp.exp(
            jnp.minimum(g - _pick(g, jj, sub), 0.0))
        here = offset == jj
        kk = jnp.where(here, jnp.sum(kn * w, axis=1, keepdims=True), kk)
        qk = jnp.where(here, jnp.sum(qn * w, axis=1, keepdims=True), qk)
    return kk, qk


def _intra_bwd(qn, kn, g, dkk, dqk, sub, bf):
    """``_intra``'s transpose: (dqn, dkn, dG) from the cotangents of its two
    blocks (masked by the caller: strictly under the diagonal, at and under
    it). A product's operand that holds a decay is differentiated as the
    rounded array the product read."""
    import jax.numpy as jnp
    f32 = jnp.float32
    c, d = g.shape
    before, offset = _blocks_of(c, sub)
    dkk_far = jnp.where(before, dkk, 0.0).astype(bf)
    dqk_far = jnp.where(before, dqk, 0.0).astype(bf)
    dq, dk, dg = ([jnp.zeros((sub, d), f32)] for _ in range(3))
    dk_right, dg_right = jnp.zeros((c, d), f32), jnp.zeros((c, d), f32)
    for a in range(1, c // sub):
        at, near, far, right = _strip(kn, g, a, sub, bf)
        kl, ql = (kn[at] * near).astype(bf), (qn[at] * near).astype(bf)
        dkl, dql = _nn(dkk_far[at], right), _nn(dqk_far[at], right)
        dright = _tn(dkk_far[at], kl) + _tn(dqk_far[at], ql)
        dk.append(dkl * near)
        dq.append(dql * near)
        dg.append(kl.astype(f32) * dkl + ql.astype(f32) * dql)
        dk_right += dright * far
        dg_right += right.astype(f32) * dright
    dq = jnp.concatenate(dq, axis=0)
    dk = jnp.concatenate(dk, axis=0) + dk_right
    dg = jnp.concatenate(dg, axis=0) - dg_right
    for jj in range(sub):
        e = jnp.exp(jnp.minimum(g - _pick(g, jj, sub), 0.0))
        w = _pick(kn, jj, sub) * e
        here = offset == jj
        ckk = jnp.sum(jnp.where(here, dkk, 0.0), axis=1, keepdims=True)
        cqk = jnp.sum(jnp.where(here, dqk, 0.0), axis=1, keepdims=True)
        u = ckk * kn + cqk * qn
        pair = u * w                    # at row i; its sub-block's sum at j
        dq += cqk * w
        dk += ckk * w + _block_sums(u * e, jj, sub)
        dg += pair - _block_sums(pair, jj, sub)
    return dq, dk, dg


def _channel_forward(qn, kn, v, g, bc, s, sub):
    """One chunk of one head under a channel decay: ``qn`` / ``kn [C, d_k]``
    the unit operands in the products' dtype, ``v [C, d_v]``, ``g [C, d_k]``
    float32 running sums, ``bc [C, 1]`` beta, ``s [d_k, d_v]`` float32 the
    state entering. Returns ``o`` (float32), the state leaving, and what the
    backward reads again."""
    import jax.numpy as jnp
    bf, f32 = qn.dtype, jnp.float32
    c = g.shape[0]
    lower, strict, diag = _masks(c)
    qf, kf = qn.astype(f32), kn.astype(f32)
    kk, qk = _intra(qf, kf, g, sub, bf)
    md = jnp.where(strict, kk, 0.0)
    p = jnp.where(lower, qk, 0.0)
    tb = _inverse_blocked(md * bc, diag.astype(f32), bf).astype(bf)
    eg, end = jnp.exp(g), g[c - 1:]
    fade = jnp.exp(end - g)
    kg, qg = (kf * eg).astype(bf), (qf * eg).astype(bf)
    kend = (kf * fade).astype(bf)
    e_end = jnp.exp(_column(end, _masks(g.shape[1])[2]))    # [d_k, 1]
    sb = s.astype(bf)
    z = v.astype(f32) - _nn(kg, sb)
    vpb = _nn(tb, (bc * z).astype(bf)).astype(bf)
    o = _nn(qg, sb) + _nn(p.astype(bf), vpb)
    s_next = e_end * s + _tn(kend, vpb)
    return o, s_next, (qf, kf, md, p, tb, eg, fade, kg, qg, kend, e_end, sb,
                       z, vpb)


def _channel_backward(qn, kn, v, g, bc, s, dsn, do, sub):
    """The chunk's gradients given the state's gradient ``dsn`` leaving it
    and ``do [C, d_v]``: (dqn, dkn, dv, dG, dbeta ``[C, 1]``, the state's
    gradient entering), float32; dqn / dkn are the unit operands'."""
    import jax
    import jax.numpy as jnp
    bf = qn.dtype
    c = g.shape[0]
    lower, strict, _ = _masks(c)
    (qf, kf, md, p, tb, eg, fade, kg, qg, kend, e_end, sb, z,
     vpb) = _channel_forward(qn, kn, v, g, bc, s, sub)[2]

    def rows(x):
        return jnp.sum(x, axis=1, keepdims=True)

    dob, dsnb = do.astype(bf), dsn.astype(bf)
    # O = (q exp(G)) S + P V';  S_next = diag(exp(G_C)) S + (k fade)^T V'
    dvp = _tn(p.astype(bf), dob) + _nn(kend, dsnb)
    dp = jnp.where(lower, _nt(dob, vpb), 0.0)
    dqg = _nt(dob, sb)
    dkend = _nt(vpb, dsnb)
    # V' = T R, R = beta (v - (k exp(G)) S);  dM = -dR V'^T
    dr = _tn(tb, dvp.astype(bf))
    dm = jnp.where(strict, -_nt(dr.astype(bf), vpb), 0.0)
    dz = bc * dr
    dzb = dz.astype(bf)
    dkg = -_nt(dzb, sb)
    ds = e_end * dsn + _tn(qg, dob) - _tn(kg, dzb)
    dq, dk, dg = _intra_bwd(qf, kf, g, dm * bc, dp, sub, bf)
    f32 = jnp.float32
    moved = kend.astype(f32) * dkend            # leaves row i for row C
    at_last = jnp.sum(moved, axis=0, keepdims=True) + _row(
        e_end * rows(dsn * s), _masks(g.shape[1])[2])
    at_end = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dg = (dg + qg.astype(f32) * dqg + kg.astype(f32) * dkg - moved
          + jnp.where(at_end, at_last, 0.0))
    return (dq + dqg * eg, dk + dkg * eg + dkend * fade, dz, dg,
            rows(dr * z) + rows(dm * md), ds)


def _fwd_kernel_channel(sub, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref,
                        s_ref):
    import jax.numpy as jnp
    pl, _ = _pl()
    bf, f32 = q_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    diag = _masks(q_ref.shape[1])[2]
    s = s_ref[0]
    st_ref[0, 0, 0] = s
    o, s_next, _ = _channel_forward(
        unit(q_ref[0].astype(f32), QUERY_SCALE).astype(bf),
        unit(k_ref[0].astype(f32)).astype(bf), v_ref[0], g_ref[0],
        _column(b_ref[0, 0, 0], diag), s, sub)
    o_ref[0] = o.astype(o_ref.dtype)
    s_ref[0] = s_next


def _bwd_kernel_channel(sub, q_ref, k_ref, v_ref, do_ref, g_ref, b_ref, st_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf, f32 = q_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)     # the last chunk: nothing follows it
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    diag = _masks(q_ref.shape[1])[2]
    qu, q_back = jax.vjp(lambda x: unit(x, QUERY_SCALE), q_ref[0].astype(f32))
    ku, k_back = jax.vjp(unit, k_ref[0].astype(f32))
    dqn, dkn, dv, dg, db, ds = _channel_backward(
        qu.astype(bf), ku.astype(bf), v_ref[0], g_ref[0],
        _column(b_ref[0, 0, 0], diag), st_ref[0, 0, 0], ds_ref[0],
        do_ref[0], sub)
    ds_ref[0] = ds
    dq_ref[0] = q_back(dqn)[0].astype(dq_ref.dtype)
    dk_ref[0] = k_back(dkn)[0].astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dg_ref[0] = dg
    db_ref[0, 0, 0] = _row(db, diag)


def channel_chunk(qn, kn, v, g, beta, s, sub=SUB):
    """``_channel_forward``'s ``o`` and next state for the composed form
    (``ops/decoder_ops.py``), ``beta [C]``: the kernels' arithmetic as plain
    ``jax.numpy``, differentiable by JAX."""
    o, s_next, _ = _channel_forward(qn, kn, v, g, beta[:, None], s, sub)
    return o, s_next


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
    128, 128]`` float32. ``gcum [B, S, heads, 128]``: a decay a key channel
    (one value head a key head)."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, n_v = gcum.shape[:3]
    (q, k, v), at, n_k = _laid_out(qkv, n_v)
    rep, chunks = n_v // n_k, seq // chunk
    key, value, scalars, state = _specs(chunk, rep, lambda i: i)
    if gcum.ndim == 4:
        kernel, decay, sums = (functools.partial(_fwd_kernel_channel, SUB),
                               key(), gcum.reshape(batch, seq, -1))
    else:
        kernel, decay, sums = (functools.partial(_fwd_kernel, rep), scalars,
                               _by_head(gcum, n_k, chunk))
    return pl.pallas_call(
        kernel, grid=(batch, n_k, chunks),
        in_specs=[key(at[0]), key(at[1]), value(at[2]), decay, scalars],
        out_specs=[value(), state],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, n_v * HEAD_DIM), v.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, chunks, n_v, HEAD_DIM, HEAD_DIM), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep, HEAD_DIM, HEAD_DIM), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(q, k, v, sums, _by_head(beta, n_k, chunk))


@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(qkv, gcum, beta, states, do, chunk, interpret):
    """The gradients of ``_fwd_call``'s three arguments (``qkv``'s in its
    own form: three arrays, or their concatenation for the packed one),
    given the states it wrote and ``do``."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, n_v = gcum.shape[:3]
    (q, k, v), at, n_k = _laid_out(qkv, n_v)
    rep, chunks = n_v // n_k, seq // chunk
    key, value, scalars, state = _specs(
        chunk, rep, lambda i: chunks - 1 - i)
    f32 = jnp.float32
    keys = jax.ShapeDtypeStruct((batch, seq, n_k * HEAD_DIM), v.dtype)
    by_head = jax.ShapeDtypeStruct((batch, n_k, chunks, rep, chunk), f32)
    if gcum.ndim == 4:      # a decay a key channel: G and dG a head a tile
        kernel, decay, gr = (functools.partial(_bwd_kernel_channel, SUB),
                             key(), gcum.reshape(batch, seq, -1))
        dg_shape = jax.ShapeDtypeStruct(gr.shape, f32)
    else:
        kernel, decay, gr = (functools.partial(_bwd_kernel, rep), scalars,
                             _by_head(gcum, n_k, chunk))
        dg_shape = by_head
    dq, dk, dv, dg, db = pl.pallas_call(
        kernel, grid=(batch, n_k, chunks),
        in_specs=[key(at[0]), key(at[1]), value(at[2]), value(), decay,
                  scalars, state],
        out_specs=[key(), key(), value(), decay, scalars],
        out_shape=[keys, keys, jax.ShapeDtypeStruct(do.shape, v.dtype),
                   dg_shape, by_head],
        scratch_shapes=[pltpu.VMEM((rep, HEAD_DIM, HEAD_DIM), f32)],
        interpret=interpret, **_params(interpret),
    )(q, k, v, do, gr, _by_head(beta, n_k, chunk), states)
    dqkv = (dq, dk, dv) if isinstance(qkv, (tuple, list)) else \
        jnp.concatenate([dq, dk, dv], axis=-1)
    dg = dg.reshape(gcum.shape) if gcum.ndim == 4 else _from_heads(dg)
    return dqkv, dg, _from_heads(db)


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
