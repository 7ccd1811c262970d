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

A grid step is (batch, ``step_heads`` key heads and all their value heads,
chunk), the chunks in order and the state of the step's value heads (value
head j reads key head ``j // rep``) carried in VMEM scratch as ``[value
heads a step, d_k, d_v]`` float32. ``step_heads`` follows from the op's head
counts: the largest divisor of the key heads whose value heads do not exceed
``STEP_HEADS`` and leave a packed operand's blocks whole (below); one key
head where none fits. A value head's chunk is a chain of dependent ``[128,
128]`` products -- twelve in the inverse alone, each waiting some 245 cycles
for 43 of MXU work -- and a step's value heads are independent, so a kernel's
body is a plain function of one value head's values (``_scalar_forward`` /
``_scalar_backward``; ``_channel_forward`` / ``_channel_backward`` under a
decay a key channel) mapped over a leading head axis (``jax.vmap``): every
value of a chunk is ``[value heads a step, ..]``, Mosaic unrolls each
operation over the heads, and in the kernel's program the heads' chains
stand side by side, stage by stage, so that one chain's waits are filled with
the others' products, while a step's fixed cost is paid that many times less
often. The order is the point: a loop over the heads, one whole chain behind
the other, leaves the chip's schedule as one head's (chip, PR 54: the channel
pair at 2, 4 and 8 heads a step; PR 58: the scalar pair's two value heads,
3.67 / 4.42 ms a layer in a loop and 2.33 / 3.12 side by side). What belongs
to a key head -- the unit q and k, their norms' vjp, ``k k^T`` and ``q k^T``
-- is made once on a ``[key heads a step, ..]`` axis and repeated along it
for the value heads that read it; the masks have no head axis and are made
once. Nothing of a head's arithmetic knows of the others, and a key head's
sums over its value heads (the gradients of its q, k, ``k k^T``, ``q k^T``)
are added a head after another: the results are one key head a step's bit
for bit.

The kernels read what the projection and the short convolution wrote, as
they wrote it; everything between that and the products happens in VMEM.
``q``, ``k`` and ``v`` come either as three arrays ``[B, S, heads * 128]``
or as the one packed array ``[B, S, (2 key heads + value heads) * 128]`` (q |
k | v along the columns), which is then handed to the call three times with
three index maps: a head is one 128-lane tile, so key head j of q is lane
tile ``j``, of k ``key heads + j``, and its ``rep`` value heads' tiles lie
behind both; a step reads its key heads' tiles as one block and their value
heads' as another (``packs`` says when v's offset is whole blocks;
``_laid_out`` counts the offsets in a step's blocks).
No copy of v is cut out. q and k are raw: a step forms ``x * rsqrt(sum(x^2)
+ 1e-6)`` (q also over ``sqrt(d_k)``: ``unit``) in float32 and rounds it to
the inputs' dtype, which is what the products read; no unit q or k exists in
HBM. A head's scalars (``G``, ``beta``) come in once, a value head a row of
``[.., value heads a step, C]`` along the lanes; the ``[C, 1]`` columns the
decay block and the row scalings want are made from the rows through the
identity's mask (a masked ``[C, C]`` sum, exact). The decays, their running
sums, the exps, ``T`` and the state are float32; the products take operands
in the inputs' dtype and accumulate in float32.

The forward kernel also writes the state entering each chunk (``[B, chunks,
heads, d_k, d_v]`` float32), which the backward reads: it walks the chunks in
reverse with the state's gradient in scratch, recomputes a chunk's ``T`` and
``V'`` from the same rounded operands as the forward, and needs no second
forward pass. The gradient of ``G`` is a head's column of row terms less the
column sums of ``E = dM * M + dP * P`` (whose row sums cancel them pair by
pair in the running sum that turns ``dG`` into ``dg``: both are sums of the
one float32 array), summed in VMEM and written with ``dbeta`` in the
scalars' own layout, a value head a row. The gradients of the unit q and k
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
taken of a positive number: the two blocks are built by halving. For ``h =
1, 2, 4, .., C / 2`` the chunk is cut into pairs of neighbouring blocks of
``h`` rows, and the lower block's first row ``r`` is its pair's reference:
for i in the lower block and j in the upper one ``G_i - G_j = (G_i - G_r) +
(G_r - G_j)``, both terms <= 0 (a factor that underflows has a product that
underflows). A level is one ``exp`` pass over the ``[C, d_k]`` tile, ``E =
exp(-|G - G_r|)`` (every row lies in exactly one lower or upper block), and
one product ``[k * E ; q * E] (k * E)^T`` on the MXU, kept where i lies in a
pair's lower block and j in its upper one: the levels' regions are disjoint
and tile the strict lower triangle (the level of a pair i > j is the highest
bit of ``i ^ j``); from blocks of ``PACKED_FROM`` rows the left operand holds
the lower blocks' rows alone, half the MXU's passes. ``P``'s diagonal has no
decay. ``T`` comes block by
block, 8-row blocks merged pair by pair (``_inverse_blocked`` says why not
by the doubling over the whole chunk). These are ``_intra``
and its transpose ``_intra_bwd``: plain functions of a chunk's values, which
the kernels' bodies trace and the composed form maps over batch, head and
chunk (``channel_chunk``). The kernels (``_fwd_kernel_channel``,
``_bwd_kernel_channel``) take one value head a key head, and share the grid,
the block specs (key, value, scalar and state blocks a step's heads wide),
the packed operand (q, k and v start whole blocks in) and the states' layout
with the scalar pair; they read ``G`` and write ``dG`` as ``[B, S,
heads * 128]`` float32, a head a lane tile. In the backward every product
that holds a decay is differentiated as the rounded operand the forward's
product read (``dG += lo * dlo`` for ``lo = (x * E).astype(bf)`` on a pair's
lower rows, ``dG -= up * dup`` on its upper ones), so a pair's reference row
takes nothing for being the reference -- ``G_r`` cancels inside every
product of its pair: channel by channel the lower rows' ``lo * dlo`` and the
upper rows' ``up * dup`` are the same sums of the same rounded arrays -- and
so does what cancels pair by pair in the running sum that turns ``dG`` into
``dg``.
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

from .pallas_ssd import _nn, _nt, _params, _pl, _tn

HEAD_DIM = 128          # key and value head size: one 128-lane tile
CHUNKS = (64, 128)      # chunk lengths the kernels take
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


STEP_HEADS = 8          # value heads a grid step takes at most


def step_heads(key_heads: int, value_heads: int | None = None) -> int:
    """The key heads a grid step of the kernels takes (with all their value
    heads; ``value_heads`` defaults to one a key head): the largest divisor
    of ``key_heads`` whose value heads do not exceed ``STEP_HEADS`` and, where
    a packed operand can be read in place (``packs``), leave v's offset of ``2
    key_heads`` tiles a whole number of the step's value blocks (q's and k's
    are whole blocks of the step's key heads: ``_laid_out``); one key head
    where no divisor fits."""
    value_heads = value_heads or key_heads
    rep = value_heads // key_heads
    whole = packs(key_heads, value_heads)
    return max((h for h in range(1, key_heads + 1)
                if key_heads % h == 0 and h * rep <= STEP_HEADS
                and not (whole and 2 * key_heads % (h * rep))), default=1)


def _heads(ref, n):
    """A block's ``[C, n * 128]``, a head a lane tile -> ``[n, C, 128]``."""
    import jax.numpy as jnp
    return jnp.stack([ref[0, :, HEAD_DIM * r:HEAD_DIM * (r + 1)]
                      for r in range(n)])


def _to_tiles(ref, x):
    """``_heads``' inverse: ``x [n, C, 128]`` into the block."""
    for r in range(x.shape[0]):
        ref[0, :, HEAD_DIM * r:HEAD_DIM * (r + 1)] = x[r]


def _scalar_rows(ref, n):
    """A block's ``[n, C]`` scalars, a head a row -> ``[n, 1, C]``."""
    import jax.numpy as jnp
    rows = ref[0, 0, 0]
    return jnp.stack([rows[r:r + 1, :] for r in range(n)])


def _to_rows(ref, x):
    """``_scalar_rows``' inverse: ``x [n, 1, C]`` into the block."""
    for r in range(x.shape[0]):
        ref[0, 0, 0, r:r + 1, :] = x[r]


def _decays(g, b, lower, diag):
    """A value head's scalars of a chunk, from its ``G`` and ``beta`` rows
    ``[1, C]``: ``beta`` as a column, the decay block ``D``, ``exp(G)``,
    ``exp(G_C)`` (along a tile's lanes: Mosaic spreads a ``[1, 1]`` value
    over one axis at a time) and ``exp(G_C - G)``."""
    import jax.numpy as jnp
    gc = _column(g, diag)                               # [C, 1]
    bc = _column(b, diag)
    d = jnp.exp(jnp.where(lower, gc - g, -jnp.inf))
    end = gc[gc.shape[0] - 1:, :]
    return (bc, d, jnp.exp(gc),
            jnp.exp(jnp.broadcast_to(end, (1, HEAD_DIM))), jnp.exp(end - gc))


def _scalar_chunk(qn, kn, kk, qk, g, b, v, s):
    """What both passes compute of one chunk of one value head under a
    scalar decay: ``qn`` / ``kn [C, d_k]`` its key head's unit operands in
    the products' dtype, ``kk`` / ``qk [C, C]`` that head's ``k k^T`` and ``q
    k^T`` (float32), ``g`` / ``b [1, C]`` the head's running sums and beta,
    ``v [C, d_v]``, ``s [d_k, d_v]`` float32 the state entering. What the
    forward reads comes first."""
    import jax.numpy as jnp
    bf, f32 = qn.dtype, jnp.float32
    lower, strict, diag = _masks(g.shape[1])
    bc, d, eg, e_end, f = _decays(g, b, lower, diag)
    md = jnp.where(strict, kk * d, 0.0)
    tb = _inverse(md * bc, diag.astype(f32), bf).astype(bf)
    sb = s.astype(bf)
    ks = _nn(kn, sb)
    z = v.astype(f32) - eg * ks
    vpb = _nn(tb, (bc * z).astype(bf)).astype(bf)
    return (eg, e_end, vpb, _nn(qn, sb), qk * d,
            (kn.astype(f32) * f).astype(bf), bc, d, f, md, tb, sb, ks, z)


def _scalar_forward(qn, kn, kk, qk, g, b, v, s):
    """``_scalar_chunk``'s head and chunk: ``o [C, d_v]`` (float32) and the
    state leaving."""
    eg, e_end, vpb, qs, p, kend, *_ = _scalar_chunk(qn, kn, kk, qk, g, b, v,
                                                    s)
    return (eg * qs + _nn(p.astype(qn.dtype), vpb),
            e_end * s + _tn(kend, vpb))


def _scalar_backward(qn, kn, kk, qk, g, b, v, s, dsn, do):
    """The chunk's gradients given the state's gradient ``dsn`` leaving it
    and ``do [C, d_v]``: dv, the head's terms of its key head's ``dqn``,
    ``dkn`` (two, in the order they are added), ``dkk`` and ``dqk``, its
    ``dG`` and ``dbeta`` rows ``[1, C]``, the state's gradient entering;
    float32."""
    import jax
    import jax.numpy as jnp
    bf, f32 = qn.dtype, jnp.float32
    c = g.shape[1]
    lower, strict, diag = _masks(c)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    knf = kn.astype(f32)
    eg, e_end, vpb, qs, p, kend, bc, d, f, md, tb, sb, ks, z = _scalar_chunk(
        qn, kn, kk, qk, g, b, v, s)
    m = md * bc

    def rows(x):
        return jnp.sum(x, axis=1, keepdims=True)

    dsnb, dob = dsn.astype(bf), do.astype(bf)
    # O = exp(G) (q S) + P V';  S_next = exp(G_C) S + (k f)^T V'
    dvp = _tn(p.astype(bf), dob) + _nn(kend, dsnb)
    dp = jnp.where(lower, _nt(dob, vpb), 0.0)
    dqs = eg * do.astype(f32)
    dqsb = dqs.astype(bf)
    ds = e_end * dsn + _tn(qn, dqsb)
    dkf = _nt(vpb, dsnb)
    moved = rows(dkf * knf) * f                         # dF_i F_i
    at_last = jnp.sum(moved, axis=0, keepdims=True) + jnp.sum(
        rows(e_end * dsn * s), axis=0, keepdims=True)
    # V' = T R, R = beta (v - exp(G) (k S));  dM = -dR V'^T
    dr = _tn(tb, dvp.astype(bf))
    dm = jnp.where(strict, -_nt(dr.astype(bf), vpb), 0.0)
    dz = bc * dr
    dks = -eg * dz
    dksb = dks.astype(bf)
    # D[i, j] = exp(G_i - G_j): E's row sums at i, its column sums at j
    e = dm * m + dp * p
    dg = (rows(dqs * qs) - moved + rows(dks * ks) + rows(e)
          + jnp.where(at_end, at_last, 0.0))
    return (dz, _nt(dqsb, sb), dkf * f, _nt(dksb, sb), dm * bc * d, dp * d,
            _row(dg, diag) - jnp.sum(e, axis=0, keepdims=True),
            _row(rows(dr * z) + rows(dm * md), diag), ds + _tn(kn, dksb))


def _of_key_heads(x, rep):
    """A key head's ``[keys, ..]`` value at each of its ``rep`` value heads,
    ``[keys * rep, ..]``: what the step's value heads read of it."""
    import jax.numpy as jnp
    return x if rep == 1 else jnp.repeat(x, rep, axis=0)


def _over_value_heads(rep, *terms):
    """The sum of ``terms [keys * rep, ..]`` over each key head's value
    heads, ``[keys, ..]``: a head after another and, within a head, a term
    after another -- the order in which one head at a time adds them."""
    terms = [t.reshape(t.shape[0] // rep, rep, *t.shape[1:]) for t in terms]
    total = None
    for r in range(rep):
        for t in terms:
            total = t[:, r] if total is None else total + t[:, r]
    return total


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, s_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf, f32 = q_ref.dtype, jnp.float32      # bf: the products' operand type

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    n = s_ref.shape[0]                      # the step's value heads
    keys = q_ref.shape[2] // HEAD_DIM       # and its key heads
    qn = unit(_heads(q_ref, keys).astype(f32), QUERY_SCALE).astype(bf)
    kn = unit(_heads(k_ref, keys).astype(f32)).astype(bf)
    nt = jax.vmap(_nt)
    # a key head's: made once, read by each of its value heads; those on a
    # leading axis of every value, as the channel kernels' (below)
    shared = [_of_key_heads(x, n // keys)
              for x in (qn, kn, nt(kn, kn), nt(qn, kn))]
    s = s_ref[...]
    st_ref[0, 0] = s
    o, s_next = jax.vmap(_scalar_forward)(
        *shared, _scalar_rows(g_ref, n), _scalar_rows(b_ref, n),
        _heads(v_ref, n), s)
    _to_tiles(o_ref, o.astype(o_ref.dtype))
    s_ref[...] = s_next


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, g_ref, b_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf, f32 = q_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)     # the last chunk: nothing follows it
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    n = ds_ref.shape[0]                 # the step's heads, as the forward's
    keys = q_ref.shape[2] // HEAD_DIM
    rep = n // keys
    # the unit q and k the forward read, and the way back through the norms
    qu, q_back = jax.vjp(lambda x: unit(x, QUERY_SCALE),
                         _heads(q_ref, keys).astype(f32))
    ku, k_back = jax.vjp(unit, _heads(k_ref, keys).astype(f32))
    qn, kn = qu.astype(bf), ku.astype(bf)
    nn, nt, tn = jax.vmap(_nn), jax.vmap(_nt), jax.vmap(_tn)
    shared = [_of_key_heads(x, rep) for x in (qn, kn, nt(kn, kn), nt(qn, kn))]
    dv, dqn, dkn_f, dkn_s, dkk, dqk, dg, db, ds = jax.vmap(_scalar_backward)(
        *shared, _scalar_rows(g_ref, n), _scalar_rows(b_ref, n),
        _heads(v_ref, n), st_ref[0, 0], ds_ref[...], _heads(do_ref, n))
    ds_ref[...] = ds
    _to_tiles(dv_ref, dv.astype(dv_ref.dtype))
    _to_rows(dg_ref, dg)
    _to_rows(db_ref, db)
    dqn = _over_value_heads(rep, dqn)
    dkn = _over_value_heads(rep, dkn_f, dkn_s)
    dqkb = _over_value_heads(rep, dqk).astype(bf)
    dkkb = _over_value_heads(rep, dkk).astype(bf)
    _to_tiles(dq_ref, q_back(dqn + nn(dqkb, kn))[0].astype(dq_ref.dtype))
    _to_tiles(dk_ref, k_back(dkn + tn(dqkb, qn) + nn(dkkb, kn)
                             + tn(dkkb, kn))[0].astype(dk_ref.dtype))


# -- a decay a key channel ---------------------------------------------------

PACKED_FROM = 8         # block rows from which a level's products take the
#                         lower blocks' rows alone: whole float32 sublane tiles


def _reference(g, h):
    """Each pair's reference row on every row of the pair: ``g [C, d]`` cut
    into pairs of neighbouring blocks of ``h`` rows, the lower block's first
    row over both blocks."""
    import jax.numpy as jnp
    c, d = g.shape
    pairs = g.reshape(c // (2 * h), 2 * h, d)
    return jnp.broadcast_to(pairs[:, h:h + 1], pairs.shape).reshape(c, d)


def _lower_rows(x, h):
    """The pairs' lower blocks of ``x [C, n]``, one after another ``[C / 2,
    n]``: the only rows of a level's left operand that its product keeps, so
    the MXU is given half the rows. From ``PACKED_FROM`` rows a block: under
    that a block is part of a sublane tile, moving its rows is a relayout
    that costs more than the passes saved (chip, PR 52), and ``x`` stays
    whole."""
    if h < PACKED_FROM:
        return x
    c, n = x.shape
    return x.reshape(c // (2 * h), 2 * h, n)[:, h:].reshape(c // 2, n)


def _at_lower_rows(y, h):
    """``_lower_rows``' transpose: ``y``'s rows back at the pairs' lower
    blocks, zero at the upper ones."""
    import jax.numpy as jnp
    if h < PACKED_FROM:
        return y
    half, n = y.shape
    y = y.reshape(half // h, h, n)
    return jnp.concatenate([jnp.zeros_like(y), y], axis=1).reshape(2 * half, n)


def _level(qn, kn, g, h, bf):
    """Level ``h`` of the halving: ``E = exp(-|G - G_ref|)`` (a pair's lower
    rows lie under its reference row and its upper rows above it, so
    ``-|.|`` is ``G - G_ref`` on the one and ``G_ref - G`` on the other, and
    never positive); the product's right operand ``up = kn * E [C, d]``, of
    which it keeps the upper blocks' rows; its left operand ``lo``:
    ``_lower_rows`` of ``kn * E`` over those of ``qn * E``. Both rounded to
    the products' dtype."""
    import jax.numpy as jnp
    e = jnp.exp(-jnp.abs(g - _reference(g, h)))
    ke = kn * e
    lo = jnp.concatenate([_lower_rows(ke, h),
                          _lower_rows(qn, h) * _lower_rows(e, h)], axis=0)
    return e, ke.astype(bf), lo.astype(bf)


def _levels(c):
    """The halving's block lengths, ``1, 2, 4, .., c / 2``."""
    return [1 << n for n in range(c.bit_length() - 1)]


def _apart(c):
    """``i ^ j`` over ``[c, c]``: for i > j its highest bit is the one level
    whose pair has i in its lower block and j in its upper one."""
    import jax
    import jax.numpy as jnp
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            ^ jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


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


def _intra(qn, kn, g, bf):
    """``sum_c x_i[c] kn_j[c] exp(G_i[c] - G_j[c])`` over a chunk for ``x``
    = ``kn`` and ``qn`` (float32 values ``[C, d]``; ``g [C, d]`` the running
    sums): two ``[C, C]`` float32 blocks, right under the diagonal and, for
    ``qn``, on it (no decay there); zero above. A level of the halving is
    one product ``lo up^T`` (``_level``), valid where i lies in a pair's
    lower block and j in its upper one: the levels' regions tile the strict
    lower triangle, and a level writes over what the levels before it left
    in the regions after its own."""
    import jax.numpy as jnp
    c = g.shape[0]
    _, strict, diag = _masks(c)
    apart = jnp.where(strict, _apart(c), 0)
    kk = qk = jnp.zeros((c, c), jnp.float32)
    for h in _levels(c):
        _, up, lo = _level(qn, kn, g, h, bf)
        both = _nt(lo, up)                              # k's rows, then q's
        half = both.shape[0] // 2
        here = apart >= h
        kk = jnp.where(here, _at_lower_rows(both[:half], h), kk)
        qk = jnp.where(here, _at_lower_rows(both[half:], h), qk)
    on = jnp.sum(qn * kn, axis=1, keepdims=True)
    return kk, jnp.where(diag, on, qk)


def _intra_bwd(qn, kn, g, dkk, dqk, bf):
    """``_intra``'s transpose: (dqn, dkn, dG) from the cotangents of its two
    blocks (masked by the caller: strictly under the diagonal, at and under
    it), a level at a time. A product's operand that holds a decay is
    differentiated as the rounded array the product read; a pair's
    reference row takes nothing for being the reference (``G_ref`` cancels
    inside every product of its pair: channel by channel the lower rows'
    ``lo * dlo`` and the upper rows' ``up * dup`` sum to the same)."""
    import jax.numpy as jnp
    f32 = jnp.float32
    c = g.shape[0]
    apart = _apart(c)
    on = _column(dqk, _masks(c)[2])             # dqk's diagonal
    dq, dk, dg = on * kn, on * qn, jnp.zeros(g.shape, f32)
    for h in _levels(c):
        e, up, lo = _level(qn, kn, g, h, bf)
        here = (apart >> (h.bit_length() - 1)) == 1
        d_both = jnp.concatenate(
            [_lower_rows(jnp.where(here, dkk, 0.0), h),
             _lower_rows(jnp.where(here, dqk, 0.0), h)], axis=0).astype(bf)
        dlo, dup = _nn(d_both, up), _tn(d_both, lo)
        half = dlo.shape[0] // 2
        fell = lo.astype(f32) * dlo                     # k's rows, then q's
        dk += (_at_lower_rows(dlo[:half], h) + dup) * e
        dq += _at_lower_rows(dlo[half:], h) * e
        dg += _at_lower_rows(fell[:half] + fell[half:], h) \
            - up.astype(f32) * dup
    return dq, dk, dg


def _channel_forward(qn, kn, v, g, bc, s):
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
    kk, qk = _intra(qf, kf, g, bf)
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


def _channel_backward(qn, kn, v, g, bc, s, dsn, do):
    """The chunk's gradients given the state's gradient ``dsn`` leaving it
    and ``do [C, d_v]``: (dqn, dkn, dv, dG, dbeta ``[C, 1]``, the state's
    gradient entering), float32; dqn / dkn are the unit operands'."""
    import jax
    import jax.numpy as jnp
    bf = qn.dtype
    c = g.shape[0]
    lower, strict, _ = _masks(c)
    (qf, kf, md, p, tb, eg, fade, kg, qg, kend, e_end, sb, z,
     vpb) = _channel_forward(qn, kn, v, g, bc, s)[2]

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
    dq, dk, dg = _intra_bwd(qf, kf, g, dm * bc, dp, bf)
    f32 = jnp.float32
    moved = kend.astype(f32) * dkend            # leaves row i for row C
    at_last = jnp.sum(moved, axis=0, keepdims=True) + _row(
        e_end * rows(dsn * s), _masks(g.shape[1])[2])
    at_end = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dg = (dg + qg.astype(f32) * dqg + kg.astype(f32) * dkg - moved
          + jnp.where(at_end, at_last, 0.0))
    return (dq + dqg * eg, dk + dkg * eg + dkend * fade, dz, dg,
            rows(dr * z) + rows(dm * md), ds)


def _fwd_kernel_channel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref,
                        s_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf, f32 = q_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def head(q, k, v, g, b, s):         # a head's chunk, values in and out
        o, s_next, _ = _channel_forward(
            unit(q.astype(f32), QUERY_SCALE).astype(bf),
            unit(k.astype(f32)).astype(bf), v, g,
            _column(b, _masks(g.shape[0])[2]), s)
        return o.astype(o_ref.dtype), s_next

    # the step's heads on a leading axis of every value: Mosaic unrolls an
    # operation over them, so their chains stand side by side in the program
    n = s_ref.shape[0]
    s = s_ref[...]
    st_ref[0, 0] = s
    o, s_next = jax.vmap(head)(
        _heads(q_ref, n), _heads(k_ref, n), _heads(v_ref, n), _heads(g_ref, n),
        _scalar_rows(b_ref, n), s)
    _to_tiles(o_ref, o)
    s_ref[...] = s_next


def _bwd_kernel_channel(q_ref, k_ref, v_ref, do_ref, g_ref, b_ref, st_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    bf, f32 = q_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)     # the last chunk: nothing follows it
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def head(q, k, v, g, b, s, dsn, do):
        diag = _masks(g.shape[0])[2]
        qu, q_back = jax.vjp(lambda x: unit(x, QUERY_SCALE), q.astype(f32))
        ku, k_back = jax.vjp(unit, k.astype(f32))
        dqn, dkn, dv, dg, db, ds = _channel_backward(
            qu.astype(bf), ku.astype(bf), v, g, _column(b, diag), s, dsn, do)
        return (q_back(dqn)[0].astype(dq_ref.dtype),
                k_back(dkn)[0].astype(dk_ref.dtype), dv.astype(dv_ref.dtype),
                dg, _row(db, diag), ds)

    n = ds_ref.shape[0]                 # the step's heads, as the forward's
    dq, dk, dv, dg, db, ds = jax.vmap(head)(
        _heads(q_ref, n), _heads(k_ref, n), _heads(v_ref, n), _heads(g_ref, n),
        _scalar_rows(b_ref, n), st_ref[0, 0], ds_ref[...], _heads(do_ref, n))
    ds_ref[...] = ds
    for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dg)):
        _to_tiles(ref, x)
    _to_rows(db_ref, db)


def channel_chunk(qn, kn, v, g, beta, s):
    """``_channel_forward``'s ``o`` and next state for the composed form
    (``ops/decoder_ops.py``), ``beta [C]``: the kernels' arithmetic as plain
    ``jax.numpy``, differentiable by JAX."""
    o, s_next, _ = _channel_forward(qn, kn, v, g, beta[:, None], s)
    return o, s_next


def _by_head(v, steps, chunk):
    """``[B, S, heads]`` -> ``[B, steps, chunks, heads / steps, C]``: a grid
    step's scalars (``steps``: the grid's extent over the heads), a value
    head a row."""
    b, s, h = v.shape
    return (v.reshape(b, s // chunk, chunk, steps, h // steps)
            .transpose(0, 3, 1, 4, 2))


def _from_heads(rows):
    """``_by_head``'s layout back to ``[B, S, heads]``."""
    b, steps, chunks, values, c = rows.shape
    return rows.transpose(0, 2, 4, 1, 3).reshape(b, chunks * c,
                                                 steps * values)


def _laid_out(qkv, value_heads):
    """(the q, k and v operands, the lane block each one's first head is at
    -- q's and k's in blocks of a step's key heads, v's in blocks of its
    value heads --, the key heads, the key heads a grid step takes:
    ``step_heads``) of ``(q, k, v)`` or of one packed ``q | k | v`` array."""
    packed = not isinstance(qkv, (tuple, list))
    key_heads = ((qkv.shape[2] // HEAD_DIM - value_heads) // 2 if packed
                 else qkv[0].shape[2] // HEAD_DIM)
    step = step_heads(key_heads, value_heads)
    if not packed:
        return qkv, (0, 0, 0), key_heads, step
    return (qkv, qkv, qkv), (
        0, key_heads // step,
        2 * key_heads // (step * value_heads // key_heads)), key_heads, step


def _specs(c, keys, values, chunk_of):
    """Block specs of what both passes read or write, the chunk a grid step
    works on given by ``chunk_of(i)``: the tiles of the step's ``keys`` key
    heads and of its ``values`` value heads, each from the lane block
    ``first`` its array's first head is at (``_laid_out``), a value head's
    scalars a row, its states."""
    pl, pltpu = _pl()

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def key(first=0):
        return spec((1, c, keys * HEAD_DIM),
                    lambda b, j, i: (b, chunk_of(i), first + j))

    def value(first=0):
        return spec((1, c, values * HEAD_DIM),
                    lambda b, j, i: (b, chunk_of(i), first + j))
    scalars = spec((1, 1, 1, values, c),
                   lambda b, j, i: (b, j, chunk_of(i), 0, 0))
    state = spec((1, 1, values, HEAD_DIM, HEAD_DIM),
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
    (q, k, v), at, n_k, step = _laid_out(qkv, n_v)
    values, steps, chunks = n_v // n_k * step, n_k // step, seq // chunk
    key, value, scalars, state = _specs(chunk, step, values, lambda i: i)
    if gcum.ndim == 4:
        kernel, decay, sums = (_fwd_kernel_channel, key(),
                               gcum.reshape(batch, seq, -1))
    else:
        kernel, decay, sums = (_fwd_kernel, scalars,
                               _by_head(gcum, steps, chunk))
    return pl.pallas_call(
        kernel, grid=(batch, steps, chunks),
        in_specs=[key(at[0]), key(at[1]), value(at[2]), decay, scalars],
        out_specs=[value(), state],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, n_v * HEAD_DIM), v.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, chunks, n_v, HEAD_DIM, HEAD_DIM), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((values, HEAD_DIM, HEAD_DIM), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(q, k, v, sums, _by_head(beta, steps, chunk))


@functools.partial(_jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(qkv, gcum, beta, states, do, chunk, interpret):
    """The gradients of ``_fwd_call``'s three arguments (``qkv``'s in its
    own form: three arrays, or their concatenation for the packed one),
    given the states it wrote and ``do``."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    batch, seq, n_v = gcum.shape[:3]
    (q, k, v), at, n_k, step = _laid_out(qkv, n_v)
    values, steps, chunks = n_v // n_k * step, n_k // step, seq // chunk
    key, value, scalars, state = _specs(
        chunk, step, values, lambda i: chunks - 1 - i)
    f32 = jnp.float32
    keys = jax.ShapeDtypeStruct((batch, seq, n_k * HEAD_DIM), v.dtype)
    by_head = jax.ShapeDtypeStruct((batch, steps, chunks, values, chunk), f32)
    if gcum.ndim == 4:      # a decay a key channel: G and dG a head a tile
        kernel, decay, gr = (_bwd_kernel_channel, key(),
                             gcum.reshape(batch, seq, -1))
        dg_shape = jax.ShapeDtypeStruct(gr.shape, f32)
    else:
        kernel, decay, gr = (_bwd_kernel, scalars,
                             _by_head(gcum, steps, chunk))
        dg_shape = by_head
    dq, dk, dv, dg, db = pl.pallas_call(
        kernel, grid=(batch, steps, chunks),
        in_specs=[key(at[0]), key(at[1]), value(at[2]), value(), decay,
                  scalars, state],
        out_specs=[key(), key(), value(), decay, scalars],
        out_shape=[keys, keys, jax.ShapeDtypeStruct(do.shape, v.dtype),
                   dg_shape, by_head],
        scratch_shapes=[pltpu.VMEM((values, HEAD_DIM, HEAD_DIM), f32)],
        interpret=interpret, **_params(interpret),
    )(q, k, v, do, gr, _by_head(beta, steps, chunk), states)
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
