"""In-graph metric ops (reference: paddle/fluid/operators/metrics/: accuracy_op,
auc_op, precision_recall_op)."""
from __future__ import annotations

from ..core.registry import register

# chunk_eval tag-scheme table (reference chunk_eval_op.h:119):
# scheme -> (num_tag_types, tag_begin, tag_inside, tag_end, tag_single).
# Shared by the sequential oracle (_chunk_segments) and the vectorized
# lowering so they cannot drift apart.
_CHUNK_SCHEMES = {"IOB": (2, 0, 1, -1, -1), "IOE": (2, -1, 0, 1, -1),
                  "IOBES": (4, 0, 1, 2, 3), "plain": (1, -1, -1, -1, -1)}


def _jnp():
    import jax.numpy as jnp
    return jnp


@register("accuracy", grad=None, nondiff_inputs=("Out", "Indices", "Label"))
def accuracy(ctx, ins):
    """Top-k accuracy: Indices [N,k] from top_k, Label [N,1]."""
    jnp = _jnp()
    idx = ins["Indices"][0]
    label = ins["Label"][0]
    if label.ndim == 1:
        label = label[:, None]
    correct = jnp.any(idx == label.astype(idx.dtype), axis=1)
    total = jnp.asarray(idx.shape[0], "float32")
    ncorrect = jnp.sum(correct.astype("float32"))
    return {"Accuracy": [(ncorrect / total).reshape((1,))],
            "Correct": [ncorrect.astype("int32").reshape((1,))],
            "Total": [jnp.asarray([idx.shape[0]], "int32")]}


@register("auc", grad=None, nondiff_inputs=("Predict", "Label"))
def auc(ctx, ins):
    """Streaming AUC via fixed histogram buckets (reference auc_op.cc).

    StatPos/StatNeg are persistable state vars threaded functionally.
    """
    jnp = _jnp()
    pred = ins["Predict"][0]  # [N, 2] (prob of neg, pos)
    label = ins["Label"][0].reshape(-1)
    stat_pos, stat_neg = ins["StatPos"][0], ins["StatNeg"][0]
    num_thresholds = ctx.attr("num_thresholds", 4095)
    p = pred[:, -1]
    bucket = jnp.clip((p * num_thresholds).astype("int32"), 0, num_thresholds)
    is_pos = (label > 0).astype(stat_pos.dtype)
    pos_out = stat_pos.at[bucket].add(is_pos)
    neg_out = stat_neg.at[bucket].add(1 - is_pos)
    # AUC = sum over buckets (descending threshold) of trapezoid areas
    tp = jnp.cumsum(pos_out[::-1])
    fp = jnp.cumsum(neg_out[::-1])
    tot_pos, tot_neg = tp[-1], fp[-1]
    tpr = tp / jnp.maximum(tot_pos, 1)
    fpr = fp / jnp.maximum(tot_neg, 1)
    tpr0 = jnp.concatenate([jnp.zeros((1,), tpr.dtype), tpr[:-1]])
    fpr0 = jnp.concatenate([jnp.zeros((1,), fpr.dtype), fpr[:-1]])
    auc_val = jnp.sum((fpr - fpr0) * (tpr + tpr0) / 2.0)
    return {"AUC": [auc_val.reshape((1,)).astype("float64")],
            "StatPosOut": [pos_out], "StatNegOut": [neg_out]}


def _chunk_segments(tags, scheme, num_chunk_types):
    """Reference chunk_eval_op.h:41 GetSegments, verbatim semantics: returns
    [(begin, end, type)] for one sequence of tag ids."""
    if scheme not in _CHUNK_SCHEMES:
        raise ValueError(f"chunk_eval: unknown chunk_scheme {scheme!r}")
    num_tag, t_beg, t_in, t_end, t_sg = _CHUNK_SCHEMES[scheme]
    other = num_chunk_types

    def is_end(pt, pty, t, ty):
        if pty == other:
            return False
        if ty == other or ty != pty:
            return True
        if pt in (t_beg, t_in):
            return t in (t_beg, t_sg)
        return pt in (t_end, t_sg)

    def is_begin(pt, pty, t, ty):
        if pty == other:
            return ty != other
        if ty == other:
            return False
        if ty != pty:
            return True
        if t == t_beg or t == t_sg:
            return True
        if t in (t_in, t_end):
            return pt in (t_end, t_sg)
        return False

    segs = []
    in_chunk, start, tag, typ = False, 0, -1, other
    for i, lab in enumerate(tags):
        pt, pty = tag, typ
        tag, typ = int(lab) % num_tag, int(lab) // num_tag
        if in_chunk and is_end(pt, pty, tag, typ):
            segs.append((start, i - 1, pty))
            in_chunk = False
        if is_begin(pt, pty, tag, typ):
            start, in_chunk = i, True
    if in_chunk and typ != other:
        segs.append((start, len(tags) - 1, typ))
    return segs


@register("chunk_eval", grad=None)
def chunk_eval(ctx, ins):
    """Reference chunk_eval_op.cc: chunk-level precision/recall/F1 between
    predicted and label tag sequences (IOB/IOE/IOBES/plain schemes).

    Fully vectorized (no host callback, so the metric stays inside the
    compiled step):
    the reference's sequential GetSegments walk reduces to per-transition
    begin/end flags (ChunkBegin/ChunkEnd are pure functions of consecutive
    tag pairs), a chunk's end is the next end-flagged transition, and two
    chunks match iff their (begin, end, type) triples align -- all
    computable with cumulative ops over padded [B, T] + SeqLength inputs
    (this repo's length-aware replacement for the reference's LoD).
    """
    import jax
    jnp = _jnp()
    inf, lab = ins["Inference"][0], ins["Label"][0]
    lengths = ins.get("SeqLength", [None])[0]
    scheme = ctx.attr("chunk_scheme", "IOB")
    nct = int(ctx.attr("num_chunk_types"))
    excluded = list(ctx.attr("excluded_chunk_types", []) or [])
    if scheme not in _CHUNK_SCHEMES:
        raise ValueError(f"chunk_eval: unknown chunk_scheme {scheme!r}")
    num_tag, t_beg, t_in, t_end, t_sg = _CHUNK_SCHEMES[scheme]
    other_tag = nct * num_tag     # any tag with type == nct parses as Other

    B, T = inf.shape

    def analyze(tags):
        """(begin [B,T] bool, type [B,T], end_pos [B,T]) under the
        reference transition rules; padded tail forced to Other."""
        tags = tags.astype(jnp.int32)
        if lengths is not None:
            pos = jax.lax.broadcasted_iota(jnp.int32, (B, T), 1)
            tags = jnp.where(pos < lengths.reshape(B, 1).astype(jnp.int32),
                             tags, other_tag)
        tag = tags % num_tag
        typ = tags // num_tag
        # previous position (virtual prev at i=0 is Other)
        ptag = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32),
                                tag[:, :-1]], axis=1)
        ptyp = jnp.concatenate([jnp.full((B, 1), nct, jnp.int32),
                                typ[:, :-1]], axis=1)
        is_other = typ == nct
        p_other = ptyp == nct
        # ChunkBegin(prev, cur) -- chunk_eval_op.h:96
        begin = jnp.where(
            p_other, ~is_other,
            jnp.where(is_other, False,
                      jnp.where(typ != ptyp, True,
                                (tag == t_beg) | (tag == t_sg)
                                | (((tag == t_in) | (tag == t_end))
                                   & ((ptag == t_end) | (ptag == t_sg))))))
        # ChunkEnd(prev, cur) evaluated at transition i (closing i-1) --
        # chunk_eval_op.h:83
        end_at = jnp.where(
            p_other, False,
            jnp.where(is_other | (typ != ptyp), True,
                      jnp.where((ptag == t_beg) | (ptag == t_in),
                                (tag == t_beg) | (tag == t_sg),
                                (ptag == t_end) | (ptag == t_sg))))
        # a chunk starting at i runs to (next j>i with end_at[j]) - 1, or
        # the last in-sequence position; encode ends as the transition
        # index j (sequence end -> T). reversed running-min of flagged j.
        idx = jax.lax.broadcasted_iota(jnp.int32, (B, T), 1)
        flagged = jnp.where(end_at, idx, T)
        # next_end[i] = min(flagged[i+1:]) -- suffix min, exclusive
        suffix = jax.lax.cummin(flagged[:, ::-1], axis=1)[:, ::-1]
        next_end = jnp.concatenate(
            [suffix[:, 1:], jnp.full((B, 1), T, jnp.int32)], axis=1)
        return begin, typ, jnp.where(begin, next_end, -1)

    b_i, t_i, e_i = analyze(inf)
    b_l, t_l, e_l = analyze(lab)
    keep_i = b_i
    keep_l = b_l
    for ex in excluded:
        keep_i = keep_i & (t_i != ex)
        keep_l = keep_l & (t_l != ex)
    n_inf = jnp.sum(keep_i).astype(jnp.int32)
    n_lab = jnp.sum(keep_l).astype(jnp.int32)
    match = keep_i & keep_l & (t_i == t_l) & (e_i == e_l)
    n_cor = jnp.sum(match).astype(jnp.int32)
    p = jnp.where(n_inf > 0, n_cor / jnp.maximum(n_inf, 1), 0.0)
    r = jnp.where(n_lab > 0, n_cor / jnp.maximum(n_lab, 1), 0.0)
    f1 = jnp.where(p + r > 0, 2 * p * r / jnp.maximum(p + r, 1e-12), 0.0)
    as1 = lambda v, dt: v.astype(dt).reshape(1)
    return {"Precision": [as1(p, jnp.float32)],
            "Recall": [as1(r, jnp.float32)],
            "F1-Score": [as1(f1, jnp.float32)],
            "NumInferChunks": [as1(n_inf, jnp.int32)],
            "NumLabelChunks": [as1(n_lab, jnp.int32)],
            "NumCorrectChunks": [as1(n_cor, jnp.int32)]}
