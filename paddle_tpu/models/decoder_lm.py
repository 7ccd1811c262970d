"""A decoder-only language model built from a config dict that carries the
key names of a published ``config.json`` (the catalog's names): token
embedding -> ``num_hidden_layers`` x block -> final RMSNorm -> untied output
projection -> next-token cross-entropy, plus the router losses.

One builder for the decoder families (ROADMAP D6); what a config asks for and
this file does not build yet raises by name. The first model through it is
OLMoE-1B-7B (Muennighoff et al., arXiv:2409.02060; HF ``modeling_olmoe.py``):

- pre-norm block, no biases, no dropout: ``h = x + o_proj(attn(norm(x)))``,
  ``y = h + moe(norm(h))``;
- attention: separate q / k / v projections, RMSNorm over the whole projected
  q and k (before the split into heads, each with its own scale), rotary
  embedding (rotate-half) on q and k, causal ``fused_attention`` at scale
  1/sqrt(head dim), ``impl="auto"``;
- feed-forward: ``layers.moe_ffn`` -- float32 router, softmax then top-k with
  the values used as they are (``norm_topk_prob`` false), dropless experts
  ``W_down (silu(W_gate x) * (W_up x))`` of width ``intermediate_size``;
- loss: mean next-token cross-entropy + ``router_aux_loss_coef`` x the
  load-balancing loss (experts x sum over experts of the share of assignments
  an expert received x its mean router probability, a layer, averaged over
  the layers) + ``router_z_loss_coef`` x the mean of logsumexp(router
  logits)^2 (likewise).

Dtypes follow ``models/bert.py``: the embedding table is float32 whatever
``dtype`` says, activations are cast to ``dtype`` right after the lookup,
weights are created in ``dtype``; RMSNorm, the router and every softmax
compute in float32 inside their ops; the logits are cast up for the loss.
"""
from __future__ import annotations

import math

from .. import layers
from ..initializer import Normal
from ..layer_helper import ParamAttr

_REQUIRED = {"hidden_act": "silu", "norm_topk_prob": False,
             "tie_word_embeddings": False, "attention_bias": False,
             "clip_qkv": None, "rope_scaling": None}


def _check(cfg: dict) -> None:
    for key, want in _REQUIRED.items():
        if cfg.get(key, want) != want:
            raise NotImplementedError(
                f"decoder_lm: {key}={cfg[key]!r} is not built yet "
                f"(only {want!r})")
    if cfg.get("num_key_value_heads",
               cfg["num_attention_heads"]) != cfg["num_attention_heads"]:
        raise NotImplementedError(
            "decoder_lm: grouped-query attention (num_key_value_heads != "
            "num_attention_heads) is not built yet")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("hidden_size must be a multiple of the head count")


def _attr(name: str) -> ParamAttr:
    return ParamAttr(name=name, initializer=Normal(0.0, 0.02))


def _linear(x, size: int, name: str):
    return layers.fc(x, size, param_attr=_attr(name), bias_attr=False)


def attention(x, cfg: dict, batch: int, seq: int, name: str):
    """Causal multi-head self-attention over tokens ``x [batch * seq, H]``."""
    H, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d = H // heads
    eps = cfg["rms_norm_eps"]

    def heads_of(t):                                # [B*S, H] -> [B, h, S, d]
        t = layers.reshape(t, [batch, seq, heads, d])
        return layers.transpose(t, [0, 2, 1, 3])

    q = layers.rms_norm(_linear(x, H, name + "_q_w"), eps,
                        ParamAttr(name=name + "_q_norm_w"))
    k = layers.rms_norm(_linear(x, H, name + "_k_w"), eps,
                        ParamAttr(name=name + "_k_norm_w"))
    v = _linear(x, H, name + "_v_w")
    q = layers.rotary_embedding(heads_of(q), cfg["rope_theta"])
    k = layers.rotary_embedding(heads_of(k), cfg["rope_theta"])
    ctx = layers.fused_attention(q, k, heads_of(v), causal=True,
                                 scale=1.0 / math.sqrt(d), impl="auto")
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [batch * seq, H])
    return _linear(ctx, H, name + "_o_w")


def block(x, cfg: dict, batch: int, seq: int, name: str):
    """One decoder layer over ``x [batch * seq, H]``; returns the layer's
    output and the router's variables (``layers.moe_ffn``)."""
    eps = cfg["rms_norm_eps"]
    normed = layers.rms_norm(x, eps, ParamAttr(name=name + "_attn_norm_w"))
    h = layers.elementwise_add(
        x, attention(normed, cfg, batch, seq, name + "_attn"))
    normed = layers.rms_norm(h, eps, ParamAttr(name=name + "_ffn_norm_w"))
    moe, aux = layers.moe_ffn(
        normed, cfg["num_experts"], cfg["num_experts_per_tok"],
        cfg["intermediate_size"], param_attr=_attr(None), name=name + "_moe")
    return layers.elementwise_add(h, moe), aux


def _mean_of(values):
    total = values[0] if len(values) == 1 else layers.sums(values)
    return layers.scale(total, 1.0 / len(values))


def build(cfg: dict, ids, labels) -> dict:
    """Append the model to the current Program. ``ids [batch, seq]`` int
    tokens, ``labels [batch * seq, 1]`` the next token of every position.

    Returns the variables a caller trains on or fetches: ``loss`` (the
    total), ``ce`` (mean cross-entropy), ``each`` (every position's
    cross-entropy ``[batch * seq, 1]``), ``load_balancing`` and ``z_loss``
    (the two router losses before their coefficients), and per layer
    ``expert_load`` (``[experts]`` int32: assignments an expert received)
    and ``expert_index`` (``[batch * seq, k]``: the experts chosen)."""
    _check(cfg)
    batch, seq = int(ids.shape[0]), int(ids.shape[1])
    H, E = cfg["hidden_size"], cfg["num_experts"]
    dtype = cfg.get("dtype", "float32")
    x = layers.embedding(ids, [cfg["vocab_size"], H], dtype="float32",
                         param_attr=_attr("tok_emb"))
    if dtype != "float32":
        x = layers.cast(x, dtype)
    x = layers.reshape(x, [batch * seq, H])
    balance, z, loads, indices = [], [], [], []
    for i in range(cfg["num_hidden_layers"]):
        x, aux = block(x, cfg, batch, seq, f"layer{i}")
        share = layers.scale(layers.cast(aux["load"], "float32"),
                             1.0 / (batch * seq * cfg["num_experts_per_tok"]))
        balance.append(layers.scale(layers.reduce_sum(layers.elementwise_mul(
            share, layers.reduce_mean(aux["prob"], dim=0))), float(E)))
        z.append(layers.mean(layers.square(aux["logz"])))
        loads.append(aux["load"])
        indices.append(aux["index"])
    x = layers.rms_norm(x, cfg["rms_norm_eps"],
                        ParamAttr(name="final_norm_w"))
    logits = _linear(x, cfg["vocab_size"], "lm_head_w")
    if dtype != "float32":
        logits = layers.cast(logits, "float32")
    each = layers.softmax_with_cross_entropy(logits, labels)
    ce = layers.mean(each)
    balance, z = _mean_of(balance), _mean_of(z)
    loss = layers.sums([
        ce, layers.scale(balance, float(cfg["router_aux_loss_coef"])),
        layers.scale(z, float(cfg["router_z_loss_coef"]))])
    return {"loss": loss, "ce": ce, "each": each, "load_balancing": balance,
            "z_loss": z, "expert_load": loads, "expert_index": indices}
