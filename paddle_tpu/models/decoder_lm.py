"""A decoder-only language model built from a config dict that carries the
key names of a published ``config.json`` (the catalog's names): token
embedding -> ``num_hidden_layers`` x block -> final RMSNorm -> output
projection (its own matrix, or the embedding table under
``tie_word_embeddings``) -> next-token cross-entropy, plus the router losses
where the config names their coefficients.

One builder for the decoder families (ROADMAP D6); what a config asks for and
this file does not build yet raises by name (``_check``). A block is
pre-norm, without biases or dropout: ``h = x + r operator(norm(x))``,
``y = h + r feed_forward(norm(h))`` with ``r`` = ``residual_multiplier``
(default 1). What it builds, by config key:

- ``total_ut_steps`` (default 1) above 1: a looped model (Ouro / LoopLM,
  arXiv:2510.25741). With ``R = total_ut_steps``, ``N =
  num_hidden_layers``, every weight shared by the passes: ``h^(0) =
  tok_emb[t]``; pass ``r = 1..R`` runs the ``N`` layers over ``h^(r-1)``
  and ``h^(r) = Norm_f(x_N)``, the final norm applied after every pass and
  carried into the next; ``logits^(r) = W_head h^(r)`` (one head) and
  ``ce^(r)_i`` its next-token cross-entropy at position ``i``; the exit
  gate ``lambda^(r)_i = sigmoid(w_g . h^(r)_i + b_g)`` in float32 (one
  ``Linear(H, 1)``, float32 parameters from 0); the exit distribution
  ``p^(1) = lambda^(1)``, ``p^(r) = lambda^(r) prod_{j<r} (1 -
  lambda^(j))`` for ``r < R``, ``p^(R) = prod_{j<R} (1 - lambda^(j))``;
  ``loss = mean_i [sum_r p^(r)_i ce^(r)_i - exit_entropy_coef H(p_i)]``
  (the report's stage-I objective; ``exit_entropy_coef`` default 0),
  gradients through ``p`` and every ``ce^(r)``. In the Program the stack is
  ONE ``scan`` op (``layers.Scan(steps=R)``) over a sub-block that holds the layers'
  ops and the final norm once: op count, parameters and startup program do
  not grow with ``R``, the weights enter the op as declared inputs and each
  one's gradient is the sum over its ``R`` uses; the passes' states leave it
  stacked, one head product and one ``softmax_with_cross_entropy`` run over
  ``R x`` the rows, and ``layers.exit_gate_loss`` is the gate, the exit
  distribution and the expected loss. ``build`` then returns ``loss``,
  ``ce`` (the expected cross-entropy), ``each`` (``[R * batch * seq, 1]``,
  pass-major), ``exit_p`` (likewise) and ``loop_checkpoints`` (what a
  ``RecomputeOptimizer`` cuts the sub-block at for recomputation by
  layer). Dense layers only; no prediction module, no mesh axes.
- ``norm_placement`` ``"pre"`` (default), or ``"sandwich"``: a second norm
  on each branch's output before the residual add, ``a = x +
  Norm_2(operator(Norm_1(x)))``, ``y = a + Norm_4(feed_forward(Norm_3(a)))``
  (HF ``modeling_ouro.py``'s ``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2``; parameters
  ``<operator>_post_norm_w`` and ``<layer>_ffn_post_norm_w``).
- ``early_exit_threshold`` concerns decoding (a token leaves the loop once
  the exit distribution's running sum reaches it; 1: never before the last
  pass): ``build`` builds training programs, which run every pass, and
  never reads it; no decode loop that would is built yet.

- ``hc_mult`` = n above 1: the residual path is n streams a token
  (manifold-constrained hyper-connections: DeepSeek-AI, mHC,
  arXiv:2512.24880, over Hyper-Connections, Zhu et al., arXiv:2409.19606).
  A token's state is ``X [n, H]``, in the Program ``[tokens, n H]`` with
  the streams side by side; ``X_0`` is the embedding in every stream. Each
  sub-layer ``F`` (the pre-norm on the H-wide input, then the operator or
  the feed-forward) has float32 ``phi [n H, 2 n + n^2]``, ``b [2 n +
  n^2]``, ``alpha [3]`` (``<operator>_hc_*``, ``<layer>_ffn_hc_*``): with
  ``z = RMSNorm_{hc_eps}(vec X) phi`` (no learned scale), ``H_pre =
  sigmoid(alpha_0 z + b)`` (n), ``H_post = 2 sigmoid(alpha_1 z + b)`` (n),
  ``H_res = SK(clip(alpha_2 z + b, mhc_h_res_clamp_min, _max))`` (n x n:
  ``exp``, then ``hc_sinkhorn_iters`` times rows over their sums + ``hc_
  eps``, columns over theirs); ``u = H_pre X``, ``y = F(u)``, ``X' = H_res
  X + H_post^T y`` (``layers.hyper_connection_pre`` / ``_post``, float32
  inside, their grad ops keep X, y and the coefficients and compute the
  rest again). After the last layer the streams are summed, then the final
  norm. ``hc_alpha_init`` (default 0.01) and ``hc_bias_std`` (default 0:
  ``b`` from 0, else from a seeded normal) are the recipe's start. Absent
  or 1: the one-stream path, op for op what it was. Not beside a
  prediction module, a looped stack, mesh axes, sandwich norms or a
  residual multiplier.
- ``layer_types`` (default: every layer ``full_attention``, or, where the
  config has ``full_attention_interval``, every interval-th layer
  ``full_attention`` and the others ``linear_attention``), one operator a
  layer. ``full_attention`` (also spelt ``attention``): separate q / k / v
  projections with ``num_attention_heads`` query heads
  (``num_attention_heads_per_layer[i]`` in layer i where the config has
  that list) of ``head_dim`` values (default hidden size / heads; heads x
  ``head_dim`` need not be the hidden size) and
  ``num_key_value_heads`` key/value heads (grouped-query attention where
  fewer; ``fused_attention`` reads them in
  place), RMSNorm of q and k -- ``qk_norm`` ``"projection"``: over the whole
  projected q and k before the split into heads, one scale an element
  (OLMoE); ``"head"``: over each head's values, one scale of head size
  shared by the heads (LFM2); ``"none"``: no norm (Granite, Laguna) --,
  rotary embedding (rotate-half) on q and k unless
  ``position_embedding_type`` is ``"nope"`` -- ``rope_theta`` over the whole
  head, or by layer type from ``rope_parameters[<layer type>]``:
  ``rope_theta``, ``partial_rotary_factor`` (the leading share of a head
  that is rotated) and ``rope_type`` ``"default"`` or ``"yarn"``
  (``layers.rotary_embedding``) --, causal ``fused_attention`` at
  scale ``attention_multiplier`` (default 1/sqrt(head dim)), lowered as
  ``attention_impl`` says (default ``"auto"``; ``"pallas"`` raises where
  the flash kernels cannot lower); under ``gating: "per-head"`` each head's output times
  ``sigmoid(W_g norm(x))``, one gate a token and head, before the output
  projection (``layers.attention_gate``); under ``attn_output_gate`` the q
  projection is twice as wide, a head's first ``head_dim`` values its q and
  the others its gate, one a token, head and channel (Qwen3-Next). Without
  ``rope_parameters``, ``partial_rotary_factor`` is read beside
  ``rope_theta``. A config with ``kv_lora_rank`` builds its
  ``full_attention`` layers as multi-head latent attention
  (``latent_attention``, below; DeepSeek-V2's, GLM-4.7-Flash's, Kimi
  Linear's: there beside linear layers, under ``q_lora_rank: null`` with q
  from one projection, under ``mla_use_nope`` without rotation, with a
  ``v_head_dim`` of its own; Xing4.0's under ``rope_scaling``, a YaRN dict:
  blended frequencies, ``mscale_all_dim``'s factor squared on the softmax
  scale).
  ``sliding_attention``: the same
  with a window of ``sliding_window`` keys (query i sees i - window < j <=
  i). ``conv``: the gated short convolution ``W_out (C * conv(B
  * u))`` with ``B, C, u = split(W_in x, 3)`` and a causal depthwise filter
  of ``conv_L_cache`` taps (``layers.short_conv``). ``mamba``: the Mamba-2
  mixer (``mamba``, below). ``linear_attention``: the Gated DeltaNet mixer
  (``delta_net``, below). ``kda``: the Kimi Delta Attention mixer
  (``kimi_delta``, below). A config with ``linear_attn_config`` names its
  layers there: ``kda_layers`` and ``full_attn_layers``, two lists of layer
  numbers counted from 1, with the mixer's ``num_heads``, ``head_dim`` and
  ``short_conv_kernel_size`` beside them.
- ``norm_form`` ``"plain"`` (default): every RMSNorm scales by ``w`` from 1;
  ``"zero_centered"``: the layers' two norms, the final norm and the q / k
  norms scale by ``1 + w`` with ``w`` from 0 (``layers.rms_norm``).
- feed-forward: the first ``num_dense_layers`` layers (also spelt
  ``first_k_dense_replace``; default 0), the
  layers ``mlp_layer_types`` calls ``"dense"`` or ``mlp_only_layers`` lists,
  and every
  layer of a config without experts (``num_local_experts: 0``), a dense
  SwiGLU ``W_down (silu(W_gate x) * (W_up x))`` of width
  ``intermediate_size`` (``shared_intermediate_size`` where the config has
  that key); the others ``layers.moe_ffn`` of width
  ``moe_intermediate_size`` (``intermediate_size`` where the config has no
  such key). ``router_scoring`` ``"softmax"`` (default): float32 router,
  softmax then top-k with the values used as they are, or over their sum
  under ``norm_topk_prob``, times ``routed_scaling_factor`` (also spelt
  ``moe_routed_scaling_factor``); ``"sigmoid"``:
  sigmoid scores, chosen by score + bias under ``use_expert_bias``, weighed
  by the score over the chosen scores' sum under ``norm_topk_prob``, times
  ``routed_scaling_factor``. ``topk_method: "noaux_tc"`` (DeepSeek-V3's
  spelling) is sigmoid scoring chosen by score + bias; ``n_group`` /
  ``topk_group`` must be 1 (no group limit). Kimi's spellings read alike:
  ``num_experts_per_token`` (top-k), ``moe_renormalize``
  (``norm_topk_prob``), ``moe_router_activation_func`` (``"sigmoid"``:
  sigmoid scores chosen by score + bias), ``num_expert_group`` (with
  ``topk_group`` 1: ``use_grouped_topk`` over one group is no limit),
  ``moe_layer_freq`` (must be 1).
  ``shared_expert_intermediate_size`` (or ``n_shared_experts: 1``, also
  spelt ``num_shared_experts``, whose width is ``moe_intermediate_size``):
  one
  shared expert, a dense SwiGLU of that width over every token, added
  beside the routed experts' sum: ungated, or under ``shared_expert_gate``
  times ``sigmoid(w_s . x)``, one gate a token.
- one chip's share of a layer that several chips hold: ``num_experts``
  (also spelt ``n_routed_experts``) is the experts held here,
  ``num_experts_routed`` (default: the same) the
  router's width and ``first_expert_held`` (default 0) the first held; the
  layer's output is the held experts' part (``layers.moe_ffn``), plus the
  shared expert where there is one (every chip computes it alike).
  ``moe_row_budget``: the sorted rows such a layer keeps (``layers.moe_ffn``'s
  ``row_budget``; rows beyond it are dropped and counted).
  ``moe_matmul_tiling``: the (m, k, n) tile of the grouped products'
  kernels where a configuration states one (``layers.moe_ffn``'s
  ``matmul_tiling``; default ``ops.decoder_ops.GMM_TILING``). A sliced
  vocabulary is a smaller ``vocab_size``.
- the deployment itself, where one host holds whole layers:
  ``expert_axis`` names the mesh axis every expert layer's experts are split
  over, with the exchange run (``layers.moe_ffn``'s ``expert_axis``: the
  rows go to the chips that hold their experts and the results come back;
  ``moe_row_budget`` is then a chip's receive buffer), and ``vocab_axis`` the
  axis the embedding table's rows and the head's columns are split over
  (declared on the parameters, ``Variable.declare_sharding``; the lookup,
  the head's product and the cross-entropy over the whole vocabulary are
  GSPMD's to partition). Both take effect under a
  ``DistributedStrategy`` whose mesh has the axis; on one device the model
  is the one without them.
- ``use_sliding_window: true`` (Qwen's key set, Mellum's): read with
  ``max_window_layers: 0`` and ``layer_types``, which names the
  ``sliding_attention`` layers; a window only on those.
- ``embedding_multiplier`` times the looked-up rows, and the logits over
  ``logits_scaling`` (both default 1); under ``tie_word_embeddings`` the
  logits are ``x tok_emb^T`` from the one float32 table, cast to ``dtype``
  for the product.
- loss: mean next-token cross-entropy, + ``router_aux_loss_coef`` x the
  load-balancing loss (experts x sum over experts of the share of
  assignments an expert received x its mean router probability, a layer,
  averaged over the layers) + ``router_z_loss_coef`` x the mean of
  logsumexp(router logits)^2 (likewise) where the config carries those keys
  (softmax scoring).
- ``num_nextn_predict_layers: 1``: a multi-token-prediction module after
  the trunk (``prediction_module``, below) and its loss: the cross-entropy
  of the token after the next through the trunk's table and head, times
  ``mtp_loss_weight`` (default 0.3), added to the trunk's.
- ``balance_experts``: the selection bias' update from the step's load,
  appended by the caller after ``minimize`` (the module's layer too).

Models through it: OLMoE-1B-7B (Muennighoff et al., arXiv:2409.02060; HF
``modeling_olmoe.py``), LFM2-8B-A1B (HF ``modeling_lfm2_moe.py``),
granite-4.0-h-micro (HF ``modeling_granitemoehybrid.py``; the scan: Dao &
Gu, arXiv:2405.21060), Laguna-S-2.1 (its ``config.json``; YaRN: Peng et
al., arXiv:2309.00071), Qwen3-Next-80B-A3B (HF ``modeling_qwen3_next.py``;
the delta rule: Yang et al., arXiv:2412.06464), GLM-4.7-Flash (its
``config.json``, ``model_type: glm4_moe_lite``; latent attention:
DeepSeek-V2, arXiv:2405.04434; routing and the prediction module:
DeepSeek-V3, arXiv:2412.19437), Kimi-Linear-48B-A3B-Instruct (its
``config.json``, ``model_type: kimi_linear``; HF ``modeling_kimi.py`` of
that repository; Kimi Delta Attention: the Kimi Linear report,
arXiv:2510.26692) and Mellum2-12B-A2.5B-Instruct (its ``config.json``,
``model_type: mellum``: Qwen3-MoE's key set with ``layer_types`` and rotary
parameters by layer type), Ouro-2.6B (its ``config.json``,
``model_type: ouro``; the LoopLM report, arXiv:2510.25741) and
Xing4.0-29B-A4B (its ``config.json``, ``model_type: xing4_0``; the mHC
report, arXiv:2512.24880; YaRN inside latent attention: HF
``DeepseekV3Attention``).

Dtypes follow ``models/bert.py``: the embedding table is float32 whatever
``dtype`` says, activations are cast to ``dtype`` right after the lookup,
weights are created in ``dtype`` (a Mamba, DeltaNet or KDA mixer's ``A_log``,
``D`` and ``dt_bias``, one number a head -- a KDA mixer's ``dt_bias`` one a
key channel --, in float32); RMSNorm (latent
attention's two among them), the router,
the short convolution, the scan's and the delta rule's decays and state, the
delta rule's l2 norms, the hyper-connections' coefficients, reads and writes
and every softmax compute in float32 inside their ops;
the logits are cast up for the loss.
"""
from __future__ import annotations

import math

from .. import layers
from ..framework import default_startup_program
from ..initializer import Constant, Initializer, Normal, Uniform
from ..layer_helper import ParamAttr

_REQUIRED = {"hidden_act": "silu", "attention_bias": False,
             "clip_qkv": None, "conv_bias": False,
             "mamba_proj_bias": False, "mamba_n_groups": 1,
             "normalization_function": "rmsnorm",
             "moe_apply_router_weight_on_input": False,
             "moe_router_logit_softcapping": 0, "decoder_sparse_step": 1,
             "moe_layer_freq": 1}
_OPERATORS = ("full_attention", "sliding_attention", "conv", "mamba",
              "linear_attention", "kda")
_ATTENTION = ("full_attention", "sliding_attention")


def _check(cfg: dict) -> None:
    if cfg.get("kv_lora_rank"):
        _check_latent(cfg)
    elif cfg.get("rope_scaling") is not None:
        raise NotImplementedError(
            f"decoder_lm: rope_scaling={cfg['rope_scaling']!r} is not built "
            f"yet (only None; a YaRN dict beside kv_lora_rank, inside latent "
            f"attention; by layer type under rope_parameters)")
    _check_loop(cfg)
    _check_streams(cfg)
    for key, want in _REQUIRED.items():
        if cfg.get(key, want) != want:
            raise NotImplementedError(
                f"decoder_lm: {key}={cfg[key]!r} is not built yet "
                f"(only {want!r})")
    kinds = _layer_types(cfg)
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every one of the "
                         "num_hidden_layers layers")
    for kind in kinds:
        if kind not in _OPERATORS:
            raise NotImplementedError(
                f"decoder_lm: layer type {kind!r} is not built yet (only "
                f"{_OPERATORS}: no chunked attention)")
    if "sliding_attention" in kinds and not cfg.get("sliding_window"):
        raise ValueError("decoder_lm: sliding_attention layers need "
                         "sliding_window")
    if cfg.get("use_sliding_window") and (
            "layer_types" not in cfg or cfg.get("max_window_layers", 0)):
        # Qwen's own rule (the layers from max_window_layers on slide) is
        # not guessed at: the config says which layers slide
        raise NotImplementedError(
            "decoder_lm: use_sliding_window=True is built where layer_types "
            "names the sliding_attention layers and max_window_layers is 0 "
            "(every layer takes the type layer_types gives it)")
    if cfg.get("expert_axis") and cfg.get(
            "num_experts_routed", _held(cfg)) != _held(cfg):
        raise ValueError("decoder_lm: expert_axis splits all of a layer's "
                         "experts over a mesh axis (the exchange is run); "
                         "num_experts_routed is one chip's share without it")
    if "kda" in kinds:
        missing = [k for k in ("num_heads", "head_dim",
                               "short_conv_kernel_size")
                   if k not in cfg.get("linear_attn_config", {})]
        if missing:
            raise ValueError(f"decoder_lm: kda layers need "
                             f"linear_attn_config{missing}")
    if "linear_attention" in kinds:
        if cfg["linear_num_value_heads"] % cfg["linear_num_key_heads"]:
            raise ValueError("linear_num_value_heads must be a multiple of "
                             "linear_num_key_heads")
        if cfg.get("linear_conv_bias"):
            raise NotImplementedError(
                "decoder_lm: linear_conv_bias is not built yet (a Gated "
                "DeltaNet mixer's filter has no bias)")
    if cfg.get("norm_form", "plain") not in ("plain", "zero_centered"):
        raise NotImplementedError(
            f"decoder_lm: norm_form={cfg['norm_form']!r} is not built yet "
            f"(only 'plain' and 'zero_centered')")
    if cfg.get("attn_output_gate") and cfg.get("gating", "none") != "none":
        raise NotImplementedError(
            "decoder_lm: attn_output_gate (one gate a token, head and "
            "channel) beside gating='per-head' is not built: one gate an "
            "attention layer")
    if cfg.get("shared_expert_gate") and not cfg.get(
            "shared_expert_intermediate_size"):
        raise ValueError("decoder_lm: shared_expert_gate needs a shared "
                         "expert (shared_expert_intermediate_size)")
    if "mamba" in kinds and (
            cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            != cfg["mamba_expand"] * cfg["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head must equal "
                         "mamba_expand x hidden_size")
    per_layer = cfg.get("num_attention_heads_per_layer")
    if per_layer is not None and len(per_layer) != len(kinds):
        raise ValueError("num_attention_heads_per_layer must give every one "
                         "of the num_hidden_layers layers a head count")
    for i, kind in enumerate(kinds):
        if kind not in _ATTENTION or cfg.get("kv_lora_rank"):
            continue
        if _heads(cfg, i) % _kv_heads(cfg) or (
                "head_dim" not in cfg and cfg["hidden_size"] % _heads(cfg, i)):
            raise ValueError(
                "the head count must be a multiple of num_key_value_heads "
                "and, without head_dim, divide hidden_size")
        kind_of_rope = _rope(cfg, kind).get("rope_type", "default")
        if kind_of_rope not in ("default", "yarn"):
            raise NotImplementedError(
                f"decoder_lm: rope_type={kind_of_rope!r} is not built yet "
                f"(only 'default' and 'yarn')")
    if cfg.get("gating", "none") not in ("none", "per-head") or any(
            g != "per_head" for g in cfg.get("gating_types", [])):
        raise NotImplementedError(
            f"decoder_lm: gating={cfg.get('gating')!r} with gating_types "
            f"other than per_head is not built yet (only 'per-head')")
    if cfg.get("qk_norm", "projection") not in ("projection", "head",
                                                 "none"):
        raise NotImplementedError(
            f"decoder_lm: qk_norm={cfg['qk_norm']!r} is not built yet")
    if cfg.get("position_embedding_type", "rope") not in ("rope", "nope"):
        raise NotImplementedError(
            f"decoder_lm: position_embedding_type="
            f"{cfg['position_embedding_type']!r} is not built yet (rotary "
            f"or none)")
    if (_shared_count(cfg) > 1
            or cfg.get("num_local_experts")):
        raise NotImplementedError(
            "decoder_lm: of shared experts only one a layer is built "
            "(shared_expert_intermediate_size): not n_shared_experts / "
            "num_shared_experts above 1, nor routed experts beside a shared "
            "feed-forward under num_local_experts")
    if _shared_count(cfg) and not _shared_width(cfg):
        raise ValueError("decoder_lm: a shared expert needs "
                         "shared_expert_intermediate_size, or "
                         "moe_intermediate_size beside n_shared_experts")
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        raise NotImplementedError(
            f"decoder_lm: topk_method={cfg['topk_method']!r} is not built "
            f"yet (only 'noaux_tc': sigmoid scores chosen by score + bias)")
    if cfg.get("use_grouped_topk", True) and max(
            cfg.get("n_group") or 1, cfg.get("num_expert_group") or 1,
            cfg.get("topk_group") or 1) > 1:
        raise NotImplementedError(
            "decoder_lm: group-limited routing (n_group / num_expert_group "
            "/ topk_group above 1) is not built yet: the experts are chosen "
            "among all of them (use_grouped_topk over one group is that)")
    if cfg.get("moe_router_activation_func", "sigmoid") not in (
            "sigmoid", "softmax"):
        raise NotImplementedError(
            f"decoder_lm: moe_router_activation_func="
            f"{cfg['moe_router_activation_func']!r} is not built yet "
            f"(sigmoid or softmax)")
    if (cfg.get("num_nextn_predict_layers") or 0) > 1:
        raise NotImplementedError(
            "decoder_lm: more than one multi-token-prediction module "
            "(num_nextn_predict_layers above 1) is not built yet")
    sigmoid = _scoring(cfg) == "sigmoid"
    if not sigmoid and cfg.get("use_expert_bias"):
        raise NotImplementedError(
            "decoder_lm: use_expert_bias is built for "
            "router_scoring='sigmoid' only")
    if cfg.get("moe_row_budget") and not cfg.get("expert_axis") and cfg.get(
            "num_experts_routed", _held(cfg)) == _held(cfg):
        raise ValueError("decoder_lm: moe_row_budget is for a layer that "
                         "holds a part of its experts (num_experts_routed) "
                         "or exchanges its rows (expert_axis)")
    if sigmoid and ("router_aux_loss_coef" in cfg
                    or "router_z_loss_coef" in cfg):
        raise NotImplementedError(
            "decoder_lm: the router losses are built for softmax scoring "
            "only")


def _check_loop(cfg: dict) -> None:
    """What a looped config (``total_ut_steps``) asks for and ``build`` does
    not build, and the keys beside it."""
    if cfg.get("norm_placement", "pre") not in ("pre", "sandwich"):
        raise NotImplementedError(
            f"decoder_lm: norm_placement={cfg['norm_placement']!r} is not "
            f"built yet (only 'pre' and 'sandwich')")
    steps = cfg.get("total_ut_steps", 1)
    if not isinstance(steps, int) or steps < 1:
        raise ValueError(f"decoder_lm: total_ut_steps={steps!r} must be a "
                         f"whole number of passes, at least 1")
    # early_exit_threshold is decoding's: a token leaves the loop after the
    # first pass at which the exit distribution's running sum reaches it
    # (1: never before the last pass). The training programs built here run
    # every pass and never read it, whatever its value; the decode loop
    # that reads it is to raise here for what it does not build
    if steps == 1:
        return
    if not all(_is_dense(cfg, i) for i in range(cfg["num_hidden_layers"])):
        raise NotImplementedError(
            "decoder_lm: expert layers under total_ut_steps above 1 are not "
            "built yet (the router's variables and losses of a layer that "
            "runs several times a step)")
    if cfg.get("num_nextn_predict_layers"):
        raise NotImplementedError(
            "decoder_lm: num_nextn_predict_layers under total_ut_steps "
            "above 1 is not built yet (a prediction module after a looped "
            "trunk)")
    if cfg.get("expert_axis") or cfg.get("vocab_axis"):
        raise NotImplementedError(
            "decoder_lm: expert_axis / vocab_axis under total_ut_steps "
            "above 1 is not built yet (the loop op under a mesh)")


def _streams(cfg: dict) -> int:
    """``hc_mult``: the residual streams a token (absent: 1)."""
    return cfg.get("hc_mult") or 1


def _check_streams(cfg: dict) -> None:
    """What a config with ``hc_mult`` above 1 (hyper-connections) asks for
    and ``build`` does not build."""
    n = _streams(cfg)
    if not isinstance(n, int) or n < 1:
        raise ValueError(f"decoder_lm: hc_mult={n!r} must be a whole number "
                         f"of residual streams, at least 1")
    if n == 1:
        return
    missing = [k for k in ("hc_sinkhorn_iters", "hc_eps",
                           "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
               if k not in cfg]
    if missing:
        raise ValueError(f"decoder_lm: hc_mult={n} needs {missing}")
    for key, without, why in (
            ("total_ut_steps", 1, "hyper-connections inside the scan op"),
            ("num_nextn_predict_layers", 0,
             "a prediction module over a multi-stream trunk: how it reads "
             "the streams is in no key of the config"),
            ("expert_axis", None, "hyper-connections under a mesh"),
            ("vocab_axis", None, "hyper-connections under a mesh")):
        if (cfg.get(key) or without) != without:
            raise NotImplementedError(
                f"decoder_lm: {key}={cfg[key]!r} beside hc_mult={n} is not "
                f"built yet ({why})")
    if cfg.get("norm_placement", "pre") != "pre" or cfg.get(
            "residual_multiplier", 1.0) != 1.0:
        raise NotImplementedError(
            "decoder_lm: norm_placement='sandwich' / residual_multiplier "
            f"beside hc_mult={n} is not built yet (the write side is H_res X "
            "+ H_post^T y, as the mHC report has it)")


def _check_latent(cfg: dict) -> None:
    """What a config with ``kv_lora_rank`` (latent attention) asks for and
    ``latent_attention`` does not build."""
    scaling = cfg.get("rope_scaling")
    if scaling is not None and (
            not isinstance(scaling, dict)
            or scaling.get("rope_type", scaling.get("type")) != "yarn"
            or cfg.get("mla_use_nope")):
        raise NotImplementedError(
            f"decoder_lm: rope_scaling inside latent attention is built for "
            f"null and for a YaRN dict over rotary parts that are rotated "
            f"(its mscale on the softmax scale), not {scaling!r}")
    if cfg.get("partial_rotary_factor", 1) != 1:
        raise NotImplementedError(
            "decoder_lm: partial_rotary_factor other than 1 inside latent "
            "attention is not built yet (the rotary head is rotated whole)")
    if "sliding_attention" in _layer_types(cfg):
        raise NotImplementedError(
            "decoder_lm: latent attention (kv_lora_rank) is built for "
            "full_attention layers only (beside conv, mamba and "
            "linear-attention layers): no window inside it")


def _held(cfg: dict):
    """The experts a layer holds here: ``num_experts``, in DeepSeek-style
    configs ``n_routed_experts``; None for a config without experts."""
    return cfg.get("num_experts", cfg.get("n_routed_experts"))


def _scoring(cfg: dict) -> str:
    """``router_scoring`` (also spelt ``scoring_func``, DeepSeek-V3's, and
    ``moe_router_activation_func``, Kimi's); a config with ``topk_method:
    "noaux_tc"`` scores by sigmoid and chooses by score + bias."""
    return cfg.get("router_scoring", cfg.get("scoring_func", cfg.get(
        "moe_router_activation_func",
        "sigmoid" if "topk_method" in cfg else "softmax")))


def _bias_chosen(cfg: dict) -> bool:
    """Whether the experts are chosen by score + a selection bias:
    ``use_expert_bias``; by default under ``topk_method`` (DeepSeek-V3's)
    and under ``moe_router_activation_func: "sigmoid"`` (Kimi's
    ``e_score_correction_bias``)."""
    return bool(cfg.get("use_expert_bias", "topk_method" in cfg or cfg.get(
        "moe_router_activation_func") == "sigmoid"))


def _shared_count(cfg: dict) -> int:
    """``n_shared_experts``, in Kimi's configs ``num_shared_experts``."""
    return max(cfg.get("n_shared_experts") or 0,
               cfg.get("num_shared_experts") or 0)


def _shared_width(cfg: dict):
    """The one shared expert's width: ``shared_expert_intermediate_size``,
    or ``moe_intermediate_size`` x ``n_shared_experts`` (also spelt
    ``num_shared_experts``); None for none."""
    return cfg.get("shared_expert_intermediate_size") or (
        cfg.get("moe_intermediate_size", 0) * _shared_count(cfg)) or None


def _top_k(cfg: dict) -> int:
    """``num_experts_per_tok``, in Kimi's configs ``num_experts_per_token``."""
    return cfg["num_experts_per_tok"] if "num_experts_per_tok" in cfg \
        else cfg["num_experts_per_token"]


def _layer_types(cfg: dict) -> list:
    kinds = cfg.get("layer_types")
    linear = cfg.get("linear_attn_config")
    if not kinds and linear and "kda_layers" in linear:
        # Kimi Linear's: two lists of layer numbers counted from 1
        kda, full = linear["kda_layers"], linear.get("full_attn_layers", [])
        if sorted(kda + full) != list(range(1, cfg["num_hidden_layers"] + 1)):
            raise ValueError(
                "decoder_lm: linear_attn_config's kda_layers and "
                "full_attn_layers must name every layer from 1 to "
                "num_hidden_layers once")
        return ["kda" if i + 1 in kda else "full_attention"
                for i in range(cfg["num_hidden_layers"])]
    if not kinds and cfg.get("full_attention_interval"):
        every = cfg["full_attention_interval"]      # HF qwen3_next's rule
        kinds = ["linear_attention" if (i + 1) % every else "full_attention"
                 for i in range(cfg["num_hidden_layers"])]
    kinds = kinds or ["full_attention"] * cfg["num_hidden_layers"]
    return ["full_attention" if k == "attention" else k for k in kinds]


def _kv_heads(cfg: dict) -> int:
    return cfg.get("num_key_value_heads") or cfg["num_attention_heads"]


def _heads(cfg: dict, layer: int) -> int:
    per_layer = cfg.get("num_attention_heads_per_layer")
    return per_layer[layer] if per_layer else cfg["num_attention_heads"]


def _rope(cfg: dict, kind: str) -> dict:
    """The rotary parameters of an attention layer of type ``kind``, in
    HF's keys: ``rope_parameters[kind]`` where the config gives them by
    layer type, else ``rope_theta`` over the whole head."""
    by_type = cfg.get("rope_parameters")
    if by_type is None:
        return {"rope_theta": cfg.get("rope_theta", 10000.0),
                "partial_rotary_factor": cfg.get("partial_rotary_factor", 1)}
    return by_type[kind]


def _is_dense(cfg: dict, layer: int) -> bool:
    kinds = cfg.get("mlp_layer_types")
    return bool(layer < cfg.get("num_dense_layers",
                                cfg.get("first_k_dense_replace", 0))
                or _held(cfg) is None
                or (kinds and kinds[layer] == "dense")
                or layer in cfg.get("mlp_only_layers", ()))


def _eps(cfg: dict) -> float:
    return cfg["rms_norm_eps"] if "rms_norm_eps" in cfg else cfg["norm_eps"]


def _attr(name: str, sharding=None) -> ParamAttr:
    return ParamAttr(name=name, initializer=Normal(0.0, 0.02),
                     sharding=sharding)


def _linear(x, size: int, name: str, sharding=None):
    return layers.fc(x, size, param_attr=_attr(name, sharding),
                     bias_attr=False)


def _embed(ids, cfg: dict):
    """The rows of the one float32 table ``tok_emb`` for ``ids``, times
    ``embedding_multiplier``, cast to the config's ``dtype``."""
    axis = cfg.get("vocab_axis")    # the table's rows split over that axis
    x = layers.embedding(ids, [cfg["vocab_size"], cfg["hidden_size"]],
                         dtype="float32", param_attr=_attr(
                             "tok_emb", (axis, None) if axis else None))
    if cfg.get("embedding_multiplier", 1) != 1:
        x = layers.scale(x, float(cfg["embedding_multiplier"]))
    if cfg.get("dtype", "float32") != "float32":
        x = layers.cast(x, cfg["dtype"])
    return x


def _norm(x, cfg: dict, name: str):
    """The config's RMSNorm of ``x`` with the scale ``name``: ``* w`` from
    1, or under ``norm_form: "zero_centered"`` ``* (1 + w)`` from 0."""
    return layers.rms_norm(
        x, _eps(cfg), ParamAttr(name=name),
        zero_centered=cfg.get("norm_form", "plain") == "zero_centered")


def attention(x, cfg: dict, batch: int, seq: int, name: str, layer: int = 0,
              kind: str = "full_attention"):
    """Causal self-attention of layer ``layer`` over tokens ``x [batch *
    seq, H]``, with ``num_key_value_heads`` key/value heads; under ``kind``
    ``"sliding_attention"`` within a window of ``sliding_window`` keys."""
    H, heads, kv_heads = cfg["hidden_size"], _heads(cfg, layer), _kv_heads(cfg)
    d = cfg.get("head_dim") or H // heads
    norm = cfg.get("qk_norm", "projection")
    by_head, whole = norm == "head", norm == "projection"
    rotary = cfg.get("position_embedding_type", "rope") == "rope"
    rope = _rope(cfg, kind)

    def heads_of(t, n, norm_w=None, positions=rotary):
        t = layers.reshape(t, [batch, seq, n, d])   # [B*S, n*d] -> [B, n, S, d]
        if norm_w:
            t = _norm(t, cfg, norm_w)
        t = layers.transpose(t, [0, 2, 1, 3])
        if not positions:
            return t
        return layers.rotary_embedding(
            t, rope["rope_theta"],
            rotary_dim=int(d * rope.get("partial_rotary_factor", 1)),
            scaling=rope)

    gate = None
    if cfg.get("attn_output_gate"):     # a head's q, then its gate
        q, gate = layers.split(layers.reshape(
            _linear(x, heads * 2 * d, name + "_q_w"),
            [batch, seq, heads, 2 * d]), 2, dim=-1)
        gate = layers.reshape(gate, [batch * seq, heads * d])
    else:
        q = _linear(x, heads * d, name + "_q_w")
    if whole:
        q = _norm(q, cfg, name + "_q_norm_w")
    k = _linear(x, kv_heads * d, name + "_k_w")
    if whole:
        k = _norm(k, cfg, name + "_k_norm_w")
    v = _linear(x, kv_heads * d, name + "_v_w")
    ctx = layers.fused_attention(
        heads_of(q, heads, name + "_q_norm_w" if by_head else None),
        heads_of(k, kv_heads, name + "_k_norm_w" if by_head else None),
        heads_of(v, kv_heads, positions=False), causal=True,
        scale=float(cfg.get("attention_multiplier", 1.0 / math.sqrt(d))),
        impl=cfg.get("attention_impl", "auto"),
        window=cfg["sliding_window"] if kind == "sliding_attention" else None)
    if cfg.get("gating", "none") == "per-head":
        ctx = layers.attention_gate(ctx, _linear(x, heads, name + "_g_w"))
    elif gate is not None:
        ctx = layers.attention_gate(ctx, gate)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [batch * seq, heads * d])
    return _linear(ctx, H, name + "_o_w")


def latent_attention(x, cfg: dict, batch: int, seq: int, name: str):
    """Causal multi-head latent attention over tokens ``x [batch * seq, H]``
    (DeepSeek-V2, arXiv:2405.04434, section 2.1; HF ``DeepseekV3Attention``,
    ``KimiMLAAttention``) with ``h = num_attention_heads`` heads of
    ``qk_nope_head_dim`` + ``qk_rope_head_dim`` for q and k and
    ``v_head_dim`` for v: ``c_q = norm(W_qa x)`` (``q_lora_rank``), a head's
    ``[q_n | q_r]`` from ``W_qb c_q`` -- under ``q_lora_rank: null`` from
    one projection ``W_q x`` --; ``[c_kv | k_r] = W_kva x`` (``kv_lora_rank``
    + ``qk_rope_head_dim``), a head's ``[k_n | v]`` from ``W_kvb
    norm(c_kv)``; ``q_r`` and the one key head ``k_r`` rotated at
    ``rope_theta`` -- not under ``mla_use_nope`` (Kimi Linear: the linear
    layers carry the positions; ``rope_theta`` is then not read) -- and
    ``k_r`` shared by the heads (``layers.latent_qkv``: the columns of
    ``W_qb`` are every head's q_n, then every head's q_r, those of ``W_kvb``
    every head's k_n, then every head's v, a permutation of HF's interleave
    by head); causal ``fused_attention`` at 1 / sqrt(the q / k head's
    width), whose output has v's width; ``W_o`` over the heads' outputs. A q
    / k head wider than one lane tile of 128 and no whole number of them is
    written up to the next whole tile, zero columns behind its two parts
    (192 -> 256: a zero column adds nothing to a score, the MXU's passes
    over 192 are those over 256, and the flash kernels read whole tiles: 13%
    faster on the chip, PR 51). Both latent norms are the config's RMSNorm.
    Under ``rope_scaling`` (a YaRN dict; HF ``DeepseekV3RotaryEmbedding`` /
    ``DeepseekV3Attention``): ``q_r`` and ``k_r`` turn at YaRN's blended
    frequencies over the rotary head (``ops.decoder_ops.yarn_inv_freq``), cos
    and sin times ``m(mscale) / m(mscale_all_dim)``, and the softmax scale
    times ``m(mscale_all_dim)^2``, ``m(s) = 0.1 s ln(factor) + 1``."""
    heads = cfg["num_attention_heads"]
    d_n, d_r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r_kv, d_v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    if cfg.get("q_lora_rank") is None:
        q = _linear(x, heads * (d_n + d_r), name + "_q_w")
    else:
        c_q = _norm(_linear(x, cfg["q_lora_rank"], name + "_q_a_w"), cfg,
                    name + "_q_a_norm_w")
        q = _linear(c_q, heads * (d_n + d_r), name + "_q_b_w")
    c_kv, k_r = layers.split(_linear(x, r_kv + d_r, name + "_kv_a_w"),
                             [r_kv, d_r], dim=-1)
    kv = _linear(_norm(c_kv, cfg, name + "_kv_a_norm_w"),
                 heads * (d_n + d_v), name + "_kv_b_w")
    d = d_n + d_r
    scaling = cfg.get("rope_scaling")
    q, k, v = layers.latent_qkv(q, kv, k_r, batch, seq, heads, d_n, d_r,
                                theta=cfg.get("rope_theta", 10000.0),
                                rotate=not cfg.get("mla_use_nope", False),
                                value_dim=d_v,
                                head_dim=-(-d // 128) * 128 if d > 128 else d,
                                scaling=scaling)
    scale = float(cfg.get("attention_multiplier", 1.0 / math.sqrt(d)))
    if scaling and scaling.get("mscale_all_dim") and scaling["factor"] > 1:
        # HF DeepseekV3Attention: yarn_get_mscale(factor, mscale_all_dim)^2
        scale *= (0.1 * scaling["mscale_all_dim"]
                  * math.log(scaling["factor"]) + 1.0) ** 2
    ctx = layers.fused_attention(q, k, v, causal=True, impl="auto",
                                 scale=scale)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [batch * seq, heads * d_v])
    return _linear(ctx, cfg["hidden_size"], name + "_o_w")


def short_conv(x, cfg: dict, seq: int, name: str):
    """The gated short-convolution operator over ``x [batch * seq, H]``."""
    H = cfg["hidden_size"]
    mixed = layers.short_conv(_linear(x, 3 * H, name + "_in_w"), seq,
                              cfg["conv_L_cache"], _attr(name + "_w"))
    return _linear(mixed, H, name + "_out_w")


class _Drawn(Initializer):
    """A uniform draw in ``[low, high]`` followed by unary ops, in place, in
    the startup program: the Mamba mixer's per-head parameters, whose usual
    start is a function of a uniform draw (``mamba``)."""

    def __init__(self, low: float, high: float, then: list):
        self.low, self.high, self.then = low, high, then

    def __call__(self, var, block=None):
        block = block or default_startup_program().global_block()
        block.create_var(var.name, var.shape, var.dtype, persistable=True)
        block.append_op("uniform_random", outputs={"Out": [var.name]},
                        attrs={"shape": list(var.shape), "dtype": var.dtype,
                               "min": self.low, "max": self.high, "seed": 0})
        for kind, attrs in self.then:
            block.append_op(kind, inputs={"X": [var.name]},
                            outputs={"Out": [var.name]}, attrs=attrs)


def _conv_attr(name: str, taps: int) -> ParamAttr:
    """A depthwise Conv1d's usual start: uniform in +-1/sqrt(taps)."""
    return ParamAttr(name=name, initializer=Uniform(
        -1.0 / math.sqrt(taps), 1.0 / math.sqrt(taps)))


def _per_head(name: str, heads: int, initializer):
    return layers.create_parameter([heads], "float32", name=name,
                                   default_initializer=initializer)


def _dt_bias_and_a_log(name: str, heads: int, dts: int = None):
    """A recurrent mixer's float32 per-head ``<name>_dt_bias`` (``dts`` of
    them where given: Kimi Delta Attention's, one a key channel) and
    ``<name>_A_log`` as mamba_ssm's ``Mamba2`` starts them: dt log-uniform
    in [1e-3, 1e-1] through the inverse softplus, A uniform in [1, 16]."""
    dt_bias = _per_head(name + "_dt_bias", dts or heads, _Drawn(
        math.log(1e-3), math.log(1e-1),
        [("exp", {}), ("exp", {}), ("scale", {"scale": 1.0, "bias": -1.0}),
         ("log", {})]))                 # log(exp(dt) - 1), dt = exp(draw)
    return dt_bias, _per_head(name + "_A_log", heads,
                              _Drawn(1.0, 16.0, [("log", {})]))


def mamba(x, cfg: dict, batch: int, seq: int, name: str):
    """The Mamba-2 mixer over ``x [batch * seq, H]`` (HF
    ``GraniteMoeHybridMambaLayer``; Dao & Gu, arXiv:2405.21060): ``[z | xBC |
    dt] = W_in x``; ``xBC = silu(conv(xBC) + b)``, a causal depthwise filter
    of ``mamba_d_conv`` taps; ``[x | B | C] = xBC`` with B and C (``mamba_d_
    state`` wide) shared by the heads; ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(A_log)``; per head ``h_t = exp(dt_t A) h_{t-1} + B_t (x) dt_t x_t``,
    ``y_t = C_t h_t + D x_t`` (``layers.ssd_scan``, in chunks of ``mamba_
    chunk_size``, lowered as ``ssd_scan_impl`` says, default ``auto``);
    ``W_out rmsnorm(y * silu(z))``, the norm over all heads' values.
    ``A_log``, ``D`` and ``dt_bias`` are float32 and start as mamba_ssm's
    ``Mamba2`` starts them: A uniform in [1, 16], D one, dt log-uniform in
    [1e-3, 1e-1] through the inverse softplus (HF's constructor writes
    ``log(1..heads)`` and ones, which a checkpoint overwrites: with dt near
    1.3 and A up to 64 no state would outlive a few positions); the filter
    and its bias uniform in +-1/sqrt(taps), a depthwise Conv1d's start in
    both (at the projections' std of 0.02 x, B and C would be a hundredth of
    z and the scan's part of y lost under ``D x``)."""
    heads, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    inner = heads * p
    z, xbc, dt = layers.split(
        _linear(x, 2 * inner + 2 * n + heads, name + "_in_w"),
        [inner, inner + 2 * n, heads], dim=-1)
    taps = cfg["mamba_d_conv"]
    xbc = layers.short_conv(
        xbc, seq, taps, _conv_attr(name + "_conv_w", taps),
        bias_attr=(_conv_attr(name + "_conv_b", taps)
                   if cfg.get("mamba_conv_bias") else False),
        gated=False, activation="silu")
    xs, b, c = layers.split(xbc, [inner, n, n], dim=-1)
    dt_bias, a_log = _dt_bias_and_a_log(name, heads)
    d = _per_head(name + "_D", heads, Constant(1.0))
    dt = layers.softplus(layers.elementwise_add(
        layers.cast(dt, "float32"), dt_bias))
    y = layers.ssd_scan(
        layers.reshape(xs, [batch, seq, heads, p]),
        layers.reshape(dt, [batch, seq, heads]),
        layers.scale(layers.exp(a_log), -1.0),
        layers.reshape(b, [batch, seq, n]), layers.reshape(c, [batch, seq, n]),
        d, chunk=cfg["mamba_chunk_size"], impl=cfg.get("ssd_scan_impl", "auto"))
    y = layers.rms_norm(
        layers.swiglu(z, layers.reshape(y, [batch * seq, inner])), _eps(cfg),
        ParamAttr(name=name + "_gated_norm_w"))
    return _linear(y, cfg["hidden_size"], name + "_out_w")


def delta_net(x, cfg: dict, batch: int, seq: int, name: str):
    """The Gated DeltaNet mixer over ``x [batch * seq, H]`` (HF
    ``Qwen3NextGatedDeltaNet``; Yang et al., arXiv:2412.06464), with ``n_k =
    linear_num_key_heads`` heads of ``linear_key_head_dim`` and ``n_v =
    linear_num_value_heads`` of ``linear_value_head_dim``: ``[q | k | v | z]
    = W_in x`` and ``[b | alpha] = W_ba x`` (contiguous columns: a
    permutation of HF's interleave by key head); ``[q | k | v] = silu(conv([q
    | k | v]))``, one causal depthwise filter of ``linear_conv_kernel_dim``
    taps without a bias; a value head each, ``beta = sigmoid(b)`` and ``g =
    -exp(A_log) softplus(alpha + dt_bias)`` in float32; the gated delta rule
    over unit q and k (``layers.gated_delta_rule_packed``: the conv's output
    whole, no q, k or v cut out of it; in chunks of ``delta_chunk_size``,
    default 64, lowered as ``delta_rule_impl`` says, default ``auto``); ``W_out (rmsnorm(o) * silu(z))``, the norm over a head's
    values with one plain scale of head size shared by the heads, before the
    gate (a Mamba mixer gates first), both in one ``rms_norm`` op given the
    gate. ``A_log`` and ``dt_bias`` are float32
    and start as ``mamba``'s do (HF's constructor writes ``dt_bias = 1``,
    which a checkpoint overwrites: no state would outlive a few positions),
    the filter as a depthwise Conv1d's."""
    n_k, d_k = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    n_v, d_v = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    keys, values = n_k * d_k, n_v * d_v
    qkv, z = layers.split(
        _linear(x, 2 * keys + 2 * values, name + "_in_w"),
        [2 * keys + values, values], dim=-1)
    b, alpha = layers.split(_linear(x, 2 * n_v, name + "_ba_w"), 2, dim=-1)
    taps = cfg["linear_conv_kernel_dim"]
    qkv = layers.short_conv(qkv, seq, taps, _conv_attr(name + "_conv_w", taps),
                            gated=False, activation="silu")
    dt_bias, a_log = _dt_bias_and_a_log(name, n_v)
    g = layers.elementwise_mul(
        layers.softplus(layers.elementwise_add(
            layers.cast(alpha, "float32"), dt_bias)),
        layers.scale(layers.exp(a_log), -1.0))
    # q | k | v stays one array: the rule's kernels read each head where the
    # conv wrote it
    o = layers.gated_delta_rule_packed(
        layers.reshape(qkv, [batch, seq, 2 * keys + values]),
        layers.reshape(g, [batch, seq, n_v]),
        layers.reshape(layers.sigmoid(layers.cast(b, "float32")),
                       [batch, seq, n_v]),
        n_k, d_k, chunk=cfg.get("delta_chunk_size", 64),
        impl=cfg.get("delta_rule_impl", "auto"))
    # the norm and the gate are one op: one pass over o and z each way
    y = layers.rms_norm(layers.reshape(o, [batch * seq, n_v, d_v]),
                        _eps(cfg), ParamAttr(name=name + "_gated_norm_w"),
                        gate=z)
    return _linear(y, cfg["hidden_size"], name + "_out_w")


def kimi_delta(x, cfg: dict, batch: int, seq: int, name: str):
    """The Kimi Delta Attention mixer over ``x [batch * seq, H]`` (HF
    ``KimiDeltaAttention``; Kimi Linear, arXiv:2510.26692), from
    ``linear_attn_config``: ``n = num_heads`` heads of ``d = head_dim`` for
    q, k and v alike. ``[q | k | v] = silu(conv(x [W_q | W_k | W_v]))``,
    one causal depthwise filter of ``short_conv_kernel_size`` taps without a
    bias over the ``3 n d`` channels (HF's three convs side by side); a
    token, head and key channel, ``g = -exp(A_log[head]) softplus((x W_fa
    W_fb) + dt_bias)`` through a low-rank pair of width ``d`` (float32; HF
    ``fused_kda_gate``); a token and head, ``beta = sigmoid(x W_b)``; the
    gated delta rule with that decay a key channel over unit q and k
    (``layers.gated_delta_rule_packed`` given ``g [batch, seq, n, d]``: the
    state's row c decays by ``exp(g[c])``; in chunks of
    ``delta_chunk_size``, default 64, lowered as ``delta_rule_impl`` says);
    ``W_o (rmsnorm(o) * sigmoid(x W_ga W_gb))``, the norm over a head's
    values with one plain scale of head size shared by the heads, then the
    sigmoid gate through a second low-rank pair, both in one ``rms_norm``
    op. ``A_log`` (a head) and ``dt_bias`` (a channel) are float32 and start
    as ``mamba``'s do, the filter as a depthwise Conv1d's."""
    lin = cfg["linear_attn_config"]
    n, d, taps = lin["num_heads"], lin["head_dim"], lin[
        "short_conv_kernel_size"]
    wide = n * d
    qkv = layers.short_conv(_linear(x, 3 * wide, name + "_qkv_w"), seq, taps,
                            _conv_attr(name + "_conv_w", taps), gated=False,
                            activation="silu")
    dt_bias, a_log = _dt_bias_and_a_log(name, n, wide)
    decay = layers.softplus(layers.elementwise_add(layers.cast(
        _linear(_linear(x, d, name + "_f_a_w"), wide, name + "_f_b_w"),
        "float32"), dt_bias))
    g = layers.elementwise_mul(
        layers.reshape(decay, [batch, seq, n, d]),
        layers.reshape(layers.scale(layers.exp(a_log), -1.0), [1, 1, n, 1]))
    beta = layers.sigmoid(layers.cast(_linear(x, n, name + "_b_w"),
                                      "float32"))
    o = layers.gated_delta_rule_packed(
        layers.reshape(qkv, [batch, seq, 3 * wide]), g,
        layers.reshape(beta, [batch, seq, n]), n, d,
        chunk=cfg.get("delta_chunk_size", 64),
        impl=cfg.get("delta_rule_impl", "auto"))
    z = _linear(_linear(x, d, name + "_g_a_w"), wide, name + "_g_b_w")
    y = layers.rms_norm(layers.reshape(o, [batch * seq, n, d]), _eps(cfg),
                        ParamAttr(name=name + "_gated_norm_w"), gate=z,
                        gate_activation="sigmoid")
    return _linear(y, cfg["hidden_size"], name + "_o_w")


def experts(x, cfg: dict, name: str):
    """The layer's routed experts, and its shared expert where the config
    has one (``layers.moe_ffn``), from the config."""
    held = _held(cfg)
    routed = cfg.get("num_experts_routed", held)
    return layers.moe_ffn(
        x, routed, _top_k(cfg),
        cfg.get("moe_intermediate_size", cfg["intermediate_size"]),
        param_attr=_attr(None), name=name,
        experts_held=(None if held == routed
                      else (cfg.get("first_expert_held", 0), held)),
        scoring=_scoring(cfg),
        norm_topk=bool(cfg.get("norm_topk_prob",
                               cfg.get("moe_renormalize", False))),
        routed_scale=float(cfg.get(
            "routed_scaling_factor",
            cfg.get("moe_routed_scaling_factor", 1.0))),
        expert_bias=_bias_chosen(cfg),
        row_budget=cfg.get("moe_row_budget"),
        shared_width=_shared_width(cfg),
        shared_gate=bool(cfg.get("shared_expert_gate", False)),
        expert_axis=cfg.get("expert_axis"),
        matmul_tiling=cfg.get("moe_matmul_tiling"))



def block(x, cfg: dict, batch: int, seq: int, name: str,
          kind: str = "full_attention", dense: bool = False, layer: int = 0):
    """Decoder layer ``layer`` over ``x [batch * seq, H]`` with the operator
    ``kind``; returns the layer's output and the router's variables
    (``layers.moe_ffn``; None for a ``dense`` feed-forward layer). Under
    ``norm_placement: "sandwich"`` each branch's output is normed once more
    before its residual add (``<operator>_post_norm_w``,
    ``<layer>_ffn_post_norm_w``). Under ``hc_mult`` = n above 1, ``x`` and
    the output are the residual state ``[batch * seq, n * H]``, the n
    streams side by side, and each of the two sub-layers is a
    hyper-connection around its branch (the module's docstring; parameters
    ``<operator>_hc_phi`` / ``_b`` / ``_alpha``, ``<layer>_ffn_hc_...``)."""
    r = float(cfg.get("residual_multiplier", 1.0))
    n = _streams(cfg)

    def add(h, branch):
        return layers.elementwise_add(
            h, branch if r == 1.0 else layers.scale(branch, r))
    sandwich = cfg.get("norm_placement", "pre") == "sandwich"

    def branch(y, norm_w):
        # sandwich: a second norm on the branch's output, before the add
        return _norm(y, cfg, norm_w) if sandwich else y

    def sublayer(state, run, hc):
        """``state + run(state)``; over ``hc_mult`` streams the
        hyper-connection ``hc``: ``H_res state + H_post^T run(H_pre
        state)``. Returns the next state and what ``run`` gave beside its
        output."""
        if n == 1:
            y, aux = run(state)
            return add(state, y), aux
        u, coef = layers.hyper_connection_pre(
            state, n, cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
            (cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
            phi_attr=_attr(hc + "_phi"),
            b_attr=ParamAttr(name=hc + "_b", initializer=(
                Normal(0.0, float(cfg["hc_bias_std"]))
                if cfg.get("hc_bias_std") else Constant(0.0))),
            alpha_attr=ParamAttr(name=hc + "_alpha", initializer=Constant(
                float(cfg.get("hc_alpha_init", 0.01)))))
        y, aux = run(u)
        return layers.hyper_connection_post(
            state, y, coef, n, cfg["hc_sinkhorn_iters"]), aux
    op_name = name + {"conv": "_conv", "mamba": "_mamba", "kda": "_kda",
                      "linear_attention": "_delta"}.get(kind, "_attn")

    def operator(x):
        normed = _norm(x, cfg, op_name + "_norm_w")
        if kind == "conv":
            mixed = short_conv(normed, cfg, seq, op_name)
        elif kind == "mamba":
            mixed = mamba(normed, cfg, batch, seq, op_name)
        elif kind == "linear_attention":
            mixed = delta_net(normed, cfg, batch, seq, op_name)
        elif kind == "kda":
            mixed = kimi_delta(normed, cfg, batch, seq, op_name)
        elif cfg.get("kv_lora_rank"):
            mixed = latent_attention(normed, cfg, batch, seq, op_name)
        else:
            mixed = attention(normed, cfg, batch, seq, op_name, layer, kind)
        return branch(mixed, op_name + "_post_norm_w"), None

    def feed_forward(h):
        normed = _norm(h, cfg, name + "_ffn_norm_w")
        if dense:
            width = cfg.get("shared_intermediate_size",
                            cfg["intermediate_size"])
            gated = layers.swiglu(
                _linear(normed, width, name + "_ffn_gate_w"),
                _linear(normed, width, name + "_ffn_up_w"))
            return branch(
                _linear(gated, cfg["hidden_size"], name + "_ffn_down_w"),
                name + "_ffn_post_norm_w"), None
        moe, aux = experts(normed, cfg, name + "_moe")
        return branch(moe, name + "_ffn_post_norm_w"), aux
    h, _ = sublayer(x, operator, op_name + "_hc")
    return sublayer(h, feed_forward, name + "_ffn_hc")


def prediction_module(h, next_tokens, cfg: dict, batch: int, seq: int,
                      name: str):
    """The multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437,
    section 2.2) over the trunk's output ``h [batch * seq, H]`` before its
    final norm and ``next_tokens [batch * seq, 1]``, the token that follows
    each position: ``u = W_eh [norm_h(h) | norm_e(Emb(next_tokens))]`` (2H
    -> H, the trunk's embedding table) through one more decoder block of
    the expert kind with its own weights. Returns the block's output, which
    the caller norms and decodes with the trunk's head, and its router's
    variables."""
    e = _embed(layers.reshape(next_tokens, [batch * seq]), cfg)
    u = _linear(layers.concat([_norm(h, cfg, name + "_h_norm_w"),
                               _norm(e, cfg, name + "_e_norm_w")], axis=-1),
                cfg["hidden_size"], name + "_eh_w")
    return block(u, cfg, batch, seq, name, "full_attention", dense=False)


def balance_experts(out: dict, rate: float) -> None:
    """Append the update of every expert layer's selection bias from the
    step's load (``layers.moe_bias_update``; ``out`` is ``build``'s result).
    Call it after ``minimize``: the bias must hold still until the backward
    has run, and a clone taken before has no update in it."""
    for bias, load in zip(out["expert_bias"], out["expert_load"]):
        layers.moe_bias_update(bias, load, rate)


def _mean_of(values):
    total = values[0] if len(values) == 1 else layers.sums(values)
    return layers.scale(total, 1.0 / len(values))


def _looped(x, cfg: dict, batch: int, seq: int):
    """The whole stack as one ``scan`` op over a sub-block that holds the
    layers and the final norm once, applied ``total_ut_steps`` times:
    returns the normed state after every pass ``[steps * batch * seq, H]``,
    pass-major, and what a ``RecomputeOptimizer`` cuts the sub-block at for
    recomputation by layer: every layer's output but the last, and the
    pass's normed end (the final norm is recomputed with the last layer:
    what is kept is a layer application's input, ``steps x layers``
    arrays)."""
    steps = cfg["total_ut_steps"]
    loop = layers.Scan(time_major=True, steps=steps)
    cut = []
    with loop.step():
        h = loop.memory(x)
        y = h
        for i, kind in enumerate(_layer_types(cfg)):
            y, _ = block(y, cfg, batch, seq, f"layer{i}", kind, dense=True,
                         layer=i)
            cut.append(y)
        y = cut[-1] = _norm(y, cfg, "final_norm_w")
        loop.update_memory(h, y)
        loop.step_output(y)
    states = loop()
    return layers.reshape(states, [steps * batch * seq,
                                   cfg["hidden_size"]]), cut


def build(cfg: dict, ids, labels, labels_next=None) -> dict:
    """Append the model to the current Program. ``ids [batch, seq]`` int
    tokens, ``labels [batch * seq, 1]`` the next token of every position;
    under ``num_nextn_predict_layers: 1`` also ``labels_next [batch * seq,
    1]``, the token after that, for the multi-token-prediction module
    (``prediction_module``): the result then has ``mtp_ce`` and
    ``mtp_each`` beside ``ce`` and ``each``, ``loss`` is ``ce`` +
    ``mtp_loss_weight`` (default 0.3) x ``mtp_ce``, and the module's router
    variables follow the trunk's in the per-layer lists.

    Returns the variables a caller trains on or fetches: ``loss`` (the
    total), ``ce`` (mean cross-entropy), ``each`` (every position's
    cross-entropy ``[batch * seq, 1]``), ``load_balancing`` and ``z_loss``
    (the two router losses before their coefficients; only where the config
    has them), and per expert layer ``expert_load`` (``[experts routed]``
    int32: assignments an expert received), ``expert_index`` (``[batch *
    seq, k]``: the experts chosen), ``expert_bias`` (the selection bias,
    under ``use_expert_bias``), ``expert_dropped`` (``[1]`` int32: the
    rows the layer's ``moe_row_budget`` has dropped since startup) and
    ``expert_routed`` (``[batch * seq, H]``: the routed experts' part of
    the layer's output, without the shared expert's). Under ``hc_mult``
    above 1 also ``stream_states``: every layer's output state ``[batch *
    seq, hc_mult * H]``. Under ``total_ut_steps`` above 1: the module's
    docstring."""
    _check(cfg)
    batch, seq = int(ids.shape[0]), int(ids.shape[1])
    H = cfg["hidden_size"]
    E = cfg.get("num_experts_routed", cfg.get("num_experts"))
    router_losses = "router_aux_loss_coef" in cfg
    dtype = cfg.get("dtype", "float32")
    x = layers.reshape(_embed(ids, cfg), [batch * seq, H])
    balance, z, loads, indices, biases, dropped, routed, states = (
        [] for _ in range(8))

    def keep(aux):
        loads.append(aux["load"])
        indices.append(aux["index"])
        routed.append(aux["routed"])
        if "bias" in aux:
            biases.append(aux["bias"])
        if "dropped" in aux:
            dropped.append(aux["dropped"])

    def cross_entropy(x, norm_w, targets):
        """Every position's cross-entropy of ``targets`` under the head
        (one matrix, or the table, whoever calls) over ``norm(x)`` (over
        ``x`` itself where ``norm_w`` is None)."""
        if norm_w:
            x = _norm(x, cfg, norm_w)
        if cfg.get("tie_word_embeddings"):
            table = x.block.program.global_block().var("tok_emb")
            if dtype != "float32":
                table = layers.cast(table, dtype)
            logits = layers.matmul(x, table, transpose_y=True)
        else:
            axis = cfg.get("vocab_axis")    # the head's columns likewise
            logits = _linear(x, cfg["vocab_size"], "lm_head_w",
                             (None, axis) if axis else None)
        if cfg.get("logits_scaling", 1) != 1:   # applied in float32
            if dtype != "float32":
                logits = layers.cast(logits, "float32")
            logits = layers.scale(logits, 1.0 / float(cfg["logits_scaling"]))
        return layers.softmax_with_cross_entropy(logits, targets)

    steps = cfg.get("total_ut_steps", 1)
    if steps > 1:
        # the passes' states under one head: steps x the rows, one product
        states, cut = _looped(x, cfg, batch, seq)
        each = cross_entropy(states, None, layers.expand(labels, [steps, 1]))
        loss, ce, exit_p = layers.exit_gate_loss(
            states, each, steps, float(cfg.get("exit_entropy_coef", 0.0)),
            param_attr=ParamAttr(name="exit_gate_w",
                                 initializer=Constant(0.0)),
            bias_attr=ParamAttr(name="exit_gate_b"))
        return {"loss": loss, "ce": ce, "each": each, "exit_p": exit_p,
                "loop_checkpoints": cut}
    n = _streams(cfg)
    if n > 1:           # X_0: the embedding in every stream
        x = layers.concat([x] * n, axis=-1)
    for i, kind in enumerate(_layer_types(cfg)):
        x, aux = block(x, cfg, batch, seq, f"layer{i}", kind,
                       dense=_is_dense(cfg, i), layer=i)
        if n > 1:
            states.append(x)
        if aux is None:
            continue
        if router_losses:
            share = layers.scale(
                layers.cast(aux["load"], "float32"),
                1.0 / (batch * seq * _top_k(cfg)))
            balance.append(layers.scale(layers.reduce_sum(
                layers.elementwise_mul(
                    share, layers.reduce_mean(aux["prob"], dim=0))),
                float(E)))
            z.append(layers.mean(layers.square(aux["logz"])))
        keep(aux)
    if n > 1:           # the streams' sum closes the stack
        x = layers.sums(layers.split(x, n, dim=-1))
    each = cross_entropy(x, "final_norm_w", labels)
    ce = layers.mean(each)
    out = {"loss": ce, "ce": ce, "each": each, "expert_load": loads,
           "expert_index": indices, "expert_bias": biases,
           "expert_dropped": dropped, "expert_routed": routed}
    if n > 1:
        out["stream_states"] = states
    if cfg.get("num_nextn_predict_layers"):
        if labels_next is None:
            raise ValueError("decoder_lm: num_nextn_predict_layers needs "
                             "labels_next, the token after the next")
        u, aux = prediction_module(x, labels, cfg, batch, seq, "mtp")
        keep(aux)
        out["mtp_each"] = cross_entropy(u, "mtp_final_norm_w", labels_next)
        out["mtp_ce"] = layers.mean(out["mtp_each"])
        out["loss"] = layers.sums([ce, layers.scale(
            out["mtp_ce"], float(cfg.get("mtp_loss_weight", 0.3)))])
    if router_losses:
        balance, z = _mean_of(balance), _mean_of(z)
        out["loss"] = layers.sums([
            out["loss"],
            layers.scale(balance, float(cfg["router_aux_loss_coef"])),
            layers.scale(z, float(cfg["router_z_loss_coef"]))])
        out.update(load_balancing=balance, z_loss=z)
    return out
