"""HuggingFace interop: load Llama-family checkpoints into this
framework's transformer.

The reference's whole demo workflow is HF-centric (its notebook loads
SmolLM2-135M with ``transformers`` and trains it through Accelerate —
reference: 00_accelerate.ipynb cells 10, 28), so a user switching to
this framework needs their HF checkpoints to come along.  This module
converts any Llama-architecture ``transformers`` model (Llama 1/2/3,
SmolLM2, TinyLlama, ...) into the layer-stacked pytree that
:func:`~nbdistributed_tpu.models.transformer.forward` consumes — after
which every TPU path here applies: tp/dp sharding via
:func:`param_shardings`, flash attention, the KV-cache generate loop,
checkpointing.

Conventions verified against ``transformers`` (tests/unit/test_hf.py
checks logits parity against the torch forward):

* torch ``nn.Linear`` stores (out_features, in_features); our params
  right-multiply, so every projection transposes.
* Head ordering: HF's q/k/v rows are [head0 x Dh, head1 x Dh, ...] —
  transposing preserves our ``reshape(B, S, H, Dh)`` grouping.
* RoPE: HF's rotate-half with cos/sin repeated over both halves is
  algebraically identical to our half-split form (same
  theta^(-2i/head_dim) frequencies).
* ``tie_word_embeddings`` (SmolLM2 does) -> ``lm_head = embed.T``.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
import numpy as np

from .transformer import TransformerConfig


def config_from_hf(hf_config) -> TransformerConfig:
    """Map a ``transformers`` Llama-family config onto
    :class:`TransformerConfig`.  Rejects rope-scaling variants this
    forward does not implement rather than silently mis-rotating."""
    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling:
        rope_type = (scaling.get("rope_type")
                     or scaling.get("type") or "?")
        if rope_type != "default":
            raise ValueError(
                f"rope_scaling type {rope_type!r} is not supported "
                "(plain rotary only); use a base-rope checkpoint")
    if getattr(hf_config, "attention_bias", False):
        raise ValueError("attention_bias=True checkpoints are not "
                         "supported (Llama family uses bias-free "
                         "projections)")
    if getattr(hf_config, "mlp_bias", False):
        raise ValueError("mlp_bias=True checkpoints are not supported")
    head_dim = getattr(hf_config, "head_dim", None)
    expect = hf_config.hidden_size // hf_config.num_attention_heads
    if head_dim is not None and head_dim != expect:
        raise ValueError(
            f"head_dim {head_dim} != hidden_size/n_heads {expect}: "
            "decoupled head_dim is not supported")
    # Some HF configs (e.g. Qwen2) carry sliding_window but gate it
    # off with use_sliding_window=False.
    window = getattr(hf_config, "sliding_window", None)
    if not getattr(hf_config, "use_sliding_window", True):
        window = None
    return TransformerConfig(
        sliding_window=window,
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads",
                           hf_config.num_attention_heads),
        d_ff=hf_config.intermediate_size,
        max_seq_len=getattr(hf_config, "max_position_embeddings", 2048),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
    )


def _np(t) -> np.ndarray:
    """torch tensor (any dtype/device) -> float32 numpy."""
    return t.detach().to("cpu").float().numpy()


def _stack(sd, fmt: str, L: int, transpose: bool,
           dtype=jnp.float32) -> jnp.ndarray:
    """Stack L per-layer tensors, casting each layer to ``dtype``
    before stacking so the fp32 transient is one layer, not the whole
    (L, ...) stack (matters at Mixtral/Llama-7B scale)."""
    arrs = [jnp.asarray(_np(sd[fmt.format(i)]).T if transpose
                        else _np(sd[fmt.format(i)]), dtype)
            for i in range(L)]
    return jnp.stack(arrs)


def _attn_and_embed(sd, L: int, dtype):
    """The conversion both families share: embed, (possibly tied)
    lm_head, attention projections, and the two per-layer norms —
    one definition so a naming/tying fix reaches dense and MoE alike."""
    embed = _np(sd["model.embed_tokens.weight"])          # (V, D)
    if "lm_head.weight" in sd:
        lm_head = _np(sd["lm_head.weight"]).T             # (D, V)
    else:
        lm_head = embed.T                                  # tied
    layers = {
        "attn_norm": _stack(
            sd, "model.layers.{}.input_layernorm.weight", L, False),
        "wq": _stack(sd, "model.layers.{}.self_attn.q_proj.weight",
                     L, True, dtype),
        "wk": _stack(sd, "model.layers.{}.self_attn.k_proj.weight",
                     L, True, dtype),
        "wv": _stack(sd, "model.layers.{}.self_attn.v_proj.weight",
                     L, True, dtype),
        "wo": _stack(sd, "model.layers.{}.self_attn.o_proj.weight",
                     L, True, dtype),
        "mlp_norm": _stack(
            sd, "model.layers.{}.post_attention_layernorm.weight", L,
            False),
    }
    return {
        "embed": jnp.asarray(embed, dtype),
        "layers": layers,
        "final_norm": jnp.asarray(_np(sd["model.norm.weight"]),
                                  jnp.float32),
        "lm_head": jnp.asarray(lm_head, dtype),
    }


def params_from_hf(model, cfg: TransformerConfig | None = None, *,
                   dtype: Any = jnp.bfloat16) -> tuple[dict, Any]:
    """Convert a ``transformers`` ``LlamaForCausalLM``-shaped model (or
    anything with the same ``state_dict()`` naming) into this
    framework's pytree.

    Returns ``(params, cfg)`` with weights cast to ``dtype`` (norms
    stay fp32, matching :func:`init_params`).  The conversion stacks
    per-layer tensors along a leading (n_layers,) axis for the
    ``lax.scan`` forward.
    """
    if cfg is None:
        cfg = config_from_hf(model.config)
    cfg = TransformerConfig(**{**cfg.__dict__, "dtype": dtype})
    sd = model.state_dict()
    L = cfg.n_layers
    params = _attn_and_embed(sd, L, dtype)
    params["layers"].update({
        "w_gate": _stack(sd, "model.layers.{}.mlp.gate_proj.weight",
                         L, True, dtype),
        "w_up": _stack(sd, "model.layers.{}.mlp.up_proj.weight",
                       L, True, dtype),
        "w_down": _stack(sd, "model.layers.{}.mlp.down_proj.weight",
                         L, True, dtype),
    })
    return params, cfg


def moe_config_from_hf(hf_config, *,
                       capacity_factor: float | None = None):
    """Map a ``transformers`` Mixtral-family config onto
    :class:`~nbdistributed_tpu.models.moe.MoEConfig`.

    HF Mixtral routes without capacity limits; this framework's
    dispatch is capacity-bounded, so the default ``capacity_factor``
    is the *lossless* value ``n_experts / top_k`` (no token ever
    dropped — logits match the torch forward).  Pass a tighter factor
    to trade exactness for bounded expert memory."""
    from .moe import MoEConfig

    E = hf_config.num_local_experts
    k = hf_config.num_experts_per_tok
    base = config_from_hf(hf_config)
    if capacity_factor is None:
        capacity_factor = E / k
    return MoEConfig(**{**base.__dict__, "n_experts": E, "top_k": k,
                        "capacity_factor": capacity_factor,
                        "lb_coef": float(getattr(
                            hf_config, "router_aux_loss_coef", 0.01))})


def moe_params_from_hf(model, *, dtype: Any = jnp.bfloat16,
                       capacity_factor: float | None = None):
    """Convert a ``transformers`` ``MixtralForCausalLM``-shaped model
    into the MoE-family pytree (attention exactly as the dense
    conversion; router fp32 transposed; per-expert w1/w3/w2 →
    w_gate/w_up/w_down stacked on a leading E axis).  Returns
    ``(params, cfg)``."""
    cfg = moe_config_from_hf(model.config,
                             capacity_factor=capacity_factor)
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": dtype})
    sd = model.state_dict()
    L, E = cfg.n_layers, cfg.n_experts

    def stack_experts(w: str):
        # (L, E, in, out) from per-expert torch (out, in) tensors —
        # cast to the target dtype PER LAYER so the fp32 transient is
        # one (E, in, out) slab, not the whole L*E expert stack (at
        # Mixtral-8x7B scale the difference is ~100 GB of host RAM).
        per_layer = [jnp.asarray(np.stack([
            _np(sd[f"model.layers.{i}.block_sparse_moe.experts.{e}"
                   f".{w}.weight"]).T for e in range(E)]), dtype)
            for i in range(L)]
        return jnp.stack(per_layer)

    params = _attn_and_embed(sd, L, dtype)
    params["layers"]["moe"] = {
        # Router stays fp32 (gating is numerically delicate; _stack's
        # default dtype).
        "router": _stack(
            sd, "model.layers.{}.block_sparse_moe.gate.weight", L,
            True),
        "w_gate": stack_experts("w1"),
        "w_up": stack_experts("w3"),
        "w_down": stack_experts("w2"),
    }
    return params, cfg


def latent_moe_config_from_hf(hf_config, **overrides):
    """Map a ``model_type: joyai_llm_flash`` config (DeepSeek-V3's
    keys: MLA ranks and head sizes, ``first_k_dense_replace``,
    ``n_routed_experts`` ...) onto
    :class:`~nbdistributed_tpu.models.mla.LatentMoEConfig`.
    ``overrides`` are fields of that class (``dtype``, ``use_flash``).

    Refused rather than mis-served: rope scaling (this forward applies
    no mscale), a group-limited choice (``n_group`` > 1), scoring other
    than sigmoid with ``norm_topk_prob``, expert layers that do not
    follow every dense one (``moe_layer_freq`` != 1).
    ``num_nextn_predict_layers`` is read and dropped: multi-token
    prediction is not served, and next-token logits do not depend on
    it.

    A real checkpoint would also need its weights permuted on the way
    in: ``rope_interleave`` pairs rotary dimension ``2j`` with
    ``2j + 1``, :func:`~.transformer._rope` pairs ``j`` with
    ``j + half``, so the rotary columns of ``q_b_proj`` (per head) and
    of ``kv_a_proj_with_mqa`` go from ``[0, 1, 2, ...]`` to
    ``[0, 2, 4, ..., 1, 3, 5, ...]``; no weight converter for this
    family exists yet."""
    from .mla import LatentMoEConfig

    get = lambda k, d=None: getattr(hf_config, k, d)
    if get("rope_scaling"):
        raise ValueError("rope_scaling is not supported for latent "
                         "attention (no mscale is applied)")
    if get("n_group", 1) != 1 or get("topk_group", 1) != 1:
        raise ValueError("group-limited expert choice (n_group > 1) "
                         "is not supported")
    if get("scoring_func") != "sigmoid" or not get("norm_topk_prob"):
        raise ValueError("only sigmoid scoring with normalised top-k "
                         "gates is supported")
    if get("moe_layer_freq", 1) != 1:
        raise ValueError("moe_layer_freq != 1 is not supported")
    if get("attention_bias", False):
        raise ValueError("attention_bias=True is not supported")
    return LatentMoEConfig(**{
        "vocab_size": hf_config.vocab_size,
        "d_model": hf_config.hidden_size,
        "n_layers": hf_config.num_hidden_layers,
        "n_heads": hf_config.num_attention_heads,
        "d_ff": hf_config.intermediate_size,
        "max_seq_len": get("max_position_embeddings", 4096),
        "rope_theta": float(get("rope_theta", 10000.0)),
        "norm_eps": float(get("rms_norm_eps", 1e-6)),
        "q_lora_rank": hf_config.q_lora_rank,
        "kv_lora_rank": hf_config.kv_lora_rank,
        "qk_nope_head_dim": hf_config.qk_nope_head_dim,
        "qk_rope_head_dim": hf_config.qk_rope_head_dim,
        "v_head_dim": hf_config.v_head_dim,
        "n_dense_layers": hf_config.first_k_dense_replace,
        "n_experts": hf_config.n_routed_experts,
        "top_k": hf_config.num_experts_per_tok,
        "d_expert": hf_config.moe_intermediate_size,
        "n_shared_experts": get("n_shared_experts", 0),
        "routed_scale": float(get("routed_scaling_factor", 1.0)),
        **overrides})


def hybrid_config_from_hf(hf_config, **overrides):
    """Map a ``model_type: phi4flash`` config onto
    :class:`~nbdistributed_tpu.models.hybrid.HybridConfig`.  The kind of
    every layer is derived from ``num_hidden_layers`` and
    ``mb_per_layer`` (:func:`~.hybrid.layer_kinds_for`), never listed;
    what the model's own file hard-codes and ``config.json`` does not
    carry (``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank =
    ceil(hidden / 16)``) are that class's defaults.

    Refused rather than mis-served: an untied head, a head or MLP bias.

    A real checkpoint would also need its weights laid out on the way
    in; no weight converter for this family exists yet.  The fused
    ``Wqkv`` is ``[q | k | v]`` by columns and goes to ``wq`` and
    ``wkv = [k | v]``; within ``wq`` the query heads of a KV pair ``j``
    are reordered from ``4j, 4j + 1, 4j + 2, 4j + 3`` to ``4j, 4j + 2,
    4j + 1, 4j + 3`` (first softmax's two heads, then the second's: the
    pairing assumed is neighbouring heads ``2p, 2p + 1``, and it has to
    be checked against the checkpoint's own reshape before the first
    real weight is served); the fused ``gate_up`` splits into
    ``w_gate`` and ``w_up``; ``A_log`` is stored with the channels
    minor, ``(d_state, d_inner)``, the transpose of the checkpoint's;
    every ``nn.Linear`` weight is transposed to ``(in, out)``."""
    import math

    from .hybrid import HybridConfig, layer_kinds_for

    get = lambda k, d=None: getattr(hf_config, k, d)
    if not get("tie_word_embeddings", True):
        raise ValueError("an untied head is not supported for "
                         "model_type phi4flash")
    if get("mlp_bias", False) or get("lm_head_bias", False):
        raise ValueError("mlp_bias / lm_head_bias are not supported")
    n_layers = hf_config.num_hidden_layers
    return HybridConfig(**{
        "vocab_size": hf_config.vocab_size,
        "d_model": hf_config.hidden_size,
        "n_layers": n_layers,
        "n_heads": hf_config.num_attention_heads,
        "n_kv_heads": hf_config.num_key_value_heads,
        "d_ff": hf_config.intermediate_size,
        "max_seq_len": get("max_position_embeddings", 4096),
        "norm_eps": float(get("layer_norm_eps", 1e-5)),
        "sliding_window": hf_config.sliding_window,
        "layer_kinds": layer_kinds_for(n_layers, get("mb_per_layer", 2)),
        "dt_rank": math.ceil(hf_config.hidden_size / 16),
        **overrides})


def nemotron_h_config_from_hf(hf_config, **overrides):
    """Map a ``model_type: nemotron_h`` config onto
    :class:`~nbdistributed_tpu.models.nemotron_h.NemotronHConfig`: one
    mixer a layer, in the order ``hybrid_override_pattern`` spells
    (``M`` Mamba-2, ``*`` attention, ``E`` experts; ``-``, a dense MLP
    layer of older checkpoints of the type, is refused).

    Two keys beside the published ones say which share of every expert
    layer the model holds, for a deployment that splits a layer's
    experts over chips: ``experts_routed_over`` (the router's width,
    by default ``n_routed_experts``) and ``experts_held_first`` (the
    first expert held, by default 0); ``n_routed_experts`` is then the
    count held.

    Refused rather than mis-served: a group-limited choice, gates that
    are not normalised, an expert that is not ``relu2``, a tied head,
    biases on the projections, no bias on the convolution.
    ``rope_theta`` and ``partial_rotary_factor`` are read and dropped:
    the published block applies no rotary embedding in its attention
    layers (to be checked against the checkpoint's own modelling file
    before the first real weight is served, as everything below).

    A real checkpoint would also need its weights laid out on the way
    in; no weight converter for this family exists yet.  Under
    ``backbone.layers.{i}``: ``norm.weight`` -> ``norm``; a Mamba-2
    layer's ``mixer.in_proj.weight`` (transposed to ``(in, out)``, its
    columns already ``[z | x | B | C | dt]``) -> ``w_in``,
    ``mixer.conv1d.weight`` ``(channels, 1, 4)`` -> ``conv_w`` ``(4,
    channels)``, ``conv1d.bias`` -> ``conv_b``, ``mixer.dt_bias`` /
    ``A_log`` / ``D`` as they are (a value a head), ``mixer.norm.weight``
    -> ``gate_norm`` (the gated norm's group size is ``d_inner /
    n_groups``: check ``norm_before_gate`` is false), ``mixer.out_proj``
    -> ``w_out``; an attention layer's ``q_proj`` -> ``wq``, ``k_proj``
    and ``v_proj`` side by side -> ``wkv``, ``o_proj`` -> ``wo``; an
    expert layer's ``mixer.gate.weight`` (float32) -> ``moe.router``,
    ``gate.e_score_correction_bias`` -> ``moe.bias``,
    ``mixer.experts.{e}.up_proj`` / ``down_proj`` for the experts held,
    stacked on a leading axis, -> ``moe.w_up`` / ``moe.w_down``,
    ``mixer.shared_experts`` -> ``moe.shared``; ``backbone.norm_f`` ->
    ``final_norm``, ``backbone.embeddings`` -> ``embed``, ``lm_head``
    transposed.  ``time_step_limit`` is taken as ``(0, inf)``: the step
    size is not clamped."""
    from .nemotron_h import LETTERS, NemotronHConfig

    get = lambda k, d=None: getattr(hf_config, k, d)
    pattern = hf_config.hybrid_override_pattern
    if set(pattern) - set(LETTERS):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: only layers of kinds "
            f"{''.join(LETTERS)} are supported")
    if get("n_group", 1) != 1 or get("topk_group", 1) != 1:
        raise ValueError("group-limited expert choice (n_group > 1) "
                         "is not supported")
    if not get("norm_topk_prob", True):
        raise ValueError("only normalised top-k gates are supported")
    if get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError("only relu2 experts are supported for "
                         "model_type nemotron_h")
    if get("tie_word_embeddings", False):
        raise ValueError("a tied head is not supported for model_type "
                         "nemotron_h")
    if (get("attention_bias", False) or get("mlp_bias", False)
            or get("mamba_proj_bias", False) or get("use_bias", False)
            or not get("use_conv_bias", True)):
        raise ValueError("projection biases (attention_bias, mlp_bias, "
                         "mamba_proj_bias, use_bias) and a convolution "
                         "without one are not supported")
    held = hf_config.n_routed_experts
    return NemotronHConfig(**{
        "vocab_size": hf_config.vocab_size,
        "d_model": hf_config.hidden_size,
        "n_layers": hf_config.num_hidden_layers,
        "n_heads": hf_config.num_attention_heads,
        "n_kv_heads": hf_config.num_key_value_heads,
        "attn_head_dim": hf_config.head_dim,
        "d_ff": 0,
        "max_seq_len": get("max_position_embeddings", 4096),
        "norm_eps": float(get("layer_norm_epsilon", 1e-5)),
        "pattern": pattern,
        "ssm_heads": hf_config.mamba_num_heads,
        "ssm_head_dim": hf_config.mamba_head_dim,
        "ssm_groups": hf_config.n_groups,
        "d_state": hf_config.ssm_state_size,
        "d_conv": hf_config.conv_kernel,
        "ssm_block": get("chunk_size", 128),
        "n_experts": get("experts_routed_over", held),
        "experts_held": (get("experts_held_first", 0), held),
        "top_k": hf_config.num_experts_per_tok,
        "d_expert": hf_config.moe_intermediate_size,
        "d_shared": hf_config.moe_shared_expert_intermediate_size
        * get("n_shared_experts", 1),
        "routed_scale": float(get("routed_scaling_factor", 1.0)),
        **overrides})


def sdar_config_from_hf(hf_config, **overrides):
    """Map a ``model_type: sdar_moe`` config (Qwen3-MoE's keys) onto
    :class:`~nbdistributed_tpu.models.sdar.SDARConfig`.  ``overrides``
    are fields of that class: ``dtype``, ``use_flash``, and the three
    the published config does not state (``block_length``,
    ``denoise_steps``, ``mask_token_id``: they live in the release's
    generation settings and tokenizer).

    Refused rather than mis-served: dense layers among the expert ones
    (``mlp_only_layers`` non-empty, ``decoder_sparse_step`` != 1), a
    sliding window, rope scaling, gates that are not renormalised
    (``norm_topk_prob`` false), biases, a tied head.
    ``intermediate_size`` (the width a dense layer would have) is read
    and dropped: no layer is dense."""
    from .sdar import SDARConfig

    get = lambda k, d=None: getattr(hf_config, k, d)
    if get("mlp_only_layers"):
        raise ValueError("mlp_only_layers is not supported for "
                         "model_type sdar_moe: every layer routes")
    if get("decoder_sparse_step", 1) != 1:
        raise ValueError("decoder_sparse_step != 1 is not supported")
    if get("use_sliding_window", False):
        raise ValueError("use_sliding_window is not supported for "
                         "model_type sdar_moe")
    if get("rope_scaling"):
        raise ValueError("rope_scaling is not supported (plain rotary "
                         "only)")
    if not get("norm_topk_prob", False):
        raise ValueError("only renormalised top-k gates "
                         "(norm_topk_prob) are supported")
    if get("attention_bias", False):
        raise ValueError("attention_bias=True is not supported")
    if get("tie_word_embeddings", False):
        raise ValueError("a tied head is not supported for model_type "
                         "sdar_moe")
    return SDARConfig(**{
        "vocab_size": hf_config.vocab_size,
        "d_model": hf_config.hidden_size,
        "n_layers": hf_config.num_hidden_layers,
        "n_heads": hf_config.num_attention_heads,
        "n_kv_heads": hf_config.num_key_value_heads,
        "head_dim": get("head_dim") or (hf_config.hidden_size
                                        // hf_config.num_attention_heads),
        "d_ff": hf_config.moe_intermediate_size,
        "n_experts": hf_config.num_experts,
        "top_k": hf_config.num_experts_per_tok,
        "max_seq_len": get("max_position_embeddings", 32768),
        "rope_theta": float(get("rope_theta", 1e6)),
        "norm_eps": float(get("rms_norm_eps", 1e-6)),
        **overrides})


def config_from_hf_json(config: dict, **overrides):
    """A published ``config.json`` (as a dict) -> the program's config,
    by its ``model_type``; one this tree cannot run raises."""
    import types
    ns = types.SimpleNamespace(**config)
    kind = config.get("model_type")
    if kind == "joyai_llm_flash":
        return latent_moe_config_from_hf(ns, **overrides)
    if kind == "phi4flash":
        return hybrid_config_from_hf(ns, **overrides)
    if kind == "nemotron_h":
        return nemotron_h_config_from_hf(ns, **overrides)
    if kind == "sdar_moe":
        # the generation settings ride the file beside the published
        # keys where the caller put them there
        gen = {k: config[k] for k in ("block_length", "denoise_steps",
                                      "mask_token_id") if k in config}
        return sdar_config_from_hf(ns, **{**gen, **overrides})
    if kind == "mixtral":
        cfg = moe_config_from_hf(ns)
    elif kind in ("llama", "mistral"):
        cfg = config_from_hf(ns)
    else:
        raise ValueError(f"model_type {kind!r} is not supported")
    return type(cfg)(**{**cfg.__dict__, **overrides})


def load_hf_pretrained(name_or_path: str, *,
                       dtype: Any = jnp.bfloat16) -> tuple[dict, Any]:
    """``from_pretrained`` (local path or cached hub name, torch CPU)
    -> (params, cfg).  Dispatches on architecture: Mixtral-family
    checkpoints convert through :func:`moe_params_from_hf`, Llama
    family through :func:`params_from_hf`.  The heavyweight torch
    model is freed before returning."""
    from transformers import AutoModelForCausalLM

    # Load in the checkpoint's own dtype: forcing fp32 would double a
    # Mixtral-class model's host footprint before conversion (the
    # per-tensor fp32 hop happens inside _np, one tensor at a time).
    model = AutoModelForCausalLM.from_pretrained(
        name_or_path, dtype="auto", low_cpu_mem_usage=True)
    try:
        if getattr(model.config, "num_local_experts", None):
            return moe_params_from_hf(model, dtype=dtype)
        return params_from_hf(model, dtype=dtype)
    finally:
        del model
