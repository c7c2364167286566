"""NVIDIA-Nemotron-3-Nano's weights from a seed, made on the device
layer by layer (``weights.py`` does the same for Mistral).

The benchmark makes the weights, not the program: the plain reference
calls ``layer_weights`` layer by layer and gets every matrix in the
*natural* order (the published one: K and V projections apart, the
convolution ``(channels, taps)``), the program gets the tree in its own
layout (``make_weights``; ``nbdistributed_tpu/models/nemotron_h.py``
describes it), and neither takes anything the other made.

What is drawn how (all listed under ``assumed`` in the configuration
file): matrices N(0, 1/fan_in) in the dtype the configuration states,
the embedding N(0, 1/hidden) (at N(0, 1) a token's own embedding
dominates its logits through the residual stream and the check has no
power: PR 31), every RMS-norm scale 1, the convolution's bias N(0,
BIAS_STD^2).  The router's matrix holds values of that dtype, kept in
float32 (the scores are computed in float32);
``e_score_correction_bias`` is drawn uniform in +-ROUTER_BIAS: a real
checkpoint's is learned, and one that is zero would let a program that
drops it pass.  The Mamba-2 mixer's own initialisation, since a layer
whose step size or decay is near 0 passes or fails for no reason: ``A``
uniform in [1, 16] a head (``A_log`` its logarithm), ``dt_bias`` the
inverse softplus of a step size log-uniform in [``time_step_min``,
``time_step_max``] and no smaller than ``time_step_floor``, ``D = 1``.

An expert is drawn from its index among *all* the experts the router
knows, so the shares ``(0, 64)`` and ``(64, 64)`` of one seed are two
halves of one 128-expert layer.

``cfg`` is the configuration file's dict, with Hugging Face key names
and the two keys of the share (``experts_routed_over``,
``experts_held_first``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _normal, dtype_of, round_to, seed_key  # noqa: F401

BIAS_STD, ROUTER_BIAS = 0.02, 0.1
A_RANGE = (1.0, 16.0)
_EMBED, _HEAD = 1 << 20, (1 << 20) + 1      # fold-in tags beside layers
F32 = jnp.float32
KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}


def sizes(cfg: dict) -> dict:
    hm, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    held = cfg["n_routed_experts"]
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "Hm": hm, "P": p, "G": g, "N": n, "K": cfg["conv_kernel"],
            "C": hm * p, "W": hm * p + 2 * g * n,
            "E": held, "Er": cfg.get("experts_routed_over", held),
            "first": cfg.get("experts_held_first", 0),
            "k": cfg["num_experts_per_tok"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_shared_expert_intermediate_size"]
            * cfg.get("n_shared_experts", 1)}


def kinds(cfg: dict) -> list[str]:
    """The kind of every layer: one letter of the pattern each."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"pattern {pattern!r} is not "
                         f"{cfg['num_hidden_layers']} letters")
    return [KINDS[c] for c in pattern]


def _experts(key, first, count: int, shape, fan_in, dt):
    """``count`` experts from ``first`` on, each from its own index
    among all, one after the other: the float32 draw of a layer's
    experts never exists, and an expert's values do not depend on which
    others are drawn with it (under ``vmap`` the ``rbg`` generator's
    do)."""
    return jax.lax.map(
        lambda e: _normal(jax.random.fold_in(key, e), shape, fan_in, dt),
        first + jnp.arange(count))


def expert_weights(key, layer, cfg: dict, held=None) -> dict:
    """An expert layer's routed experts ``held = (first, count)`` (by
    default the configuration's share), stacked on a leading axis."""
    z, dt = sizes(cfg), dtype_of(cfg)
    first, count = held or (z["first"], z["E"])
    ks = jax.random.split(jax.random.fold_in(key, layer), 24)
    d, f = z["D"], z["Fe"]
    return {"w_up": _experts(ks[20], first, count, (d, f), d, dt),
            "w_down": _experts(ks[21], first, count, (f, d), f, dt)}


def layer_weights(key, layer, cfg: dict, kind: str) -> dict:
    """One layer of ``kind`` in the natural order, an expert layer
    without its routed experts (``expert_weights``); ``layer`` (its
    index in the model) may be traced."""
    z, dt = sizes(cfg), dtype_of(cfg)
    d = z["D"]
    ks = iter(jax.random.split(jax.random.fold_in(key, layer), 24))
    mat = lambda shape: _normal(next(ks), shape, shape[0], dt)
    out = {"norm": jnp.ones((d,), F32)}
    if kind == "mamba2":
        hm, c, w = z["Hm"], z["C"], z["W"]
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            next(ks), (hm,), F32, math.log(cfg["time_step_min"]),
            math.log(cfg["time_step_max"]))), cfg["time_step_floor"])
        out.update(
            w_in=mat((d, c + w + hm)),              # [z | x | B | C | dt]
            conv_w=_normal(next(ks), (w, z["K"]), z["K"], dt),
            conv_b=BIAS_STD * jax.random.normal(next(ks), (w,), F32),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),      # softplus^-1
            A_log=jnp.log(jax.random.uniform(next(ks), (hm,), F32,
                                             *A_RANGE)),
            D=jnp.ones((hm,), F32), gate_norm=jnp.ones((c,), F32),
            w_out=mat((c, d)))
    elif kind == "attention":
        q, kv = z["H"] * z["Dh"], z["Hkv"] * z["Dh"]
        out.update(wq=mat((d, q)), wk=mat((d, kv)), wv=mat((d, kv)),
                   wo=mat((q, d)))
    elif kind == "experts":
        out.update(
            router=mat((d, z["Er"])).astype(F32),
            bias=jax.random.uniform(next(ks), (z["Er"],), F32,
                                    -ROUTER_BIAS, ROUTER_BIAS),
            shared={"w_up": mat((d, z["Fs"])),
                    "w_down": mat((z["Fs"], d))})
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return out


def embed_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _EMBED), (z["V"], z["D"]),
                   z["D"], dtype_of(cfg))


def head_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _HEAD), (z["D"], z["V"]),
                   z["D"], dtype_of(cfg))


def program_layer(w: dict, kind: str, experts=None) -> dict:
    """A natural-order layer in the program's layout: the convolution
    ``(taps, channels)``, K and V projections side by side, an expert
    layer's router, bias, experts and shared expert under ``moe``, the
    routed experts' ``w_up`` padded with zero columns to whole 128-lane
    tiles (the program drops them again)."""
    w = dict(w)
    if kind == "mamba2":
        w["conv_w"] = w["conv_w"].T
    elif kind == "attention":
        w["wkv"] = jnp.concatenate([w.pop("wk"), w.pop("wv")], axis=1)
    elif kind == "experts":
        up = experts["w_up"]
        up = jnp.pad(up, ((0, 0), (0, 0), (0, -up.shape[-1] % 128)))
        w = {"norm": w["norm"],
             "moe": {"router": w["router"], "bias": w["bias"],
                     "shared": w["shared"], "w_up": up,
                     "w_down": experts["w_down"]}}
    return w


def make_weights(key, cfg: dict) -> dict:
    """The whole tree in the program's layout, the layers a tuple of
    one tree each (jit this; ``key`` is an argument so that every seed
    shares one compiled program)."""
    z = sizes(cfg)
    layers = tuple(
        program_layer(layer_weights(key, l, cfg, kind), kind,
                      expert_weights(key, l, cfg)
                      if kind == "experts" else None)
        for l, kind in enumerate(kinds(cfg)))
    return {"embed": embed_weights(key, cfg), "layers": layers,
            "final_norm": jnp.ones((z["D"],), F32),
            "lm_head": head_weights(key, cfg)}
