"""Phi-4-mini-flash-reasoning's weights from a seed, made on the device
layer by layer (``weights.py`` does the same for Mistral).

The benchmark makes the weights, not the program: the plain reference
calls ``layer_weights`` layer by layer and gets every matrix in the
*natural* order (heads 0, 1, 2, ... as the equations number them), the
program gets the tree in its own layout (``make_weights``;
``nbdistributed_tpu/models/hybrid.py`` describes it), and neither takes
anything the other made.  So the program's column order is checked, not
assumed: ``make_weights`` permutes, the reference does not.

What is drawn how (all listed under ``assumed`` in the configuration
file): matrices N(0, 1/fan_in) in the dtype the configuration states
(the embedding N(0, 1/hidden), as the head it also is); LayerNorm scale
1 and bias 0; the projections' biases
and the convolution's N(0, BIAS^2); the differential attention's four
lambda vectors N(0, LAMBDA^2) and its sub-norm scale 1; the state-space
mixer's own initialisation, since a layer whose step size or decay is
near 0 passes or fails for no reason: ``A_log = log(1..d_state)`` a
channel, ``b_dt`` the inverse softplus of a step size log-uniform in
[DT_MIN, DT_MAX], ``D = 1``.

Sizes the published ``config.json`` does not carry (its model file
hard-codes them) are ``sizes``' constants: ``d_state`` 16, ``d_conv`` 4,
``expand`` 2, ``dt_rank = ceil(hidden / 16)``.

``cfg`` is the configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _normal, dtype_of, round_to, seed_key  # noqa: F401

BIAS, LAMBDA = 0.02, 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1
_EMBED = 1 << 20                    # fold-in tag beside the layers
F32 = jnp.float32


def sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"D": d, "H": h, "Hkv": cfg["num_key_value_heads"],
            "Dh": d // h, "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "C": cfg.get("ssm_expand", 2) * d,
            "N": cfg.get("ssm_d_state", 16), "K": cfg.get("ssm_d_conv", 4),
            "R": cfg.get("ssm_dt_rank") or math.ceil(d / 16),
            "window": cfg["sliding_window"],
            "period": cfg["mb_per_layer"]}


def kinds(cfg: dict) -> list[str]:
    """The kind of every layer, from the published keys: the first half
    and the layer after it alternate state-space (even) and window
    attention (odd); layer L/2 + 1 attends everything and keeps the
    shared K/V; the rest alternate gated memory units (even) and
    cross-attention (odd)."""
    z = sizes(cfg)
    half, out = z["L"] // 2, []
    for i in range(z["L"]):
        even = i % z["period"] == 0
        if i <= half:
            out.append("ssm" if even else "window")
        elif i == half + 1:
            out.append("full")
        else:
            out.append("gmu" if even else "cross")
    return out


def _small(key, shape, std):
    return std * jax.random.normal(key, shape, F32)


def layer_weights(key, layer, cfg: dict, kind: str) -> dict:
    """One layer of ``kind`` in the natural order; ``layer`` (its index
    in the model) may be traced.  Every layer: ``norm1_*``, ``norm2_*``
    and the SwiGLU ``w_gate`` / ``w_up`` (the published ``W_1`` is the
    two side by side) / ``w_down``."""
    z, dt = sizes(cfg), dtype_of(cfg)
    d, c, n, f = z["D"], z["C"], z["N"], z["F"]
    q, kv = z["H"] * z["Dh"], z["Hkv"] * z["Dh"]
    ks = iter(jax.random.split(jax.random.fold_in(key, layer), 24))
    mat = lambda shape: _normal(next(ks), shape, shape[0], dt)
    one = lambda w: jnp.ones((w,), F32)
    out = {"norm1_scale": one(d), "norm1_bias": jnp.zeros((d,), F32),
           "norm2_scale": one(d), "norm2_bias": jnp.zeros((d,), F32),
           "w_gate": mat((d, f)), "w_up": mat((d, f)),
           "w_down": mat((f, d))}
    if kind == "ssm":
        step = jnp.exp(jax.random.uniform(
            next(ks), (c,), F32, math.log(DT_MIN), math.log(DT_MAX)))
        out.update(
            w_in=mat((d, 2 * c)),                       # [x | z]
            conv_w=_normal(next(ks), (z["K"], c), z["K"], dt),
            conv_b=_small(next(ks), (c,), BIAS),
            w_x=mat((c, z["R"] + 2 * n)),               # [delta | B | C]
            w_dt=mat((z["R"], c)),
            b_dt=step + jnp.log(-jnp.expm1(-step)),     # softplus^-1
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=F32)), (c, n)),
            D=one(c), w_out=mat((c, d)))
    elif kind in ("window", "full", "cross"):
        out.update(wq=mat((d, q)), bq=_small(next(ks), (q,), BIAS),
                   wo=mat((q, d)), bo=_small(next(ks), (d,), BIAS),
                   subln=one(2 * z["Dh"]),
                   **{name: _small(next(ks), (z["Dh"],), LAMBDA)
                      for name in ("lambda_q1", "lambda_k1",
                                   "lambda_q2", "lambda_k2")})
        if kind != "cross":
            out.update(wk=mat((d, kv)), bk=_small(next(ks), (kv,), BIAS),
                       wv=mat((d, kv)), bv=_small(next(ks), (kv,), BIAS))
    elif kind == "gmu":
        out.update(w_in=mat((d, c)), w_out=mat((c, d)))
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return out


def embed_weights(key, cfg: dict):
    z = sizes(cfg)
    # N(0, 1/D), a head's scale: the head is this matrix, and at N(0, 1)
    # a token's own embedding would dominate its logits through the
    # residual stream (every position's best token the input token, by
    # hundreds: seen on the chip, where every control then read 0.0)
    return _normal(jax.random.fold_in(key, _EMBED), (z["V"], z["D"]),
                   z["D"], dtype_of(cfg))


def program_layer(w: dict, cfg: dict, kind: str) -> dict:
    """A natural-order layer in the program's layout: the state-space
    mixer's ``A_log`` with the channels minor; an attention layer's
    query heads four a KV pair, ``[q1 | q1' | q2 | q2']`` (natural heads
    ``4j, 4j + 2, 4j + 1, 4j + 3``: pair ``p`` is heads ``2p, 2p + 1``
    and reads KV pair ``p // 2``), and K and V side by side (a KV pair
    ``[k1 | k2]`` is two neighbouring natural heads already)."""
    z = sizes(cfg)
    w = dict(w)
    if kind == "ssm":
        w["A_log"] = w["A_log"].T
    if "wq" in w:
        order = lambda a: a.reshape(a.shape[:-1] + (z["Hkv"] // 2, 4,
                                                    z["Dh"]))[
            ..., jnp.array([0, 2, 1, 3]), :].reshape(a.shape)
        w["wq"], w["bq"] = order(w["wq"]), order(w["bq"])
    if "wk" in w:
        w["wkv"] = jnp.concatenate([w.pop("wk"), w.pop("wv")], axis=1)
        w["bkv"] = jnp.concatenate([w.pop("bk"), w.pop("bv")])
    return w


def make_weights(key, cfg: dict) -> dict:
    """The whole tree in the program's layout (jit this; ``key`` is an
    argument so that every seed shares one compiled program)."""
    z, ks = sizes(cfg), kinds(cfg)
    mid = ks.index("full") - 1

    def pairs(first, n, a, b):
        def one(i):
            l = first + 2 * i
            return {a: program_layer(layer_weights(key, l, cfg, a), cfg, a),
                    b: program_layer(layer_weights(key, l + 1, cfg, b),
                                     cfg, b)}
        return jax.lax.map(one, jnp.arange(n))

    return {"embed": embed_weights(key, cfg),
            "self_pairs": pairs(0, mid // 2, "ssm", "window"),
            "mid": {"ssm": program_layer(
                        layer_weights(key, mid, cfg, "ssm"), cfg, "ssm"),
                    "full": program_layer(
                        layer_weights(key, mid + 1, cfg, "full"), cfg,
                        "full")},
            "cross_pairs": pairs(mid + 2, (z["L"] - mid - 2) // 2, "gmu",
                                 "cross"),
            "final_norm_scale": jnp.ones((z["D"],), F32),
            "final_norm_bias": jnp.zeros((z["D"],), F32)}
