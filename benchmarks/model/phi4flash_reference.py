"""The plain reference for Phi-4-mini-flash-reasoning: its forward pass
in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision.  No cache, no kernels, no chunked scan, no padded heads, no
batching: the recurrence runs token by token, attention is a full
masked softmax.  It imports nothing of the program and takes nothing
the program made: weights come from ``phi4flash_weights.py`` and the
seed, layer by layer, in the natural order.

The equations (every layer ``x + mixer(LN1(x))`` then ``x + W_down
(silu(W_gate h) * W_up h)``, ``h = LN2(x)``; LayerNorm with scale and
bias; no positional encoding; logits ``LN(x) E^T`` with ``E`` the
embedding):

* state-space (``mamba``): ``[x | z] = h W_in``; ``x = silu(conv(x) +
  b)``, depthwise and causal over ``d_conv`` inputs; ``[delta | B | C]
  = x W_x``; ``Delta = softplus(delta W_dt + b_dt)``; ``s_t = exp(Delta_t
  A) s_{t-1} + Delta_t B_t x_t`` a channel and state, ``A = -exp(A_log)``;
  ``y_t = C_t . s_t + D x_t``; out ``(y * silu(z)) W_out``.  The last
  such layer's ``y`` is the token's memory.
* differential attention (``diff_attention``): query heads ``2p, 2p+1``
  are pair ``p``'s ``q1, q2``; KV heads ``2j, 2j+1`` pair ``j``'s
  ``k1, k2`` and ``v1, v2``; pair ``p`` reads KV pair ``p // 2``;
  ``o = softmax(q1 k1^T / sqrt(Dh)) V - lambda softmax(q2 k2^T /
  sqrt(Dh)) V`` with ``V = [v1 | v2]``, ``lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3
  i)`` at layer ``i``; then ``rms(o) * subln * (1 - lambda_init)``,
  then ``W_o`` and its bias.  Causal; a window layer sees the last
  ``sliding_window`` positions.
* cross-attention: queries of its own, the keys and values of the one
  full-attention layer.
* gated memory unit: ``(m * silu(h W_in)) W_out``.

Departures, each noted where it is made: parameters are *stored* in the
configuration's dtype and every operation on them is float32.

``q`` is the control's switch as in ``reference.py``: ``None`` for the
reference itself, ``fp8`` to round the operands of every linear layer
to float8.  ``variant`` names the two controls the builder runs once:
``"state_bf16"`` keeps the recurrent state in bfloat16, ``"gmu_gated"``
hands the memory units the gated output in place of ``y``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import phi4flash_weights as W
from .reference import F32, HI, _f32, fp8, mm  # noqa: F401

LOGIT_BLOCK = 256       # positions whose logits exist at once


def ln(x, w, name, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w[name + "_scale"]
            + w[name + "_bias"])


def mamba(h, w, cfg, q=None, variant=None):
    """h (S, D) -> (out (S, D), the memory handed on (S, C))."""
    z = W.sizes(cfg)
    c, n, r, k = z["C"], z["N"], z["R"], z["K"]
    s = h.shape[0]
    xz = mm(h, w["w_in"], q)
    x, gate = xz[:, :c], xz[:, c:]
    pad = jnp.concatenate([jnp.zeros((k - 1, c), F32), x])
    x = jax.nn.silu(sum(pad[j:j + s] * w["conv_w"][j] for j in range(k))
                    + w["conv_b"])
    dbc = mm(x, w["w_x"], q)
    delta = jax.nn.softplus(mm(dbc[:, :r], w["w_dt"], q) + w["b_dt"])
    a = -jnp.exp(w["A_log"])                            # (C, N)

    def token(state, inp):
        d, b, cc, xt = inp              # (C,), (N,), (N,), (C,)
        state = (jnp.exp(d[:, None] * a) * state
                 + (d * xt)[:, None] * b[None, :])
        if variant == "state_bf16":
            state = state.astype(jnp.bfloat16).astype(F32)
        return state, jnp.sum(state * cc[None, :], -1)    # exact float32

    _, y = jax.lax.scan(token, jnp.zeros((c, n), F32),
                        (delta, dbc[:, r:r + n], dbc[:, r + n:], x))
    y = y + w["D"] * x
    gated = y * jax.nn.silu(gate)
    return mm(gated, w["w_out"], q), (gated if variant == "gmu_gated"
                                      else y)


def diff_attention(qh, kh, vh, w, layer, cfg, window=None):
    """qh (S, H, Dh); kh, vh (T, Hkv, Dh), T = S -> (S, H * Dh)."""
    s, h, dh = qh.shape
    i = jnp.arange(s)
    keep = i[None, :] <= i[:, None]
    if window:
        keep &= i[None, :] > i[:, None] - window
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam_init)

    def soft(qq, kk, vv):
        sc = jnp.einsum("sd,td->st", qq, kk, precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), -1)
        return jnp.einsum("st,td->sd", p, vv, precision=HI)

    def pair(p):
        j = p // 2
        v = jnp.concatenate([vh[:, 2 * j], vh[:, 2 * j + 1]], -1)
        o = (soft(qh[:, 2 * p], kh[:, 2 * j], v)
             - lam * soft(qh[:, 2 * p + 1], kh[:, 2 * j + 1], v))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg["layer_norm_eps"])
        return o * w["subln"] * (1.0 - lam_init)

    o = jax.lax.map(pair, jnp.arange(h // 2))           # (H/2, S, 2Dh)
    return o.transpose(1, 0, 2).reshape(s, h * dh)


def mlp(x, w, cfg, q=None):
    h = ln(x, w, "norm2", cfg["layer_norm_eps"])
    return x + mm(jax.nn.silu(mm(h, w["w_gate"], q)) * mm(h, w["w_up"], q),
                  w["w_down"], q)


def block(x, shared, w, layer, kind: str, cfg, q=None, variant=None):
    """One layer on one sequence.  x (S, D); ``shared``: what earlier
    layers handed on (``memory``, ``k``, ``v``).  -> (x, shared)."""
    z = W.sizes(cfg)
    s = x.shape[0]
    h = ln(x, w, "norm1", cfg["layer_norm_eps"])
    if kind == "ssm":
        out, memory = mamba(h, w, cfg, q, variant)
        shared = {**shared, "memory": memory}   # the last one's is read
    elif kind == "gmu":
        out = mm(shared["memory"] * jax.nn.silu(mm(h, w["w_in"], q)),
                 w["w_out"], q)
    else:
        qh = (mm(h, w["wq"], q) + w["bq"]).reshape(s, z["H"], z["Dh"])
        if kind != "cross":
            k = (mm(h, w["wk"], q) + w["bk"]).reshape(s, z["Hkv"], z["Dh"])
            v = (mm(h, w["wv"], q) + w["bv"]).reshape(s, z["Hkv"], z["Dh"])
            if kind == "full":
                shared = {**shared, "k": k, "v": v}
        else:
            k, v = shared["k"], shared["v"]
        out = mm(diff_attention(qh, k, v, w, layer, cfg,
                                z["window"] if kind == "window" else None),
                 w["wo"], q) + w["bo"]
    return mlp(x + out, w, cfg, q), shared


def forward_hidden(seed: int, cfg: dict, toks, at=None, q=None,
                   variant=None):
    """toks (R, S) -> the last layer's output after the final LayerNorm,
    float32 (R, S, D), or (R, n, D) at the positions ``at`` (R, n).
    Layer by layer, each layer's weights made from the seed and dropped
    again, one sequence at a time."""
    key = W.seed_key(seed)
    z, ks = W.sizes(cfg), W.kinds(cfg)

    @jax.jit
    def embed(key, toks):
        return W.embed_weights(key, cfg).astype(F32)[toks]

    # ``layer`` is traced: one compiled program a kind of layer
    @functools.partial(jax.jit, static_argnames=("kind", "q", "variant"))
    def run(key, x, shared, layer, kind, q, variant):
        w = _f32(W.layer_weights(key, layer, cfg, kind))
        return block(x, shared, w, layer, kind, cfg, q, variant)

    toks = jnp.asarray(toks)
    out = []
    for r in range(toks.shape[0]):
        x = embed(key, toks[r])
        s = x.shape[0]
        shared = {"memory": jnp.zeros((s, z["C"]), F32),
                  "k": jnp.zeros((s, z["Hkv"], z["Dh"]), F32),
                  "v": jnp.zeros((s, z["Hkv"], z["Dh"]), F32)}
        for layer in range(z["L"]):
            x, shared = run(key, x, shared, layer, ks[layer], q, variant)
        if at is not None:
            x = x[jnp.asarray(at)[r]]
        final = {"f_scale": jnp.ones((z["D"],), F32),
                 "f_bias": jnp.zeros((z["D"],), F32)}
        out.append(ln(x, final, "f", cfg["layer_norm_eps"]))
    return jnp.stack(out)


def forward(seed: int, cfg: dict, toks, q=None, variant=None):
    """toks (R, S) -> logits float32 (R, S, V), all at once: for the
    sizes of a test, not of the cell."""
    h = forward_hidden(seed, cfg, toks, None, q, variant)
    e = W.embed_weights(W.seed_key(seed), cfg).astype(F32)
    return mm(h, e.T, q)


def served_logit_gaps(seed: int, cfg: dict, pairs, pad_to: int,
                      control=None, variant=None) -> dict:
    """``pairs``: (prompt, served tokens) of the sampled requests.  One
    full forward over each prompt with its served tokens.

    Returns ``gap``: by how much the served token's logit lies below
    the reference's best, at every served position.  With ``control``
    (a rounding function) or ``variant`` also ``control_gap``: the same
    gap for the token the control puts first at each position.  Logits
    exist ``LOGIT_BLOCK`` positions at a time."""
    n = max(len(s) for _, s in pairs)
    n = -(-n // LOGIT_BLOCK) * LOGIT_BLOCK
    toks = np.zeros((len(pairs), pad_to), np.int32)
    at = np.zeros((len(pairs), n), np.int32)
    live = np.zeros((len(pairs), n), bool)
    served = np.zeros_like(at)
    for r, (prompt, out) in enumerate(pairs):
        seq = list(prompt) + list(out)
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} tokens, pad_to {pad_to}")
        toks[r, :len(seq)] = seq
        at[r, :len(out)] = len(prompt) - 1 + np.arange(len(out))
        live[r, :len(out)] = True
        served[r, :len(out)] = out

    controlled = control is not None or variant is not None
    h = forward_hidden(seed, cfg, toks, at)
    hc = (forward_hidden(seed, cfg, toks, at, control, variant)
          if controlled else h)

    @functools.partial(jax.jit, static_argnames=("q",))
    def stats(key, h, hc, served, q):
        e = W.embed_weights(key, cfg).astype(F32).T
        ref = mm(h, e)
        pick = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
        first = jnp.argmax(mm(hc, e, q), -1)
        best = ref.max(-1)
        return best - pick(served), best - pick(first)

    key = W.seed_key(seed)
    gap, cgap = np.zeros(at.shape, np.float32), np.zeros(at.shape,
                                                          np.float32)
    for r in range(len(pairs)):
        for b in range(0, n, LOGIT_BLOCK):
            cut = slice(b, b + LOGIT_BLOCK)
            if not live[r, cut].any():
                break
            g, c = stats(key, h[r, cut], hc[r, cut],
                         jnp.asarray(served[r, cut]), control)
            gap[r, cut], cgap[r, cut] = np.asarray(g), np.asarray(c)
    out = {"gap": gap[live]}
    if controlled:
        out["control_gap"] = cgap[live]
    return out
