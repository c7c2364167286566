"""The plain reference for JoyAI-LLM-Flash: its forward pass in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision.  No kernels, no cache, no absorbed attention, no batching, no
grouped matmul.  It imports nothing of the program and takes nothing
the program made: weights come from ``joyai_weights.py`` and the seed,
layer by layer.

Published description followed (the model's ``config.json`` and the
DeepSeek-V3 equations it instantiates; all norms RMSNorm):

* attention (MLA): ``c_q = norm(x W_qa)``; ``q = c_q W_qb``, per head
  ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv =
  norm(c_kv)``, ``k_r = RoPE(k_r)`` one head shared by all,
  ``q_rope = RoPE(q_rope)``; ``[k_nope | v] = c_kv W_kvb`` per head;
  scores ``(q_nope.k_nope + q_rope.k_r) / sqrt(dn + dr)``, causal
  softmax, ``o = sum p v`` -> ``W_o``.  No mscale (``rope_scaling``
  null).
* layers below ``first_k_dense_replace``: a dense SwiGLU of width
  ``intermediate_size``; the others: ``s = sigmoid(x W_r)``, the top
  ``num_experts_per_tok`` of ``s + b`` chosen (no group limit:
  ``n_group`` 1), gates the chosen ``s`` over their sum (+1e-20) times
  ``routed_scaling_factor``, ``y = sum g_i E_i(x) + E_shared(x)``.
  Every expert runs over every token and the gate of a token that did
  not choose it is zero: the mask form of "each expert over the tokens
  that chose it", one expert at a time so that it fits.

Departures, each noted where it is made: parameters are *stored* in the
configuration's dtype and every operation on them is float32; norm
scales are ones; RoPE pairs (j, j + half) where the checkpoint
interleaves (a permutation of random columns: ``joyai_weights.py``);
the multi-token-prediction layer is left out (next-token logits do not
depend on it).

``q`` is the control's switch as in ``reference.py``: ``None`` for the
reference itself, ``fp8`` to round the operands of every linear layer,
the router's among them, to float8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import joyai_weights as W
from .reference import F32, HI, _f32, fp8, mm, rms, rope  # noqa: F401


def attention(q, k, v):
    """q, k: (S, H, dq); v: (S, H, dv); causal, one head at a time so
    that the (S, S) scores fit."""
    s, _, dq = q.shape
    i = jnp.arange(s)
    keep = i[None, :] <= i[:, None]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                       # (S, dq), (S, dq), (S, dv)
        sc = jnp.einsum("sd,td->st", qh, kh, precision=HI) * dq ** -0.5
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), -1)
        return jnp.einsum("st,td->sd", p, vh, precision=HI)

    o = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return o.transpose(1, 0, 2).reshape(s, -1)


def mla(x, w, cfg, q=None):
    """The attention half of a layer on one sequence.  x: (S, D)."""
    z, eps, theta = W.sizes(cfg), cfg["rms_norm_eps"], cfg["rope_theta"]
    s, h, dn, r = x.shape[0], z["H"], z["dn"], z["r"]
    one = lambda n: jnp.ones((n,), F32)         # norm scales are ones
    hx = rms(x, one(z["D"]), eps)
    c_q = rms(mm(hx, w["w_qa"], q), one(z["rq"]), eps)
    qh = mm(c_q, w["w_qb"], q).reshape(s, h, dn + z["dr"])
    kva = mm(hx, w["w_kva"], q)
    c_kv = rms(kva[:, :r], one(r), eps)
    k_r = rope(kva[:, None, r:], theta)         # (S, 1, dr): one head
    qh = jnp.concatenate([qh[..., :dn], rope(qh[..., dn:], theta)], -1)
    kvb = mm(c_kv, w["w_kvb"], q).reshape(s, h, dn + z["dv"])
    kh = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_r, (s, h, z["dr"]))], -1)
    return x + mm(attention(qh, kh, kvb[..., dn:]), w["wo"], q)


def swiglu(h, w, q=None):
    return mm(jax.nn.silu(mm(h, w["w_gate"], q)) * mm(h, w["w_up"], q),
              w["w_down"], q)


def route(h, w, cfg, q=None):
    """-> gates (S, E) float32, zero where an expert was not chosen,
    and the margin (S,) between the last score chosen and the first
    passed over (scores with the bias: what the choice is made on)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(mm(h, w["router"], q))
    top, idx = jax.lax.top_k(s + w["bias"], k + 1)
    g = jnp.take_along_axis(s, idx[:, :k], -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    rows = jnp.arange(s.shape[0])[:, None]
    return (jnp.zeros_like(s).at[rows, idx[:, :k]].set(g),
            top[:, k - 1] - top[:, k])


def experts(h, ew, gates, q=None):
    """sum_e gates[:, e] * E_e(h), one expert at a time.  ``ew``: the
    layer's experts as stored (leading E axis).  Every expert runs over
    every token and the gate of a token that did not choose it is zero
    (the mask form of "each expert over the tokens that chose it": a
    sequence's tokens crowd single experts, 1,429 of 4,608 on one in a
    chip run, so a gather with less room than all would not do)."""
    def one(acc, inp):
        w, g = inp
        return acc + g[:, None] * swiglu(h, _f32(w), q), None

    return jax.lax.scan(one, jnp.zeros_like(h), (ew, gates.T))[0]


def dense_block(x, w, cfg, q=None):
    x = mla(x, w, cfg, q)
    h = rms(x, jnp.ones((x.shape[-1],), F32), cfg["rms_norm_eps"])
    return x + swiglu(h, w, q)


def expert_block(x, w, cfg, q=None):
    """-> (x, the routing margin of every position)."""
    x = mla(x, w, cfg, q)
    h = rms(x, jnp.ones((x.shape[-1],), F32), cfg["rms_norm_eps"])
    gates, margin = route(h, w, cfg, q)
    return (x + experts(h, w["experts"], gates, q)
            + swiglu(h, w["shared"], q), margin)


def forward(seed: int, cfg: dict, toks, at=None, q=None):
    """toks (R, S) -> (logits float32 (R, S, V), or (R, n, V) at the
    positions ``at`` (R, n); the smallest routing margin over the
    expert layers, (R, S)).  Layer by layer, each layer's weights made
    from the seed and dropped again, one sequence at a time."""
    key = W.seed_key(seed)
    z = W.sizes(cfg)

    @jax.jit
    def embed(key, toks):
        return W.embed_weights(key, cfg).astype(F32)[toks]

    @functools.partial(jax.jit, static_argnames=("q",), donate_argnums=(2,))
    def dense_layer(key, l, x, q):
        w = _f32({**W.attention_weights(key, l, cfg),
                  **W.dense_weights(key, l, cfg)})
        return jax.lax.map(lambda row: dense_block(row, w, cfg, q), x)

    @functools.partial(jax.jit, static_argnames=("q",), donate_argnums=(2,))
    def expert_layer(key, l, x, q):
        w = _f32({**W.attention_weights(key, l, cfg),
                  **W.router_weights(key, l, cfg),
                  "shared": W.shared_weights(key, l, cfg)})
        # the routed experts stay as stored; one at a time is taken to
        # float32 inside ``experts``
        w["experts"] = W.expert_weights(key, l, cfg)
        return jax.lax.map(lambda row: expert_block(row, w, cfg, q), x)

    @functools.partial(jax.jit, static_argnames=("q",))
    def logits(key, x, q):
        h = rms(x, jnp.ones((x.shape[-1],), F32), cfg["rms_norm_eps"])
        return mm(h, W.head_weights(key, cfg).astype(F32), q)

    # One sequence a call (a Python loop over the rows, each a batch of
    # one): three rows under one ``lax.map`` did not come back in a
    # quarter of an hour on the chip, where one row takes 3 s (PERF.md,
    # PR 27).
    toks = jnp.asarray(toks)
    out, margins = [], []
    for r in range(toks.shape[0]):
        x = embed(key, toks[r:r + 1])
        margin = jnp.full((1, toks.shape[1]), jnp.inf, F32)
        for l in range(z["L"]):
            if l < z["Ld"]:
                x = dense_layer(key, l, x, q)
                continue
            x, m = expert_layer(key, l, x, q)
            margin = jnp.minimum(margin, m)
        if at is not None:
            x = jnp.take_along_axis(x, jnp.asarray(at)[r:r + 1, :, None], 1)
        out.append(logits(key, x, q))
        margins.append(margin)
    return jnp.concatenate(out), jnp.concatenate(margins)


def served_logit_gaps(seed: int, cfg: dict, pairs, pad_to: int,
                      q=None, control=None) -> dict:
    """``pairs``: (prompt, served tokens) of the sampled requests.  One
    full forward over each prompt with its served tokens.

    Returns ``gap``: by how much the served token's logit lies below
    the reference's best, at every served position (``q`` must be None
    for that: it is the reference that judges), and ``margin``: at the
    same positions, the smallest distance over the expert layers
    between the last expert chosen and the first passed over (where it
    is tiny, bfloat16 rounding may choose otherwise, and a token served
    from another expert set is no fault).  With ``control`` (a rounding
    function) also ``control_gap``: the same gap for the token the
    lower precision puts first at each position.
    """
    n = max(len(s) for _, s in pairs)
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

    ref, margin = forward(seed, cfg, toks, at, q)
    best = ref.max(-1)
    pick = lambda t: jnp.take_along_axis(ref, jnp.asarray(t)[:, :, None],
                                         -1)[..., 0]
    out = {"gap": np.asarray(best - pick(served))[live],
           "margin": np.asarray(jnp.take_along_axis(
               margin, jnp.asarray(at), 1))[live]}
    if control is not None:
        first = np.asarray(forward(seed, cfg, toks, at, control)[0]
                           .argmax(-1))
        out["control_gap"] = np.asarray(best - pick(first))[live]
    return out
