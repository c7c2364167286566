"""The plain reference: Mistral's forward pass, its loss and gradients
and AdamW, in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision.  No kernels, no cache, no batching.  It imports nothing
of the program and takes nothing the program made: weights come from
``weights.py`` and the seed.

Published description followed: pre-norm blocks, RMSNorm, rotary
embedding on the two halves of each head (theta ``rope_theta``),
grouped-query attention under a causal mask limited to the last
``sliding_window`` keys, SwiGLU, untied head.  Departures, each noted
where it is made: parameters are *stored* in the configuration's dtype
(bfloat16 matrices, float32 norm scales) and every operation on them is
float32; the training reference follows two optimizer steps and the
third step's loss, not three steps (time and memory), and one step and
the second's loss where a traffic file says ``reference_steps: 1``.

``q`` on every function is the control's switch: ``None`` for the
reference itself, ``fp8`` to round the operands of every linear layer to
float8 (e4m3, one scale per tensor) — the nearest precision below the
bfloat16 the configuration states, the step that would tempt a later PR.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "wd": 1e-4}


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient
    passes straight through (the mildest form of an fp8 linear layer)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(r - x)


def mm(a, b, q=None):
    if q is not None:
        a, b = q(a), q(b)
    return jnp.matmul(a, b, precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (S, heads, Dh); position i rotates pair (j, j + Dh/2) by
    i * theta**(-2j/Dh)."""
    s, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(q, k, v, window):
    """q: (S, H, Dh); k, v: (S, Hkv, Dh).  One KV head's group of query
    heads at a time, so that the (S, S) scores fit."""
    s, h, dh = q.shape
    hkv = k.shape[1]
    i = jnp.arange(s)
    keep = i[None, :] <= i[:, None]
    if window:
        keep &= i[None, :] > i[:, None] - window
    qg = q.reshape(s, hkv, h // hkv, dh).transpose(1, 2, 0, 3)

    @jax.checkpoint
    def group(args):
        qh, kh, vh = args           # (G, S, Dh), (S, Dh), (S, Dh)
        sc = jnp.einsum("gsd,td->gst", qh, kh, precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
        return jnp.einsum("gst,td->gsd", p, vh, precision=HI)

    o = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(2, 0, 1, 3).reshape(s, h * dh)


def block(x, w, cfg, q=None):
    """One decoder layer on one sequence.  x: (S, D) float32; w: that
    layer's matrices and norm scales, float32."""
    z, eps = W.sizes(cfg), cfg["rms_norm_eps"]
    s = x.shape[0]
    h = rms(x, w["attn_norm"], eps)
    qh = rope(mm(h, w["wq"], q).reshape(s, z["H"], z["Dh"]),
              cfg["rope_theta"])
    kh = rope(mm(h, w["wk"], q).reshape(s, z["Hkv"], z["Dh"]),
              cfg["rope_theta"])
    vh = mm(h, w["wv"], q).reshape(s, z["Hkv"], z["Dh"])
    x = x + mm(attention(qh, kh, vh, cfg.get("sliding_window")), w["wo"], q)
    h = rms(x, w["mlp_norm"], eps)
    gated = jax.nn.silu(mm(h, w["w_gate"], q)) * mm(h, w["w_up"], q)
    return x + mm(gated, w["w_down"], q)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


# ----------------------------------------------------------------------
# training: loss, gradients, AdamW


def row_loss(p32, row, cfg, q=None):
    """Next-token cross-entropy of one sequence (mean over S-1 targets)."""
    x = p32["embed"][row]

    def body(x, w):
        return jax.checkpoint(lambda x, w: block(x, w, cfg, q))(x, w), None

    x, _ = jax.lax.scan(body, x, p32["layers"])
    x = rms(x, p32["final_norm"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm(x[:-1], p32["lm_head"], q), -1)
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], -1))


@functools.partial(jax.jit, static_argnames=("t", "to"), donate_argnums=(0,))
def _adamw_leaf(p, gs, t, to):
    """The leaf after step ``t`` of AdamW from the gradients of steps
    1..t (moments rebuilt from them, float32); stored rounded to ``to``."""
    a = ADAMW
    mu = sum(a["b1"] ** (t - 1 - i) * (1 - a["b1"]) * g
             for i, g in enumerate(gs))
    nu = sum(a["b2"] ** (t - 1 - i) * (1 - a["b2"]) * g * g
             for i, g in enumerate(gs))
    mhat, vhat = mu / (1 - a["b1"] ** t), nu / (1 - a["b2"] ** t)
    new = p - a["lr"] * (mhat / (jnp.sqrt(vhat) + a["eps"]) + a["wd"] * p)
    return W.round_to(new, to)


def _norm(leaf) -> float:
    if isinstance(leaf, np.ndarray):        # a sum kept off the chip
        return float(np.sqrt(np.sum(np.square(leaf, dtype=np.float64))))
    return float(jnp.sqrt(jnp.sum(jnp.square(leaf.astype(F32)))))


def leaf_norms(tree) -> dict:
    """Per-leaf L2 norms, by the leaf's path (``layers/wq``)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): _norm(leaf)
            for path, leaf in flat}


def train_reference(seed: int, cfg: dict, batches, q=None,
                    steps: int = 2) -> dict:
    """Follow the first steps from the seed.  ``batches``: int arrays
    (rows, S), the rows the program's first steps were fed.  Takes
    ``steps`` (1 or 2) optimizer steps and then one more loss.  Returns
    each of those losses, the per-leaf norms of the first gradient, and
    of the parameters' change after ``steps`` steps."""
    key = W.seed_key(seed)
    stored = jax.jit(functools.partial(W.make_weights, cfg=cfg))(key)
    dtypes = jax.tree.map(lambda a: a.dtype, stored)
    p = jax.jit(_f32, donate_argnums=(0,))(stored)
    grad = jax.jit(jax.value_and_grad(
        functools.partial(row_loss, cfg=cfg, q=q)))

    def loss_and_grad(p, rows):
        """Mean loss and gradient over the rows, one row at a time; with
        several rows the sum is kept off the chip, which holds the
        parameters, one row's gradient and its activations."""
        if len(rows) == 1:
            l, g = grad(p, jnp.asarray(rows[0]))
            return float(l), g
        tot, acc = 0.0, None
        for row in rows:
            l, g = grad(p, jnp.asarray(row))
            g = jax.tree.map(np.asarray, g)
            tot += float(l)
            acc = g if acc is None else jax.tree.map(np.add, acc, g)
        n = len(rows)
        return tot / n, jax.tree.map(lambda x: x / np.float32(n), acc)

    def update(p, grads_by_step, t):
        flat_p, tree = jax.tree.flatten(p)
        flat_g = [jax.tree.leaves(g) for g in grads_by_step]
        flat_d = jax.tree.leaves(dtypes)
        out = [_adamw_leaf(leaf, tuple(jnp.asarray(g[i]) for g in flat_g),
                           t, np.dtype(flat_d[i]).name)
               for i, leaf in enumerate(flat_p)]
        return jax.tree.unflatten(tree, out)

    loss1, g1 = loss_and_grad(p, batches[0])
    grad_norms = leaf_norms(g1)
    g1_host = jax.tree.map(np.asarray, g1)      # moments live off the chip
    p = update(p, [g1], 1)
    del g1
    losses = [loss1]
    if steps == 2:
        loss2, g2 = loss_and_grad(p, batches[1])
        p = update(p, [g1_host, g2], 2)
        losses.append(loss2)
        del g2
    del g1_host
    first = jax.jit(_f32)(jax.jit(functools.partial(
        W.make_weights, cfg=cfg))(key))
    diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b),
                   donate_argnums=(1,))
    change = leaf_norms(diff(p, first))
    loss_only = jax.jit(functools.partial(row_loss, cfg=cfg, q=q))
    last = batches[steps]
    losses.append(sum(float(loss_only(p, jnp.asarray(r)))
                      for r in last) / len(last))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def compare_train(got: dict, ref: dict) -> dict:
    """The numbers a training cell is judged by: the widest relative gap
    of a step's loss, and for the first gradient and the parameters'
    change the gap between the two norms of the worst leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    out = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                           zip(got["losses"], ref["losses"]))}
    for name in ("grad_norms", "change_norms"):
        floor = float(np.median(list(ref[name].values())))
        out[name[:-6] + "_gap"] = max(
            abs(got[name][k] - r) / max(r, floor)
            for k, r in ref[name].items())
    return out


# ----------------------------------------------------------------------
# serving: teacher-forced logits at the served positions


def served_logit_gaps(seed: int, cfg: dict, pairs, pad_to: int,
                      q=None, control=None) -> dict:
    """``pairs``: (prompt, served tokens) of the sampled requests.  One
    full forward over each prompt with its served tokens, layer by
    layer (each layer's weights made from the seed and dropped again).

    Returns ``gap``: by how much the served token's logit lies below
    the reference's best, at every served position (``q`` must be None
    for that: it is the reference that judges).  With ``control`` (a
    rounding function) also ``control_gap``: the same gap for the token
    the lower precision puts first at each position.
    """
    key = W.seed_key(seed)
    n = max(len(s) for _, s in pairs)
    toks = np.zeros((len(pairs), pad_to), np.int32)
    at = np.zeros((len(pairs), n), np.int32)
    live = np.zeros((len(pairs), n), bool)
    for r, (prompt, served) in enumerate(pairs):
        seq = list(prompt) + list(served)
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} tokens, pad_to {pad_to}")
        toks[r, :len(seq)] = seq
        at[r, :len(served)] = len(prompt) - 1 + np.arange(len(served))
        live[r, :len(served)] = True

    @jax.jit
    def embed(key, toks):
        return W.embed_weights(key, cfg).astype(F32)[toks]

    @functools.partial(jax.jit, static_argnames=("q",), donate_argnums=(2,))
    def layer(key, l, x, q):
        w = _f32(W.layer_weights(key, l, cfg))
        w["attn_norm"] = w["mlp_norm"] = jnp.ones((x.shape[-1],), F32)
        return jax.lax.map(lambda row: block(row, w, cfg, q), x)

    @functools.partial(jax.jit, static_argnames=("q",))
    def logits(key, x, at, q):
        h = rms(jnp.take_along_axis(x, at[:, :, None], 1),
                jnp.ones((x.shape[-1],), F32), cfg["rms_norm_eps"])
        return mm(h, W.head_weights(key, cfg).astype(F32), q)

    def run(q):
        x = embed(key, toks)
        for l in range(W.sizes(cfg)["L"]):
            x = layer(key, l, x, q)
        return logits(key, x, jnp.asarray(at), q)

    ref = run(q)
    best = ref.max(-1)
    served = np.zeros_like(at)
    for r, (_, s) in enumerate(pairs):
        served[r, :len(s)] = s
    pick = lambda t: jnp.take_along_axis(ref, jnp.asarray(t)[:, :, None],
                                         -1)[..., 0]
    out = {"gap": np.asarray(best - pick(served))[live]}
    if control is not None:
        first = np.asarray(run(control).argmax(-1))
        out["control_gap"] = np.asarray(best - pick(first))[live]
    return out
