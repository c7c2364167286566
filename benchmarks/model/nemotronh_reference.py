"""The plain reference for NVIDIA-Nemotron-3-Nano-30B-A3B: its forward
pass in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision.  No cache, no kernels, no block form of the recurrence, no
grouped matmul, no batching: the recurrence runs token by token,
attention is a full masked softmax, the experts are a plain loop over
the experts held.  It imports nothing of the program and takes nothing
the program made: weights come from ``nemotronh_weights.py`` and the
seed, layer by layer, in the natural order.

The layers as published (``model_type: nemotron_h``; the order of kinds
is ``hybrid_override_pattern``, one letter a layer).  Every layer is
``x <- x + mixer(h)``, ``h = RMSNorm(x)`` (scale only, eps
``layer_norm_epsilon``), one mixer and nothing behind it; logits
``RMSNorm(x) W_head``, the head untied.

* ``M``, Mamba-2 (``mamba2``; ``d_inner`` = ``mamba_num_heads`` x
  ``mamba_head_dim``, not ``expand`` x hidden; G = ``n_groups``, N =
  ``ssm_state_size``): ``[z | xBC | dt] = h W_in`` (no bias);
  ``xBC = silu(conv(xBC) + b)``, depthwise, causal, ``conv_kernel``
  taps; ``xBC`` splits into ``x`` (heads x head_dim), ``B`` and ``C``
  (G x N each), head ``i`` reading group ``i // (heads / G)``;
  ``Delta = softplus(dt + dt_bias)`` a head, ``a = -exp(A_log)`` a
  head; ``s_t = exp(Delta_t a) s_{t-1} + Delta_t x_t (x) B_t`` (a state
  of head_dim x N a head), ``y_t = s_t C_t + D x_t``; ``y <- y *
  silu(z)``, then an RMS norm over each group of ``d_inner / G``
  channels with a learned scale, then ``W_out``.
* ``*``, attention: ``q, k, v = h W_q, h W_k, h W_v`` (no biases),
  causal ``softmax(q k^T / sqrt(head_dim)) v``, ``num_attention_heads /
  num_key_value_heads`` query heads a KV head, then ``W_o``.  No
  rotary embedding (a departure if the checkpoint's own file applies
  one: the configuration lists it under ``assumed``).
* ``E``, experts (``experts_block``): ``s = sigmoid(h W_r)`` in
  float32 over all the experts the router knows; the
  ``num_experts_per_tok`` with the largest ``s + e_score_correction_
  bias`` chosen (``n_group`` = ``topk_group`` = 1: no group limit);
  gates the chosen ``s`` over their sum (+1e-20; ``norm_topk_prob``)
  times ``routed_scaling_factor``; ``y = sum_i g_i W_down,i relu(W_up,i
  h)^2 + W_down,s relu(W_up,s h)^2``, no biases, no gate matrix.  The
  sum runs over the experts *held*, ``(first, count)`` of the router's:
  a choice that falls on another is that chip's to compute and adds
  nothing here.

Departures, each noted where it is made: parameters are *stored* in the
configuration's dtype and every operation on them is float32;
``time_step_limit`` is (0, inf), so the step size is not clamped.

``q`` is the control's switch as in ``reference.py``: ``None`` for the
reference itself, ``fp8`` to round the operands of every linear layer,
the router's among them, to float8.  ``variant`` names the control the
builder runs once: ``"state_bf16"`` keeps the recurrent state in
bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import nemotronh_weights as W
from .reference import F32, HI, _f32, attention, fp8, mm, rms  # noqa: F401

LOGIT_BLOCK = 256       # positions whose logits exist at once


def mamba2(h, w, cfg, q=None, variant=None, state=None):
    """h (S, D) -> (out (S, D), the state after the last token (Hm, P,
    N)).  ``state``: the state before the first (zeros by default)."""
    z, eps = W.sizes(cfg), cfg["layer_norm_epsilon"]
    c, wd, hm, p, g, n, k = (z["C"], z["W"], z["Hm"], z["P"], z["G"],
                             z["N"], z["K"])
    s = h.shape[0]
    zxd = mm(h, w["w_in"], q)
    gate, xbc, dt = zxd[:, :c], zxd[:, c:c + wd], zxd[:, c + wd:]
    pad = jnp.concatenate([jnp.zeros((k - 1, wd), F32), xbc])
    xbc = jax.nn.silu(sum(pad[j:j + s] * w["conv_w"][:, j]
                          for j in range(k)) + w["conv_b"])
    x = xbc[:, :c].reshape(s, hm, p)
    # head i reads group i // (hm / g)
    of_head = lambda m: jnp.repeat(m.reshape(s, g, n), hm // g, axis=1)
    b, cc = of_head(xbc[:, c:c + g * n]), of_head(xbc[:, c + g * n:])
    delta = jax.nn.softplus(dt + w["dt_bias"])          # (S, Hm)
    a = -jnp.exp(w["A_log"])                            # (Hm,)

    def token(st, inp):
        d, bt, ct, xt = inp         # (Hm,), (Hm, N), (Hm, N), (Hm, P)
        st = (jnp.exp(d * a)[:, None, None] * st
              + (d[:, None] * xt)[:, :, None] * bt[:, None, :])
        if variant == "state_bf16":
            st = st.astype(jnp.bfloat16).astype(F32)
        return st, jnp.sum(st * ct[:, None, :], -1)     # exact float32

    st0 = jnp.zeros((hm, p, n), F32) if state is None else state
    st, y = jax.lax.scan(token, st0, (delta, b, cc, x))
    y = (y + w["D"][:, None] * x).reshape(s, c) * jax.nn.silu(gate)
    yg = y.reshape(s, g, c // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    return mm(yg.reshape(s, c) * w["gate_norm"], w["w_out"], q), st


def attention_mixer(h, w, cfg, q=None):
    z = W.sizes(cfg)
    s = h.shape[0]
    heads = lambda name, n: mm(h, w[name], q).reshape(s, n, z["Dh"])
    o = attention(heads("wq", z["H"]), heads("wk", z["Hkv"]),
                  heads("wv", z["Hkv"]), None)
    return mm(o, w["wo"], q)


def relu2(h, w, q=None):
    return mm(jnp.square(jax.nn.relu(mm(h, w["w_up"], q))), w["w_down"], q)


def route(h, w, cfg, q=None):
    """-> gates (S, Er) float32 over all the experts the router knows,
    zero where an expert was not chosen, and the margin (S,) between
    the last score chosen and the first passed over (scores with the
    bias: what the choice is made on)."""
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
    """sum over the experts held of ``gates[:, e] * E_e(h)``, one
    expert at a time.  ``ew``: the experts held, as stored (leading
    axis); ``gates`` (S, held): their columns of the router's gates.
    Every expert held runs over every token and the gate of a token
    that did not choose it is zero."""
    def one(acc, inp):
        w, g = inp
        return acc + g[:, None] * relu2(h, _f32(w), q), None

    return jax.lax.scan(one, jnp.zeros_like(h), (ew, gates.T))[0]


def experts_block(h, w, cfg, q=None, held=None):
    """The mixer of an expert layer -> (y, the routing margin).
    ``w["experts"]`` holds the experts ``held = (first, count)`` (by
    default the configuration's share)."""
    z = W.sizes(cfg)
    first, count = held or (z["first"], z["E"])
    gates, margin = route(h, w, cfg, q)
    return (experts(h, w["experts"], gates[:, first:first + count], q)
            + relu2(h, w["shared"], q), margin)


def block(x, w, kind: str, cfg, q=None, variant=None):
    """One layer on one sequence -> (x, the routing margin or inf)."""
    h = rms(x, w["norm"], cfg["layer_norm_epsilon"])
    margin = jnp.full((x.shape[0],), jnp.inf, F32)
    if kind == "mamba2":
        out, _ = mamba2(h, w, cfg, q, variant)
    elif kind == "attention":
        out = attention_mixer(h, w, cfg, q)
    else:
        out, margin = experts_block(h, w, cfg, q)
    return x + out, margin


def forward_hidden(seed: int, cfg: dict, toks, at=None, q=None,
                   variant=None):
    """toks (R, S) -> (the last layer's output after the final norm,
    float32 (R, S, D), or (R, n, D) at the positions ``at`` (R, n);
    the smallest routing margin over the expert layers, there).  Layer
    by layer, each layer's weights made from the seed and dropped again,
    one sequence at a time."""
    key = W.seed_key(seed)
    ks = W.kinds(cfg)

    @jax.jit
    def embed(key, toks):
        return W.embed_weights(key, cfg).astype(F32)[toks]

    # ``layer`` is traced: one compiled program a kind of layer
    @functools.partial(jax.jit, static_argnames=("kind", "q", "variant"))
    def run(key, x, layer, kind, q, variant):
        w = _f32(W.layer_weights(key, layer, cfg, kind))
        if kind == "experts":
            # the routed experts stay as stored; one at a time is taken
            # to float32 inside ``experts``
            w["experts"] = W.expert_weights(key, layer, cfg)
        return block(x, w, kind, cfg, q, variant)

    toks = jnp.asarray(toks)
    out, margins = [], []
    for r in range(toks.shape[0]):
        x = embed(key, toks[r])
        margin = jnp.full((x.shape[0],), jnp.inf, F32)
        for layer, kind in enumerate(ks):
            x, m = run(key, x, layer, kind, q, variant)
            margin = jnp.minimum(margin, m)
        if at is not None:
            x, margin = x[jnp.asarray(at)[r]], margin[jnp.asarray(at)[r]]
        out.append(rms(x, jnp.ones((x.shape[-1],), F32),
                       cfg["layer_norm_epsilon"]))
        margins.append(margin)
    return jnp.stack(out), jnp.stack(margins)


def forward(seed: int, cfg: dict, toks, q=None, variant=None):
    """toks (R, S) -> logits float32 (R, S, V), all at once: for the
    sizes of a test, not of the cell."""
    h, _ = forward_hidden(seed, cfg, toks, None, q, variant)
    return mm(h, W.head_weights(W.seed_key(seed), cfg).astype(F32), q)


def served_logit_gaps(seed: int, cfg: dict, pairs, pad_to: int,
                      control=None, variant=None) -> dict:
    """``pairs``: (prompt, served tokens) of the sampled requests.  One
    full forward over each prompt with its served tokens.

    Returns ``gap``: by how much the served token's logit lies below
    the reference's best, at every served position, and ``margin``: at
    the same positions, the smallest distance over the expert layers
    between the last expert chosen and the first passed over (where it
    is tiny, bfloat16 rounding may choose otherwise, and a token served
    from another expert set is no fault).  With ``control`` (a rounding
    function) or ``variant`` also ``control_gap``: the same gap for the
    token the control puts first at each position.  Logits exist
    ``LOGIT_BLOCK`` positions at a time."""
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
    h, margin = forward_hidden(seed, cfg, toks, at)
    hc = (forward_hidden(seed, cfg, toks, at, control, variant)[0]
          if controlled else h)

    @functools.partial(jax.jit, static_argnames=("q",))
    def stats(key, h, hc, served, q):
        head = W.head_weights(key, cfg).astype(F32)
        ref = mm(h, head)
        pick = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
        first = jnp.argmax(mm(hc, head, q), -1)
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
    out = {"gap": gap[live], "margin": np.asarray(margin)[live]}
    if controlled:
        out["control_gap"] = cgap[live]
    return out
