"""The plain reference for SDAR-30B-A3B: its forward pass under the
block-causal mask and its generation loop, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision.  No kernels,
no cache, no batching, no grouped matmul, experts a loop.  It imports
nothing of the program and takes nothing the program made: weights come
from ``sdar_weights.py`` and the seed, layer by layer.

Published description followed (the model's ``config.json``,
``model_type: sdar_moe``, and the Qwen3-MoE layer it instantiates; all
norms RMSNorm):

* attention: ``q = x W_q``, ``k = x W_k``, ``v = x W_v`` with
  ``head_dim`` its own key; an RMS norm with a learned scale over each
  head's ``q`` and ``k``; rotary embedding on the two halves of a head
  (theta ``rope_theta``); grouped-query scores over
  ``sqrt(head_dim)``, softmax under the mask, ``W_o``.
* experts: ``p = softmax(x W_r)`` over ``num_experts`` in float32, the
  ``num_experts_per_tok`` largest renormalised to sum 1
  (``norm_topk_prob``), ``y = sum g_i E_i(x)``, SwiGLU experts, no
  shared expert, no dense layer.  Every expert runs over every token
  and the gate of a token that did not choose it is zero.
* the mask is block-causal: position ``i`` attends ``j`` where
  ``j // L <= i // L``.
* ``generate``: the prompt's whole blocks are context; a block starts
  as the prompt's remainder followed by ``[MASK]``; a pass takes at
  every open position ``x0 = argmax logits`` (the logits of a position
  predict that position's own token) and its confidence ``max
  softmax``, and fixes ``L / T`` open positions, the most confident
  first (``low_confidence_static``); when none is open the block is
  committed and the next begins.

Departures, each noted where it is made: parameters are *stored* in the
configuration's dtype and every operation on them is float32; the
layers' norm scales are ones and the per-head ones are drawn
(``sdar_weights.py``); a pass over a block with fewer open positions
than ``L / T`` fixes those that are open (the released loop's ``topk``
would then reach into positions already fixed); ties go to the lower
index; the loop runs each pass as a full forward over the sequence so
far and keeps no cache.

``q`` is the control's switch as in ``reference.py``: ``None`` for the
reference itself, ``fp8`` to round the operands of every linear layer,
the router's among them, to float8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import sdar_weights as W
from .joyai_reference import experts
from .reference import F32, HI, _f32, fp8, mm, rms  # noqa: F401

OPEN = 1 << 20      # ``when`` of a position no pass has fixed


def rope_at(x, pos, theta):
    """x: (S, heads, Dh) at positions ``pos`` (S,): pair (j, j + Dh/2)
    rotates by ``pos * theta**(-2j/Dh)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(q, k, v, keep):
    """q: (S, H, Dh); k, v: (S, Hkv, Dh); ``keep`` (S, S) the mask.
    One KV head's group of query heads at a time, so that the scores
    fit."""
    s, h, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, h // hkv, dh).transpose(1, 2, 0, 3)

    @jax.checkpoint
    def group(args):
        qh, kh, vh = args           # (G, S, Dh), (S, Dh), (S, Dh)
        sc = jnp.einsum("gsd,td->gst", qh, kh, precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
        return jnp.einsum("gst,td->gsd", p, vh, precision=HI)

    o = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(2, 0, 1, 3).reshape(s, h * dh)


def route(h, w, cfg, q=None):
    """-> gates (S, E) float32, zero where an expert was not chosen,
    and the margin (S,) between the last probability chosen and the
    first passed over."""
    k = cfg["num_experts_per_tok"]
    p = jax.nn.softmax(mm(h, w["router"], q), -1)
    top, idx = jax.lax.top_k(p, k + 1)
    g = top[:, :k] / jnp.sum(top[:, :k], -1, keepdims=True)
    rows = jnp.arange(p.shape[0])[:, None]
    return (jnp.zeros_like(p).at[rows, idx[:, :k]].set(g),
            top[:, k - 1] - top[:, k])


def layer(x, w, cfg, pos, keep, q=None):
    """One decoder layer on one sequence.  x: (S, D) float32 at the
    positions ``pos`` under the mask ``keep`` -> (x, the routing margin
    of every position)."""
    z, eps, theta = W.sizes(cfg), cfg["rms_norm_eps"], cfg["rope_theta"]
    s = x.shape[0]
    one = jnp.ones((z["D"],), F32)              # norm scales are ones
    h = rms(x, one, eps)
    qh = rms(mm(h, w["wq"], q).reshape(s, z["H"], z["Dh"]),
             w["q_norm"], eps)
    kh = rms(mm(h, w["wk"], q).reshape(s, z["Hkv"], z["Dh"]),
             w["k_norm"], eps)
    vh = mm(h, w["wv"], q).reshape(s, z["Hkv"], z["Dh"])
    x = x + mm(attention(rope_at(qh, pos, theta), rope_at(kh, pos, theta),
                         vh, keep), w["wo"], q)
    h = rms(x, one, eps)
    gates, margin = route(h, w, cfg, q)
    return x + experts(h, w["experts"], gates, q), margin


def block_causal(n: int, block: int):
    """(n, n) bool: query i sees key j where ``j // block <= i //
    block``."""
    b = jnp.arange(n) // block
    return b[None, :] <= b[:, None]


def noised_beside_clean(n: int, block: int):
    """The mask of a noised copy laid before a clean copy, ``2n``
    positions (the family's training layout): a noised block attends
    itself and the clean blocks before it; a clean block attends the
    clean blocks up to itself.  -> (positions (2n,), keep (2n, 2n))."""
    b = jnp.arange(n) // block
    same, before = b[None, :] == b[:, None], b[None, :] < b[:, None]
    none = jnp.zeros((n, n), bool)
    keep = jnp.block([[same, before], [none, same | before]])
    return jnp.tile(jnp.arange(n), 2), keep


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: tuple):
    """The jitted pieces of one configuration, made once: a layer is one
    compiled program whatever its index, and a sequence length compiles
    once."""
    cfg = dict(cfg_key)

    @jax.jit
    def embed(key, toks):
        return W.embed_weights(key, cfg).astype(F32)[toks]

    @functools.partial(jax.jit, static_argnames=("q",), donate_argnums=(2,))
    def run_layer(key, l, x, pos, keep, q):
        w = _f32({**W.attention_weights(key, l, cfg),
                  **W.router_weights(key, l, cfg)})
        # the experts stay as stored; one at a time is taken to float32
        w["experts"] = W.expert_weights(key, l, cfg)
        return layer(x, w, cfg, pos, keep, q)

    @functools.partial(jax.jit, static_argnames=("q", "whole"))
    def head(key, x, picks, q, whole=False):
        """x (n, D), picks (n, P) token ids -> per row: the best logit,
        the log of the sum of exponentials, the best token, the logits
        of ``picks``; ``whole``: the logits themselves too."""
        h = rms(x, jnp.ones((x.shape[-1],), F32), cfg["rms_norm_eps"])
        w = W.head_weights(key, cfg).astype(F32)

        def rows(args):
            hx, pk = args
            lg = mm(hx, w, q)
            out = {"best": lg.max(-1), "lse": jax.nn.logsumexp(lg, -1),
                   "arg": lg.argmax(-1).astype(jnp.int32),
                   "picked": jnp.take_along_axis(lg, pk, -1)}
            return {**out, "logits": lg} if whole else out

        n = x.shape[0]
        step = min(256, n)
        pad = -n % step
        hx = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, step, h.shape[-1])
        pk = jnp.pad(picks, ((0, pad), (0, 0))).reshape(
            -1, step, picks.shape[-1])
        out = jax.lax.map(rows, (hx, pk))
        return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:])[:n], out)

    return embed, run_layer, head


def _key_of(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str)) or v is None))


def run(seed: int, cfg: dict, toks, pos, keep, at, picks=None, q=None,
        whole=False) -> dict:
    """One sequence ``toks`` (S,) at positions ``pos`` under ``keep``,
    layer by layer, each layer's weights made from the seed and dropped
    again -> the head's statistics at the positions ``at`` (n,)
    (``_programs``' ``head``) and ``margin``: the smallest routing
    margin over the layers there."""
    embed, run_layer, head = _programs(_key_of(cfg))
    key = W.seed_key(seed)
    toks, pos, at = (jnp.asarray(a, jnp.int32) for a in (toks, pos, at))
    x = embed(key, toks)
    margin = jnp.full(toks.shape, jnp.inf, F32)
    for l in range(cfg["num_hidden_layers"]):
        x, m = run_layer(key, l, x, pos, keep, q)
        margin = jnp.minimum(margin, m)
    if picks is None:
        picks = jnp.zeros((at.shape[0], 1), jnp.int32)
    out = head(key, x[at], jnp.asarray(picks, jnp.int32), q, whole)
    return {**{k: np.asarray(v) for k, v in out.items()},
            "margin": np.asarray(margin[at])}


def forward(seed: int, cfg: dict, toks, block: int, q=None) -> dict:
    """(a) The full forward of one token sequence under the
    block-causal mask -> ``logits`` (S, V) float32 and ``margin``
    (S,)."""
    n = len(toks)
    return run(seed, cfg, toks, np.arange(n), block_causal(n, block),
               np.arange(n), q=q, whole=True)


def fix_by_confidence(conf, is_open, count: int):
    """The positions a pass fixes: the ``count`` most confident open
    ones (those that are open, if fewer), the lower index on a tie."""
    order = sorted((i for i in range(len(conf)) if is_open[i]),
                   key=lambda i: (-conf[i], i))
    return order[:count]


def generate(seed: int, cfg: dict, prompt, max_new: int, *, block: int,
             steps: int, mask_id: int, pad_to: int, q=None):
    """(b) The published loop by full forwards -> (the ``max_new``
    tokens, the pass at which each was fixed, per block and pass the
    log-confidences the choice was made on).  The sequence is laid out
    in ``pad_to`` positions (whole blocks), masks beyond what exists:
    under the mask no block sees a later one."""
    prompt = [int(t) for t in prompt]
    seq = np.full((pad_to,), mask_id, np.int64)
    seq[:len(prompt)] = prompt
    when = np.full((pad_to,), OPEN, np.int64)
    when[:len(prompt)] = -1
    keep, every = block_causal(pad_to, block), np.arange(pad_to)
    end = len(prompt) + max_new
    trail = []
    for b in range(len(prompt) // block, -(-end // block)):
        at = np.arange(b * block, (b + 1) * block)
        for s in range(steps):
            is_open = when[at] == OPEN
            if not is_open.any():
                break
            out = run(seed, cfg, seq, every, keep, at, q=q)
            conf = out["best"] - out["lse"]
            trail.append((b, s, conf.copy(), is_open.copy()))
            for i in fix_by_confidence(conf, is_open, block // steps):
                seq[at[i]], when[at[i]] = out["arg"][i], s
    new = slice(len(prompt), end)
    return seq[new].tolist(), when[new].tolist(), trail


def schedule_faults(requests, block: int, steps: int) -> int:
    """Blocks of ``requests`` ((prompt, tokens, when) each) in which
    some pass fixed another count than the schedule's, or a token
    carries a pass outside ``[-1, steps)``.  A block the budget cut
    short shows only its first tokens: its passes are held to the
    range and to at most the schedule's count."""
    per_pass, bad = block // steps, 0
    for prompt, toks, when in requests:
        if len(when) != len(toks):
            bad += 1
            continue
        start, end = len(prompt), len(prompt) + len(toks)
        for b in range(start // block, -(-end // block)):
            lo, hi = max(b * block, start), min((b + 1) * block, end)
            ws = [int(w) for w in when[lo - start:hi - start]]
            counts = [ws.count(s) for s in range(steps)]
            if any(not -1 <= w < steps for w in ws):
                bad += 1
            elif hi < (b + 1) * block:          # cut short by the budget
                bad += any(c > per_pass for c in counts)
            else:
                opened = (b + 1) * block - lo
                want = [max(0, min(per_pass, opened - s * per_pass))
                        for s in range(steps)]
                bad += counts != want
    return bad


def served_gaps(seed: int, cfg: dict, requests, pad_to: int, *,
                block: int, steps: int, mask_id: int, q=None,
                control=None, one_pass: bool = True) -> dict:
    """(c) Teacher forcing at the granularity of a pass.  ``requests``:
    (prompt, served tokens, the pass at which each was fixed) of the
    sampled requests.  For block ``b`` and pass ``s`` the input is the
    prompt, the served blocks before ``b``, and block ``b`` with the
    tokens fixed before pass ``s`` in place and ``[MASK]`` elsewhere.
    From the reference's logits at the block, for each token fixed at
    pass ``s``: ``token_gap`` = the best logit less the served token's
    at its position, and ``pick_gap`` = the largest log-confidence
    among the positions open at ``s`` and not fixed by it, less that of
    the served position (0 where the served one leads), and
    ``margin``: the smallest routing margin of the position in that
    forward.  Only blocks served whole are read (a block the budget cut
    short hides the rest of its tokens): ``skipped`` counts the tokens
    left out.

    The definition is the loop over blocks (``one_pass=False``: one
    forward a block and pass).  ``one_pass`` evaluates pass ``s`` of
    every block in one forward over the noised copy beside the clean
    copy (:func:`noised_beside_clean`), once a pass: the same inputs to
    every block, since a noised block attends itself and the clean
    blocks before it.

    With ``control`` (a rounding function) also ``control_token_gap``
    and ``control_pick_gap``: the same for the token, and the position,
    that the lower precision puts first."""
    if pad_to % block:
        raise ValueError(f"pad_to {pad_to} is not whole blocks of {block}")
    per_pass = block // steps
    pos2, keep2 = noised_beside_clean(pad_to, block)
    keep1, every = block_causal(pad_to, block), np.arange(pad_to)
    res = {k: [] for k in ("token_gap", "pick_gap", "margin",
                           "control_token_gap", "control_pick_gap")}
    skipped = 0

    def passes(clean, when, blocks, s, picks, qq):
        """The head's statistics at every position of ``blocks`` for
        pass ``s``: (len(blocks) * block,) each."""
        noised = np.where(when >= s, mask_id, clean)
        at = np.concatenate([np.arange(b * block, (b + 1) * block)
                             for b in blocks])
        if one_pass:
            return run(seed, cfg, np.concatenate([noised, clean]), pos2,
                       keep2, at, picks, qq)
        outs = []
        for i, b in enumerate(blocks):
            toks = clean.copy()
            toks[b * block:] = mask_id
            toks[b * block:(b + 1) * block] = noised[b * block:(b + 1) * block]
            sl = slice(i * block, (i + 1) * block)
            outs.append(run(seed, cfg, toks, every, keep1, at[sl],
                            picks[sl], qq))
        return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}

    for prompt, toks, fixed in requests:
        n = len(prompt) + len(toks)
        if n > pad_to:
            raise ValueError(f"sequence of {n} tokens, pad_to {pad_to}")
        clean = np.full((pad_to,), mask_id, np.int64)
        clean[:n] = list(prompt) + list(toks)
        when = np.full((pad_to,), -1, np.int64)
        when[len(prompt):n] = fixed
        blocks = list(range(len(prompt) // block, n // block))
        skipped += n - max(n // block * block, len(prompt))
        if not blocks:
            continue
        at = np.concatenate([np.arange(b * block, (b + 1) * block)
                             for b in blocks])
        for s in range(steps):
            served = clean[at][:, None]
            if control is not None:
                ctl = passes(clean, when, blocks, s,
                             np.zeros_like(served), control)
                served = np.concatenate([served, ctl["arg"][:, None]], 1)
            ref = passes(clean, when, blocks, s, served, q)
            conf = ref["best"] - ref["lse"]
            for i in range(len(blocks)):
                sl = slice(i * block, (i + 1) * block)
                w, c = when[at[sl]], conf[sl]
                is_open, rest = w >= s, w > s
                others = c[rest].max() if rest.any() else -np.inf
                for j in np.flatnonzero(w == s):
                    p = sl.start + j
                    res["token_gap"].append(
                        ref["best"][p] - ref["picked"][p, 0])
                    res["pick_gap"].append(max(0.0, others - c[j]))
                    res["margin"].append(ref["margin"][p])
                if control is None or not (w == s).any():
                    continue
                cc = (ctl["best"] - ctl["lse"])[sl]
                took = fix_by_confidence(cc, is_open, per_pass)
                left = [j for j in np.flatnonzero(is_open)
                        if j not in took]
                lead = max([c[j] for j in left], default=-np.inf)
                for j in np.flatnonzero(w == s):
                    # the tokens the control would have put at the
                    # positions this pass fixed, and the positions it
                    # would have fixed in their place
                    p = sl.start + j
                    res["control_token_gap"].append(
                        ref["best"][p] - ref["picked"][p, 1])
                    res["control_pick_gap"].append(
                        max(0.0, lead - min(c[t] for t in took)))
    out = {k: np.asarray(v, np.float64) for k, v in res.items()
           if v or not k.startswith("control")}
    out["skipped"] = skipped
    return out
