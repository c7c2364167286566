"""Autoregressive generation with a KV cache — both model families.

The reference framework has no inference path of its own (its users call
HF ``model.generate`` in cells); a first-party TPU decode loop is part
of making the model families usable interactively.  The attention stack
is shared between the dense and MoE transformers, so one cached forward
serves both (the feed-forward branch dispatches on the config type).
Design for XLA:

* static shapes everywhere — the cache is a fixed ``max_len`` ring of
  zeros, new K/V written by ``lax.dynamic_update_slice``; attention
  masks against global positions instead of slicing a traced length;
* the whole decode loop is one ``lax.scan`` (one compile, no Python
  per-token dispatch); prefill is one batched forward over the prompt;
* grouped-query attention against the cache without materializing
  repeated KV heads (grouped einsum, fp32 accumulation);
* tensor-parallel ready: :func:`kv_cache_shardings` shards the cache
  over KV heads on the ``tp`` axis, matching
  :func:`~nbdistributed_tpu.models.transformer.param_shardings`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .transformer import (TransformerConfig, _mlp_block, _rms_norm,
                          _rope, qlinear)

_NEG_INF = -1e30


# ----------------------------------------------------------------------
# cache

def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int, *,
                  mesh=None, rules: dict | None = None,
                  quantized: bool = False):
    """Zeroed (L, B, Hkv, max_len, Dh) K and V buffers.

    The cache is **heads-major**: (token, head-dim) are the minor two
    axes, which is what the Pallas decode kernel's block specs tile
    (Mosaic requires the last two block dims divisible by (8, 128) or
    equal to the array's — a (B, T, Hkv, D) layout puts the tiny Hkv
    extent in the sublane slot, which real-TPU lowering rejects; the
    CPU interpreter does not enforce this, so only on-chip runs catch
    it).  It is also the natural TPU tiling: D on lanes, tokens on
    sublanes.

    With ``mesh``, the buffers are laid out by ``rules`` (default:
    :func:`kv_cache_shardings` restricted to the axes the mesh has) so
    the decode loop keeps the cache sharded like the parameters.

    ``quantized=True`` stores the cache **int8** with per-(token,
    kv-head) fp32 scales (``k_s``/``v_s``, (L, B, Hkv, T, 1)): at long
    context the cache — not the weights — dominates decode HBM traffic,
    and the scales commute through both attention matmuls (see
    ops/decode.py), so the kernel streams half the bytes."""
    if getattr(cfg, "kv_lora_rank", None):
        # A latent (MLA) cache: one leaf, one head, ``[c_kv | k_rope]``
        # a token (models/mla.py).
        if quantized:
            raise ValueError("a latent cache has no int8 layout yet")
        cache = {"ckv": jnp.zeros(
            (cfg.n_layers, batch, 1, max_len, cfg.cache_width),
            cfg.dtype)}
        if mesh is not None:
            spec = P(None, "dp" if "dp" in mesh.shape else None)
            cache = {"ckv": jax.device_put(
                cache["ckv"], NamedSharding(mesh, spec))}
        return cache
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    if quantized:
        sshape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, 1)
        cache = {"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "k_s": jnp.zeros(sshape, jnp.float32),
                 "v_s": jnp.zeros(sshape, jnp.float32)}
    else:
        cache = {"k": jnp.zeros(shape, cfg.dtype),
                 "v": jnp.zeros(shape, cfg.dtype)}
    if mesh is not None:
        if rules is None:
            rules = kv_cache_shardings(
                dp_axis="dp" if "dp" in mesh.shape else None,
                tp_axis="tp" if "tp" in mesh.shape else None,
                sp_axis="sp" if "sp" in mesh.shape else None,
                quantized=quantized)
        missing = set(cache) - set(rules)
        if missing:
            hint = (" — a quantized cache needs scale specs too (see "
                    "kv_cache_shardings(quantized=True))"
                    if missing & {"k_s", "v_s"} else "")
            raise ValueError(f"cache sharding rules missing specs for "
                             f"{sorted(missing)}{hint}")
        cache = {name: jax.device_put(
            buf, NamedSharding(mesh, rules[name]))
            for name, buf in cache.items()}
    return cache


def kv_cache_shardings(dp_axis: str | None = "dp",
                       tp_axis: str | None = "tp",
                       sp_axis: str | None = None,
                       quantized: bool = False):
    """PartitionSpec for the cache: batch over dp, KV heads over tp,
    and optionally the TOKEN axis over ``sp_axis`` — sequence-parallel
    decode for contexts whose cache outgrows one chip's HBM (each
    shard holds a T/n slice; the decode kernel combines shards by
    log-sum-exp, see :func:`_flash_decode_on_mesh`).  Both the int8
    scales and the heads-major K/V buffers carry the KV heads at
    axis 2 and tokens at axis 3."""
    spec = P(None, dp_axis, tp_axis, sp_axis, None)
    rules = {"k": spec, "v": spec}
    if quantized:
        rules["k_s"] = spec
        rules["v_s"] = spec
    return rules


def _quantize_kv(x):
    """Per-(token, kv-head) symmetric int8 for a new K or V slab.

    x: (B, Hkv, S, D) heads-major -> (q8 int8 same shape, scales
    (B, Hkv, S, 1) fp32) — both already in the cache layout.  The int8
    core is quant.quantize_weight (one scheme for weights and cache)."""
    from .quant import quantize_weight
    qw = quantize_weight(x, axis=-1)
    return qw["q8"], qw["s"]


def _dequantize_kv(q8, s):
    """Inverse of :func:`_quantize_kv`: int8 (B, Hkv, T, D) + scales
    (B, Hkv, T, 1) -> fp32 (B, Hkv, T, D)."""
    return q8.astype(jnp.float32) * s


# ----------------------------------------------------------------------
# cache-aware forward

def _cached_attention(q, kc, vc, positions, scale, window=None):
    """GQA attention of new-token queries against the full cache.

    q: (B, S, H, Dh) — S new tokens; kc/vc: (B, Hkv, T, Dh) — the
    whole heads-major cache buffer; positions: (B, S) global positions
    of the queries.  Valid keys are exactly cache slots t <= position
    (later slots are unwritten zeros and masked out by the same
    comparison).
    """
    B, S, H, Dh = q.shape
    Hkv, T = kc.shape[1], kc.shape[2]
    group = H // Hkv
    qg = (q.astype(jnp.float32) * scale).reshape(B, S, Hkv, group, Dh)
    s = jnp.einsum("bskgd,bktd->bkgst", qg, kc.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    t_idx = jnp.arange(T)
    mask = t_idx[None, None, :] <= positions[:, :, None]  # (B,S,T)
    if window is not None:
        mask = mask & (t_idx[None, None, :]
                       > positions[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bskgd", p, vc.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H * Dh).astype(q.dtype)


def _flash_decode_on_mesh(q, kc, vc, pos, mesh, scale, window=None,
                          k_s=None, v_s=None):
    """Run the Pallas decode kernel under GSPMD via shard_map: batch
    over ``dp``, heads over ``tp``, and the cache's TOKEN axis over
    ``sp`` (sequence-parallel decode — other mesh axes replicated).

    The GQA grouping survives head sharding because q-head block
    [t·H/tp, (t+1)·H/tp) maps exactly onto kv-head block
    [t·Hkv/tp, (t+1)·Hkv/tp) — each shard keeps the full group ratio,
    so the local kernel call is the global computation.

    With an ``sp`` axis, each shard runs the kernel over its local
    T/n cache slice at shifted positions (``pos − shard·T/n``; the
    sliding-window bound is offset-invariant, so ``window`` composes
    unchanged) and the shards merge by log-sum-exp:
    ``o = Σ exp(lse_i − m)·o_i / Σ exp(lse_i − m)`` with
    ``m = max_i lse_i`` — exactly the flash inter-block combine, run
    across chips (one fused psum over ICI per layer per step).  A
    shard wholly past ``pos`` reports ``lse = −inf`` and weighs zero.

    q: (B, H, Dh); kc/vc: (B, Hkv, T, Dh) heads-major; pos: (B,);
    optional int8 cache scales k_s/v_s: (B, Hkv, T, 1).
    """
    from ..ops.decode import flash_decode_attention

    dp = "dp" if "dp" in mesh.shape else None
    tp = "tp" if "tp" in mesh.shape else None
    sp = "sp" if "sp" in mesh.shape else None
    qspec = P(dp, tp, None)
    cspec = P(dp, tp, sp, None)
    sspec = P(dp, tp, sp, None)

    def inner(q, kc, vc, pos, *scales):
        ks, vs = scales if scales else (None, None)
        if sp is None:
            return flash_decode_attention(q, kc, vc, pos, scale=scale,
                                          window=window, k_s=ks,
                                          v_s=vs)
        t_loc = kc.shape[2]
        pos_loc = pos - jax.lax.axis_index(sp) * t_loc
        o, lse = flash_decode_attention(q, kc, vc, pos_loc,
                                        scale=scale, window=window,
                                        k_s=ks, v_s=vs,
                                        return_lse=True)
        lse = lse[..., None]                            # (B, H, 1)
        m = jax.lax.pmax(lse, sp)
        w = jnp.exp(lse - m)
        # ONE psum on the hot path (per layer per step): the weight
        # column rides as an extra feature of the weighted output.
        both = jax.lax.psum(
            jnp.concatenate([o.astype(jnp.float32) * w, w], axis=-1),
            sp)
        num, den = both[..., :-1], both[..., -1:]
        return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)

    quant = k_s is not None
    in_specs = ((qspec, cspec, cspec, P(dp))
                + ((sspec, sspec) if quant else ()))
    args = (q, kc, vc, pos) + ((k_s, v_s) if quant else ())
    return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=qspec, check_vma=False)(*args)


def _can_flash_decode_on_mesh(mesh, B, H, Hkv, T=None):
    """The sharded kernel needs each shard to hold whole head groups,
    whole batch rows, and (under ``sp``) equal token slices."""
    tp_n = mesh.shape.get("tp", 1)
    dp_n = mesh.shape.get("dp", 1)
    sp_n = mesh.shape.get("sp", 1)
    return (H % tp_n == 0 and Hkv % tp_n == 0 and B % dp_n == 0
            and (T is None or T % sp_n == 0))


def _attend(q, kc, vc, ks, vs, positions, scale, cfg, mesh):
    """Attention of the new tokens' queries over ONE layer's dense
    ``(B, Hkv, T, Dh)`` K/V (``ks``/``vs``: the scales of an int8
    cache, else None), by the path the configuration and the mesh
    allow.  q: (B, S, H, Dh) -> (B, S, H*Dh)."""
    B, S, H, Dh = q.shape
    window = getattr(cfg, "sliding_window", None)
    if S == 1 and cfg.use_flash and mesh is None:
        # Decode hot path: fused Pallas kernel streams the cache
        # once with the masked online softmax (ops/decode.py); an
        # int8 cache streams at half width with its scales
        # commuted through the matmuls.
        from ..ops.decode import flash_decode_attention
        return flash_decode_attention(
            q[:, 0], kc, vc, positions[:, 0], scale=scale,
            window=window, k_s=ks, v_s=vs).reshape(B, 1, H * Dh)
    if (S == 1 and cfg.use_flash and mesh is not None
            and _can_flash_decode_on_mesh(mesh, B, H, kc.shape[1],
                                          kc.shape[2])):
        # Same kernel under GSPMD: shard_map carves the batch over
        # dp and the (already tp-sharded) heads over tp, so the
        # kernel runs on local shards instead of forcing GSPMD to
        # replicate a raw pallas_call.
        return _flash_decode_on_mesh(
            q[:, 0], kc, vc, positions[:, 0], mesh,
            scale, window, ks, vs).reshape(B, 1, H * Dh)
    if ks is not None:
        # Compat/prefill path: dequantize for the einsum.
        kc, vc = _dequantize_kv(kc, ks), _dequantize_kv(vc, vs)
    return _cached_attention(q, kc, vc, positions, scale, window=window)


class GQAMixer:
    """How a layer of the dense family mixes tokens: wq/wk/wv/wo with
    rotary grouped-query attention over ``(B, Hkv, T, Dh)`` K and V.
    A mixer is the model's half of :func:`forward_with_cache`'s seam
    (the cache's half is :class:`DenseKV` / :class:`~.paged_kv.PagedKV`):
    :meth:`project` makes the queries and the cache's leaves for the S
    new tokens, :meth:`attend` reads one layer's dense buffers,
    :meth:`attend_paged` the paged pool where it lies, :meth:`out`
    projects back to the residual stream.  The latent-attention mixer
    is :class:`~.mla.MLAMixer`."""

    def __init__(self, cfg, mesh, kv_quantized: bool = False):
        self.cfg, self.mesh = cfg, mesh
        self.scale = 1.0 / float(cfg.head_dim) ** 0.5
        self._quantized = kv_quantized

    def project(self, h, layer, positions):
        cfg = self.cfg
        B, S = h.shape[:2]
        H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        # ``qk_norm``: a learned RMS norm over each head's values,
        # before the rotary embedding (the Qwen3 layer).
        head_norm = ((lambda x, name: _rms_norm(x, layer[name],
                                                cfg.norm_eps))
                     if getattr(cfg, "qk_norm", False)
                     else (lambda x, name: x))
        q = _rope(head_norm(qlinear(h, layer["wq"]).reshape(B, S, H, Dh),
                            "q_norm"), positions, cfg.rope_theta)
        k = _rope(head_norm(qlinear(h, layer["wk"]).reshape(B, S, Hkv, Dh),
                            "k_norm"), positions, cfg.rope_theta)
        v = qlinear(h, layer["wv"]).reshape(B, S, Hkv, Dh)
        # Heads-major for the cache: (B, S, Hkv, Dh) -> (B, Hkv, S, Dh).
        new = {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}
        if self._quantized:
            new["k"], new["k_s"] = _quantize_kv(new["k"])
            new["v"], new["v_s"] = _quantize_kv(new["v"])
        return q, new

    def attend(self, q, bufs, positions, layer):
        return _attend(q, bufs["k"], bufs["v"], bufs.get("k_s"),
                       bufs.get("v_s"), positions, self.scale, self.cfg,
                       self.mesh)

    def attend_paged(self, q, pool, layer_idx, table, pos, active,
                     layer, length=None):
        """One new token a row (a decode step: ``pos`` its position,
        ``active`` the rows that take part), or a chunk of them
        (``pos`` the first one's position, ``length`` the real ones of
        each row's chunk).  Under a block-causal mask (``cfg.
        block_length`` > 1) a decode step carries a block a row
        (``active`` given, ``pos`` the block's first position): its
        queries share one bound on the keys, the block's last
        position."""
        from ..ops.decode import (paged_decode_attention,
                                  paged_prefill_attention)
        kw = dict(scale=self.scale,
                  window=getattr(self.cfg, "sliding_window", None),
                  k_s=pool.get("k_s"), v_s=pool.get("v_s"))
        if q.shape[1] == 1:
            o = paged_decode_attention(
                q[:, 0], pool["k"], pool["v"], layer_idx, table, pos,
                active=active, **kw)
        elif active is not None:        # a block a row: one shared bound
            o = paged_decode_attention(
                q, pool["k"], pool["v"], layer_idx, table,
                pos + q.shape[1] - 1, active=active, **kw)
        else:
            o = paged_prefill_attention(
                q, pool["k"], pool["v"], layer_idx, table, pos, length,
                block=getattr(self.cfg, "block_length", 1), **kw)
        return o.reshape(*q.shape[:2], -1)

    def out(self, o, layer):
        return qlinear(o, layer["wo"])


class DenseKV:
    """The dense cache's side of :func:`forward_with_cache`'s one
    seam: how a layer writes its new cache entries and how it attends.
    (The paged pool's side is :class:`~.paged_kv.PagedKV`.)  An
    implementation gives the layer scan what it ``held`` across layers
    (carry) and what it takes ``per_layer`` (xs); its :meth:`layer`
    writes ``new`` (name -> (B, Hkv, S, width), the cache's leaves for
    the S new tokens), attends through the model's mixer, and hands
    back the attention output, what is held, and the layer's ys;
    :meth:`result` makes the updated cache of the scan's two results.

    Here the ``(L, B, Hkv, T, width)`` cache rides the scan as xs and
    comes back as ys, one layer's buffers at a time, and nothing is
    held."""

    def __init__(self, cache: dict, cache_len, mixer):
        self.held = ()
        self.per_layer = cache
        self._cache_len = cache_len
        self._mixer = mixer

    def _write(self, buf, new):
        """Insert S new entries at the cache pointer: one slice update
        for a shared scalar pointer, a per-row (vmapped, scatter-
        lowered) update for per-stream pointers.  K/V buffers, latent
        rows and int8 scales share the heads-major layout — the token
        axis sits at -2 for all."""
        at = self._cache_len
        if at.ndim == 1:
            return jax.vmap(lambda c, u, s: jax.lax.dynamic_update_slice(
                c, u, (0, s, 0)))(buf, new, at)
        return jax.lax.dynamic_update_slice(buf, new, (0, 0, at, 0))

    def layer(self, held, bufs, q, new, positions, layer):
        bufs = {name: self._write(buf, new[name].astype(buf.dtype))
                for name, buf in bufs.items()}
        return self._mixer.attend(q, bufs, positions, layer), held, bufs

    def result(self, held, per_layer):
        return per_layer


def _make_mlp_fn(cfg: TransformerConfig, mesh, ep_axis: str,
                 token_mask=None):
    """The per-layer feed-forward branch, ``(x, layer) -> (x, load)``:
    dense SwiGLU, the capacity-dispatched MoE layer when the config is
    a :class:`~.moe.MoEConfig` (sharing ``moe._moe_mlp_block`` so the
    two paths can never diverge), or shared + routed fine-grained
    experts for a layer that holds ``moe`` under a
    :class:`~.mla.LatentMoEConfig`.  ``token_mask`` reaches only the
    expert dispatch (dense SwiGLU is per-token, so inactive tokens
    cannot couple anything there).  ``load`` is the layer's
    :func:`~..parallel.expert.routing_load` where the last kind
    routed, else None."""
    from .mla import LatentMoEConfig, latent_moe_mlp_block
    from .moe import MoEConfig, _moe_mlp_block
    from .sdar import SDARConfig, sdar_mlp_block

    if isinstance(cfg, SDARConfig):
        return lambda x, layer: sdar_mlp_block(x, layer, cfg, token_mask)
    if isinstance(cfg, MoEConfig):
        def mlp(x, layer):
            x, _aux = _moe_mlp_block(x, layer, cfg, mesh, ep_axis,
                                     token_mask=token_mask)
            return x, None

        return mlp
    if isinstance(cfg, LatentMoEConfig):
        def mlp(x, layer):
            if "moe" in layer:
                return latent_moe_mlp_block(x, layer, cfg, token_mask)
            return _mlp_block(x, layer, cfg), None

        return mlp
    return lambda x, layer: (_mlp_block(x, layer, cfg), None)


def forward_with_cache(params: dict, tokens, cache: dict, cache_len,
                       cfg: TransformerConfig, *,
                       last_only: bool = False, last_index=None,
                       mesh=None, ep_axis: str = "ep", row_mask=None,
                       token_mask=None, block_table=None,
                       with_moe_load: bool = False, slot=None,
                       final: bool = True, head_rows: int | None = None):
    """Run ``tokens`` (B, S) through the model, reading/writing the KV
    cache at offset ``cache_len`` (traced scalar ok, or a per-row
    ``(B,)`` vector when the streams in the batch sit at different
    logical lengths — batched speculative decoding advances each
    stream by its own acceptance count).

    Works for both model families: the attention stack is shared and
    the feed-forward branch dispatches on the config (dense SwiGLU vs
    expert-parallel MoE — ``mesh`` routes the expert all-to-alls).

    Returns (logits fp32, updated cache): (B, S, vocab), or (B, 1,
    vocab) with ``last_only`` — prefill for generation needs only the
    final position, which skips S-1 of the (d_model × vocab) lm_head
    matmul.  ``last_index`` (B,) generalizes that to a per-row
    position (right-padded prompts whose last real token is not at
    S-1: the serving admission path), gathering the hidden state
    before final-norm/lm_head so the padded positions never touch
    the (d_model × vocab) matmul.  Covers both prefill (S = prompt
    length, cache_len = 0) and decode (S = 1).

    ``token_mask`` (B, S) bool marks which positions are *real*: pad
    positions must not enter MoE expert dispatch, where they would
    consume capacity slots and could evict real tokens (dense SwiGLU
    is per-token, so the mask only reaches the expert router).
    ``row_mask`` (B,) is the whole-row shorthand the decode step uses
    for inactive streams; passing both ANDs them.

    ``block_table`` (B, MB) makes ``cache`` the *paged* physical pool
    (:mod:`.paged_kv`): each layer writes its new entries into the
    row's pages and attends over the pool where it lies
    (:class:`~.paged_kv.PagedKV`).  S = 1 with a per-row ``cache_len``
    is a decode step (rows outside ``row_mask`` write to the trash
    block); S > 1 is a chunk of a prefill, whose ``token_mask`` also
    bounds the keys it attends (none at or past the last real token).
    The layer's mathematics is the same code either way; only where K/V
    are kept differs (:class:`DenseKV`).

    The stack need not be one homogeneous scan: a
    :class:`~.mla.LatentMoEConfig` holds its leading dense layers under
    ``params["dense_layers"]`` (stacked, scanned) and its expert layers
    under ``params["layers"]`` (one tree a layer, unrolled), run one
    after the other over the one cache, and mixes tokens by latent
    attention
    (:class:`~.mla.MLAMixer`) where the dense family uses
    :class:`GQAMixer`.  ``with_moe_load`` adds a third result, the
    expert layers' :func:`~..parallel.expert.routing_load` as
    ``[experts touched (mean over layers), most rows on one expert,
    rows routed a layer]`` (zeros for a config that routes nothing
    that way).

    A :class:`~.hybrid.StatefulConfig` (state-space layers beside
    attention: :class:`~.hybrid.HybridConfig`,
    :class:`~.nemotron_h.NemotronHConfig`) keeps several kinds of cache
    and runs its family's own forward
    (:func:`~.hybrid.hybrid_forward_with_cache`,
    :func:`~.nemotron_h.nemotron_h_forward_with_cache`), over the paged
    caches only: ``slot`` is the row a prefill chunk belongs to (its
    state is counted in rows), and ``final`` (static) whether the chunk
    ends its prompt, since one that does not runs nothing past the last
    layer that writes a cache and returns None for logits.  Every other
    family takes ``final=False`` to mean the same: no head, None for
    logits (a block server's prefill never asks for any).

    A config with ``block_length`` > 1 (:class:`~.sdar.SDARConfig`)
    attends under the block-causal mask, over the paged pool only: a
    chunk's bound on the keys is the last position of the query's
    block, and a decode step (``row_mask`` given) carries
    ``block_length`` tokens a row at ``cache_len .. cache_len +
    block_length - 1``, written to the row's pages and attended in
    both directions, whose logits all come back: those of the first
    ``head_rows`` rows where that is given (the rows after them are
    there for what they write: :func:`~.sdar.with_lanes`).
    """
    from .hybrid import StatefulConfig, hybrid_forward_with_cache
    from .mla import LatentMoEConfig, MLAMixer
    from .nemotron_h import NemotronHConfig, nemotron_h_forward_with_cache
    if isinstance(cfg, StatefulConfig):
        if block_table is None or mesh is not None:
            raise ValueError("state-space layers are served over the "
                             "paged caches on one device: pass "
                             "block_table (and no mesh)")
        kw = dict(block_table=block_table, row_mask=row_mask,
                  token_mask=token_mask, last_index=last_index, slot=slot,
                  final=final)
        if isinstance(cfg, NemotronHConfig):
            *out, load = nemotron_h_forward_with_cache(
                params, tokens, cache, cache_len, cfg, **kw)
        else:
            out = hybrid_forward_with_cache(params, tokens, cache,
                                            cache_len, cfg, **kw)
            load = jnp.zeros((3,), jnp.float32)
        return (*out, load) if with_moe_load else tuple(out)
    if getattr(cfg, "block_length", 1) > 1 and block_table is None:
        raise ValueError("a block-causal model is served over the paged "
                         "pool: pass block_table")
    B, S = tokens.shape
    cache_len = jnp.asarray(cache_len)
    per_row = cache_len.ndim == 1  # per-stream cache pointers
    offs = cache_len[:, None] if per_row else cache_len
    positions = offs + jnp.broadcast_to(jnp.arange(S), (B, S))
    x = params["embed"][tokens].astype(cfg.dtype)
    # row_mask (B,) bool: inactive batch rows (finished speculative
    # streams) must not couple to live rows — only MoE capacity
    # dispatch can couple rows, so the mask feeds the expert router.
    if row_mask is not None:
        rows = jnp.broadcast_to(row_mask[:, None], (B, S))
        token_mask = rows if token_mask is None else token_mask & rows
    mlp = _make_mlp_fn(cfg, mesh, ep_axis, token_mask=token_mask)
    if isinstance(cfg, LatentMoEConfig):
        mixer = MLAMixer(cfg, mesh)
    else:
        mixer = GQAMixer(cfg, mesh, kv_quantized="k_s" in cache)
    if block_table is not None:
        from .paged_kv import PagedKV
        kv = PagedKV(cache, block_table, row_mask, mixer, cfg, mesh,
                     token_mask=token_mask)
    else:
        kv = DenseKV(cache, cache_len, mixer)

    def layer_step(carry, inputs):
        x, held = carry
        layer, per_layer = inputs
        h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, new = mixer.project(h, layer, positions)
        # Named scopes (trace-time metadata): attention, mlp and, in
        # the serving step, sample can be told apart in a profile.
        with jax.named_scope("attention"):
            o, held, per_layer = kv.layer(held, per_layer, q, new,
                                          positions, layer)
            x = x + mixer.out(o, layer)
        with jax.named_scope("mlp"):
            x, load = mlp(x, layer)
        return (x, held), (per_layer, load)

    # One stack a kind of layer, in the model's order, over the one
    # cache, whose per-layer part is cut where the stacks meet.  A
    # stack is a dict of leaves stacked on a leading layer axis, which
    # is scanned, or a tuple of one dict a layer, which is unrolled:
    # weights that a layer hands to a kernel whole (grouped expert
    # matmuls) must not be slices of a stack, or every layer copies
    # them out of it first (models/mla.py).
    stacks = [params[name] for name in ("dense_layers", "layers")
              if name in params]
    held, at, outs, loads = kv.held, 0, [], []
    for stack in stacks:
        if isinstance(stack, dict):
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            xs = (kv.per_layer if len(stacks) == 1
                  else jax.tree_util.tree_map(lambda c: c[at:at + n],
                                              kv.per_layer))
            (x, held), (ys, load) = jax.lax.scan(
                layer_step, (x, held), (stack, xs))
            outs.append(ys)
            loads.append(load)
            at += n
            continue
        for layer in stack:
            xs = jax.tree_util.tree_map(lambda c: c[at], kv.per_layer)
            (x, held), (ys, load) = layer_step((x, held), (layer, xs))
            outs.append(jax.tree_util.tree_map(lambda y: y[None], ys))
            loads.append(None if load is None else load[None])
            at += 1
    per_layer = (outs[0] if len(outs) == 1 else jax.tree_util.tree_map(
        lambda *ys: jnp.concatenate(ys), *outs))
    new = kv.result(held, per_layer)
    if not final:
        return None, new
    if last_index is not None:
        idx = jnp.asarray(last_index, jnp.int32).reshape(B, 1, 1)
        x = jnp.take_along_axis(x, jnp.broadcast_to(
            idx, (B, 1, x.shape[-1])), axis=1)         # (B, 1, D)
    elif last_only:
        x = x[:, -1:]
    if head_rows is not None:
        x = x[:head_rows]
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = qlinear(x, params["lm_head"]).astype(jnp.float32)
    if with_moe_load:
        routed = [l for l in loads if l is not None]
        if not routed:
            return logits, new, jnp.zeros((3,), jnp.float32)
        routed = jnp.concatenate(routed)                # (layers, 3)
        return logits, new, jnp.stack([jnp.mean(routed[:, 0]),
                                       jnp.max(routed[:, 1]),
                                       jnp.mean(routed[:, 2])])
    return logits, new


# ----------------------------------------------------------------------
# sampling + the decode loop

def truncate_logits(logits, top_k: int | None = None,
                    top_p: float | None = None):
    """Mask ``logits`` (…, vocab) outside the ``top_k`` largest and/or
    the smallest ``top_p`` nucleus (Holtzman et al. 2019) to ``-inf``.

    Both filters are static-shape (sort + mask, no data-dependent
    shapes) so every consumer jits and scans.  Callers apply
    temperature *before* filtering — the nucleus depends on it.
    Shared by :func:`_sample` and the speculative path (which filters
    draft AND target distributions with the same knobs, making the
    accepted output distribution equal the truncated target's)."""
    if top_k is not None:
        # Mask everything below the k-th largest logit per row.
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # Nucleus: keep the smallest prefix of the sorted distribution
        # with cumulative probability >= top_p.  The shifted cumsum
        # keeps every token whose *preceding* mass is < top_p, so the
        # top-1 token always survives.
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1) - probs
        cutoff_idx = jnp.sum((cum < top_p).astype(jnp.int32), axis=-1,
                             keepdims=True) - 1
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _sample(logits, temperature: float, key, top_k: int | None = None,
            top_p: float | None = None):
    """logits: (B, vocab) -> (B,) int32.

    Greedy at ``temperature == 0``; otherwise categorical over the
    temperature-scaled logits, optionally truncated by
    :func:`truncate_logits`."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = truncate_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def generate(params: dict, prompt, cfg: TransformerConfig,
             max_new_tokens: int, *, temperature: float = 0.0,
             top_k: int | None = None, top_p: float | None = None,
             key=None, max_len: int | None = None, mesh=None,
             ep_axis: str = "ep", kv_quantized: bool = False):
    """Generate ``max_new_tokens`` continuations of ``prompt`` (B, S0).

    Greedy when ``temperature == 0`` (default), else categorical
    sampling with ``key`` (required), optionally truncated by ``top_k``
    and/or nucleus ``top_p`` (see :func:`_sample`).  With ``mesh``, the
    KV cache is created sharded (batch over ``dp``, KV heads over
    ``tp`` — pass tensor-parallel params sharded by
    ``param_shardings``).  Returns (B, S0+max_new_tokens) tokens.
    Jit-compatible: wrap in ``jax.jit`` with ``static_argnums``/closure
    for cfg and max_new_tokens, or use :func:`make_generate_fn`.
    """
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got "
                         f"{max_new_tokens}")
    if max_new_tokens == 0:
        return prompt
    if prompt.shape[1] == 0:
        raise ValueError("cannot generate from an empty prompt "
                         "(S == 0)")
    if temperature != 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
        raise ValueError(f"top_k must be in [1, vocab_size="
                         f"{cfg.vocab_size}], got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if key is None:
        key = jax.random.PRNGKey(0)
    B, S0 = prompt.shape
    T = max_len if max_len is not None else S0 + max_new_tokens
    if T < S0 + max_new_tokens:
        raise ValueError(f"max_len {T} < prompt {S0} + new "
                         f"{max_new_tokens}")
    cache = init_kv_cache(cfg, B, T, mesh=mesh,
                          quantized=kv_quantized)
    logits, cache = forward_with_cache(params, prompt, cache, 0, cfg,
                                       last_only=True, mesh=mesh,
                                       ep_axis=ep_axis)
    key, k0 = jax.random.split(key)
    tok = _sample(logits[:, -1], temperature, k0, top_k, top_p)

    def step(carry, i):
        cache, tok, key = carry
        logits, cache = forward_with_cache(
            params, tok[:, None], cache, S0 + i, cfg, mesh=mesh,
            ep_axis=ep_axis)
        key, ks = jax.random.split(key)
        nxt = _sample(logits[:, -1], temperature, ks, top_k, top_p)
        return (cache, nxt, key), tok

    (_, last, _), toks = jax.lax.scan(
        step, (cache, tok, key), jnp.arange(max_new_tokens - 1))
    out = jnp.moveaxis(toks, 0, 1) if max_new_tokens > 1 \
        else jnp.zeros((B, 0), jnp.int32)
    return jnp.concatenate([prompt, out, last[:, None]], axis=1)


def make_generate_fn(cfg: TransformerConfig, max_new_tokens: int, *,
                     temperature: float = 0.0, top_k: int | None = None,
                     top_p: float | None = None,
                     max_len: int | None = None,
                     mesh=None, ep_axis: str = "ep",
                     kv_quantized: bool = False):
    """A jitted ``(params, prompt, key) -> tokens`` closure."""

    def fn(params, prompt, key=None):
        return generate(params, prompt, cfg, max_new_tokens,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, key=key, max_len=max_len,
                        mesh=mesh, ep_axis=ep_axis,
                        kv_quantized=kv_quantized)

    return jax.jit(fn)


def prefill_chunked(params: dict, tokens, cache: dict,
                    cfg: TransformerConfig, *, chunk: int,
                    mesh=None, ep_axis: str = "ep"):
    """Prefill a long prompt in fixed-size chunks: peak activation
    memory during prefill drops from O(S_prompt) to O(chunk) while the
    KV cache fills identically (causal attention makes chunked and
    single-shot prefill mathematically the same computation).

    tokens: (B, S) with S divisible by ``chunk``.  Returns
    (last_logits (B, 1, V), cache) — the same contract ``last_only``
    prefill has, ready for the decode loop.  Wrap in ``jax.jit``
    (the chunk loop is a ``lax.scan``: one compile at chunk shape).
    """
    B, S = tokens.shape
    if S == 0:
        raise ValueError("cannot prefill an empty prompt (S == 0): the "
                         "zero-length scan would return all-zero "
                         "logits and seed decode with token 0")
    if S % chunk:
        raise ValueError(f"prompt length {S} not divisible by chunk "
                         f"{chunk}")
    n_chunks = S // chunk
    chunks = tokens.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def step(carry, inp):
        cache, _ = carry
        i, tok = inp
        logits, cache = forward_with_cache(
            params, tok, cache, i * chunk, cfg, last_only=True,
            mesh=mesh, ep_axis=ep_axis)
        return (cache, logits), None

    zero_logits = jnp.zeros((B, 1, cfg.vocab_size), jnp.float32)
    (cache, last_logits), _ = jax.lax.scan(
        step, (cache, zero_logits), (jnp.arange(n_chunks), chunks))
    return last_logits, cache
