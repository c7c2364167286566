"""Latent attention over fine-grained experts: the DeepSeek-V3 block
family as JoyAI-LLM-Flash publishes it, on the serving path.

Two things set this family apart from the dense one
(:mod:`.transformer`) and from Mixtral-style experts (:mod:`.moe`):

* **Multi-head latent attention (MLA).**  Queries and keys/values go
  through low-rank bottlenecks (``q_lora_rank``, ``kv_lora_rank``), a
  head's query/key has a part without position (``qk_nope_head_dim``)
  and a rotary part (``qk_rope_head_dim``) whose key is ONE head shared
  by all, and the cache holds per token and layer only
  ``[c_kv | k_rope]`` — ``kv_lora_rank + qk_rope_head_dim`` values,
  in rows of whole 128-lane tiles (:class:`MLAMixer`,
  :attr:`LatentMoEConfig.cache_width`).  Prefill over a dense cache
  attends with up-projected K and V; a decode step, and a prefill
  chunk over a paged pool, are *absorbed*: ``W_uk`` goes into the
  query and ``W_uv`` after the weighted sum, so attention runs over the
  latent rows themselves (one KV head, the values a prefix of the keys)
  and a paged pool is read in place by
  :func:`~..ops.decode.paged_latent_decode_attention` and
  :func:`~..ops.decode.paged_prefill_attention`.
* **The stack is not one homogeneous scan.**  ``n_dense_layers``
  leading layers carry a dense SwiGLU of width ``d_ff``; the rest carry
  ``n_experts`` routed experts of width ``d_expert`` chosen by sigmoid
  scores with a selection bias, plus a shared expert
  (:func:`~..parallel.expert.shared_routed_ffn`, dropless).  The
  parameter tree holds the dense layers under ``dense_layers``, stacked
  on a leading axis and scanned, and the expert layers under ``layers``
  as a tuple of one tree a layer, unrolled
  (:func:`~.generate.forward_with_cache`).  Not stacked, because the
  grouped expert matmul is a kernel call: it takes its ``(E, D, F)``
  operand whole, and a scan's slice of an ``(L, E, D, F)`` stack is a
  copy of it (2.4 GB a layer at the published widths, read and written
  on every decode step; seen in the step compiled for v5e).

Not here: multi-token prediction (``num_nextn_predict_layers``) — the
next token's logits do not depend on it, and a tick that yields more
than one token a row is ROADMAP R5 — and the training forward (in
training MLA is two low-rank projections; nothing in the tree trains
this family yet).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops._common import NEG_INF as _NEG_INF
from ..parallel.expert import shared_routed_ffn
from ..utils import fan_in_normal
from .transformer import (TransformerConfig, _preset, _rms_norm, _rope,
                          qlinear)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(TransformerConfig):
    """``d_ff`` is the dense layers' width; ``n_kv_heads`` counts the
    cache's heads, which is one (the latent row)."""
    n_kv_heads: int = 1
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_dense_layers: int = 1
    n_experts: int = 256
    top_k: int = 8
    d_expert: int = 768
    n_shared_experts: int = 1
    routed_scale: float = 2.5

    @property
    def latent_width(self) -> int:
        """Values the cache holds a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Width of the cache's rows: :attr:`latent_width` rounded up
        to whole 128-lane tiles, the rest zeros.  The TPU pads a
        576-wide row to 640 in memory either way (as it would two
        leaves of 512 and 64); padded in the shape, the pool keeps the
        tokens-by-lanes layout the decode kernel reads in place, where
        XLA gives a 576-wide pool a block-minor layout and copies it
        whole around every step."""
        return -(-self.latent_width // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def num_params(self) -> int:
        D, H = self.d_model, self.n_heads
        attn = (D * self.q_lora_rank + self.q_lora_rank * H * self.qk_head_dim
                + D * self.latent_width
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * D)
        dense = 3 * D * self.d_ff
        moe = (3 * D * self.d_expert * (self.n_experts
                                        + self.n_shared_experts)
               + D * self.n_experts)
        n_moe = self.n_layers - self.n_dense_layers
        return (2 * self.vocab_size * D + self.n_layers * attn
                + self.n_dense_layers * dense + n_moe * moe)


def joyai_flash_config(**kw) -> LatentMoEConfig:
    """JoyAI-LLM-Flash (48B-A2.7B) as its ``config.json`` publishes
    it."""
    return _preset(kw, cls=LatentMoEConfig, vocab_size=129280,
                   d_model=2048, n_layers=40, n_heads=32, d_ff=7168,
                   max_seq_len=131072, rope_theta=32e6, norm_eps=1e-6)


def tiny_latent_moe_config(**kw) -> LatentMoEConfig:
    return _preset(kw, cls=LatentMoEConfig, vocab_size=512, d_model=64,
                   n_layers=3, n_heads=4, d_ff=128, max_seq_len=256,
                   q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, n_dense_layers=1,
                   n_experts=8, top_k=2, d_expert=32)


# ----------------------------------------------------------------------
# parameters

def attention_weight_dims(cfg: LatentMoEConfig) -> dict:
    D, H = cfg.d_model, cfg.n_heads
    return {"w_qa": (D, cfg.q_lora_rank),
            "w_qb": (cfg.q_lora_rank, H * cfg.qk_head_dim),
            "w_kva": (D, cfg.latent_width),
            "w_kvb": (cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (H * cfg.v_head_dim, D)}


def init_latent_moe_model(key, cfg: LatentMoEConfig) -> dict:
    """``dense_layers`` carries a leading axis of its depth;
    ``layers`` is a tuple of one tree an expert layer (the module
    docstring says why).  Within ``w_qb`` a head's columns are
    ``[nope | rope]``, within ``w_kvb`` ``[k_nope | v]``, within
    ``w_kva`` ``[c_kv | k_rope]``."""
    D, E = cfg.d_model, cfg.n_experts
    Ld, Lm = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    keys = iter(jax.random.split(key, 32))

    def normal(shape, fan_in):
        return fan_in_normal(next(keys), shape, fan_in, cfg.dtype)

    def swiglu(lead, width):
        return {"w_gate": normal(lead + (D, width), D),
                "w_up": normal(lead + (D, width), D),
                "w_down": normal(lead + (width, D), width)}

    def attention(L):
        out = {n: normal((L,) + d, d[0])
               for n, d in attention_weight_dims(cfg).items()}
        out.update(attn_norm=jnp.ones((L, D), jnp.float32),
                   q_norm=jnp.ones((L, cfg.q_lora_rank), jnp.float32),
                   kv_norm=jnp.ones((L, cfg.kv_lora_rank), jnp.float32),
                   mlp_norm=jnp.ones((L, D), jnp.float32))
        return out

    moe = swiglu((Lm, E), cfg.d_expert)
    moe.update(
        router=jax.random.normal(next(keys), (Lm, D, E), jnp.float32)
        * D ** -0.5,
        bias=jnp.zeros((Lm, E), jnp.float32),
        shared=swiglu((Lm,), cfg.n_shared_experts * cfg.d_expert))
    layers = {**attention(Lm), "moe": moe}
    return {"embed": normal((cfg.vocab_size, D), 1.0),
            "dense_layers": {**attention(Ld), **swiglu((Ld,), cfg.d_ff)},
            "layers": tuple(jax.tree_util.tree_map(lambda a: a[i], layers)
                            for i in range(Lm)),
            "final_norm": jnp.ones((D,), jnp.float32),
            "lm_head": normal((D, cfg.vocab_size), D)}


def latent_moe_shardings(cfg: LatentMoEConfig, ep_axis: str = "ep",
                         tp_axis: str | None = "tp") -> dict:
    """Heads over ``tp`` (the up-projections' columns, ``wo``'s rows;
    the bottlenecks and the one latent head stay whole), experts over
    ``ep``."""
    def attention(*lead):
        return {"attn_norm": P(*lead, None), "q_norm": P(*lead, None),
                "kv_norm": P(*lead, None), "mlp_norm": P(*lead, None),
                "w_qa": P(*lead, None, None),
                "w_qb": P(*lead, None, tp_axis),
                "w_kva": P(*lead, None, None),
                "w_kvb": P(*lead, None, tp_axis),
                "wo": P(*lead, tp_axis, None)}

    def swiglu(*lead):
        return {"w_gate": P(*lead, None, tp_axis),
                "w_up": P(*lead, None, tp_axis),
                "w_down": P(*lead, tp_axis, None)}

    moe = {"w_gate": P(ep_axis, None, None), "w_up": P(ep_axis, None, None),
           "w_down": P(ep_axis, None, None), "router": P(None, None),
           "bias": P(None), "shared": swiglu()}
    n_moe = cfg.n_layers - cfg.n_dense_layers
    return {"embed": P(None, tp_axis),
            "dense_layers": {**attention(None), **swiglu(None)},
            "layers": tuple({**attention(), "moe": moe}
                            for _ in range(n_moe)),
            "final_norm": P(None), "lm_head": P(None, tp_axis)}


# ----------------------------------------------------------------------
# the layer's two halves

def latent_moe_mlp_block(x, layer, cfg: LatentMoEConfig, token_mask):
    """An expert layer's feed-forward residual block ->
    (x, the layer's routing load)."""
    h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    y, load = shared_routed_ffn(h, layer["moe"], top_k=cfg.top_k,
                                routed_scale=cfg.routed_scale,
                                token_mask=token_mask)
    return x + y, load


class MLAMixer:
    """Latent attention's half of :func:`~.generate.forward_with_cache`'s
    seam (the contract is :class:`~.generate.GQAMixer`'s).  The cache's
    one leaf ``ckv`` holds ``[norm(c_kv) | RoPE(k_rope)]`` a token.

    Several new tokens a row over a dense cache (prefill, a chunk of
    it) attend with K and V up-projected from the row; one new token a
    row (a decode step), and anything over a paged pool, attends
    absorbed, over the latent rows themselves.  Both compute
    ``softmax((q_nope . k_nope + q_rope . k_rope) / sqrt(qk_head_dim))
    v`` to rounding.

    RoPE pairs dimension ``j`` with ``j + half`` (:func:`~.transformer.
    _rope`), where the published checkpoint interleaves (``2j`` with
    ``2j + 1``): the same function after a fixed permutation of the
    rotary columns of ``w_qb`` and ``w_kva``
    (:func:`~.hf.latent_moe_config_from_hf` says which)."""

    def __init__(self, cfg: LatentMoEConfig, mesh):
        self.cfg, self.mesh = cfg, mesh
        self.scale = 1.0 / float(cfg.qk_head_dim) ** 0.5

    def project(self, h, layer, positions):
        cfg = self.cfg
        B, S = h.shape[:2]
        r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        c_q = _rms_norm(qlinear(h, layer["w_qa"]), layer["q_norm"],
                        cfg.norm_eps)
        q = qlinear(c_q, layer["w_qb"]).reshape(
            B, S, cfg.n_heads, cfg.qk_head_dim)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], positions, cfg.rope_theta)],
            axis=-1)
        kv = qlinear(h, layer["w_kva"])                 # (B, S, r + dr)
        c_kv = _rms_norm(kv[..., :r], layer["kv_norm"], cfg.norm_eps)
        k_r = _rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
        pad = jnp.zeros((B, S, cfg.cache_width - cfg.latent_width),
                        c_kv.dtype)
        new = jnp.concatenate([c_kv, k_r, pad], axis=-1)[:, None]
        return q, {"ckv": new}                  # (B, 1, S, cache_width)

    def _w_kvb(self, layer):
        cfg = self.cfg
        return layer["w_kvb"].reshape(
            cfg.kv_lora_rank, cfg.n_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)

    def _absorb(self, q, layer):
        """``[q_nope W_uk^T | q_rope | 0]``: (B, S, H, cache_width)."""
        cfg = self.cfg
        dn = cfg.qk_nope_head_dim
        w_uk = self._w_kvb(layer)[..., :dn]             # (r, H, dn)
        q_lat = jnp.einsum("bshd,rhd->bshr", q[..., :dn], w_uk)
        pad = jnp.zeros(q.shape[:3] + (cfg.cache_width
                                       - cfg.latent_width,), q.dtype)
        return jnp.concatenate([q_lat, q[..., dn:], pad], axis=-1)

    def _unabsorb(self, o_lat, layer):
        """(B, S, H, r) -> (B, S, H * dv)."""
        w_uv = self._w_kvb(layer)[..., self.cfg.qk_nope_head_dim:]
        o = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)
        return o.reshape(*o.shape[:2], -1)

    def attend(self, q, bufs, positions, layer):
        cfg = self.cfg
        ckv = bufs["ckv"][:, 0]                 # (B, T, cache_width)
        r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        k_r = ckv[..., r:cfg.latent_width]
        T = ckv.shape[1]
        mask = jnp.arange(T)[None, None, :] <= positions[:, :, None]
        f32 = jnp.float32
        if q.shape[1] == 1:
            # Absorbed, over a dense row (the paged pool's in-place
            # twin is attend_paged).
            qa = self._absorb(q, layer).astype(f32) * self.scale
            s = jnp.einsum("bshw,btw->bhst", qa, ckv.astype(f32))
            s = jnp.where(mask[:, None], s, _NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bhst,btr->bshr", p,
                               ckv[..., :r].astype(f32)).astype(q.dtype)
            return self._unabsorb(o_lat, layer)
        kv = jnp.einsum("btr,rhd->bthd", ckv[..., :r],
                        self._w_kvb(layer))             # (B, T, H, dn+dv)
        qf = q.astype(f32) * self.scale
        s = (jnp.einsum("bshd,bthd->bhst", qf[..., :dn],
                        kv[..., :dn].astype(f32))
             + jnp.einsum("bshd,btd->bhst", qf[..., dn:],
                          k_r.astype(f32)))
        s = jnp.where(mask[:, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", p, kv[..., dn:].astype(f32))
        return o.reshape(*o.shape[:2], -1).astype(q.dtype)

    def attend_paged(self, q, pool, layer_idx, table, pos, active,
                     layer, length=None):
        """Absorbed either way (the contract is
        :meth:`~.generate.GQAMixer.attend_paged`'s): a chunk too runs
        over the latent rows themselves, a page read once for both
        products and no position of the row up-projected."""
        from ..ops.decode import (paged_latent_decode_attention,
                                  paged_prefill_attention)
        qa, r = self._absorb(q, layer), self.cfg.kv_lora_rank
        if q.shape[1] == 1:
            o_lat = paged_latent_decode_attention(
                qa[:, 0], pool["ckv"], layer_idx, table, pos, v_width=r,
                scale=self.scale, active=active)[:, None]
        else:
            o_lat = paged_prefill_attention(
                qa, pool["ckv"], None, layer_idx, table, pos, length,
                v_width=r, scale=self.scale)
        return self._unabsorb(o_lat, layer)

    def out(self, o, layer):
        return qlinear(o, layer["wo"])


def latent_moe_forward(params: dict, tokens, cfg: LatentMoEConfig):
    """tokens (B, S) -> logits (B, S, V) float32: the whole sequence in
    one pass (the prefill path over a cache of its own length)."""
    from .generate import forward_with_cache, init_kv_cache
    B, S = tokens.shape
    return forward_with_cache(params, tokens, init_kv_cache(cfg, B, S),
                              0, cfg)[0]
