"""JoyAI-LLM-Flash's weights from a seed, made on the device layer by
layer (``weights.py`` does the same for Mistral).

The benchmark makes the weights, not the program: the program gets the
tree in its own layout (``make_weights``; ``nbdistributed_tpu/models/
mla.py`` describes it), the plain reference calls ``attention_weights``
/ ``dense_weights`` / ``expert_weights`` layer by layer, and neither
takes anything the other made.  Matrices are N(0, 1/fan_in) in the
dtype the configuration states (embed N(0, 1)), norm scales ones.  The
router's matrix holds values of that dtype, kept in float32 (the
scores are computed in float32); ``e_score_correction_bias`` is drawn
uniform in +-BIAS: a real checkpoint's is learned, and one that is
zero would let a program that drops it pass.

Column order inside a matrix (an assumption of this pair of files,
listed in the configuration file): ``w_qb`` per head ``[nope | rope]``;
``w_kva`` ``[c_kv | k_rope]``; ``w_kvb`` per head ``[k_nope | v]``; the
rotary pairs are (j, j + half), not the checkpoint's interleaved
(2j, 2j + 1) — with random columns a permutation of them.

``cfg`` is the configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .weights import _normal, dtype_of, seed_key  # noqa: F401

BIAS = 0.1
_EMBED, _HEAD = 1 << 20, (1 << 20) + 1      # fold-in tags beside layers
ATTENTION = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo")


def sizes(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "rq": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "E": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "Ld": cfg["first_k_dense_replace"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"]}


def attention_dims(cfg: dict) -> dict:
    z = sizes(cfg)
    return {"w_qa": (z["D"], z["rq"]),
            "w_qb": (z["rq"], z["H"] * (z["dn"] + z["dr"])),
            "w_kva": (z["D"], z["r"] + z["dr"]),
            "w_kvb": (z["r"], z["H"] * (z["dn"] + z["dv"])),
            "wo": (z["H"] * z["dv"], z["D"])}


def _swiglu(keys, d, f, dt, experts=None):
    """``experts``: a leading axis of that many, drawn 32 at a time so
    that the float32 draw of a whole layer's experts never exists."""
    def mat(k, shape, fan_in):
        if experts is None:
            return _normal(k, shape, fan_in, dt)
        return jax.lax.map(
            lambda e: _normal(jax.random.fold_in(k, e), shape, fan_in, dt),
            jnp.arange(experts), batch_size=min(32, experts))
    return {"w_gate": mat(keys[0], (d, f), d),
            "w_up": mat(keys[1], (d, f), d),
            "w_down": mat(keys[2], (f, d), f)}


def attention_weights(key, layer, cfg: dict) -> dict:
    """One layer's attention matrices; ``layer`` may be traced."""
    dims, dt = attention_dims(cfg), dtype_of(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)
    return {n: _normal(k, dims[n], dims[n][0], dt)
            for n, k in zip(ATTENTION, ks)}


def dense_weights(key, layer, cfg: dict) -> dict:
    """A leading layer's dense SwiGLU."""
    z = sizes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)
    return _swiglu(ks[5:8], z["D"], z["F"], dtype_of(cfg))


def router_weights(key, layer, cfg: dict) -> dict:
    z, dt = sizes(cfg), dtype_of(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)
    return {"router": _normal(ks[8], (z["D"], z["E"]), z["D"],
                              dt).astype(jnp.float32),
            "bias": jax.random.uniform(ks[9], (z["E"],), jnp.float32,
                                       -BIAS, BIAS)}


def shared_weights(key, layer, cfg: dict) -> dict:
    z = sizes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)
    return _swiglu(ks[10:13], z["D"], z["Fs"], dtype_of(cfg))


def expert_weights(key, layer, cfg: dict) -> dict:
    """An expert layer's routed experts, stacked on a leading E axis."""
    z = sizes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)
    return _swiglu(ks[13:16], z["D"], z["Fe"], dtype_of(cfg),
                   experts=z["E"])


def embed_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _EMBED), (z["V"], z["D"]), 1.0,
                   dtype_of(cfg))


def head_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _HEAD), (z["D"], z["V"]),
                   z["D"], dtype_of(cfg))


def _norms(cfg: dict, lead=()) -> dict:
    z = sizes(cfg)
    one = lambda w: jnp.ones(lead + (w,), jnp.float32)
    return {"attn_norm": one(z["D"]), "q_norm": one(z["rq"]),
            "kv_norm": one(z["r"]), "mlp_norm": one(z["D"])}


def make_weights(key, cfg: dict) -> dict:
    """The whole tree in the program's layout: the dense layers
    stacked on a leading axis, the expert layers a tuple of one tree
    each (jit this; ``key`` is an argument so that every seed shares
    one compiled program)."""
    z = sizes(cfg)
    ld = z["Ld"]

    def dense(l):
        return {**attention_weights(key, l, cfg),
                **dense_weights(key, l, cfg)}

    def routed(l):
        return {**attention_weights(key, l, cfg), **_norms(cfg),
                "moe": {**expert_weights(key, l, cfg),
                        **router_weights(key, l, cfg),
                        "shared": shared_weights(key, l, cfg)}}

    return {"embed": embed_weights(key, cfg),
            "dense_layers": {**jax.lax.map(dense, jnp.arange(ld)),
                             **_norms(cfg, (ld,))},
            "layers": tuple(routed(l) for l in range(ld, z["L"])),
            "final_norm": jnp.ones((z["D"],), jnp.float32),
            "lm_head": head_weights(key, cfg)}
