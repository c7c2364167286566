"""SDAR-30B-A3B's weights from a seed, made on the device layer by
layer (``joyai_weights.py`` does the same for JoyAI).

The benchmark makes the weights, not the program: the program gets the
tree in its own layout (``make_weights``; ``nbdistributed_tpu/models/
sdar.py`` describes it), the plain reference calls
``attention_weights`` / ``router_weights`` / ``expert_weights`` layer by
layer, and neither takes anything the other made.  Matrices are
N(0, 1/fan_in) in the dtype the configuration states, the embedding
N(0, 1/hidden) (PERF.md, PR 31), the layers' norm scales ones.  The
router's matrix holds values of that dtype, kept in float32 (the
probabilities are computed in float32).  The scales of the per-head
norms of ``q`` and ``k`` are drawn uniform in [0.5, 1.5]: a
checkpoint's are learned, and scales of one would let a program that
drops them pass.

``cfg`` is the configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .weights import _normal, dtype_of, seed_key  # noqa: F401

_EMBED, _HEAD = 1 << 20, (1 << 20) + 1      # fold-in tags beside layers
ATTENTION = ("wq", "wk", "wv", "wo")


def sizes(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "Fe": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"]}


def attention_dims(cfg: dict) -> dict:
    z = sizes(cfg)
    q, kv = z["H"] * z["Dh"], z["Hkv"] * z["Dh"]
    return {"wq": (z["D"], q), "wk": (z["D"], kv), "wv": (z["D"], kv),
            "wo": (q, z["D"])}


def attention_weights(key, layer, cfg: dict) -> dict:
    """One layer's attention matrices and the two per-head norm
    scales; ``layer`` may be traced."""
    dims, dt, z = attention_dims(cfg), dtype_of(cfg), sizes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)
    out = {n: _normal(k, dims[n], dims[n][0], dt)
           for n, k in zip(ATTENTION, ks)}
    for n, k in (("q_norm", ks[4]), ("k_norm", ks[5])):
        out[n] = jax.random.uniform(k, (z["Dh"],), jnp.float32, 0.5, 1.5)
    return out


def router_weights(key, layer, cfg: dict) -> dict:
    z, dt = sizes(cfg), dtype_of(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)
    return {"router": _normal(ks[8], (z["D"], z["E"]), z["D"],
                              dt).astype(jnp.float32)}


def expert_weights(key, layer, cfg: dict) -> dict:
    """A layer's routed experts, stacked on a leading E axis, drawn 32
    at a time so that the float32 draw of a whole layer's experts never
    exists."""
    z, dt = sizes(cfg), dtype_of(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), 16)

    def mat(k, shape, fan_in):
        return jax.lax.map(
            lambda e: _normal(jax.random.fold_in(k, e), shape, fan_in, dt),
            jnp.arange(z["E"]), batch_size=min(32, z["E"]))

    return {"w_gate": mat(ks[13], (z["D"], z["Fe"]), z["D"]),
            "w_up": mat(ks[14], (z["D"], z["Fe"]), z["D"]),
            "w_down": mat(ks[15], (z["Fe"], z["D"]), z["Fe"])}


def embed_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _EMBED), (z["V"], z["D"]),
                   z["D"], dtype_of(cfg))


def head_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _HEAD), (z["D"], z["V"]),
                   z["D"], dtype_of(cfg))


def make_weights(key, cfg: dict) -> dict:
    """The whole tree in the program's layout: the layers a tuple of
    one tree each (jit this; ``key`` is an argument so that every seed
    shares one compiled program)."""
    z = sizes(cfg)
    one = lambda: jnp.ones((z["D"],), jnp.float32)

    def layer(l):
        return {**attention_weights(key, l, cfg),
                "attn_norm": one(), "mlp_norm": one(),
                "moe": {**expert_weights(key, l, cfg),
                        **router_weights(key, l, cfg)}}

    return {"embed": embed_weights(key, cfg),
            "layers": tuple(layer(l) for l in range(z["L"])),
            "final_norm": one(),
            "lm_head": head_weights(key, cfg)}
