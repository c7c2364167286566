"""Weights from a seed, made on the device in one jitted call.

The benchmark makes the weights (not the program): the program gets the
tree in its own layout, and the plain reference calls the same
``layer_weights`` layer by layer, so neither takes anything the other
made.  Layout (the program's interface): ``embed`` (V, D), ``layers`` of
stacked ``(L, d_in, d_out)`` matrices and ``(L, D)`` norm scales,
``final_norm`` (D,), ``lm_head`` (D, V).  Matrices are N(0, 1/fan_in) in
the dtype the configuration states (embed N(0, 1)); norm scales are ones
in float32.

``cfg`` everywhere in ``benchmarks/model`` is the configuration file's
dict, with Hugging Face key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_EMBED, _HEAD = 1 << 20, (1 << 20) + 1      # fold-in tags beside layers


def sizes(cfg: dict) -> dict:
    d, h, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    dh = cfg.get("head_dim") or d // h
    return {"D": d, "H": h, "Hkv": hkv, "Dh": dh,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def matrix_dims(cfg: dict) -> dict:
    z = sizes(cfg)
    d, f = z["D"], z["F"]
    q, kv = z["H"] * z["Dh"], z["Hkv"] * z["Dh"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def dtype_of(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype", "bfloat16"))


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's seeds pass
    2**31): the low 31 bits seed it, the rest is folded in."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def round_to(x, dtype):
    """x (float32) rounded to the values ``dtype`` holds, still float32.
    An explicit rounding: XLA may skip a float32 -> bfloat16 -> float32
    pair of converts (``xla_allow_excess_precision``), and did on the
    chip, which left the reference with weights the program never had."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _normal(key, shape, fan_in, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return round_to(x * (fan_in ** -0.5), dtype).astype(dtype)


def layer_weights(key, layer, cfg: dict) -> dict:
    """The matrices of one layer; ``layer`` may be traced."""
    dims, dt = matrix_dims(cfg), dtype_of(cfg)
    ks = jax.random.split(jax.random.fold_in(key, layer), len(MATRICES))
    return {n: _normal(k, dims[n], dims[n][0], dt)
            for n, k in zip(MATRICES, ks)}


def embed_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _EMBED), (z["V"], z["D"]), 1.0,
                   dtype_of(cfg))


def head_weights(key, cfg: dict):
    z = sizes(cfg)
    return _normal(jax.random.fold_in(key, _HEAD), (z["D"], z["V"]),
                   z["D"], dtype_of(cfg))


def make_weights(key, cfg: dict) -> dict:
    """The whole tree (jit this; ``key`` is an argument so that every
    seed shares one compiled program)."""
    z = sizes(cfg)
    layers = jax.lax.map(lambda l: layer_weights(key, l, cfg),
                         jnp.arange(z["L"]))
    layers["attn_norm"] = jnp.ones((z["L"], z["D"]), jnp.float32)
    layers["mlp_norm"] = jnp.ones((z["L"], z["D"]), jnp.float32)
    return {"embed": embed_weights(key, cfg), "layers": layers,
            "final_norm": jnp.ones((z["D"],), jnp.float32),
            "lm_head": head_weights(key, cfg)}


def tokens_for(seed: int, stream: int, shape, vocab: int):
    """Seeded token ids as numpy, off the device (``stream`` tells
    apart steps, ranks and requests)."""
    import numpy as np
    rng = np.random.default_rng([int(seed), int(stream)])
    return rng.integers(0, vocab, shape, dtype=np.int32)
