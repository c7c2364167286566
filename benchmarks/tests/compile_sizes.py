#!/usr/bin/env python3
"""Compile a configuration's timed programs for v5e in the sandbox (no
chip: ``jax.experimental.topologies``) and print XLA's buffer-assignment
sizes — the numbers that fixed each depth.  Run by hand:

    JAX_PLATFORMS=cpu python benchmarks/tests/compile_sizes.py train [layers] [chips]
    JAX_PLATFORMS=cpu python benchmarks/tests/compile_sizes.py serve [layers]
"""

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402
from jax.experimental import topologies                 # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks.drivers.train_worker import program_config  # noqa: E402
from benchmarks.model import weights as W               # noqa: E402


def _force_compiled_kernels():
    from nbdistributed_tpu.ops import attention, decode
    attention._use_interpret = lambda: False
    decode._use_interpret = lambda: False


def _report(name, compiled):
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    print(name, json.dumps(out), flush=True)
    return out


def train(layers, chips):
    import optax
    from nbdistributed_tpu.models import loss_fn
    from nbdistributed_tpu.parallel.tensor_parallel import make_tp_train_step
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/mistral7b-train.json")))
    cfg["num_hidden_layers"] = layers
    pc = program_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(chips), ("dp",))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    opt = optax.adamw(3e-4)
    shapes = jax.eval_shape(functools.partial(W.make_weights, cfg=cfg),
                            jax.eval_shape(lambda: W.seed_key(0)))
    state = jax.eval_shape(opt.init, shapes)
    put = lambda t, sh: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), t)
    step = make_tp_train_step(lambda p, b: loss_fn(p, b, pc), opt, mesh, None)
    batch = {"tokens": jax.ShapeDtypeStruct((chips, 4096), jnp.int32,
                                            sharding=rows)}
    compiled = step.lower(put(shapes, repl), put(state, repl), batch).compile()
    text = compiled.as_text()
    print("mosaic calls", text.count("tpu_custom_call"),
          "all-reduce", text.count("all-reduce("))
    return _report(f"train L={layers} chips={chips}", compiled)


def serve(layers):
    """The paged decode step of the live geometry (the larger of the
    serving programs: it gathers every slot's blocks to a dense view)."""
    from jax.sharding import SingleDeviceSharding
    from nbdistributed_tpu.models import DecodeServer
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/mistral7b-serve.json")))
    cfg["num_hidden_layers"] = layers
    geo = cfg["assumed"]
    pc = program_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.eval_shape(functools.partial(W.make_weights, cfg=cfg),
                            jax.eval_shape(lambda: W.seed_key(0)))
    srv = DecodeServer(shapes, pc, max_batch=geo["max_batch"],
                       max_len=geo["max_len"], pad_to=geo["pad_to"],
                       kv_block_tokens=geo["kv_block_tokens"],
                       prefill_chunk=geo["prefill_chunk"],
                       interleave_prefill=True)
    put = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    args = (shapes, srv._cache, srv._paged.device_table(), srv._lens,
            srv._last, srv._active, srv._key)
    compiled = srv._step_fn.lower(*put(args)).compile()
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(shapes))
    pool = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(srv._cache))
    print("weights bytes", weights, "KV pool bytes", pool,
          "mosaic calls", compiled.as_text().count("tpu_custom_call"))
    return _report(f"serve step L={layers}", compiled)


if __name__ == "__main__":
    _force_compiled_kernels()
    what = sys.argv[1]
    if what == "train":
        train(int(sys.argv[2]) if len(sys.argv) > 2 else 3,
              int(sys.argv[3]) if len(sys.argv) > 3 else 1)
    if what == "serve":
        serve(int(sys.argv[2]) if len(sys.argv) > 2 else 16)
