#!/usr/bin/env python3
"""Compile SDAR-30B-A3B's timed programs (the denoise pass, a prefill
chunk, the weights' program) for v5e in the sandbox (no chip:
``jax.experimental.topologies``) and print XLA's buffer-assignment
sizes: both serving programs must fit 0.85 of the reported memory limit
at the cell's geometry, and the pass's temporaries must show no copy of
a layer's experts (1.21 GB) or of the pool.  Run by hand:

    JAX_PLATFORMS=cpu python benchmarks/tests/compile_sizes_sdar.py [layers] [dump-prefix]
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
from jax.sharding import SingleDeviceSharding           # noqa: E402

from benchmarks.drivers.serve_sdar_worker import program_config  # noqa: E402
from benchmarks.model import sdar_weights as W          # noqa: E402
from benchmarks.tests.compile_sizes import (            # noqa: E402
    _force_compiled_kernels, _report)

LIMIT = 0.85 * 16.9e9


def serve(layers=None, dump=None):
    from nbdistributed_tpu.models import DecodeServer
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/sdar-30b-a3b-serve.json")))
    if layers:
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
    size = lambda t: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                         for x in jax.tree.leaves(t))
    print("layers", pc.n_layers, "weights bytes", size(shapes),
          "parameters", pc.num_params(), "pool bytes", size(srv._cache),
          "limit", LIMIT, flush=True)
    step = srv._step_fn.lower(*put((
        shapes, srv._cache, srv._paged.device_table(), srv._lens,
        srv._block, srv._active, srv._key))).compile()
    text = step.as_text()
    print("pass: mosaic calls", text.count("tpu_custom_call"))
    if dump:
        open(dump + ".step.hlo", "w").write(text)
    out = {"step": _report("denoise pass", step)}
    ck = geo["prefill_chunk"]
    pre = srv._prefill_fn.program.lower(*put((
        shapes, srv._cache, srv._paged.device_row(0),
        jnp.zeros((1, ck), jnp.int32), jnp.int32(0), jnp.int32(ck))),
        final=False).compile()
    if dump:
        open(dump + ".prefill.hlo", "w").write(pre.as_text())
    out["prefill"] = _report(f"prefill chunk {ck}", pre)
    mk = jax.jit(functools.partial(W.make_weights, cfg=cfg)).lower(
        jax.ShapeDtypeStruct((), jax.eval_shape(
            lambda: W.seed_key(0)).dtype, sharding=one)).compile()
    out["weights"] = _report("make_weights", mk)
    return out


if __name__ == "__main__":
    _force_compiled_kernels()
    serve(int(sys.argv[1]) if len(sys.argv) > 1 else None,
          sys.argv[2] if len(sys.argv) > 2 else None)
