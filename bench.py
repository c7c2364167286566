"""Benchmark: the framework's headline numbers, measured through the
real stack (worker processes driven cell-by-cell over the control
plane) on TPU workers.  No chip, no number: the run fails instead of
measuring some other device.

Prints exactly ONE JSON line to stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "extra": {...}}

Three measurements per run (BASELINE.json configs #3 and #5 + the
driver-defined all_reduce metric):

1. **Cell-wise DDP step/s** (primary metric): an SGD loop on
   Linear(1024,1024), each step its own ``execute`` cell — compute plus
   the full interactive-framework overhead.  ``vs_baseline`` compares
   against the reference's architectural per-cell floor (~0.2 s: its
   coordinator polls the ZMQ socket and the display buffer at 100 ms
   each, SURVEY §3.2) on top of the same measured compute.
2. **Flagship-model MFU** (``extra.smol135m``): SmolLM2-135M-scale
   config, bf16, flash kernels — forward and train-step tokens/s on
   rank 0's accelerator, converted to model FLOP/s against the chip
   peak (v5e: 197 bf16 TFLOP/s) with analytic matmul FLOPs/token.
3. **all_reduce bandwidth sweep** (``extra.allreduce``): bus bandwidth
   2(n-1)/n·bytes/t per chip at 1–64 MiB.  On a single-chip world the
   collective degenerates, so the sweep reports the HBM-bound on-device
   copy figure instead, labeled as such.
4. **Elastic pools** (``extra.elastic``, ISSUE 16): cold vs warm
   first-cell compile seconds (the persistent XLA cache serving a
   resized-in fleet), the resize drain-barrier + whole-flip
   wall-clock, and a tenant migration end to end — measured in CPU
   pools of their own after the bench world is torn down.
5. **Serving fast path** (``extra.serving``, ISSUE 17): closed-loop
   loadgen against a paged, multi-rank decode plane — sustained
   tokens/s with client-observed p99 TTFT/TPOT, then the shed rate
   at 2x the measured sustainable rate — in a CPU pool of its own.
6. **Training integrity guard** (``extra.trainguard``, ISSUE 19):
   guarded vs unguarded DDP steps/s at the default audit/snapshot
   cadences plus the audit step's fingerprint cost — the <10%
   guarded-overhead acceptance number, measured on CPU in-process.

Workers are launched ``--backend tpu`` and refuse to start on anything
else, so a machine without a chip makes this script exit non-zero
without printing a row.  (The elastic / serving / trainguard / transfer
sections still run in CPU pools of their own, as their text says; the
benchmark issue splits them off.)

**Per-measurement process isolation is the rule**: every heavy TPU
measurement family (MFU, flash-vs-XLA, decode, speculative, serving,
7B int8) runs in its own freshly-spawned worker process, torn down
(blocking) before the next spawns — see :func:`measure_family`.
"""

from __future__ import annotations

import ast
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from nbdistributed_tpu.manager import ProcessManager
from nbdistributed_tpu.messaging import CommunicationManager
from nbdistributed_tpu.utils import knobs

STEPS = 60
WARMUP = 5
# Peak dense bf16 FLOP/s of one chip, keyed by JAX's ``device_kind``
# (v5e: Google Cloud documentation, "TPU v5e", 197 TFLOP/s).  A device
# that is not in the table is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}
# What a measurement cell evaluates on the worker that holds the
# device (``_jax`` is the cell's own import): KeyError on an unknown
# device_kind.
PEAK_EXPR = f"{PEAK_BF16_FLOPS!r}[_jax.devices()[0].device_kind]"

SETUP = """
import jax, jax.numpy as jnp, optax
key = jax.random.PRNGKey(rank)
W = jax.random.normal(key, (1024, 1024), jnp.float32) * 0.02
b = jnp.zeros((1024,), jnp.float32)
opt = optax.sgd(1e-3)
state = opt.init((W, b))
x = jax.random.normal(jax.random.PRNGKey(100 + rank), (256, 1024))
y = jax.random.normal(jax.random.PRNGKey(200 + rank), (256, 1024))

def loss_fn(params, x, y):
    W, b = params
    pred = x @ W + b
    return jnp.mean((pred - y) ** 2)

if world_size > 1:
    # DDP: jit the two halves and all-reduce grads eagerly in between
    # (eager collectives cannot be traced into jit).
    @jax.jit
    def local_grads(params, x, y):
        return jax.value_and_grad(loss_fn)(params, x, y)

    @jax.jit
    def apply_grads(params, state, g):
        u, state = opt.update(g, state, params)
        return optax.apply_updates(params, u), state

    def local_step(params, state, x, y):
        l, g = local_grads(params, x, y)
        g = jax.tree.map(lambda t: all_reduce(t, "mean"), g)
        params, state = apply_grads(params, state, g)
        return params, state, l
else:
    # Single worker: one fused XLA program, no collective needed.
    @jax.jit
    def local_step(params, state, x, y):
        l, g = jax.value_and_grad(loss_fn)(params, x, y)
        u, state = opt.update(g, state, params)
        return optax.apply_updates(params, u), state, l

params = (W, b)
params, state, _ = local_step(params, state, x, y)  # compile
jax.block_until_ready(params)
'ready'
"""

STEP_CELL = """
params, state, loss_val = local_step(params, state, x, y)
jax.block_until_ready(params)
float(loss_val)
"""

# Flagship-model MFU, measured on the worker's accelerator.  The final
# expression is a json.dumps string so the coordinator can parse the
# result out of the REPL echo.
MFU_CELL = """
import functools as _functools, json as _json, time as _time
import jax as _jax, jax.numpy as _jnp, optax as _optax
from nbdistributed_tpu.models import (forward as _fwd_fn,
                                      init_params as _init,
                                      loss_fn as _loss,
                                      {cfg_name} as _cfg_fn)

_cfg = _cfg_fn(dtype=_jnp.bfloat16, use_flash=True{extra_cfg})
# Train step uses per-layer remat — the standard long-context training
# configuration (keeps activation memory O(S); without it the B=8
# S=2048 train step needs ~20 G HBM vs the v5e's 16 G).  MFU stays the
# PaLM convention: 3x fwd model FLOPs, recompute not counted.
_cfg_t = _cfg_fn(dtype=_jnp.bfloat16, use_flash=True,
                 remat=True{extra_cfg})
_p = _init(_jax.random.PRNGKey(0), _cfg)
_B, _S, _N = {shape}
# Timed-loop repetitions (fwd, train): median/min across reps guards
# against one-off spikes.
_R_FWD, _R_TR = {reps}
# Token buffer at 4x the fwd batch: the train ladder probes UPWARD
# from 2*_B (per-layer remat keeps activations O(S) per layer, so a
# bigger batch often fits and lifts MFU) and the chunked-CE control
# row probes 2x beyond whatever that finds; _tok[:_vB] then slices a
# genuine _vB rows instead of silently capping.
_tok = _jax.random.randint(_jax.random.PRNGKey(1), (4 * _B, _S), 0,
                           _cfg.vocab_size)

# Analytic matmul FLOPs/token (fwd): qkv + out projections, SwiGLU
# mlp, the two attention einsums at causal-average S/2 keys, lm_head.
_d, _L, _H, _Hkv, _Dh, _ff, _V = (_cfg.d_model, _cfg.n_layers,
                                  _cfg.n_heads, _cfg.n_kv_heads,
                                  _cfg.head_dim, _cfg.d_ff,
                                  _cfg.vocab_size)
_per_layer = (2 * _d * _H * _Dh + 2 * _d * 2 * _Hkv * _Dh
              + 2 * _H * _Dh * _d + 3 * 2 * _d * _ff)
_attn = 2 * 2 * (_S / 2) * _H * _Dh
_fwd_flops_tok = _L * (_per_layer + _attn) + 2 * _d * _V

# The fwd loop donates the previous logits buffer: the timed loop
# stays fully async (no host sync per iteration) yet only ONE B*S*V
# logits buffer ever exists (~1 G at 1B scale — an undonated async loop
# queues _N of them in flight and OOMs the 16 G chip).  keep_unused=True is
# load-bearing: without it JAX prunes the unused arg and silently
# drops the donation (no aliasing, no eager free).
# Every iteration runs on DIFFERENT token values and the loop ends in
# a value fetch (the ops/timing.py contract): each timed forward does
# its own work and the clock stops after the device finished.  Median
# of 3 timed loops tames one-off spikes.
_f = _jax.jit(lambda p, t, prev: _fwd_fn(p, t, _cfg),
              donate_argnums=(2,), keep_unused=True)
_ftok = _tok[:_B]
_prev = _jnp.zeros((_B, _S, _cfg.vocab_size), _jnp.float32)
_t0 = _time.time(); _o = _f(_p, _ftok, _prev)
float(_o[0, 0, 0])
_fwd_compile_s = _time.time() - _t0
_fwd_samples = []
for _rep in range(_R_FWD):
    _t0 = _time.time()
    for _i in range(_N):
        _ti = (_ftok + (_rep * _N + _i + 1)) % _cfg.vocab_size
        _o = _f(_p, _ti, _o)
    float(_o[0, 0, 0])            # value fetch forces the whole loop
    _fwd_samples.append((_time.time() - _t0) / _N)
_fwd_s = sorted(_fwd_samples)[len(_fwd_samples) // 2]
_o = None   # 1 G of logits must not stay live across the train phase

_opt = _optax.adamw(1e-4)

# Donate params + opt state so XLA updates them in place: without
# donation the step holds both generations of (params, mu, nu) —
# 2x 6.6 G at 1B scale — which is exactly what OOMed the first
# on-chip run of this cell.
@_jax.jit
def _mk_state(p):
    return _opt.init(p)

# Train-phase batch ladder: start at the caller-chosen batch (the
# TPU families probe 2*_B first, the CPU fallback _B), halve on
# ResourceExhausted (the train step needs ~2.5x the fwd working set).
def _time_train(_cfg_variant, _start_B):
    _tr = _comp = None
    _vB = _start_B
    _loss2 = lambda p, t: _loss(p, {{"tokens": t}}, _cfg_variant)
    while _vB >= 1:
        try:
            @_functools.partial(_jax.jit, donate_argnums=(0, 1))
            def _train(p, s, t):
                l, g = _jax.value_and_grad(_loss2)(p, t)
                u, s = _opt.update(g, s, p)
                return _optax.apply_updates(p, u), s, l

            _ttok = _tok[:_vB]
            _st = _mk_state(_p)
            _t0 = _time.time()
            _p2, _st2, _l = _train(_jax.tree_util.tree_map(
                _jnp.copy, _p), _st, _ttok)
            float(_l)                 # value fetch, not an async ack
            _comp = _time.time() - _t0
            # Params/opt state evolve every step, so every step does
            # fresh work; two timed loops (min) guard against one-off
            # spikes.
            _trs = []
            for _rep in range(_R_TR):
                _t0 = _time.time()
                for _ in range(_N):
                    _p2, _st2, _l = _train(_p2, _st2, _ttok)
                float(_l)
                _trs.append((_time.time() - _t0) / _N)
            _tr = min(_trs)
            _p2 = _st2 = _st = None
            return _tr, _comp, _vB
        except Exception as _e:
            if "RESOURCE_EXHAUSTED" not in str(_e):
                raise
            _p2 = _st2 = _st = _train = None
            import gc as _gc; _gc.collect()
            _vB //= 2
    return None, None, 0


# Ladder start ({tr_start}): on TPU it probes UPWARD from 2*_B —
# per-layer remat keeps activation memory O(S) per layer, so a bigger
# batch than the fwd pass often fits, and more tokens per step is the
# cheapest MFU lever there is.  OOM halves back (one extra compile,
# amortized by the persistent compilation cache).  The CPU fallback
# passes _B to stay inside its budget.
_tr_s, _train_compile_s, _train_B = _time_train(_cfg_t, {tr_start})
if _tr_s is None:
    raise RuntimeError("train step OOMed even at batch 1")
# The remat-policy table: full remat recomputes
# the whole forward; "dots" keeps matmul outputs (min recompute, max
# memory); "attn_only"/"mlp_only" checkpoint one sub-block.  Measure
# every policy that fits so the round records WHICH one wins at this
# scale/HBM, not just that a knob exists.
import dataclasses as _dc

def _row(_tp, _tb):
    return (None if _tp is None else
            {{"ms": round(_tp * 1e3, 2), "batch": _tb,
              "mfu": round(_tb * _S / _tp * 3 * _fwd_flops_tok
                           / {peak}, 4)}})

_policies = {{}}
for _pol in ("dots", "attn_only", "mlp_only"):
    _tp, _, _tb = _time_train(
        _dc.replace(_cfg_t, remat_policy=_pol), _train_B)
    _policies[_pol] = _row(_tp, _tb)
# Control row, NOT a remat policy: use_flash=False swaps the Pallas
# flash fwd+bwd kernels for the reference einsum attention compiled
# by XLA (materializes the (B, H, S, S) scores — the same baseline
# the flash speedup row compares against), in the SAME remat config.
# If this row beats the flash rows, the Pallas backward is costing
# more than it saves and the honest train setting is XLA attention.
# Ladder starts at _B, not _train_B: the materialized scores OOM far
# earlier than flash-remat, and every OOM rung costs a cold compile.
_tp, _, _tb = _time_train(_dc.replace(_cfg_t, use_flash=False), _B)
_ref_attn_row = _row(_tp, _tb)
# Chunked-vocab CE control row (ops/xent.py): the (B, S, V) logits
# never materialize — the buffer that caps the train batch — so the
# ladder probes 2x beyond whatever batch the standard loss found.
_tp, _, _tb = _time_train(
    _dc.replace(_cfg_t, ce_chunk=_cfg.vocab_size // 4),
    2 * max(_train_B, _B))
_ce_chunk_row = _row(_tp, _tb)
_tr_d = None if _policies["dots"] is None else \
    _policies["dots"]["ms"] / 1e3
_train_B_d = 0 if _policies["dots"] is None else \
    _policies["dots"]["batch"]

_peak = {peak}
_json.dumps({{
    "batch": _B, "seq": _S, "train_batch": _train_B,
    "n_params_m": round(sum(x.size for x in
                            _jax.tree_util.tree_leaves(_p)) / 1e6, 1),
    "fwd_ms": round(_fwd_s * 1e3, 2),
    "fwd_tokens_per_s": round(_B * _S / _fwd_s),
    "fwd_tflops_per_s": round(_B * _S / _fwd_s * _fwd_flops_tok / 1e12,
                              2),
    "fwd_mfu": round(_B * _S / _fwd_s * _fwd_flops_tok / _peak, 4),
    "train_ms": round(_tr_s * 1e3, 2),
    "train_tokens_per_s": round(_train_B * _S / _tr_s),
    "train_tflops_per_s": round(_train_B * _S / _tr_s
                                * 3 * _fwd_flops_tok / 1e12, 2),
    "train_mfu": round(_train_B * _S / _tr_s * 3 * _fwd_flops_tok
                       / _peak, 4),
    "train_dots_ms": (None if _tr_d is None else round(_tr_d * 1e3, 2)),
    "train_dots_mfu": (None if _tr_d is None else
                       round(_train_B_d * _S / _tr_d
                             * 3 * _fwd_flops_tok / _peak, 4)),
    "train_dots_batch": _train_B_d,
    "train_remat_policies": _policies,
    "train_ref_attn": _ref_attn_row,
    "train_ce_chunk": _ce_chunk_row,
    "compile_s": [round(_fwd_compile_s, 1), round(_train_compile_s, 1)],
}})
"""

# Flash kernel vs XLA reference attention.  Timing is CHAINED: each
# iteration's q depends on the previous output, all inside one scan
# program, and per-call time is the (long - short) chain difference
# (ops/timing.py), so the fixed dispatch+fetch cost cancels.  Each
# chain length is the MEDIAN of several fresh-input timed calls: a
# single-shot delta is noise, the median of 3+ is stable.
FLASH_CELL = """
import json as _json
import jax as _jax, jax.numpy as _jnp
from nbdistributed_tpu.ops import attention_reference as _ref
from nbdistributed_tpu.ops import flash_attention as _flash
from nbdistributed_tpu.ops.timing import chained_delta_ms as _cdm
_B, _S, _H, _Hkv, _D = 4, 2048, 8, 2, 128
_q = _jax.random.normal(_jax.random.PRNGKey(0), (_B, _S, _H, _D),
                        _jnp.bfloat16)
_k = _jax.random.normal(_jax.random.PRNGKey(1), (_B, _S, _Hkv, _D),
                        _jnp.bfloat16)
_v = _jax.random.normal(_jax.random.PRNGKey(2), (_B, _S, _Hkv, _D),
                        _jnp.bfloat16)

_out = {}
_fm, _fsamp = _cdm(lambda q: _flash(q, _k, _v, True), _q)
_rm, _rsamp = _cdm(lambda q: _ref(q, _k, _v, causal=True), _q)
_out["flash_ms"] = None if _fm <= 0 else round(_fm, 3)
_out["xla_ref_ms"] = None if _rm <= 0 else round(_rm, 3)
_out["speedup"] = (None if _fm <= 0 or _rm <= 0
                   else round(_rm / _fm, 3))
_out["samples"] = {"flash": _fsamp, "xla_ref": _rsamp}
_out["shape"] = (f"B{_B} S{_S} H{_H} Hkv{_Hkv} D{_D} "
                 f"{_q.dtype.name} causal, chained median-of-5 timing")
_json.dumps(_out)
"""

# Single-batch decode throughput, fp vs int8 weight-only: decode is
# HBM-bound (every step streams every weight), so int8 should approach
# 2x.  Per-token time is the DELTA between a long and a short generate
# program (median of fresh-prompt reps each): the delta cancels the
# fixed dispatch+fetch round-trip, every timed call uses a prompt no
# earlier call saw (a program+input result cache can never serve it),
# and the final np.asarray is a value fetch, so the clock stops after
# the device finished.
# Each row also reports tokens/s as a percent of the v5e HBM roofline
# bytes/token = weight bytes + the FULL allocated KV
# cache (the decode kernel's grid covers every k-block of max_len and
# masks in compute — static shapes stream it all), and the roofline is
# 819 GB/s / bytes_per_token.
DECODE_CELL = """
import json as _json, time as _time
import jax as _jax, jax.numpy as _jnp, numpy as _np
from nbdistributed_tpu.models import (init_params as _init,
                                      make_generate_fn as _mkgen,
                                      quantize_params as _quant,
                                      quantize_params4 as _quant4,
                                      smol_135m_config as _cfg_fn)
_cfg = _cfg_fn(dtype=_jnp.bfloat16, use_flash=True)
_p = _init(_jax.random.PRNGKey(0), _cfg)
_qp = _quant(_p)
_q4p = _quant4(_p)
_N1, _N2, _ML = 32, 256, 512
_HBM_V5E = 819e9
_REPS = 3

def _tree_bytes(t):
    return sum(x.size * x.dtype.itemsize
               for x in _jax.tree_util.tree_leaves(t))

def _kv_bytes(q8):
    _per_tok = _cfg.n_layers * _cfg.n_kv_heads * _cfg.head_dim
    _kv = 2 * _per_tok * _ML * (1 if q8 else 2)
    if q8:
        _kv += 2 * _cfg.n_layers * _cfg.n_kv_heads * _ML * 4  # scales
    return _kv

def _prompt_for(_seed):
    return _jax.random.randint(_jax.random.PRNGKey(_seed), (1, 16), 0,
                               _cfg.vocab_size)

_seed = [0]
def _median_gen_s(_g, _params):
    _ts = []
    for _ in range(_REPS):
        _seed[0] += 1
        _pr = _prompt_for(_seed[0])
        _t0 = _time.time()
        int(_np.asarray(_g(_params, _pr))[0, -1])   # value fetch
        _ts.append(_time.time() - _t0)
    _ts.sort()
    return _ts[len(_ts) // 2]

_out = {}
for _name, _params, _q8 in (("bf16", _p, False),
                            ("int8", _qp, False),
                            ("int8_kv8", _qp, True),
                            ("int4_kv8", _q4p, True)):
    _g1 = _mkgen(_cfg, _N1, max_len=_ML, kv_quantized=_q8)
    _g2 = _mkgen(_cfg, _N2, max_len=_ML, kv_quantized=_q8)
    _seed[0] += 1
    int(_np.asarray(_g1(_params, _prompt_for(_seed[0])))[0, -1])
    _seed[0] += 1
    int(_np.asarray(_g2(_params, _prompt_for(_seed[0])))[0, -1])
    _lo = _median_gen_s(_g1, _params)
    _hi = _median_gen_s(_g2, _params)
    _per_tok_s = (_hi - _lo) / (_N2 - _N1)
    _bpt = _tree_bytes(_params) + _kv_bytes(_q8)
    if _per_tok_s <= 0:
        _out[_name + "_tok_per_s"] = None     # noise won: say so
        _out[_name + "_ms_per_tok"] = None
        _out[_name + "_roofline_pct_v5e"] = None
    else:
        _tps = 1.0 / _per_tok_s
        _out[_name + "_tok_per_s"] = round(_tps, 1)
        _out[_name + "_ms_per_tok"] = round(_per_tok_s * 1e3, 3)
        _out[_name + "_roofline_pct_v5e"] = round(
            100.0 * _tps / (_HBM_V5E / _bpt), 1)
    _out[_name + "_bytes_per_tok_mb"] = round(_bpt / 1e6, 1)
    _out[_name + "_lo_hi_s"] = [round(_lo, 4), round(_hi, 4)]
_out["int8_speedup"] = (
    round(_out["int8_tok_per_s"] / _out["bf16_tok_per_s"], 2)
    if _out["bf16_tok_per_s"] and _out["int8_tok_per_s"] else None)
_json.dumps(_out)
"""

# Speculative decoding with a self-draft: acceptance is always gamma
# (upper bound), so the row isolates the MECHANICS — how much of the
# per-token cost the batched verify amortizes when acceptance is high.
# A real small draft lands between this and plain decode.
SPEC_CELL = """
import json as _json, time as _time
import jax as _jax, jax.numpy as _jnp, numpy as _np
from nbdistributed_tpu.models import (generate as _gen,
                                      init_params as _init,
                                      quantize_params4 as _quant4,
                                      smol_135m_config as _cfg_fn,
                                      speculative_generate as _spec)
_cfg = _cfg_fn(dtype=_jnp.bfloat16, use_flash=True)
_p = _init(_jax.random.PRNGKey(0), _cfg)
_q4 = _quant4(_p)
_N1, _N2, _G, _B = 16, 64, 4, 4
_REPS = 3

def _mk(_n, _mode):
    # "spec" = self-draft (acceptance == gamma, pure-mechanics upper
    # bound); "spec4" = int4-quantized-self draft (the textbook cheap
    # draft: near-gamma acceptance, draft forward streams half the
    # bytes) — the realistic point between self-draft and plain.
    if _mode == "spec":
        return _jax.jit(lambda p, t: _spec(p, p, t, _cfg, _cfg, _n,
                                           gamma=_G))
    if _mode == "spec4":
        # Draft tree rides as a traced ARGUMENT, not a closure: a
        # closed-over pytree is baked into each executable as
        # constants (extra HBM copies, slower compiles).
        _f4 = _jax.jit(lambda p, d, t: _spec(p, d, t, _cfg, _cfg, _n,
                                             gamma=_G))
        return lambda p, t: _f4(p, _q4, t)
    return _jax.jit(lambda p, t: _gen(p, t, _cfg, _n))

_seed = [100]
def _prompt_for(_b):
    _seed[0] += 1
    return _jax.random.randint(_jax.random.PRNGKey(_seed[0]), (_b, 16),
                               0, _cfg.vocab_size)

def _fetch(_r):
    # Value fetch forces completion; every rep runs a fresh prompt.
    _toks = _r[0] if isinstance(_r, tuple) else _r
    int(_np.asarray(_toks)[0, -1])
    return _r

def _median_s(_f, _b):
    _ts = []
    for _ in range(_REPS):
        _pr = _prompt_for(_b)
        _t0 = _time.time()
        _r = _fetch(_f(_p, _pr))
        _ts.append(_time.time() - _t0)
    _ts.sort()
    return _ts[len(_ts) // 2], _r

_out = {}
_spec_r = None
# Batched streams share every draft/verify forward, so B streams cost
# ~one stream's wall-clock: report aggregate tokens/s at B=1 and B=4.
# Per-token time = (N2-run - N1-run)/(N2-N1), medians of fresh-prompt
# reps — the delta cancels the fixed dispatch+fetch round-trip.
for _name, _mode, _b in (("plain", "plain", 1),
                         ("spec_selfdraft", "spec", 1),
                         ("plain_b4", "plain", _B),
                         ("spec_selfdraft_b4", "spec", _B),
                         ("spec_int4draft_b4", "spec4", _B)):
    _f1, _f2 = _mk(_N1, _mode), _mk(_N2, _mode)
    _fetch(_f1(_p, _prompt_for(_b)))     # compile + first run
    _fetch(_f2(_p, _prompt_for(_b)))
    _lo, _ = _median_s(_f1, _b)
    _hi, _r = _median_s(_f2, _b)
    _per_tok = (_hi - _lo) / (_N2 - _N1)
    _out[_name + "_tok_per_s"] = (
        None if _per_tok <= 0 else round(_b / _per_tok, 1))
    _out[_name + "_lo_hi_s"] = [round(_lo, 4), round(_hi, 4)]
    if _mode == "spec4":
        _out["int4draft_mean_accepted"] = round(float(_r[1]), 2)
    elif _mode == "spec":
        _spec_r = _r
_out["gamma"] = _G
_out["batch"] = _B
_out["mean_accepted"] = round(float(_spec_r[1]), 2)
_json.dumps(_out)
"""

# Continuous-batching server vs sequential decode.  Decode is
# HBM-bound (every step streams the weights once regardless of B), so
# B requests served together approach Bx the aggregate tokens/s of
# serving them one after another.  Three rows:
#   sequential  — B separate generate() calls (the no-server baseline)
#   batched_gen — one generate() at batch B (device-side upper bound)
#   server      — DecodeServer, which adds the per-step host sync the
#                 interactive streaming/EOS contract requires
#                 (reported as-is, it IS the product).
SERVE_CELL = """
import json as _json, time as _time
import jax as _jax, jax.numpy as _jnp, numpy as _np
from nbdistributed_tpu.models import (DecodeServer, init_params,
                                      make_generate_fn,
                                      smol_135m_config)
_cfg = smol_135m_config(dtype=_jnp.bfloat16, use_flash=True)
_p = init_params(_jax.random.PRNGKey(0), _cfg)
_N, _B, _L = 48, 4, 16
_prompts = [[(7 * i + j) % 100 + 1 for j in range(_L)]
            for i in range(_B)]
_g1 = make_generate_fn(_cfg, _N, max_len=256)
_gB = make_generate_fn(_cfg, _N, max_len=256)
_pb = _jnp.asarray(_prompts, _jnp.int32)

# Warm with prompt VALUES the timed calls never reuse, end every
# timed call in a value fetch, and take the median of 3 varied-input
# reps (the ops/timing.py contract).
_warm = (_pb + 37) % _cfg.vocab_size
int(_np.asarray(_g1(_p, _warm[:1]))[0, -1])     # warm B=1
int(_np.asarray(_gB(_p, _warm))[0, -1])         # warm B=4

def _median3(_f):
    _ts = []
    for _rep in range(3):
        _pbr = (_pb + _rep * 101) % _cfg.vocab_size
        _t0 = _time.time()
        _f(_pbr)
        _ts.append(_time.time() - _t0)
    _ts.sort()
    return _ts[1]

def _run_seq(_pbr):
    for _i in range(_B):
        int(_np.asarray(_g1(_p, _pbr[_i:_i + 1]))[0, -1])

_dt_seq = _median3(_run_seq)
_dt_bat = _median3(lambda _pbr: int(_np.asarray(_gB(_p, _pbr))[0, -1]))

_srv = DecodeServer(_p, _cfg, max_batch=_B, max_len=256, pad_to=_L)
_w = _srv.submit(_prompts[0], 2)                # warm prefill + step
_srv.run_until_done(); _srv.release(_w)
_t0 = _time.time()
_rids = [_srv.submit(_pr, _N) for _pr in _prompts]
_srv.run_until_done(max_steps=4 * _N)
_dt_srv = _time.time() - _t0
assert all(len(_srv.outputs[_r]) == _N for _r in _rids)

_tot = _B * _N
_json.dumps({
    "batch": _B, "new_tokens": _N,
    "sequential_tok_per_s": round(_tot / _dt_seq, 1),
    "batched_generate_tok_per_s": round(_tot / _dt_bat, 1),
    "server_tok_per_s": round(_tot / _dt_srv, 1),
    "batching_speedup": round(_dt_seq / _dt_bat, 2),
    "server_vs_sequential": round(_dt_seq / _dt_srv, 2),
    "per_step_host_sync_ms": round(
        (_dt_srv - _dt_bat) / _N * 1e3, 2),
})
"""


# 7B-class quantized decode at a real memory footprint (BASELINE.json
# config #5's Llama-2-7B intent): weights init on the host CPU backend
# (a full bf16 7B never touches the 16G chip) and are quantized there;
# the int8 (~6.7G) and int4 (~3.4G) trees move to the TPU one at a
# time (two generate programs compile per variant).  Decode is
# weight-streaming-bound, so tokens/s tracks HBM bandwidth and int4
# should approach 2x int8.
DECODE7B_CELL = """
import gc as _gc, json as _json, time as _time
import jax as _jax, jax.numpy as _jnp
from nbdistributed_tpu.models import (init_params as _init,
                                      llama2_7b_config as _cfg_fn,
                                      make_generate_fn as _mkgen,
                                      quantize_params as _quant,
                                      quantize_params4 as _quant4)
_cfg = _cfg_fn(dtype=_jnp.bfloat16, use_flash=True)
# Host-side init via numpy, not jax.random: threefry for 6.7e9
# elements on the CPU backend takes 20+ minutes; numpy's generator
# fills the same tree in ~1 min.  Values only need realistic scale —
# decode timing on TPU is value-independent.
import numpy as _np
_shapes = _jax.eval_shape(lambda k: _init(k, _cfg),
                          _jax.random.PRNGKey(0))
_rng = _np.random.default_rng(0)
with _jax.default_device(_jax.devices("cpu")[0]):
    _p_host = _jax.tree_util.tree_map(
        lambda s: _jnp.asarray(
            (_rng.standard_normal(s.shape, _np.float32) * 0.02),
            s.dtype),
        _shapes)
_dev = _jax.devices()[0]
_N1, _N2, _CL = 8, 32, 2048
# Roofline %: the decode kernel streams the FULL allocated cache every
# step (static grid over max_len k-blocks, masked compute), so
# bytes/token = weights + int8 K+V rows + fp32 scales at _CL.
_kv_bytes = (2 * _cfg.n_layers * _cfg.n_kv_heads * _CL
             * (_cfg.head_dim * 1 + 4))

_seed = [0]
def _prompt_for():
    _seed[0] += 1
    return _jax.random.randint(_jax.random.PRNGKey(_seed[0]), (1, 16),
                               0, _cfg.vocab_size)

# Per-token time = delta between a long and a short generate program
# (medians of fresh-prompt reps): cancels the fixed round-trip, and
# the np.asarray value fetch forces completion.
def _median_s(_g, _qp, _reps=3):
    _ts = []
    for _ in range(_reps):
        _pr = _prompt_for()
        _t0 = _time.time()
        int(_np.asarray(_g(_qp, _pr))[0, -1])
        _ts.append(_time.time() - _t0)
    _ts.sort()
    return _ts[len(_ts) // 2]

# int8 and int4 variants measured back to back on the same random 7B:
# only one quantized tree is ever resident on the chip (int8 is 6.7 G
# of the 16 G; freed before the 3.4 G int4 tree transfers).
_out = {"model": "llama2-7b (random init), weight-only quant + int8 KV",
        "cache_len": _CL}
for _name, _qfn in (("int8", _quant), ("int4", _quant4)):
    with _jax.default_device(_jax.devices("cpu")[0]):
        _qh = _qfn(_p_host)
    if _name == "int4":
        # Last quantize consumed it: drop the ~13.4 GB bf16 host tree
        # now so it never overlaps the int4 transfer (keeping it
        # resident across both passes nearly doubled peak
        # host memory on the TPU VM).
        del _p_host
    _qp = _jax.tree_util.tree_map(lambda a: _jax.device_put(a, _dev),
                                  _qh)
    del _qh; _gc.collect()
    _jax.block_until_ready(_jax.tree_util.tree_leaves(_qp)[0])
    _g1 = _mkgen(_cfg, _N1, max_len=_CL, kv_quantized=True)
    _g2 = _mkgen(_cfg, _N2, max_len=_CL, kv_quantized=True)
    int(_np.asarray(_g1(_qp, _prompt_for()))[0, -1])  # compile+first
    int(_np.asarray(_g2(_qp, _prompt_for()))[0, -1])
    _lo = _median_s(_g1, _qp)
    _hi = _median_s(_g2, _qp)
    _dt_tok = (_hi - _lo) / (_N2 - _N1)
    _w_bytes = sum(x.size * x.dtype.itemsize
                   for x in _jax.tree_util.tree_leaves(_qp))
    _bpt = _w_bytes + _kv_bytes
    _out[_name + "_weight_gb"] = round(_w_bytes / 1e9, 2)
    _out[_name + "_lo_hi_s"] = [round(_lo, 4), round(_hi, 4)]
    _out[_name + "_bytes_per_tok_gb"] = round(_bpt / 1e9, 2)
    if _dt_tok <= 0:
        _out[_name + "_tok_per_s"] = None     # noise won: say so
        _out[_name + "_ms_per_tok"] = None
        _out[_name + "_roofline_pct_v5e"] = None
    else:
        _out[_name + "_tok_per_s"] = round(1.0 / _dt_tok, 1)
        _out[_name + "_ms_per_tok"] = round(_dt_tok * 1e3, 2)
        _out[_name + "_roofline_pct_v5e"] = round(
            100.0 * (1.0 / _dt_tok) / (819e9 / _bpt), 1)
    del _qp, _g1, _g2; _gc.collect()
_out["int4_vs_int8"] = (
    round(_out["int4_tok_per_s"] / _out["int8_tok_per_s"], 2)
    if _out["int8_tok_per_s"] and _out["int4_tok_per_s"] else None)
_json.dumps(_out)
"""

# MoE dispatch-mode throughput: one train-step (loss+grads) per
# dispatch mode on a ~0.5B-expert MoE.  The dense one-hot dispatch
# materializes a (T, k, E, C) slot tensor — with C ~ cf*k*T/E that is
# O(T^2) MEMORY, terabytes at T = 8192 — so dense is measured only at
# a small token count (T = 512, where it is feasible), while sparse
# (sort/segment, linear) and dropless (ragged_dot) run the big shape
# too.  The small-shape three-way + big-shape pair together turn the
# dispatch-mode design (linear vs quadratic in tokens) into numbers.
MOE_CELL = """
import dataclasses, json as _json, time as _time
import jax as _jax, jax.numpy as _jnp
from nbdistributed_tpu.models.moe import (MoEConfig, init_moe_model,
                                          moe_loss_fn)
_DM, _DF, _NL, _B, _S, _steps = 1024, 2048, 8, 8, 1024, 3
_cfg0 = MoEConfig(vocab_size=32000, d_model=_DM, n_layers=_NL,
                  n_heads=16, n_kv_heads=4, d_ff=_DF,
                  max_seq_len=2048, n_experts=8, top_k=2,
                  dtype=_jnp.bfloat16, use_flash=True)
_p = init_moe_model(_jax.random.PRNGKey(0), _cfg0)
_out = {"capacity_factor": _cfg0.capacity_factor,
        "n_experts": _cfg0.n_experts, "top_k": _cfg0.top_k}

import numpy as _np
_seed = [1000]
def _measure(mode, B, S):
    # Per-step time = delta between a (1+_steps)-step and a 1-step
    # loop (median of 2 each), every step on FRESH token values and
    # every loop ending in a value fetch (the ops/timing.py contract).
    _cfg = dataclasses.replace(_cfg0, moe_dispatch=mode)
    _f = _jax.jit(_jax.grad(lambda p, b: moe_loss_fn(p, b, _cfg)))
    def _toks():
        _seed[0] += 1
        return _jax.random.randint(_jax.random.PRNGKey(_seed[0]),
                                   (B, S), 0, _cfg0.vocab_size)
    def _loop_s(_n):
        _ts = []
        for _ in range(2):
            _batches = [_toks() for _i in range(_n)]
            _t0 = _time.time()
            for _tk in _batches:
                _g = _f(_p, {"tokens": _tk})
            float(_np.asarray(
                _jax.tree_util.tree_leaves(_g)[0]).ravel()[0])
            _ts.append(_time.time() - _t0)
        return min(_ts)
    float(_np.asarray(_jax.tree_util.tree_leaves(
        _f(_p, {"tokens": _toks()}))[0]).ravel()[0])   # compile
    _dt = (_loop_s(1 + _steps) - _loop_s(1)) / _steps
    return None if _dt <= 0 else B * S / _dt           # noise: say so

_Bs, _Ss = max(1, _B // 4), max(32, _S // 4)       # small: T feasible
_out["small_tokens"] = _Bs * _Ss                    # for dense
for _mode in ("dense", "sparse", "dropless"):
    _tps = _measure(_mode, _Bs, _Ss)
    _out["small_" + _mode + "_tok_per_s"] = (
        None if _tps is None else round(_tps, 1))
_out["big_tokens"] = _B * _S
for _mode in ("sparse", "dropless"):
    _tps = _measure(_mode, _B, _S)
    _out["big_" + _mode + "_tok_per_s"] = (
        None if _tps is None else round(_tps, 1))
for _mode in ("sparse", "dropless"):
    _num = _out["small_" + _mode + "_tok_per_s"]
    _den = _out["small_dense_tok_per_s"]
    _out["small_" + _mode + "_vs_dense"] = (
        None if not _num or not _den else round(_num / _den, 2))
_json.dumps(_out)
"""

# all_reduce bus-bandwidth sweep; degenerates to an HBM on-device copy
# measurement on a 1-process world (labeled as such).
ALLREDUCE_CELL = """
import json as _json, time as _time
import jax as _jax, jax.numpy as _jnp
_rows = []
for _mib in (1, 4, 16, 64):
    _n = _mib * (1 << 20) // 4
    _x = _jax.random.normal(_jax.random.PRNGKey(_mib), (_n,),
                            _jnp.float32)
    _jax.block_until_ready(_x)
    if world_size > 1:
        _jax.block_until_ready(all_reduce(_x))      # warm the program
        _t0 = _time.time()
        for _i in range(5):
            # Vary the operand per call so a program+input result
            # cache can never serve a timed iteration (i+1: factor
            # 1.0 would replay the warm-up input bit-for-bit).
            _y = all_reduce(_x * (1.0 + (_i + 1) * 0.015625))
        float(_y[0])                                # value fetch
        _dt = (_time.time() - _t0) / 5
        _bus = 2 * (world_size - 1) / world_size * _mib / 1024 / _dt
        _rows.append({"mib": _mib, "s": round(_dt, 6),
                      "bus_gb_per_s_per_chip": round(_bus, 3)})
    else:
        # Chained scan delta (same pattern as the flash cell): the
        # carry feeds each +1.0, so per-iteration HBM read+write time
        # is (long-short chain)/delta with a value fetch at the end.
        def _loop_s(_n):
            _g = _jax.jit(lambda a: _jax.lax.scan(
                lambda c, _: (c + 1.0, None), a, None, length=_n)[0])
            float(_g(_x).sum())                     # compile + first
            _ts = []
            for _i in range(3):
                _xi = _x * (1.0 + 0.0625 * (_i + 1))
                _t0 = _time.time()
                float(_g(_xi).sum())
                _ts.append(_time.time() - _t0)
            return sorted(_ts)[1]
        _dt = (_loop_s(12) - _loop_s(2)) / 10
        _rows.append({"mib": _mib, "s": round(_dt, 6),
                      "hbm_rw_gb_per_s": (
                          None if _dt <= 0 else
                          round(2 * _mib / 1024 / _dt, 1))})
_json.dumps({"mode": "bus" if world_size > 1 else
             "single_chip_hbm_bound", "rows": _rows})
"""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_result_json(resp) -> dict | None:
    """The cells above end in json.dumps(...), so the REPL echo is the
    repr of a JSON string."""
    out = resp.data.get("output", "")
    line = out.strip().splitlines()[-1] if out.strip() else ""
    try:
        return json.loads(ast.literal_eval(line))
    except Exception:
        return None


def _spawn_world(backend: str, world: int):
    """Spawn a worker world; returns (comm, pm) attached and ready."""
    from nbdistributed_tpu.manager import wait_until_ready
    comm = CommunicationManager(num_workers=world, timeout=300)
    pm = ProcessManager()
    try:
        pm.add_death_callback(lambda r, rc: comm.mark_worker_dead(r))
        pm.start_workers(world, comm.port, backend=backend)
        wait_until_ready(comm, pm, 150)
    except Exception:
        _teardown(comm, pm, world)
        raise
    return comm, pm


def _teardown(comm, pm, world: int) -> None:
    """Polite shutdown broadcast, then the tiered kill ladder, then the
    listener close.  BLOCKING (pm.shutdown waits through SIGTERM →
    SIGKILL), so by the time it returns no worker of this world can
    still be holding chip HBM when the next world spawns."""
    try:
        comm.post(list(range(world)), "shutdown")
        time.sleep(0.3)
    except Exception:
        pass
    try:
        pm.shutdown()
    except Exception:
        pass
    try:
        comm.shutdown()
    except Exception:
        pass


def _exec_measure(comm, name: str, cell: str, timeout: int) -> dict | None:
    """Run one measurement cell on rank 0; parse its trailing JSON."""
    resp = comm.send_to_ranks([0], "execute", cell, timeout=timeout)
    m = resp[0]
    if m.data.get("error"):
        log(f"[bench] {name} cell failed: "
            f"{m.data.get('traceback', m.data['error'])}")
        return None
    out = parse_result_json(m)
    if out is not None:
        log(f"[bench] {name}: {out}")
    return out


def measure_flight_recorder(comm, echoes: int = 40) -> dict:
    """ISSUE 3 numbers for the BENCH json: how many events this run's
    coordinator ring holds, the raw append cost, and the flight
    recorder's overhead on a control-plane echo round-trip measured
    directly — the same ``get_status`` echo with recording on
    (default) and forced off.  The acceptance bar is < 5 %: the append
    is microseconds against a multi-hundred-microsecond socket
    round-trip."""
    import statistics

    from nbdistributed_tpu.observability import flightrec

    out: dict = {"coordinator_events": len(comm.flight),
                 "ring_path": getattr(comm.flight, "path", None)}

    rec = flightrec.FlightRecorder(
        os.path.join(flightrec.run_dir(), "bench-micro.ring"))
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        rec.record("dispatch", msg_id="0123456789abcdef",
                   type="execute", attempt=0)
    out["append_ns"] = round((time.perf_counter() - t0) / n * 1e9)
    rec.close()

    def _echo_s() -> float:
        t0 = time.perf_counter()
        comm.send_to_ranks([0], "get_status", timeout=60)
        return time.perf_counter() - t0

    def _median_echo() -> float:
        return statistics.median(_echo_s() for _ in range(echoes))

    def _worker_flight(enabled: bool) -> None:
        # BOTH ends record on the echo path (coordinator 'send',
        # worker 'dispatch'): the no-record leg must silence the
        # worker's ring too or the comparison hides half the cost.
        comm.send_to_ranks(
            [0], "execute",
            "import nbdistributed_tpu.observability.flightrec as _f\n"
            f"_f.recorder().enabled = {enabled}", timeout=60)

    _median_echo()                      # warm both paths
    on_s = _median_echo()
    comm.flight.enabled = False
    _worker_flight(False)
    try:
        off_s = _median_echo()
    finally:
        comm.flight.enabled = True
        _worker_flight(True)
    out["echo_us_record"] = round(on_s * 1e6, 1)
    out["echo_us_norecord"] = round(off_s * 1e6, 1)
    out["echo_overhead_pct"] = round((on_s - off_s) / off_s * 100, 2) \
        if off_s > 0 else None
    return out


def measure_pipeline(comm, world: int, k: int = 24,
                     ddp_steps: int = 12) -> dict:
    """ISSUE 14 numbers: per-cell dispatch overhead under the three
    dispatch modes on the SAME cells, so the differences are pure
    control plane —

    * ``sync``: today's send-and-wait per cell (k round trips);
    * ``async``: k cells streamed through ``comm.submit`` with one
      wait at the end (the in-flight-window wire path; admission
      gating lives a layer up and adds nothing for independent
      cells);
    * ``repeat``: ONE dispatch that loops k steps worker-side
      (``%%distributed --repeat k``) — the amortization bound.

    Reported per-cell/per-step in ms for a trivial cell (pure
    dispatch overhead) and as steps/s for the cell-wise DDP
    ``STEP_CELL`` (the headline BENCH metric's three modes).  Runs on
    CPU worlds too — the row is BENCH-comparable everywhere; the
    <0.1 ms/step target is judged on the next live TPU window.
    """
    trivial = "_pipe = 1 + 1"
    ranks = list(range(world))

    def _sync(cell: str, n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            comm.send_to_all("execute", cell, timeout=600)
        return time.perf_counter() - t0

    def _async(cell: str, n: int) -> float:
        t0 = time.perf_counter()
        handles = [comm.submit(ranks, "execute", cell, timeout=600)
                   for _ in range(n)]
        for h in handles:
            h.wait()
        return time.perf_counter() - t0

    def _repeat(cell: str, n: int) -> float:
        t0 = time.perf_counter()
        resp = comm.send_to_all(
            "execute", {"code": cell, "target_ranks": ranks,
                        "repeat": n}, timeout=600)
        for m in resp.values():
            if m.data.get("error"):
                raise RuntimeError(m.data["error"])
        return time.perf_counter() - t0

    # Warm each path once so compile/first-dispatch costs don't skew
    # the per-mode comparison.
    comm.send_to_all("execute", trivial, timeout=600)
    out: dict = {"cells": k, "ddp_steps": ddp_steps}
    sync_s = _sync(trivial, k)
    async_s = _async(trivial, k)
    rep_s = _repeat(trivial, k)
    out["dispatch_ms_per_cell"] = {
        "sync": round(sync_s / k * 1e3, 3),
        "async": round(async_s / k * 1e3, 3),
        "repeat": round(rep_s / k * 1e3, 3),
    }
    out["overlap_speedup"] = round(sync_s / async_s, 2) \
        if async_s > 0 else None

    # Cell-wise DDP under each mode: the headline metric's three
    # dispatch disciplines on the real local_step cell.
    ddp = {}
    for name, fn in (("sync", _sync), ("async", _async),
                     ("repeat", _repeat)):
        try:
            el = fn(STEP_CELL, ddp_steps)
            ddp[name] = round(ddp_steps / el, 2)
        except Exception as e:
            log(f"[bench] pipeline ddp/{name} failed: {e}")
            ddp[name] = None
    out["ddp_steps_per_s"] = ddp
    if ddp.get("sync") and ddp.get("repeat"):
        # How much of the worker-local loop's rate cell-wise dispatch
        # reaches per mode — the "within 10% of a worker-local loop"
        # acceptance ratio, measurable every run.
        out["vs_worker_local_loop"] = {
            m: round(v / ddp["repeat"], 3)
            for m, v in ddp.items() if v}
    return out


def measure_telemetry_peaks(comm) -> dict:
    """Peak-HBM summary from the heartbeat-piggybacked telemetry
    snapshots the coordinator accumulated during the run — the device-
    memory-over-time trajectory for the BENCH json."""
    from nbdistributed_tpu.observability import telemetry as _tel

    peaks = {}
    last = {}
    for r in range(comm.num_workers):
        hist = comm.telemetry_history(r)
        if not hist:
            continue
        p = _tel.peak_hbm(hist)
        if p:
            peaks[str(r)] = p
        snap = hist[-1]
        last[str(r)] = {k: snap.get(k)
                        for k in ("bufs", "compiles", "compile_s")
                        if snap.get(k) is not None}
    out = {}
    if peaks:
        out["peak_hbm_bytes"] = peaks
    if last:
        out["last_snapshot"] = last
    return out


# Sentinel: measure_family could not even attach a worker — the signal
# run_families uses to distinguish "this cell failed" (keep going) from
# "no chip is answering" (stop burning attach timeouts).
SPAWN_FAILED = object()


def measure_family(backend: str, name: str, cell: str, timeout: int):
    """Run ONE measurement family in its own fresh worker process.

    Per-measurement process isolation is the bench rule, learned the
    hard way: round 3's only on-chip flash sample measured 0.065x vs
    XLA inside a worker whose HBM a previously-OOMed 1B train cell had
    filled — no amount of in-process cleanup (namespace sweeps,
    jax.clear_caches, live-array deletion) reliably un-poisons a
    wedged allocator, and a contaminated number is worse than none.
    The worker is spawned fresh, runs exactly one measurement cell,
    and is torn down (blocking) before the next family starts, so no
    family can see another's leftovers.

    Returns the parsed result dict, None (cell failed — measurement
    lost but the world is healthy), or :data:`SPAWN_FAILED` (no worker
    attached at all).
    """
    log(f"[bench] {name}: spawning fresh worker")
    try:
        comm, pm = _spawn_world(backend, 1)
    except Exception as e:
        log(f"[bench] {name} skipped (spawn failed): {e}")
        return SPAWN_FAILED
    try:
        return _exec_measure(comm, name, cell, timeout)
    except Exception as e:
        log(f"[bench] {name} skipped: {e}")
        return None
    finally:
        _teardown(comm, pm, 1)


def tpu_families():
    """(name, cell, timeout) per TPU measurement family — shared by
    the full run and the NBD_BENCH_ONLY re-measure mode."""
    return (
        # Flagship MFU (135M — the reference demo scale).
        ("smol135m", MFU_CELL.format(
            peak=PEAK_EXPR, shape="(8, 2048, 10)", reps="(3, 2)",
            tr_start="2 * _B", extra_cfg="",
            cfg_name="smol_135m_config"), 2400),
        # MFU at a scale where MFU means something: ~1.1B params,
        # d_model=2048 — GEMMs a v5e MXU can fill.
        ("tinyllama_1b", MFU_CELL.format(
            peak=PEAK_EXPR, shape="(8, 2048, 5)", reps="(3, 2)",
            tr_start="2 * _B", extra_cfg="",
            cfg_name="tinyllama_1b_config"), 2400),
        # Long-context single-chip training: S=8192 with per-layer
        # remat; the policy table (and the ce_chunk row — at S=8192
        # the fp32 logits alone are 1.6 G/row) lands alongside.
        ("smol135m_s8192", MFU_CELL.format(
            peak=PEAK_EXPR, shape="(1, 8192, 3)", reps="(3, 2)",
            tr_start="2 * _B", extra_cfg=", max_seq_len=8192",
            cfg_name="smol_135m_config"), 2400),
        # Kernel-vs-XLA only where the kernel compiles (interpret
        # mode on CPU is orders slower by design).
        ("flash_attn", FLASH_CELL, 900),
        ("decode", DECODE_CELL, 1200),
        # +2 compiles for the int4-draft row.
        ("speculative", SPEC_CELL, 1500),
        # Prefix-admission measurement added two more server worlds
        # (extra prefill/absorb compiles) — budget accordingly.
        ("serving", SERVE_CELL, 1800),
        # ~10 G of quantized weights (int8 then int4 trees) are built
        # host-side and four generate programs compile at 7B: budget
        # wide.
        ("decode_7b_int8", DECODE7B_CELL, 3000),
        # MoE dispatch modes (dense/sparse/dropless train-step
        # throughput at the same routing) — evidences the dispatch
        # design (linear vs quadratic in tokens) on silicon.
        ("moe_dispatch", MOE_CELL, 1800),
    )


def run_families_only(names: list[str]) -> int:
    """NBD_BENCH_ONLY mode: measure just the named families, each in a
    fresh TPU worker (after ``tune_flash.py`` writes a block table,
    fresh workers import it, so the kernel families alone show the
    tuned numbers without a full bench pass)."""
    unknown = [n for n in names
               if n not in {f[0] for f in tpu_families()}]
    if unknown:
        log(f"[bench] unknown families {unknown}; known: "
            f"{[f[0] for f in tpu_families()]}")
        return 1
    extra: dict = {}
    fams = [f for f in tpu_families() if f[0] in names]
    run_families("tpu", fams, extra)
    if not extra:
        log("[bench] no family produced a result")
        return 1
    print(json.dumps({"metric": "bench_families_remeasure_tpu",
                      "value": len(extra), "unit": "families",
                      "vs_baseline": 1.0, "extra": extra}), flush=True)
    return 0


def run_families(backend: str, families, extra: dict,
                 measure=None) -> None:
    """Run measurement families, each in a fresh process, filling
    ``extra[name]``.  Bails out after two consecutive spawn failures:
    a chip that stopped answering would otherwise cost the full
    ~150 s attach timeout per remaining family, serially.

    ``NBD_BENCH_FAMILY_BUDGET_S`` (default 5400) bounds the whole
    family stage: once exceeded, remaining families are skipped with a
    loud log instead of risking an outer deadline killing the run
    before its one JSON line prints."""
    measure = measure if measure is not None else measure_family
    try:
        budget = float(knobs.get_raw("NBD_BENCH_FAMILY_BUDGET_S",
                                     "5400"))
    except ValueError:
        log("[bench] NBD_BENCH_FAMILY_BUDGET_S is not a number; "
            "using 5400")
        budget = 5400.0
    t_start = time.time()
    spawn_failures = 0
    families = list(families)
    for i, (name, cell, cell_timeout) in enumerate(families):
        elapsed = time.time() - t_start
        if elapsed > budget:
            log(f"[bench] family budget {budget:.0f}s exhausted after "
                f"{elapsed:.0f}s — skipping "
                f"{[n for n, _, _ in families[i:]]}")
            return
        out = measure(backend, name, cell, cell_timeout)
        if out is SPAWN_FAILED:
            spawn_failures += 1
            if spawn_failures >= 2:
                log("[bench] two consecutive spawn failures — no chip "
                    "is answering, skipping remaining families")
                return
            continue
        spawn_failures = 0
        if out is not None:
            extra[name] = out


# Elastic-pool family (ISSUE 16): a deliberately odd-shaped jit so
# neither the in-memory nor a stale persistent cache can pre-own it —
# the SAME cell runs cold on a fresh pool, then again on a
# resized-in fleet whose persistent compile cache should serve it
# warm.  The final expression is the worker-side compile+run seconds.
ELASTIC_COMPILE_CELL = """
import time as _t
import jax as _jax, jax.numpy as _jnp
_t0 = _t.time()
_f = _jax.jit(lambda x: _jnp.tanh(x @ x.T).sum()
              + _jnp.sin(x).mean())
_x = _jnp.ones((521, 517), _jnp.float32)
float(_f(_x))
_t.time() - _t0
"""


def measure_elastic() -> dict | None:
    """The ISSUE 16 numbers: cold vs warm first-cell seconds (the
    persistent compile cache serving a resized-in worker), the resize
    drain-barrier and whole-flip wall-clock, and a tenant migration
    end to end between two pools under one runs root.

    Always measured on the CPU backend in pools of its own (the
    mechanism under test is the control plane + XLA cache, not the
    accelerator), AFTER the pooled bench world is gone."""
    import shutil
    import tempfile

    from nbdistributed_tpu.gateway import router as router_mod
    from nbdistributed_tpu.gateway.client import TenantClient
    from nbdistributed_tpu.gateway.daemon import GatewayDaemon
    from nbdistributed_tpu.gateway.scheduler import SchedPolicy

    runs_root = tempfile.mkdtemp(prefix="nbd-bench-elastic-")
    run_a = os.path.join(runs_root, "pool-a")
    run_b = os.path.join(runs_root, "pool-b")
    os.makedirs(run_a)
    os.makedirs(run_b)
    saved = os.environ.get("NBD_RUN_DIR")
    gw_a = gw_b = client = None
    out: dict = {"backend": "cpu"}

    def _cell_seconds(cl) -> float:
        r = cl.execute(ELASTIC_COMPILE_CELL, target_ranks=[0],
                       timeout=300)
        res = (r.get("results") or {}).get("0") or {}
        if r.get("error") or res.get("error"):
            raise RuntimeError(r.get("error") or res["error"])
        return float(ast.literal_eval(res["output"]))

    try:
        os.environ["NBD_RUN_DIR"] = run_a
        gw_a = GatewayDaemon(
            1, backend="cpu",
            policy=SchedPolicy("fair", mesh_slots=1,
                               tenant_inflight=8, queue_depth=16),
            request_timeout=None, attach_timeout=240.0)
        client = TenantClient(gw_a.tenant_host, gw_a.tenant_port,
                              "bench", pool_token=gw_a.pool_token)
        out["cold_first_cell_s"] = round(_cell_seconds(client), 4)

        res = gw_a.resize(2, reason="bench")
        if res.get("status") != "resized":
            raise RuntimeError(f"resize failed: {res}")
        out["resize_drain_s"] = res["drain_s"]
        out["resize_wall_s"] = res["wall_s"]
        # Fresh processes, wiped namespaces — only the persistent
        # cache can make this fast.
        out["warm_first_cell_s"] = round(_cell_seconds(client), 4)
        if out["warm_first_cell_s"] > 0:
            out["warm_speedup"] = round(
                out["cold_first_cell_s"] / out["warm_first_cell_s"],
                2)
        client.close()
        client = None

        os.environ["NBD_RUN_DIR"] = run_b
        gw_b = GatewayDaemon(
            1, backend="cpu",
            policy=SchedPolicy("fair", mesh_slots=1,
                               tenant_inflight=8, queue_depth=16),
            request_timeout=None, attach_timeout=240.0)
        t0 = time.time()
        router_mod.migrate_tenant("bench", run_a, run_b, force=True)
        out["migrate_s"] = round(time.time() - t0, 4)
        return out
    finally:
        if client is not None:
            try:
                client.close()
            except Exception:
                pass
        for gw in (gw_b, gw_a):
            if gw is not None:
                try:
                    gw.close()
                except Exception:
                    pass
        if saved is None:
            os.environ.pop("NBD_RUN_DIR", None)
        else:
            os.environ["NBD_RUN_DIR"] = saved
        shutil.rmtree(runs_root, ignore_errors=True)


SERVE_SPEC_CELL = (
    "import jax as _j, jax.numpy as _jn\n"
    "from nbdistributed_tpu.models import tiny_config, init_params\n"
    "cfg = tiny_config(dtype=_jn.float32, use_flash=False)\n"
    "params = init_params(_j.random.PRNGKey(0), cfg)\n")


def measure_serving() -> dict | None:
    """The ISSUE 17 serving-fast-path numbers from the closed-loop
    load harness: sustained tokens/s with client-observed p99
    TTFT/TPOT, then the shed rate at 2x the measured sustainable
    request rate — all through the real tenant plane (the exact core
    ``tools/nbd_loadgen.py`` runs) against a paged, multi-rank decode
    plane.

    CPU backend in a pool of its own (the mechanism under test is the
    serving control plane, not the accelerator), AFTER the pooled
    bench world is gone."""
    import shutil
    import tempfile

    from nbdistributed_tpu.gateway.client import TenantClient
    from nbdistributed_tpu.gateway.daemon import GatewayDaemon
    from nbdistributed_tpu.gateway.scheduler import SchedPolicy
    from nbdistributed_tpu.serving_fast import LoadConfig, run_load

    run_dir = tempfile.mkdtemp(prefix="nbd-bench-serving-")
    saved = os.environ.get("NBD_RUN_DIR")
    gw = client = None
    out: dict = {"backend": "cpu"}

    def _load(cl, rps: float, duration: float) -> dict:
        from nbdistributed_tpu.serving_fast.loadgen import (
            ClientTransport)
        cfg = LoadConfig(rps=rps, duration_s=duration,
                         arrival="poisson", seed=7,
                         prompt_len=(4, 12), max_new=(4, 10),
                         drain_s=120.0)
        return run_load(ClientTransport(cl), cfg)

    try:
        os.environ["NBD_RUN_DIR"] = run_dir
        gw = GatewayDaemon(
            2, backend="cpu",
            policy=SchedPolicy("fair", mesh_slots=1,
                               tenant_inflight=64, queue_depth=64),
            request_timeout=None, attach_timeout=240.0)
        client = TenantClient(gw.tenant_host, gw.tenant_port,
                              "loadgen", pool_token=gw.pool_token)
        client.serve_start(SERVE_SPEC_CELL, max_batch=4, max_len=48,
                           pad_to=4, steps=4, queue_depth=8,
                           inflight=64, decode_ranks=2,
                           kv_block_tokens=8, timeout=600)
        # Sustained phase: modest offered rate, everything completes.
        rep = _load(client, rps=2.0, duration=8.0)
        out["tokens_per_s"] = rep["tokens_per_s"]
        out["p99_ttft_ms"] = (rep["client"]["ttft_ms"]
                              or {}).get("p99")
        out["p99_tpot_ms"] = (rep["client"]["tpot_ms"]
                              or {}).get("p99")
        out["sustained_completed"] = rep["completed"]
        out["sustained_hung"] = rep["hung"]
        # Overload phase: 2x the COMPLETION rate the plane just
        # demonstrated (floor 2x offered) — the bounded queue must
        # shed with explicit verdicts, not hang.
        sustainable = max(rep["completed"] / max(rep["duration_s"],
                                                 1e-9), 2.0)
        rep2 = _load(client, rps=2.0 * sustainable, duration=6.0)
        out["overload_rps"] = round(2.0 * sustainable, 2)
        out["overload_shed_rate"] = rep2["shed_rate"]
        out["overload_completed"] = rep2["completed"]
        out["overload_hung"] = rep2["hung"]
        st = client.serve_status()
        kv = st.get("kv") or {}
        if kv:
            out["kv_block_tokens"] = kv.get("block_tokens")
            out["kv_blocks_per_rank"] = kv.get("blocks_per_rank")
        return out
    finally:
        if client is not None:
            try:
                client.serve_stop()
            except Exception:
                pass
            try:
                client.close()
            except Exception:
                pass
        if gw is not None:
            try:
                gw.close()
            except Exception:
                pass
        if saved is None:
            os.environ.pop("NBD_RUN_DIR", None)
        else:
            os.environ["NBD_RUN_DIR"] = saved
        shutil.rmtree(run_dir, ignore_errors=True)


def measure_trainguard() -> dict | None:
    """The ISSUE 19 training-integrity-guard numbers: guarded vs
    unguarded DDP step rate at the default audit/snapshot cadences,
    plus the cost of one replica-consistency audit step (the param
    fingerprint fold).  The acceptance bar is guarded overhead <10%:
    the device-side finite gate rides the compiled step and the host
    side resolves verdicts one step late, so the steady-state cost is
    a deque rotation plus an already-materialized scalar read.

    CPU, in-process: the mechanism under test is the guard
    orchestration, not the accelerator."""
    import time as _time

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    from nbdistributed_tpu.parallel import data_parallel
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.resilience import trainguard as tg

    n_steps = 600
    m = mesh_mod.make_mesh({"dp": 1})

    def loss_fn(params, batch):
        x, y = batch
        pred = jnp.tanh(x @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - y) ** 2)

    key = jax.random.PRNGKey(0)
    k1, k2, kx = jax.random.split(key, 3)
    params = {"w1": jax.random.normal(k1, (256, 256), jnp.float32) * 0.05,
              "w2": jax.random.normal(k2, (256, 64), jnp.float32) * 0.05}
    opt = optax.adam(1e-3)
    # Batch 256 (= the hidden width): the guard's device-side work —
    # the fp32 grad-norm² reduction and the cond's grad
    # materialization — is O(params) and batch-INdependent, while the
    # step's useful compute scales with the batch.  A 64-row batch
    # over an 81K-param model makes the step artificially tiny
    # relative to that fixed cost and measures mostly dispatch noise;
    # square batches are the representative operating point.
    batch = (jax.random.normal(kx, (256, 256)), jnp.zeros((256, 64)))

    def make_runner(guard: bool):
        # Fresh copies: replicate() aliases when the sharding already
        # matches, and the donating step would eat the template tree.
        p, _ = data_parallel.ddp_init(
            jax.tree_util.tree_map(jnp.copy, params), None, m)
        s = jax.jit(opt.init)(p)
        step = data_parallel.make_ddp_step(loss_fn, opt, m, guard=guard)
        if guard:
            g = tg.TrainGuard(step, p, s, rank=0)

            def run(n: int) -> None:
                loss = None
                for _ in range(n):
                    loss = g.step(batch)
                jax.block_until_ready(loss)

            return run, g.finish
        state = [p, s]

        def run(n: int) -> None:
            p, s = state
            for _ in range(n):
                p, s, loss = step(p, s, batch)
            state[:] = [p, s]
            jax.block_until_ready(loss)

        return run, (lambda: None)

    # The CPU here is shared and noisy (identical reps vary by >20%),
    # so back-to-back whole-loop timings compare different wall-clock
    # windows and the noise swamps the signal.  Interleave the two
    # loops in small slices instead: any interference burst lands on
    # both sides roughly equally, and the *ratio* — the number under
    # acceptance — stays honest.  The guarded side still steps its own
    # counter, so the default audit/snapshot cadences fire exactly as
    # they would in a straight run.
    run_u, fin_u = make_runner(guard=False)
    run_g, fin_g = make_runner(guard=True)
    # Warm the guarded runner PAST its first audit+snapshot (default
    # cadence 50): the first post-step snapshot re-specializes the
    # jitted tree copy for the stepped opt state's layouts, a one-time
    # per-process compile that a 200-step microbenchmark would
    # otherwise misread as recurring audit cost.
    run_u(55)
    run_g(55)
    # Per-side throughput = chunk size over the MINIMUM chunk time
    # (standard timeit practice): interference only ever adds time, so
    # the fastest of many small interleaved chunks estimates each
    # side's uncontended cost — medians still carried 5-10 points of
    # run-to-run jitter on this box.  The chunk equals the default
    # audit/snapshot cadence (50), so EVERY guarded chunk carries
    # exactly one audit + one snapshot — the minimum cannot dodge the
    # event cost the acceptance bar is about.
    chunk = 50
    ts_u: list[float] = []
    ts_g: list[float] = []
    for _ in range(n_steps // chunk):
        t0 = _time.perf_counter()
        run_u(chunk)
        ts_u.append(_time.perf_counter() - t0)
        t0 = _time.perf_counter()
        run_g(chunk)
        ts_g.append(_time.perf_counter() - t0)
    fin_g()
    fin_u()
    base = chunk / min(ts_u)
    guarded = chunk / min(ts_g)
    # One audit step's cost in isolation: fingerprint fold over the
    # params (world=1, so the gather/vote legs are the short-circuit).
    p, _ = data_parallel.ddp_init(
        jax.tree_util.tree_map(jnp.copy, params), None, m)
    tg.tree_fingerprint(p)  # compile
    t0 = _time.perf_counter()
    reps = 5
    for _ in range(reps):
        tg.tree_fingerprint(p)
    audit_ms = (_time.perf_counter() - t0) / reps * 1000.0
    return {"backend": "cpu", "steps": n_steps,
            "steps_per_s_unguarded": round(base, 2),
            "steps_per_s_guarded": round(guarded, 2),
            "overhead_pct": round((base - guarded) / base * 100.0, 2),
            "audit_step_ms": round(audit_ms, 3)}


def measure_transfer() -> dict | None:
    """The ISSUE 20 numbers: bulk-plane push/pull throughput — the
    chunked streaming protocol vs one legacy frame — plus the
    per-chunk compression ratio on compressible data.  CPU loopback,
    1-worker world of its own: the mechanism under test is the
    chunked wire protocol (flow control, crc, assembly copies), not
    the accelerator or a real NIC."""
    import numpy as np

    from nbdistributed_tpu.messaging import xfer

    size = 64 << 20
    out: dict = {"backend": "cpu", "bytes": size}
    rng = np.random.default_rng(0)
    incompressible = rng.integers(0, 256, size, dtype=np.uint8)
    comm = pm = None
    try:
        comm, pm = _spawn_world("cpu", 1)

        t0 = time.time()
        st = xfer.push_value(comm, [0], "xb", incompressible)
        out["push_chunked_gb_s"] = round(size / (time.time() - t0)
                                         / 1e9, 3)
        out["chunks"] = st["chunks"]
        out["inflight_peak_mb"] = round(
            st["inflight_peak_bytes"] / 1e6, 1)

        t0 = time.time()
        comm.send_to_ranks([0], "set_var", {"name": "xl"},
                           bufs={"value": incompressible},
                           timeout=xfer.scaled_timeout(size))
        out["push_legacy_gb_s"] = round(size / (time.time() - t0)
                                        / 1e9, 3)

        t0 = time.time()
        _, stats = xfer.pull_value(comm, 0, "xb")
        out["pull_chunked_gb_s"] = round(size / (time.time() - t0)
                                         / 1e9, 3)
        out["pull_resent_chunks"] = stats["resent_chunks"]

        t0 = time.time()
        resp = comm.send_to_rank(0, "get_var", "xl",
                                 timeout=xfer.scaled_timeout(size))
        np.asarray(resp.bufs["value"])  # materialize the decode view
        out["pull_legacy_gb_s"] = round(size / (time.time() - t0)
                                        / 1e9, 3)

        # Compression ratio on low-entropy data (repeated-pattern
        # bytes — the shape of embedding tables / quantized state),
        # forced through the always-available stdlib codec.
        compressible = np.tile(np.arange(256, dtype=np.uint8),
                               size // 256)
        saved = os.environ.get("NBD_XFER_CODEC")
        os.environ["NBD_XFER_CODEC"] = "zlib"
        try:
            st = xfer.push_value(comm, [0], "xc", compressible)
        finally:
            if saved is None:
                os.environ.pop("NBD_XFER_CODEC", None)
            else:
                os.environ["NBD_XFER_CODEC"] = saved
        out["compress_codec"] = st["codec"]
        out["compress_ratio"] = round(
            st["bytes"] / max(1, st["wire_bytes"]), 2)
        out["push_zlib_gb_s"] = round(
            size / max(1e-9, st["seconds"]) / 1e9, 3)
        out["codecs_available"] = xfer.available_codecs()
        return out
    finally:
        if comm is not None:
            _teardown(comm, pm, 1)


def main() -> int:
    # A SIGTERM (e.g. an outer `timeout` expiring) must tear down the
    # spawned workers: raising SystemExit lets run()'s finally-block
    # ProcessManager.shutdown() execute.  An orphaned worker keeps its
    # HBM allocations alive and poisons every later run on the shared
    # chip with RESOURCE_EXHAUSTED (observed on-chip this round).
    import signal

    def _term(signum, frame):
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, _term)
    only = knobs.get_str("NBD_BENCH_ONLY")
    if only:
        return run_families_only(
            [n.strip() for n in only.split(",") if n.strip()])
    # World size: NBD_BENCH_WORLD overrides; default is one worker on
    # one chip.  The backend is not detected: this script measures the
    # chip, and a worker that cannot get one refuses to start.
    world = int(knobs.get_raw("NBD_BENCH_WORLD", "1"))
    return run("tpu", world)


def run(backend: str, world: int) -> int:
    log(f"[bench] backend={backend} world={world}")

    comm = pm = None
    try:
        comm, pm = _spawn_world(backend, world)
        log("[bench] workers attached; running setup cell")
        resp = comm.send_to_all("execute", SETUP, timeout=600)
        for r, m in resp.items():
            if m.data.get("error"):
                log(f"[bench] setup failed on rank {r}: "
                    f"{m.data['traceback']}")
                return 1

        for _ in range(WARMUP):
            comm.send_to_all("execute", STEP_CELL, timeout=600)

        # compute = worker-side measured duration (excludes the control
        # plane), collected from the same steps we time end-to-end
        durations = []
        t0 = time.time()
        for i in range(STEPS):
            resp = comm.send_to_all("execute", STEP_CELL, timeout=600)
            for r, m in resp.items():
                if m.data.get("error"):
                    log(f"[bench] step {i} failed on rank {r}")
                    return 1
            durations.append(max(m.data["duration_s"]
                                 for m in resp.values()))
        elapsed = time.time() - t0
        steps_per_s = STEPS / elapsed
        durations.sort()
        compute = durations[len(durations) // 2]
        overhead_ms = (elapsed / STEPS - compute) * 1000

        # Reference architectural floor: 100ms display poll + 100ms ZMQ
        # poll per cell (SURVEY §3.2) on top of the same compute.
        ref_floor_steps_per_s = 1.0 / (0.2 + compute)
        vs_baseline = steps_per_s / ref_floor_steps_per_s
        log(f"[bench] {STEPS} cell-steps in {elapsed:.2f}s; "
            f"compute={compute*1000:.2f}ms/step, "
            f"framework overhead={overhead_ms:.2f}ms/step")

        extra: dict = {"overhead_ms_per_cell": round(overhead_ms, 3)}

        # Async pipelined dispatch (ISSUE 14): the same cells under
        # sync vs streamed-window vs --repeat dispatch, BEFORE the
        # latency snapshot below so the async cells' stage records
        # land in extra.latency_stages — the waterfall then shows the
        # overlap (pipelined cells book predecessor-wait as `queue`).
        try:
            pipe = measure_pipeline(comm, world)
            extra["pipeline"] = pipe
            log(f"[bench] pipeline: {pipe}")
        except Exception as e:
            log(f"[bench] pipeline measurement skipped: {e}")

        # Stage-latency decomposition of the cells just timed (ISSUE
        # 13): WHERE the per-cell overhead goes (queue/wire/dispatch/
        # compile/execute/reply/deliver p50-p99), so BENCH_* rows can
        # track dispatch-overhead decomposition across PRs instead of
        # one opaque overhead number.
        try:
            lat = comm.lat.summary()
            if lat.get("count"):
                extra["latency_stages"] = lat
                log(f"[bench] latency stages (ms, p50): "
                    + ", ".join(f"{s}={v['p50']}" for s, v in
                                lat["stages"].items()))
        except Exception as e:
            log(f"[bench] latency-stage snapshot skipped: {e}")

        # The context measurements below are best-effort: a failure
        # there must not discard the already-measured primary metric.
        try:
            # ---- all_reduce bandwidth sweep (needs the pooled world:
            # the collective spans all workers) ----------------------
            log("[bench] all_reduce bandwidth sweep")
            resp = comm.send_to_all("execute", ALLREDUCE_CELL,
                                    timeout=600)
            m = resp[0]
            if m.data.get("error"):
                log(f"[bench] allreduce cell failed: "
                    f"{m.data.get('traceback', m.data['error'])}")
            else:
                sweep = parse_result_json(m)
                if sweep is not None:
                    extra["allreduce"] = sweep
                    log(f"[bench] allreduce: {sweep}")
        except Exception as e:
            log(f"[bench] allreduce sweep skipped: {e}")

        # Snapshot the observability registry into the BENCH json so
        # perf runs carry comms/retry counters alongside the timings
        # (the coordinator's codec wire hook has been counting every
        # frame of the run).  Best-effort like the other context
        # measurements.
        try:
            from nbdistributed_tpu.observability import metrics as _obsm
            snap = _obsm.registry().to_json()
            extra["observability_metrics"] = {
                "retries_sent": comm.retries_sent,
                "wire_counters": snap.get("counters", {}),
            }
        except Exception as e:
            log(f"[bench] metrics snapshot skipped: {e}")

        try:
            extra["flight_recorder"] = measure_flight_recorder(comm)
            log(f"[bench] flight recorder: {extra['flight_recorder']}")
        except Exception as e:
            log(f"[bench] flight recorder measurement skipped: {e}")

        try:
            tel = measure_telemetry_peaks(comm)
            if tel:
                extra["telemetry"] = tel
                log(f"[bench] telemetry peaks: {tel}")
        except Exception as e:
            log(f"[bench] telemetry summary skipped: {e}")

        # The pooled world's job is done.  Tear it down (blocking)
        # BEFORE the per-family measurements: two processes share the
        # one chip's HBM, so the pooled workers must be gone before a
        # family worker attaches.
        _teardown(comm, pm, world)
        comm = pm = None

        # Elastic pools (ISSUE 16): cold vs warm first-cell compile,
        # resize drain-barrier wall-clock, migration end-to-end — in
        # CPU pools of its own, after the bench world is gone.
        try:
            el = measure_elastic()
            if el:
                extra["elastic"] = el
                log(f"[bench] elastic: {el}")
        except Exception as e:
            log(f"[bench] elastic measurement skipped: {e}")

        # Serving fast path (ISSUE 17): closed-loop loadgen against a
        # paged multi-rank decode plane — sustained tokens/s + p99
        # TTFT/TPOT, then shed rate at 2x overload.
        try:
            sv = measure_serving()
            if sv:
                extra["serving"] = sv
                log(f"[bench] serving: {sv}")
        except Exception as e:
            log(f"[bench] serving measurement skipped: {e}")

        # Training integrity guard (ISSUE 19): guarded vs unguarded
        # DDP step rate + the audit step's fingerprint cost.
        try:
            gd = measure_trainguard()
            if gd:
                extra["trainguard"] = gd
                log(f"[bench] trainguard: {gd}")
        except Exception as e:
            log(f"[bench] trainguard measurement skipped: {e}")

        # Bulk data plane (ISSUE 20): chunked vs legacy push/pull
        # throughput + compression ratio, in a 1-worker world of its
        # own.
        try:
            tx = measure_transfer()
            if tx:
                extra["transfer"] = tx
                log(f"[bench] transfer: {tx}")
        except Exception as e:
            log(f"[bench] transfer measurement skipped: {e}")

        result = {
            "metric": f"ddp_linear1024_steps_per_s_cellwise_{backend}"
                      f"_x{world}",
            "value": round(steps_per_s, 2),
            "unit": "steps/s",
            "vs_baseline": round(vs_baseline, 2),
            "extra": extra,
        }
        # Every heavy measurement family runs in its own fresh worker
        # process (see measure_family's docstring for why).  ``extra``
        # is shared by reference with ``result``.
        run_families(backend, tpu_families(), extra)
        print(json.dumps(result), flush=True)
        return 0
    except Exception:
        import traceback
        log(f"[bench] {backend} run failed:\n{traceback.format_exc()}")
        return 1
    finally:
        if pm is not None or comm is not None:
            _teardown(comm, pm, world)


if __name__ == "__main__":
    sys.exit(main())
