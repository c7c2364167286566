"""On-chip flash-attention block-size sweep.

Run on a TPU, in a process of its own (it holds the chip: never in the
same parent as a worker fleet); takes ~5-10 min of compiles:

    python tune_flash.py

Sweeps explicit (block_q, block_k) for the flash kernels on the bench
shapes, timing with the chained-dependency pattern of ``ops/timing.py``
(each scan step's q depends on the previous output; per-call time =
(long-short chain)/delta with a host fetch at the end), beside the
tiles the kernels derive from the shapes themselves
(``ops/attention.py::_block_sizes``): a sweep that beats the derived
tiles is a reason to change that function, not a table to load.

Prints per-config timings and the best-vs-XLA speedup.  The decode
kernel still reads a table: its sweep **writes
``nbdistributed_tpu/ops/tuned_blocks.json``** (see ``ops/_tuned.py``).

``NBD_TUNE_CPU_SMOKE=1`` shrinks the sweep to one tiny shape, lifts
the TPU gate, and writes the table to /tmp — an end-to-end harness
check runnable in CI (a sweep-script bug must not be discovered on
budgeted chip time).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from nbdistributed_tpu.ops import attention_reference
from nbdistributed_tpu.ops.attention import _block_sizes, flash_attention

SMOKE = bool(os.environ.get("NBD_TUNE_CPU_SMOKE"))

SHAPES = [
    # (name, B, S, H, Hkv, D) — the round-2 GQA bench shape first.
    ("gqa_bench", 4, 2048, 8, 2, 128),
    ("mha_r1", 4, 2048, 8, 8, 128),
    ("long_gqa", 1, 8192, 8, 2, 128),
]
BLOCKS = (128, 256, 512)
DECODE_SHAPES = [
    # (name, B, T, H, Hkv, D)
    ("smol_decode", 1, 2048, 9, 3, 64),
    ("llama7b_decode", 1, 2048, 32, 32, 128),
    ("gqa_long_decode", 1, 8192, 32, 8, 128),
]
if SMOKE:
    SHAPES = [("smoke", 1, 256, 2, 1, 64)]
    BLOCKS = (128, 256)
    DECODE_SHAPES = [("smoke_decode", 1, 256, 2, 2, 64)]


# The chained-delta protocol (fresh-input medians, value fetches,
# (long-short)/delta) lives in ops/timing.py — the SAME code path the
# bench flash cell and the watcher's preflight probe use, so a sweep
# measures exactly the program the bench times.  A <= 0 return means
# noise won; callers retry once then skip the row.
from nbdistributed_tpu.ops.timing import chained_delta_ms


def chain_ms(f, q, k, v, n1=2, n2=18):
    return chained_delta_ms(lambda qc: f(qc, k, v), q,
                            n1=n1, n2=n2)[0]


def grad_chain_ms(f, q, k, v, n1=2, n2=10):
    def step(qc):
        return jax.grad(lambda qq: f(qq, k, v).astype(
            jnp.float32).sum())(qc)

    return chained_delta_ms(step, q, n1=n1, n2=n2)[0]


def main() -> int:
    if jax.default_backend() != "tpu" and not SMOKE:
        print("tune_flash.py needs a live TPU "
              f"(backend={jax.default_backend()})", file=sys.stderr)
        return 1
    results = {}
    decode_tbl: dict = {}

    def checkpoint_tables():
        """Write the accumulated tables after EVERY shape: a sweep
        stopped at its time limit keeps what it measured.  MERGED over
        the existing on-disk table — an early checkpoint must never
        gut a previous complete table down to the one shape measured
        so far (save() replaces the whole file)."""
        if decode_tbl:
            from nbdistributed_tpu.ops import _tuned
            path = "/tmp/tuned_blocks_smoke.json" if SMOKE else None
            old_flash, old_decode = _tuned.load(path)
            p = _tuned.save(
                old_flash, {**old_decode, **decode_tbl},
                meta={"measured_at": time.strftime(
                          "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                      "device": jax.devices()[0].device_kind},
                path=path)
            results["tuned_blocks_path"] = p
            print(f"[tune] checkpointed {p}", file=sys.stderr)

    def valid(ms):
        return ms is not None and ms > 0

    for name, B, S, H, Hkv, D in SHAPES:
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D),
                              jnp.bfloat16)
        # XLA reference FIRST: a sweep cut short still has the
        # comparison for whatever configs landed.  Same
        # noise-retry-then-None contract as the kernel rows — a spike
        # on a ref sample must not publish a negative "speedup".
        def _ref(q_, k_, v_):
            return attention_reference(q_, k_, v_, causal=True)
        ref_fwd = chain_ms(_ref, q, k, v)
        if not valid(ref_fwd):
            ref_fwd = chain_ms(_ref, q, k, v)
        ref_fb = grad_chain_ms(_ref, q, k, v)
        if not valid(ref_fb):
            ref_fb = grad_chain_ms(_ref, q, k, v)
        print(f"[{name}] XLA ref: fwd {ref_fwd:.3f} ms, fwd+bwd "
              f"{ref_fb:.3f} ms", file=sys.stderr)
        rows = []
        for bq in BLOCKS:
            for bk in BLOCKS:
                if bq > S or bk > S:
                    continue
                fl = functools.partial(flash_attention, causal=True,
                                       block_q=bq, block_k=bk)
                try:
                    fwd = chain_ms(fl, q, k, v)
                    if not valid(fwd):      # noise won: one retry
                        fwd = chain_ms(fl, q, k, v)
                except Exception as e:  # Mosaic rejects some shapes
                    print(f"[{name}] bq={bq} bk={bk}: FAILED {e}",
                          file=sys.stderr)
                    continue
                rows.append({"bq": bq, "bk": bk,
                             "fwd_ms": (round(fwd, 3) if valid(fwd)
                                        else None)})
                print(f"[{name}] bq={bq} bk={bk}: fwd {fwd:.3f} ms",
                      file=sys.stderr)
        ok_rows = [r for r in rows if valid(r["fwd_ms"])]
        if not ok_rows:
            # Every config failed to compile or measure: record that
            # and keep the other shapes' results.
            results[name] = {"shape": f"B{B} S{S} H{H} Hkv{Hkv} D{D}",
                             "rows": rows,
                             "error": "no block config measured"}
            continue
        # fwd+bwd sweep only for the top fwd configs: the bwd kernel
        # compiles are the expensive half of the sweep, and a config
        # outside the fwd top-3 never wins the combined time.
        ok_rows.sort(key=lambda r: r["fwd_ms"])
        for r in ok_rows[:3]:
            fl = functools.partial(flash_attention, causal=True,
                                   block_q=r["bq"], block_k=r["bk"])
            try:
                fb = grad_chain_ms(fl, q, k, v)
                if not valid(fb):
                    fb = grad_chain_ms(fl, q, k, v)
            except Exception as e:
                print(f"[{name}] bq={r['bq']} bk={r['bk']}: "
                      f"bwd FAILED {e}", file=sys.stderr)
                continue
            r["fwd_bwd_ms"] = round(fb, 3) if valid(fb) else None
            print(f"[{name}] bq={r['bq']} bk={r['bk']}: fwd+bwd "
                  f"{fb:.3f} ms", file=sys.stderr)
        with_fb = [r for r in ok_rows if valid(r.get("fwd_bwd_ms"))]
        best = (min(with_fb, key=lambda r: r["fwd_bwd_ms"])
                if with_fb else ok_rows[0])
        results[name] = {
            "shape": f"B{B} S{S} H{H} Hkv{Hkv} D{D} bf16 causal",
            "rows": rows,
            "xla_ref": {"fwd_ms": (round(ref_fwd, 3)
                                   if valid(ref_fwd) else None),
                        "fwd_bwd_ms": (round(ref_fb, 3)
                                       if valid(ref_fb) else None)},
            "best": best,
            "tuned_speedup_fwd": (round(ref_fwd / best["fwd_ms"], 3)
                                  if valid(ref_fwd) else None),
            "tuned_speedup_fwd_bwd": (
                round(ref_fb / best["fwd_bwd_ms"], 3)
                if valid(ref_fb) and valid(best.get("fwd_bwd_ms"))
                else None),
            # what the kernels choose for this shape on their own
            "derived": {kern: list(_block_sizes(
                None, None, S, S, D, H // Hkv, interpret=False,
                kernel=kern)) for kern in ("fwd", "dq", "dkv")},
        }
        print(f"[{name}] best flash bq={best['bq']} bk={best['bk']}; "
              f"derived {results[name]['derived']}", file=sys.stderr)
    # ---- decode kernel sweep: block_k over realistic cache shapes.
    from nbdistributed_tpu.ops.decode import flash_decode_attention

    for name, B, T, H, Hkv, D in DECODE_SHAPES:
        q = jax.random.normal(jax.random.PRNGKey(0), (B, H, D),
                              jnp.bfloat16)
        kc = jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, T, D),
                               jnp.bfloat16)
        vc = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, T, D),
                               jnp.bfloat16)
        pos = jnp.full((B,), T - 1, jnp.int32)
        rows = []
        for bk in BLOCKS:
            if bk > T:
                continue
            try:
                ms = chain_ms(
                    lambda qc, k_, v_: flash_decode_attention(
                        qc, k_, v_, pos, block_k=bk),
                    q, kc, vc, n1=4, n2=36)
                if not valid(ms):           # noise won: one retry
                    ms = chain_ms(
                        lambda qc, k_, v_: flash_decode_attention(
                            qc, k_, v_, pos, block_k=bk),
                        q, kc, vc, n1=4, n2=36)
            except Exception as e:
                print(f"[{name}] block_k={bk}: FAILED {e}",
                      file=sys.stderr)
                continue
            if valid(ms):
                rows.append({"block_k": bk, "ms": round(ms, 4)})
            print(f"[{name}] block_k={bk}: {ms:.4f} ms",
                  file=sys.stderr)
        if not rows:
            results[name] = {"error": "no block_k measured"}
            continue
        best = min(rows, key=lambda r: r["ms"])
        results[name] = {
            "shape": f"B{B} T{T} H{H} Hkv{Hkv} D{D} bf16",
            "rows": rows, "best": best,
            # DECODE_TUNED_BLOCKS key: (T, head_dim, gqa_group).
            "tuned_entry": {f"({T}, {D}, {H // Hkv})":
                            best["block_k"]},
        }
        decode_tbl[(T, D, H // Hkv)] = best["block_k"]
        checkpoint_tables()

    print(json.dumps(results, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
