#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, through the entry points a user calls, on
real TPU devices:

  this process (an IPython shell that never touches the chip)
    -> %dist_init            -> one runtime.worker process per chip
       -> kernels, a few optimizer steps, generate
    -> %dist_shutdown
    -> %dist_pool start      -> gateway daemon -> workers
       -> %dist_attach, %dist_serve: paged KV + chunked prefill,
          every stream the greedy `generate` stream (exactly, in
          float32; to a near-tie margin in bf16 — see TIE_MARGIN)
    -> %dist_pool stop

The model is `mistral_7b_config` at its published widths (d_model 4096,
32 query / 8 KV heads of 128, d_ff 14336, vocab 32000, window 4096,
bf16, use_flash) with n_layers cut to what the chip's memory holds; the
weights are random, from a seed.  Every phase is checked here — a
caught exception or an `error` field in a reply is a failed phase.

    python chip_smoke.py               # one worker, one chip
    python chip_smoke.py --workers 4   # the same path on a 4-chip host

Exit 0 only if every phase passed on TPU devices.  Then (and whenever
the fleet came up on TPU devices) the last line of stdout is the
verdict, one JSON object of exactly two keys,
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}},
and the line before it is `SUMMARY {json}`: chosen depth, per-phase
pass/fail and seconds, compile seconds, versions, transport.
With no accelerator the fleet phase fails, nothing is printed as a
result and the exit code is 2.  Anything printed besides pass/fail
(compile seconds, step times, tokens) is a log line, never a metric.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import signal
import sys
import tempfile
import time
import uuid

REPO = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1100           # the driver allows 1200 s, teardown included

# Serving geometry: prompts longer than one prefill chunk stream in
# 128-token chunks; short ones pad to the same 128 so admission
# compiles one shape.
SERVE = {"max_batch": 4, "max_len": 512, "pad_to": 128,
         "kv_block_tokens": 64, "prefill_chunk": 128}
# (prompt length, new tokens): mixed lengths, two longer than a chunk.
REQUESTS = [(24, 24), (96, 16), (200, 24), (57, 32), (130, 16)]
VOCAB = 32000               # prompts are drawn here, off the chip

# A served stream must equal the greedy `generate` reference.  In
# float32 at `highest` matmul precision it does, on the chip, through
# paged KV + chunked prefill + the row-masked batch step (checked on
# every pool rank for the requests F32_EXACT names).  In bf16 the
# server's step over max_batch rows and generate's one-row step are
# different programs that round differently, and a near-tie then flips
# the argmax and everything after it (isolated on the chip, PERF.md
# finding 7: every flip sat where the top two logits were within
# 0.008 sigma).  So a bf16 stream that differs must still be greedy
# under the model: every served token's logit within this many standard
# deviations of the best at its position (teacher-forced; a wrong token
# sits several sigma down — the best of 32000 is about four above the
# mean).
TIE_MARGIN = 0.05
F32_EXACT = (0, 2, 4)       # 24, 200 (two chunks) and 130 tokens

# Depth: bytes one more Mistral-7B layer / the rest of the train step
# needs at S=4096, batch 1/chip, bf16 params + adamw (XLA's buffer
# assignment for v5e: 13.8 GB at 3 layers, 17.2 GB at 4).  The largest
# depth whose step fits in 85% of the device's reported limit.
_STEP_FIXED_GB, _STEP_PER_LAYER_GB, _HBM_SHARE = 3.8, 3.34, 0.85


PHASES = ("fleet", "kernels", "hybrid", "diffusion", "train", "generate",
          "teardown", "serve", "parent_off_chip")


class PhaseFailed(Exception):
    pass


class _Tee(io.TextIOBase):
    """stdout that also keeps what passed through it (the magics
    report by printing)."""

    def __init__(self, real):
        self.real, self.buf = real, io.StringIO()

    def write(self, s):
        self.real.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.real.flush()


def _captured(fn, *a):
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        fn(*a)
    return tee.buf.getvalue()


def _sentinels(text: str) -> list[dict]:
    """The `SMOKE {json}` lines worker cells print, one per rank."""
    return [json.loads(m) for m in re.findall(r"SMOKE (\{.*\})", text)]


def _marked_processes(marker: str) -> dict[int, str]:
    """Live (non-zombie) processes started under this run, found by
    the marker every child inherits in its environment — pid -> argv."""
    out = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if marker.encode() not in f.read():
                    continue
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().replace(b"\0", b" ").decode().strip()
        except OSError:
            continue
        if state != "Z":
            out[int(pid)] = argv
    return out


def _maps_libtpu(pid: int) -> bool:
    """A process that initialised the TPU backend has libtpu mapped."""
    with open(f"/proc/{pid}/maps") as f:
        return "libtpu" in f.read()


class Smoke:
    def __init__(self, workers: int):
        self.n = workers
        # Exported before anything starts, so every process of this
        # run carries it and a leftover can be told from a stranger.
        self.marker = f"NBD_SMOKE_RUN={uuid.uuid4().hex}"
        os.environ.update([self.marker.split("=")])
        self.phases: dict[str, dict] = {}
        self.facts: dict = {}
        self.device: dict | None = None
        self.layers: int | None = None
        self.pool_dir: str | None = None
        self.ip = None
        self.DM = None

    # -- plumbing ------------------------------------------------------

    def shell(self):
        from IPython.testing.globalipapp import get_ipython, start_ipython
        self.ip = start_ipython() or get_ipython()
        os.chdir(REPO)      # start_ipython may move the cwd
        self.ip.run_line_magic("load_ext", "nbdistributed_tpu")
        from nbdistributed_tpu.magics.magic import DistributedMagics
        self.DM = DistributedMagics

    def magic(self, name: str, line: str = "") -> str:
        return _captured(self.ip.run_line_magic, name, line)

    def cell(self, code: str, ranks: str | None = None) -> list[dict]:
        """Run a cell on the fleet (or the attached pool) and return
        one sentinel per rank that ran it; a rank without one failed."""
        if ranks is None:
            out = _captured(self.ip.run_cell_magic, "distributed", "",
                            code)
            want = self.n
        else:
            out = _captured(self.ip.run_cell_magic, "rank", ranks, code)
            want = 1
        got = _sentinels(out)
        if len(got) != want or any("error" in g for g in got):
            raise PhaseFailed(
                f"cell reported {len(got)}/{want} ranks: "
                + out.strip()[-1500:])
        return sorted(got, key=lambda g: g.get("rank", 0))

    def phase(self, name: str, fn) -> bool:
        print(f"\n=== phase {name}", flush=True)
        t0 = time.time()
        try:
            fn()
            rec = {"ok": True}
        except PhaseFailed as e:
            rec = {"ok": False, "error": str(e)}
        except Exception as e:     # boundary: every failure is a verdict
            rec = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        rec["seconds"] = round(time.time() - t0, 1)
        self.phases[name] = rec
        print(f"=== phase {name}: {'PASS' if rec['ok'] else 'FAIL'} "
              f"({rec['seconds']}s)"
              + ("" if rec["ok"] else f"\n{rec['error']}"), flush=True)
        return rec["ok"]

    def status(self) -> dict[int, dict]:
        resp = self.DM._comm.send_to_all("get_status", None, timeout=120)
        return {r: m.data or {} for r, m in resp.items()}

    # -- phases --------------------------------------------------------

    def fleet(self):
        out = self.magic("dist_init", f"-n {self.n} --backend tpu "
                                      f"--attach-timeout 240")
        if self.DM._comm is None:
            raise PhaseFailed("no fleet: " + out.strip()[-1500:])
        st = self.status()
        seen = set()
        for r in range(self.n):
            d = st.get(r) or {}
            devs = d.get("devices") or []
            if (d.get("backend") != "tpu" or len(devs) != 1
                    or devs[0].get("platform") != "tpu"
                    or not devs[0].get("kind")
                    or d.get("global_device_count") != self.n):
                raise PhaseFailed(f"rank {r} is not one TPU device of "
                                  f"{self.n}: {d}")
            seen.add(devs[0]["id"])
        if len(seen) != self.n:
            raise PhaseFailed(f"ranks share devices: ids {sorted(seen)}")
        dev0 = st[0]["devices"][0]
        self.device = {"platform": str(dev0["platform"]),
                       "kind": str(dev0["kind"]),
                       "count": int(st[0]["global_device_count"])}
        limit_gb = min(st[r]["devices"][0]["memory_gb"]["limit"]
                       for r in range(self.n))
        self.layers = int((_HBM_SHARE * limit_gb - _STEP_FIXED_GB)
                          // _STEP_PER_LAYER_GB)
        if self.layers < 1:
            raise PhaseFailed(f"{limit_gb} GB of device memory holds no "
                              f"full-width layer")
        facts = self.cell(_FACTS_CELL)
        if self.n > 1:
            want = self.n * (self.n + 1) / 2
            if any(f["all_reduce"] != want for f in facts):
                raise PhaseFailed(f"all_reduce(rank+1) != {want}: {facts}")
            if len({tuple(f["coords"]) for f in facts}) != self.n:
                raise PhaseFailed(f"ranks share chip coords: {facts}")
        f0 = facts[0]
        self.facts = {"versions": f0["versions"],
                      "transport": {"fleet": self.DM._comm.transport},
                      "flash_tiles": f0["flash_tiles"],
                      "compile_cache": f0["compile_cache"],
                      "hbm_limit_gb": limit_gb}
        print(f"device {self.device} · {f0['versions']} · transport "
              f"{self.DM._comm.transport} · flash tiles at S=4096 "
              f"{f0['flash_tiles']} · compile "
              f"cache {f0['compile_cache']} · depth {self.layers}")

    def kernels(self):
        k = self.cell(_KERNELS_CELL, ranks="[0]")[0]
        self.facts["kernels"] = k["checks"]
        for c in k["checks"]:
            if "ms" in c:
                print(f"{c['name']}: {c['ms']} ms a call, XLA's ragged_dot "
                      f"{c['xla_ms']} ms; err {c['err']:.4g} (tol "
                      f"{c['tol']:.4g})")
        bad = [c for c in k["checks"]
               if not (c["mosaic"] >= 1 and c["err"] <= c["tol"])]
        if bad:
            raise PhaseFailed(f"kernel checks failed: {bad}")

    def hybrid(self):
        """The state-space layer's one-token update and a windowed
        differential decode step over a ring of pages, at the published
        widths of Phi-4-mini-flash; the Mamba-2 mixer's one-token update
        and its block form against that update token by token, at
        Nemotron-3-Nano's; all at ``highest`` precision, against
        ``jax.numpy``."""
        k = self.cell(_HYBRID_CELL, ranks="[0]")[0]
        self.facts["hybrid"] = k["checks"]
        bad = [c for c in k["checks"]
               if not (c["mosaic"] >= c["kernels"] and c["err"] <= c["tol"])]
        if bad:
            raise PhaseFailed(f"hybrid checks failed: {bad}")

    def diffusion(self):
        """Generation by diffusion over blocks: a block server at a
        small size (heads of 128, 16 experts, two layers) serves three
        prompts (a remainder of 2, one of 1 under two blocks long, whole
        blocks over two chunks) in float32 at ``highest`` precision;
        every denoising pass is then replayed by a plain ``jax.numpy``
        forward under the block-causal mask, teacher-forced by what was
        served: the token a pass fixed must be the reference's best and
        the position it fixed the reference's most confident, both
        within ``tol`` (in logits: float32 on both sides, sums in
        another order through two layers and a softmax; a wrong token
        lies O(1) away).  The pass holds one Mosaic call a layer, and
        every block took its scheduled passes."""
        k = self.cell(_DIFFUSION_CELL, ranks="[0]")[0]
        self.facts["diffusion"] = k
        if not (k["mosaic"] == k["layers"] and k["err"] <= k["tol"]
                and k["off_schedule"] == 0 and k["tokens"] == k["wanted"]):
            raise PhaseFailed(f"diffusion check failed: {k}")

    def train(self):
        res = self.cell(_HEADER.format(layers=self.layers) + _TRAIN_CELL)
        t0 = res[0]
        self.facts["train"] = {k: t0[k] for k in
                               ("params_m", "losses", "mosaic",
                                "compile_s", "step_s", "collectives")}
        print(f"depth {self.layers} · {t0['params_m']} M parameters · "
              f"losses {t0['losses']} · {t0['collectives']}")
        for t in res:
            ls = t["losses"]
            if not all(l == l and abs(l) != float("inf") for l in ls):
                raise PhaseFailed(f"rank {t['rank']}: loss not finite {ls}")
            if not ls[-1] < ls[0]:
                raise PhaseFailed(f"rank {t['rank']}: loss did not fall "
                                  f"on a repeated batch {ls}")
            if t["mosaic"] < 3:
                raise PhaseFailed(f"train step holds {t['mosaic']} Mosaic "
                                  f"calls, expected flash fwd + 2 bwd")
            if t["losses"] != t0["losses"]:
                raise PhaseFailed(f"losses differ across ranks: {res}")
            # What still takes a blocking all-reduce over several
            # shards: a layer's attention matrices (84 MB at this
            # width; the loop's body is in the text once), the norms
            # and the loss.  GSPMD's step reads 960 MB and no send.
            c = t["collectives"]
            if len(res) > 1 and not (
                    c["async_sends"] > 0
                    and c["blocking_all_reduce_bytes"] < 1 << 27):
                raise PhaseFailed(
                    f"the DDP step sums its large gradients by blocking "
                    f"all-reduces, not by sends inside the backward: {c}")
        in_use = {r: d["devices"][0]["memory_gb"]["in_use"]
                  for r, d in self.status().items()}
        self.facts["train"]["in_use_gb"] = in_use
        idle = [r for r, gb in in_use.items() if not gb or gb < 1.0]
        if idle:
            raise PhaseFailed(f"chips of ranks {idle} hold under 1 GB "
                              f"after training — not in use: {in_use}")

    def generate(self):
        res = self.cell(_GENERATE_CELL)
        self.facts["generate"] = {k: res[0][k] for k in
                                  ("mosaic", "compile_s", "tokens")}
        for g in res:
            if not (g["reproducible"] and g["mosaic"]["bf16"] >= 1
                    and g["mosaic"]["int8"] >= 1 and g["in_vocab"]):
                raise PhaseFailed(f"generate check failed: {g}")

    def teardown_fleet(self):
        self.magic("dist_shutdown")
        self._no_survivors()

    def _survivors(self, wait_s: float = 20) -> dict[int, str]:
        """Our processes still alive once ``wait_s`` have passed."""
        deadline = time.time() + wait_s
        left = _marked_processes(self.marker)
        while left and time.time() < deadline:
            time.sleep(0.5)
            left = _marked_processes(self.marker)
        return left

    def _no_survivors(self):
        left = self._survivors()
        if left:
            raise PhaseFailed(f"processes survived teardown: {left}")

    def serve(self):
        from nbdistributed_tpu.gateway.daemon import read_gateway_manifest
        self.pool_dir = tempfile.mkdtemp(prefix="nbd_smoke_pool_")
        out = self.magic("dist_pool", f"start -n {self.n} --backend tpu "
                                      f"--run-dir {self.pool_dir}")
        manifest = read_gateway_manifest(self.pool_dir)
        if "pool up" not in out or not manifest:
            raise PhaseFailed("pool did not start: " + out.strip()[-1500:])
        if manifest.get("backend") != "tpu":
            raise PhaseFailed(f"pool backend is {manifest.get('backend')}")
        self.facts["transport"]["pool"] = manifest.get("transport")
        self.facts["daemon_loaded_libtpu"] = _maps_libtpu(manifest["pid"])
        if self.facts["daemon_loaded_libtpu"]:
            raise PhaseFailed("the gateway daemon mapped libtpu — only "
                              "workers may touch the chip")
        self.magic("dist_attach", f"--tenant smoke {self.pool_dir}")
        client = self.DM._tenant
        if client is None:
            raise PhaseFailed("tenant attach failed")
        head = _HEADER.format(layers=self.layers)
        self.ip.user_ns["smoke_spec"] = head + _SPEC_CELL
        flags = " ".join(f"--{k.replace('_', '-')} {v}"
                         for k, v in SERVE.items())
        if self.n > 1:
            flags += f" --decode-ranks {self.n}"
        out = self.magic("dist_serve", f"start --spec smoke_spec {flags}")
        if "serving as tenant" not in out:
            raise PhaseFailed("serve start failed: " + out.strip()[-1500:])
        # One request set per decode rank, so every rank gets traffic.
        import numpy as np
        rng = np.random.default_rng(11)
        reqs = [(rng.integers(0, VOCAB, n).tolist(), n_new)
                for n, n_new in REQUESTS * self.n]
        rids = []
        for prompt, n_new in reqs:
            out = self.magic("dist_serve", "submit --prompt "
                             + ",".join(map(str, prompt))
                             + f" --max-new {n_new}")
            m = re.search(r"accepted (\S+)", out)
            if not m:
                raise PhaseFailed("submit refused: " + out.strip()[-800:])
            rids.append(m.group(1))
        deadline = time.time() + 420
        results = {}
        while len(results) < len(rids):
            if time.time() > deadline:
                raise PhaseFailed(
                    f"requests unfinished after 420s: "
                    f"{sorted(set(rids) - set(results))} · "
                    f"{client.serve_status().get('last_error')}")
            for rid in rids:
                if rid not in results:
                    r = client.serve_result(rid)
                    if r.get("error"):
                        raise PhaseFailed(f"{rid}: {r['error']}")
                    if r.get("done"):
                        results[rid] = r
            time.sleep(0.3)
        served = []
        for rid, (prompt, n_new) in zip(rids, reqs):
            toks = [int(t) for t in results[rid].get("tokens") or []]
            if results[rid].get("status") != "completed" \
                    or len(toks) != n_new:
                raise PhaseFailed(f"{rid}: {results[rid].get('status')} "
                                  f"with {len(toks)}/{n_new} tokens")
            served.append(toks)
        server_kw = dict(SERVE, interleave_prefill=True)
        ver = self.cell(head + _SPEC_CELL + _VERIFY_CELL.format(
            prompts=[p for p, _ in reqs], served=served,
            max_len=SERVE["max_len"], server_kw=server_kw,
            f32_exact=F32_EXACT))
        bad = [v for v in ver if v["platform"] != "tpu"
               or not all(v["exact_f32"])]
        if bad:
            raise PhaseFailed(f"float32 serving does not reproduce "
                              f"generate on TPU: {bad}")
        v0 = ver[0]
        for rid, exact, deficit in zip(rids, v0["exact"], v0["deficit"]):
            if not exact and deficit > TIE_MARGIN:
                raise PhaseFailed(
                    f"{rid}: stream differs from generate and is not "
                    f"greedy under the model (a served token sits "
                    f"{deficit:.3f} logit-sigmas under the best)")
        self.magic("dist_serve", "status")
        st = client.serve_status()
        n_tok = sum(n for _, n in reqs)
        if st.get("completed") != len(reqs) \
                or st.get("tokens_total") != n_tok or st.get("last_error"):
            raise PhaseFailed(
                f"serve status: completed {st.get('completed')}/"
                f"{len(reqs)}, tokens {st.get('tokens_total')}/{n_tok}, "
                f"last_error {st.get('last_error')}")
        served_by = sorted({r.get("rank") for r in
                            (st.get("lat") or {}).get("records") or []})
        if self.n > 1 and served_by != list(range(self.n)):
            raise PhaseFailed(f"decode ranks that served: {served_by}, "
                              f"expected all of 0..{self.n - 1}")
        # What each decode rank's own server reported at serve_open:
        # Mosaic calls in the program its step() runs (the row-masked
        # step over the paged pool), lowered at the live pool's shapes.
        step_kernels = {int(r): v.get("step_kernels")
                        for r, v in (st.get("ranks") or {}).items()}
        if sorted(step_kernels) != served_by \
                or not all(step_kernels.values()):
            raise PhaseFailed(f"decode steps without a compiled Pallas "
                              f"kernel (rank: Mosaic calls): "
                              f"{step_kernels}")
        self.facts["serve"] = {
            "requests": len(reqs), "tokens": n_tok,
            "decode_ranks": served_by,
            "equal_to_generate": sum(v0["exact"]),
            "worst_deficit": max(v0["deficit"]),
            "equal_to_generate_f32": [sum(v["exact_f32"]) for v in ver],
            "step_kernels": step_kernels,
            "verify_s": v0["verify_s"]}
        self.stop_pool()
        self._no_survivors()

    def stop_pool(self):
        if self.pool_dir is None:
            return
        d, self.pool_dir = self.pool_dir, None
        self.magic("dist_pool", f"stop --run-dir {d}")

    def parent_off_chip(self):
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            raise PhaseFailed("this process initialised a JAX backend")
        if _maps_libtpu(os.getpid()):
            raise PhaseFailed("this process mapped libtpu")

    # -- run -----------------------------------------------------------

    def run(self) -> int:
        try:
            self.shell()
            if self.phase("fleet", self.fleet):
                for name in ("kernels", "hybrid", "diffusion", "train",
                             "generate"):
                    self.phase(name, getattr(self, name))
            self.phase("teardown", self.teardown_fleet)
            if self.phases["fleet"]["ok"]:
                self.phase("serve", self.serve)
            self.phase("parent_off_chip", self.parent_off_chip)
        finally:
            self.cleanup()
        return self.report()

    def cleanup(self):
        """Always: stop the pool and the fleet, then kill whatever of
        ours is still alive (a survivor holds the chip)."""
        with contextlib.suppress(Exception):
            self.stop_pool()
        with contextlib.suppress(Exception):
            if self.DM is not None and (self.DM._comm is not None
                                        or self.DM._tenant is not None):
                self.magic("dist_shutdown")
        for pid, argv in self._survivors().items():
            print(f"killing survivor {pid}: {argv}", file=sys.stderr)
            with contextlib.suppress(OSError):
                os.kill(pid, signal.SIGKILL)

    def report(self) -> int:
        ok = all(self.phases.get(p, {}).get("ok") for p in PHASES)
        sys.stdout.flush()
        if self.device is None:
            print("chip_smoke: no fleet on TPU devices — "
                  + self.phases.get("fleet", {}).get("error", "not run"),
                  file=sys.stderr)
            return 2
        print("SUMMARY " + json.dumps({
            "ok": ok, "device": self.device, "workers": self.n,
            "model": "mistral_7b_config", "n_layers": self.layers,
            "phases": self.phases, **self.facts, "claim": None}))
        # The verdict: these two keys and nothing else, last on stdout.
        print(json.dumps({"ok": ok, "device": self.device}), flush=True)
        return 0 if ok else 1


# ----------------------------------------------------------------------
# worker cells.  Each ends by printing one `SMOKE {json}` line per rank.

_EMIT = '''
def _emit(**kw):
    import json, sys
    sys.stdout.write("SMOKE " + json.dumps(dict(rank=rank, **kw)) + "\\n")
'''

_HEADER = _EMIT + '''
import time
from nbdistributed_tpu.models import mistral_7b_config, init_params
cfg = mistral_7b_config(n_layers={layers})
'''

_FACTS_CELL = _EMIT + '''
import jaxlib, libtpu
from nbdistributed_tpu.ops import attention as _att
_d = jax.local_devices()[0]
_emit(versions=dict(jax=jax.__version__, jaxlib=jaxlib.__version__,
                    libtpu=libtpu.__version__),
      coords=list(_d.coords),
      all_reduce=float(all_reduce(jnp.float32(rank + 1))),
      # (block_q, block_k) each flash kernel derives for the training
      # shape; the `kernels` phase checks the gradients at them
      flash_tiles={k: list(_att._block_sizes(
          None, None, 4096, 4096, 128, 4, interpret=False, kernel=k))
          for k in ("fwd", "dq", "dkv")},
      compile_cache=jax.config.jax_compilation_cache_dir)
'''

# bf16 tolerance: the largest error allowed is 2% of the reference's
# largest magnitude (about five bf16 ulps there).
_KERNELS_CELL = _EMIT + '''
from nbdistributed_tpu.ops import (attention_reference, flash_attention,
                                   flash_decode_attention)
from nbdistributed_tpu.models.generate import _dequantize_kv, _quantize_kv
B, S, H, Hkv, D, W = 1, 4096, 32, 8, 128, 4096
_ks = jax.random.split(jax.random.PRNGKey(7), 8)
q, do = (jax.random.normal(_ks[i], (B, S, H, D), jnp.bfloat16) for i in (0, 1))
k, v = (jax.random.normal(_ks[i], (B, S, Hkv, D), jnp.bfloat16) for i in (2, 3))
checks = []
def _check(name, fn, ref, *args):
    jf = jax.jit(fn)
    mosaic = jf.lower(*args).as_text().count("tpu_custom_call")
    got, want = jax.tree.leaves(jf(*args)), jax.tree.leaves(jax.jit(ref)(*args))
    err = max(float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32))))
              for g, w in zip(got, want))
    top = max(float(jnp.max(jnp.abs(w.astype(jnp.float32)))) for w in want)
    checks.append(dict(name=name, mosaic=mosaic, err=err, tol=0.02 * top))
_fl = lambda q, k, v: flash_attention(q, k, v, True, None, None, None, W, None)
_rf = lambda q, k, v: attention_reference(q, k, v, causal=True, window=W)
_check("flash_fwd", _fl, _rf, q, k, v)
_g = lambda f: jax.grad(lambda q, k, v: (f(q, k, v).astype(jnp.float32)
                                         * do.astype(jnp.float32)).sum(), argnums=(0, 1, 2))
_check("flash_bwd", _g(_fl), _g(_rf), q, k, v)
Bd, T = 2, 4096
pos = jnp.asarray([1000, T - 1], jnp.int32)
qd = jax.random.normal(_ks[4], (Bd, H, D), jnp.bfloat16)
kc, vc = (jax.random.normal(_ks[i], (Bd, Hkv, T, D), jnp.bfloat16) for i in (5, 6))
def _dref(qd, kc, vc, pos):
    # one query per row against keys [0, pos]: mask by position
    kk, vv = kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3)
    seg_q = jnp.zeros((Bd, 1), jnp.int32)
    seg_k = (jnp.arange(T)[None, :] > pos[:, None]).astype(jnp.int32)
    return attention_reference(qd[:, None], kk, vv, causal=False,
                               segment_ids=seg_q, kv_segment_ids=seg_k)[:, 0]
_check("decode_bf16", lambda *a: flash_decode_attention(*a, window=W), _dref, qd, kc, vc, pos)
k8, k_s = _quantize_kv(kc); v8, v_s = _quantize_kv(vc)
_check("decode_int8",
       lambda qd, k8, v8, pos, k_s, v_s: flash_decode_attention(
           qd, k8, v8, pos, window=W, k_s=k_s, v_s=v_s),
       lambda qd, k8, v8, pos, k_s, v_s: _dref(
           qd, _dequantize_kv(k8, k_s).astype(jnp.bfloat16),
           _dequantize_kv(v8, v_s).astype(jnp.bfloat16), pos),
       qd, k8, v8, pos, k_s, v_s)
del q, k, v, do, qd, kc, vc, k8, v8, k_s, v_s
# The grouped matmul at SDAR-30B-A3B's call: 512 token rows x 8 choices
# sorted over 128 experts of 2048 x 768, uneven groups that keep 3,871
# of the 4,096 rows; the rows the groups cover against XLA's ragged_dot,
# and both kernels' time a call.
import time
import numpy as np
from nbdistributed_tpu.ops.grouped import grouped_matmul
M, E, KEPT = 4096, 128, 3871
_rng = np.random.default_rng(7)
gs = jnp.asarray(_rng.multinomial(KEPT, _rng.dirichlet(np.full(E, 4.0))),
                 jnp.int32)
def _ms(fn, *args, n=50):
    jf = jax.jit(fn)
    jf(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        out = jf(*args)
    out.block_until_ready()
    return round((time.perf_counter() - t0) / n * 1e3, 4)
_gm = lambda x, w, g: grouped_matmul(x, w, g)[:KEPT]
_rd = lambda x, w, g: jax.lax.ragged_dot(x, w, g)[:KEPT]
for name, kk, nn in (("gmm_sdar_up", 2048, 768), ("gmm_sdar_down", 768, 2048)):
    xg = jax.random.normal(_ks[0], (M, kk), jnp.bfloat16)
    wg = (jax.random.normal(_ks[1], (E, kk, nn), jnp.float32)
          * kk ** -0.5).astype(jnp.bfloat16)
    _check(name, _gm, _rd, xg, wg, gs)
    checks[-1].update(ms=_ms(_gm, xg, wg, gs), xla_ms=_ms(_rd, xg, wg, gs))
# a training step differentiates it: the derivative is ragged_dot's
_dg = lambda f: jax.grad(
    lambda x, w, g: (f(x, w, g).astype(jnp.float32) ** 2).sum(), argnums=(0, 1))
_check("gmm_sdar_grad", _dg(_gm), _dg(_rd), xg, wg, gs)
del xg, wg
_emit(checks=checks)
'''

# float32 at ``highest``: the largest error allowed is 1e-4 of the
# reference's largest magnitude (sums in another order).
_HYBRID_CELL = _EMIT + '''
from nbdistributed_tpu.models.hybrid import (DiffAttnMixer, HybridCache,
    SSMMixer, init_layer, make_hybrid_cache, phi4_mini_flash_config)
hcfg = phi4_mini_flash_config(dtype=jnp.float32)
rows, bt, max_len = 8, 64, 2048
_ks = jax.random.split(jax.random.PRNGKey(11), 8)
checks = []
def _check(name, kernels, fn, ref, *args):
    with jax.default_matmul_precision("highest"):
        jf = jax.jit(fn)
        mosaic = jf.lower(*args).as_text().count("tpu_custom_call")
        got, want = jax.tree.leaves(jf(*args)), jax.tree.leaves(jax.jit(ref)(*args))
    err = max(float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want))
    top = max(float(jnp.max(jnp.abs(w))) for w in want)
    checks.append(dict(name=name, mosaic=mosaic, kernels=kernels, err=err,
                       tol=1e-4 * top))
C, N, K, R = hcfg.d_inner, hcfg.d_state, hcfg.d_conv, hcfg.dt_rank
ssm = init_layer(_ks[0], hcfg, "ssm")
h = jax.random.normal(_ks[1], (rows, 1, hcfg.d_model))
state = jax.random.normal(_ks[2], (rows, N, C))
tail = jax.random.normal(_ks[3], (rows, K - 1, C))
live = jnp.arange(rows) % 4 != 3            # every fourth row sits out
def _ssm(h, state, tail):
    return SSMMixer(hcfg).mix(h, ssm, state, tail, live[:, None])
def _ssm_ref(h, state, tail):
    xz = h[:, 0] @ ssm["w_in"]
    x, z = xz[:, :C], xz[:, C:]
    win = jnp.concatenate([tail, x[:, None]], 1)            # (rows, K, C)
    x = jax.nn.silu(jnp.einsum("bkc,kc->bc", win, ssm["conv_w"]) + ssm["conv_b"])
    dbc = x @ ssm["w_x"]
    d = jax.nn.softplus(dbc[:, :R] @ ssm["w_dt"] + ssm["b_dt"])
    s = (jnp.exp(jnp.einsum("bc,nc->bnc", d, -jnp.exp(ssm["A_log"]))) * state
         + jnp.einsum("bc,bn->bnc", d * x, dbc[:, R:R + N]))
    y = jnp.einsum("bnc,bn->bc", s, dbc[:, R + N:]) + ssm["D"] * x
    keep = live[:, None, None]
    return ((y * jax.nn.silu(z)) @ ssm["w_out"])[:, None], y[:, None], \
        jnp.where(keep, s, state), jnp.where(keep, win[:, 1:], tail)
def _live_rows(fn):         # a row that sits out returns no output of use
    def run(*a):
        out, y, s, t = fn(*a)
        return out * live[:, None, None], y * live[:, None, None], s, t
    return run
_check("state_update", 0, _live_rows(_ssm), _live_rows(_ssm_ref), h, state, tail)
att = init_layer(_ks[4], hcfg, "window")
att = {**att, "bq": 0.02 * jax.random.normal(_ks[5], att["bq"].shape),
       "bkv": 0.02 * jax.random.normal(_ks[6], att["bkv"].shape)}
cache = make_hybrid_cache(hcfg, rows * max_len // bt, bt, rows=rows,
                          max_len=max_len, chunk=512)
wk = jax.random.normal(_ks[7], cache["window"]["k"].shape)
pool = {"k": wk, "v": wk[::-1] * 0.5}
pos = jnp.asarray([0, 63, 64, 511, 512, 1087, 1088, 2047], jnp.int32)
table = jnp.broadcast_to(jnp.arange(max_len // bt, dtype=jnp.int32), (rows, max_len // bt))
hx = jax.random.normal(_ks[1], (rows, 1, hcfg.d_model))
def _parts(pool, hx):
    kv = HybridCache({**cache, "window": pool}, hcfg, table, slot=None,
                     active=jnp.ones((rows,), bool), length=None, start=None,
                     mixers={"full": DiffAttnMixer(hcfg, None),
                             "window": DiffAttnMixer(hcfg, hcfg.sliding_window)})
    return kv, kv.window.project_q(hx, att), kv.window.project_kv(hx, att)
def _step(pool, hx):
    kv, q, new = _parts(pool, hx)
    o, _pool = kv.window_layer(pool, jnp.int32(3), q, new, pos[:, None])
    return kv.window.out(o, att, 7)
def _step_ref(pool, hx):
    # the same written pool, read through a dense view of the ring with
    # the two softmaxes a pair apart, heads in the program's order
    from nbdistributed_tpu.models.paged_kv import gather_layer, write_token
    kv, q, new = _parts(pool, hx)
    pool = write_token(pool, jnp.int32(3), new, kv._ring_table, pos, None)
    view = gather_layer(pool, jnp.int32(3), kv._ring_table)
    P, Dh = hcfg.kv_pairs, hcfg.head_dim
    qq = q.reshape(rows, P, 4, 2 * Dh)
    t = jnp.arange(view["k"].shape[2])
    keep = (t[None] <= pos[:, None]) & (t[None] > pos[:, None] - hcfg.sliding_window)
    def soft(qh, kh):       # (rows, P, 2, Dh), (rows, P, T, Dh)
        sc = jnp.einsum("bpgd,bptd->bpgt", qh, kh) / Dh ** 0.5
        p = jax.nn.softmax(jnp.where(keep[:, None, None], sc, -jnp.inf), -1)
        return jnp.einsum("bpgt,bptd->bpgd", p, view["v"])
    o = jnp.concatenate([soft(qq[:, :, :2, :Dh], view["k"][..., :Dh]),
                         soft(qq[:, :, 2:, Dh:], view["k"][..., Dh:])], 2)
    return kv.window.out(o.reshape(rows, 1, -1), att, 7)
_check("windowed_differential_step", 1, _step, _step_ref, pool, hx)
del cache, pool, wk, state, tail

# The Mamba-2 mixer at Nemotron-3-Nano's published widths: its one-token
# update against the equations written out, and the block form over a
# padded chunk, from a state that is not zero, against that update run
# token by token.
from nbdistributed_tpu.models.nemotron_h import (Mamba2Mixer,
    init_layer as _init_m2, nemotron3_nano_config)
ncfg = nemotron3_nano_config(dtype=jnp.float32)
m2 = _init_m2(_ks[0], ncfg, "mamba2")
m2 = {**m2, "conv_b": 0.02 * jax.random.normal(_ks[5], m2["conv_b"].shape),
      "dt_bias": m2["dt_bias"] + jax.random.normal(_ks[6], m2["dt_bias"].shape)}
mix2 = Mamba2Mixer(ncfg)
H2, P2, G2, N2 = ncfg.ssm_heads, ncfg.ssm_head_dim, ncfg.ssm_groups, ncfg.d_state
C2, W2, K2 = ncfg.d_inner, ncfg.conv_width, ncfg.d_conv
h1 = jax.random.normal(_ks[1], (rows, 1, ncfg.d_model))
st2 = jax.random.normal(_ks[2], (rows, H2, P2, N2))
tl2 = jax.random.normal(_ks[3], (rows, K2 - 1, W2))
def _m2_step(h, st, tl):
    out, st, tl = mix2.mix(h, m2, st, tl, live[:, None])
    return out * live[:, None, None], st, tl
def _m2_step_ref(h, st, tl):
    zxd = h[:, 0] @ m2["w_in"]
    z, xbc, dt = zxd[:, :C2], zxd[:, C2:C2 + W2], zxd[:, C2 + W2:]
    win = jnp.concatenate([tl, xbc[:, None]], 1)            # (rows, K, W)
    xbc = jax.nn.silu(jnp.einsum("bkc,kc->bc", win, m2["conv_w"]) + m2["conv_b"])
    x = xbc[:, :C2].reshape(rows, H2, P2)
    of_head = lambda m: jnp.repeat(m.reshape(rows, G2, N2), H2 // G2, 1)
    Bm, Cm = of_head(xbc[:, C2:C2 + G2 * N2]), of_head(xbc[:, C2 + G2 * N2:])
    d = jax.nn.softplus(dt + m2["dt_bias"])
    s = (jnp.exp(d * -jnp.exp(m2["A_log"]))[..., None, None] * st
         + jnp.einsum("bh,bhp,bhn->bhpn", d, x, Bm))
    y = jnp.einsum("bhpn,bhn->bhp", s, Cm) + m2["D"][:, None] * x
    y = y.reshape(rows, C2) * jax.nn.silu(z)
    yg = y.reshape(rows, G2, C2 // G2)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + ncfg.norm_eps)
    out = (yg.reshape(rows, C2) * m2["gate_norm"]) @ m2["w_out"]
    return (out[:, None] * live[:, None, None],
            jnp.where(live[:, None, None, None], s, st),
            jnp.where(live[:, None, None], win[:, 1:], tl))
_check("mamba2_state_update", 0, _m2_step, _m2_step_ref, h1, st2, tl2)
S2 = 384                                    # three blocks of 128
hs = jax.random.normal(_ks[4], (2, S2, ncfg.d_model))
real = jnp.arange(S2)[None, :] < jnp.asarray([S2, 300])[:, None]
def _m2_blocks(h, st, tl):
    out, st, tl = mix2.mix(h, m2, st, tl, real)
    return out * real[..., None], st, tl
def _m2_tokens(h, st, tl):
    def token(carry, inp):
        ht, v = inp
        out, st, tl = mix2.mix(ht[:, None], m2, *carry, v[:, None])
        return (st, tl), out[:, 0]
    (st, tl), out = jax.lax.scan(token, (st, tl), (h.swapaxes(0, 1), real.T))
    return out.swapaxes(0, 1) * real[..., None], st, tl
_check("mamba2_block_form", 0, _m2_blocks, _m2_tokens, hs, 0.1 * st2[:2], tl2[:2])
del st2, tl2, hs
_emit(checks=checks)
'''

_DIFFUSION_CELL = _EMIT + '''
import re
import numpy as np
from nbdistributed_tpu.models import DecodeServer
from nbdistributed_tpu.models.sdar import SDARConfig, init_sdar_model
dcfg = SDARConfig(vocab_size=4096, d_model=512, n_layers=2, n_heads=8,
                  n_kv_heads=2, head_dim=128, d_ff=256, n_experts=16,
                  top_k=4, max_seq_len=512, rope_theta=1e6, norm_eps=1e-6,
                  dtype=jnp.float32, mask_token_id=4095)
L, T, MASK, PAD = dcfg.block_length, dcfg.denoise_steps, 4095, 192
def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
def _rope(x):                               # (S, heads, Dh)
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0])[:, None] * 1e6 ** (
        -jnp.arange(half, dtype=jnp.float32) / half)[None]
    c, s_ = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s_, a * s_ + b * c], -1)
def _ref_logits(p, toks):                   # (PAD,) -> (PAD, V), no cache
    S, H, Hkv, Dh = toks.shape[0], 8, 2, 128
    blk = jnp.arange(S) // L
    keep = blk[None, :] <= blk[:, None]
    x = p["embed"][toks]
    for w in p["layers"]:
        h = _rms(x, w["attn_norm"])
        q = _rope(_rms((h @ w["wq"]).reshape(S, H, Dh), w["q_norm"]))
        k = _rope(_rms((h @ w["wk"]).reshape(S, Hkv, Dh), w["k_norm"]))
        v = (h @ w["wv"]).reshape(S, Hkv, Dh)
        k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
        sc = jnp.einsum("shd,thd->hst", q, k) * Dh ** -0.5
        pr = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
        x = x + jnp.einsum("hst,thd->shd", pr, v).reshape(S, -1) @ w["wo"]
        h, m = _rms(x, w["mlp_norm"]), w["moe"]
        top, idx = jax.lax.top_k(jax.nn.softmax(h @ m["router"], -1), 4)
        gates = jnp.zeros((S, 16)).at[jnp.arange(S)[:, None], idx].set(
            top / top.sum(-1, keepdims=True))
        hid = (jax.nn.silu(jnp.einsum("sd,edf->esf", h, m["w_gate"]))
               * jnp.einsum("sd,edf->esf", h, m["w_up"]))
        x = x + jnp.einsum("se,esd->sd", gates,
                           jnp.einsum("esf,efd->esd", hid, m["w_down"]))
    return _rms(x, p["final_norm"]) @ p["lm_head"]
with jax.default_matmul_precision("highest"):
    dparams = init_sdar_model(jax.random.PRNGKey(39), dcfg)
    srv = DecodeServer(dparams, dcfg, max_batch=4, max_len=256, pad_to=64,
                       kv_block_tokens=64, prefill_chunk=64,
                       interleave_prefill=True)
    # the compiled pass's calls of the paged kernel, by its name (the
    # lowered text holds the jitted kernel wrapper once, however many
    # layers call it; XLA's own grouped matmul is a tpu_custom_call too)
    mosaic = len(re.findall(r"%nbd_flash_decode_paged[.\\d]* = ",
                            srv._step_fn.lower(
        srv._params, srv._cache, srv._paged.device_table(), srv._lens,
        srv._block, srv._active, srv._key).compile().as_text()))
    rng = np.random.default_rng(39)
    reqs = [(rng.integers(0, 4000, n).tolist(), m)
            for n, m in ((70, 10), (9, 11), (128, 8))]
    rids = [srv.submit(p, m) for p, m in reqs]
    outs = srv.run_until_done(400)
    ref = jax.jit(_ref_logits)
    err, off, seen = 0.0, 0, 0
    for (prompt, m), rid in zip(reqs, rids):
        toks, when = outs[rid], srv.fixed_at[rid]
        seq = np.asarray(prompt + toks)
        fixed = np.asarray([-1] * len(prompt) + when)
        for b in range(len(prompt) // L, len(seq) // L):
            at = slice(b * L, (b + 1) * L)
            opened = int((fixed[at] >= 0).sum())
            off += [int((fixed[at] == s).sum()) for s in range(T)] != [
                1 if s < opened else 0 for s in range(T)]
            for s_ in range(T):
                now = np.full((PAD,), MASK)
                now[:b * L] = seq[:b * L]
                now[at] = np.where(fixed[at] < s_, seq[at], MASK)
                lg = np.asarray(ref(dparams, jnp.asarray(now))[at])
                conf = -np.log(np.exp(       # log of the max softmax
                    lg - lg.max(-1, keepdims=True)).sum(-1))
                for j in np.flatnonzero(fixed[at] == s_):
                    rest = conf[fixed[at] > s_]
                    err = max(err, float(lg[j].max() - lg[j, seq[at][j]]),
                              float(rest.max() - conf[j]) if rest.size
                              else 0.0)
                    seen += 1
_emit(mosaic=mosaic, layers=dcfg.n_layers, err=err, tol=1e-3,
      off_schedule=off, tokens=seen, wanted=sum(m for _, m in reqs))
'''

_TRAIN_CELL = '''
import optax
from nbdistributed_tpu.models import loss_fn, make_train_step
from nbdistributed_tpu.parallel.data_parallel import (collectives_of, ddp_init,
                                                      make_ddp_step)
S = 4096
params = init_params(jax.random.PRNGKey(0), cfg)
opt = optax.adamw(3e-4)
tokens = np.random.default_rng(1000 + rank).integers(
    0, cfg.vocab_size, (1, S), dtype=np.int32)
if world_size == 1:
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    batch = {"tokens": jnp.asarray(tokens)}
else:
    # DDP over one device per rank, batch sharded over the ranks.
    mesh = make_mesh({"dp": world_size})
    params, _ = ddp_init(params, (), mesh)
    opt_state = opt.init(params)
    step = make_ddp_step(lambda p, b: loss_fn(p, b, cfg), opt, mesh)
    batch = shard_batch({"tokens": tokens}, mesh)
lowered = step.lower(params, opt_state, batch)
mosaic = lowered.as_text().count("tpu_custom_call")
t0 = time.time()
compiled = lowered.compile()
compile_s = round(time.time() - t0, 1)
collectives = collectives_of(compiled)
losses, step_s = [], []
for _ in range(4):
    t0 = time.time()
    params, opt_state, loss = compiled(params, opt_state, batch)
    losses.append(float(loss))
    step_s.append(round(time.time() - t0, 3))
del opt_state, batch, compiled, lowered
_emit(params_m=round(cfg.num_params() / 1e6, 1), losses=losses,
      mosaic=mosaic, compile_s=compile_s, step_s=step_s,
      collectives=collectives)
'''

# Greedy decode of the trained parameters, bf16 and int8 caches; the
# prompt (160) is longer than one serving prefill chunk (128).
_GENERATE_CELL = _EMIT + '''
import time
from nbdistributed_tpu.models import make_generate_fn
local = jax.tree.map(lambda x: x.addressable_data(0), params)
prompt = jnp.asarray(np.random.default_rng(5).integers(
    0, cfg.vocab_size, (1, 160), dtype=np.int32))
mosaic, compile_s, runs = {}, {}, {}
for name, q8 in (("bf16", False), ("int8", True)):
    fn = make_generate_fn(cfg, 32, max_len=512, kv_quantized=q8)
    mosaic[name] = fn.lower(local, prompt).as_text().count("tpu_custom_call")
    t0 = time.time()
    first = np.asarray(fn(local, prompt))[0, 160:]
    compile_s[name] = round(time.time() - t0, 1)
    runs[name] = (first, np.asarray(fn(local, prompt))[0, 160:])
same = all(len(a) == 32 and np.array_equal(a, b) for a, b in runs.values())
in_vocab = all(bool(((a >= 0) & (a < cfg.vocab_size)).all())
               for a, _ in runs.values())
tokens = dict((name, a.tolist()) for name, (a, _) in runs.items())
del local
_emit(mosaic=mosaic, compile_s=compile_s, tokens=tokens,
      reproducible=same, in_vocab=in_vocab)
'''

_SPEC_CELL = '''
params = init_params(jax.random.PRNGKey(0), cfg)
'''

# After serving, on every pool rank: the greedy reference for every
# request and each served stream scored under the model (teacher-forced
# logits); then the float32 run — a server of the served geometry
# against `generate`, both on float32 copies of the same weights.
_VERIFY_CELL = '''
import dataclasses
from nbdistributed_tpu.models import DecodeServer, forward, make_generate_fn
prompts, served = {prompts}, {served}
t0 = time.time()
def _reference(params, cfg, prompts, budgets):
    gen, out = dict(), []
    for p, n in zip(prompts, budgets):
        if n not in gen:          # one jit per budget, one trace per shape
            gen[n] = make_generate_fn(cfg, n, max_len={max_len})
        toks = gen[n](params, jnp.asarray(p, jnp.int32)[None])
        out.append([int(t) for t in np.asarray(toks)[0, len(p):]])
    return out
ref = _reference(params, cfg, prompts, [len(o) for o in served])
exact = [r == o for r, o in zip(ref, served)]
deficit = []
_fwd = jax.jit(lambda params, toks: forward(params, toks, cfg))
for p, out in zip(prompts, served):
    logits = _fwd(params, jnp.asarray(p + out, jnp.int32)[None])[0]
    at = logits[len(p) - 1:len(p) + len(out) - 1]       # predicts out[i]
    gap = at.max(-1) - at[jnp.arange(len(out)), jnp.asarray(out)]
    deficit.append(float((gap / at.std(-1)).max()))
cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
p32 = [prompts[i] for i in {f32_exact}]
n32 = [len(served[i]) for i in {f32_exact}]
with jax.default_matmul_precision("highest"):
    srv = DecodeServer(params32, cfg32, **{server_kw})
    rids = [srv.submit(p, n) for p, n in zip(p32, n32)]
    srv.run_until_done()
    exact_f32 = [list(srv.outputs[r]) == want for r, want in
                 zip(rids, _reference(params32, cfg32, p32, n32))]
del params32, srv
_emit(platform=jax.default_backend(), exact=exact, deficit=deficit,
      exact_f32=exact_f32, verify_s=round(time.time() - t0, 1))
'''


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workers", type=int, default=1,
                    help="fleet size = chips used (1, or 4 on a "
                         "four-chip host)")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    try:
        import nbdistributed_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the nbdistributed_tpu package is not beside "
              f"this script ({e})", file=sys.stderr)
        return 2

    def _bail(signum, _frame):
        raise SystemExit(f"chip_smoke: stopped by signal {signum}")

    signal.signal(signal.SIGTERM, _bail)
    signal.signal(signal.SIGALRM, _bail)
    signal.alarm(DEADLINE_S)
    return Smoke(args.workers).run()


if __name__ == "__main__":
    sys.exit(main())
