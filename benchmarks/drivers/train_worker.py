"""What a training cell runs on every worker, called from the
``%%distributed`` cells that ``drivers/train.py`` sends.  The only
module of the benchmark that imports the program's model code: it
builds the user's loop (the plain ``loss_fn`` under
``make_tp_train_step`` / ``make_ddp_step``, AdamW) around weights and
rows that the benchmark makes from the seed.
"""

from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

from benchmarks.model import reference as R
from benchmarks.model import weights as W

WARM_STEPS = 3          # the first steps, followed by the reference


def program_config(cfg: dict):
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp
    from nbdistributed_tpu.models.transformer import TransformerConfig
    z = W.sizes(cfg)
    return TransformerConfig(
        vocab_size=z["V"], d_model=z["D"], n_layers=z["L"], n_heads=z["H"],
        n_kv_heads=z["Hkv"], d_ff=z["F"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["torch_dtype"]),
        sliding_window=cfg.get("sliding_window"), use_flash=True)


class Trainer:
    """The one object that set-up builds and the window drives: the
    compiled step, its state, and the feed."""

    def __init__(self, seed, cfg, traffic, rank, world, broken):
        import jax
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from nbdistributed_tpu.models import loss_fn
        from nbdistributed_tpu.parallel.data_parallel import make_ddp_step
        from nbdistributed_tpu.parallel.mesh import make_mesh, shard_batch
        from nbdistributed_tpu.parallel.tensor_parallel import (
            make_tp_train_step)
        self.seed, self.cfg, self.rank, self.world = seed, cfg, rank, world
        self.rows, self.seq = traffic["rows_per_rank"], traffic["seq_len"]
        self.ref_steps = int(traffic.get("reference_steps", 2))
        self.pc = program_config(cfg)
        self.mesh = make_mesh({"dp": world})
        self._shard = functools.partial(shard_batch, mesh=self.mesh)
        repl = NamedSharding(self.mesh, P())
        self.key = W.seed_key(seed)
        self._make = jax.jit(functools.partial(W.make_weights, cfg=cfg),
                             out_shardings=repl)
        self.params = self._make(self.key)
        opt = optax.adamw(R.ADAMW["lr"])
        self.opt_state = opt.init(self.params)
        loss = lambda p, b: loss_fn(p, b, self.pc)
        self.step = (make_ddp_step(loss, opt, self.mesh) if world > 1
                     else make_tp_train_step(loss, opt, self.mesh, None))
        if broken == "state_unchanged":     # tests: a step that does nothing
            real = self.step
            self.step = lambda p, s, b: (p, s, real(
                jax.tree.map(lambda x: x.copy(), p),
                jax.tree.map(lambda x: x.copy(), s), b)[2])
        self.broken = broken
        self.step_no = 0

    def host_rows(self, step_no, rank=None):
        """Rows of one rank at one step: all differ, all from the seed."""
        rank = self.rank if rank is None else rank
        rows = W.tokens_for(self.seed, step_no * 4096 + rank,
                            (self.rows, self.seq), W.sizes(self.cfg)["V"])
        return rows

    def one_step(self):
        if self.broken == "stall" and self.step_no == WARM_STEPS + 3:
            time.sleep(1.0)         # tests: a stall must move the rate
        batch = self._shard({"tokens": self.host_rows(self.step_no)})
        self.params, self.opt_state, loss = self.step(
            self.params, self.opt_state, batch)
        self.step_no += 1
        return loss

    # -- set-up: the first steps, with the readings `correct` needs ----

    def warm_up(self) -> dict:
        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            t))
        change = jax.jit(lambda p, key: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            p, self._make(key)))
        losses, marks = [], []
        for i in range(WARM_STEPS):
            losses.append(float(self.one_step()))
            marks.append(time.perf_counter() - t0)
            if i == 0:      # AdamW's first moment is (1 - b1) * gradient
                mu = self.opt_state[0].mu
                grad = {k: v / (1 - R.ADAMW["b1"]) for k, v in
                        R.leaf_norms(norms(mu)).items()}
            if i == self.ref_steps - 1:
                moved = R.leaf_norms(change(self.params, self.key))
        return {"losses": losses, "grad_norms": grad, "change_norms": moved,
                "warm_marks_s": marks}

    # -- the window ----------------------------------------------------

    def window(self, seconds, fetch_every, trace=None) -> dict:
        """Whole steps for ``seconds``, the loss fetched every
        ``fetch_every`` steps as a user's logging does; ends at a fetch,
        so every step counted is ready.  ``trace``: (dir, seconds) to
        profile that long from the second fetch on, each rank into
        its own ``rank<r>`` under dir."""
        import jax
        marks, fetches, traced = [], [], None
        t0 = time.perf_counter()
        while True:
            loss = self.one_step()
            marks.append(time.perf_counter() - t0)
            if len(marks) % fetch_every:
                continue
            value = float(loss)
            now = time.perf_counter() - t0
            fetches.append([len(marks), now, value])
            if trace and traced is None and len(fetches) == 2:
                jax.profiler.start_trace(
                    os.path.join(trace[0], f"rank{self.rank}"))
                traced = [now, None]
            elif trace and traced and traced[1] is None \
                    and now - traced[0] >= trace[1]:
                jax.profiler.stop_trace()
                traced[1] = now
            if now >= seconds and not (traced and traced[1] is None):
                break
        jax.block_until_ready((self.params, self.opt_state))
        t1 = time.perf_counter() - t0
        return {"steps": len(marks), "seconds": t1,
                "tokens": len(marks) * self.rows * self.seq * self.world,
                "dispatch_marks_s": marks, "fetches": fetches,
                "traced": traced}

    # -- afterwards ----------------------------------------------------

    def memory_peak(self) -> int:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def free(self):
        self.params = self.opt_state = self.step = None

    def reference(self, control: bool) -> dict:
        """The plain reference over the rows the first steps were fed
        (every rank's, for the mean over the global batch)."""
        batches = [np.concatenate([self.host_rows(i, r)
                                   for r in range(self.world)])
                   for i in range(WARM_STEPS)]
        t0 = time.perf_counter()
        out = {"ref": R.train_reference(self.seed, self.cfg, batches,
                                        steps=self.ref_steps)}
        out["reference_s"] = time.perf_counter() - t0
        if control:
            out["control"] = R.train_reference(
                self.seed, self.cfg, batches, q=R.fp8, steps=self.ref_steps)
        return out


def emit(tag: str, rank: int, **kw):
    import sys
    sys.stdout.write(f"{tag} " + json.dumps(dict(rank=rank, **kw)) + "\n")
