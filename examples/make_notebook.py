"""Generate examples/00_quickstart.ipynb — the acceptance-scenario demo
notebook (mirrors the role of the reference's 00_accelerate.ipynb)."""

import os

import nbformat as nbf

nb = nbf.v4.new_notebook()
nb.metadata["kernelspec"] = {
    "display_name": "Python 3", "language": "python", "name": "python3"}

C = []


def md(src):
    # Deterministic ids: regeneration diffs show only real changes.
    C.append(nbf.v4.new_markdown_cell(src, id=f"cell-{len(C)}"))


def code(src):
    C.append(nbf.v4.new_code_cell(src, id=f"cell-{len(C)}"))


md("""# Interactive distributed JAX on TPU — quick start

This notebook is the end-to-end acceptance scenario for
`nbdistributed_tpu` (the role `00_accelerate.ipynb` plays for the
reference): bring up a worker cluster from the notebook, run plain cells
on every rank with streamed per-rank output, target single ranks with
`%%rank`, and train a small transformer data-parallel — all cell by
cell, with full REPL semantics.

On a TPU host the workers each own a chip (`--backend tpu`, the
default when chips are present); everywhere else `--backend cpu` gives a
real multi-process world with cross-process gloo collectives.""")

code("%load_ext nbdistributed_tpu")

code("""\
import os
# The demo runs anywhere: pick the backend from the environment so CI
# can force cpu. On a TPU host "auto" selects the chips.
backend = os.environ.get("NBD_NOTEBOOK_BACKEND", "auto")
nw = int(os.environ.get("NBD_NOTEBOOK_WORKERS", "2"))""")

code("%dist_init -n {nw} --backend {backend} -t 300")

md("""## Every cell now runs on all workers

After `%dist_init`, plain cells are transparently dispatched to every
worker (disable with `%dist_mode -d`). Each worker has a persistent
namespace pre-seeded with `rank`, `world_size`, `jax`, `jnp`, eager
collectives (`all_reduce`, `all_gather`, `broadcast`, ...), and the
sharding toolkit (`Mesh`, `P`, `shard_map`).""")

code("""\
x = jnp.ones((100, 100)) * (rank + 1)
print(f"rank {rank}: x.sum() = {x.sum()}")
x.mean()""")

md("""### Collectives, interactively

`all_reduce` sums across the whole world — each rank contributes its
own `x`, every rank gets the same total back.""")

code("""\
total = all_reduce(x)
float(total[0, 0])  # sum over ranks of (rank+1) — identical everywhere""")

md("""## `%%rank` — target a subset

Create parameters on rank 0 only, then broadcast them to the world
(the reference README's tensor-parallel warm-up pattern).""")

code("""\
%%rank [0]
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (256, 256)) * 0.02
print("created on rank 0 only:", W.shape)""")

code("""\
if rank != 0:
    W = jnp.zeros((256, 256))
W = broadcast(W, root=0)
float(W.sum())  # identical on every rank after broadcast""")

md("""## Data-parallel training, cell by cell

A tiny Llama-style transformer from the built-in model family, trained
DDP: each rank computes grads on its own shard of the batch and
all-reduces them — the same loop structure as the reference's
Accelerate demo, but in JAX.""")

code("""\
import optax
from nbdistributed_tpu.models import tiny_config, init_params, loss_fn

cfg = tiny_config()
params = init_params(jax.random.PRNGKey(0), cfg)  # same init everywhere
opt = optax.adamw(3e-4)
opt_state = opt.init(params)

# The torch.distributed-style DDP loop: jit the local compute, keep the
# cross-process all_reduce eager between the two jitted halves (eager
# collectives cannot be traced — they move host-local values into a
# global XLA program).
@jax.jit
def local_grads(params, batch):
    return jax.value_and_grad(loss_fn)(params, batch, cfg)

@jax.jit
def apply_grads(params, opt_state, grads):
    updates, opt_state = opt.update(grads, opt_state, params)
    # Params are bfloat16 (MXU-friendly); accumulate the update in
    # float32 so tiny steps aren't rounded away.
    params = jax.tree.map(
        lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
        params, updates)
    return params, opt_state

def ddp_step(params, opt_state, batch):
    loss, grads = local_grads(params, batch)
    if world_size > 1:
        grads = jax.tree.map(lambda g: all_reduce(g, "mean"), grads)
        loss = all_reduce(loss, "mean")
    params, opt_state = apply_grads(params, opt_state, grads)
    return params, opt_state, loss
print("world size:", world_size)""")

code("""\
# Deterministic per-rank data sharding (the seeded batch_iterator):
# every rank builds the SAME shuffled permutation and takes its own
# rows of each global batch — the Accelerate-dataloader role, without
# a dataloader.
full_data = {"tokens": np.random.default_rng(0).integers(
    0, cfg.vocab_size, size=(64, 65)).astype("int32")}
batches = batch_iterator(full_data, batch_size=8, rank=rank,
                         world_size=world_size, seed=0, epochs=None)""")

code("""\
for step in range(5):
    batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
    params, opt_state, loss = ddp_step(params, opt_state, batch)
    if rank == 0:
        print(f"step {step}: loss {float(loss):.4f}")""")

md("""### Eval

Every rank evaluates the *same* held-out batch; after DDP the params are
identical on all ranks, so the losses must agree exactly.""")

code("""\
eval_batch = {"tokens": jax.random.randint(jax.random.PRNGKey(999),
                                           (8, 64), 0, cfg.vocab_size)}
eval_loss = float(loss_fn(params, eval_batch, cfg))
print(f"rank {rank}: eval loss {eval_loss:.4f}")""")

md("""## Checkpoint / restore

`%dist_checkpoint` snapshots named namespace pytrees from every rank
(atomic per-rank dirs, bfloat16-exact); `%dist_restore` loads them
back — the save/resume loop for long interactive sessions.""")

code("""\
# Fresh checkpoint dir: a stale one from an earlier run must never be
# silently restored below.
import shutil
shutil.rmtree("/tmp/nbd_demo_ckpt", ignore_errors=True)""")

code("%dist_checkpoint /tmp/nbd_demo_ckpt params opt_state")

code("""\
# Clobber the params, then restore them.
params = None""")

code("%dist_restore /tmp/nbd_demo_ckpt")

code("""\
# Restored params must give the exact same eval loss — a silent save
# failure above would surface here as an assertion error.
restored_loss = float(loss_fn(params, eval_batch, cfg))
assert restored_loss == eval_loss, (restored_loss, eval_loss)
print(f"rank {rank}: eval after restore {restored_loss:.4f} (exact)")""")

md("""### Background (async) checkpointing

`--background` returns immediately: each array is defensively copied
on-device (safe next to donating train steps) and the device→host
drain + disk IO run on a worker thread, so the next training cell
starts at once. `%dist_checkpoint --status` polls per rank.""")

code("%dist_checkpoint /tmp/nbd_demo_ckpt_bg params opt_state --background")

code("""\
# Training continues immediately while the save drains...
batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
params, opt_state, loss = ddp_step(params, opt_state, batch)
print(f"rank {rank}: trained a step during the save "
      f"(loss {float(loss):.4f})")""")

code("""\
import time
time.sleep(1.0)  # let the background write land for the poll below""")

code("%dist_checkpoint --status")

md("""## Generation

The model family includes a static-shape KV-cache decode loop (one
`lax.scan`, greedy or sampled) — here greedy continuations of a toy
prompt on every rank.""")

code("""\
from nbdistributed_tpu.models import generate
prompt = jnp.ones((1, 4), jnp.int32) * (rank + 1)
out_tokens = generate(params, prompt, cfg, max_new_tokens=8)
print(f"rank {rank}: {out_tokens[0].tolist()}")""")

md("""## Continuous-batching serving

`DecodeServer` (seeded in every worker namespace) serves staggered
requests from one paged KV pool — every decode step is one shared
batched forward no matter how requests arrive, a request holds only
the pages its tokens need, and greedy outputs are bit-identical per
request to standalone `generate`.""")

code("""\
%%rank [0]
srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4)
system_prompt = [7, 3, 9, 1]
ra = srv.submit(system_prompt + [5], 6)
rb = srv.submit(system_prompt + [8, 2], 6)
srv.run_until_done()
print("request A:", srv.outputs[ra])
print("request B:", srv.outputs[rb])
solo = generate(params, jnp.asarray([system_prompt + [5]], jnp.int32),
                cfg, max_new_tokens=6)[0][5:].tolist()
assert srv.outputs[ra] == solo, "serving must match solo generate"
print("bit-identical to solo generate:", solo)""")

md("""## Quantized decode: int8 and nibble-packed int4

Decode streams every weight per token, so bytes are throughput:
`quantize_params` stores the matmul weights int8 (half the bf16
stream), `quantize_params4` nibble-packs them into uint8 at exactly
0.5 bytes/weight with per-64-input-group scales.  Both trees serve
through the same `generate`/`DecodeServer` paths via `qlinear`
dispatch.""")

code("""\
%%rank [0]
from nbdistributed_tpu.models import quantize_params, quantize_params4

def tree_mb(t):
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(t)) / 1e6

q8, q4 = quantize_params(params), quantize_params4(params)
toks8 = generate(q8, prompt, cfg, max_new_tokens=8)[0].tolist()
toks4 = generate(q4, prompt, cfg, max_new_tokens=8)[0].tolist()
print(f"fp {tree_mb(params):.1f} MB -> int8 {tree_mb(q8):.1f} MB "
      f"-> int4 {tree_mb(q4):.1f} MB")
print("int8 decode:", toks8)
print("int4 decode:", toks4)""")

md("""## Pull model state into the kernel — no pickle

`%dist_pull` / `%dist_push` carry whole params/optimizer pytrees as a
JSON tree description plus raw array buffers — model state crosses the
control plane without pickle, so hardened (`allow_pickle=False`)
deployments lose nothing.""")

code("%dist_pull params --rank 0 --as kernel_params")

md("""## Bring your HuggingFace checkpoint

Any Llama-architecture `transformers` model converts into this
framework's pytree — after which the whole TPU path applies (sharding
rules, flash kernels, the generate loop above). Here a tiny randomly
initialized HF Llama proves the round trip inside the notebook: the
converted model's greedy continuation must match HF's own
`generate`.""")

code("""\
import torch
from transformers import LlamaConfig, LlamaForCausalLM
from nbdistributed_tpu.models import params_from_hf, generate

torch.manual_seed(0)
hf_model = LlamaForCausalLM(LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=256)).eval()
hf_prompt = torch.tensor([[5, 9, 2, 44]])
with torch.no_grad():
    hf_tokens = hf_model.generate(hf_prompt, max_new_tokens=6,
                                  do_sample=False)[0].tolist()

jx_params, jx_cfg = params_from_hf(hf_model, dtype=jnp.float32)
jx_cfg = type(jx_cfg)(**{**jx_cfg.__dict__, "use_flash": False})
jx_tokens = generate(jx_params, jnp.asarray([[5, 9, 2, 44]], jnp.int32),
                     jx_cfg, max_new_tokens=6)[0].tolist()
assert jx_tokens == hf_tokens, (jx_tokens, hf_tokens)
print(f"rank {rank}: HF and converted tokens match: {jx_tokens}")""")

md("## Cluster status, timeline, shutdown")

code("%dist_status")

code("%timeline_show")

code("%dist_shutdown")

nb.cells = C
out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "00_quickstart.ipynb")
nbf.write(nb, out)
print("wrote", out, "-", len(C), "cells")
