"""Generate examples/02_finetune.ipynb — the reference's flagship user
journey (00_accelerate.ipynb cells 10/18/28/36-40): load a pretrained
checkpoint, tokenize a dataset, and fine-tune it interactively,
cell-by-cell, data-parallel across workers.

This build environment has zero network egress (no HF hub, no
datasets downloads), so the checkpoint is
constructed LOCALLY at the real SmolLM2-135M architecture and saved
with ``save_pretrained``; the load -> convert -> fine-tune path the
notebook exercises is byte-identical to pulling the same files from
the hub.  The corpus is real English text sourced locally (this
repository's own documentation), byte-tokenized."""

import nbformat as nbf

nb = nbf.v4.new_notebook()
nb.metadata["kernelspec"] = {
    "display_name": "Python 3", "language": "python", "name": "python3"}

C = []


def md(src):
    C.append(nbf.v4.new_markdown_cell(src, id=f"cell-{len(C)}"))


def code(src):
    C.append(nbf.v4.new_code_cell(src, id=f"cell-{len(C)}"))


md("""# Fine-tune a checkpoint, interactively — the accelerate journey

The reference framework's flagship demo (`00_accelerate.ipynb`) loads a
pretrained SmolLM2-135M, tokenizes a dataset, and fine-tunes it with
DDP — every step an ordinary notebook cell running on all workers.
This notebook is that journey on the TPU-native stack: HF checkpoint →
JAX pytree (`load_hf_pretrained`), local text corpus → packed token
batches (`pack_tokens` / `shard_arrays`), cell-by-cell data-parallel
fine-tuning with eager gradient `all_reduce`, and generation from the
tuned weights.

> **Checkpoint provenance**: this environment has no network egress, so
> the checkpoint is built locally at the exact SmolLM2-135M
> architecture (`LlamaForCausalLM`, 576 hidden / 30 layers / 9 heads /
> 3 KV heads, tied embeddings) and saved with `save_pretrained` — the
> directory the loader consumes is indistinguishable from a hub
> download of the same files.""")

code("%load_ext nbdistributed_tpu")

code("""\
import os
backend = os.environ.get("NBD_NOTEBOOK_BACKEND", "auto")
nw = int(os.environ.get("NBD_NOTEBOOK_WORKERS", "2"))
# Overridable so tests use a per-run temp dir (no /tmp litter/races).
ckpt_dir = os.environ.get("NBD_NOTEBOOK_CKPT_DIR",
                          "/tmp/nbd_smol135m_local")
ck_out = os.environ.get("NBD_NOTEBOOK_CK_OUT", "/tmp/nbd_finetune_ck")
""")

md("""## Build the local checkpoint (stands in for the hub download)

A hub pull would be `AutoModelForCausalLM.from_pretrained(
"HuggingFaceTB/SmolLM2-135M")`; offline, we construct the identical
architecture with `transformers` and `save_pretrained` it.  This runs
*before* `%dist_init`, locally in the kernel — exactly where a user
would run their download cell.""")

code("""\
import torch
from transformers import LlamaConfig, LlamaForCausalLM

torch.manual_seed(0)
hf_cfg = LlamaConfig(
    vocab_size=49152, hidden_size=576, intermediate_size=1536,
    num_hidden_layers=30, num_attention_heads=9, num_key_value_heads=3,
    max_position_embeddings=2048, rope_theta=100000.0,
    tie_word_embeddings=True)
model = LlamaForCausalLM(hf_cfg)
n_params = sum(p.numel() for p in model.parameters())
model.save_pretrained(ckpt_dir, safe_serialization=True)
del model
print(f"saved {n_params/1e6:.1f}M-param SmolLM2-135M-architecture "
      f"checkpoint to {ckpt_dir}")""")

code("%dist_init -n {nw} --backend {backend} -t 600")

md("""## Load the checkpoint on every worker

`load_hf_pretrained` converts the torch checkpoint to a JAX pytree +
`TransformerConfig` (tied embeddings become `lm_head = embed.T`); each
rank holds a full replica — data parallelism, like the reference's
Accelerate DDP.""")

code("""\
# (cells now run on the workers: define worker-side paths/imports here
# — the workers inherit the coordinator's environment)
import os
ckpt_dir = os.environ.get("NBD_NOTEBOOK_CKPT_DIR",
                          "/tmp/nbd_smol135m_local")
params, cfg = load_hf_pretrained(ckpt_dir, dtype=jnp.float32)
n = sum(x.size for x in jax.tree_util.tree_leaves(params))
print(f"rank {rank}: loaded {n/1e6:.1f}M params, "
      f"d_model={cfg.d_model}, layers={cfg.n_layers}")""")

md("""## The dataset: real local text, packed into training batches

The reference tokenizes MRPC from the hub; offline, the corpus is this
repository's own documentation (real English prose), byte-tokenized
(ids 0-255 ⊂ the model's vocabulary) and packed into fixed-length
rows.  `batch_iterator` is the shipped per-rank dataloader: every rank
builds it with the same seed and takes its own stride through an
identical permutation — the sharding Accelerate's dataloader wrapper
does.""")

code("""\
import numpy as _np
# Corpus files live at the repo root; resolve from the installed
# package so the notebook works from any working directory.
import nbdistributed_tpu as _pkg
repo = os.path.dirname(os.path.dirname(os.path.abspath(_pkg.__file__)))
corpus = ""
for f in ("README.md", "ROADMAP.md", "SURVEY.md"):
    p = os.path.join(repo, f)
    if os.path.exists(p):
        corpus += open(p, encoding="utf-8").read() + "\\n\\n"
ids = _np.frombuffer(corpus.encode("utf-8"), dtype=_np.uint8)
S = 128
n_rows = len(ids) // S
assert n_rows > 0, f"empty corpus — no docs found under {repo}"
data = _np.asarray(ids[:n_rows * S], dtype=_np.int32).reshape(n_rows, S)
print(f"rank {rank}: {len(ids)} bytes of local text -> "
      f"{n_rows} rows of {S}")""")

md("""## Cell-by-cell DDP fine-tuning

The local gradient step is jitted; gradients cross ranks through the
eager `all_reduce` (mean) between the two jitted halves — the
`torch.distributed` DDP pattern, XLA-native.  Every `print` streams
back rank-tagged while the loop runs.""")

code("""\
import optax
opt = optax.adamw(3e-4)
state = opt.init(params)
B = 2  # per-rank batch

from nbdistributed_tpu.models import loss_fn

@jax.jit
def local_grads(p, batch):
    return jax.value_and_grad(lambda p: loss_fn(p, batch, cfg))(p)

@jax.jit
def apply_grads(p, s, g):
    u, s = opt.update(g, s, p)
    return optax.apply_updates(p, u), s

def ddp_step(p, s, batch):
    l, g = local_grads(p, batch)
    if world_size > 1:
        g = jax.tree.map(lambda t: all_reduce(t, "mean"), g)
    return *apply_grads(p, s, g), l

print(f"rank {rank}: fine-tune step ready (B={B}/rank, "
      f"global batch {B * world_size})")""")

code("""\
import time
it = batch_iterator({"tokens": data}, batch_size=B, rank=rank,
                    world_size=world_size, seed=0, epochs=None)
losses = []
for step in range(4):
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    t0 = time.time()
    params, state, l = ddp_step(params, state, batch)
    losses.append(float(l))
    print(f"step {step}: loss {float(l):.4f} "
          f"({time.time() - t0:.1f}s)")
print(f"rank {rank}: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
      f"({'improved' if losses[-1] < losses[0] else 'NOT improved'})")""")

md("""## Memory-lean loss: chunked-vocab cross-entropy

`ce_chunk=N` makes the loss stream the lm_head in N-column blocks
(`ops/xent.py`): the `(B, S, V)` logits — the buffer that caps the
train batch at LM scale — never materialize, in forward or backward.
Same numbers, a fraction of the memory:""")

code("""\
import dataclasses
cfg_lean = dataclasses.replace(cfg, ce_chunk=8192)
check = {"tokens": jnp.asarray(data[:2])}
l_full = float(loss_fn(params, check, cfg))
l_lean = float(loss_fn(params, check, cfg_lean))
print(f"rank {rank}: full-logits loss {l_full:.6f}, "
      f"chunked {l_lean:.6f} (match: {abs(l_full - l_lean) < 1e-4})")""")

md("""## Generate from the fine-tuned weights (rank 0)

`%%rank [0]` targets one worker, like the reference's rank-0
inspection cells.  The prompt is a byte-tokenized string; the greedy
continuation decodes back to text.""")

code("""\
%%rank [0]
from nbdistributed_tpu.models import generate
prompt_text = "The reference "
prompt = jnp.asarray(
    _np.frombuffer(prompt_text.encode(), dtype=_np.uint8)
    .astype(_np.int32))[None]
toks = generate(params, prompt, cfg, max_new_tokens=16)
cont = bytes(int(t) for t in toks[0, prompt.shape[1]:]
             if 0 <= int(t) < 256).decode("utf-8", "replace")
print(f"prompt {prompt_text!r} -> continuation {cont!r}")""")

md("""## Checkpoint the fine-tuned state and shut down

`%dist_checkpoint` saves named namespace entries per rank (atomic,
exact round-trip) — the resume story the reference leaves to
`torch.save` in user cells.""")

code("%dist_checkpoint {ck_out} params")

code("%dist_shutdown")

nb.cells = C

if __name__ == "__main__":
    import os

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "02_finetune.ipynb")
    nbf.write(nb, out)
    print(f"wrote {out} ({len(C)} cells)")
