"""Generation by diffusion over blocks: the SDAR family
(``model_type: sdar_moe``), on the serving path.

A layer is the Qwen3-MoE decoder layer: rotary grouped-query attention
whose heads are ``head_dim`` wide whatever ``d_model // n_heads`` is,
an RMS norm with a learned scale over each head's ``q`` and ``k``
before the rotary embedding (``qk_norm``), and
:func:`~..parallel.expert.softmax_routed_ffn` (softmax over all
experts in float32, the ``top_k`` largest renormalised, no shared
expert, no dense layer).  It runs in the code the dense family runs
(:func:`~.generate.forward_with_cache`, :class:`~.generate.GQAMixer`,
:class:`~.paged_kv.PagedKV`), the expert layers a tuple of one tree a
layer, unrolled, as :mod:`.mla`'s are and for its reason: a grouped
matmul takes its ``(E, D, F)`` operand whole, and a scan's slice of a
stack is a copy of it.

What makes it SDAR is the mask and the loop.

* **The mask is block-causal.**  With block length ``L``, position
  ``i`` attends every key ``j`` with ``j // L <= i // L``: everything
  before its block, and its whole block in both directions.  One
  integer generalises both paged attention calls
  (``ops/decode.py``): a prefill chunk's bound on the keys becomes the
  last position of the query's block, and a decode step carries ``L``
  queries a row that share one bound, so they fold into the kernel's
  query-group axis and a row's pages are read once, not once a
  position.  ``L`` = 1 is the causal mask, and the programs of every
  other family are then what they were.
* **A block is generated by denoising passes.**  The prompt's whole
  blocks are prefilled under that mask.  A block starts as the prompt's
  remainder followed by ``[MASK]``; a *denoising pass* runs the block's
  ``L`` positions over the committed cache, takes at every open
  position ``x0 = argmax logits`` and its confidence ``max
  softmax(logits)`` (the logits of a position predict that position's
  own token: no shift), and fixes the scheduled number of open
  positions, the most confident first (:func:`denoise`;
  ``low_confidence_static``: ``denoise_steps`` passes of ``L /
  denoise_steps`` positions each).  A pass writes the block's K/V to
  the row's pages and attends keys up to the block's last position;
  what it wrote is not committed: the next pass writes it again.
* **A finished block is committed by a lane of a later pass.**  Its
  final tokens' K/V must lie in the row's pages before a later block
  attends them, and that is all a commit is.  A pass therefore runs
  ``rows + lanes`` rows of ``L`` tokens (:func:`with_lanes`): the open
  blocks, and :func:`lanes` *commit lanes*, each the finished block of
  the row the host names, at that block's own position and under its
  own bound on the keys.  A layer writes every row's K/V first and
  attends second, so the open block of the same row, ``L`` further,
  attends the lane's fresh K/V in that very layer: the pass that opens
  a row's next block commits the one before it, ``lens`` moves there,
  and a block costs ``denoise_steps`` passes and no pass of its own
  for the commit.  A lane's logits are never computed: the head runs
  over the open blocks.  :class:`~.serving.DecodeServer` runs one such
  pass a step and emits a block at its last denoising pass.

Not here: thresholds on the confidence (``low_confidence_dynamic``:
how many positions a pass fixes is then data the host learns a pass
late) and sampling with a temperature inside the block.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..parallel.expert import softmax_routed_ffn
from ..utils import fan_in_normal
from .moe import MoEConfig
from .transformer import _preset, _rms_norm


@dataclasses.dataclass(frozen=True)
class SDARConfig(MoEConfig):
    """``d_ff`` is an expert's width; ``head_dim`` the family's own
    key.  ``denoise_steps`` divides ``block_length``."""
    moe_dispatch: str = "dropless"
    qk_norm: bool = True
    block_length: int = 4
    denoise_steps: int = 4
    mask_token_id: int = 151669

    def __post_init__(self):
        super().__post_init__()
        if (self.block_length < 1 or self.denoise_steps < 1
                or self.block_length % self.denoise_steps):
            raise ValueError(
                f"denoise_steps {self.denoise_steps} must divide "
                f"block_length {self.block_length}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is "
                             f"outside the vocabulary")
        if self.moe_dispatch != "dropless":
            raise ValueError("this family's experts are dropless")

    @property
    def fixed_per_pass(self) -> int:
        """Open positions a denoising pass fixes."""
        return self.block_length // self.denoise_steps

    def num_params(self) -> int:
        return super().num_params() + self.n_layers * 2 * self.head_dim


def sdar_30b_a3b_config(**kw) -> SDARConfig:
    """SDAR-30B-A3B-Chat as its ``config.json`` publishes it."""
    return _preset(kw, cls=SDARConfig, vocab_size=151936, d_model=2048,
                   n_layers=48, n_heads=32, n_kv_heads=4, head_dim=128,
                   d_ff=768, n_experts=128, top_k=8, max_seq_len=32768,
                   rope_theta=1e6, norm_eps=1e-6)


def tiny_sdar_config(**kw) -> SDARConfig:
    return _preset(kw, cls=SDARConfig, vocab_size=512, d_model=64,
                   n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32,
                   d_ff=32, n_experts=8, top_k=2, max_seq_len=256,
                   rope_theta=1e6, norm_eps=1e-6, mask_token_id=511)


def init_sdar_model(key, cfg: SDARConfig) -> dict:
    """``layers`` is a tuple of one tree a layer (the module docstring
    says why); embedding N(0, 1/d_model), matrices N(0, 1/fan_in), norm
    scales ones, the router kept in float32."""
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, F = cfg.n_experts, cfg.d_ff
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layers))

    def normal(shape, fan_in):
        return fan_in_normal(next(keys), shape, fan_in, cfg.dtype)

    def layer():
        return {"attn_norm": jnp.ones((D,), jnp.float32),
                "wq": normal((D, H * Dh), D),
                "wk": normal((D, Hkv * Dh), D),
                "wv": normal((D, Hkv * Dh), D),
                "wo": normal((H * Dh, D), H * Dh),
                "q_norm": jnp.ones((Dh,), jnp.float32),
                "k_norm": jnp.ones((Dh,), jnp.float32),
                "mlp_norm": jnp.ones((D,), jnp.float32),
                "moe": {"router": normal((D, E), D).astype(jnp.float32),
                        "w_gate": normal((E, D, F), D),
                        "w_up": normal((E, D, F), D),
                        "w_down": normal((E, F, D), F)}}

    return {"embed": normal((cfg.vocab_size, D), D),
            "layers": tuple(layer() for _ in range(cfg.n_layers)),
            "final_norm": jnp.ones((D,), jnp.float32),
            "lm_head": normal((D, cfg.vocab_size), D)}


def sdar_mlp_block(x, layer, cfg: SDARConfig, token_mask):
    """A layer's feed-forward residual block -> (x, the layer's
    routing load)."""
    h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    y, load = softmax_routed_ffn(h, layer["moe"], top_k=cfg.top_k,
                                 token_mask=token_mask)
    return x + y, load


OPEN = -2   # ``when`` of a position no pass has fixed yet


def lanes(cfg: SDARConfig, rows: int) -> int:
    """Commit lanes a pass over ``rows`` open blocks carries: a row
    finishes a block every ``denoise_steps`` passes and needs a lane
    once for it, so ``rows / denoise_steps`` is the mean demand.  Rows
    that ask for more in one pass wait a pass, which staggers them."""
    return -(-rows // cfg.denoise_steps)


def fresh_block(cfg: SDARConfig, rows: int) -> dict:
    """The state a block server keeps a row: the open block's tokens
    (all ``[MASK]``), ``when`` each was fixed (the pass of its block,
    -1 for a prompt's remainder, :data:`OPEN` while it is open), the
    pass the row's block is at, and ``closed``: the block the row
    finished last, kept until a lane commits it.  Which positions are
    open is this state and never a comparison with the mask id: a token
    a pass chose may be that id.  ``lane`` is the host's word for one
    pass and no state: the row each commit lane serves (``rows``:
    none)."""
    L = cfg.block_length
    masks = jnp.full((rows, L), cfg.mask_token_id, jnp.int32)
    return {"tokens": masks,
            "when": jnp.full((rows, L), OPEN, jnp.int32),
            "at": jnp.zeros((rows,), jnp.int32),
            "closed": masks,
            "lane": jnp.full((lanes(cfg, rows),), rows, jnp.int32)}


def seat_block(block: dict, slot: int, remainder: list[int],
               cfg: SDARConfig) -> dict:
    """Row ``slot``'s first block: the prompt's remainder (fewer than
    ``block_length`` tokens, fixed), then masks."""
    L, r = cfg.block_length, len(remainder)
    toks = list(remainder) + [cfg.mask_token_id] * (L - r)
    when = [-1] * r + [OPEN] * (L - r)
    return {**block,
            "tokens": block["tokens"].at[slot].set(
                jnp.asarray(toks, jnp.int32)),
            "when": block["when"].at[slot].set(
                jnp.asarray(when, jnp.int32)),
            "at": block["at"].at[slot].set(0)}


def with_lanes(block: dict, lens, table, active, cfg: SDARConfig):
    """The rows one pass runs, the open blocks first and the commit
    lanes after them -> (tokens, the position of each row's first
    token, block table, the rows that take part, ``lens`` as the pass
    leaves it).  Lane ``j`` is row ``block["lane"][j]``'s finished
    block at the position ``lens`` still stands at, under that row's
    table; the row's open block then sits a block further, and
    ``lens`` moves past the block the lane writes."""
    rows = active.shape[0]
    lane = block["lane"]
    on = lane < rows
    of = jnp.minimum(lane, rows - 1)
    carried = jnp.any(on[None, :] & (lane[None, :]
                                     == jnp.arange(rows)[:, None]), axis=1)
    step = cfg.block_length * carried.astype(lens.dtype)
    return (jnp.concatenate([block["tokens"], block["closed"][of]]),
            jnp.concatenate([lens + step, lens[of]]),
            jnp.concatenate([table, table[of]]),
            jnp.concatenate([active, on]),
            lens + step)


def denoise(logits, block: dict, active, cfg: SDARConfig):
    """One pass's bookkeeping, after the forward over the open blocks:
    ``logits`` (B, L, V) float32.  An active row fixes
    ``fixed_per_pass`` of its open positions (all that are open, if
    fewer), the most confident first and the lower index on a tie, to
    ``argmax logits``.  A row that has none open after that has
    finished its block: the block goes out (``out``) and into
    ``closed``, where a lane of a later pass finds it, and the row's
    next block is all masks.
    -> (block, out): ``out`` holds every row's tokens and ``when`` as
    the pass left them and ``done``, the rows that finished a block."""
    with jax.named_scope("denoise"):
        is_open = block["when"] == OPEN                     # (B, L)
        with jax.named_scope("sample"):
            x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # max softmax = exp(max - logsumexp): its logarithm orders
            # the positions alike
            conf = (jnp.max(logits, axis=-1)
                    - jax.nn.logsumexp(logits, axis=-1))
        conf = jnp.where(is_open, conf, -jnp.inf)
        # rank 0 = the most confident open position
        order = jnp.argsort(-conf, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        fix = is_open & (rank < cfg.fixed_per_pass) & active[:, None]
        tokens = jnp.where(fix, x0, block["tokens"])
        when = jnp.where(fix, block["at"][:, None], block["when"])
        done = active & ~jnp.any(when == OPEN, axis=-1)
        out = {"tokens": tokens, "when": when, "done": done}
        fresh = fresh_block(cfg, tokens.shape[0])
        at = jnp.where(active, block["at"] + 1, block["at"])
        roll = done[:, None]
        block = {**block,
                 "tokens": jnp.where(roll, fresh["tokens"], tokens),
                 "when": jnp.where(roll, fresh["when"], when),
                 "at": jnp.where(done, 0, at),
                 "closed": jnp.where(roll, tokens, block["closed"])}
    return block, out
