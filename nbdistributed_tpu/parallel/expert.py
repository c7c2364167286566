"""Expert parallelism: capacity-based MoE dispatch over an ``ep`` mesh
axis.

The reference has no MoE/expert-parallel support (SURVEY §2.3: "Expert
parallel (EP/MoE) — Absent"); this module goes beyond parity with a
TPU-first design.  Instead of per-token gather/scatter (dynamic shapes
XLA cannot tile), routing is expressed as dense one-hot dispatch/combine
einsums with a fixed per-expert capacity — the GShard/Switch recipe:

* every shape is static, so the whole layer lives inside one ``jit``;
* expert weights carry a leading ``(n_experts,)`` axis sharded over the
  ``ep`` mesh axis, and a sharding constraint on the dispatched
  activations ``(E, C, D)`` makes GSPMD compile the token exchange as an
  ``all_to_all`` over ICI — the hand-written NCCL alltoall of
  GPU MoE stacks falls out of the sharding lattice instead;
* over-capacity tokens are dropped (they pass through the residual),
  bounding memory and keeping the MXU batched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P



def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.bfloat16) -> dict:
    """Router + stacked SwiGLU expert weights (leading E axis)."""
    from ..utils import fan_in_normal

    kr, kg, ku, kd = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return fan_in_normal(k, shape, fan_in, dtype)

    E, D, F = n_experts, d_model, d_ff
    return {
        # fp32 router: gating is numerically delicate and tiny.
        "router": jax.random.normal(kr, (D, E), jnp.float32) * 0.02,
        "w_gate": normal(kg, (E, D, F), D),
        "w_up": normal(ku, (E, D, F), D),
        "w_down": normal(kd, (E, F, D), F),
    }


def moe_param_shardings(ep_axis: str = "ep", tp_axis: str | None = None,
                        leading=()) -> dict:
    """PartitionSpec rules for :func:`init_moe_params` trees.  Experts
    shard over ``ep_axis``; optionally the ffn dim also shards over
    ``tp_axis`` (combined ep×tp).  ``leading`` prefixes extra axes (the
    models stack a (n_layers,) axis in front)."""
    lead = tuple(leading)
    return {
        "router": P(*lead, None, None),
        "w_gate": P(*lead, ep_axis, None, tp_axis),
        "w_up": P(*lead, ep_axis, None, tp_axis),
        "w_down": P(*lead, ep_axis, tp_axis, None),
    }


def compute_capacity(num_tokens: int, n_experts: int, top_k: int,
                     capacity_factor: float) -> int:
    """Per-expert token capacity C; multiple of 8 for TPU-friendly
    (8,128) tiling of the (E, C, D) dispatched activations."""
    cap = int(capacity_factor * top_k * num_tokens / n_experts)
    return max(8, -(-cap // 8) * 8)


def top_k_routing(logits, top_k: int):
    """Normalized top-k gates.  logits (T, E) fp32 ->
    gates (T, k), expert_idx (T, k), probs (T, E)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, expert_idx, probs


def sigmoid_bias_routing(logits, bias, top_k: int, scale: float):
    """Sigmoid scoring with a selection bias (DeepSeek-V3's
    ``noaux_tc`` without a group limit): scores ``s = sigmoid(logits)``
    in float32, the ``top_k`` experts chosen by ``s + bias``
    (``bias`` (E,): a per-expert buffer that steers the choice and
    never the weight), gates the chosen ``s`` over their sum
    (+1e-20) times ``scale``.  logits (T, E) -> gates (T, k),
    expert_idx (T, k)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, expert_idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, expert_idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * scale, expert_idx


def routing_load(expert_idx, n_experts: int, token_mask=None):
    """What one layer's routing asks of the expert weights it holds, as
    three float32 numbers: experts that received a row, the most rows
    on one expert, rows routed.  ``expert_idx`` counts from the first
    expert held; a choice of ``n_experts`` or more fell on an expert
    held elsewhere and counts nowhere, and neither do masked tokens."""
    T, k = expert_idx.shape
    w = (jnp.ones((T,), jnp.int32) if token_mask is None
         else token_mask.astype(jnp.int32))
    counts = jnp.zeros((n_experts + 1,), jnp.int32).at[
        jnp.minimum(expert_idx.reshape(-1), n_experts)].add(
        jnp.repeat(w, k))[:n_experts]
    return jnp.stack([jnp.sum(counts > 0), jnp.max(counts),
                      jnp.sum(counts)]).astype(jnp.float32)


def make_dispatch(gates, expert_idx, n_experts: int, capacity: int,
                  token_mask=None):
    """Dense dispatch/combine tensors from routing decisions.

    Position of each (token, choice) inside its expert's capacity buffer
    is a cumulative count in choice-major order, so every token's first
    choice outranks any token's second choice — the Switch priority
    rule.  ``token_mask`` (T,) bool: masked-out tokens take NO capacity
    slot (they do not merely get zero gates — they are invisible to
    other tokens' slot competition).  Returns ``dispatch`` (T, E, C)
    {0,1} and ``combine`` (T, E, C) = dispatch * gate.
    """
    T, k = expert_idx.shape
    onehot = jax.nn.one_hot(expert_idx, n_experts,
                            dtype=jnp.float32)        # (T, k, E)
    if token_mask is not None:
        onehot = onehot * token_mask.astype(jnp.float32)[:, None, None]
    flat = onehot.transpose(1, 0, 2).reshape(k * T, n_experts)
    pos = jnp.cumsum(flat, axis=0) - flat             # (k*T, E)
    pos = pos.reshape(k, T, n_experts).transpose(1, 0, 2)  # (T, k, E)
    keep = onehot * (pos < capacity)                  # drop over-capacity
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)          # (T, k, E, C)
    slot = slot * keep[..., None]
    dispatch = jnp.sum(slot, axis=1)                  # (T, E, C)
    combine = jnp.sum(slot * gates[:, :, None, None], axis=1)
    return dispatch, combine


def load_balance_loss(probs, expert_idx, n_experts: int,
                      token_mask=None):
    """Switch-style auxiliary loss: n_experts * Σ_e f_e · P_e, where
    f_e = fraction of tokens whose FIRST choice is e and P_e = mean
    router probability of e.  Minimized (=1) at uniform routing.
    ``token_mask`` excludes masked-out tokens from both means."""
    first = jax.nn.one_hot(expert_idx[:, 0], n_experts, dtype=jnp.float32)
    if token_mask is None:
        f = jnp.mean(first, axis=0)
        p = jnp.mean(probs, axis=0)
    else:
        m = token_mask.astype(jnp.float32)[:, None]
        n = jnp.maximum(jnp.sum(m), 1.0)
        f = jnp.sum(first * m, axis=0) / n
        p = jnp.sum(probs * m, axis=0) / n
    return n_experts * jnp.sum(f * p)


def _expert_linear(xe, w, spec: str):
    """Per-expert einsum where ``w`` is a plain array or an int8
    weight-only quantized leaf ``{"q8", "s"}`` (models/quant.py).  The
    scales are per (expert, output-channel) — constant along the
    contraction dim — so they commute with the einsum exactly as in
    ``transformer.qlinear``: the dot reads raw int8 and the rescale is
    one fused multiply on the (E, C, out) activation."""
    from ..models.transformer import is_quantized
    if is_quantized(w):
        y = jnp.einsum(spec, xe, w["q8"].astype(xe.dtype))
        return (y.astype(jnp.float32) * w["s"]).astype(xe.dtype)
    return jnp.einsum(spec, xe, w)


def _route_sort(expert_idx, E: int, token_mask=None):
    """The ONE routing-sort prologue shared by the sparse and dropless
    paths: flatten (T, k) choice-major (choice-major ordering is what
    makes the Switch priority rule and mask semantics line up), relabel
    masked tokens to the sentinel expert E (sorting past every real
    segment), and stable-sort by expert.

    Returns (order, e_sorted, tok, counts): the argsort, the sorted
    expert ids, the source token id per sorted row, and the
    ``bincount(length=E+1)`` including the sentinel bin."""
    T, k = expert_idx.shape
    flat_e = expert_idx.T.reshape(-1)             # choice-major (kT,)
    if token_mask is not None:
        flat_e = jnp.where(jnp.tile(token_mask, k), flat_e, E)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    counts = jnp.bincount(flat_e, length=E + 1)   # [..., masked bin]
    return order, e_sorted, (order % T).astype(jnp.int32), counts


def _ragged_expert_linear(xs, w, group_sizes, e_sorted):
    """``ragged_dot`` over expert segments (on the TPU the Pallas
    grouped matmul: :func:`~..ops.grouped.ragged_dot`), supporting int8
    weight-only quantized leaves: the per-(expert, output-channel)
    scales become a per-ROW rescale gathered by each row's expert id
    (constant along the contraction dim, so the grouped dot still reads
    raw int8)."""
    from ..models.transformer import is_quantized
    from ..ops.grouped import ragged_dot
    if is_quantized(w):
        y = jax.lax.ragged_dot(xs, w["q8"].astype(xs.dtype),
                               group_sizes)
        s_rows = w["s"][jnp.clip(e_sorted, 0, w["s"].shape[0] - 1), 0]
        return (y.astype(jnp.float32) * s_rows).astype(xs.dtype)
    return ragged_dot(xs, w, group_sizes)


def _dropless_ffn(xt, params, gates, expert_idx, E: int,
                  token_mask=None, expert: str = "swiglu"):
    """MegaBlocks-style dropless expert compute: sort the (token,
    choice) pairs by expert and run the experts (``expert``: one of
    :data:`EXPERT_FORMS`) as grouped matmuls over the variable-size
    segments (:func:`_ragged_expert_linear`) — every routed token is
    computed, no capacity buffer exists, and compute is exactly
    sum_e n_e GEMM rows (what the MXU would do with perfect per-expert
    batching).

    ``E`` is the number of experts ``params`` holds.  Masked tokens,
    and choices relabelled ``E`` (an expert held elsewhere:
    :func:`shared_routed_ffn`), sort into a sentinel bin PAST every
    real segment (group_sizes covers only the experts held), and both
    their rows and their gate weights are zeroed.
    """
    T, D = xt.shape
    order, e_sorted, tok, counts = _route_sort(expert_idx, E,
                                               token_mask)
    keep = e_sorted < E
    group_sizes = counts[:E].astype(jnp.int32)

    xs = jnp.where(keep[:, None], xt[tok], 0)     # (kT, D)
    grouped = lambda x, w: _ragged_expert_linear(x, w, group_sizes,
                                                 e_sorted)
    rows = EXPERT_FORMS[expert](xs, params, grouped)    # (kT, D)
    # The rows past the covered total are zeros only in XLA's own
    # ragged_dot; the TPU's grouped-matmul kernel leaves them unwritten,
    # and 0 * (whatever memory held) may be NaN.  A masked token's
    # NaN would reach the KV cache (the trash block, pad positions),
    # and from there every row whose p @ v multiplies it by zero.
    rows = jnp.where(keep[:, None], rows, 0)
    g_sorted = gates.T.reshape(-1)[order]
    w = jnp.where(keep, g_sorted, 0.0).astype(xt.dtype)
    return jnp.zeros((T, D), xt.dtype).at[tok].add(rows * w[:, None])


def _dropless_ffn_ep(xt, params, logits, top_k: int, E: int, mesh,
                     ep_axis: str, capacity_factor: float,
                     token_mask=None, capacity: int | None = None,
                     token_axes: tuple = ("dp",)):
    """Expert-parallel dropless: hierarchical per-token-shard routing
    feeding locally dropless ``ragged_dot`` segments — no global
    collective anywhere on the token path.

    True dropless dispatch (variable per-expert group sizes) cannot
    cross an SPMD shard boundary — a static bound is needed somewhere.
    Earlier revisions bounded a global (ep, Cs, D) exchange buffer and
    let GSPMD compile the token movement, but the routing sort ran on
    the GLOBALLY flattened (kT,) choice array: with tokens sharded
    over a data axis, GSPMD lowers that sort (and the sorted (kT, D)
    row gather feeding the buffer) as all-gather-shaped collectives —
    fine at bench scale, quadratic wire cost at pod scale.

    This version keeps every step shard-local (a ``shard_map`` over
    the token axes × ``ep_axis``):

    * tokens stay sharded over ``token_axes`` (activations between
      layers are replicated over ``ep``, so each (token-shard, ep)
      device already holds its token block — dispatch needs NO
      exchange at all, only a local sort of ``kT/n_dp`` choices);
    * each device selects the rows routed to ITS ``E/ep`` experts into
      a static ``(Cs, D)`` buffer, ``Cs = ceil(cf·k·T_loc/ep)`` pooled
      over the shard's experts (an explicit per-expert ``capacity``
      pools to ``(E/ep)·capacity``) — drops only at whole-(token-
      shard, ep) overflow, vanishing once the bound reaches
      ``k·T_loc``;
    * the SwiGLU runs as three ``ragged_dot`` grouped matmuls over the
      variable-size local expert segments (every received row
      computed);
    * combine is one ``psum`` over ``ep`` of the (T_loc, D) partial
      outputs — the single collective in the layer, riding ICI.

    Takes the raw router ``logits`` rather than precomputed
    gates/indices: ``lax.top_k`` lowers to XLA's TopK custom call,
    which GSPMD does not partition over sharded rows (it all-gathers
    the (T, E) probs) — running the top-k on each shard's local
    logits block inside the shard_map keeps routing collective-free
    and is exact (top-k is row-wise).

    The ep-redundant sort (each ep shard re-sorts its token block's
    choices) trades ``n_ep``× duplicated O(kT_loc log kT_loc) integer
    work for zero token-exchange collectives — integer sorts are noise
    next to the expert GEMMs on the MXU.  ``token_axes`` names the
    mesh axes the flattened token dim is sharded over (axes absent
    from the mesh are ignored; a token count not divisible by the
    token-shard product falls back to replicated-token semantics).
    """
    from ..models.transformer import is_quantized

    T, D = xt.shape
    k = top_k
    n_ep = mesh.shape[ep_axis]
    if E % n_ep:
        raise ValueError(f"n_experts {E} not divisible by ep axis "
                         f"size {n_ep}")
    E_loc = E // n_ep
    tok_axes = tuple(a for a in token_axes
                     if a in mesh.shape and a != ep_axis)
    n_tok = 1
    for a in tok_axes:
        n_tok *= mesh.shape[a]
    if n_tok == 1 or T % n_tok:
        tok_axes, n_tok = (), 1
    T_loc = T // n_tok
    kT_loc = k * T_loc
    # Same formula as the per-expert paths, pooled at shard level:
    # "experts" = shards, so the bound is ceil(cf·k·T_loc/ep) rounded
    # to 8.  An explicit ``capacity`` keeps its dense/sparse meaning —
    # per-EXPERT — and pools to E_loc·capacity per shard, so a caller
    # switching dispatch modes with a tuned per-expert value gets at
    # least the headroom the other modes gave (plus the pooling).
    Cs = (E_loc * capacity if capacity is not None
          else compute_capacity(T_loc, n_ep, k, capacity_factor))
    Cs = min(Cs, kT_loc)   # a shard never receives more than kT rows

    def wspec(w):
        if is_quantized(w):
            return {"q8": P(ep_axis, None, None),
                    "s": P(ep_axis, None, None)}
        return P(ep_axis, None, None)

    tok_entry = tok_axes if tok_axes else None
    mask = (jnp.ones((T,), bool) if token_mask is None else token_mask)

    def local_ffn(x, lg, tm, wg, wu, wd):
        # x (T_loc, D); lg (T_loc, E) router logits; wg/wu/wd local
        # (E_loc, ...).  Routing (softmax + top-k + sort) is computed
        # here, on the shard's rows — row-wise ops, exact vs global.
        j = jax.lax.axis_index(ep_axis)
        g, ei, _ = top_k_routing(lg, k)
        order, e_sorted, tok, counts = _route_sort(ei, E, tm)
        counts_e = counts[:E]
        starts_e = jnp.cumsum(counts_e) - counts_e        # (E,)
        lo = j * E_loc
        # This shard's segment is rows [starts_e[lo], starts_e[lo] +
        # sum of its expert counts): expert ids ascending => shard
        # segments contiguous in the sorted order.
        start_shard = starts_e[lo]
        in_shard = (e_sorted >= lo) & (e_sorted < lo + E_loc)
        pos = jnp.arange(kT_loc, dtype=jnp.int32) - start_shard
        keep = in_shard & (pos < Cs)
        slot = jnp.where(keep, pos, Cs).astype(jnp.int32)
        xs = jnp.where(keep[:, None], x[tok], 0)
        buf = jnp.zeros((Cs, D), x.dtype).at[slot].set(
            xs, mode="drop")                              # (Cs, D)
        # Per-local-expert group sizes after the Cs cut: expert e's
        # rows sit at within-shard positions [off_e, off_e + n_e).
        # (dynamic_slice: ``lo`` is a traced axis_index.)
        off_e = jax.lax.dynamic_slice(starts_e, (lo,),
                                      (E_loc,)) - start_shard
        n_e = jax.lax.dynamic_slice(counts_e, (lo,), (E_loc,))
        gs = (jnp.clip(off_e + n_e, 0, Cs)
              - jnp.clip(off_e, 0, Cs)).astype(jnp.int32)
        # Row -> local expert id (rows past the covered total are
        # zeros and land on the clipped last id).
        e_row = jnp.minimum(
            jnp.searchsorted(jnp.cumsum(gs), jnp.arange(Cs),
                             side="right"),
            E_loc - 1)
        h = (jax.nn.silu(_ragged_expert_linear(buf, wg, gs, e_row))
             * _ragged_expert_linear(buf, wu, gs, e_row))
        out = _ragged_expert_linear(h, wd, gs, e_row)     # (Cs, D)
        g_sorted = g.T.reshape(-1)[order]
        wgt = jnp.where(keep, g_sorted, 0.0).astype(x.dtype)
        rows = jnp.take(out, slot, axis=0, mode="fill", fill_value=0)
        y = jnp.zeros((T_loc, D), x.dtype).at[tok].add(
            rows * wgt[:, None])
        return jax.lax.psum(y, ep_axis)                   # combine

    return jax.shard_map(
        local_ffn, mesh=mesh,
        in_specs=(P(tok_entry, None), P(tok_entry, None),
                  P(tok_entry),
                  wspec(params["w_gate"]), wspec(params["w_up"]),
                  wspec(params["w_down"])),
        out_specs=P(tok_entry, None), check_vma=False)(
        xt, logits, mask, params["w_gate"],
        params["w_up"], params["w_down"])


def sparse_slots(expert_idx, E: int, C: int, token_mask=None):
    """Sort/segment routing: the same Switch priority rule as
    :func:`make_dispatch` without materializing any (T, E, C) tensor.

    Flattening (T, k) choice-major and stable-sorting by expert
    preserves choice-major order within each expert segment, so the
    rank inside the segment equals the dense path's cumulative-count
    position — drops are bit-identical.  ``token_mask`` (T,) bool:
    masked-out tokens are re-labeled to a sentinel expert E, sorting
    past every real segment — they take no capacity slot, exactly as
    in the dense path.  Returns, in sorted order: ``slot`` (kT,) int32
    index into the flat (E*C,) capacity buffer (== E*C for
    dropped/masked entries, for ``mode="drop"`` scatters), ``tok``
    (kT,) source token ids, ``keep`` (kT,) bool, and ``order`` (the
    argsort, for carrying gates along).
    """
    order, e_sorted, tok, counts = _route_sort(expert_idx, E,
                                               token_mask)
    k, T = expert_idx.shape[1], expert_idx.shape[0]
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(k * T, dtype=jnp.int32) - starts[e_sorted]
    keep = (pos < C) & (e_sorted < E)
    slot = jnp.where(keep, e_sorted * C + pos, E * C).astype(jnp.int32)
    return slot, tok, keep, order


def moe_ffn(x, params: dict, *, top_k: int = 2,
            capacity_factor: float = 1.25, mesh=None,
            ep_axis: str = "ep", dispatch_mode: str = "dense",
            token_mask=None, capacity: int | None = None,
            token_axes: tuple = ("dp",)):
    """Mixture-of-experts SwiGLU feed-forward.

    x: (..., D) -> (same shape, aux_loss scalar).  When ``mesh`` (with an
    ``ep`` axis) is given, the dispatched activations are sharding-
    constrained so GSPMD places each expert's (C, D) block on its ``ep``
    shard — compiling dispatch/combine into all_to_all collectives.

    ``dispatch_mode`` selects how tokens reach the (E, C, D) capacity
    buffer (expert compute is identical):

    * ``"dense"`` — one-hot dispatch/combine einsums (the oracle).
      FLOPs: 2·T·E·C·D each way; with E·C ≈ cf·k·T that is
      O(cf·k·T²·D) — **quadratic in token count** — plus the
      (T, k, E, C) slot one-hot in memory.  Fine at small T; the
      dispatch einsums (4·T·E·C·D) overtake the experts themselves
      (6·E·C·D·d_ff) once T > 1.5·d_ff — ~21.5k tokens for Mixtral,
      independent of cf and k (both scale dispatch and experts
      alike).
    * ``"sparse"`` — sort/segment routing: stable-sort the kT (token,
      choice) pairs by expert, take the first C per segment (the same
      priority rule, bit-identical drops), move rows by gather/scatter.
      Cost: O(kT log kT) sort + 2·kT·D copied elements — **linear in
      token count**, no T×E×C tensor anywhere.  Same shardings
      constrained under a mesh.

    * ``"dropless"`` — MegaBlocks-style: no per-expert capacity
      buffer.  Tokens sort by expert and the SwiGLU runs as three
      ``jax.lax.ragged_dot`` grouped matmuls over the variable-size
      expert segments — every token reaches every expert it routed
      to, so there are NO drops and ``capacity_factor``/``capacity``
      are ignored.  Equals the dense oracle whenever the oracle's
      capacity is lossless; under tight capacity it is the *better*
      answer (the one capacity only approximates).  Over an ``ep``
      mesh axis it becomes the hierarchical shard-capacity hybrid
      (:func:`_dropless_ffn_ep`): routing sorts stay local to each
      token shard (``token_axes`` names the mesh axes the flattened
      token dim is sharded over, default ``("dp",)``), each
      (token-shard, ep) device selects its experts' rows into a
      static ``(Cs, D)`` buffer (``Cs = ceil(cf·k·T_loc/ep)``; an
      explicit per-expert ``capacity`` pools to ``(E/ep)·capacity``)
      feeding locally dropless ragged segments, and combine is one
      ``psum`` over ``ep`` — no global all-gather/all-to-all on the
      token path.  Per-expert slack pools across each shard's E/ep
      experts, so drops only occur at whole-shard overflow.

    ``token_mask`` (bool, shape ``x.shape[:-1]``): masked-out tokens
    contribute nothing — zero output, no capacity slot consumed, and
    no effect on the aux loss — so active tokens route exactly as if
    the masked ones did not exist (at equal ``capacity``).  Batched
    speculative decoding uses this to keep finished streams from
    perturbing live ones.  ``capacity`` overrides the
    ``capacity_factor`` formula (needed when comparing runs whose
    token counts differ).
    """
    if dispatch_mode not in ("dense", "sparse", "dropless"):
        raise ValueError(f"unknown dispatch_mode {dispatch_mode!r}")
    orig_shape = x.shape
    D = orig_shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    E = params["router"].shape[-1]
    C = (capacity if capacity is not None
         else compute_capacity(T, E, top_k, capacity_factor))
    mask_t = (None if token_mask is None
              else token_mask.reshape(-1))

    logits = xt.astype(jnp.float32) @ params["router"]

    if (dispatch_mode == "dropless" and mesh is not None
            and ep_axis in mesh.shape):
        # Routing (top-k) happens per token shard inside the
        # hierarchical path's shard_map (lax.top_k's TopK custom call
        # is not GSPMD-partitioned — see _dropless_ffn_ep).  The aux
        # loss needs only the FIRST choice, which argmax (a plain
        # partitionable reduce) computes identically (both break ties
        # toward the lowest index).
        probs = jax.nn.softmax(logits, axis=-1)
        first = jnp.argmax(probs, axis=-1).astype(jnp.int32)[:, None]
        aux = load_balance_loss(probs, first, E, token_mask=mask_t)
        y = _dropless_ffn_ep(xt, params, logits, top_k, E,
                             mesh, ep_axis, capacity_factor,
                             token_mask=mask_t, capacity=capacity,
                             token_axes=token_axes)
        return y.reshape(orig_shape), aux

    gates, expert_idx, probs = top_k_routing(logits, top_k)
    aux = load_balance_loss(probs, expert_idx, E, token_mask=mask_t)

    if dispatch_mode == "dropless":
        y = _dropless_ffn(xt, params, gates, expert_idx, E,
                          token_mask=mask_t)
        return y.reshape(orig_shape), aux

    if dispatch_mode == "sparse":
        slot, tok, keep, order = sparse_slots(expert_idx, E, C,
                                              token_mask=mask_t)
        g_sorted = gates.T.reshape(-1)[order]
        xe = jnp.zeros((E * C, D), x.dtype).at[slot].set(
            xt[tok], mode="drop").reshape(E, C, D)
    else:
        dispatch, combine = make_dispatch(gates, expert_idx, E, C,
                                          token_mask=mask_t)
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    if mesh is not None and ep_axis in mesh.shape:
        sh = NamedSharding(mesh, P(ep_axis, None, None))
        xe = jax.lax.with_sharding_constraint(xe, sh)
    h = (jax.nn.silu(_expert_linear(xe, params["w_gate"], "ecd,edf->ecf"))
         * _expert_linear(xe, params["w_up"], "ecd,edf->ecf"))
    ye = _expert_linear(h, params["w_down"], "ecf,efd->ecd")
    if mesh is not None and ep_axis in mesh.shape:
        ye = jax.lax.with_sharding_constraint(ye, sh)
    if dispatch_mode == "sparse":
        w = jnp.where(keep, g_sorted, 0.0).astype(x.dtype)
        # mode="fill": dropped entries (slot == E*C) read zeros —
        # symmetric with the scatter's mode="drop", not reliant on the
        # gate weight alone to cancel them.
        rows = jnp.take(ye.reshape(E * C, D), slot, axis=0,
                        mode="fill", fill_value=0)
        y = jnp.zeros((T, D), x.dtype).at[tok].add(rows * w[:, None])
    else:
        y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye)
    return y.reshape(orig_shape), aux


def _swiglu(x, p, linear=None):
    """One SwiGLU feed-forward ``{w_gate, w_up, w_down}``:
    ``W_down (silu(W_gate x) * W_up x)``.  ``linear(x, w)`` is the
    matmul: a plain one, or grouped over expert segments."""
    linear = linear or _qlinear
    return linear(jax.nn.silu(linear(x, p["w_gate"]))
                  * linear(x, p["w_up"]), p["w_down"])


def _relu2(x, p, linear=None):
    """One squared-ReLU feed-forward ``{w_up, w_down}``, two matrices
    and no gate: ``W_down relu(W_up x)^2``.  ``w_up`` may carry more
    columns than ``w_down`` has rows (a width that is not whole
    128-lane tiles, stored padded: a grouped matmul takes its operand in
    the row-major layout, and XLA lays a parameter whose minor axis is
    not whole tiles out otherwise and copies it for every call); the
    surplus is dropped."""
    linear = linear or _qlinear
    h = linear(x, p["w_up"])[..., :_rows(p["w_down"])]
    return linear(jnp.square(jax.nn.relu(h)), p["w_down"])


def _rows(w) -> int:
    from ..models.transformer import is_quantized
    return (w["q8"] if is_quantized(w) else w).shape[-2]


def _qlinear(x, w):
    from ..models.transformer import qlinear
    return qlinear(x, w)


# The forms an expert (routed or shared) takes: name -> f(x, params,
# linear).
EXPERT_FORMS = {"swiglu": _swiglu, "relu2": _relu2}


def shared_routed_ffn(x, params: dict, *, top_k: int,
                      routed_scale: float, token_mask=None,
                      held: tuple[int, int] | None = None,
                      expert: str = "swiglu"):
    """Fine-grained experts beside a shared one, as served from a chip
    that holds all of a layer's routed experts or a share of them:
    ``y = sum_{i held} g_i E_i(x) + E_shared(x)``.

    The gates are :func:`sigmoid_bias_routing`'s over *all* the experts
    the router knows (``params["router"]`` (D, E), ``params["bias"]``
    (E,)), whoever holds them.  ``held = (first, count)`` says which
    of them ``params`` carries on its leading expert axis: experts
    ``first .. first + count - 1``, all ``E`` by default.  A choice
    that falls outside is dropped here (it is another chip's to
    compute, and nothing stands in for that chip or for the exchange
    with it), so the shares ``(0, E/2)`` and ``(E/2, E/2)`` of one
    layer, the shared expert counted once, add up to the whole layer.
    ``expert`` names the form of every expert, routed and shared
    (:data:`EXPERT_FORMS`: ``"swiglu"``, three matrices; ``"relu2"``,
    two).  The routed experts run as dropless grouped-matmul segments
    (:func:`_dropless_ffn`: no capacity, so the result of a token
    depends on no other token and on no shape — bucketed, chunked and
    batched calls compute the same thing); every token passes through
    ``params["shared"]``.

    ``token_mask`` (bool, ``x.shape[:-1]``): masked tokens (pad
    positions, idle slots) route nowhere and touch no expert's
    weights; their output rows are the shared expert's alone and are
    never read.  x: (..., D) -> (same shape, :func:`routing_load` over
    the experts held)."""
    orig_shape = x.shape
    xt = x.reshape(-1, orig_shape[-1])
    E = params["router"].shape[-1]
    first, count = held or (0, E)
    mask_t = None if token_mask is None else token_mask.reshape(-1)
    logits = jnp.matmul(xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    gates, expert_idx = sigmoid_bias_routing(
        logits, params["bias"], top_k, routed_scale)
    if (first, count) != (0, E):
        # Counted from the first expert held; ``count`` is the
        # sentinel of a choice held elsewhere.
        local = expert_idx - first
        expert_idx = jnp.where((local >= 0) & (local < count), local,
                               count)
    with jax.named_scope("experts"):
        y = _dropless_ffn(xt, params, gates, expert_idx, count,
                          token_mask=mask_t, expert=expert)
    with jax.named_scope("shared_expert"):
        y = y + EXPERT_FORMS[expert](xt, params["shared"])
    return (y.reshape(orig_shape),
            routing_load(expert_idx, count, mask_t))


def softmax_routed_ffn(x, params: dict, *, top_k: int, token_mask=None):
    """Fine-grained SwiGLU experts chosen by a softmax router, no
    shared expert (the Qwen3-MoE layer): ``y = sum_{i in top_k} g_i
    E_i(x)`` with ``p = softmax(x W_r)`` over all of
    ``params["router"]``'s experts in float32 and the ``top_k`` largest
    ``p`` renormalised to sum 1 (:func:`top_k_routing`).  The experts
    run as dropless grouped-matmul segments exactly as
    :func:`shared_routed_ffn`'s do (:func:`_dropless_ffn`: a token's
    result depends on no other token and on no shape), and
    ``token_mask`` means what it means there.  x: (..., D) -> (same
    shape, :func:`routing_load`)."""
    orig_shape = x.shape
    xt = x.reshape(-1, orig_shape[-1])
    E = params["router"].shape[-1]
    mask_t = None if token_mask is None else token_mask.reshape(-1)
    logits = jnp.matmul(xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    gates, expert_idx, _ = top_k_routing(logits, top_k)
    with jax.named_scope("experts"):
        y = _dropless_ffn(xt, params, gates, expert_idx, E,
                          token_mask=mask_t)
    return y.reshape(orig_shape), routing_load(expert_idx, E, mask_t)
