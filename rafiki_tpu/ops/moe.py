"""Switch-style mixture-of-experts FFN with expert parallelism.

Beyond-parity op (SURVEY.md §2.9: expert parallelism absent upstream):
a top-1 (Switch) routed expert feed-forward expressed entirely as
einsums over a leading expert axis, so sharding that axis over the
``ep`` mesh axis (``rafiki_tpu.parallel.build_mesh(..., ep=n)``; expert
parameters get ``PartitionSpec("ep", ...)``) makes XLA partition the
expert compute across chips and insert the dispatch/combine
all-to-alls + psum itself — the annotate-and-let-XLA-partition recipe,
no hand-written collectives.

Routing is **group-local** (the GShard/Switch formulation): tokens are
processed in fixed-size groups, each with its own per-expert capacity
``ceil(capacity_factor · group / E)``. This bounds the dispatch one-hot
at O(capacity_factor · group²) per group — linear in total tokens —
where a single global dispatch would be quadratic in N. Tokens over
capacity are dropped (their FFN output is zero — the caller's residual
connection passes them through unchanged), keeping every shape static
for XLA.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _switch_group(x, mask, gate_w, w1, b1, w2, b2, offset, *,
                  capacity: int):
    """Route one token group. x (G, D); mask (G,) True = real token.

    ``offset`` is this rank's first expert id within the GLOBAL expert
    range: routing/dispatch always run over all ``gate_w.shape[1]``
    experts, but the expert FFN weights may be a LOCAL slice
    (``w1.shape[0]`` experts starting at ``offset`` — the shard_map
    expert-parallel path; the caller psums the partial outputs). The
    single-rank case is ``offset == 0`` with the full stack, where the
    slice below is the identity.
    """
    e = gate_w.shape[1]
    e_loc = w1.shape[0]

    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # (G, E)
    expert = jnp.argmax(probs, axis=-1)                  # (G,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)
    # Padding tokens neither claim capacity slots nor influence the
    # router statistics.
    onehot = onehot * mask[:, None]

    # Slot index of each token within its expert (first-come order);
    # tokens past the expert's capacity are dropped.
    position = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # (G, E)
    in_cap = (position >= 0) & (position < capacity)
    dispatch = onehot * in_cap                            # (G, E)
    slots = jax.nn.one_hot(jnp.clip(position, 0, capacity - 1).astype(
        jnp.int32), capacity, dtype=jnp.float32)          # (G, E, C)
    disp = slots * dispatch[..., None]                    # (G, E, C)
    # This rank's expert slice of the dispatch/combine tensors.
    disp = jax.lax.dynamic_slice_in_dim(disp, offset, e_loc, axis=1)

    xe = jnp.einsum("nec,nd->ecd", disp, x.astype(jnp.float32))
    xe = xe.astype(x.dtype)
    h = jnp.einsum("ecd,edf->ecf", xe, w1) + b1[:, None]
    h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None]  # (Eloc, C, D)
    combine = disp * gate[:, None, None]
    out = jnp.einsum("nec,ecd->nd", combine,
                     ye.astype(jnp.float32)).astype(x.dtype)

    # Switch aux loss over REAL tokens: E · Σ_e (token fraction)·(prob
    # mass fraction); ≈1 at near-uniform routing (not a hard bound).
    # Router statistics are global (identical on every expert rank).
    denom = jnp.maximum(mask.sum(), 1.0)
    frac_tokens = onehot.sum(axis=0) / denom
    frac_probs = (probs * mask[:, None]).sum(axis=0) / denom
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def switch_moe(x, gate_w, w1, b1, w2, b2, *,
               capacity_factor: float = 1.25,
               token_mask: Optional[jnp.ndarray] = None,
               group_size: int = 1024,
               expert_axis: Optional[str] = None,
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 routed expert FFN over flattened tokens.

    Args:
      x: (N, D) tokens (callers flatten batch × seq).
      gate_w: (D, E) router weights (compute runs in f32); E is always
        the GLOBAL expert count.
      w1, b1: (E, D, F), (E, F) first expert layer.
      w2, b2: (E, F, D), (E, D) second expert layer. With
        ``expert_axis`` set these are this rank's LOCAL slice
        (E/ep, ...).
      capacity_factor: per-expert slot head-room over the uniform share.
      token_mask: (N,) bool, True = real token. Padding tokens are
        never routed: they claim no capacity, contribute nothing to the
        router statistics, and get zero output.
      group_size: routing-group length (capacity is per group).
      expert_axis: when called INSIDE a shard_map (the pipeline-parallel
        path, where GSPMD cannot partition for us), the mesh axis name
        the expert stack is sharded over. Tokens are replicated across
        that axis; each rank routes globally, computes its local
        experts' outputs, and the partial results are psummed here.
        None (the default) is the single-rank / GSPMD path, where
        sharding ``w1..b2`` with ``PartitionSpec("ep", ...)`` under jit
        makes XLA insert the dispatch/combine collectives instead.

    Returns ``(out, aux)``: ``out`` (N, D) combined expert outputs
    (zero rows for dropped/masked tokens), ``aux`` the mean Switch
    load-balancing loss across groups (add a small multiple to the
    training loss).
    """
    n, d = x.shape
    e = gate_w.shape[1]
    if token_mask is None:
        token_mask = jnp.ones((n,), bool)
    g = min(group_size, n)
    n_groups = -(-n // g)
    pad = n_groups * g - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        token_mask = jnp.pad(token_mask, (0, pad))
    capacity = max(1, math.ceil(capacity_factor * g / e))

    if expert_axis is not None:
        offset = jax.lax.axis_index(expert_axis) * w1.shape[0]
    else:
        offset = jnp.int32(0)
    run = functools.partial(_switch_group, capacity=capacity)
    out, aux = jax.vmap(run, in_axes=(0, 0, None, None, None, None,
                                      None, None))(
        x.reshape(n_groups, g, d),
        token_mask.reshape(n_groups, g).astype(jnp.float32),
        gate_w, w1, b1, w2, b2, offset)
    if expert_axis is not None:
        out = jax.lax.psum(out, expert_axis)
    return out.reshape(n_groups * g, d)[:n], aux.mean()


# ---------------------------------------------------------------------------
# Dropless top-k experts, of which this rank holds a share
# ---------------------------------------------------------------------------
#
# The DeepSeek-V3 family's expert layer (sigmoid scores, bias-corrected
# top-k selection, normalised and scaled gates, no capacity) for a rank
# that is TOLD which experts it holds: selection and the gates'
# normalisation run over all E experts, the products only over the
# held ones, and what the absent experts would have added is left out.
# On one chip the layer runs without its exchange; nothing here stands
# in for the other ranks.


def sigmoid_topk_gates(x, gate_w, bias, *, k: int, scale: float,
                       eps: float = 1e-20):
    """Router of a dropless top-k layer, float32 throughout.

    ``x`` (N, D), ``gate_w`` (D, E), ``bias`` (E,). Scores are
    ``sigmoid(x @ gate_w)``; the ``k`` experts of a token are the top-k
    of ``scores + bias`` (the bias steers selection only and takes no
    gradient: ``noaux_tc``); its gates are the chosen experts' scores
    WITHOUT the bias, divided by their sum + ``eps`` (DeepSeek-V3's
    1e-20; the LFM2 family adds 1e-6) and multiplied by ``scale``.

    Returns ``(gates, chosen)``, both (N, E) and dense over ALL experts:
    ``gates`` is zero off a token's chosen experts. A rank slices its
    held columns out; no index is gathered (a per-element gather is the
    slow path on a TPU).
    """
    e = gate_w.shape[1]
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    chosen = (idx[..., None] == jnp.arange(e, dtype=idx.dtype)).any(-2)
    picked = jnp.where(chosen, scores, 0.0)
    gates = scale * picked / (picked.sum(-1, keepdims=True) + eps)
    return gates, chosen


#: Rows of one block of sorted assignments, each of one expert
#: (``benchmarks/metrics/moe_expert_roofline.py`` finds the blocks by it).
BLOCK = 128


def _held_layout(chosen, gates):
    """Sort the (token, held expert) assignments by expert, payloads
    carried by the sort itself. Returns the sorted token ids and gates
    (each padded by one block, so a block may be sliced at any start),
    the sorted flat assignment ids (to un-sort a cotangent), and per
    held expert its count, first sorted row and last block (cumulative).
    """
    n, h = chosen.shape
    a = n * h
    key = jnp.where(chosen, jnp.arange(h, dtype=jnp.int32), h).reshape(a)
    flat = jnp.arange(a, dtype=jnp.int32)
    _, flat_s, gate_s = jax.lax.sort(
        (key, flat, gates.astype(jnp.float32).reshape(a)), num_keys=1,
        is_stable=True)
    counts = chosen.sum(0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    blk_end = jnp.cumsum(-(-counts // BLOCK))
    return (jnp.pad(flat_s // h, (0, BLOCK)), jnp.pad(gate_s, (0, BLOCK)),
            flat_s, counts, starts, blk_end)


def _held_block(b, layout):
    """Block ``b`` of the sorted assignments: (held expert, token rows,
    gates with the rows past the expert's last zeroed, first row)."""
    tok_s, gate_s, _, counts, starts, blk_end = layout
    e = jnp.sum(b >= blk_end).astype(jnp.int32)
    e = jnp.minimum(e, counts.shape[0] - 1)
    j = b - (blk_end[e] - -(-counts[e] // BLOCK))
    start = starts[e] + j * BLOCK
    live = jnp.arange(BLOCK, dtype=jnp.int32) < counts[e] - j * BLOCK
    rows = jax.lax.dynamic_slice(tok_s, (start,), (BLOCK,))
    gate = jnp.where(live, jax.lax.dynamic_slice(gate_s, (start,),
                                                 (BLOCK,)), 0.0)
    return e, rows, gate, live, start


def _expert_hidden(xb, wg, wu):
    """SwiGLU's hidden of one block under one expert, float32 out."""
    hg = jnp.dot(xb, wg, preferred_element_type=jnp.float32)
    hu = jnp.dot(xb, wu, preferred_element_type=jnp.float32)
    return hg, hu, jax.nn.silu(hg) * hu


@jax.custom_vjp
def held_experts_swiglu(x, gates, chosen, w_gate, w_up, w_down):
    """Σ over the HELD experts a token chose of gate · SwiGLU_e(x).

    ``x`` (N, D) in the compute type (bfloat16); ``gates`` (N, H)
    float32 and ``chosen`` (N, H) bool, the held columns of
    :func:`sigmoid_topk_gates`; ``w_gate``, ``w_up`` (H, D, F) and
    ``w_down`` (H, F, D) float32 masters (the products run on operands
    of ``x``'s type, accumulated in float32). Returns (N, D) float32.
    No token is dropped.

    The assignments are sorted by expert and cut into blocks of
    ``BLOCK`` rows, each of one expert; a ``while_loop`` runs exactly
    the blocks that hold a routed row (gather the rows, three products,
    scatter-add with the gates), so the WORK follows the rows routed
    here and not tokens x experts held, while every shape stays static:
    the only arrays sized for the worst case (every token choosing
    every held expert) are the int32 / float32 sort buffers of N·H
    elements. The backward pass is a second such loop that recomputes
    each block's hidden (nothing is saved per block).
    """
    return _held_fwd(x, gates, chosen, w_gate, w_up, w_down)[0]


def _held_fwd(x, gates, chosen, w_gate, w_up, w_down):
    layout = _held_layout(chosen, gates)
    wg, wu, wd = (w.astype(x.dtype) for w in (w_gate, w_up, w_down))

    def body(carry):
        b, out = carry
        e, rows, gate, _, _ = _held_block(b, layout)
        _, _, hid = _expert_hidden(x[rows], wg[e], wu[e])
        y = jnp.dot(hid.astype(x.dtype), wd[e],
                    preferred_element_type=jnp.float32)
        return b + 1, out.at[rows].add(y * gate[:, None])

    n_blocks = layout[-1][-1]
    _, out = jax.lax.while_loop(
        lambda c: c[0] < n_blocks, body,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32)))
    return out, (x, gates, chosen, w_gate, w_up, w_down)


def _held_bwd(res, dout):
    x, gates, chosen, w_gate, w_up, w_down = res
    layout = _held_layout(chosen, gates)
    wg, wu, wd = (w.astype(x.dtype) for w in (w_gate, w_up, w_down))
    a = gates.size

    def body(carry):
        b, dx, dgate_s, dwg, dwu, dwd = carry
        e, rows, gate, live, start = _held_block(b, layout)
        xb = x[rows]
        hg, hu, hid = _expert_hidden(xb, wg[e], wu[e])
        dy = dout[rows]
        dhid0 = jnp.dot(dy.astype(x.dtype), wd[e].T,
                        preferred_element_type=jnp.float32)
        dgate_s = jax.lax.dynamic_update_slice(
            dgate_s, jnp.where(live, (dhid0 * hid).sum(-1), 0.0), (start,))
        dwd = dwd.at[e].add(jnp.dot(
            hid.astype(x.dtype).T, (dy * gate[:, None]).astype(x.dtype),
            preferred_element_type=jnp.float32))
        dhid = dhid0 * gate[:, None]
        sig = jax.nn.sigmoid(hg)
        dhg = (dhid * hu * sig * (1.0 + hg * (1.0 - sig))).astype(x.dtype)
        dhu = (dhid * hg * sig).astype(x.dtype)
        dwg = dwg.at[e].add(jnp.dot(xb.T, dhg,
                                    preferred_element_type=jnp.float32))
        dwu = dwu.at[e].add(jnp.dot(xb.T, dhu,
                                    preferred_element_type=jnp.float32))
        dxb = jnp.dot(dhg, wg[e].T, preferred_element_type=jnp.float32) \
            + jnp.dot(dhu, wu[e].T, preferred_element_type=jnp.float32)
        return b + 1, dx.at[rows].add(dxb), dgate_s, dwg, dwu, dwd

    n_blocks = layout[-1][-1]
    _, dx, dgate_s, dwg, dwu, dwd = jax.lax.while_loop(
        lambda c: c[0] < n_blocks, body,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32),
         jnp.zeros((a + BLOCK,), jnp.float32),
         jnp.zeros(w_gate.shape, jnp.float32),
         jnp.zeros(w_up.shape, jnp.float32),
         jnp.zeros(w_down.shape, jnp.float32)))
    # Back to (token, held expert) order by sorting on the flat ids the
    # forward sort carried along.
    _, dgates = jax.lax.sort((layout[2], dgate_s[:a]), num_keys=1)
    return (dx.astype(x.dtype), dgates.reshape(gates.shape), None,
            dwg, dwu, dwd)


held_experts_swiglu.defvjp(_held_fwd, _held_bwd)
