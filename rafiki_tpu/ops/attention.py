"""Attention ops: blockwise (online softmax), Pallas flash kernel, ring.

The reference platform has no long-context machinery (SURVEY.md §5
"Long-context / sequence parallelism: absent"), but this framework treats
long sequences and distributed execution as first-class: sequence models
in the zoo attend with these ops, and the ``sp`` mesh axis
(``rafiki_tpu.parallel.build_mesh``) shards sequences across chips.

Three tiers, one numerical scheme (the online-softmax merge):

- ``blockwise_attention`` — pure-XLA ``lax.scan`` over K/V blocks with a
  rematerialised per-block body: O(T·block) live memory instead of the
  O(T²) score matrix, differentiable, runs anywhere.
- ``flash_attention`` — Pallas TPU kernels for BOTH passes (MXU
  matmuls, f32 accumulators in VMEM scratch, one HBM pass over K/V):
  the forward saves the per-row log-sum-exp and the backward
  regenerates the softmax block-by-block in two kernels (dq; dk+dv)
  via ``jax.custom_vjp``. Runs in the Pallas interpreter on the CPU
  backend so tests run on the CPU mesh; on a TPU backend the kernel
  compiles or the call fails — there is no fallback between tiers.
- ``ring_attention`` — sequence parallelism over an ``sp`` mesh axis:
  each chip holds a sequence shard, K/V shards rotate around the ICI ring
  via ``lax.ppermute`` while the online-softmax accumulator absorbs one
  shard per step; compute and the next hop overlap inside one XLA program.
- ``ulysses_attention`` — the all-to-all schedule: one ``all_to_all``
  re-shards sequence-split inputs to head-split, full-T attention runs
  locally per head subset, a second ``all_to_all`` restores sequence
  sharding (needs ``heads % sp == 0``).

All take ``(batch, heads, seq, head_dim)`` arrays. ``naive_attention``
and ``flash_attention`` also take a ``v`` of another width than q and k
(latent attention: 192 / 128) and return v's width; ``flash_attention``
takes k and v with fewer heads than q, a divisor of q's (grouped-query
attention: query head i reads key-value head i // group).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DP_AXIS, SP_AXIS

# Large-negative instead of -inf: exp(NEG_INF - NEG_INF) must be finite
# for fully-masked rows (padding), where -inf would yield nan.
NEG_INF = -1e30


def naive_attention(q, k, v, *, causal: bool = False, kv_mask=None):
    """Reference O(T²) attention; the numerical ground truth for tests.

    ``kv_mask`` (B, Tkv) bool, True = real token: key-padding mask for
    variable-length batches (all tiers accept it).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        allowed = (jnp.arange(tq)[:, None] + (tk - tq)
                   >= jnp.arange(tk)[None, :])
        s = jnp.where(allowed, s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype),
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _attend_chunk(q, k, v, m, l, o, *, scale, q_ids, kv_ids, causal,
                  kv_mask=None):
    """Absorb one K/V chunk into the online-softmax state.

    q: (B,H,Tq,D); k,v: (B,H,C,D); m,l: f32 (B,H,Tq); o: f32 (B,H,Tq,D).
    ``q_ids`` (Tq,) / ``kv_ids`` (C,) are *global* token positions so the
    same body serves local blocks and rotated ring shards; a kv id of -1
    marks block padding. ``kv_mask`` (B, C) masks per-example padding.
    """
    s = jnp.einsum("bhqd,bhcd->bhqc", q, k,
                   preferred_element_type=jnp.float32) * scale
    valid = (kv_ids >= 0)[None, :]
    if causal:
        valid = valid & (q_ids[:, None] >= kv_ids[None, :])
    valid = valid[None, None]                       # (1, 1, Tq|1, C)
    if kv_mask is not None:
        valid = valid & kv_mask[:, None, None, :]   # (B, 1, Tq|1, C)
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqc,bhcd->bhqd", p, v.astype(p.dtype),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _finish(o, l):
    return o / jnp.maximum(l, 1e-30)[..., None]


def blockwise_attention(q, k, v, *, causal: bool = False,
                        block_kv: int = 256, kv_mask=None):
    """Memory-efficient attention: ``lax.scan`` over K/V blocks.

    The per-block body is ``jax.checkpoint``-ed, so the backward pass
    recomputes each block's scores instead of storing the O(T²) attention
    matrix — the standard flash-attention memory profile, expressed in
    XLA (scan + remat) rather than a hand-written kernel.
    """
    b, h, tq, d = q.shape
    tkv = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    block_kv = min(block_kv, tkv)
    n_blocks = -(-tkv // block_kv)
    pad = n_blocks * block_kv - tkv
    kv_ids = jnp.arange(tkv, dtype=jnp.int32)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_ids = jnp.concatenate(
            [kv_ids, jnp.full((pad,), -1, jnp.int32)])
        if kv_mask is not None:
            kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))
    q_ids = jnp.arange(tq, dtype=jnp.int32) + (tkv - tq)

    # (n_blocks, ...) leading axis for scan.
    kb = jnp.moveaxis(k.reshape(b, h, n_blocks, block_kv, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, n_blocks, block_kv, d), 2, 0)
    ib = kv_ids.reshape(n_blocks, block_kv)
    xs = (kb, vb, ib)
    if kv_mask is not None:
        xs = xs + (jnp.moveaxis(
            kv_mask.reshape(b, n_blocks, block_kv), 1, 0),)

    attend = jax.checkpoint(functools.partial(
        _attend_chunk, scale=scale, q_ids=q_ids, causal=causal))

    def body(carry, xs):
        m, l, o = carry
        k_blk, v_blk, ids = xs[:3]
        mask_blk = xs[3] if len(xs) > 3 else None
        m, l, o = attend(q, k_blk, v_blk, m, l, o, kv_ids=ids,
                         kv_mask=mask_blk)
        return (m, l, o), None

    init = (jnp.full((b, h, tq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, tq), jnp.float32),
            jnp.zeros((b, h, tq, d), jnp.float32))
    (m, l, o), _ = jax.lax.scan(body, init, xs)
    return _finish(o, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel
# ---------------------------------------------------------------------------


def _flash_kernel(*refs, scale, causal, block_q, block_kv, seq_q, seq_kv,
                  has_bias):
    """One (batch·head, q-block, kv-block) grid step.

    The kv dimension is the innermost ("arbitrary") grid axis, so VMEM
    scratch (m, l, acc) persists across it: init at j == 0, accumulate the
    online-softmax state each step, normalise and write out at the last j.
    m/l are stored lane-broadcast as (block_q, 128) to respect TPU tiling.
    ``has_bias`` adds a per-example (1, block_kv) additive score bias (the
    key-padding mask, 0 or NEG_INF).

    Besides the attention output, the kernel writes the per-row
    log-sum-exp (``lse = m + log l``, lane-8 broadcast) — the residual
    the Pallas backward kernels below need to regenerate the softmax
    without a second online pass.
    """
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        bias_ref = None
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal masking end-aligns q against kv (matching naive/blockwise):
    # q row r is global position r + seq_kv - seq_q. kv blocks strictly
    # above the shifted diagonal are all-masked — skip their compute.
    shift = seq_kv - seq_q
    needed = (j * block_kv <= (i + 1) * block_q - 1 + shift) \
        if causal else True

    @pl.when(needed)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        q_ids = i * block_q + shift + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_ids = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        valid = kv_ids < seq_kv
        if causal:
            valid = jnp.logical_and(valid, q_ids >= kv_ids)
        s = jnp.where(valid, s, NEG_INF)
        if bias_ref is not None:
            s = s + bias_ref[0]                     # (1, bk) broadcast

        m_prev = m_scr[:, :1]                       # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        acc = acc_scr[:]
        if acc.shape[1] != o_ref.shape[2]:  # v narrower than q
            acc = acc[:, :o_ref.shape[2]]
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l),
                                      lse_ref.shape[1:])



def _flash_blocking(q, k, v, bias, block_q, block_kv):
    """The ONE block-clamping computation the forward and backward
    kernels must agree on: the saved lse residual's layout is
    ``nq * block_q`` as computed HERE, so a divergent copy in the
    backward would misalign its BlockSpecs against the saved array.

    Two lane widths, each its own multiple of 128: ``dp`` for q, k, dq,
    dk and ``dvp`` for v, o, do, dv. The MXU is 128 lanes wide, so a
    v of 128 under a q of 192 halves the passes of v·doᵀ and pᵀ·do in
    the backward kernels; at equal widths ``dvp == dp``."""
    b, h, tq, d = q.shape
    tkv = k.shape[2]
    d_v = v.shape[3]
    block_q = min(block_q, max(tq, 8))
    block_kv = min(block_kv, max(tkv, 8))
    if tq > block_q and block_q % 128 != 0:
        # The backward kernels read the lse/delta residuals through
        # (1, 1, block_q) row blocks — block_q is their LANE dim, which
        # Mosaic requires to be 128-divisible unless a single block
        # spans the whole (padded) array. Round up (never past one
        # whole-q block) so jax.grad lowers for ANY requested block_q;
        # the forward shares this clamp, keeping the saved lse layout
        # (nq * block_q) consistent between the passes.
        block_q = min(-(-block_q // 128) * 128, -(-tq // 128) * 128)
    if bias is not None and tkv > block_kv and block_kv % 128 != 0:
        # The bias block's lane dim must be 128-divisible (TPU tiling)
        # unless a single block spans the whole (padded) kv length.
        block_kv = min(-(-block_kv // 128) * 128, -(-tkv // 128) * 128)
    nq, nk = -(-tq // block_q), -(-tkv // block_kv)
    dp = d + (-d % 128)
    dvp = d_v + (-d_v % 128)
    return block_q, block_kv, nq, nk, dp, dvp


def _pad_to_blocks(a, t_to, d_to):
    return jnp.pad(a, ((0, 0), (0, 0), (0, t_to - a.shape[2]),
                       (0, d_to - a.shape[3])))


def _pad_v(v, t_to, dp, dvp):
    """v as the kernels' operand: (batch·heads, t_to, lanes). A v
    narrower than q still rides in an array of q's ``dp`` lanes, zeros
    past its own. The forward kernel reads them all (below); the
    backward kernels' (1, block_kv, dvp) blocks read lane block 0 and
    the DMA moves no other. The benchmark's roofline readers know the
    three kernels by three leading operands of one shape
    (``benchmarks/metrics/mla_attn_fwd_roofline.py``)."""
    b, h = v.shape[:2]
    lanes = max(dp, dvp)
    return _pad_to_blocks(v, t_to, lanes).reshape(b * h, t_to, lanes)


def _kv_row(group: int):
    """Grid index batch·q-head -> row batch·kv-head of k and v: with
    ``h = hk · group``, ``(b·h + i) // group == b·hk + i // group``.
    At equal head counts the index passes through untouched, so the
    kernels' block maps, and their programs, are what they always were.
    A group's key-value block is read through the block map; k and v
    are never repeated in HBM."""
    if group == 1:
        return lambda bh: bh
    return lambda bh: jax.lax.div(bh, jnp.int32(group))


def _flash_forward(q, k, v, bias, causal, block_q, block_kv, interpret,
                   return_lse=False):
    b, h, tq, d = q.shape
    hk, tkv, d_v = k.shape[1], k.shape[2], v.shape[3]
    kv_row = _kv_row(h // hk)
    scale = 1.0 / math.sqrt(d)
    block_q, block_kv, nq, nk, dp, dvp = _flash_blocking(
        q, k, v, bias, block_q, block_kv)
    qp = _pad_to_blocks(q, nq * block_q, dp).reshape(
        b * h, nq * block_q, dp)
    kp = _pad_to_blocks(k, nk * block_kv, dp).reshape(
        b * hk, nk * block_kv, dp)
    vp = _pad_v(v, nk * block_kv, dp, dvp)
    # p·v runs over all of vp's lanes and only o is cut to dvp: with
    # v's block and acc at 128 lanes under a q of 256 the kernel has
    # less to do and takes a fifth LONGER on a v5e (12.5 ms against
    # 10.4 at 32 x 8192 x 8192, 1024 x 512 blocks; PERF.md, PR 31): its
    # time is the softmax's per-row vector work, not the MXU's, and the
    # compiler schedules the narrower step worse.
    lanes = vp.shape[2]

    in_specs = [
        pl.BlockSpec((1, block_q, dp), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_kv, dp),
                     lambda bh, i, j: (kv_row(bh), j, 0)),
        pl.BlockSpec((1, block_kv, lanes),
                     lambda bh, i, j: (kv_row(bh), j, 0)),
    ]
    inputs = [qp, kp, vp]
    if bias is not None:
        # (B, 1, Tkv) additive score bias, shared across heads: the index
        # map folds the batch·head grid index back to the example row.
        # The unit middle axis keeps the block's sublane dim equal to the
        # array's (TPU tiling requires it when it isn't 8-divisible).
        bp = jnp.pad(bias, ((0, 0), (0, nk * block_kv - tkv)))[:, None, :]

        def bias_index(bh, i, j):
            del i
            return jax.lax.div(bh, jnp.int32(h)), 0, j

        in_specs.append(pl.BlockSpec((1, 1, block_kv), bias_index))
        inputs.append(bp)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, seq_q=tq, seq_kv=tkv, has_bias=bias is not None)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dvp), lambda bh, i, j: (bh, i, 0)),
            # Row log-sum-exp, lane-8 broadcast (a full 128-lane copy
            # would 16x the residual bytes the train loop saves per
            # layer for the backward kernels).
            pl.BlockSpec((1, block_q, 8), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, nq * block_q, dvp), q.dtype),
            jax.ShapeDtypeStruct((b * h, nq * block_q, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "flash_fwd"},
    )(*inputs)
    out = out.reshape(b, h, nq * block_q, dvp)[:, :, :tq, :d_v]
    if return_lse:
        return out, lse
    return out


def _flash_dq_kernel(*refs, scale, causal, block_q, block_kv, seq_q,
                     seq_kv, has_bias):
    """dq for one (batch·head, q-block) — kv blocks stream innermost.

    Scores are computed TRANSPOSED (``st = k·qᵀ``, shape (bkv, bq)) so
    the per-q-row residuals (lse, delta) broadcast along the LANE axis
    as (1, bq) rows — a column layout would need an in-kernel
    transpose, which the TPU vector unit does not do cheaply. The
    kv-side padding mask enters as a lane-8 column (bkv, 1), matching
    the forward's m/l storage trick.

      pᵀ   = exp(st·scale − lse)           regenerated softmax
      dpᵀ  = v · doᵀ
      dsᵀ  = pᵀ ⊙ (dpᵀ − delta) · scale
      dq  += dsᵀᵀ · k    (contraction over the kv dim of both)
    """
    if has_bias:
        (k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, maskt_ref,
         dq_ref, dq_scr) = refs
    else:
        (k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        maskt_ref = None
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    shift = seq_kv - seq_q
    needed = (j * block_kv <= (i + 1) * block_q - 1 + shift) \
        if causal else True

    @pl.when(needed)
    def _():
        k = k_ref[0]
        st = jax.lax.dot_general(
            k, q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bkv, bq)
        kv_ids = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, block_q), 0)
        q_ids = i * block_q + shift + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, block_q), 1)
        valid = kv_ids < seq_kv
        if causal:
            valid = jnp.logical_and(valid, q_ids >= kv_ids)
        if maskt_ref is not None:
            valid = jnp.logical_and(valid, maskt_ref[0][:, :1] > 0.5)
        pt = jnp.where(valid, jnp.exp(st - lse_ref[0]), 0.0)
        dpt = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bkv, bq)
        dst = pt * (dpt - delta_ref[0]) * scale
        dq_scr[:] += jax.lax.dot_general(
            dst.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, dp)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(*refs, scale, causal, block_q, block_kv, seq_q,
                      seq_kv, has_bias, group=1, q_blocks=None):
    """dk and dv for one (batch·kv-head, kv-block) — q blocks stream
    innermost. Same transposed-score layout as ``_flash_dq_kernel``:

      dv += pᵀ · do
      dk += dsᵀ · q

    With ``group`` query heads to a key-value head the innermost axis
    runs the ``q_blocks`` blocks of each of the group's heads in turn,
    and dk, dv accumulate over all of them before they are written.
    """
    if has_bias:
        (k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, maskt_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        maskt_ref = None
    j, y = pl.program_id(1), pl.program_id(2)
    n_inner = pl.num_programs(2)
    # i: the q block within its head (all of y at equal head counts)
    i = y if group == 1 else jax.lax.rem(y, jnp.int32(q_blocks))

    @pl.when(y == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    shift = seq_kv - seq_q
    needed = (j * block_kv <= (i + 1) * block_q - 1 + shift) \
        if causal else True

    @pl.when(needed)
    def _():
        k = k_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bkv, bq)
        kv_ids = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, block_q), 0)
        q_ids = i * block_q + shift + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, block_q), 1)
        # Padded q rows carry zero lse/delta — exp(st − 0) is garbage
        # that would ACCUMULATE into dk/dv (unlike the forward, where
        # padded rows are simply sliced away), so they are masked here.
        valid = jnp.logical_and(kv_ids < seq_kv, q_ids - shift < seq_q)
        if causal:
            valid = jnp.logical_and(valid, q_ids >= kv_ids)
        if maskt_ref is not None:
            valid = jnp.logical_and(valid, maskt_ref[0][:, :1] > 0.5)
        pt = jnp.where(valid, jnp.exp(st - lse_ref[0]), 0.0)
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bkv, dvp)
        dpt = jax.lax.dot_general(
            v_ref[0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0]) * scale
        dk_scr[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bkv, dp)

    @pl.when(y == n_inner - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, bias, out, lse, g, causal, block_q,
                    block_kv, interpret):
    """Assemble dq/dk/dv from the two Pallas backward kernels."""
    b, h, tq, d = q.shape
    hk, tkv, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = h // hk
    kv_row = _kv_row(group)
    scale = 1.0 / math.sqrt(d)
    block_q, block_kv, nq, nk, dp, dvp = _flash_blocking(
        q, k, v, bias, block_q, block_kv)
    if d_v != d:
        # Found on the chip (PR 31): with no pad between them, the
        # compiler fuses delta's row sum into the product that makes
        # ``g`` and its last bits change. Behind the barrier dq, dk, dv
        # are bit for bit what v zero-padded to q's width gave. At
        # equal widths the program is left as it always was.
        out, g = jax.lax.optimization_barrier((out, g))
    qp = _pad_to_blocks(q, nq * block_q, dp).reshape(
        b * h, nq * block_q, dp)
    kp = _pad_to_blocks(k, nk * block_kv, dp).reshape(
        b * hk, nk * block_kv, dp)
    vp = _pad_v(v, nk * block_kv, dp, dvp)
    dop = _pad_to_blocks(g, nq * block_q, dvp).reshape(
        b * h, nq * block_q, dvp)
    # Per-q-row residuals as (bh, 1, T) ROW arrays — the kernels read
    # (1, 1, block_q) blocks (the bias trick: a unit middle axis keeps
    # the block's sublane dim equal to the array's) whose ref[0] is a
    # (1, block_q) row broadcasting along lanes against the transposed
    # (bkv, bq) scores with zero in-kernel relayout. The forward's
    # lane-8 lse collapses to one lane here.
    lse_row = lse[:, None, :, 0]
    # delta = rowsum(do ⊙ o), the softmax-jacobian correction term.
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = jnp.pad(delta.reshape(b * h, tq),
                    ((0, 0), (0, nq * block_q - tq)))[:, None, :]

    def by_x(rows, lanes):
        return pl.BlockSpec((1, rows, lanes), lambda bh, x, y: (bh, x, 0))

    def kv_by_y(rows, lanes):
        return pl.BlockSpec((1, rows, lanes),
                            lambda bh, x, y: (kv_row(bh), y, 0))

    # The dkv grid's innermost axis: a q block of a query head. At
    # equal head counts that is (bh, y), as it always was; in a group
    # y counts the nq blocks of each of the key-value head's query
    # heads in turn.
    if group == 1:
        def q_of(bh, y):
            return bh, y
    else:
        def q_of(bh, y):
            return (bh * group + jax.lax.div(y, jnp.int32(nq)),
                    jax.lax.rem(y, jnp.int32(nq)))

    def q_by_y(rows, lanes):
        return pl.BlockSpec((1, rows, lanes),
                            lambda bh, x, y: (*q_of(bh, y), 0))

    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, x, y: (bh, 0, x))

    def row_index_t(bh, x, y):
        row, block = q_of(bh, y)
        return row, 0, block

    row_spec_t = pl.BlockSpec((1, 1, block_q), row_index_t)

    # k, v, q, do: the dq grid is (bh, q, kv); the dkv grid (bh, kv, q)
    # swaps which grid axis feeds which block index.
    inputs = [kp, vp, qp, dop, lse_row, delta]
    in_specs = [kv_by_y(block_kv, dp), kv_by_y(block_kv, dvp),
                by_x(block_q, dp), by_x(block_q, dvp), row_spec, row_spec]
    in_specs_t = [by_x(block_kv, dp), by_x(block_kv, dvp),
                  q_by_y(block_q, dp), q_by_y(block_q, dvp), row_spec_t,
                  row_spec_t]
    if bias is not None:
        # kv-side padding mask as a lane-8 COLUMN (the transposed-score
        # layout needs it per kv row); 1.0 = keep.
        maskt = (bias > NEG_INF / 2).astype(jnp.float32)
        maskt = jnp.pad(maskt, ((0, 0), (0, nk * block_kv - tkv)))
        maskt = jnp.broadcast_to(
            jnp.repeat(maskt, h, axis=0)[..., None],
            (b * h, nk * block_kv, 8))
        inputs.append(maskt)
        in_specs.append(pl.BlockSpec((1, block_kv, 8),
                                     lambda bh, x, y: (bh, y, 0)))
        # (the mask's rows repeat per QUERY head: any of the group's)
        in_specs_t.append(pl.BlockSpec(
            (1, block_kv, 8), lambda bh, x, y: (
                bh if group == 1 else bh * group, x, 0)))

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_kv=block_kv, seq_q=tq, seq_kv=tkv,
                  has_bias=bias is not None)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **common),
        grid=(b * h, nq, nk),
        in_specs=in_specs,
        out_specs=by_x(block_q, dp),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * block_q, dp),
                                       q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "flash_dq"},
    )(*inputs)
    grouped = {} if group == 1 else {"group": group, "q_blocks": nq}
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **common, **grouped),
        grid=(b * hk, nk, group * nq),
        in_specs=in_specs_t,
        out_specs=[by_x(block_kv, dp), by_x(block_kv, dvp)],
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, nk * block_kv, dp), k.dtype),
            jax.ShapeDtypeStruct((b * hk, nk * block_kv, dvp), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_kv, dp), jnp.float32),
                        pltpu.VMEM((block_kv, dvp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "flash_dkv"},
    )(*inputs)
    dq = dq.reshape(b, h, nq * block_q, dp)[:, :, :tq, :d]
    dk = dk.reshape(b, hk, nk * block_kv, dp)[:, :, :tkv, :d]
    dv = dv.reshape(b, hk, nk * block_kv, dvp)[:, :, :tkv, :d_v]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, causal, block_q, block_kv, interpret):
    return _flash_forward(q, k, v, bias, causal, block_q, block_kv,
                          interpret)


def _flash_fwd(q, k, v, bias, causal, block_q, block_kv, interpret):
    out, lse = _flash_forward(q, k, v, bias, causal, block_q, block_kv,
                              interpret, return_lse=True)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(causal, block_q, block_kv, interpret, res, g):
    # Backward through two Pallas kernels (dq; dk+dv) fed by the saved
    # log-sum-exp — the O(T²) softmax is regenerated block-by-block on
    # the MXU, never stored. (Round 4 shipped this backward as the
    # blockwise XLA VJP; its scan-of-slices ran at ~5 TFLOP/s and
    # dominated flagship train steps — the r5 profiler trace that
    # motivated these kernels.)
    q, k, v, bias, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, bias, out, lse, g, causal,
                                 block_q, block_kv, interpret)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False, block_q: int = 1024,
                    block_kv: int = 1024, kv_mask=None,
                    interpret: Optional[bool] = None):
    """Pallas-kernel attention (TPU); the interpreter on the CPU.

    Default block sizes (1024/1024) come from a sweep on one v5e chip
    early in the project, not re-measured since 2026-07-31; what the
    kernels reach on today's code is ``PERF.md`` §5 (roofline shares)
    and §7 (block readings at 1 x 32 x 8192).
    ``kv_mask`` (B, Tkv) bool, True = real token. ``v`` may have
    another width than q and k: the result has v's, the scale is
    1/sqrt(q's). ``k`` and ``v`` may have fewer heads than q, a divisor
    of q's: query head i attends key-value head i // (h / hk), and dk,
    dv are summed over a group's query heads inside the dkv kernel.
    """
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"q's {q.shape[1]} heads are no multiple of k's {k.shape[1]} "
            f"(v has {v.shape[1]})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bias = None if kv_mask is None else jnp.where(
        kv_mask, 0.0, NEG_INF).astype(jnp.float32)
    return _flash(q, k, v, bias, causal, block_q, block_kv, interpret)


def batch_sharded_flash_attention(q, k, v, mesh, *, causal: bool = False,
                                  kv_mask=None,
                                  interpret: Optional[bool] = None,
                                  **blocks: int):
    """:func:`flash_attention` under a jit whose ``mesh`` spans chips.

    Mosaic kernels are never partitioned automatically: called bare
    under a multi-device jit the lowering refuses ("wrap the call in a
    shard_map"). So the kernel runs inside ``shard_map`` over the whole
    mesh, each device attending its own slice of the batch (``dp``
    axis); every other axis sees q/k/v replicated. A batch ``dp`` does
    not divide (a lone serving query on a chip group) is attended whole
    on every device. ``mesh=None`` or a one-device mesh is the bare
    call. ``blocks`` (``block_q``, ``block_kv``) pass through to the
    kernels; without them their defaults hold.
    """
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                               interpret=interpret, **blocks)
    spec = P(DP_AXIS if q.shape[0] % mesh.shape[DP_AXIS] == 0 else None)
    args = (q, k, v) if kv_mask is None else (q, k, v, kv_mask)

    def run(q_, k_, v_, mask_=None):
        return flash_attention(q_, k_, v_, causal=causal, kv_mask=mask_,
                               interpret=interpret, **blocks)

    return shard_map(run, mesh=mesh, in_specs=(spec,) * len(args),
                     out_specs=spec, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Ring attention (sequence parallelism over the sp mesh axis)
# ---------------------------------------------------------------------------


def ring_attention(q, k, v, *, axis_name: str = SP_AXIS,
                   causal: bool = False, axis_size: Optional[int] = None,
                   kv_mask=None):
    """Sequence-parallel attention inside ``shard_map``.

    ``q``/``k``/``v`` are the *local* sequence shards ``(B, H, T/n, D)``
    of a length-T sequence split over ``n = axis_size`` devices along
    ``axis_name``. K/V shards rotate one ICI neighbour per step
    (``lax.ppermute``); each step folds the visiting shard into the
    online-softmax state with global-position causal masking, so the
    result equals full-sequence attention exactly. After n steps K/V are
    back home, and XLA overlaps each hop with the current step's compute.
    """
    if axis_size is None:
        axis_size = jax.lax.psum(1, axis_name)
        if not isinstance(axis_size, int):
            axis_size = int(axis_size)  # concrete under shard_map trace
    n = axis_size
    b, h, t_local, d = q.shape
    scale = 1.0 / math.sqrt(d)
    my = jax.lax.axis_index(axis_name)
    q_ids = my * t_local + jnp.arange(t_local, dtype=jnp.int32)
    local_ids = jnp.arange(t_local, dtype=jnp.int32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    attend = jax.checkpoint(functools.partial(
        _attend_chunk, scale=scale, q_ids=q_ids, causal=causal))

    # The per-example padding mask shard rotates around the ring with its
    # K/V shard. A dummy (all-True) mask when absent keeps one scan body.
    has_mask = kv_mask is not None
    mask0 = kv_mask if has_mask else jnp.ones((b, t_local), bool)

    def body(carry, step):
        k_cur, v_cur, mask_cur, m, l, o = carry
        owner = jax.lax.rem(my - step + n, n)
        kv_ids = owner * t_local + local_ids
        m, l, o = attend(q, k_cur, v_cur, m, l, o, kv_ids=kv_ids,
                         kv_mask=mask_cur if has_mask else None)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm) \
            if has_mask else mask_cur
        return (k_nxt, v_nxt, mask_nxt, m, l, o), None

    init = (k, v, mask0,
            jnp.full((b, h, t_local), NEG_INF, jnp.float32),
            jnp.zeros((b, h, t_local), jnp.float32),
            jnp.zeros((b, h, t_local, d), jnp.float32))
    # Scan covers steps 0..n-2 (attend + rotate); the last visiting shard
    # is attended outside the scan so no wasted final ppermute is issued.
    (k_cur, v_cur, mask_cur, m, l, o), _ = jax.lax.scan(
        body, init, jnp.arange(n - 1, dtype=jnp.int32))
    owner = jax.lax.rem(my - (n - 1) + n, n)
    m, l, o = attend(q, k_cur, v_cur, m, l, o,
                     kv_ids=owner * t_local + local_ids,
                     kv_mask=mask_cur if has_mask else None)
    return _finish(o, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses attention (all-to-all sequence parallelism over the sp axis)
# ---------------------------------------------------------------------------


def ulysses_attention(q, k, v, *, axis_name: str = SP_AXIS,
                      causal: bool = False,
                      axis_size: Optional[int] = None, kv_mask=None,
                      interpret: Optional[bool] = None):
    """All-to-all sequence parallelism inside ``shard_map``.

    The complement to :func:`ring_attention` (the two standard
    context-parallel schedules): instead of rotating K/V shards n times
    around the ICI ring, ONE ``all_to_all`` re-shards the inputs from
    sequence-split ``(B, H, T/n, D)`` to head-split ``(B, H/n, T, D)``,
    each chip runs ordinary full-sequence attention over its head
    subset (the Pallas flash kernel on TPU), and a second ``all_to_all``
    restores sequence sharding. Two collectives total — cheaper than
    the ring's n hops when heads divide evenly and the full-T score
    working set fits one chip's attention tier; the ring remains the
    choice for extreme T (its K/V working set stays T/n per chip).

    Requires ``H % n == 0``. ``kv_mask`` is the local ``(B, T/n)``
    shard; it is all-gathered (tiny, bool) to mask the full sequence.
    """
    if axis_size is None:
        axis_size = jax.lax.psum(1, axis_name)
        if not isinstance(axis_size, int):
            axis_size = int(axis_size)
    n = axis_size
    b, h, t_local, d = q.shape
    if h % n != 0:
        raise ValueError(f"ulysses needs heads % sp == 0; got {h} % {n}")

    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    mask_full = None
    if kv_mask is not None:
        mask_full = jax.lax.all_gather(kv_mask, axis_name, axis=1,
                                       tiled=True)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret:
        # Pure-XLA tier: the Pallas interpreter inside shard_map on the
        # CPU mesh is needlessly slow for tests.
        out = blockwise_attention(qh, kh, vh, causal=causal,
                                  kv_mask=mask_full)
    else:
        out = flash_attention(qh, kh, vh, causal=causal,
                              kv_mask=mask_full, interpret=False)
    return jax.lax.all_to_all(out, axis_name, split_axis=2,
                              concat_axis=1, tiled=True)


def default_attention(mesh=None, *, causal: bool = False):
    """Backend-dispatched full-sequence attention: the Pallas flash
    kernel on TPU, the blockwise XLA formulation on the CPU. Returns a
    ``(q, k, v, kv_mask) -> out`` callable — the one place the backend
    branch lives for every zoo model.

    ``mesh`` is the mesh of the jit the callable will be traced under
    (see :func:`batch_sharded_flash_attention`); leave it ``None`` only
    for a one-device program or inside a ``shard_map`` that is already
    manual over the whole mesh."""
    if jax.default_backend() == "tpu":
        return lambda q, k, v, m: batch_sharded_flash_attention(
            q, k, v, mesh, causal=causal, kv_mask=m)
    return lambda q, k, v, m: blockwise_attention(
        q, k, v, causal=causal, kv_mask=m)


def sequence_sharded_attention(q, k, v, mesh, *, causal: bool = False,
                               batch_axis: Optional[str] = DP_AXIS,
                               kv_mask=None, mode: str = "ring"):
    """Convenience wrapper: shard q/k/v ``(B, H, T, D)`` with batch over
    ``dp`` and sequence over ``sp``, and run the chosen schedule under
    ``shard_map`` on ``mesh``. ``kv_mask`` (B, T) bool shards with k.

    ``mode``: ``"ring"`` (ppermute K/V rotation; T/n working set per
    chip) or ``"alltoall"`` (Ulysses head re-sharding; two collectives,
    needs heads % sp == 0).
    """
    sp = mesh.shape[SP_AXIS]
    spec = P(batch_axis, None, SP_AXIS, None)
    mask_spec = P(batch_axis, SP_AXIS)
    if mode not in ("ring", "alltoall"):
        raise ValueError(f"unknown sequence-parallel mode {mode!r}")
    inner = ring_attention if mode == "ring" else ulysses_attention

    if kv_mask is None:
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        def run(q_, k_, v_):
            return inner(q_, k_, v_, causal=causal, axis_size=sp)

        return run(q, k, v)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec, mask_spec),
                       out_specs=spec, check_vma=False)
    def run_masked(q_, k_, v_, mask_):
        return inner(q_, k_, v_, causal=causal, axis_size=sp,
                     kv_mask=mask_)

    return run_masked(q, k, v, kv_mask)
