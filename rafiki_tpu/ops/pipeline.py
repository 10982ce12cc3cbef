"""Pipeline parallelism: a GPipe microbatch schedule over a ``pp`` axis.

Beyond-parity op (SURVEY.md §2.9: pipeline parallelism absent
upstream): stage ``s`` of the mesh's ``pp`` axis holds the parameters
of its layer span (stacked pytree, leading axis sharded over ``pp``);
microbatches stream through the stages with ONE ``lax.ppermute`` per
schedule tick inside a ``lax.scan`` — the whole pipeline is a single
XLA program, so the compiler overlaps each tick's stage compute with
the activation hop, and it is differentiable end-to-end (AD through
``scan``+``ppermute`` yields the reverse schedule automatically).

Schedule: plain GPipe over ``M`` microbatches and ``S`` stages —
``M + S - 1`` ticks with a pipeline bubble of ``(S-1)/(M+S-1)``; pick
``M >= 4·S`` to amortise. Every stage runs every tick (XLA needs static
shapes); out-of-window ticks compute on garbage and their results are
masked out, costing bubble FLOPs but no correctness.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..parallel.mesh import PP_AXIS


def pipeline_apply(stage_fn: Callable[..., jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray, *,
                   axis_name: str = PP_AXIS,
                   axis_size: int,
                   stage_takes_tick: bool = False) -> jnp.ndarray:
    """Run ``x`` through ``axis_size`` pipeline stages inside shard_map.

    Args:
      stage_fn: ``(params_slice, mb) -> mb`` — one stage's computation.
        ``mb`` may be a single array or a PYTREE of arrays (e.g.
        ``(activations, kv_mask)``); the stage must return the SAME
        tree structure with the same shapes (equal layer spans), since
        its output is the next stage's input.
      stage_params: THIS stage's parameter pytree (the caller shard_maps
        a stacked pytree with ``P("pp", ...)`` so each device receives
        its own slice with the leading stage axis already squeezed).
      x: microbatched input — an array or pytree whose leaves are
        (M, mb, ...), replicated across ``pp``.
      stage_takes_tick: when True, ``stage_fn`` is called as
        ``stage_fn(params_slice, mb, t)`` with the schedule tick index
        ``t`` (int32 tracer) — the ingredient stochastic stages need to
        fold a per-tick RNG key (dropout inside the pipeline: each
        (tick, stage) pair must draw an independent mask, and the tick
        index is exactly what distinguishes the microbatch a stage is
        working on).

    Returns outputs matching ``x``'s tree structure, leaves (M, mb,
    ...) (replicated across ``pp``; the last stage's results are
    broadcast back so every stage returns the same value — convenient
    for loss computation under ``out_specs=P()``). Bool leaves ride
    through a numeric cast for the collection scatter.
    """
    s = axis_size
    leaves = jax.tree_util.tree_leaves(x)
    m = leaves[0].shape[0]
    stage = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % s) for i in range(s)]
    tmap = jax.tree_util.tree_map

    def tick(carry, t):
        state = carry  # activation arriving from the previous stage
        # Stage 0 injects microbatch t (garbage once t >= m: masked by
        # the collection window below); later stages consume the hop.
        mb_in = tmap(lambda xs, st: jnp.where(
            stage == 0, xs[jnp.clip(t, 0, m - 1)], st), x, state)
        out = stage_fn(stage_params, mb_in, t) if stage_takes_tick \
            else stage_fn(stage_params, mb_in)
        # The last stage's tick-t output is microbatch t - (s - 1);
        # collect it only inside the valid window.
        idx = t - (s - 1)
        collect = (stage == s - 1) & (idx >= 0) & (idx < m)
        state_next = tmap(
            lambda o: jax.lax.ppermute(o, axis_name, perm), out)
        return state_next, (jnp.where(collect, 1.0, 0.0), idx, out)

    init = tmap(lambda xs: jnp.zeros_like(xs[0]), x)
    _, (collect, idxs, outs) = jax.lax.scan(
        tick, init, jnp.arange(m + s - 1, dtype=jnp.int32))

    # Scatter collected ticks into microbatch order. Only the last
    # stage has real data; psum broadcasts it to every stage (each
    # other stage contributes zeros).
    idx_safe = jnp.clip(idxs, 0, m - 1)

    def scatter(xs, o):
        w = collect.reshape(-1, *([1] * (o.ndim - 1)))
        dt = o.dtype
        if dt == jnp.bool_:  # scatter-add needs a numeric dtype
            o = o.astype(jnp.int8)
        z = jnp.zeros((m, *o.shape[1:]), o.dtype)
        g = jax.lax.psum(z.at[idx_safe].add(o * w.astype(o.dtype)),
                         axis_name)
        return g.astype(dt) if dt == jnp.bool_ else g

    return tmap(scatter, x, outs)


def pipelined(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
              mesh, *, n_microbatches: int):
    """Wrap ``stage_fn`` into a full-batch pipelined apply on ``mesh``.

    Returns ``apply(stacked_params, batch) -> batch`` where
    ``stacked_params`` is a pytree whose leaves carry a leading stage
    axis of length ``mesh.shape["pp"]`` (place with
    ``PartitionSpec("pp", ...)``; ``rafiki_tpu.parallel.param_spec``
    does this for names containing ``stage``). The batch's leading axis
    must divide into ``n_microbatches``.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    s = mesh.shape[PP_AXIS]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(PP_AXIS), P()), out_specs=P(), check_vma=False)
    def run(stacked_params, batch):
        def unstack(a):
            # Each device must receive exactly ONE stage slice; a
            # larger local axis means the caller stacked more stages
            # than mesh pp — silently using a[0] would drop layers.
            if a.shape[0] != 1:
                raise ValueError(
                    f"stacked params have {a.shape[0] * s} stages for "
                    f"a pp={s} mesh; stack exactly pp stages (fold "
                    f"multiple layers into stage_fn instead)")
            return a[0]

        params = jax.tree_util.tree_map(unstack, stacked_params)
        b = batch.shape[0]
        mb = b // n_microbatches
        x = batch.reshape(n_microbatches, mb, *batch.shape[1:])
        out = pipeline_apply(stage_fn, params, x, axis_size=s)
        return out.reshape(b, *out.shape[2:])

    return run
