"""Gated short convolution: the sequence operator of the LFM2 family's
``conv`` layers (three of every four layers of LFM2-8B-A1B).

``v_t = Σ_j w_j ⊙ (b ⊙ u)_{t-(L-1)+j}`` for ``j = 0..L-1`` (depth-wise:
one L-tap filter a channel, causal, zeros before the sequence, no
bias), then ``c ⊙ v``: the input projection's three parts ``b, c, u``
gate the filter's input and its output. The projections on either side
are the caller's matmuls.

Written as L shifted multiply-adds in ``jax.numpy``: at L = 3 the
operator reads and writes a few (B, T, D) arrays and multiplies nothing
on the MXU, so XLA's fusions are the roofline already and ``jax.grad``
gives the backward (the same shifts the other way). A Pallas kernel is
owed only if a chip trace shows the operator over a few percent of a
step (``PERF.md`` §5, ``lfm2_conv_ms``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _shift(x, n: int):
    """``x`` (B, T, D) delayed by ``n`` positions, zeros shifted in."""
    if n == 0:
        return x
    return jnp.pad(x, ((0, 0), (n, 0), (0, 0)))[:, :x.shape[1]]


def gated_short_conv(b, c, u, w):
    """``c ⊙ causal_depthwise_conv(b ⊙ u; w)``.

    ``b``, ``c``, ``u`` (B, T, D) in the compute type (bfloat16);
    ``w`` (L, D), tap ``j`` weighing position ``t - (L-1) + j`` (the
    last row weighs the current position, as a ``conv1d`` with left
    padding L-1 reads its kernel). Products are accumulated in float32;
    returns (B, T, D) in ``u``'s type. Position t depends on positions
    ``t-L+1 .. t`` only.
    """
    taps = w.shape[0]
    with jax.named_scope("short_conv"):
        x = b.astype(jnp.float32) * u.astype(jnp.float32)
        wf = w.astype(u.dtype).astype(jnp.float32)
        acc = x * wf[taps - 1]
        for j in range(taps - 1):
            acc = acc + _shift(x, taps - 1 - j) * wf[j]
        return (c.astype(jnp.float32) * acc).astype(u.dtype)
