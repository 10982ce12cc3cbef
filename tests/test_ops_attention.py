"""Attention ops: blockwise / flash (interpret) / ring vs the naive oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafiki_tpu.ops import (blockwise_attention, flash_attention,
                            naive_attention, ring_attention,
                            sequence_sharded_attention)
from rafiki_tpu.parallel import build_mesh


def _qkv(rng, b=2, h=2, t=64, d=32, dtype=np.float32, tkv=None, d_v=None):
    """q, k of ``d`` lanes and v of ``d_v`` (``d`` unless given)."""
    tkv = t if tkv is None else tkv
    q = rng.standard_normal((b, h, t, d)).astype(dtype)
    k = rng.standard_normal((b, h, tkv, d)).astype(dtype)
    v = rng.standard_normal((b, h, tkv, d_v or d)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_naive(rng, causal):
    q, k, v = _qkv(rng)
    out = blockwise_attention(q, k, v, causal=causal, block_kv=16)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_blockwise_ragged_kv_and_uneven_blocks(rng):
    # Tkv not divisible by block_kv exercises the -1 padded-id mask.
    q, k, v = _qkv(rng, t=24, tkv=50)
    out = blockwise_attention(q, k, v, block_kv=16)
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_grads_match_naive(rng, causal):
    q, k, v = _qkv(rng, b=1, h=1, t=32, d=16)

    def loss_block(q, k, v):
        return blockwise_attention(q, k, v, causal=causal,
                                   block_kv=8).sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=causal).sum()

    g1 = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_naive(rng, causal):
    q, k, v = _qkv(rng, t=48, d=32)  # t not a block multiple, d < 128
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_cross_attention_shapes(rng):
    q, k, v = _qkv(rng, t=16, tkv=40, d=8)
    out = flash_attention(q, k, v, block_q=8, block_kv=16)
    ref = naive_attention(q, k, v)
    assert out.shape == (2, 2, 16, 8)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("fn", [blockwise_attention, flash_attention])
def test_causal_cross_attention_end_aligned(rng, fn):
    # tq != tkv with causal: q positions end-align against kv (decoding
    # convention) — q token 0 of an 8-token query over a 24-token kv may
    # attend kv[0..16], not just kv[0].
    q, k, v = _qkv(rng, t=8, tkv=24, d=16)
    out = fn(q, k, v, causal=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_bf16(rng):
    q, k, v = _qkv(rng, dtype=np.float32)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    ref = naive_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_flash_grads_match_naive(rng):
    q, k, v = _qkv(rng, b=1, h=1, t=32, d=16)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=8,
                               block_kv=8).sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_blocking_rounds_block_q_for_backward(rng):
    """Mosaic requires the backward's lse/delta row blocks
    (1, 1, block_q) to have a 128-divisible lane dim whenever the q
    axis is actually blocked (nq > 1): jax.grad with block_q=32,
    T=256 once failed TPU lowering. _flash_blocking rounds block_q up
    (never past one whole-q block), for forward and backward
    identically; the real lowering of this shape is compiled in
    tests/test_chip_compile.py and run by chip_smoke.py."""
    from rafiki_tpu.ops.attention import _flash_blocking

    q = jnp.zeros((1, 1, 256, 64))
    k = jnp.zeros((1, 1, 256, 64))
    for req_bq in (8, 32, 96, 100, 128, 256):
        bq, _, nq, _, _, _ = _flash_blocking(q, k, k, None, req_bq, 64)
        assert nq == 1 or bq % 128 == 0, (req_bq, bq, nq)
        assert nq * bq >= 256
    # under one whole-q block the size is unconstrained
    q8 = jnp.zeros((1, 1, 48, 64))
    bq, _, nq, _, _, _ = _flash_blocking(q8, q8, q8, None, 64, 64)
    assert nq == 1 and bq == 48

    # numerics (fwd + bwd) survive the rounding, at the shape that failed
    q, k, v = _qkv(rng, b=1, h=2, t=256, d=32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=64)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    g1 = jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=32, block_kv=64).sum())(q)
    g2 = jax.grad(lambda q: naive_attention(
        q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


# (t, tkv, causal, kv_mask, flash_attention's blocks)
_NARROW_V_CASES = {
    "one_block": (40, 40, False, False, {}),
    "causal": (40, 40, True, False, {}),
    "masked": (40, 40, False, True, {}),
    # T not a block multiple, nq = 3 and nk = 3
    "blocked": (300, 300, True, False, {"block_q": 128, "block_kv": 128}),
    "blocked_masked_cross": (200, 330, True, True,
                             {"block_q": 128, "block_kv": 128}),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("widths", [(24, 16), (192, 128), (64, 192)],
                         ids=lambda w: f"{w[0]}/{w[1]}")
@pytest.mark.parametrize("case", list(_NARROW_V_CASES))
def test_flash_takes_v_at_its_own_width(rng, case, widths, dtype):
    """Latent attention's shapes (q, k 192 lanes, v 128) and a v wider
    than q: values and the gradients of q, k, v against the naive
    reference, which is two einsums and never knew one head_dim. The
    result has v's width; the scale is 1/sqrt(q's)."""
    t, tkv, causal, masked, blocks = _NARROW_V_CASES[case]
    d, d_v = widths
    q, k, v = _qkv(rng, b=1, t=t, d=d, dtype=dtype, tkv=tkv, d_v=d_v)
    mask = None
    if masked:
        mask = jnp.asarray(np.arange(tkv)[None, :] < tkv - 13)
    ct = jnp.asarray(rng.standard_normal((1, 2, t, d_v)), jnp.float32)

    def loss(fn, **kw):
        def f(q, k, v):
            out = fn(q, k, v, causal=causal, kv_mask=mask, **kw)
            return (out.astype(jnp.float32) * ct).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, out), got = loss(flash_attention, **blocks)(q, k, v)
    (_, ref), want = loss(naive_attention)(q, k, v)
    assert out.shape == (1, 2, t, d_v) and out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
    gtol = 1e-4 if dtype == jnp.float32 else 6e-2
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape and a.dtype == dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=gtol * max(1.0, np.abs(b).max()),
                                   rtol=gtol, err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_narrow_v_equals_the_zero_padded_path_bit_for_bit(rng, dtype):
    """What the narrow path stops computing are lanes of zeros: o, dq,
    dk and dv are the very bits of the path it replaces (v zero-padded
    to q's width, o sliced back), at a shape with nq = nk = 3, a
    key-padding mask and T not a block multiple."""
    q, k, v = _qkv(rng, b=1, t=300, d=192, dtype=dtype, d_v=128)
    mask = jnp.asarray(np.arange(300)[None, :] < 290)
    ct = jnp.asarray(rng.standard_normal((1, 2, 300, 128)), jnp.float32)
    blocks = {"block_q": 128, "block_kv": 128}

    def narrow(q, k, v):
        return flash_attention(q, k, v, causal=True, kv_mask=mask, **blocks)

    def padded(q, k, v):
        wide = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, 64)))
        return flash_attention(q, k, wide, causal=True, kv_mask=mask,
                               **blocks)[..., :128]

    def run(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            return (out.astype(jnp.float32) * ct).sum(), out
        (_, out), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    for name, a, b in zip(("o", "dq", "dk", "dv"), run(narrow),
                          run(padded)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), name


@pytest.mark.parametrize("d_v,v_lanes", [(192, 256), (128, 128)],
                         ids=["equal", "narrow_v"])
def test_flash_block_shapes_handed_to_pallas_call(monkeypatch, d_v,
                                                  v_lanes):
    """At equal widths every block, result and scratch of the three
    ``pallas_call``s is what it was before v had a width of its own
    (all 256 lanes for a 192-lane head); with v at 128, o, do, dv, dv's
    accumulator and the backward kernels' v blocks narrow, while the
    forward still multiplies all of v's padded lanes (faster on a v5e
    than the narrower step: ``_flash_forward``). v's ARRAY keeps q's
    padded lanes either way: the benchmark's roofline readers know the
    kernels by three leading operands of one shape."""
    from rafiki_tpu.ops import attention

    calls = {}
    real = attention.pl.pallas_call

    def spy(kernel, **kw):
        run = real(kernel, **kw)

        def wrapped(*operands):
            def blocks(specs):
                return [tuple(s.block_shape) for s in jax.tree.leaves(
                    specs, is_leaf=lambda x: hasattr(x, "block_shape"))]
            calls[kw["metadata"]["kernel"]] = {
                "operands": [o.shape for o in operands],
                "in": blocks(kw["in_specs"]),
                "out": blocks(kw["out_specs"]),
                "results": [o.shape for o in jax.tree.leaves(
                    kw["out_shape"])],
                "scratch": [s.shape for s in kw["scratch_shapes"]]}
            return run(*operands)
        return wrapped

    monkeypatch.setattr(attention.pl, "pallas_call", spy)
    q = jnp.ones((1, 2, 256, 192), jnp.bfloat16)
    v = jnp.ones((1, 2, 256, d_v), jnp.bfloat16)
    jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_kv=128
    ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, q, v)

    qk, w = (1, 128, 256), (1, 128, v_lanes)
    row, arr = (1, 1, 128), (2, 256, 256)
    assert calls["flash_fwd"] == {
        "operands": [arr] * 3, "in": [qk, qk, qk],
        "out": [w, (1, 128, 8)],
        "results": [(2, 256, v_lanes), (2, 256, 8)],
        "scratch": [(128, 128), (128, 128), (128, 256)]}
    assert calls["flash_dq"] == {
        "operands": [arr] * 3 + [(2, 256, v_lanes), (2, 1, 256),
                                 (2, 1, 256)],
        "in": [qk, w, qk, w, row, row], "out": [qk],
        "results": [arr], "scratch": [(128, 256)]}
    assert calls["flash_dkv"] == dict(
        calls["flash_dq"], out=[qk, w],
        results=[arr, (2, 256, v_lanes)],
        scratch=[(128, 256), (128, v_lanes)])


@pytest.mark.parametrize("d_v,barrier", [(192, False), (128, True)],
                         ids=["equal", "narrow_v"])
def test_flash_backward_holds_do_behind_a_barrier_only_for_a_narrow_v(
        d_v, barrier):
    """With v at its own width nothing stands between the product that
    makes ``do`` and delta's row sum, and the chip's compiler fuses the
    two: delta's last bits change (found on the chip, PR 31). The
    barrier keeps dq, dk, dv bit for bit what the zero-padded path
    gave; at equal widths the traced program has none, as before."""
    q = jnp.ones((1, 2, 256, 192), jnp.bfloat16)
    v = jnp.ones((1, 2, 256, d_v), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_kv=128
    ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, q, v))
    assert ("optimization_barrier" in text) == barrier


def _grouped(rng, h, hk, t=160, d=64, d_v=None, dtype=np.float32):
    q = jnp.asarray(rng.standard_normal((2, h, t, d)).astype(dtype))
    k = jnp.asarray(rng.standard_normal((2, hk, t, d)).astype(dtype))
    v = jnp.asarray(rng.standard_normal((2, hk, t, d_v or d)).astype(dtype))
    return q, k, v


@pytest.mark.parametrize("h,hk,masked", [(8, 2, False), (4, 4, False),
                                         (8, 2, True), (4, 1, False)],
                         ids=["4-a-group", "1-a-group", "4-a-group-masked",
                              "one-kv-head"])
def test_flash_grouped_query_heads_match_naive_with_kv_repeated(
        rng, h, hk, masked):
    """k and v with fewer heads than q: query head i reads key-value
    head i // (h / hk). Forward and all three gradients against
    ``naive_attention`` over K/V written out once a query head, at
    nq = nk = 2 with T no block multiple; dk and dv are sums over a
    group's query heads."""
    q, k, v = _grouped(rng, h, hk)
    mask = jnp.asarray(np.arange(160)[None, :] < np.array([[160], [131]])) \
        if masked else None
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    group = h // hk

    def flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, kv_mask=mask,
                                block_q=128, block_kv=128) * ct).sum()

    def naive(q, k, v):
        return (naive_attention(q, jnp.repeat(k, group, 1),
                                jnp.repeat(v, group, 1), causal=True,
                                kv_mask=mask) * ct).sum()

    got = jax.value_and_grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(naive, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=name)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_grouped_equals_the_repeated_equal_head_call_bit_for_bit(
        rng, dtype):
    """A group's key-value block is read through the block map, never
    written out: o and dq are the very bits of the equal-head call on
    K/V repeated in memory (the program this path had before it knew
    groups); dk and dv, which that call leaves to a sum over the
    repeats outside the kernel, agree to rounding."""
    q, k, v = _grouped(rng, 8, 2, t=300, dtype=dtype)
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    blocks = {"block_q": 128, "block_kv": 128}

    def run(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            return (out.astype(jnp.float32) * ct).sum(), out
        (_, out), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    grouped = run(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                  **blocks))
    repeated = run(lambda q, k, v: flash_attention(
        q, jnp.repeat(k, 4, 1), jnp.repeat(v, 4, 1), causal=True, **blocks))
    for name, a, b in zip(("o", "dq"), grouped, repeated):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), name
    tol = 1e-5 if dtype == np.float32 else 3e-2
    for a, b in zip(grouped[2:], repeated[2:]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol,
                                   rtol=tol)


@pytest.mark.parametrize("hk", [4, 1], ids=["equal", "grouped"])
def test_flash_grids_and_block_maps_handed_to_pallas_call(monkeypatch, hk):
    """At equal head counts the three ``pallas_call``s get the grids
    they always had and block maps that are bare pass-throughs of the
    grid indices (no operation in their jaxprs: the kernels' programs
    are the ones from before groups existed, and the dkv kernel is
    called without a group). With 4 query heads to a key-value head, k
    and v ride at their own head count, the k / v maps divide the grid
    index and the dkv grid runs the group's q blocks innermost."""
    from rafiki_tpu.ops import attention

    calls = {}
    real = attention.pl.pallas_call

    def spy(kernel, **kw):
        run = real(kernel, **kw)

        def wrapped(*operands):
            n_eqns = []
            for spec in kw["in_specs"]:
                jaxpr = jax.make_jaxpr(spec.index_map)(
                    *(jnp.int32(0),) * 3)
                n_eqns.append(len(jaxpr.eqns))
            calls[kw["metadata"]["kernel"]] = {
                "grid": kw["grid"], "map_eqns": n_eqns,
                "operands": [o.shape for o in operands[:3]],
                "kernel_kw": sorted(getattr(kernel, "keywords", {}))}
            return run(*operands)
        return wrapped

    monkeypatch.setattr(attention.pl, "pallas_call", spy)
    q = jnp.ones((2, 4, 256, 64), jnp.bfloat16)
    kv = jnp.ones((2, hk, 256, 64), jnp.bfloat16)
    jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_kv=128
    ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, kv, kv)
    big, small = (8, 256, 128), (2 * hk, 256, 128)
    common = ["block_kv", "block_q", "causal", "has_bias", "scale",
              "seq_kv", "seq_q"]
    if hk == 4:
        assert calls["flash_fwd"] == {
            "grid": (8, 2, 2), "map_eqns": [0, 0, 0],
            "operands": [big] * 3, "kernel_kw": common}
        assert calls["flash_dq"] == dict(calls["flash_fwd"],
                                         map_eqns=[0] * 6)
        assert calls["flash_dkv"] == calls["flash_dq"]
    else:
        assert calls["flash_fwd"]["grid"] == (8, 2, 2)
        assert calls["flash_fwd"]["operands"] == [big, small, small]
        assert calls["flash_fwd"]["map_eqns"] == [0, 1, 1]
        assert calls["flash_dq"]["grid"] == (8, 2, 2)
        assert calls["flash_dq"]["operands"] == [small, small, big]
        assert calls["flash_dq"]["map_eqns"][:4] == [1, 1, 0, 0]
        # 2 kv rows x 2 kv blocks x (4 heads of the group x 2 q blocks)
        assert calls["flash_dkv"]["grid"] == (2, 2, 8)
        assert calls["flash_dkv"]["kernel_kw"] == sorted(
            common + ["group", "q_blocks"])


def test_flash_refuses_head_counts_that_do_not_divide(rng):
    q, k, v = _grouped(rng, 6, 4, t=32)
    with pytest.raises(ValueError, match="no multiple"):
        flash_attention(q, k, v, causal=True)


@pytest.mark.slow
def test_kv_mask_all_tiers(rng):
    # Key-padding mask: ragged batch of real lengths; every tier must
    # equal the naive oracle with the same mask.
    q, k, v = _qkv(rng, b=3, h=2, t=32, d=16)
    lengths = np.array([32, 7, 19])
    mask = jnp.asarray(np.arange(32)[None, :] < lengths[:, None])
    ref = naive_attention(q, k, v, kv_mask=mask)
    out_b = blockwise_attention(q, k, v, block_kv=8, kv_mask=mask)
    out_f = flash_attention(q, k, v, block_q=8, block_kv=8, kv_mask=mask)
    np.testing.assert_allclose(out_b, ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out_f, ref, atol=1e-5, rtol=1e-5)

    mesh = build_mesh(jax.devices(), sp=8)
    out_r = sequence_sharded_attention(q, k, v, mesh, batch_axis=None,
                                       kv_mask=mask)
    np.testing.assert_allclose(out_r, ref, atol=1e-5, rtol=1e-5)

    # Gradients through the masked flash path (custom vjp w/ bias arg).
    g1 = jax.grad(lambda q: flash_attention(
        q, k, v, block_q=8, block_kv=8, kv_mask=mask).sum())(q)
    g2 = jax.grad(lambda q: naive_attention(
        q, k, v, kv_mask=mask).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(rng, causal):
    mesh = build_mesh(jax.devices(), sp=8)
    q, k, v = _qkv(rng, b=2, h=2, t=64, d=16)
    out = sequence_sharded_attention(q, k, v, mesh, causal=causal,
                                     batch_axis=None)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_ring_attention_grads(rng):
    mesh = build_mesh(jax.devices(), sp=4)
    q, k, v = _qkv(rng, b=1, h=1, t=32, d=8)

    def loss_ring(q, k, v):
        return sequence_sharded_attention(
            q, k, v, mesh, causal=True, batch_axis=None).sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_ring_attention_jit_under_mesh(rng):
    # The training path runs ring attention inside jit; make sure the
    # shard_map composition compiles and executes.
    mesh = build_mesh(jax.devices(), sp=8)
    q, k, v = _qkv(rng, b=2, h=1, t=128, d=16)

    @jax.jit
    def f(q, k, v):
        return sequence_sharded_attention(q, k, v, mesh, causal=True,
                                          batch_axis=None)

    out = f(q, k, v)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(rng, causal):
    """All-to-all (Ulysses) SP equals full attention exactly: heads are
    re-sharded, computed whole-sequence, and re-sharded back."""
    mesh = build_mesh(jax.devices(), sp=4)
    q, k, v = _qkv(rng, b=2, h=4, t=64, d=16)  # h % sp == 0
    out = sequence_sharded_attention(q, k, v, mesh, causal=causal,
                                     batch_axis=None, mode="alltoall")
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_ulysses_with_kv_mask_matches_ring(rng):
    mesh = build_mesh(jax.devices(), sp=4)
    q, k, v = _qkv(rng, b=2, h=4, t=32, d=8)
    mask = np.ones((2, 32), bool)
    mask[0, 20:] = False
    mask[1, 7:] = False
    mask = jnp.asarray(mask)
    out_u = sequence_sharded_attention(q, k, v, mesh, batch_axis=None,
                                       kv_mask=mask, mode="alltoall")
    out_r = sequence_sharded_attention(q, k, v, mesh, batch_axis=None,
                                       kv_mask=mask, mode="ring")
    ref = naive_attention(q, k, v, kv_mask=mask)
    np.testing.assert_allclose(out_u, ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out_u, out_r, atol=1e-5, rtol=1e-5)


def test_ulysses_grads_match_naive(rng):
    mesh = build_mesh(jax.devices(), sp=4)
    q, k, v = _qkv(rng, b=1, h=4, t=32, d=8)

    def loss_u(q, k, v):
        return sequence_sharded_attention(
            q, k, v, mesh, causal=True, batch_axis=None,
            mode="alltoall").sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_ulysses_rejects_indivisible_heads(rng):
    mesh = build_mesh(jax.devices(), sp=4)
    q, k, v = _qkv(rng, b=1, h=2, t=32, d=8)  # 2 % 4 != 0
    with pytest.raises(ValueError, match="heads"):
        sequence_sharded_attention(q, k, v, mesh, batch_axis=None,
                                   mode="alltoall")
