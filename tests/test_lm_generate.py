"""Paged-KV generation engine (models/lm_generate.py).

Two contracts pinned here:

- **Allocator**: refcounted single-page granularity — alloc/free,
  sharing, exhaustion, interleaved churn (no fragmentation possible),
  page 0 reserved.
- **Decode parity**: incremental decode through the paged cache must
  reproduce the full forward pass's next-token logits at EVERY step
  (tolerance-bounded — bf16 compute, flash-kernel vs gather-attention
  reduction orders differ) and the greedy token chain exactly.

Tiny shapes on the CPU mesh, untrained (device-init) params — parity
is a pure-math property, training would only slow the suite down.
"""

import numpy as np
import pytest

from rafiki_tpu.models import JaxTransformerLM
from rafiki_tpu.models.lm_generate import (LMGenerator, PagePool,
                                           PoolExhausted)

TINY = {"d_model": 256, "n_layers": 2, "seq_len": 256, "batch_size": 2,
        "learning_rate": 1e-3, "train_steps": 20, "vocab_size": 512,
        "quick_train": False}


# ---- PagePool ---------------------------------------------------------


def test_pool_alloc_free_roundtrip():
    pool = PagePool(8)
    assert pool.free_pages == 7  # page 0 reserved
    pages = [pool.alloc() for _ in range(7)]
    assert 0 not in pages and sorted(pages) == list(range(1, 8))
    assert pool.used_pages == 7
    for p in pages:
        pool.free(p)
    assert pool.free_pages == 7 and pool.used_pages == 0


def test_pool_exhaustion_and_recovery():
    pool = PagePool(4)
    got = [pool.alloc() for _ in range(3)]
    with pytest.raises(PoolExhausted):
        pool.alloc()
    pool.free(got[1])
    assert pool.alloc() == got[1]  # any free page serves any request


def test_pool_refcount_sharing():
    pool = PagePool(4)
    p = pool.alloc()
    pool.retain(p)
    assert pool.refcount(p) == 2
    pool.free(p)           # one holder left — page stays allocated
    assert pool.refcount(p) == 1 and pool.free_pages == 2
    pool.free(p)           # last holder — page recycled
    assert pool.refcount(p) == 0 and pool.free_pages == 3


def test_pool_interleaved_churn_no_fragmentation():
    """Single-page granularity: after ANY interleaving of allocs and
    frees, every free page is usable — the pool never strands
    capacity the way a contiguous allocator would."""
    pool = PagePool(16)
    held = [pool.alloc() for _ in range(15)]
    for p in held[::2]:    # free every other page (worst-case holes)
        pool.free(p)
    refill = [pool.alloc() for _ in range(8)]
    assert pool.free_pages == 0 and len(set(refill)) == 8
    with pytest.raises(PoolExhausted):
        pool.alloc()


def test_pool_guards_misuse():
    pool = PagePool(4)
    with pytest.raises(ValueError):
        pool.free(3)       # never allocated
    with pytest.raises(ValueError):
        pool.retain(2)
    with pytest.raises(ValueError):
        PagePool(1)        # page 0 alone is not a pool


# ---- engine -----------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    m = JaxTransformerLM(**JaxTransformerLM.validate_knobs(TINY))
    m._params = m._init_params()  # untrained: parity is about math
    yield m
    m.destroy()


@pytest.fixture(scope="module")
def gen(lm):
    """One shared engine: decode-program compile is the expensive part
    and the step cache keys on shape, so tests share a config."""
    g = lm.make_generator(page_size=4, n_pages=64, decode_batch=2,
                          max_new_cap=16, prefix_cache_entries=4)
    yield g
    g.close()


def _drain(gen, live):
    """Run decode steps until the given seq_ids all finish; returns
    {seq_id: [tokens...]} including the admit-time first token."""
    out = {}
    live = set(live)
    guard = 0
    while live:
        guard += 1
        assert guard < 200, "decode loop did not converge"
        results, evicted = gen.step()
        assert not evicted
        for sid, tok, fin in results:
            out.setdefault(sid, []).append(tok)
            if fin is not None and sid in live:
                live.remove(sid)
    return out


def test_decode_parity_with_full_forward(lm, gen):
    """The tentpole contract: at every step, the paged-KV decode's
    logits match a from-scratch forward over the whole prefix, and the
    greedy chain is exactly the full-forward argmax chain. Prompt
    length 11 is deliberately page-unaligned (page_size=4)."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 512, size=11).tolist()
    sid, first = gen.admit(prompt, max_new=8, temperature=0.0)

    import jax.numpy as jnp
    params = gen._params

    def full_logits(toks):
        ids = jnp.asarray(np.asarray(toks, np.int32)[None])
        return np.asarray(lm._forward(params, ids))[0, len(toks) - 1]

    ref = full_logits(prompt)
    np.testing.assert_allclose(gen.last_logits[sid], ref,
                               atol=0.08, rtol=0.05)
    assert first == int(np.argmax(ref))

    toks = list(prompt) + [first]
    done = False
    while not done:
        before = list(toks)
        results, evicted = gen.step()
        assert not evicted
        (rsid, tok, fin), = results
        assert rsid == sid
        ref = full_logits(before)
        np.testing.assert_allclose(gen.last_logits[sid], ref,
                                   atol=0.08, rtol=0.05)
        assert tok == int(np.argmax(ref)), \
            f"greedy divergence at position {len(before)}"
        toks.append(tok)
        done = fin is not None
    assert len(toks) == len(prompt) + 8  # max_new honored


def test_continuous_admission_mid_decode(lm, gen):
    """Per-step admission: a second prompt joins while the first is
    mid-decode, and BOTH finish with the same tokens they'd produce
    alone (lane packing must not leak across sequences)."""
    rng = np.random.default_rng(11)
    p1 = rng.integers(0, 512, size=9).tolist()
    p2 = rng.integers(0, 512, size=6).tolist()

    sid1, t1 = gen.admit(p1, max_new=6, temperature=0.0)
    solo1 = [t1] + _drain(gen, [sid1])[sid1]

    sid1, t1 = gen.admit(p1, max_new=6, temperature=0.0)
    r1, _ = gen.step()  # sid1 decodes alone for a step...
    pre = [tok for s, tok, _ in r1 if s == sid1]
    sid2, t2 = gen.admit(p2, max_new=3, temperature=0.0)
    mixed = _drain(gen, [sid1, sid2])
    assert [t1] + pre + mixed[sid1] == solo1
    # ...and the shorter request finished while sid1 was resident:
    # its last frame arrived no later than sid1's.
    assert len(mixed[sid2]) + 1 == 3  # max_new incl. the admit token


def test_prefix_cache_skips_prefill(lm, gen):
    """Same prompt twice: the second admission must skip prefill
    (digest hit), share the full pages by refcount, and still produce
    the identical greedy continuation."""
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, 512, size=11).tolist()  # 2 full + 1 partial page
    skipped0 = gen.prefill_skipped_total
    prefills0 = gen.prefills_total

    sid_a, ta = gen.admit(prompt, max_new=4, temperature=0.0)
    toks_a = [ta] + _drain(gen, [sid_a])[sid_a]
    assert gen.prefills_total == prefills0 + 1

    sid_b, tb = gen.admit(prompt, max_new=4, temperature=0.0)
    assert gen.prefill_skipped_total == skipped0 + 1
    assert gen.prefills_total == prefills0 + 1  # no second prefill
    # Cache + resident seq share the FULL prompt pages.
    seq = gen._seqs[sid_b]
    for page in seq.pages[:len(prompt) // gen.page_size]:
        assert gen.pool.refcount(page) >= 2
    toks_b = [tb] + _drain(gen, [sid_b])[sid_b]
    assert toks_a == toks_b


def test_eviction_under_pool_pressure(lm):
    """Pool sized so two growing sequences cannot both extend: the
    YOUNGEST is preempted with its full token trail (recompute-style
    restart state), the older one keeps decoding to completion."""
    m = JaxTransformerLM(**JaxTransformerLM.validate_knobs(TINY))
    m._params = m._init_params()
    g = m.make_generator(page_size=4, n_pages=6, decode_batch=2,
                         max_new_cap=16, prefix_cache_entries=0)
    try:
        rng = np.random.default_rng(17)
        p1 = rng.integers(0, 512, size=4).tolist()
        p2 = rng.integers(0, 512, size=4).tolist()
        sid1, _ = g.admit(p1, max_new=12, temperature=0.0)
        sid2, _ = g.admit(p2, max_new=12, temperature=0.0)
        assert g.pool.free_pages == 1  # 2 pages each, 5 usable
        evicted_all = []
        for _ in range(40):
            results, evicted = g.step()
            evicted_all.extend(evicted)
            if not g._seqs:
                break
        assert evicted_all, "pool pressure never triggered preemption"
        ev = evicted_all[0]
        assert ev["seq_id"] == sid2  # youngest goes first
        assert ev["tokens"][:4] == [int(t) for t in p2]
        assert ev["n_done"] >= 1 and ev["max_new"] == 12
        assert g.evictions_total >= 1
        assert sid1 not in g._seqs  # the survivor ran to completion
    finally:
        g.close()
        m.destroy()


def test_admission_gate_reclaims_prefix_cache(lm):
    """Live sequences outrank cached prefixes: when the pool is full
    of cache-held pages, can_admit spills the cache instead of
    refusing admission."""
    m = JaxTransformerLM(**JaxTransformerLM.validate_knobs(TINY))
    m._params = m._init_params()
    g = m.make_generator(page_size=4, n_pages=6, decode_batch=2,
                         max_new_cap=8, prefix_cache_entries=4)
    try:
        rng = np.random.default_rng(19)
        p1 = rng.integers(0, 512, size=6).tolist()
        sid1, t1 = g.admit(p1, max_new=2, temperature=0.0)
        _drain(g, [sid1])
        # Sequence finished; its pages persist ONLY via the cache.
        assert g.pool.used_pages > 0 and not g._seqs
        p2 = rng.integers(0, 512, size=12).tolist()  # needs 4 pages
        assert g.can_admit(len(p2))  # spilled the cache to say yes
        sid2, _ = g.admit(p2, max_new=2, temperature=0.0)
        assert sid2 in g._seqs
    finally:
        g.close()
        m.destroy()


def test_generator_close_returns_all_pages(lm, gen):
    """After every test above, close() must leave zero leaked pages —
    checked on a fresh engine to keep the shared fixture usable."""
    m = JaxTransformerLM(**JaxTransformerLM.validate_knobs(TINY))
    m._params = m._init_params()
    g = m.make_generator(page_size=4, n_pages=16, decode_batch=2,
                         max_new_cap=8)
    prompt = list(range(1, 8))
    g.admit(prompt, max_new=4, temperature=0.0)
    g.step()
    g.close()
    assert g.pool.used_pages == 0
    m.destroy()


def test_engine_lives_on_its_own_chip():
    """A replica's engine on chip 3 touches no other chip: pools born on
    the model's mesh, prompt ids / page indices shipped from the host —
    any device-to-device copy (staging through the default device)
    raises."""
    import jax

    from rafiki_tpu.parallel import ChipGroup

    ChipGroup(indices=(3,)).bind_to_thread()
    try:
        with jax.transfer_guard_device_to_device("disallow_explicit"):
            model = JaxTransformerLM(
                **JaxTransformerLM.validate_knobs(TINY))
            model._params = model._init_params()
            gen = model.make_generator(page_size=16, n_pages=32,
                                       decode_batch=2, max_new_cap=8)
            prompt = list(range(1, 20))  # partial tail page: copied
            _, first = gen.admit(prompt, max_new=3)
            gen.step()
            _, again = gen.admit(prompt, max_new=3)  # prefix-cache hit
            gen.step()
        assert first == again and gen.prefill_skipped_total == 1
        pools = (gen._k_pool, gen._v_pool, *jax.tree.leaves(gen._params))
        assert {d.id for a in pools for d in a.devices()} == {3}
        gen.close()
        model.destroy()
    finally:
        ChipGroup.unbind_thread()
