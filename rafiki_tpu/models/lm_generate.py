"""Paged-KV generative engine for :class:`JaxTransformerLM`.

``JaxTransformerLM.predict`` is one-shot: it recomputes the FULL
forward pass per call, so serving generation through it would cost
O(T²) recompute per emitted token and serialize every request behind
the longest sequence in its batch. This module is the token-level
split (Orca-style iteration scheduling over vLLM-style paged KV):

- **Page pool.** One preallocated device slab per projection —
  ``(L, n_pages·page_size, d)`` bf16 — plus a host-side allocator
  (:class:`PagePool`). Pages are an ALLOCATOR concept only: the device
  sees a flat token slab and every program indexes it by
  ``page·page_size + slot``, so alloc/free never move bytes. Physical
  page 0 is reserved scratch — padded/inactive lanes write there, so
  one fixed-shape program needs no masking on its stores.
- **Prefill program** (AOT, bucketed prompt lengths): the existing
  causal flash kernel over the whole prompt, K/V scattered into the
  sequence's pages, last-position logits out. Compiled once per
  bucket via the shared step cache.
- **Decode program** (ONE compiled shape): a single-token forward for
  a fixed batch width ``B`` reading K/V through a fixed-shape gather
  of ``P`` page slots per lane — any mix of sequence lengths runs the
  same executable, which is what makes per-step admission free.
  Sampling (greedy / gumbel-temperature, per-lane seed folded with
  position for batch-composition-independent draws) happens in-graph
  so resident tokens never leave the device between steps.
- **Prefix reuse.** Prompt pages are read-only after prefill (decode
  appends into LATER slots), so sequences sharing a prompt share its
  full pages by refcount; only a partially-filled tail page is copied
  (one on-device page copy). Keyed by the same content-address digest
  the r12 edge cache uses (``predictor.edge_cache.query_key``), so a
  shared system prompt skips prefill entirely.

The engine is single-threaded by contract: the worker's decode
scheduler (``worker/decode_scheduler.py``) is the only caller, from
its own loop thread. Nothing here touches metrics or the bus — the
scheduler layers those on.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..model.jax_model import (_step_cache_get, _step_cache_put,
                               step_cache_key)
from ..parallel import replicated
from ..predictor.edge_cache import query_key
from .transformer import _sinusoidal

NEG_INF = -1e30

#: Prompt-length buckets: each distinct bucket is one prefill compile,
#: so the ladder is geometric (the r16 megabatch lesson — a handful of
#: executables cover every shape).
PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)


class PoolExhausted(RuntimeError):
    """No free page and nothing evictable — the admission gate."""


class PagePool:
    """Host-side refcounted page allocator over the device slab.

    Page 0 is reserved scratch (never handed out): fixed-shape
    programs direct padded/inactive writes there. ``retain`` is the
    prefix-sharing hook — a page is recycled only when its LAST
    holder frees it, so shared prompt pages survive any one
    sequence's exit. Single-page granularity means external
    fragmentation cannot exist: any free page serves any request.
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, 0, -1))  # pop() -> low first
        self._ref: Dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    def alloc(self) -> int:
        """One free page (refcount 1). Raises :class:`PoolExhausted`
        when none is left — callers gate admission or evict first."""
        if not self._free:
            raise PoolExhausted("page pool exhausted")
        page = self._free.pop()
        self._ref[page] = 1
        return page

    def retain(self, page: int) -> None:
        if page not in self._ref:
            raise ValueError(f"retain of unallocated page {page}")
        self._ref[page] += 1

    def free(self, page: int) -> None:
        n = self._ref.get(page)
        if n is None:
            raise ValueError(f"free of unallocated page {page}")
        if n == 1:
            del self._ref[page]
            self._free.append(page)
        else:
            self._ref[page] = n - 1

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)


class _Seq:
    """One resident sequence's host-side state."""

    __slots__ = ("seq_id", "lane", "pages", "length", "prompt_len",
                 "last_token", "n_new", "max_new", "temperature",
                 "seed", "eos", "order", "tokens")

    def __init__(self, seq_id, lane, pages, length, prompt_len,
                 last_token, max_new, temperature, seed, eos, order,
                 tokens):
        self.seq_id = seq_id
        self.lane = lane              # decode-batch row
        self.pages = pages            # physical pages, logical order
        self.length = length          # tokens whose K/V are in the slab
        self.prompt_len = prompt_len
        self.last_token = last_token  # next decode input
        self.n_new = 1                # generated count (incl. last_token)
        self.max_new = max_new
        self.temperature = temperature
        self.seed = seed
        self.eos = eos
        self.order = order            # admission order (eviction picks max)
        self.tokens = tokens          # prompt + generated (for preemption)


def prefix_digest(tokens) -> str:
    """Content address of a token prefix — the same digest family the
    r12 edge cache uses, applied to the token ids themselves."""
    return query_key(list(int(t) for t in tokens))


class LMGenerator:
    """Continuous-batching generation engine over one trained
    :class:`JaxTransformerLM`.

    Fixed shapes: ``decode_batch`` lanes × ``pages_per_seq`` page
    slots; one compiled decode program serves any mix of lengths.
    ``admit`` prefs a prompt into freshly-allocated pages (or reuses
    a cached prefix) and returns the first sampled token;
    ``step`` advances every resident sequence one token. ``step``
    auto-evicts the YOUNGEST resident sequence when a mid-step page
    allocation fails and reports it, so the scheduler can re-queue
    the preempted request (its tokens so far become the new prompt).
    """

    def __init__(self, model, *, page_size: int = 16,
                 n_pages: int = 128, decode_batch: int = 4,
                 max_new_cap: int = 256,
                 prefix_cache_entries: int = 16,
                 stager: Optional[Callable[[np.ndarray], Any]] = None):
        if page_size < 1 or decode_batch < 1:
            raise ValueError("page_size and decode_batch must be >= 1")
        self._model = model
        self._dims = model._dims()
        self.page_size = page_size
        self.n_pages = n_pages
        self.decode_batch = decode_batch
        self.max_new_cap = max_new_cap
        # Per-lane page-slot budget: enough for a full-length prompt
        # plus the generation cap, rounded up to pages.
        self.pages_per_seq = max(
            1, -(-(self._dims["t"] + max_new_cap) // page_size))
        self.max_tokens = self.pages_per_seq * page_size
        self.pool = PagePool(n_pages)
        self._rep = replicated(model.mesh)
        self._stager = stager or (
            lambda ids: jax.device_put(ids, self._rep))
        self._params = jax.device_put(model._params, self._rep)
        s = self._dims
        slab = (s["layers"], n_pages * page_size, s["d"])
        # Born on the mesh (a jitted fill with out_shardings): an eager
        # jnp.zeros is made on the default device and copied over.
        self._k_pool, self._v_pool = jax.jit(
            lambda: (jnp.zeros(slab, jnp.bfloat16),
                     jnp.zeros(slab, jnp.bfloat16)),
            out_shardings=self._rep)()
        self._seqs: Dict[Any, _Seq] = {}
        self._lanes: List[Optional[Any]] = [None] * decode_batch
        self._order = 0
        #: digest -> (pages, n_full, prompt_len, first_logits np)
        self._prefix: "Dict[str, Tuple[List[int], int, int, np.ndarray]]" = {}
        self._prefix_lru: List[str] = []
        self._prefix_cap = max(0, prefix_cache_entries)
        # Counters (host ints; the scheduler exports the interesting
        # ones through the gated observe.lm family).
        self.prefills_total = 0
        self.prefill_skipped_total = 0
        self.decode_steps_total = 0
        self.tokens_total = 0
        self.evictions_total = 0
        self.last_logits: Dict[Any, np.ndarray] = {}
        # AOT: the decode executable is the per-token hot path — pay
        # its compile at construction, not under the first request.
        self._decode = self._decode_fn()
        self._decode_aot = None
        self._warm_decode()

    # ---- compiled programs (shared step cache) ----

    def _decode_fn(self):
        m = self._model
        key = step_cache_key(m, "paged_decode", m.mesh,
                             self.decode_batch, self.pages_per_seq,
                             self.page_size, self.n_pages)
        cached = _step_cache_get(key)
        if cached is not None:
            return cached["fn"]
        fn = _build_decode(self._dims, self.page_size,
                           self.pages_per_seq, self.decode_batch)
        _step_cache_put(key, {"fn": fn})
        return fn

    def _warm_decode(self) -> None:
        """Lower+compile the decode program ahead of traffic (AOT).
        Donated-buffer warmup would consume the live pool, so compile
        against abstract shapes only."""
        B, P = self.decode_batch, self.pages_per_seq
        sd = jax.ShapeDtypeStruct

        def like(a):  # keep the live arrays' sharding in the AOT trace
            return sd(a.shape, a.dtype, sharding=a.sharding)

        rep = self._rep
        args = (jax.tree.map(like, self._params),
                like(self._k_pool), like(self._v_pool),
                sd((B,), jnp.int32, sharding=rep),
                sd((B, P), jnp.int32, sharding=rep),
                sd((B,), jnp.int32, sharding=rep),
                sd((B,), jnp.float32, sharding=rep),
                sd((B,), jnp.int32, sharding=rep))
        self._decode_aot = self._decode.lower(*args).compile()

    def _prefill_fn(self, bucket: int):
        m = self._model
        key = step_cache_key(m, "paged_prefill", m.mesh, bucket,
                             self.page_size, self.n_pages)
        cached = _step_cache_get(key)
        if cached is not None:
            return cached["fn"]
        fn = _build_prefill(self._dims, bucket, m._block)
        _step_cache_put(key, {"fn": fn})
        return fn

    def _copy_page_fn(self):
        m = self._model
        key = step_cache_key(m, "paged_copy", m.mesh, self.page_size,
                             self.n_pages)
        cached = _step_cache_get(key)
        if cached is not None:
            return cached["fn"]
        ps = self.page_size
        s = self._dims

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def copy_page(k_pool, v_pool, src, dst):
            ksrc = jax.lax.dynamic_slice(
                k_pool, (0, src * ps, 0), (s["layers"], ps, s["d"]))
            vsrc = jax.lax.dynamic_slice(
                v_pool, (0, src * ps, 0), (s["layers"], ps, s["d"]))
            k_pool = jax.lax.dynamic_update_slice(
                k_pool, ksrc, (0, dst * ps, 0))
            v_pool = jax.lax.dynamic_update_slice(
                v_pool, vsrc, (0, dst * ps, 0))
            return k_pool, v_pool

        _step_cache_put(key, {"fn": copy_page})
        return copy_page

    # ---- admission ----

    def resident(self) -> int:
        return len(self._seqs)

    def pool_used_ratio(self) -> float:
        usable = self.pool.n_pages - 1
        return self.pool.used_pages / usable if usable else 0.0

    def resident_tokens(self) -> int:
        """Tokens whose K/V is live in the paged cache right now."""
        return sum(s.length for s in self._seqs.values())

    def _pages_needed(self, prompt_len: int) -> int:
        return -(-max(1, prompt_len + 1) // self.page_size)

    def can_admit(self, prompt_len: int) -> bool:
        """Admission gate: a free lane AND enough pages for the prompt
        plus the first generated token (prefix-cache hits need fewer,
        but the gate stays conservative — a hit only helps). Reclaims
        cache-held prefix pages (LRU) when short: LIVE sequences
        always outrank cached prefixes for pool space."""
        if len(self._seqs) >= self.decode_batch:
            return False
        need = self._pages_needed(prompt_len)
        if self.pool.free_pages < need:
            self._reclaim_prefix(need)
        return self.pool.free_pages >= need

    def _alloc_page(self) -> int:
        """Pool alloc that spills the prefix cache before failing."""
        try:
            return self.pool.alloc()
        except PoolExhausted:
            self._reclaim_prefix(1)
            return self.pool.alloc()

    def _reclaim_prefix(self, want_pages: int) -> None:
        """Drop LRU prefix-cache entries until ``want_pages`` pages
        are free (or the cache is empty). Shared pages only lose the
        cache's reference — sequences still decoding over them are
        untouched."""
        while self.pool.free_pages < want_pages and self._prefix_lru:
            digest = self._prefix_lru.pop(0)
            pages, _nf, _pl, _lg = self._prefix.pop(digest)
            for p in pages:
                self.pool.free(p)

    def admit(self, tokens: List[int], *, max_new: int,
              temperature: float = 0.0, seed: int = 0,
              eos: Optional[int] = None, seq_id: Any = None
              ) -> Tuple[Any, int]:
        """Prefill (or prefix-reuse) one prompt and return
        ``(seq_id, first_token)``. Raises :class:`PoolExhausted` when
        ``can_admit`` would be False — callers gate first."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("empty prompt")
        if len(tokens) + 1 > self.max_tokens:
            tokens = tokens[-(self.max_tokens - max(1, max_new)):]
        max_new = max(1, min(int(max_new), self.max_new_cap,
                             self.max_tokens - len(tokens)))
        lane = next((i for i, s in enumerate(self._lanes)
                     if s is None), None)
        if lane is None or not self.can_admit(len(tokens)):
            raise PoolExhausted("no lane/pages for admission")
        digest = prefix_digest(tokens)
        hit = self._prefix.get(digest)
        if hit is not None:
            pages, first_logits = self._adopt_prefix(hit)
            self.prefill_skipped_total += 1
        else:
            pages, first_logits = self._prefill(tokens)
            self._insert_prefix(digest, pages, len(tokens),
                                first_logits)
        first = self._sample_host(first_logits, temperature, seed,
                                  len(tokens))
        if seq_id is None:
            seq_id = f"seq-{self._order}"
        seq = _Seq(seq_id, lane, pages, len(tokens), len(tokens),
                   first, max_new, float(temperature), int(seed), eos,
                   self._order, tokens + [first])
        self._order += 1
        self._lanes[lane] = seq_id
        self._seqs[seq_id] = seq
        self.last_logits[seq_id] = first_logits
        self.tokens_total += 1
        return seq_id, first

    def _prefill(self, tokens: List[int]
                 ) -> Tuple[List[int], np.ndarray]:
        n = len(tokens)
        pages = [self._alloc_page()
                 for _ in range(self._pages_needed(n))]
        bucket = next((b for b in PREFILL_BUCKETS if b >= n),
                      PREFILL_BUCKETS[-1])
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = tokens
        pos = np.zeros((bucket,), np.int32)  # padding -> scratch page 0
        for i in range(n):
            pos[i] = pages[i // self.page_size] * self.page_size \
                + i % self.page_size
        fn = self._prefill_fn(bucket)
        # Host arrays go straight to the params' devices with the call.
        logits, self._k_pool, self._v_pool = fn(
            self._params, self._k_pool, self._v_pool, ids, pos,
            np.int32(n - 1))
        self.prefills_total += 1
        return pages, np.asarray(logits)

    # ---- prefix cache ----

    def _insert_prefix(self, digest: str, pages: List[int],
                       prompt_len: int, logits: np.ndarray) -> None:
        if self._prefix_cap <= 0 or digest in self._prefix:
            return
        for p in pages:
            self.pool.retain(p)  # the cache's own reference
        n_full = prompt_len // self.page_size
        self._prefix[digest] = (list(pages), n_full, prompt_len,
                                logits)
        self._prefix_lru.append(digest)
        while len(self._prefix_lru) > self._prefix_cap:
            old = self._prefix_lru.pop(0)
            old_pages, _nf, _pl, _lg = self._prefix.pop(old)
            for p in old_pages:
                self.pool.free(p)

    def _adopt_prefix(self, hit) -> Tuple[List[int], np.ndarray]:
        """Share the hit's full pages by refcount; copy a partial tail
        page (decode will append INTO it). Device copy is one fused
        dynamic-slice program per adoption."""
        pages, n_full, _prompt_len, logits = hit
        out: List[int] = []
        for p in pages[:n_full]:
            self.pool.retain(p)
            out.append(p)
        for p in pages[n_full:]:  # at most one partial tail page
            dst = self._alloc_page()
            self._k_pool, self._v_pool = self._copy_page_fn()(
                self._k_pool, self._v_pool, np.int32(p), np.int32(dst))
            out.append(dst)
        return out, logits

    # ---- decode ----

    def _ensure_page(self, seq: _Seq) -> bool:
        """Make sure the slot for position ``seq.length`` exists.
        False = allocation failed (pool pressure)."""
        need = seq.length // self.page_size
        if need < len(seq.pages):
            return True
        try:
            seq.pages.append(self._alloc_page())
            return True
        except PoolExhausted:
            return False

    def evict_youngest(self) -> Optional[Dict[str, Any]]:
        """Preempt the most recently admitted resident sequence: free
        its pages and return enough state to re-queue it (tokens so
        far become the new prompt; generated count carries so the
        budget is honored across the preemption)."""
        if not self._seqs:
            return None
        seq = max(self._seqs.values(), key=lambda s: s.order)
        self._release(seq)
        self.evictions_total += 1
        return {"seq_id": seq.seq_id, "tokens": list(seq.tokens),
                "n_done": seq.n_new, "max_new": seq.max_new,
                "temperature": seq.temperature, "seed": seq.seed,
                "eos": seq.eos}

    def finish(self, seq_id: Any) -> None:
        seq = self._seqs.get(seq_id)
        if seq is not None:
            self._release(seq)

    def _release(self, seq: _Seq) -> None:
        for p in seq.pages:
            self.pool.free(p)
        self._lanes[seq.lane] = None
        del self._seqs[seq.seq_id]
        # last_logits deliberately survives release: the finishing
        # step's logits are read AFTER the sequence is gone (parity
        # checks, the scheduler's final frame); pruned in step().

    def step(self) -> Tuple[List[Tuple[Any, int, Optional[str]]],
                            List[Dict[str, Any]]]:
        """One decode step for every resident sequence.

        Returns ``(results, evicted)``: results are
        ``(seq_id, token, finish)`` triples — ``finish`` is ``None``
        (still going), ``"eos"`` or ``"length"`` — and ``evicted``
        lists preempted-sequence states (pool pressure made room for
        the sequences that DID step).
        """
        evicted: List[Dict[str, Any]] = []
        # Page pressure: every stepping sequence needs its write slot;
        # evict youngest-first until the remaining set fits.
        while True:
            ordered = sorted(self._seqs.values(), key=lambda s: s.order)
            if all(self._ensure_page(s) for s in ordered):
                break
            ev = self.evict_youngest()
            if ev is None:
                break
            evicted.append(ev)
        if not self._seqs:
            return [], evicted
        B, P = self.decode_batch, self.pages_per_seq
        ids = np.zeros((B,), np.int32)
        slots = np.zeros((B, P), np.int32)
        lengths = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        seeds = np.zeros((B,), np.int32)
        for seq in self._seqs.values():
            ids[seq.lane] = seq.last_token
            slots[seq.lane, :len(seq.pages)] = seq.pages
            lengths[seq.lane] = seq.length
            temps[seq.lane] = seq.temperature
            seeds[seq.lane] = seq.seed
        # The per-step token H2D hop rides the pinned stager when the
        # runtime has one (worker registration records which).
        ids_dev = self._stager(ids)
        put = functools.partial(jax.device_put, device=self._rep)
        next_ids, logits, self._k_pool, self._v_pool = \
            self._decode_aot(self._params, self._k_pool, self._v_pool,
                             ids_dev, put(slots), put(lengths),
                             put(temps), put(seeds))
        self.decode_steps_total += 1
        next_host = np.asarray(next_ids)
        logits_host = None  # fetched lazily, only if a caller asks
        results: List[Tuple[Any, int, Optional[str]]] = []
        for seq in list(self._seqs.values()):
            tok = int(next_host[seq.lane])
            seq.length += 1          # last_token's K/V is now in-slab
            seq.last_token = tok
            seq.n_new += 1
            seq.tokens.append(tok)
            self.tokens_total += 1
            if logits_host is None:
                logits_host = np.asarray(logits)
            self.last_logits[seq.seq_id] = logits_host[seq.lane]
            finish = None
            if seq.eos is not None and tok == seq.eos:
                finish = "eos"
            elif seq.n_new >= seq.max_new:
                finish = "length"
            results.append((seq.seq_id, tok, finish))
            if finish is not None:
                self._release(seq)
        while len(self.last_logits) > 8 * self.decode_batch:
            self.last_logits.pop(next(iter(self.last_logits)))
        return results, evicted

    # ---- host sampling (first token, from prefill logits) ----

    @staticmethod
    def _sample_host(logits: np.ndarray, temperature: float,
                     seed: int, position: int) -> int:
        if temperature <= 0:
            return int(np.argmax(logits))
        rng = np.random.default_rng((int(seed) << 20) ^ position)
        g = rng.gumbel(size=logits.shape)
        return int(np.argmax(logits / max(temperature, 1e-6) + g))

    def close(self) -> None:
        for seq_id in list(self._seqs):
            self.finish(seq_id)
        for digest in list(self._prefix_lru):
            pages, _nf, _pl, _lg = self._prefix.pop(digest)
            for p in pages:
                self.pool.free(p)
        self._prefix_lru.clear()
        self._k_pool = self._v_pool = None


# ---- program builders -------------------------------------------------


def _layer_norm(x, g):
    xf = x.astype(jnp.float32)
    m = xf.mean(-1, keepdims=True)
    v = ((xf - m) ** 2).mean(-1, keepdims=True)
    return (xf - m) * jax.lax.rsqrt(v + 1e-6) * g


def _build_decode(dims, page_size: int, pages_per_seq: int,
                  batch: int):
    """The ONE decode executable: fixed ``(B, P)`` shapes, any mix of
    sequence lengths. Pools are donated — the step updates in place."""
    d, h, L, v = dims["d"], dims["h"], dims["layers"], dims["v"]
    dh = d // h
    ps, P, B = page_size, pages_per_seq, batch
    T = P * ps
    pe = _sinusoidal(T, d)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def decode_step(params, k_pool, v_pool, ids, slots, lengths,
                    temps, seeds):
        emb = params["embed"].astype(jnp.bfloat16)
        pos = jnp.asarray(pe)
        x = emb[ids] * jnp.bfloat16(math.sqrt(d)) \
            + pos[lengths].astype(jnp.bfloat16)          # (B, d)
        # Store slot for the incoming token; gather map for the whole
        # logical sequence. Lengths of 0 (idle lanes) write/read the
        # scratch page — finite garbage the mask keeps out of real
        # lanes and idle lanes' outputs are discarded on the host.
        write_pos = slots[jnp.arange(B), lengths // ps] * ps \
            + lengths % ps                               # (B,)
        gather = (slots[:, :, None] * ps
                  + jnp.arange(ps)[None, None, :]).reshape(B, T)
        kv_mask = jnp.arange(T)[None, :] <= lengths[:, None]

        def one_layer(x, layer):
            lp, kp, vp = layer
            hid = _layer_norm(x, lp["ln1"]).astype(jnp.bfloat16)
            qkv = hid @ lp["qkv"].astype(jnp.bfloat16)   # (B, 3d)
            q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
            kp = kp.at[write_pos].set(k_new)
            vp = vp.at[write_pos].set(v_new)
            kh = kp[gather].reshape(B, T, h, dh).transpose(0, 2, 1, 3)
            vh = vp[gather].reshape(B, T, h, dh).transpose(0, 2, 1, 3)
            qh = q.reshape(B, h, dh)
            s = jnp.einsum("bhd,bhtd->bht", qh, kh
                           ).astype(jnp.float32) / math.sqrt(dh)
            s = jnp.where(kv_mask[:, None, :], s, NEG_INF)
            w = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
            o = jnp.einsum("bht,bhtd->bhd", w, vh).reshape(B, d)
            x = x + (o @ lp["proj"].astype(jnp.bfloat16)
                     ).astype(x.dtype)
            hid = _layer_norm(x, lp["ln2"]).astype(jnp.bfloat16)
            hid = jax.nn.gelu(hid @ lp["w1"].astype(jnp.bfloat16))
            return x + (hid @ lp["w2"].astype(jnp.bfloat16)
                        ).astype(x.dtype), (kp, vp)

        x, (k_pool, v_pool) = jax.lax.scan(
            one_layer, x, (params["layers"], k_pool, v_pool))
        x = _layer_norm(x, params["lnf"]).astype(jnp.bfloat16)
        logits = (x @ emb.T).astype(jnp.float32)         # (B, v)
        greedy = jnp.argmax(logits, -1)
        # Seed folded with the POSITION, not the lane: the same
        # (seed, position) draws the same gumbel noise no matter how
        # admission packed the batch — sampling is reproducible under
        # continuous batching by construction.
        base = jax.random.key(0)
        keys = jax.vmap(lambda s_, l_: jax.random.fold_in(
            jax.random.fold_in(base, s_), l_))(seeds, lengths)
        gum = jax.vmap(
            lambda k_: jax.random.gumbel(k_, (v,), jnp.float32))(keys)
        temp = jnp.maximum(temps, 1e-6)[:, None]
        sampled = jnp.argmax(logits / temp + gum, -1)
        next_ids = jnp.where(temps > 0.0, sampled,
                             greedy).astype(jnp.int32)
        return next_ids, logits, k_pool, v_pool

    return decode_step


def _build_prefill(dims, bucket: int, block_fn):
    """One prefill executable per prompt-length bucket: the existing
    causal flash block over the padded prompt, K/V captured per layer
    and scattered into the sequence's pages (padding lands on the
    scratch page), last-valid-position logits out. ``block_fn`` is the
    model's ``_block`` — prefill shares the training block's math (and
    its flash kernel) verbatim; only the K/V capture is new."""
    d, h, L = dims["d"], dims["h"], dims["layers"]
    Tb = bucket
    pe = _sinusoidal(Tb, d)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def prefill(params, k_pool, v_pool, ids, pos_idx, last):
        emb = params["embed"].astype(jnp.bfloat16)
        x = emb[ids] * jnp.bfloat16(math.sqrt(d))
        x = x + jnp.asarray(pe)[None].astype(x.dtype)

        def one_layer(x, lp):
            # Same block as training/forward, but capture K/V: redo
            # the qkv projection on the normalized input (cheap next
            # to attention) so block_fn itself stays untouched.
            hid = _layer_norm(x, lp["ln1"]).astype(jnp.bfloat16)
            qkv = hid @ lp["qkv"].astype(jnp.bfloat16)
            _q, k, v = jnp.split(qkv, 3, axis=-1)
            return block_fn(x, lp, h), (k[0], v[0])

        x, (ks, vs) = jax.lax.scan(one_layer, x, params["layers"])
        # ks (L, Tb, d) -> scatter into the slab rows pos_idx.
        k_pool = k_pool.at[:, pos_idx].set(ks)
        v_pool = v_pool.at[:, pos_idx].set(vs)
        x = _layer_norm(x, params["lnf"]).astype(jnp.bfloat16)
        xlast = jax.lax.dynamic_index_in_dim(x[0], last, 0,
                                             keepdims=False)
        logits = (xlast @ emb.T).astype(jnp.float32)     # (v,)
        return logits, k_pool, v_pool

    return prefill
