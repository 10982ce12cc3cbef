"""The packed serving wire (r13): codec round-trips, negotiation,
mixed-fleet e2e, the worker staging buffer, int8 serving quantization,
and the zero-new-series guard.

Codec invariants are property-style over the supported dtype/shape
matrix (incl. non-contiguous inputs); e2e tests run real Predictor /
InferenceWorker components over a MemoryBus with no mocks of the
protocol itself — only the model is a stand-in where jax would be
noise.
"""

import threading

import numpy as np
import pytest

from rafiki_tpu.bus import MemoryBus
from rafiki_tpu.cache import (WIRE_NDBATCH, Cache, PackedBatch,
                              decode_batch, decode_payload,
                              encode_payload)
from rafiki_tpu.observe import metrics as obs_metrics
from rafiki_tpu.observe import wire as obs_wire
from rafiki_tpu.predictor.predictor import Predictor
from rafiki_tpu.worker.inference import (_HostStager, _PackedEnsemble,
                                         InferenceWorker)

DTYPES = [np.uint8, np.int8, np.uint16, np.int32, np.int64,
          np.float16, np.float32, np.float64, np.bool_]
SHAPES = [(), (3,), (2, 3), (8, 8, 1), (2, 2, 2, 2)]


def _arrays(dtype, shape, n=5, seed=0):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 2, size=(n, *shape)) if dtype == np.bool_ \
        else rng.integers(0, 100, size=(n, *shape))
    # np.array (not astype on the iterated row) so 0-d shapes stay
    # ndarrays rather than collapsing to numpy scalars.
    return [np.array(a, dtype=dtype) for a in raw]


# --- Codec round-trips -------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_pack_roundtrip_every_dtype_shape(dtype, shape):
    arrays = _arrays(dtype, shape)
    pb = PackedBatch.from_arrays(arrays)
    assert pb is not None and pb.n == len(arrays)
    out = decode_batch(pb.slice(0, pb.n))
    assert out.dtype == np.dtype(dtype) and out.shape == (5, *shape)
    for a, b in zip(arrays, out):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_packed_equals_perquery_format(dtype):
    """The two wire formats must decode to identical tensors — the
    mixed-fleet correctness contract."""
    arrays = _arrays(dtype, (4, 3))
    encoded = [encode_payload(a) for a in arrays]
    pb = PackedBatch.from_encoded(encoded)
    assert pb is not None
    packed_rows = decode_batch(pb.slice(0, pb.n))
    for enc, row in zip(encoded, packed_rows):
        np.testing.assert_array_equal(decode_payload(enc), row)


def test_pack_noncontiguous_inputs():
    base = np.arange(64, dtype=np.float32).reshape(8, 8)
    views = [base[::2, ::2], base.T[:4, :4], base[1:5, 2:6]]
    assert not any(v.flags["C_CONTIGUOUS"] for v in views)
    pb = PackedBatch.from_arrays(views)
    out = decode_batch(pb.slice(0, 3))
    for v, row in zip(views, out):
        np.testing.assert_array_equal(np.ascontiguousarray(v), row)


def test_slice_and_take_are_row_exact():
    arrays = _arrays(np.int32, (3,), n=7)
    pb = PackedBatch.from_arrays(arrays)
    mid = decode_batch(pb.slice(2, 4))
    for i, row in enumerate(mid):
        np.testing.assert_array_equal(arrays[2 + i], row)
    sub = pb.take([6, 0, 3])
    out = decode_batch(sub.slice(0, 3))
    for want, row in zip([arrays[6], arrays[0], arrays[3]], out):
        np.testing.assert_array_equal(want, row)


def test_from_lists_refuses_unpackable():
    a = np.zeros((2, 2), np.float32)
    assert PackedBatch.from_arrays([]) is None
    assert PackedBatch.from_arrays([a, np.zeros((2, 3), np.float32)]) \
        is None                                        # mixed shapes
    assert PackedBatch.from_arrays([a, a.astype(np.int32)]) is None
    assert PackedBatch.from_arrays([a, [1, 2]]) is None  # non-tensor
    assert PackedBatch.from_arrays(
        [np.array(["x", "y"], dtype=object)]) is None
    enc = encode_payload(a)
    assert PackedBatch.from_encoded([enc, {"no": "nd"}]) is None
    assert PackedBatch.from_encoded([enc, encode_payload(
        np.zeros((3, 3), np.float32))]) is None
    assert PackedBatch.from_encoded([1, 2]) is None
    # a lying per-query frame (payload shorter than its header) is
    # refused, not silently mis-packed
    bad = dict(enc)
    bad["__nd__"] = bad["__nd__"][:8]
    assert PackedBatch.from_encoded([bad, enc]) is None


def _good_frame(n=3):
    return PackedBatch.from_arrays(
        _arrays(np.float32, (2, 2), n=n)).slice(0, n)


@pytest.mark.parametrize("mutate", [
    lambda f: f.pop("__ndbatch__"),
    lambda f: f.update(v=2),                      # unknown version
    lambda f: f.pop("v"),
    lambda f: f.update(dtype="no-such-dtype"),
    lambda f: f.update(shape=[-1, 2]),
    lambda f: f.update(n=-1),
    lambda f: f.update(n=99),                     # truncated payload
    lambda f: f.update(__ndbatch__="!!!notb64!!!"),
    lambda f: f.update(
        __ndbatch__=f["__ndbatch__"][:len(f["__ndbatch__"]) // 2]),
    lambda f: f.update(offsets=[0, 1, 2]),        # disagree with header
    lambda f: f.update(offsets=[0]),              # wrong count
])
def test_decode_rejects_corrupt_frames(mutate):
    frame = _good_frame()
    mutate(frame)
    with pytest.raises(ValueError):
        decode_batch(frame)


def test_from_encoded_rejects_lying_header_before_allocating():
    """A client-controlled shape header must not size an allocation
    its payload doesn't vouch for (shape [1e12] over a 4-byte payload
    refuses instead of attempting a multi-TB np.empty), and negative
    dims are refused outright."""
    huge = {"__nd__": encode_payload(np.zeros((1,), np.float32))["__nd__"],
            "dtype": "float32", "shape": [10 ** 12]}
    assert PackedBatch.from_encoded([huge]) is None
    neg = {"__nd__": "AAAA", "dtype": "float32", "shape": [-1]}
    assert PackedBatch.from_encoded([neg]) is None


def test_decode_rejects_dict_offsets_as_valueerror():
    """Corrupt offsets of the wrong TYPE (a dict round-tripped through
    JSON string keys) must land in the ValueError contract, never
    escape as KeyError through the worker's serve loop."""
    frame = _good_frame()
    frame["offsets"] = {str(i): v for i, v in enumerate(frame["offsets"])}
    with pytest.raises(ValueError):
        decode_batch(frame)


def test_decode_accepts_offsetless_frame():
    """offsets are a validation aid, not load-bearing — a minimal
    well-formed header decodes."""
    frame = _good_frame()
    frame.pop("offsets")
    assert decode_batch(frame).shape == (3, 2, 2)


# --- Worker-side decode + staging --------------------------------------


class _StagedModel:
    """Stand-in model exposing the staged contract; counts entries."""
    max_predict_batch = 64

    def __init__(self):
        self.staged_calls = 0
        self.flat_calls = 0
        self.buffers = []

    def predict_bucket(self, n, dtype=None):
        if not (1 <= n <= self.max_predict_batch):
            return None
        b = 1
        while b < n:
            b *= 2
        return b

    def predict_staged_submit(self, buf, n):
        self.staged_calls += 1
        self.buffers.append(buf)
        rows = buf[:n].reshape(n, -1).astype(np.float64)
        return lambda: [[float(r.sum()), float(r.sum()) + 0.5]
                        for r in rows]

    def predict_submit(self, queries):
        self.flat_calls += 1
        return lambda: [[float(np.asarray(q, dtype=np.float64).sum()),
                         float(np.asarray(q, dtype=np.float64).sum())
                         + 0.5] for q in queries]


def _worker(bus, wid="w1", job="job", trial="t1", wire_on=True,
            model=None):
    """A real InferenceWorker wired by hand (no meta/params), its loop
    driven by the test."""
    w = InferenceWorker(wid, job, trial, meta=None, params=None,
                        bus=bus, pipeline=False)
    w._model = model if model is not None else _StagedModel()
    w._wire_formats = [WIRE_NDBATCH] if wire_on else []
    w._reg_info = {"trial_id": trial, "wire": w._wire_formats}
    w.cache.register_worker(job, wid, info=w._reg_info)

    def loop():
        while not w.stop_flag.is_set():
            items = w.cache.pop_queries(wid, timeout=0.1)
            if items:
                w._complete_batch(*w._dispatch_batch(items))

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return w


def _expected(qs):
    return [float(np.asarray(q, dtype=np.float64).sum()) for q in qs]


def test_packed_e2e_direct_and_preencoded_paths():
    bus = MemoryBus()
    w = _worker(bus)
    try:
        p = Predictor("job", bus, gather_timeout=5.0,
                      worker_wait_timeout=5.0)
        qs = [np.full((4, 3), i, np.uint8) for i in range(6)]
        res = p.predict(qs)
        assert [r[0] for r in res] == _expected(qs)
        assert w._model.staged_calls == 1 and w._model.flat_calls == 0
        res2 = p.predict([encode_payload(q) for q in qs],
                         pre_encoded=True)
        assert [r[0] for r in res2] == _expected(qs)
        assert w._model.staged_calls == 2 and w._model.flat_calls == 0
    finally:
        w.stop_flag.set()


def test_staging_buffer_reused_across_bursts():
    bus = MemoryBus()
    w = _worker(bus)
    try:
        p = Predictor("job", bus, gather_timeout=5.0,
                      worker_wait_timeout=5.0)
        qs = [np.full((2, 2), i, np.float32) for i in range(3)]
        for _ in range(4):
            p.predict(qs)
        bufs = w._model.buffers
        assert len(bufs) == 4
        # Double-buffered reuse: alternating bursts share a buffer (no
        # per-burst allocation), successive ones never do (the async
        # device_put of burst N must not race burst N+1's staging).
        assert bufs[0] is bufs[2] and bufs[1] is bufs[3]
        assert bufs[0] is not bufs[1]
    finally:
        w.stop_flag.set()


def test_mixed_fleet_old_worker_and_old_predictor(monkeypatch):
    """New predictor + one packed and one legacy worker (two bins,
    both vote); then an old-style (packed-off) predictor against the
    new workers — every combination must serve identically."""
    bus = MemoryBus()
    w_new = _worker(bus, wid="wn", trial="t-new", wire_on=True)
    w_old = _worker(bus, wid="wo", trial="t-old", wire_on=False)
    try:
        qs = [np.full((3,), i, np.float32) for i in range(5)]
        p = Predictor("job", bus, gather_timeout=5.0,
                      worker_wait_timeout=5.0)
        res = p.predict(qs)
        assert [r[0] for r in res] == _expected(qs)  # 2-bin mean of equal votes
        assert w_new._model.staged_calls >= 1   # packed frames arrived
        assert w_old._model.flat_calls >= 1     # legacy frames arrived
        assert w_old._model.staged_calls == 0   # never packed at it

        monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "off")
        obs_wire.reset_for_tests()
        p_old = Predictor("job", bus, gather_timeout=5.0,
                          worker_wait_timeout=5.0)
        res2 = p_old.predict(qs)
        assert [r[0] for r in res2] == _expected(qs)
        # the packed-capable worker happily took per-query frames
        assert w_new._model.flat_calls >= 1
    finally:
        w_new.stop_flag.set()
        w_old.stop_flag.set()
        obs_wire.reset_for_tests()


def test_packed_wire_mode_fails_safe_on_typo():
    """A hand-set worker env never passes NodeConfig validation, so an
    unrecognized spelling must not silently resolve to 'on' (a typo'd
    rollback keeping the feature alive) — it fails safe to compat."""
    assert obs_wire.packed_wire_mode("offf") == "compat"
    assert obs_wire.packed_wire_mode("onn") == "compat"
    assert obs_wire.packed_wire_mode("off") == "off"
    assert obs_wire.packed_wire_mode("0") == "off"
    assert obs_wire.packed_wire_mode("on") == "on"
    assert obs_wire.packed_wire_mode("") == "on"
    assert obs_wire.packed_wire_mode("COMPAT") == "compat"
    # quant typos fail safe to UNQUANTIZED serving (a worker must not
    # go ERRORED at model load over a hand-set env typo)
    assert obs_wire.quant_mode("int-8") == ""
    assert obs_wire.quant_mode("fp8") == ""
    assert obs_wire.quant_mode("int8") == "int8"
    assert obs_wire.quant_mode("OFF") == ""


def test_compat_mode_worker_not_advertised(monkeypatch):
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "compat")
    obs_wire.reset_for_tests()
    w = InferenceWorker("w", "j", "t", meta=None, params=None,
                        bus=MemoryBus(), pipeline=False)
    assert w._wire_formats == []
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "on")
    obs_wire.reset_for_tests()
    w2 = InferenceWorker("w2", "j", "t", meta=None, params=None,
                         bus=MemoryBus(), pipeline=False)
    assert w2._wire_formats == [WIRE_NDBATCH]
    obs_wire.reset_for_tests()


def test_wire_payload_packs_only_when_a_plan_needs_it():
    """Lazy packing (review finding): a plan that never targets a
    packed-capable worker — e.g. a tiered phase-1 against a legacy
    best bin — must not pay the assembly decode/alloc; the first plan
    that does triggers it exactly once."""
    from rafiki_tpu.predictor.predictor import _Shard, _WirePayload

    frames = [encode_payload(np.full((3,), i, np.float32))
              for i in range(4)]
    wire = _WirePayload(frames, True, frozenset({"wcap"}))
    enc, packed = wire.for_plan([_Shard("wleg", "b", 0, 4)])
    assert packed is None and enc is frames
    assert wire._packed_done is False  # assembly never ran
    enc2, packed2 = wire.for_plan([_Shard("wcap", "b", 0, 4)])
    assert enc2 is None and packed2 is not None
    assert wire.packed is packed2  # memoized, not re-assembled


def test_corrupt_packed_frame_errors_only_its_own_frame():
    """A corrupt packed frame in a burst is answered with per-query
    error dicts; co-batched frames still serve, and the worker thread
    survives."""
    bus = MemoryBus()
    w = _worker(bus)
    try:
        cache = Cache(bus)
        good = PackedBatch.from_arrays(
            [np.full((2,), 7, np.float32)]).slice(0, 1)
        bad = PackedBatch.from_arrays(
            [np.full((2,), 1, np.float32)]).slice(0, 2)  # lying n
        bad["n"] = 2
        bus.push("q:w1", {"batch_id": "bgood", "batch": good})
        bus.push("q:w1", {"batch_id": "bbad", "batch": bad})
        good_reply = bus.pop("r:bgood", timeout=5.0)
        bad_reply = bus.pop("r:bbad", timeout=5.0)
        assert good_reply["predictions"][0][0] == 14.0
        assert len(bad_reply["predictions"]) == 2
        assert all("error" in p for p in bad_reply["predictions"])
        # worker still serves after the bad frame
        p = Predictor("job", bus, gather_timeout=5.0,
                      worker_wait_timeout=5.0)
        res = p.predict([np.full((2,), 3, np.float32)])
        assert res[0][0] == 6.0
    finally:
        w.stop_flag.set()


def test_corrupt_frame_reply_size_is_capped():
    """A corrupt frame's header is untrusted: a lying n=1e9 must not
    make the error path allocate a billion error dicts."""
    from rafiki_tpu.cache import _CORRUPT_REPLY_CAP

    bus = MemoryBus()
    w = _worker(bus)
    try:
        frame = PackedBatch.from_arrays(
            [np.zeros((2,), np.float32)]).slice(0, 1)
        frame["n"] = 10 ** 9  # payload no longer matches -> corrupt
        bus.push("q:w1", {"batch_id": "bhuge", "batch": frame})
        reply = bus.pop("r:bhuge", timeout=5.0)
        assert len(reply["predictions"]) == _CORRUPT_REPLY_CAP
        assert all("error" in p for p in reply["predictions"])
    finally:
        w.stop_flag.set()


def test_fanout_packed_and_perquery_mix():
    """send_query_batch_fanout's packed path (the unsharded fanout the
    wire contract also names): capable workers get ONE shared packed
    frame, the rest the per-query list — decode-identical."""
    bus = MemoryBus()
    cache = Cache(bus)
    arrays = _arrays(np.float32, (3,), n=4)
    encoded = [encode_payload(a) for a in arrays]
    packed = PackedBatch.from_encoded(encoded)
    cache.send_query_batch_fanout(["wnew", "wold"], encoded,
                                  packed=packed, packed_ok={"wnew"})
    new_frame = bus.pop("q:wnew", timeout=2.0)
    old_frame = bus.pop("q:wold", timeout=2.0)
    assert "batch" in new_frame and "queries" not in new_frame
    assert old_frame["queries"] is encoded  # shared, not copied
    rows = decode_batch(new_frame["batch"])
    for a, row in zip(arrays, rows):
        np.testing.assert_array_equal(a, row)
    # all-capable fanout needs no per-query list at all
    cache.send_query_batch_fanout(["wnew"], None, packed=packed,
                                  packed_ok={"wnew"})
    assert "batch" in bus.pop("q:wnew", timeout=2.0)


def test_quant_host_arrays_single_pass(ff_model):
    """enable_serving_quant's report and the first compile share ONE
    host quantization pass (review finding: it used to run twice per
    worker load)."""
    ff_model.enable_serving_quant("int8")
    try:
        first = ff_model._quant_host
        assert first is not None
        assert ff_model._quant_host_arrays() is first
    finally:
        ff_model.enable_serving_quant("")


def test_packed_ensemble_staged_contract():
    m1, m2 = _StagedModel(), _StagedModel()
    pack = _PackedEnsemble([m1, m2])
    assert pack.predict_bucket(5) == 8
    buf = np.ones((8, 2), np.float32)
    out = pack.predict_staged_submit(buf, 5)()
    assert len(out) == 5 and out[0] == [2.0, 2.5]  # mean of equal votes
    assert m1.buffers[0] is m2.buffers[0]  # one shared staging buffer
    # disagreement (or a member without the entry) falls back
    m2.max_predict_batch = 2
    assert pack.predict_bucket(5) is None
    assert _PackedEnsemble([m1, object()]).predict_bucket(3) is None


def test_host_stager_keys_and_reuse():
    st = _HostStager()
    a = st.buffer(8, (2, 2), np.uint8)
    b = st.buffer(8, (2, 2), np.uint8)
    assert a.shape == (8, 2, 2) and a.dtype == np.uint8
    assert b is not a                          # double buffer rotation
    assert st.buffer(8, (2, 2), np.uint8) is a  # ...of exactly two
    assert st.buffer(8, (2, 2), np.float32) is not a
    assert st.buffer(16, (2, 2), np.uint8) is not a


# --- Metrics: accounting + the zero-new-series guard -------------------

_WIRE_METRICS = ("rafiki_tpu_serving_wire_bytes_total",
                 "rafiki_tpu_serving_host_copies_total",
                 "rafiki_tpu_serving_quant_total")


@pytest.fixture()
def fresh_registry(monkeypatch):
    """A private registry so absence-of-series is judgeable: the real
    one is process-global and other tests already fed it."""
    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setattr(obs_metrics, "_registry", reg)
    obs_wire.reset_for_tests()
    yield reg
    obs_wire.reset_for_tests()


def _serve_once(packed_predictor=True):
    bus = MemoryBus()
    w = _worker(bus, wire_on=packed_predictor)
    try:
        p = Predictor("job", bus, gather_timeout=5.0,
                      worker_wait_timeout=5.0)
        p.predict([np.full((2, 2), i, np.uint8) for i in range(4)])
    finally:
        w.stop_flag.set()


def test_zero_new_series_when_disabled(fresh_registry, monkeypatch):
    """Packed wire off + quant off ⇒ a full serve registers NONE of
    the wire/copies/quant families (the r12 discipline)."""
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "off")
    monkeypatch.delenv(obs_wire.QUANT_ENV, raising=False)
    obs_wire.reset_for_tests()
    _serve_once(packed_predictor=False)
    for name in _WIRE_METRICS:
        assert fresh_registry.find(name) is None, name


def test_wire_metrics_account_both_formats(fresh_registry, monkeypatch):
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "on")
    obs_wire.reset_for_tests()
    _serve_once(packed_predictor=True)
    wire = fresh_registry.find("rafiki_tpu_serving_wire_bytes_total")
    copies = fresh_registry.find("rafiki_tpu_serving_host_copies_total")
    assert wire is not None and copies is not None
    assert wire.value(format="packed", direction="scatter") > 0
    # r14: dense float-vector replies pack too (the query frame's "rw"
    # negotiation), so the packed side's reply bytes are packed now.
    assert wire.value(format="packed", direction="reply") > 0
    assert wire.value(format="perquery", direction="reply") == 0
    # packed path: assembly decode + per-shard encode, no stack/pad
    assert copies.value(site="encode") >= 1
    assert copies.value(site="stack") == 0
    _serve_once(packed_predictor=False)  # legacy worker: perquery side
    assert wire.value(format="perquery", direction="scatter") > 0
    assert copies.value(site="decode") > 0


def test_compat_mode_accounts_without_packing(fresh_registry,
                                              monkeypatch):
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "compat")
    obs_wire.reset_for_tests()
    _serve_once(packed_predictor=False)
    wire = fresh_registry.find("rafiki_tpu_serving_wire_bytes_total")
    assert wire is not None
    assert wire.value(format="packed", direction="scatter") == 0
    assert wire.value(format="perquery", direction="scatter") > 0


def test_packed_wire_bytes_materially_lower(fresh_registry,
                                            monkeypatch):
    """The packed wire's claim, pinned as a unit property: the same
    super-batch costs materially fewer wire bytes packed than
    per-query (framing overhead amortizes to one header per shard)."""
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "on")
    obs_wire.reset_for_tests()
    cache = Cache(MemoryBus())
    qs = [np.zeros((8, 8, 1), np.uint8) for _ in range(32)]
    encoded = [encode_payload(q) for q in qs]
    packed = PackedBatch.from_encoded(encoded)
    wire = None
    cache.send_query_shards([("w1", 0, 32, "s1")], encoded)
    reg = fresh_registry.find("rafiki_tpu_serving_wire_bytes_total")
    perquery = reg.value(format="perquery", direction="scatter")
    cache.send_query_shards([("w1", 0, 32, "s2")], None,
                            packed=packed, packed_ok={"w1"})
    packed_bytes = reg.value(format="packed", direction="scatter")
    assert perquery > 0 and packed_bytes > 0
    assert packed_bytes < 0.85 * perquery, (packed_bytes, perquery)


# --- int8 serving quantization ----------------------------------------


@pytest.fixture(scope="module")
def ff_model():
    """A tiny initialized (untrained) JaxFeedForward — weights are
    random but deterministic, which is all the numeric contracts
    need."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models.feedforward import JaxFeedForward

    m = JaxFeedForward(hidden_layer_count=2, hidden_layer_units=32,
                       learning_rate=1e-3, batch_size=32, max_epochs=1)
    m._ensure_module(4, (8, 8, 1))
    variables = m._module.init(
        jax.random.key(0), jnp.zeros((1, 8, 8, 1)), train=False,
        **{k: jnp.asarray(v) for k, v in m.extra_apply_inputs().items()})
    m._variables = jax.tree.map(lambda a: np.asarray(a), variables)
    m._meta = {"n_classes": 4, "image_shape": [8, 8, 1]}
    yield m
    m.enable_serving_quant("")


@pytest.fixture()
def quant_queries():
    rng = np.random.default_rng(7)
    return (rng.random((6, 8, 8, 1)) * 255).astype(np.uint8)


def test_int8_quant_close_to_f32(ff_model, quant_queries):
    ff_model.enable_serving_quant("")
    p_f32 = np.asarray(ff_model.predict_proba(quant_queries))
    report = ff_model.enable_serving_quant("int8")
    assert report["mode"] == "int8" and report["n_int8"] == 4
    p_q = np.asarray(ff_model.predict_proba(quant_queries))
    assert np.abs(p_f32 - p_q).max() < 0.02
    assert (p_f32.argmax(-1) == p_q.argmax(-1)).all()
    # disabling restores the exact f32 path
    ff_model.enable_serving_quant("")
    np.testing.assert_allclose(
        np.asarray(ff_model.predict_proba(quant_queries)), p_f32)


def test_int8_generic_fallback_matches_module_path(ff_model,
                                                   quant_queries):
    """Force the generic dequantized-weights fallback (quantized_apply
    -> None) and compare with the module's dequant-free int8 path —
    both must stay near f32; the fallback is weight-only so it is
    numerically the tighter of the two."""
    ff_model.enable_serving_quant("")
    p_f32 = np.asarray(ff_model.predict_proba(quant_queries))
    ff_model.enable_serving_quant("int8")
    try:
        p_int8 = np.asarray(ff_model.predict_proba(quant_queries))
        orig = type(ff_model).quantized_apply
        type(ff_model).quantized_apply = \
            lambda self, q, s, f, x, e: None
        try:
            ff_model._predict_cache.clear()  # recompile generic variant
            p_generic = np.asarray(ff_model.predict_proba(quant_queries))
        finally:
            type(ff_model).quantized_apply = orig
            ff_model._predict_cache.clear()
        assert np.abs(p_f32 - p_generic).max() < 0.01
        assert np.abs(p_int8 - p_generic).max() < 0.02
    finally:
        ff_model.enable_serving_quant("")


def test_quant_staged_and_flat_paths_agree(ff_model, quant_queries):
    ff_model.enable_serving_quant("int8")
    try:
        flat = np.asarray(ff_model.predict_proba(quant_queries))
        n = quant_queries.shape[0]
        bucket = ff_model.predict_bucket(n, np.uint8)
        buf = np.zeros((bucket, 8, 8, 1), np.uint8)
        buf[:n] = quant_queries
        staged = np.asarray(ff_model.predict_staged_submit(buf, n)())
        np.testing.assert_allclose(staged, flat, rtol=1e-5, atol=1e-6)
    finally:
        ff_model.enable_serving_quant("")


def test_quant_mode_validation(ff_model):
    with pytest.raises(ValueError):
        ff_model.enable_serving_quant("fp4")


def test_quant_counter_only_when_active(fresh_registry, monkeypatch):
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "on")
    obs_wire.reset_for_tests()
    _serve_once()  # unquantized serving
    assert fresh_registry.find("rafiki_tpu_serving_quant_total") is None
    obs_wire.count_quant(4, "int8")
    c = fresh_registry.find("rafiki_tpu_serving_quant_total")
    assert c is not None and c.value(mode="int8") == 4


def test_worker_quantizes_at_load(monkeypatch):
    """The worker's load path applies RAFIKI_TPU_SERVING_QUANT to a
    model exposing enable_serving_quant, and its registration records
    what it serves (promotion-spawned workers recompute scales by
    construction — same code path)."""
    calls = []

    class _QModel:
        @staticmethod
        def validate_knobs(knobs):
            return knobs

        def load_parameters(self, params):
            pass

        def enable_serving_quant(self, mode):
            calls.append(mode)
            return {"mode": mode, "n_int8": 2, "n_f32": 1}

    class _Meta:
        def get_trial(self, tid):
            return {"model_id": "m", "knobs": {}, "score": 0.5,
                    "params_id": "p"}

        def get_model(self, mid):
            return {"model_class": "x:Y", "model_source": None}

    class _Params:
        def load(self, pid):
            return {}

    monkeypatch.setenv(obs_wire.QUANT_ENV, "int8")
    obs_wire.reset_for_tests()
    w = InferenceWorker("s", "j", "t", _Meta(), _Params(), MemoryBus(),
                        pipeline=False)
    monkeypatch.setattr(
        "rafiki_tpu.worker.inference.load_model_class",
        lambda cls, src: _QModel)
    w._load_model()
    assert calls == ["int8"]
    assert w._quant_active is True
    obs_wire.reset_for_tests()


# --- Reply-direction packed frames (r14) ------------------------------

def _reply_roundtrip(preds, packed_ok=True, env="on", monkeypatch=None):
    from rafiki_tpu.cache import pack_prediction_rows  # noqa: F401

    bus = MemoryBus()
    cache = Cache(bus)
    cache.send_prediction_batch("rb", "w1", preds, weight=2,
                                shard="sh", packed_ok=packed_ok)
    out = cache.gather_prediction_batches("rb", 1, timeout=2.0)
    assert len(out) == 1
    return out[0]


def test_reply_pack_roundtrip_and_metadata():
    preds = [[0.1 * i, 1.0 - 0.1 * i] for i in range(8)]
    reply = _reply_roundtrip(preds)
    assert reply["weight"] == 2 and reply["shard"] == "sh"
    got = reply["predictions"]
    assert len(got) == 8
    for g, p in zip(got, preds):
        np.testing.assert_allclose(np.asarray(g), p)


def test_reply_pack_refuses_unpackable():
    from rafiki_tpu.cache import pack_prediction_rows

    assert pack_prediction_rows([{"error": "x"}, [0.1, 0.9]]) is None
    assert pack_prediction_rows([[0.1, 0.9]]) is None          # n < 2
    assert pack_prediction_rows([[1, 2], [3, 4]]) is None      # ints
    assert pack_prediction_rows([[0.1, 0.9],
                                 [0.1, 0.9, 0.0]]) is None     # ragged
    assert pack_prediction_rows(["a", "b"]) is None
    assert pack_prediction_rows(
        [{"__members__": [[0.1], [0.9]]}] * 2) is None
    # ...and an unpackable batch still round-trips per-query.
    reply = _reply_roundtrip([{"error": "x"}, [0.1, 0.9]])
    assert reply["predictions"] == [{"error": "x"}, [0.1, 0.9]]


def test_reply_pack_negotiation_is_frame_carried(monkeypatch):
    """Workers pack replies ONLY toward senders whose query frame
    advertised `rw` (an old predictor never sets it), and only while
    their own packed mode is "on" (compat keeps per-query replies)."""
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "on")
    obs_wire.reset_for_tests()
    bus = MemoryBus()
    on = Cache(bus)
    on.send_query_shards([("wq", 0, 2, "s1")],
                         [encode_payload(np.zeros((2,), np.float32))] * 2)
    frame = bus.pop_all("q:wq", timeout=0.5)[0]
    assert frame.get("rw") == [WIRE_NDBATCH]
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "compat")
    obs_wire.reset_for_tests()
    compat = Cache(bus)
    compat.send_query_shards([("wq", 0, 2, "s2")],
                             [encode_payload(np.zeros((2,),
                                             np.float32))] * 2)
    frame = bus.pop_all("q:wq", timeout=0.5)[0]
    assert "rw" not in frame
    # compat sender side: packed_ok granted but own mode says no.
    compat.send_prediction_batch("rc", "w1", [[0.5, 0.5]] * 4,
                                 packed_ok=True)
    raw = bus.pop_all("r:rc", timeout=0.5)[0]
    assert "batch" not in raw and "predictions" in raw
    obs_wire.reset_for_tests()


def test_reply_packed_bytes_materially_lower(fresh_registry,
                                             monkeypatch):
    """The reply-direction unit gate (ISSUE r14): the same dense reply
    batch costs fewer estimated wire bytes packed than per-query."""
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "on")
    obs_wire.reset_for_tests()
    cache = Cache(MemoryBus())
    preds = [list(np.linspace(0.0, 1.0, 10) + i) for i in range(32)]
    cache.send_prediction_batch("rp", "w1", preds, packed_ok=True)
    reg = fresh_registry.find("rafiki_tpu_serving_wire_bytes_total")
    packed = reg.value(format="packed", direction="reply")
    cache.send_prediction_batch("rq", "w1", preds, packed_ok=False)
    perquery = reg.value(format="perquery", direction="reply")
    assert packed > 0 and perquery > 0
    assert packed < 0.85 * perquery, (packed, perquery)


def test_reply_corrupt_packed_frame_is_dropped(monkeypatch):
    """A corrupt packed reply is DROPPED, never returned: its shard
    must read as genuinely unanswered so the straggler resubmit /
    partial-bin machinery covers it — returning it (even with empty
    predictions) would mark the shard answered and could supersede a
    healthy in-flight retry. A good reply behind it still gathers."""
    bus = MemoryBus()
    cache = Cache(bus)
    bus.push("r:bad", {"worker_id": "w1", "weight": 1,
                       "batch": {"__ndbatch__": "!!!", "v": 1,
                                 "dtype": "float64", "shape": [2],
                                 "n": 2, "offsets": [0, 16]}})
    bus.push("r:bad", {"worker_id": "w2", "weight": 1,
                       "predictions": [[0.5, 0.5]]})
    out = cache.gather_prediction_batches("bad", 1, timeout=2.0)
    assert len(out) == 1 and out[0]["worker_id"] == "w2"


def test_reply_packed_e2e_through_real_worker(monkeypatch):
    """Real InferenceWorker + real Predictor over a MemoryBus: the
    reply rides ONE packed frame and the ensemble output is
    unchanged."""
    monkeypatch.setenv(obs_wire.PACKED_WIRE_ENV, "on")
    obs_wire.reset_for_tests()
    bus = MemoryBus()
    w = _worker(bus)
    try:
        p = Predictor("job", bus, gather_timeout=5.0,
                      worker_wait_timeout=5.0)
        qs = [np.full((2, 2), i, np.uint8) for i in range(4)]
        res = p.predict(qs)
        assert [r[0] for r in res] == _expected(qs)
        # Prove the wire actually packed the reply.
        reg = obs_metrics.registry().find(
            "rafiki_tpu_serving_wire_bytes_total")
        assert reg.value(format="packed", direction="reply") > 0
    finally:
        w.stop_flag.set()
        obs_wire.reset_for_tests()
