"""Serving wire-format + host-copy accounting (the zero-copy evidence).

The packed serving path (docs/serving.md "Wire format & quantization")
claims two things a throughput number on a noisy box cannot prove: the
bytes that actually ride the bus shrink, and the per-burst host copies
(per-query decode, ``np.stack``, pad-``concatenate``) disappear. These
counters ARE that evidence (``tests/test_wire_codec.py::
test_packed_wire_bytes_materially_lower`` pins their deltas; what the
packed wire is worth in time is not measured: ``PERF.md`` §7 row 5).

- ``rafiki_tpu_serving_wire_bytes_total{format=packed|perquery,
  direction=scatter|reply}`` — estimated serialized payload bytes at
  every Cache send site (an estimate: b64 length + per-frame framing
  overhead, computed without re-serializing the frame).
- ``rafiki_tpu_serving_host_copies_total{site=encode|decode|stack|pad|assemble}``
  — per-tensor host copies on the serving path: per-query base64
  encodes (predictor), per-query/per-shard decodes (worker and packed
  assembly), ``np.stack`` rows, and pad-``concatenate`` events.
- ``rafiki_tpu_serving_quant_total{mode}`` — queries served by a
  quantized model (worker-side; own lazy family, so a quant-off
  process never grows a series).
- ``rafiki_tpu_serving_stacked_dispatch_total{mode=stacked|fallback}``
  + ``rafiki_tpu_serving_dispatches_per_query_ratio`` — the stacked-
  ensemble dispatch evidence (worker-side; own lazy family gated on
  ``RAFIKI_TPU_SERVING_STACKED``, so a stacked-off process exposes
  zero stacked series).

Gating (the r11 disabled-means-free discipline): the wire/copies
family exists only while ``RAFIKI_TPU_SERVING_PACKED_WIRE`` is not
``off`` AND metrics are enabled — resolved ONCE at first use, so hot
paths pay one function call + one None check. ``compat`` keeps the
accounting while disabling packed *emission/advertisement* (each
Cache/worker/predictor snapshots the mode at construction), which is
an operational kill switch that keeps observability. Labels are
bounded static vocabularies, so the series are deliberately
process-immortal (no per-instance label to remove).
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

from . import metrics as _metrics

PACKED_WIRE_ENV = "RAFIKI_TPU_SERVING_PACKED_WIRE"
QUANT_ENV = "RAFIKI_TPU_SERVING_QUANT"
STACKED_ENV = "RAFIKI_TPU_SERVING_STACKED"

#: The ONE accepted-spelling vocabulary for each knob — NodeConfig
#: validation imports these (rejecting typos loudly at config time),
#: while the lenient mode readers below fail SAFE on anything outside
#: them (a hand-set worker env never passes validation).
PACKED_WIRE_SPELLINGS = ("", "1", "on", "true", "yes",
                         "0", "off", "false", "no", "compat")
QUANT_OFF_SPELLINGS = ("", "0", "off", "none", "no", "false")
QUANT_MODES = ("int8",)
STACKED_SPELLINGS = ("", "1", "on", "true", "yes",
                     "0", "off", "false", "no")


def known_packed_wire_spelling(raw: str) -> bool:
    return raw.strip().lower() in PACKED_WIRE_SPELLINGS


def known_quant_spelling(raw: str) -> bool:
    return raw.strip().lower() in QUANT_OFF_SPELLINGS + QUANT_MODES


def known_stacked_spelling(raw: str) -> bool:
    return raw.strip().lower() in STACKED_SPELLINGS


def packed_wire_mode(raw: Optional[str] = None) -> str:
    """The ONE spelling of the packed-wire tri-mode: ``"on"`` (emit +
    account, the default), ``"off"`` (legacy frames, zero new series),
    ``"compat"`` (legacy frames, accounting kept). NodeConfig
    validation and every construction-time env read resolve through
    here so the spellings cannot drift.

    Unrecognized spellings FAIL SAFE to ``"compat"`` (with a warning):
    NodeConfig rejects typos loudly, but env is the documented
    transport and a hand-set worker env never passes validation — a
    typo'd rollback (``offf``) resolving to "on" would silently keep
    the feature it was meant to kill, while compat is always
    behavior-correct (legacy frames, metrics kept)."""
    if raw is None:
        raw = os.environ.get(PACKED_WIRE_ENV, "on")
    raw = raw.strip().lower()
    if raw == "compat":
        return "compat"
    if raw in ("0", "false", "no", "off"):
        return "off"
    if raw in PACKED_WIRE_SPELLINGS:  # the remaining on-spellings
        return "on"
    import logging

    logging.getLogger(__name__).warning(
        "%s=%r is not one of on/off/compat; failing safe to 'compat' "
        "(legacy frames, wire metrics kept)", PACKED_WIRE_ENV, raw)
    return "compat"


def quant_mode(raw: Optional[str] = None) -> str:
    """``""`` (off) or a member of :data:`QUANT_MODES` — the
    InferenceWorker's construction-time read. Unrecognized spellings
    fail SAFE to ``""`` (serve the trained dtype) with a warning: a
    typo'd hand-set env must degrade to f32 serving, not ERROR every
    worker at model load (same rationale as ``packed_wire_mode``;
    NodeConfig validation still rejects typos loudly)."""
    if raw is None:
        raw = os.environ.get(QUANT_ENV, "")
    raw = raw.strip().lower()
    if raw in QUANT_OFF_SPELLINGS:
        return ""
    if raw in QUANT_MODES:
        return raw
    import logging

    logging.getLogger(__name__).warning(
        "%s=%r is not one of %s; failing safe to unquantized serving",
        QUANT_ENV, raw, ("",) + QUANT_MODES)
    return ""


def stacked_mode(raw: Optional[str] = None) -> bool:
    """Whether stacked-ensemble serving is requested
    (``RAFIKI_TPU_SERVING_STACKED``, default on — stacking is a pure
    dispatch-count win gated by the congruence probe, and parity is
    pinned by tests). Unrecognized spellings fail SAFE to **off** with
    a warning: for a perf feature the behavior-correct fallback is the
    per-member path a typo'd rollback was reaching for (NodeConfig
    validation still rejects typos loudly)."""
    if raw is None:
        raw = os.environ.get(STACKED_ENV, "on")
    raw = raw.strip().lower()
    if raw in ("0", "off", "false", "no"):
        return False
    if raw in STACKED_SPELLINGS:  # the remaining on-spellings
        return True
    import logging

    logging.getLogger(__name__).warning(
        "%s=%r is not one of on/off; failing safe to per-member "
        "serving", STACKED_ENV, raw)
    return False


#: (wire_bytes counter | None, host_copies counter | None); resolved at
#: first use under the lock, then read lock-free.
_state: Optional[Tuple] = None
_quant_counter = None
#: (dispatch counter, dispatches-per-query gauge) | (None, None);
#: lazy own family like the quant counter — registered only when a
#: stacked-capable ensemble actually serves AND the knob is on, so a
#: stacked-off process exposes ZERO stacked series
#: (``tests/test_stacked.py::test_stacked_off_zero_series``).
_stacked_state: Optional[Tuple] = None
_lock = threading.Lock()


def _counters() -> Tuple:
    global _state
    # rta: disable=RTA101 double-checked init: the bare read is the fast path; the write re-checks under _lock
    s = _state
    if s is None:
        with _lock:
            s = _state
            if s is None:
                if packed_wire_mode() != "off" \
                        and _metrics.metrics_enabled():
                    reg = _metrics.registry()
                    s = (
                        reg.counter(
                            "rafiki_tpu_serving_wire_bytes_total",
                            "Estimated serialized serving payload "
                            "bytes (format=packed|perquery, "
                            "direction=scatter|reply)"),
                        reg.counter(
                            "rafiki_tpu_serving_host_copies_total",
                            "Per-tensor host copies on the serving "
                            "path (site=encode|decode|stack|pad|"
                            "assemble)"),
                    )
                else:
                    s = (None, None)
                _state = s
    return s


def counting() -> bool:
    """Whether the wire/copies family is live — callers that must
    COMPUTE a byte estimate check this first so a disabled plane pays
    nothing."""
    return _counters()[0] is not None


def count_bytes(fmt: str, direction: str, nbytes: int) -> None:
    c = _counters()[0]
    if c is not None and nbytes > 0:
        # rta: disable=RTA301 format/direction are a 2x2 fixed vocabulary (packed|perquery x scatter|reply); the family is process-global and deliberately immortal
        c.inc(nbytes, format=fmt, direction=direction)


def count_copies(site: str, n: int = 1) -> None:
    c = _counters()[1]
    if c is not None and n > 0:
        # rta: disable=RTA301 site is the fixed encode|decode|stack|pad|assemble vocabulary; process-global family, deliberately immortal
        c.inc(n, site=site)


def count_quant(n: int, mode: str) -> None:
    """Queries served by a quantized model. Lazy own family: a process
    that never serves quantized registers nothing (the zero-new-series
    guard in tests/test_wire_codec.py pins this)."""
    global _quant_counter
    if n <= 0 or not mode:
        return
    # rta: disable=RTA101 double-checked init: the bare read is the fast path; the write re-checks under _lock
    c = _quant_counter
    if c is None:
        with _lock:
            c = _quant_counter
            if c is None:
                if not _metrics.metrics_enabled():
                    return
                c = _metrics.registry().counter(
                    "rafiki_tpu_serving_quant_total",
                    "Queries served by a quantized ensemble model "
                    "(mode=int8)")
                _quant_counter = c
    # rta: disable=RTA301 mode is the fixed quant vocabulary (int8); registered only while quantized serving is live, deliberately immortal
    c.inc(n, mode=mode)


def _stacked_counters() -> Tuple:
    global _stacked_state
    # rta: disable=RTA101 double-checked init: the bare read is the fast path; the write re-checks under _lock
    s = _stacked_state
    if s is None:
        with _lock:
            s = _stacked_state
            if s is None:
                if stacked_mode() and _metrics.metrics_enabled():
                    reg = _metrics.registry()
                    s = (
                        reg.counter(
                            "rafiki_tpu_serving_stacked_dispatch_total",
                            "Ensemble-burst device dispatches on a "
                            "stacked-capable worker (mode=stacked: one "
                            "vmapped program served the whole member "
                            "group; mode=fallback: per-member "
                            "dispatches of a burst that could not ride "
                            "the stacked program)"),
                        reg.gauge(
                            "rafiki_tpu_serving_dispatches_per_query_ratio",
                            "Device dispatches per served query of the "
                            "last ensemble burst (stacked mode: "
                            "1/queries; per-member fallback: "
                            "members/queries)"),
                    )
                else:
                    s = (None, None)
                _stacked_state = s
    return s


def count_stacked_dispatch(mode: str, n: int = 1) -> None:
    """``mode="stacked"``: one vmapped dispatch served the whole
    member group; ``mode="fallback"``: per-member dispatches of a
    burst a stacked-capable worker had to serve the legacy way."""
    c = _stacked_counters()[0]
    if c is not None and n > 0:
        # No RTA301 waiver needed: the module's one `mode` finding
        # anchors at count_quant's earlier inc, waived there (fixed
        # vocabularies both).
        c.inc(n, mode=mode)


def observe_dispatches_per_query(dispatches: int, queries: int) -> None:
    g = _stacked_counters()[1]
    if g is not None and queries > 0:
        g.set(dispatches / queries)


def reset_for_tests() -> None:
    """Drop the cached enabled-state so a test that flips
    ``RAFIKI_TPU_SERVING_PACKED_WIRE`` / ``RAFIKI_TPU_METRICS`` sees
    its env take effect (production resolves once, by design)."""
    global _state, _quant_counter, _stacked_state
    with _lock:
        _state = None
        _quant_counter = None
        _stacked_state = None
