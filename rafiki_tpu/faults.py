"""Deterministic, seedable fault-injection plane.

SURVEY.md §5 makes the Admin/ServicesManager responsible for failure
detection and recovery, and this repo already grew the recovery paths —
straggler resubmit, partial-bin degrade, lease expiry, supervise
respawn, write-behind drain. None of them were exercised under
*injected* faults, so none could be trusted or timed. This module is
the one place faults come from: every injection site in the tree asks
it for a hook at CONSTRUCTION time, and a process with no fault plan
stores ``None`` — the hot path pays exactly one attribute comparison
(the "strictly zero-overhead when disabled" contract, tested in
``tests/test_faults.py``).

Plan grammar (``RAFIKI_TPU_FAULT_PLAN``; rules ``;``-separated)::

    rule   := site '.' kind [ ':' params ]
    params := key '=' value [ ',' key '=' value ... ]

Sites and kinds (the seams this repo owns):

==========  ===========  ==================================================
site        kind         effect at the site
==========  ===========  ==================================================
``bus``     ``delay``    sleep ``ms`` before the op (memory + tcp backends)
``bus``     ``drop``     silently discard a ``push``/``push_many`` (message
                         loss; non-push ops ignore a drop verdict)
``bus``     ``disconnect``  raise ``ConnectionError`` (tcp: the client
                         socket is also dropped — a detected dead broker)
``http``    ``error``    reply ``code`` (default 503) before dispatch
``http``    ``timeout``  stall the handler ``ms`` before dispatch
``worker``  ``slow``     sleep ``ms`` before an inference predict dispatch
``worker``  ``crash``    raise :class:`InjectedCrash` in the serve loop —
                         the worker thread dies HARD (meta row left
                         RUNNING, bus registration left stale), emulating
                         a kill -9 so ``supervise()`` must notice
``node``    ``kill``     kill EVERY service the matching node owns at the
                         end of its supervise sweep (hard: meta rows left
                         RUNNING, registrations stale) — whole-node death;
                         ``op=`` matches the node_id, so a plan can target
                         one virtual node in a multi-node test
==========  ===========  ==================================================

Selection params (exactly one per rule; default ``p=1``):

- ``p=0.1``   — fire with probability 0.1, drawn from a PRNG seeded by
  ``RAFIKI_TPU_FAULT_SEED`` + the rule's position, so a seeded plan
  replays the same decision SEQUENCE (per-site call interleavings across
  threads still vary — determinism is per-rule, not global).
- ``n=3``     — fire on exactly the 3rd eligible call (1-based), once.
- ``every=5`` — fire on every 5th eligible call.

Match params (all optional; omitted = match anything):

- ``op=push_many`` — bus op name / http method.
- ``kind=query``   — bus queue kind (``query``/``reply``/``other``).
- ``route=/predict`` — http route pattern.

Other params: ``ms`` (delay/slow/timeout milliseconds, default 50),
``code`` (http error status, default 503).

Every fired injection is counted in
``rafiki_tpu_fault_injections_total{site,kind}`` so chaos runs (and the
zero-overhead test, which asserts the counter stays unborn) read the
same number production scrapes.

Runtime arming: ``set_plan(text, seed)`` swaps the live rule set —
sites that were constructed while a plan existed consult the CURRENT
rules on every op, so a chaos harness can build the stack quietly
(``set_plan("")`` — armed, no rules), run a clean baseline, then arm
the real plan mid-flight. ``set_plan(None)`` disarms the module
entirely; only constructions AFTER that see hooks vanish.
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .observe import metrics as _metrics

_log = logging.getLogger(__name__)

PLAN_ENV = "RAFIKI_TPU_FAULT_PLAN"
SEED_ENV = "RAFIKI_TPU_FAULT_SEED"

SITES = ("bus", "http", "worker", "node")

_KINDS = {
    "bus": ("delay", "drop", "disconnect"),
    "http": ("error", "timeout"),
    "worker": ("slow", "crash"),
    "node": ("kill",),
}

#: Every param key a rule may carry (selection + match + effect).
_PARAM_KEYS = frozenset(
    {"p", "n", "every", "op", "kind", "route", "ms", "code"})


class FaultInjected(Exception):
    """Base for exceptions raised BY the fault plane (never by real
    failures), so tests and logs can tell injected damage apart."""


class InjectedCrash(FaultInjected):
    """A worker-site ``crash`` rule fired: the serve loop must die hard
    (not ``RuntimeError`` — the loop's bus-recovery catch would absorb
    it and the 'crash' would heal itself)."""


class _Rule:
    __slots__ = ("site", "kind", "params", "rng", "_count", "_spent",
                 "_lock")

    def __init__(self, site: str, kind: str, params: Dict[str, str],
                 seed: int, index: int):
        self.site = site
        self.kind = kind
        self.params = params
        # Seeded per rule (seed + position): the decision sequence of
        # each rule replays exactly under the same plan + seed.
        self.rng = random.Random(f"{seed}:{index}:{site}.{kind}")
        self._count = 0  # eligible (matched) calls seen
        self._spent = False  # n= rules fire once
        self._lock = threading.Lock()

    def matches(self, op: str, kind: str, route: str) -> bool:
        want_op = self.params.get("op")
        if want_op is not None and want_op != op:
            return False
        want_kind = self.params.get("kind")
        if want_kind is not None and want_kind != kind:
            return False
        want_route = self.params.get("route")
        if want_route is not None and want_route != route:
            return False
        return True

    def due(self) -> bool:
        """One eligible call: advance this rule's counter/PRNG and say
        whether it fires. Locked — injection sites are multithreaded
        and a torn counter would break ``n=``/``every=`` exactness."""
        with self._lock:
            if self._spent:
                return False
            self._count += 1
            if "n" in self.params:
                if self._count == int(self.params["n"]):
                    self._spent = True
                    return True
                return False
            if "every" in self.params:
                return self._count % max(1, int(self.params["every"])) == 0
            p = float(self.params.get("p", 1.0))
            if p >= 1.0:
                return True
            return self.rng.random() < p

    def ms(self, default: float = 50.0) -> float:
        return float(self.params.get("ms", default))


class FaultPlan:
    """A parsed plan: rules grouped by site, plus the injection
    counter. Immutable after construction; ``set_plan`` swaps whole
    plans rather than mutating one."""

    def __init__(self, rules: List[_Rule]):
        self.rules: Dict[str, List[_Rule]] = {}
        for r in rules:
            self.rules.setdefault(r.site, []).append(r)
        # The counter is born on the FIRST fire, not at parse time:
        # NodeConfig.validate() parses plans it never arms, and a
        # never-fired plan must leave the registry untouched (the
        # zero-overhead test reads the registry to prove silence).
        # Locked: concurrent first fires on different threads must not
        # see _counter_known without _counter (a skipped inc would
        # undercount an n=1 rule's single injection).
        self._counter = None
        self._counter_known = False
        self._counter_lock = threading.Lock()

    @classmethod
    def parse(cls, text: str, seed: int = 0) -> "FaultPlan":
        """Parse a plan string; unknown sites/kinds and malformed rules
        are rejected loudly (a typo'd chaos plan silently injecting
        nothing would 'prove' recovery that was never exercised)."""
        rules: List[_Rule] = []
        for i, raw in enumerate(t for t in text.split(";")
                                if t.strip()):
            head, _, param_s = raw.strip().partition(":")
            site, _, kind = head.strip().partition(".")
            site, kind = site.strip(), kind.strip()
            if site not in _KINDS or kind not in _KINDS[site]:
                raise ValueError(
                    f"fault plan rule {raw.strip()!r}: unknown "
                    f"site.kind {head.strip()!r} (valid: "
                    f"{ {s: list(k) for s, k in _KINDS.items()} })")
            params: Dict[str, str] = {}
            for pair in (p for p in param_s.split(",") if p.strip()):
                k, sep, v = pair.partition("=")
                if not sep:
                    raise ValueError(
                        f"fault plan rule {raw.strip()!r}: param "
                        f"{pair.strip()!r} is not key=value")
                params[k.strip()] = v.strip()
            # Reject unknown keys: a typo'd param ("probability=",
            # "N=") would otherwise be silently never read and the
            # rule would default to fire-on-every-call — a chaos run
            # measured under the wrong plan while claiming the typed
            # one.
            unknown = set(params) - _PARAM_KEYS
            if unknown:
                raise ValueError(
                    f"fault plan rule {raw.strip()!r}: unknown "
                    f"param(s) {sorted(unknown)} (valid: "
                    f"{sorted(_PARAM_KEYS)})")
            sel = [k for k in ("p", "n", "every") if k in params]
            if len(sel) > 1:
                raise ValueError(
                    f"fault plan rule {raw.strip()!r}: selection "
                    f"params {sel} are mutually exclusive (exactly "
                    f"one of p=/n=/every=)")
            # Validate numeric params now, not at fire time.
            for k in ("p", "ms"):
                if k in params:
                    float(params[k])
            for k in ("n", "every", "code"):
                if k in params:
                    int(params[k])
            rules.append(_Rule(site, kind, params, seed, i))
        return cls(rules)

    def fire(self, site: str, op: str = "", kind: str = "",
             route: str = "") -> Optional[Tuple[str, Any]]:
        """Evaluate one call at ``site``. Applies every matching due
        rule (sleeps happen here; disconnect/crash raise) and returns
        the last action verdict — ``("drop", None)`` /
        ``("error", code)`` — or None."""
        out: Optional[Tuple[str, Any]] = None
        for rule in self.rules.get(site, ()):
            if not rule.matches(op, kind, route):
                continue
            if not rule.due():
                continue
            if not self._counter_known:  # rta: disable=RTA101 double-checked locking fast path; _counter_known is published (under the lock) only after _counter is assigned
                with self._counter_lock:
                    if not self._counter_known:
                        if _metrics.metrics_enabled():
                            self._counter = _metrics.registry().counter(
                                "rafiki_tpu_fault_injections_total",
                                "Fault-plane injections fired, by "
                                "site and kind")
                        self._counter_known = True
            if self._counter is not None:  # rta: disable=RTA101 read-only fast path; immutable once published by the locked init above
                # rta: disable=RTA301 site/kind are the bounded _KINDS vocabulary; chaos-plane series are deliberately immortal
                self._counter.inc(site=site, kind=rule.kind)
            k = rule.kind
            if k in ("delay", "slow", "timeout"):
                time.sleep(rule.ms() / 1e3)
            elif k == "drop":
                out = ("drop", None)
            elif k == "disconnect":
                raise ConnectionError(
                    f"injected: {site}.disconnect ({op or route})")
            elif k == "crash":
                raise InjectedCrash("injected: worker.crash")
            elif k == "kill":
                # A verdict, not an action: the supervise sweep owns
                # the node-wide teardown (it knows which services the
                # node holds); raising here would just kill the sweep.
                out = ("kill", None)
            elif k == "error":
                out = ("error", int(rule.params.get("code", 503)))
        return out


def should_drop(act: Optional[Tuple[str, Any]], op: str) -> bool:
    """Whether a :meth:`FaultPlan.fire` verdict means *discard this
    op*. One place, used by every bus backend, so memory and tcp can
    never drift on drop semantics: only ``push``/``push_many`` honor a
    ``drop`` verdict (message loss); other ops ignore it."""
    return act is not None and act[0] == "drop" and op.startswith("push")


# --- Module state: the armed plan + construction-time hooks -----------

_state_lock = threading.Lock()
_armed: Optional[FaultPlan] = None
_loaded = False  # env consulted at least once


class _SiteHook:
    """The per-site callable an injection site stores. Consults the
    CURRENT armed plan on every call, so ``set_plan`` re-arms sites
    that were constructed earlier (``tests/test_chaos.py``: build
    quietly, injure mid-flight)."""

    __slots__ = ("site",)

    def __init__(self, site: str):
        self.site = site

    def __call__(self, op: str = "", kind: str = "", route: str = "",
                 ) -> Optional[Tuple[str, Any]]:
        plan = _armed
        if plan is None:
            return None
        return plan.fire(self.site, op=op, kind=kind, route=route)


def _load_env_locked() -> None:
    global _armed, _loaded
    # rta: disable=RTA101 every call site holds _state_lock (the _locked-suffix contract; module pass has no caller-holds fixpoint)
    if _loaded:
        return
    _loaded = True
    text = os.environ.get(PLAN_ENV, "")
    if not text.strip():
        return
    try:
        seed = int(os.environ.get(SEED_ENV, "0") or "0")
    except ValueError:
        seed = 0
    try:
        # rta: disable=RTA101 caller holds _state_lock (see above)
        _armed = FaultPlan.parse(text, seed=seed)
    except ValueError:
        _log.exception("invalid %s; fault plane stays disarmed",
                       PLAN_ENV)


def site_hook(site: str):
    """Resolve a site's hook at CONSTRUCTION time. Returns ``None``
    when the fault plane is disabled — the caller stores the None and
    its hot path is one attribute check, byte-for-byte the pre-fault
    behavior. Returns a live hook when a plan is (or was) armed, so
    ``set_plan`` can change the rules mid-run."""
    if site not in _KINDS:
        raise ValueError(f"unknown fault site {site!r}")
    with _state_lock:
        _load_env_locked()
        if _armed is None:
            return None
        return _SiteHook(site)


def set_plan(text: Optional[str], seed: int = 0) -> None:
    """Swap the armed plan: a plan string (``""`` = armed with zero
    rules — constructions get hooks, nothing fires) or ``None`` to
    disarm entirely. Raises ``ValueError`` on a malformed plan."""
    global _armed, _loaded
    plan = None if text is None else FaultPlan.parse(text, seed=seed)
    with _state_lock:
        _loaded = True  # an explicit plan overrides the env
        _armed = plan


def enabled() -> bool:
    """Whether the plane is armed (possibly with zero rules)."""
    with _state_lock:
        _load_env_locked()
        return _armed is not None


def reset() -> None:
    """Forget everything; the next ``site_hook`` re-reads the env
    (test isolation)."""
    global _armed, _loaded
    with _state_lock:
        _armed = None
        _loaded = False
