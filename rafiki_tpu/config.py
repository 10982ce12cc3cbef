"""Typed node configuration: one validated object per serve process.

Parity: SURVEY.md §5 "Config / flag system" — the reference configures
every service through ``.env.sh`` exports and env vars injected by the
ServicesManager; the rebuild keeps that transport (env vars are how
container/subprocess children inherit settings) but fronts it with a
dataclass so a node constructs from ONE validated object instead of
scattered ``os.environ`` reads.

Precedence: explicit constructor/CLI overrides > ``RAFIKI_TPU_*`` env
vars > defaults. ``apply_env()`` writes the tunables back into
``os.environ`` so both in-process workers (threads reading env at
construction) and spawned service children see the same resolved values.
"""

from __future__ import annotations

import dataclasses
import os
import typing
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Optional, Union

_PREFIX = "RAFIKI_TPU_"


def _parse_bool(raw: str) -> bool:
    return raw.strip().lower() not in ("0", "false", "no", "off", "")


def parse_tristate_bool(raw: str) -> Optional[bool]:
    """The ONE spelling of the tri-state env contract ("auto" -> None,
    falsy spellings -> False, else True) — NodeConfig coercion and
    direct env readers (InferenceWorker) must resolve identically."""
    if raw.strip().lower() == "auto":
        return None
    return _parse_bool(raw)


@dataclass(frozen=True)
class NodeConfig:
    """Everything a ``python -m rafiki_tpu serve`` node needs.

    Env var for field ``x``: ``RAFIKI_TPU_<X>`` (see ``_ENV_MAP`` for
    the exceptions that predate this layer).
    """

    # --- Node identity / state ---
    workdir: str = "./rafiki_workdir"
    port: int = 3000
    n_chips: Optional[int] = None          # None = all visible chips
    bus_uri: str = ""                      # "" = in-process bus
    supervise_interval: float = 10.0       # 0 disables the sweep
    log_level: str = "info"

    # --- Multi-host slice membership (jax.distributed) ---
    coordinator: str = ""                  # host:port; "" = single host
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    # --- Service tunables (inherited by workers) ---
    # One-burst-in-flight serving overlap. None = "auto": each
    # inference worker measures its device->host sync latency at
    # startup and pipelines only when it exceeds pipeline_sync_min —
    # below that the handoff would COST a few percent for nothing to
    # hide.
    serving_pipeline: Optional[bool] = None
    checkpoint_trials: bool = False        # mid-trial epoch snapshots
    trace_dir: str = ""                    # per-trial profiler traces

    # --- Serving frontend: continuous cross-request micro-batching ---
    # The predictor coalesces every /predict arriving within one fill
    # window into ONE scatter-gather super-batch (predictor/batcher.py).
    serving_microbatch: bool = True        # off = one scatter per request
    serving_fill_window: float = 0.005     # adaptive-window ceiling
    #                                        default (legacy fixed knob)
    serving_fill_window_min: float = 0.0   # adaptive floor; == max pins
    serving_fill_window_max: Optional[float] = None  # None = use
    #                                        serving_fill_window
    serving_max_batch: int = 1024          # queries per super-batch
    serving_max_inflight: int = 2          # scattered-ungathered batches
    serving_queue_cap: int = 4096          # admission bound (queries);
    #                                        beyond it: 429 + Retry-After
    # Data-parallel replica sharding: slice each trial bin's
    # super-batch across ALL live same-bin replicas (latency-weighted)
    # instead of sending it whole to one rotating pick.
    serving_shard_replicas: bool = True
    # Per-client fairness: cap one client key's share of the admission
    # queue. The key comes from the request header named by
    # serving_client_header ("" = fairness off).
    serving_client_header: str = ""
    serving_client_share: float = 0.25     # fraction of queue_cap

    # --- Predictor edge cache + tiered serving (docs/serving.md) ---
    # Content-addressed response cache at the predictor edge: repeat
    # queries are answered without touching the ensemble scatter.
    # Byte budget; 0 (the default) disables the cache entirely — the
    # serving hot path then pays one attribute check and registers NO
    # cache metric series.
    serving_cache_bytes: int = 0
    # Max age of a cached answer, seconds. Entries are additionally
    # invalidated wholesale whenever trial promotion changes any served
    # bin (the admin promotion path bumps the cache epoch), so TTL only
    # bounds staleness against out-of-band model changes.
    serving_cache_ttl_s: float = 60.0
    # Admission control: a key is cached only on its Nth miss (2 =
    # second-touch, the default), so one-off keys don't churn the LRU.
    # 1 admits on first touch.
    serving_cache_admit_after: int = 2
    # Confidence-tiered ensemble serving: scatter to the BEST bin (by
    # tracked eval score) first and escalate to the full ensemble vote
    # only for queries whose confidence (softmax margin) falls below
    # this threshold. 0 (the default) disables tiering — every query
    # fans out to the full ensemble, and no tier series is registered.
    serving_tier_threshold: float = 0.0

    # Packed batch-tensor wire format (docs/serving.md "Wire format"):
    # "on" (default) packs same-shape tensor super-batches into one
    # contiguous __ndbatch__ buffer per shard toward workers that
    # advertise it (negotiated — old workers keep per-query frames);
    # "compat" emits/advertises nothing packed but KEEPS the wire-bytes
    # / host-copies accounting (kill switch with observability);
    # "off" = legacy frames and ZERO wire metric series.
    serving_packed_wire: str = "on"
    # Serving quantization mode: "int8" quantizes each InferenceWorker's
    # model post-load (per-channel symmetric weight scales, dequant-free
    # int8 matmuls where the module supports it, f32 fallback per
    # layer); "" (default) serves the trained dtype. Promotion-spawned
    # workers recompute scales for their bin at load. Accuracy contract:
    # tests/test_wire_codec.py::test_int8_quant_close_to_f32 and
    # tests/test_stacked.py::test_cnn_int8_close_to_f32 hold the
    # f32-vs-int8 delta.
    serving_quant: str = ""
    # Stacked-ensemble serving (docs/serving.md "Stacked ensembles"):
    # "on" (default) lets an InferenceWorker hosting a multi-member
    # same-family bin stack the member weights along a leading model
    # axis and serve every burst as ONE vmapped device dispatch
    # (shape-congruence probed at load; incongruent or sk-style
    # members fall back to per-member runners). "off" = per-member
    # serving and ZERO stacked metric series.
    serving_stacked: str = "on"

    # --- Generative serving (docs/serving.md "Generative serving") ---
    # Token-level continuous batching on LM-hosting inference workers:
    # paged KV cache, per-step admission, streamed token frames.
    # Default OFF — a generate-off node pays one attribute check per
    # worker loop pass and exposes ZERO rafiki_tpu_lm_* series.
    serving_generate: bool = False
    # Tokens per KV page (the allocation granule). Smaller pages waste
    # less on short tails but grow the per-sequence page table.
    generate_page_size: int = 16
    # Device page-pool size (pages; page 0 is reserved scratch). Total
    # KV bytes/layer/projection = pages * page_size * d_model * 2 (bf16).
    generate_pool_pages: int = 256
    # Decode-batch width: resident-sequence lanes per compiled decode
    # step. The continuous-batching dispatch win is ~1/width.
    generate_decode_batch: int = 8
    # Per-request cap on generated tokens (requests may ask for less).
    generate_max_new: int = 128

    # --- Metrics-driven autoscaler (docs/autoscaling.md) ---
    # Default OFF: supervise pays one attribute check, zero new metric
    # series, byte-identical sweep behavior. On, the admin-side control
    # loop scales inference replicas per bin from the predictors' own
    # /metrics (backpressure, queue depth, p99) and preempts idle
    # training for starved hot bins.
    autoscale: bool = False
    # Record would-have decisions (ring + counters) without actuating.
    autoscale_dry_run: bool = False
    # Per-bin replica ceiling and per-sweep scale-up step bound.
    autoscale_max_replicas: int = 4
    autoscale_step: int = 1
    # Asymmetric cooldowns: scale up within seconds of pressure, scale
    # down only after a long quiet spell (and never right after an up).
    autoscale_up_cooldown_s: float = 10.0
    autoscale_down_cooldown_s: float = 60.0
    # Hysteresis band over queue_depth/queue_cap: >= high scales up,
    # <= low (with zero backpressure) scales down, between holds.
    autoscale_queue_high: float = 0.25
    autoscale_queue_low: float = 0.02
    # Optional /predict p99 high-water, milliseconds (0 = p99 not
    # consulted by the policy; it is still recorded in decisions).
    autoscale_p99_high_ms: float = 0.0
    # Idle-train preemption: a sub-job whose MFU gauge sat below this
    # floor for autoscale_idle_sweeps consecutive sweeps may be shrunk
    # by one worker to feed a starved serving bin (re-grown when
    # pressure subsides). 0 disables preemption — set 0 in subprocess
    # deployments, where worker MFU is invisible to this registry.
    autoscale_mfu_floor: float = 0.05
    autoscale_idle_sweeps: int = 3
    # Predictive scale-ahead (docs/capacity.md): with a horizon > 0 the
    # autoscaler projects each job's queue occupancy forward along its
    # per-sweep trend (EWMA slope) and scales UP with reason
    # "predicted" when the projection crosses autoscale_queue_high
    # within the horizon — ahead of the ramp instead of behind it.
    # 0 (the default) disables the predictive path entirely.
    autoscale_predict_horizon_s: float = 0.0
    # Optional periodicity table (a JSON file learned from a recorded
    # workload trace by `python -m rafiki_tpu.capacity learn`): the
    # second predictive signal — a recurring ramp due within the
    # horizon whose expected qps exceeds the current bin's by
    # autoscale_predict_ramp_ratio pre-provisions the same way.
    # "" = trend signal only.
    autoscale_periodicity: str = ""
    autoscale_predict_ramp_ratio: float = 1.5

    # Time-sliced tenancy cap: max co-owners per chip when shared
    # placement is admitted (parallel/chips.py). Promoted from the
    # env-only expert baseline (r14): the autoscaler's scale-up leans
    # on time-sliced placement when the slice is full, which makes the
    # cap a per-deployment sizing decision, not an incident knob.
    max_chip_share: int = 4

    # InferenceWorker bus-registration lease cadence, seconds: the
    # registration is re-asserted at this period so a restarted broker
    # re-learns live workers (docs/robustness.md). Promoted from an
    # env-only expert knob (r12): per-deployment now that promotion /
    # cache invalidation correctness leans on registration freshness.
    worker_reregister: float = 5.0

    # How long a foreign node's RUNNING row stays credible without a
    # heartbeat, seconds (admin/services_manager.py). Promoted from an
    # env-only expert knob (r15): multi-node deployments size it from
    # their own heartbeat cadence + NFS/sqlite stall budget, which
    # makes it a per-deployment decision — and the old class-attribute
    # read froze the value at FIRST import, before apply_env could run.
    node_lease: float = 120.0

    # InferenceWorker serving-pipeline auto-probe threshold, seconds:
    # with serving_pipeline=auto the worker pipelines only when the
    # measured device->host sync latency exceeds this. Promoted from an
    # env-only expert knob (r15): the sync latency is a per-deployment
    # fact, not an incident override.
    pipeline_sync_min: float = 0.02

    # --- Trial lifecycle / dataset residency (docs/training.md) ---
    # Host dataset cache: parsed datasets stay resident across trials,
    # keyed by (path, mtime, size), byte-budget LRU. 0 disables.
    dataset_cache_bytes: int = 1 << 30
    # Device staging cache: the replicated uint8 dataset arrays stay
    # resident on the mesh across trials (never donated). 0 disables.
    stage_cache_bytes: int = 2 << 30
    # Per-trial on-device staging threshold: datasets up to this many
    # bytes are staged whole on the mesh (one H2D, index-gathered
    # batches); larger ones fall back to per-chunk shipping.
    stage_bytes: int = 2 << 30
    # TrainWorkers compute the NEXT proposal on a background thread
    # while the current trial trains (advisor/prefetch.py). Opt-out.
    advisor_prefetch: bool = True
    # ParamStore write-behind: save() returns before the disk flush
    # (store/params.py). Off = synchronous saves again.
    params_write_behind: bool = True

    # --- Robustness (docs/robustness.md) ---
    # Fault-injection plan (rafiki_tpu/faults.py): ";"-separated
    # site.kind:params rules injected at the bus / http / worker seams.
    # "" = fault plane disabled (injection sites are strict no-ops).
    fault_plan: str = ""
    # PRNG seed for probabilistic (p=) fault rules: a seeded plan
    # replays the same per-rule decision sequence.
    fault_seed: int = 0
    # TCP bus client reconnection (bus/tcp.py): base backoff step for
    # the bounded exponential retry after a transport failure, and the
    # total retry budget. 0 budget = legacy behavior (one immediate
    # resend of an unsent frame, then fail). Only frame-UNSENT ops and
    # idempotent reads retry — a non-idempotent op whose frame was
    # fully sent is never blindly replayed across a broker restart.
    bus_retry_base_s: float = 0.05
    bus_retry_total_s: float = 15.0

    # --- Cluster serving fabric (docs/cluster.md) ---
    # Master gate for the multi-node serving plane: node registry rows
    # on the bus (admin/nodes.py), frontend peer-cache probes +
    # invalidation gossip (predictor/edge_cache.py), node-routed bus
    # relay and node-aware shard locality. Default OFF — zero new
    # metric series, zero extra threads, byte-identical single-node
    # behavior (one attribute/env check per seam).
    cluster_fabric: bool = False
    # Bound on ONE peer-cache probe, seconds: a frontend miss consults
    # at most one peer for at most this long before scattering to the
    # workers (the probe is strictly additive latency on a cold key, so
    # it must stay well under a scatter's own p50).
    cluster_probe_timeout_s: float = 0.25
    # Same-node replica preference in shard-plan weights: a replica
    # whose chips live on THIS node gets its inverse-latency weight
    # multiplied by this factor (EWMA latency still rules — a slow
    # local replica loses to a fast remote one once the measured gap
    # exceeds the boost). 1.0 = no locality preference.
    cluster_locality_boost: float = 1.0

    # --- Observability (docs/observability.md) ---
    metrics: bool = True                   # /metrics route + bus/http
    #                                        instrumentation wiring
    trace_sample: float = 1.0              # fresh-trace sample rate 0..1
    #                                        (incoming X-Trace-Id always
    #                                        honored)
    trace_max_mb: float = 64.0             # per-SEGMENT spans.jsonl size
    #                                        cap before a roll
    # Segmented span-store retention: how many rolled generations
    # (.1 .. .N, each sidecar-indexed for GET /trace/<id>) stay on
    # disk, and the total byte budget across them (oldest deleted
    # first; the newest rolled segment always survives).
    trace_retain_segments: int = 4
    trace_retain_mb: float = 256.0
    # Tail-based sampling, decided at trace COMPLETION on the minting
    # edge: error and slower-than-trace_tail_slow_ms traces are always
    # retained; fast/ok ones are kept at trace_tail_sample. 1.0 (the
    # default) disables tail sampling — every head-sampled trace is
    # written eagerly, the pre-r17 behavior. Head trace_sample
    # semantics are unchanged and apply first.
    trace_tail_sample: float = 1.0
    trace_tail_slow_ms: float = 250.0
    # OpenMetrics-style exemplars: histograms attach the last traced
    # observation's trace id per bucket to the exposition (and the
    # dashboard links p99 to its stitched timeline). Default off.
    metrics_exemplars: bool = False
    # Serving attribution ledger (docs/observability.md): per-bin and
    # per-tenant request/queue/device-time accounting at the serving
    # frontend and inference workers. Default OFF — disabled means one
    # None check per account site and ZERO rafiki_tpu_serving_bin_* /
    # serving_tenant_* series; the autoscaler consumes the per-bin
    # signals when a scraped frontend exposes them.
    serving_attribution: bool = False
    # --- SLO plane (docs/observability.md "SLOs & alerting") ---
    # Declarative objectives + multi-window burn-rate alerting over
    # the serving metrics (observe/slo.py): a path to a JSON/TOML
    # rules file (value ends .json/.toml) or the compact inline
    # grammar ("name:p99<50ms,window=300,...;..."). "" (the default)
    # disables the whole plane — supervise pays one attribute check
    # and a scrape shows ZERO rafiki_tpu_slo_* series.
    slo_rules: str = ""
    # Optional alert webhook: every alert transition is POSTed as one
    # JSON object (2 s timeout, best-effort) so an external pager can
    # attach. "" = off. Transitions always land in the bounded
    # <logs>/alerts.jsonl sink regardless.
    slo_webhook_url: str = ""
    # Size cap (MB) of the JSONL alert log before it rolls to one .1
    # generation.
    slo_alert_log_mb: float = 16.0

    # Workload recorder (docs/capacity.md): one JSONL arrival record
    # per /predict request at the predictor edge — what the capacity
    # engine replays. Default OFF — one bool check per request, zero
    # rafiki_tpu_workload_* series. The store rolls at
    # workload_max_mb per segment, keeping workload_retain_segments
    # rolled generations (the span store's discipline).
    workload_record: bool = False
    workload_max_mb: float = 64.0
    workload_retain_segments: int = 4

    # Metrics-only HTTP server for subprocess/docker worker runners
    # (they have no HTTP surface of their own). 0 = off; spawned
    # children inherit it via apply_env only when set.
    metrics_port: int = 0

    # Fields whose env names predate this layer (back-compat).
    _ENV_MAP = {
        "serving_pipeline": "RAFIKI_TPU_SERVING_PIPELINE",
        "checkpoint_trials": "RAFIKI_TPU_CKPT",
        "trace_dir": "RAFIKI_TPU_TRACE_DIR",
    }
    _types_cache = None  # deliberately un-annotated: not fields
    _tristate_cache = None

    @classmethod
    def env_name(cls, field: str) -> str:
        return cls._ENV_MAP.get(field, _PREFIX + field.upper())

    @classmethod
    def from_env(cls, env: Optional[Mapping[str, str]] = None,
                 **overrides: Any) -> "NodeConfig":
        """Build from env vars; ``overrides`` (CLI args) win. An
        override of ``None`` means "not given" and is dropped."""
        env = os.environ if env is None else env
        values: Dict[str, Any] = {}
        for f in dataclasses.fields(cls):
            raw = env.get(cls.env_name(f.name))
            if raw is None:
                continue
            values[f.name] = cls._coerce(f.name, raw)
        values.update({k: v for k, v in overrides.items()
                       if v is not None})
        cfg = cls(**values)
        cfg.validate()
        return cfg

    @classmethod
    def _coerce(cls, name: str, raw: str) -> Any:
        target = cls._field_types().get(name, str)
        try:
            if target is bool:
                if name in cls._tristate_bools():
                    return parse_tristate_bool(raw)
                if raw.strip().lower() == "auto":
                    # Only tri-state (Optional[bool]) fields accept
                    # "auto"; on a plain bool it would silently become
                    # a falsy None (RAFIKI_TPU_CKPT=auto used to parse
                    # truthy) — reject loudly instead.
                    raise ValueError("'auto' is only valid for "
                                     "tri-state fields")
                return _parse_bool(raw)
            if target is int:
                return int(raw)
            if target is float:
                return float(raw)
        except ValueError as e:
            raise ValueError(
                f"{cls.env_name(name)}={raw!r}: {e}") from None
        return raw

    @classmethod
    def _field_types(cls) -> Dict[str, type]:
        """Resolved (Optional-unwrapped) scalar type per field. Fields
        whose hint is not a plain scalar / Optional[scalar] stay str —
        adding such a field must extend ``_coerce``, loudly, instead of
        being silently substring-matched to the wrong parser."""
        if cls._types_cache is None:
            resolved: Dict[str, type] = {}
            tristate = set()
            hints = typing.get_type_hints(cls)
            import types as _types

            # Optional[x] resolves to typing.Union; a PEP 604 `x | None`
            # resolves to types.UnionType — unwrap both.
            union_kinds = (Union, getattr(_types, "UnionType", Union))
            for f in dataclasses.fields(cls):
                hint = hints.get(f.name, str)
                if typing.get_origin(hint) in union_kinds:
                    args = [a for a in typing.get_args(hint)
                            if a is not type(None)]
                    hint = args[0] if len(args) == 1 else str
                    if hint is bool:
                        tristate.add(f.name)  # Optional[bool] = auto-able
                resolved[f.name] = hint if isinstance(hint, type) else str
            cls._types_cache = resolved
            cls._tristate_cache = tristate
        return cls._types_cache

    @classmethod
    def _tristate_bools(cls) -> set:
        cls._field_types()
        return cls._tristate_cache

    def validate(self) -> "NodeConfig":
        if not (0 <= self.port <= 65535):
            raise ValueError(f"port {self.port} out of range")
        if self.n_chips is not None and self.n_chips <= 0:
            raise ValueError("n_chips must be positive (or unset)")
        if self.supervise_interval < 0:
            raise ValueError("supervise_interval must be >= 0")
        if self.serving_fill_window < 0:
            raise ValueError("serving_fill_window must be >= 0")
        if self.serving_max_batch < 1 or self.serving_max_inflight < 1 \
                or self.serving_queue_cap < 1:
            raise ValueError("serving_max_batch, serving_max_inflight "
                             "and serving_queue_cap must be >= 1")
        fw_max = (self.serving_fill_window
                  if self.serving_fill_window_max is None
                  else self.serving_fill_window_max)
        if not (0 <= self.serving_fill_window_min <= fw_max):
            raise ValueError("need 0 <= serving_fill_window_min <= "
                             "serving_fill_window_max")
        if not (0.0 <= self.serving_client_share <= 1.0):
            raise ValueError("serving_client_share must be within "
                             "[0, 1]")
        if self.serving_cache_bytes < 0:
            raise ValueError("serving_cache_bytes must be >= 0 "
                             "(0 disables the edge cache)")
        if self.serving_cache_ttl_s <= 0:
            raise ValueError("serving_cache_ttl_s must be positive")
        if self.serving_cache_admit_after < 1:
            raise ValueError("serving_cache_admit_after must be >= 1 "
                             "(1 = admit on first touch)")
        if self.serving_tier_threshold < 0:
            raise ValueError("serving_tier_threshold must be >= 0 "
                             "(0 disables tiered serving)")
        # The accepted-spelling vocabularies live in observe.wire (the
        # env readers fail SAFE on anything outside them; config
        # rejects typos LOUDLY here — one list, two postures).
        from .observe.wire import (known_packed_wire_spelling,
                                   known_quant_spelling,
                                   known_stacked_spelling)

        if not known_packed_wire_spelling(self.serving_packed_wire):
            raise ValueError(
                f"serving_packed_wire {self.serving_packed_wire!r} is "
                f"not one of on/off/compat")
        if not known_quant_spelling(self.serving_quant):
            raise ValueError(
                f"serving_quant {self.serving_quant!r} is not one of "
                f"''/int8")
        if not known_stacked_spelling(self.serving_stacked):
            raise ValueError(
                f"serving_stacked {self.serving_stacked!r} is not one "
                f"of on/off")
        if self.generate_page_size < 1:
            raise ValueError("generate_page_size must be >= 1")
        if self.generate_pool_pages < 2:
            raise ValueError("generate_pool_pages must be >= 2 "
                             "(page 0 is reserved scratch)")
        if self.generate_decode_batch < 1:
            raise ValueError("generate_decode_batch must be >= 1")
        if self.generate_max_new < 1:
            raise ValueError("generate_max_new must be >= 1")
        if self.worker_reregister <= 0:
            raise ValueError("worker_reregister must be positive")
        if self.node_lease <= 0:
            raise ValueError("node_lease must be positive (it bounds "
                             "foreign-node liveness detection)")
        if self.pipeline_sync_min < 0:
            raise ValueError("pipeline_sync_min must be >= 0 (0 = "
                             "auto-pipeline whenever any sync latency "
                             "is measured)")
        if self.autoscale_max_replicas < 1 or self.autoscale_step < 1:
            raise ValueError("autoscale_max_replicas and autoscale_step "
                             "must be >= 1")
        if self.autoscale_up_cooldown_s < 0 \
                or self.autoscale_down_cooldown_s < 0:
            raise ValueError("autoscale cooldowns must be >= 0")
        if not (0.0 <= self.autoscale_queue_low
                <= self.autoscale_queue_high <= 1.0):
            raise ValueError("need 0 <= autoscale_queue_low <= "
                             "autoscale_queue_high <= 1")
        if self.autoscale_p99_high_ms < 0:
            raise ValueError("autoscale_p99_high_ms must be >= 0 "
                             "(0 = p99 not consulted)")
        if self.autoscale_mfu_floor < 0:
            raise ValueError("autoscale_mfu_floor must be >= 0 "
                             "(0 disables preemption)")
        if self.autoscale_idle_sweeps < 1:
            raise ValueError("autoscale_idle_sweeps must be >= 1")
        if self.autoscale_predict_horizon_s < 0:
            raise ValueError("autoscale_predict_horizon_s must be >= 0 "
                             "(0 disables predictive scale-ahead)")
        if self.autoscale_predict_ramp_ratio < 1.0:
            raise ValueError("autoscale_predict_ramp_ratio must be "
                             ">= 1 (a recurring ramp must mean MORE "
                             "load, not less)")
        if self.autoscale_periodicity.strip():
            # Parse now: a typo'd/missing table must fail the node's
            # construction, not silently predict nothing (the
            # fault-plan / slo-rules discipline).
            from .admin.capacity import load_periodicity

            load_periodicity(self.autoscale_periodicity)
        if self.max_chip_share < 1:
            raise ValueError("max_chip_share must be >= 1 (1 = no "
                             "time-sliced co-ownership)")
        if self.dataset_cache_bytes < 0 or self.stage_cache_bytes < 0:
            raise ValueError("dataset_cache_bytes and stage_cache_bytes "
                             "must be >= 0 (0 disables the cache)")
        if self.stage_bytes < 0:
            raise ValueError("stage_bytes must be >= 0 (0 forces "
                             "per-chunk staging)")
        if self.bus_retry_base_s <= 0:
            raise ValueError("bus_retry_base_s must be positive")
        if self.bus_retry_total_s < 0:
            raise ValueError("bus_retry_total_s must be >= 0 "
                             "(0 disables the retry budget)")
        if self.cluster_probe_timeout_s <= 0:
            raise ValueError("cluster_probe_timeout_s must be positive "
                             "(it bounds the single peer-cache probe)")
        if self.cluster_locality_boost < 1.0:
            raise ValueError("cluster_locality_boost must be >= 1 "
                             "(1.0 = no locality preference; below 1 "
                             "would PENALIZE same-node replicas)")
        if self.fault_plan.strip():
            # Parse now: a typo'd chaos plan must fail the node's
            # construction, not silently inject nothing.
            from .faults import FaultPlan

            FaultPlan.parse(self.fault_plan, seed=self.fault_seed)
        if not (0.0 <= self.trace_sample <= 1.0):
            raise ValueError("trace_sample must be within [0, 1]")
        if self.trace_max_mb <= 0:
            raise ValueError("trace_max_mb must be positive")
        if self.trace_retain_segments < 1:
            raise ValueError("trace_retain_segments must be >= 1 "
                             "(1 = the legacy single .1 generation)")
        if self.trace_retain_mb <= 0:
            raise ValueError("trace_retain_mb must be positive")
        if not (0.0 <= self.trace_tail_sample <= 1.0):
            raise ValueError("trace_tail_sample must be within [0, 1] "
                             "(1.0 disables tail sampling)")
        if self.trace_tail_slow_ms < 0:
            raise ValueError("trace_tail_slow_ms must be >= 0")
        if self.slo_rules.strip():
            # Parse now: a typo'd objective must fail the node's
            # construction, not silently judge nothing (the fault-plan
            # discipline). A file source must exist and parse here too.
            from .observe.slo import parse_rules

            parse_rules(self.slo_rules)
        if self.slo_webhook_url and not (
                self.slo_webhook_url.startswith("http://")
                or self.slo_webhook_url.startswith("https://")):
            raise ValueError(
                f"slo_webhook_url {self.slo_webhook_url!r} must be an "
                f"http(s) URL")
        if self.slo_alert_log_mb <= 0:
            raise ValueError("slo_alert_log_mb must be positive")
        if self.workload_max_mb <= 0:
            raise ValueError("workload_max_mb must be positive")
        if self.workload_retain_segments < 1:
            raise ValueError("workload_retain_segments must be >= 1")
        if not (0 <= self.metrics_port <= 65535):
            raise ValueError(f"metrics_port {self.metrics_port} out of "
                             f"range (0 = no standalone server)")
        if self.log_level.upper() not in (
                "DEBUG", "INFO", "WARNING", "ERROR", "CRITICAL"):
            raise ValueError(f"unknown log_level {self.log_level!r}")
        multi = [self.coordinator != "", self.num_processes is not None,
                 self.process_id is not None]
        if any(multi) and not all(multi):
            raise ValueError("coordinator, num_processes and process_id "
                             "must be given together")
        if self.bus_uri and not (self.bus_uri.startswith("tcp://")
                                 or self.bus_uri.startswith("memory://")):
            raise ValueError(f"unsupported bus_uri {self.bus_uri!r}")
        return self

    def apply_env(self) -> None:
        """Export the service tunables so in-process workers and spawned
        children resolve the same values this node validated."""
        os.environ[self.env_name("serving_pipeline")] = \
            "auto" if self.serving_pipeline is None \
            else ("1" if self.serving_pipeline else "0")
        if self.checkpoint_trials:
            os.environ[self.env_name("checkpoint_trials")] = "1"
        else:
            os.environ.pop(self.env_name("checkpoint_trials"), None)
        if self.trace_dir:
            os.environ[self.env_name("trace_dir")] = self.trace_dir
        # Micro-batcher knobs: the PredictorService reads these at
        # construction (it may be built in a spawned child or an
        # in-process thread — env is the one transport both inherit).
        os.environ[self.env_name("serving_microbatch")] = \
            "1" if self.serving_microbatch else "0"
        os.environ[self.env_name("serving_shard_replicas")] = \
            "1" if self.serving_shard_replicas else "0"
        for f in ("serving_fill_window", "serving_fill_window_min",
                  "serving_max_batch", "serving_max_inflight",
                  "serving_queue_cap", "serving_client_share",
                  "serving_cache_bytes", "serving_cache_ttl_s",
                  "serving_cache_admit_after"):
            os.environ[self.env_name(f)] = str(getattr(self, f))
        # Read at construction by Predictor / InferenceWorker directly
        # (not through the app-layer _env_knob helper), so RTA505
        # tracks these two by name.
        os.environ[self.env_name("serving_tier_threshold")] = \
            str(self.serving_tier_threshold)
        os.environ[self.env_name("worker_reregister")] = \
            str(self.worker_reregister)
        # Read at construction by ServicesManager (the lease window)
        # and InferenceWorker (the pipeline auto-probe threshold) — env
        # is the transport both in-process threads and spawned children
        # inherit, so RTA505 tracks these two by name.
        os.environ[self.env_name("node_lease")] = str(self.node_lease)
        os.environ[self.env_name("pipeline_sync_min")] = \
            str(self.pipeline_sync_min)
        # Generative serving: the InferenceWorker reads the gate and
        # the engine shape at construction (observe.lm resolves the
        # gate once at first use); the flag pops when off so "absent =
        # disabled" stays the contract for hand-launched children.
        if self.serving_generate:
            os.environ[self.env_name("serving_generate")] = "1"
        else:
            os.environ.pop(self.env_name("serving_generate"), None)
        # Spelled out one by one (not a loop) so RTA505 can track each
        # export by name, like the other construction-time knobs above.
        os.environ[self.env_name("generate_page_size")] = \
            str(self.generate_page_size)
        os.environ[self.env_name("generate_pool_pages")] = \
            str(self.generate_pool_pages)
        os.environ[self.env_name("generate_decode_batch")] = \
            str(self.generate_decode_batch)
        os.environ[self.env_name("generate_max_new")] = \
            str(self.generate_max_new)
        # Autoscaler: the platform constructs the controller from these
        # at startup (admin/autoscaler.py Autoscaler.from_env); the
        # enable flag is popped when off so "absent = disabled" stays
        # the contract for hand-launched children.
        if self.autoscale:
            os.environ[self.env_name("autoscale")] = "1"
        else:
            os.environ.pop(self.env_name("autoscale"), None)
        os.environ[self.env_name("autoscale_dry_run")] = \
            "1" if self.autoscale_dry_run else "0"
        for f in ("autoscale_max_replicas", "autoscale_step",
                  "autoscale_up_cooldown_s", "autoscale_down_cooldown_s",
                  "autoscale_queue_high", "autoscale_queue_low",
                  "autoscale_p99_high_ms", "autoscale_mfu_floor",
                  "autoscale_idle_sweeps",
                  "autoscale_predict_horizon_s",
                  "autoscale_predict_ramp_ratio"):
            os.environ[self.env_name(f)] = str(getattr(self, f))
        # Periodicity table path pops when empty so "absent = trend
        # signal only" stays the contract for hand-launched children.
        if self.autoscale_periodicity.strip():
            os.environ[self.env_name("autoscale_periodicity")] = \
                self.autoscale_periodicity
        else:
            os.environ.pop(self.env_name("autoscale_periodicity"), None)
        # Read per allocate() call by the chip allocator (a layer that
        # must work without a NodeConfig), so RTA505 tracks it by name.
        os.environ[self.env_name("max_chip_share")] = \
            str(self.max_chip_share)
        # Packed wire + quantization: Cache/Predictor/InferenceWorker
        # snapshot these at construction (observe.wire normalizes the
        # spellings); the quant knob pops when empty so a worker's
        # getenv default ("" = serve trained dtype) stays the contract.
        from .observe.wire import packed_wire_mode, stacked_mode

        os.environ[self.env_name("serving_packed_wire")] = \
            packed_wire_mode(self.serving_packed_wire)
        if self.serving_quant.strip():
            os.environ[self.env_name("serving_quant")] = \
                self.serving_quant
        else:
            os.environ.pop(self.env_name("serving_quant"), None)
        # Stacked serving: the InferenceWorker snapshots this at
        # construction (observe.wire normalizes the spellings).
        os.environ[self.env_name("serving_stacked")] = \
            "on" if stacked_mode(self.serving_stacked) else "off"
        # The adaptive ceiling defaults to the legacy fixed knob; only
        # an explicit override is exported (consumers fall back to
        # SERVING_FILL_WINDOW themselves).
        if self.serving_fill_window_max is not None:
            os.environ[self.env_name("serving_fill_window_max")] = \
                str(self.serving_fill_window_max)
        else:
            os.environ.pop(self.env_name("serving_fill_window_max"),
                           None)
        if self.serving_client_header:
            os.environ[self.env_name("serving_client_header")] = \
                self.serving_client_header
        else:
            os.environ.pop(self.env_name("serving_client_header"), None)
        # Trial-lifecycle knobs: the dataset/staging caches read their
        # budgets per call (model/dataset.py, model/jax_model.py); the
        # TrainWorker reads the prefetch toggle when its loop starts;
        # the ParamStore reads the write-behind toggle per save.
        os.environ[self.env_name("dataset_cache_bytes")] = \
            str(self.dataset_cache_bytes)
        os.environ[self.env_name("stage_cache_bytes")] = \
            str(self.stage_cache_bytes)
        os.environ[self.env_name("stage_bytes")] = str(self.stage_bytes)
        os.environ[self.env_name("advisor_prefetch")] = \
            "1" if self.advisor_prefetch else "0"
        os.environ[self.env_name("params_write_behind")] = \
            "1" if self.params_write_behind else "0"
        # Robustness: the fault plane and the tcp bus client read these
        # at construction; an empty plan is popped (absent = disabled),
        # matching the serving_client_header absent-means-off contract.
        if self.fault_plan.strip():
            os.environ[self.env_name("fault_plan")] = self.fault_plan
            os.environ[self.env_name("fault_seed")] = \
                str(self.fault_seed)
        else:
            os.environ.pop(self.env_name("fault_plan"), None)
            os.environ.pop(self.env_name("fault_seed"), None)
        os.environ[self.env_name("bus_retry_base_s")] = \
            str(self.bus_retry_base_s)
        os.environ[self.env_name("bus_retry_total_s")] = \
            str(self.bus_retry_total_s)
        # Cluster fabric: Predictor / PredictorService / ServicesManager
        # read the gate at construction; it pops when off so "absent =
        # disabled" stays the contract for hand-launched children (zero
        # node/relay/fabric series on an off node). The two tunables are
        # read at construction alongside it, so RTA505 tracks them by
        # name.
        if self.cluster_fabric:
            os.environ[self.env_name("cluster_fabric")] = "1"
        else:
            os.environ.pop(self.env_name("cluster_fabric"), None)
        os.environ[self.env_name("cluster_probe_timeout_s")] = \
            str(self.cluster_probe_timeout_s)
        os.environ[self.env_name("cluster_locality_boost")] = \
            str(self.cluster_locality_boost)
        # Observability: the /metrics route and bus/http instrumentation
        # check RAFIKI_TPU_METRICS at construction; the trace edges read
        # RAFIKI_TPU_TRACE_SAMPLE per request, the span sink its size
        # cap per flush.
        os.environ[self.env_name("metrics")] = \
            "1" if self.metrics else "0"
        os.environ[self.env_name("trace_sample")] = str(self.trace_sample)
        os.environ[self.env_name("trace_max_mb")] = str(self.trace_max_mb)
        # Span-store retention + tail sampling: the sink reads these
        # per roll / per mint, so late-spawned children and in-process
        # services resolve the same store shape. The tail knob pops at
        # 1.0 (absent = tail off) so the legacy eager-write contract
        # stays the default for hand-launched children.
        os.environ[self.env_name("trace_retain_segments")] = \
            str(self.trace_retain_segments)
        os.environ[self.env_name("trace_retain_mb")] = \
            str(self.trace_retain_mb)
        if self.trace_tail_sample < 1.0:
            os.environ[self.env_name("trace_tail_sample")] = \
                str(self.trace_tail_sample)
        else:
            os.environ.pop(self.env_name("trace_tail_sample"), None)
        os.environ[self.env_name("trace_tail_slow_ms")] = \
            str(self.trace_tail_slow_ms)
        # Exemplars + the attribution ledger resolve once at first use
        # (observe.metrics / observe.attribution); both pop when off so
        # "absent = disabled" stays the contract.
        if self.metrics_exemplars:
            os.environ[self.env_name("metrics_exemplars")] = "1"
        else:
            os.environ.pop(self.env_name("metrics_exemplars"), None)
        if self.serving_attribution:
            os.environ[self.env_name("serving_attribution")] = "1"
        else:
            os.environ.pop(self.env_name("serving_attribution"), None)
        # SLO plane: the platform constructs the engine from these at
        # startup (admin/slo_engine.py SloEngine.from_env); rules and
        # webhook pop when empty so "absent = disabled" stays the
        # contract for hand-launched children.
        if self.slo_rules.strip():
            os.environ[self.env_name("slo_rules")] = self.slo_rules
        else:
            os.environ.pop(self.env_name("slo_rules"), None)
        if self.slo_webhook_url:
            os.environ[self.env_name("slo_webhook_url")] = \
                self.slo_webhook_url
        else:
            os.environ.pop(self.env_name("slo_webhook_url"), None)
        os.environ[self.env_name("slo_alert_log_mb")] = \
            str(self.slo_alert_log_mb)
        # Workload recorder: the predictor edge resolves the gate once
        # at first use (observe.workload); pops when off so "absent =
        # disabled" stays the contract (the attribution pattern). The
        # store knobs are read per roll by the sink.
        if self.workload_record:
            os.environ[self.env_name("workload_record")] = "1"
        else:
            os.environ.pop(self.env_name("workload_record"), None)
        os.environ[self.env_name("workload_max_mb")] = \
            str(self.workload_max_mb)
        os.environ[self.env_name("workload_retain_segments")] = \
            str(self.workload_retain_segments)
        # 0 = "no standalone metrics server": exporting "0" would make
        # worker runners bind port 0 (a random free port) — pop instead,
        # mirroring serving_client_header's absent-means-off contract.
        if self.metrics_port:
            os.environ[self.env_name("metrics_port")] = \
                str(self.metrics_port)
        else:
            os.environ.pop(self.env_name("metrics_port"), None)
