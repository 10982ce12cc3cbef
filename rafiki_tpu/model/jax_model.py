"""JaxModel: the JAX/flax implementation path of the BaseModel contract.

Parity + redesign: the reference's model zoo implements ``BaseModel``
directly against TF1/Torch with hand-rolled session/device management
(SURVEY.md §2 "Example models"). Here the SDK itself provides the
TPU-native scaffolding once, and zoo models only declare a flax module plus
knobs:

- ``train()`` runs a jit-compiled train step over a ``("dp", "tp")`` Mesh
  built from the service's chip group (``RAFIKI_TPU_CHIPS``), batch
  data-parallel with gradients psum-ed over ICI by XLA; donated state, so
  optimizer updates are in-place in HBM.
- Compute is bfloat16-friendly (modules take a ``dtype``; inputs stay f32
  and cast at the first matmul/conv) to keep the MXU fed.
- ``predict()`` AOT-compiles per batch-bucket (powers of two up to
  ``max_predict_batch``) and pads queries into the nearest bucket —
  variable serving load never retraces (SURVEY.md §7 "AOT-compiled
  serving").
- Parameters interchange as a flat ``{path: ndarray}`` dict
  (``flax.traverse_util.flatten_dict``), the ParamStore's native format.

Knob conventions the scaffolding understands (all optional):
``batch_size``, ``learning_rate``, ``max_epochs``, ``weight_decay``,
``early_stop_epochs``, ``quick_train`` (policy).
"""

from __future__ import annotations

import logging
import os
import time
from collections import OrderedDict
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import traverse_util
from flax.training import train_state

from ..observe import MfuMeter, flops_of_compiled, flops_of_lowered
from ..observe import metrics as _obs_metrics
from ..observe import phases as _phases
from ..observe import wire as _wire
from ..parallel import (batch_sharding, build_mesh, device_get_tree,
                        replicated,
                        shard_variables)
from ..parallel.chips import ChipGroup
from .base import BaseModel, Params
from .dataset import (ByteBudgetLRU, ImageDataset, dataset_fingerprint,
                      load_image_dataset)
from .logger import logger

_log = logging.getLogger(__name__)


class TrainState(train_state.TrainState):
    batch_stats: Any = None


# Process-level compiled-step cache. Repeat trials with the same static
# config (module, optimizer schedule, mesh) reuse the SAME jitted train /
# eval step objects — and, crucially, the same optax transformation object
# (TrainState carries ``tx`` as a static field, so a fresh tx per trial
# would defeat jit's cache even with identical graphs). This is what makes
# ENAS-style searches one-compile-total: the architecture encoding is a
# *traced input* (see ``extra_apply_inputs``), so hundreds of proposed
# architectures hit one XLA executable.
#
# Bounded LRU: searches over continuous knobs (e.g. a FloatKnob learning
# rate) produce a distinct key per trial; without eviction every trial
# would pin a compiled executable for the life of the worker.
_STEP_CACHE: "OrderedDict[Any, Dict[str, Any]]" = OrderedDict()
_STEP_CACHE_MAX = 16


def _step_cache_get(key: Any) -> Optional[Dict[str, Any]]:
    entry = _STEP_CACHE.get(key)
    if entry is not None:
        _STEP_CACHE.move_to_end(key)
    _phases.cache_event("step", "miss" if entry is None else "hit")
    return entry


def _step_cache_put(key: Any, entry: Dict[str, Any]) -> None:
    _STEP_CACHE[key] = entry
    _STEP_CACHE.move_to_end(key)
    while len(_STEP_CACHE) > _STEP_CACHE_MAX:
        _STEP_CACHE.popitem(last=False)


def clear_step_cache() -> None:
    _STEP_CACHE.clear()


# Process-level device staging cache. The compiled-step cache (above)
# made repeat trials one-compile-total; this makes them one-H2D-total:
# the replicated uint8 dataset arrays (plus int32 labels) a train or
# eval loop gathers from stay resident on the mesh across trials,
# keyed by (dataset fingerprint, mesh device ids). A rewritten dataset
# file (new mtime/size) or a different chip group is a different key —
# never a stale hit. Byte-budget LRU (bytes counted per replica, not
# times mesh size) so a worker cycling through many sub-train-jobs
# cannot pin HBM forever.
#
# The staged arrays are GUARANTEED never donated: the only donated
# argument of any compiled step is the train state (donate_argnums=(0,)
# on train_chunk), and the defensive is_deleted() check below re-stages
# if any future code path ever frees a cached buffer instead of
# serving it dangling.

STAGE_CACHE_ENV = "RAFIKI_TPU_STAGE_CACHE_BYTES"
STAGE_CACHE_DEFAULT = 2 << 30  # keep NodeConfig.stage_cache_bytes equal

#: key -> (data_dev, labels_dev); byte-budget LRU shared-impl with the
#: host dataset cache (dataset.ByteBudgetLRU) so the eviction logic
#: cannot drift between the two residency caches.
_STAGE_CACHE = ByteBudgetLRU("stage")


def _stage_cache_budget() -> int:
    try:
        return int(os.environ.get(STAGE_CACHE_ENV, STAGE_CACHE_DEFAULT))
    except ValueError:
        return STAGE_CACHE_DEFAULT


def clear_stage_cache() -> None:
    _STAGE_CACHE.clear()


def stage_cache_info() -> Dict[str, int]:
    return _STAGE_CACHE.info()


def staged_dataset_arrays(dataset_path: str, ds: ImageDataset, mesh):
    """Replicated device-resident ``(uint8 images, int32 labels)`` for
    one dataset on one mesh, cached across trials (see the cache
    comment above). Shared by ``train`` and ``evaluate`` — trial 2..N
    of a sub-train-job pays zero full-dataset host->device transfer.

    Keyed by the fingerprint the dataset was LOADED under
    (``ds.fingerprint``, stamped by the loaders) — never a fresh stat,
    which would cache old data under a new file identity when the file
    is rewritten between load and staging."""
    budget = _stage_cache_budget()
    nbytes = int(ds.images.nbytes) + 4 * int(ds.labels.shape[0])
    key = None
    if budget > 0 and nbytes <= budget:
        fp = getattr(ds, "fingerprint", None)
        if fp is None:
            # Dataset object not from the loaders (in-memory
            # construction); best effort on the file's current state.
            try:
                fp = dataset_fingerprint(dataset_path)
            except OSError:
                fp = None  # file vanished after load; stage uncached
        if fp is not None:
            key = (fp, tuple(int(d.id) for d in mesh.devices.flat))
    if key is not None:
        entry = _STAGE_CACHE.get(key)
        if entry is not None and not entry[0].is_deleted() \
                and not entry[1].is_deleted():
            _phases.cache_event("stage", "hit")
            return entry
        _phases.cache_event("stage", "miss")
    data_dev = jax.device_put(np.ascontiguousarray(ds.images),
                              replicated(mesh))
    labels_dev = jax.device_put(ds.labels.astype(np.int32),
                                replicated(mesh))
    if key is not None:
        _STAGE_CACHE.put(key, (data_dev, labels_dev), nbytes, budget)
    return data_dev, labels_dev


def staged_token_ids(dataset_path: str, ds, mesh):
    """Replicated device-resident int32 token stream for one
    :class:`~rafiki_tpu.model.dataset.TokenDataset` on one mesh, cached
    across trials in the SAME byte-budget LRU (and under the same
    ``stage`` hit/miss/evict counters) as the image arrays — the r9
    carried item, closed for the token/LM path. Keys carry a ``"token"``
    tag so an image entry and a token entry of one file can never
    collide. Eval 2..N of a sub-train-job then ships NO token data to
    the device at all: windows are gathered in-graph from the resident
    stream by device-computed iota indices (models/lm.py). The TRAIN
    loop deliberately keeps cutting windows on the host — gathering
    windows in-graph per step measured ~35x slower than the step
    itself (see the comment in ``JaxTransformerLM.train``)."""
    budget = _stage_cache_budget()
    ids = ds.ids if ds.ids.dtype == np.int32 \
        else ds.ids.astype(np.int32)
    nbytes = int(ids.nbytes)
    key = None
    if budget > 0 and nbytes <= budget:
        fp = getattr(ds, "fingerprint", None)
        if fp is None:
            try:
                fp = dataset_fingerprint(dataset_path)
            except OSError:
                fp = None  # file vanished after load; stage uncached
        if fp is not None:
            key = ("token", fp,
                   tuple(int(d.id) for d in mesh.devices.flat))
    if key is not None:
        entry = _STAGE_CACHE.get(key)
        if entry is not None and not entry[0].is_deleted():
            _phases.cache_event("stage", "hit")
            return entry[0]
        _phases.cache_event("stage", "miss")
    ids_dev = jax.device_put(np.ascontiguousarray(ids),
                             replicated(mesh))
    if key is not None:
        _STAGE_CACHE.put(key, (ids_dev,), nbytes, budget)
    return ids_dev


def step_cache_key(model: "BaseModel", kind: str, mesh, *parts: Any,
                   exclude: frozenset = frozenset()) -> Any:
    """The one cache-key convention for compiled steps, shared by every
    model class (JaxModel subclasses and the standalone sequence/tabular
    models): (class, kind, flax module, knobs-minus-excluded, mesh,
    extra static parts). ``mesh`` objects are interned by build_mesh, so
    identity is stable."""
    knob_items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in model.knobs.items() if k not in exclude))
    return (type(model), kind, model._module, knob_items, mesh, parts)


def pad_crop_flip_graph(x: Any, rng: Any, pad: int = 4,
                        min_size: int = 16) -> Any:
    """Reflect-pad random crop + horizontal flip (the CIFAR recipe) as
    XLA ops — augmentation runs ON DEVICE inside the train step, so the
    input pipeline ships uint8 indices instead of augmented float batches
    over the host link.

    Images smaller than ``min_size`` pass through UNAUGMENTED: a ±4
    crop is half the content of an 8x8 scan, and measured on the UCI
    digits it drives an otherwise-fine ENAS child from 0.93 to 0.21
    accuracy — the CIFAR recipe's constants only make sense at CIFAR
    scales (the 16 floor keeps 28x28 fashion-MNIST and 32x32 CIFAR
    augmented)."""
    b, h, w, _ = x.shape
    if min(h, w) < min_size:
        return x
    r_y, r_x, r_f = jax.random.split(rng, 3)
    padded = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     mode="reflect")
    ys = jax.random.randint(r_y, (b,), 0, 2 * pad + 1)
    xs = jax.random.randint(r_x, (b,), 0, 2 * pad + 1)
    rows = ys[:, None] + jnp.arange(h)                    # (b, h)
    cols = xs[:, None] + jnp.arange(w)                    # (b, w)
    out = padded[jnp.arange(b)[:, None, None],
                 rows[:, :, None], cols[:, None, :]]
    flip = jax.random.bernoulli(r_f, 0.5, (b,))
    return jnp.where(flip[:, None, None, None], out[:, :, ::-1, :], out)


def dynamic_int8_matmul(x: Any, wq: Any, scale: Any) -> Any:
    """Dequant-free int8 x int8 matmul with dynamic per-row activation
    quantization: the activation scale is computed in-graph (symmetric
    max-abs per row — no calibration pass needed), both operands enter
    the MXU as int8, the accumulator is int32, and the result is
    rescaled to f32 once. ``wq`` is an ``(in, out)`` int8 kernel with
    per-output-channel ``scale`` from
    :meth:`JaxModel.enable_serving_quant`. Module-specific
    ``quantized_apply`` overrides build their forward pass from this
    (see ``models/feedforward.py``)."""
    s_x = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s_x = jnp.maximum(s_x, 1e-8)
    xq = jnp.clip(jnp.round(x / s_x), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, wq, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * s_x * scale[None, :]


def dynamic_int8_conv(x: Any, wq: Any, scale: Any,
                      strides=(1, 1), padding="SAME") -> Any:
    """Dequant-free int8 x int8 NHWC convolution, the conv-zoo
    counterpart of :func:`dynamic_int8_matmul`: activations quantize
    dynamically per SAMPLE (symmetric max-abs over the sample's
    h/w/c — per-pixel scales would defeat the int8 conv's single
    rescale), both operands enter the convolution as int8 with an
    int32 accumulator, and the result rescales to f32 once with the
    per-output-channel weight ``scale``. ``wq`` is an ``(kh, kw, cin,
    cout)`` int8 kernel from :meth:`JaxModel.enable_serving_quant`
    (4-D conv kernels carry per-``cout`` scales exactly like the 2-D
    dense ones)."""
    s_x = jnp.max(jnp.abs(x), axis=(1, 2, 3), keepdims=True) / 127.0
    s_x = jnp.maximum(s_x, 1e-8)
    xq = jnp.clip(jnp.round(x / s_x), -127, 127).astype(jnp.int8)
    acc = jax.lax.conv_general_dilated(
        xq, wq, strides, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * s_x * scale[None, None, None, :]


def _canonicalize_state(state: Any, mesh) -> Any:
    """Pin every train-state leaf to a mesh NamedSharding and a strong
    dtype. ``TrainState.create`` leaves the step counter as a weak Python
    int and eagerly-initialised optimizer scalars with default (GSPMD)
    shardings; without this, the first train step of every trial traces a
    one-off variant before settling on the steady-state signature —
    i.e. one wasted XLA compile per trial."""
    from jax.sharding import NamedSharding

    def canon(a):
        if isinstance(a, jax.Array):
            sh = a.sharding
            if isinstance(sh, NamedSharding) and sh.mesh == mesh:
                return a
            return jax.device_put(a, replicated(mesh))
        if isinstance(a, (int, np.integer)):
            return jax.device_put(jnp.asarray(a, jnp.int32),
                                  replicated(mesh))
        if isinstance(a, (float, np.floating)):
            return jax.device_put(jnp.asarray(a, jnp.float32),
                                  replicated(mesh))
        return a

    return jax.tree.map(canon, state)


class JaxModel(BaseModel):
    """Base for flax-module-backed image classifiers.

    Subclasses implement ``create_module(n_classes, image_shape)`` and may
    override ``create_optimizer`` / ``augment_in_graph``.
    """

    max_predict_batch: int = 512

    def __init__(self, **knobs: Any):
        super().__init__(**knobs)
        if hasattr(type(self), "augment_batch"):
            # The host-side hook was replaced by the in-graph pipeline;
            # silently ignoring an override would train without the
            # model's augmentation.
            raise TypeError(
                f"{type(self).__name__} overrides the removed "
                "augment_batch hook; augmentation now runs on device — "
                "override augment_in_graph(x, rng) (see "
                "pad_crop_flip_graph) instead")
        self._variables: Optional[Dict[str, Any]] = None
        self._module = None
        self._meta: Dict[str, Any] = {}
        self._mesh = None
        # (bucket, is_u8, quant_mode) -> zero-copy runner closure over
        # the AOT-compiled executable + its device-resident weights.
        self._predict_cache: Dict[Any, Any] = {}
        self._sharded_vars = None
        self._extra_dev = None
        # Serving quantization: the REQUESTED mode survives parameter
        # reloads (a promote-spawned worker re-quantizes the incoming
        # bin's fresh params automatically); the derived device data
        # does not.
        self._quant_mode: Optional[str] = None
        self._quant_dev = None   # (qvars, scales, fvars, layers), device
        self._quant_host = None  # same tuple on host (one pass per
        #                          load; DROPPED after the device
        #                          upload — it is a full second weight
        #                          copy)
        self._quant_layers: Optional[Dict[str, str]] = None

    # --- Subclass API ---

    def create_module(self, n_classes: int, image_shape) -> Any:
        raise NotImplementedError

    def create_optimizer(self, steps_per_epoch: int,
                         max_epochs: int) -> optax.GradientTransformation:
        lr = float(self.knobs.get("learning_rate", 1e-3))
        total = max(1, steps_per_epoch * max_epochs)
        sched = optax.cosine_decay_schedule(lr, decay_steps=total, alpha=0.01)
        wd = float(self.knobs.get("weight_decay", 0.0))
        if wd > 0:
            return optax.adamw(sched, weight_decay=wd)
        return optax.adam(sched)

    def augment_in_graph(self, x: Any, rng: Any) -> Any:
        """In-graph (XLA) augmentation hook applied to each float batch
        inside the compiled train step; default identity. Runs on device
        so the input pipeline never ships augmented float data over the
        host link."""
        return x

    def extra_apply_inputs(self) -> Dict[str, np.ndarray]:
        """Extra *traced* inputs forwarded to every ``module.apply`` call
        as keyword arguments (train, evaluate, and predict).

        Values are passed as jit arguments, never baked into the graph —
        so a knob routed through here (e.g. the ENAS architecture
        encoding) can change per trial without a recompile. Knobs whose
        names appear in the returned dict are excluded from the
        compiled-step cache key for the same reason.
        """
        return {}

    # Knob names that enter the compiled step as traced optimizer
    # hyperparameters (optax.inject_hyperparams) instead of baked
    # schedule constants — continuous lr/wd searches then reuse ONE
    # executable across trials. Subclasses that opt in must build their
    # tx with ``traced_hyperparam_optimizer`` (whose hyperparameter
    # names must match this set) and list a default per name (models are
    # directly constructible without every knob).
    traced_knobs: frozenset = frozenset()
    traced_knob_defaults: Dict[str, float] = {}

    def traced_hyperparam_optimizer(self, steps_per_epoch: int,
                                    max_epochs: int, opt: str = "adam",
                                    warmup: bool = False,
                                    weight_decay: bool = False):
        """An optimizer whose lr (and optionally wd) live in the opt
        state: the normalised (peak=1) schedule bakes in, the per-trial
        values multiply it at trace time from ``opt_state.hyperparams``.
        """
        total = max(1, steps_per_epoch * max_epochs)
        if warmup:
            wsteps = max(1, min(total // 20, 5 * steps_per_epoch))
            sched01 = optax.warmup_cosine_decay_schedule(
                init_value=0.1, peak_value=1.0, warmup_steps=wsteps,
                decay_steps=total, end_value=1e-3)
        else:
            sched01 = optax.cosine_decay_schedule(1.0, decay_steps=total,
                                                  alpha=0.01)
        scale_by = {"adam": optax.scale_by_adam,
                    "sgdm": lambda: optax.trace(decay=0.9, nesterov=True),
                    }[opt]

        if weight_decay:
            def make(learning_rate, weight_decay):
                return optax.chain(
                    optax.add_decayed_weights(weight_decay),
                    scale_by(),
                    optax.scale_by_schedule(sched01),
                    optax.scale(-1.0 * learning_rate))
            return optax.inject_hyperparams(make)(learning_rate=0.0,
                                                  weight_decay=0.0)

        def make(learning_rate):
            return optax.chain(
                scale_by(),
                optax.scale_by_schedule(sched01),
                optax.scale(-1.0 * learning_rate))
        return optax.inject_hyperparams(make)(learning_rate=0.0)

    def _step_cache_key(self, kind: str, mesh, *parts: Any) -> Any:
        # Knobs routed through extra_apply_inputs are traced inputs, not
        # graph constants — exclude them so e.g. every ENAS architecture
        # hits one executable. Same for traced optimizer hyperparameters.
        exclude = set(self.extra_apply_inputs()) | self.traced_knobs
        return step_cache_key(self, kind, mesh, *parts,
                              exclude=frozenset(exclude))

    # --- Mesh / module plumbing ---

    @property
    def mesh(self):
        if self._mesh is None:
            group = ChipGroup.current()
            tp = int(self.knobs.get("tensor_parallel", 1))
            self._mesh = build_mesh(group.devices(), tp=tp)
        return self._mesh

    def _ensure_module(self, n_classes: int, image_shape) -> None:
        if self._module is None:
            self._module = self.create_module(n_classes, image_shape)
            self._meta.update(n_classes=int(n_classes),
                              image_shape=list(image_shape))

    # --- BaseModel: train ---

    def train(self, dataset_path: str, *,
              shared_params: Optional[Params] = None, **kwargs: Any) -> None:
        with _phases.span("load"):
            ds = load_image_dataset(dataset_path)
        self._ensure_module(ds.n_classes, ds.image_shape)
        mesh = self.mesh
        dp = mesh.shape["dp"]

        batch_size = int(self.knobs.get("batch_size", 128))
        # Never larger than the dataset, and divisible over dp shards.
        batch_size = min(batch_size, ds.size)
        batch_size = max(dp, (batch_size // dp) * dp)
        max_epochs = int(self.knobs.get("max_epochs", 5))
        if self.knobs.get("quick_train", False):
            # QUICK_TRAIN policy: short search-phase pass (ENAS-style);
            # trial_epochs controls its length, default 1.
            max_epochs = min(max_epochs,
                             int(self.knobs.get("trial_epochs", 1)))
        steps_per_epoch = max(1, ds.size // batch_size)

        extra_np = self.extra_apply_inputs()
        extra = {k: jnp.asarray(v) for k, v in extra_np.items()}

        init_rng = jax.random.key(int(self.knobs.get("seed", 0)))
        dummy = jnp.zeros((1, *ds.image_shape), jnp.float32)
        # Jitted (and process-cached) init: eager flax init dispatches
        # every layer op to the device one by one — hundreds of
        # dispatches for deep nets; as one compiled program it is a
        # single dispatch.
        init_key = self._step_cache_key("init", mesh, tuple(dummy.shape))
        ientry = _step_cache_get(init_key)
        if ientry is None:
            module = self._module
            init_jit = jax.jit(
                lambda rng, x, extra: module.init(rng, x, train=False,
                                                  **extra))
            ientry = {"init": init_jit}
            _step_cache_put(init_key, ientry)
        variables = ientry["init"](init_rng, dummy, extra)
        if shared_params is not None:
            variables = self._merge_shared(variables, shared_params)
        has_bs = "batch_stats" in variables

        # A caller may size the lr schedule to a LARGER total than this
        # run executes (``schedule_total_epochs``): successive-halving
        # rungs all live on ONE schedule shape and each rung's
        # checkpoint-resume continues it, so the rung sequence is
        # step-for-step an uninterrupted full-budget run (ASHA warm
        # starts; see advisor/asha.py).
        from .loop_ckpt import epoch_rng, schedule_epochs

        sched_epochs = schedule_epochs(kwargs, max_epochs)

        cache_key = self._step_cache_key(
            "train", mesh, steps_per_epoch, max_epochs, sched_epochs,
            has_bs)
        entry = _step_cache_get(cache_key)
        if entry is not None:
            tx, train_chunk = entry["tx"], entry["step"]
        else:
            tx = self.create_optimizer(steps_per_epoch, sched_epochs)
            module = self._module
            augment = self.augment_in_graph
            base_key = jax.random.key(int(self.knobs.get("seed", 0)) + 1)
            x_spec = batch_sharding(mesh)

            def one_step(state: TrainState, data, labels, sel, step_idx,
                         extra):
                # Gather this step's batch from the device-resident uint8
                # dataset, then normalize + augment in-graph: the host
                # ships int32 indices, not float image data (the remote
                # host link measures ~32 MB/s — float staging was the
                # training bottleneck, not compute).
                x = jnp.take(data, sel, axis=0).astype(jnp.float32) / 255.0
                x = jax.lax.with_sharding_constraint(x, x_spec)
                y = jax.lax.with_sharding_constraint(
                    jnp.take(labels, sel, axis=0), x_spec)
                step_rng = jax.random.fold_in(base_key, step_idx)
                aug_rng, drop_rng = jax.random.split(step_rng)
                x = augment(x, aug_rng)

                def loss_fn(params):
                    vs = {"params": params}
                    if has_bs:
                        vs["batch_stats"] = state.batch_stats
                        logits, upd = module.apply(
                            vs, x, train=True, mutable=["batch_stats"],
                            rngs={"dropout": drop_rng}, **extra)
                        new_bs = upd["batch_stats"]
                    else:
                        logits = module.apply(vs, x, train=True,
                                              rngs={"dropout": drop_rng},
                                              **extra)
                        new_bs = None
                    logits = logits.astype(jnp.float32)
                    loss = optax.softmax_cross_entropy_with_integer_labels(
                        logits, y).mean()
                    acc = (logits.argmax(-1) == y).mean()
                    return loss, (new_bs, acc)

                (loss, (new_bs, acc)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params)
                state = state.apply_gradients(grads=grads)
                if has_bs:
                    state = state.replace(batch_stats=new_bs)
                return state, loss, acc

            # K optimizer steps per device dispatch: lax.scan runs the
            # steps inside ONE XLA program over a (K, batch) index matrix.
            # This amortises the per-dispatch host latency; combined
            # with the in-graph gather it reduces per-epoch host traffic
            # to the index matrix (KB, not MB).
            # Scan compiles the body once regardless of K.
            @partial(jax.jit, donate_argnums=(0,))
            def train_chunk(state: TrainState, data, labels, sels, idxs,
                            extra):
                def body(state, inp):
                    sel, i = inp
                    state, loss, acc = one_step(state, data, labels, sel,
                                                i, extra)
                    return state, (loss, acc)

                state, (losses, accs) = jax.lax.scan(
                    body, state, (sels, idxs))
                # One stacked (2,) metrics array: the host reads loss and
                # acc in a single D2H (each separate readback costs a
                # full ~100ms flush window on the proxied TPU transport).
                return state, jnp.stack([losses.mean(), accs.mean()])

            entry = {"tx": tx, "step": train_chunk, "exec": {},
                     "flops": None}
            _step_cache_put(cache_key, entry)

        variables = shard_variables(variables, mesh)
        # apply_fn=None: the step closes over the module directly, and a
        # bound method in the TrainState's static metadata would break
        # pytree equality across trials (a retrace per trial).
        state = TrainState.create(
            apply_fn=None,
            params=variables["params"],
            batch_stats=variables.get("batch_stats"),
            tx=tx,
        )
        for name in self.traced_knobs:
            # Per-trial hyperparameters ride in the (traced) optimizer
            # state; the compiled step never sees them as constants.
            value = self.knobs.get(name, self.traced_knob_defaults.get(
                name, 0.0))
            state.opt_state.hyperparams[name] = jnp.asarray(
                float(value), jnp.float32)
        state = _canonicalize_state(state, mesh)

        logger.define_plot("Training", ["loss", "train_acc", "chip_util"],
                           x_axis="epoch")

        # Stage the whole dataset on device ONCE as uint8 (4x smaller
        # than float, paid a single time); every epoch afterwards ships
        # only an int32 index matrix — and with the cross-trial staging
        # cache, trial 2..N of a sub-train-job pays no full-dataset H2D
        # at all. Falls back to per-chunk staging for datasets over the
        # staging budget.
        stage_bytes = int(os.environ.get("RAFIKI_TPU_STAGE_BYTES",
                                         2 << 30))
        staged = ds.images.nbytes <= stage_bytes
        if staged:
            with _phases.span("stage"):
                data_dev, labels_dev = staged_dataset_arrays(
                    dataset_path, ds, mesh)
        chunk_steps = max(1, min(steps_per_epoch, 128))

        # AOT-compile per chunk length (at most two: full K + epoch tail),
        # cached with the step. The executable's own cost analysis
        # supplies FLOPs for the MFU / chip-utilization metric — XLA
        # reports one scan iteration's cost, i.e. per-step FLOPs.
        compiled_this_call = [False]

        def dispatch(state, data, labels, sels, idxs):
            sig = (int(sels.shape[0]), int(data.shape[0]))
            exe = entry["exec"].get(sig)
            if exe is None:
                compiled_this_call[0] = True
                try:
                    lowered = train_chunk.lower(state, data, labels, sels,
                                                idxs, extra)
                    exe = lowered.compile()
                    if entry["flops"] is None:
                        entry["flops"] = flops_of_compiled(exe) \
                            or flops_of_lowered(lowered)
                        meter.flops_per_step = entry["flops"]
                except Exception:
                    _log.warning("AOT chunk compile failed; jit fallback",
                                 exc_info=True)
                    exe = train_chunk
                entry["exec"][sig] = exe
            return exe(state, data, labels, sels, idxs, extra)

        meter = MfuMeter(entry.get("flops"), n_devices=mesh.size)
        # Registry metrics: per-step wall time and a periodically
        # published MFU gauge, labeled with whatever the caller bound
        # (the TrialRunner binds trial=<id>, so the admin's /status and
        # the dashboard can surface chip utilization per trial).
        _mlabels = _obs_metrics.bound_labels()
        _reg = _obs_metrics.registry()
        _step_hist = _reg.histogram(
            "rafiki_tpu_train_step_seconds",
            "Optimizer step wall time (chunk time / steps per chunk)")
        _mfu_gauge = _reg.gauge(
            "rafiki_tpu_train_mfu_ratio",
            "Model-FLOPs-utilization of the trial's chip group "
            "(published per epoch)")

        early_stop = int(self.knobs.get("early_stop_epochs", 0))
        best_loss, bad_epochs = float("inf"), 0

        # Optional mid-trial checkpointing (SURVEY.md §5): the caller
        # (TrialRunner with RAFIKI_TPU_CKPT=1, or a direct user) passes a
        # ``checkpoint_dir``; full train-state leaves are snapshotted
        # every ``checkpoint_every_epochs`` and a rerun with the same dir
        # resumes at the next epoch. Per-epoch host RNG and per-step
        # fold_in keys make the resumed schedule identical to an
        # uninterrupted run.
        ckpt_dir = kwargs.get("checkpoint_dir")
        ckpt_every = int(kwargs.get("checkpoint_every_epochs", 1))
        mgr = None
        start_epoch = 0
        if ckpt_dir and ckpt_every > 0:
            from ..store.checkpoint import CheckpointManager
            mgr = CheckpointManager(ckpt_dir)
            if mgr.latest_step() is not None:
                state, start_epoch, best_loss, bad_epochs = \
                    self._restore_ckpt(mgr, state)
                if early_stop and bad_epochs >= early_stop:
                    # The restored run had already early-stopped: an
                    # uninterrupted run would train nothing past this
                    # point, so neither does the resume (ASHA rungs stay
                    # step-identical even when rung r stopped early).
                    start_epoch = max_epochs

        t0 = time.time()
        last_epoch = None
        step = start_epoch * steps_per_epoch
        for epoch in range(start_epoch, max_epochs):
            order = epoch_rng(int(self.knobs.get("seed", 0)),
                              epoch).permutation(ds.size)
            need = steps_per_epoch * batch_size
            if need > ds.size:
                # Tiny dataset: wrap so every epoch still takes real
                # optimizer steps at full batch shape.
                order = np.resize(order, need)
            sel_all = order[:need].reshape(steps_per_epoch, batch_size)
            ep_loss, ep_acc, nw = 0.0, 0.0, 0
            s = 0
            while s < steps_per_epoch:
                t_chunk = time.monotonic()
                k = min(chunk_steps, steps_per_epoch - s)
                sel = sel_all[s:s + k]
                rep = replicated(mesh)
                if staged:
                    data, labels = data_dev, labels_dev
                    sels = jax.device_put(
                        np.ascontiguousarray(sel, np.int32), rep)
                else:
                    # Per-chunk staging for oversized datasets: ship this
                    # chunk's images (still uint8 — 4x less than float;
                    # normalize/augment stay on device) with identity
                    # indices, keeping the executable's shapes constant.
                    flat = sel.reshape(-1)
                    data = jax.device_put(
                        np.ascontiguousarray(ds.images[flat]), rep)
                    labels = jax.device_put(
                        ds.labels[flat].astype(np.int32), rep)
                    sels = jax.device_put(
                        np.arange(len(flat), dtype=np.int32)
                        .reshape(k, batch_size), rep)
                idxs = jax.device_put(
                    np.arange(step, step + k, dtype=np.int32), rep)
                state, metrics = dispatch(state, data, labels, sels, idxs)
                step += k
                s += k
                meter.tick(k)
                if compiled_this_call[0]:
                    # Any dispatch that paid an XLA compile (first chunk,
                    # epoch-tail chunk) is excluded from the MFU window.
                    compiled_this_call[0] = False
                    meter.reset()
                loss_acc = np.asarray(metrics)  # single D2H per chunk
                # The asarray above is the chunk's real sync point, so
                # the elapsed time is honest per-step wall time.
                # rta: disable=RTA301 bound trial= labels; TrialRunner removes them at trial end (worker/runner.py)
                _step_hist.observe(
                    (time.monotonic() - t_chunk) / k, **_mlabels)
                ep_loss += float(loss_acc[0]) * k
                ep_acc += float(loss_acc[1]) * k
                nw += k
            ep_loss /= max(nw, 1)
            ep_acc /= max(nw, 1)
            util = {"chip_util": round(meter.mfu, 6)} \
                if meter.mfu is not None else {}
            if meter.mfu is not None:
                _mfu_gauge.set(meter.mfu, **_mlabels)
            logger.log(epoch=epoch, loss=ep_loss, train_acc=ep_acc,
                       steps_per_sec=(step - start_epoch * steps_per_epoch)
                       / (time.time() - t0), **util)
            last_epoch = epoch
            if early_stop:
                if ep_loss < best_loss - 1e-4:
                    best_loss, bad_epochs = ep_loss, 0
                else:
                    bad_epochs += 1
                    if bad_epochs >= early_stop:
                        break
            if mgr is not None and (epoch + 1) % ckpt_every == 0 \
                    and epoch + 1 < max_epochs:
                self._save_ckpt(mgr, epoch, state, best_loss, bad_epochs)
        # The LAST state is snapshotted after the loop, only on request
        # (checkpoint_final_epoch): a plain trial is complete here, but a
        # successive-halving rung resumes exactly this state. Post-loop
        # placement covers both the early-stop break and a max_epochs
        # that is not a multiple of the cadence — the in-loop cadence
        # save alone would leave a stale final checkpoint either way.
        if mgr is not None and kwargs.get("checkpoint_final_epoch") \
                and last_epoch is not None:
            self._save_ckpt(mgr, last_epoch, state, best_loss, bad_epochs)

        # Results stay DEVICE-RESIDENT: the device->host pull was the
        # dominant cost of an ENAS trial (r5 profile). dump_parameters
        # hands the device arrays to the ParamStore, whose write-behind
        # flush does ONE packed background pull (store/params.py) while
        # the next trial already computes; in-process warm starts reuse
        # the device arrays with no transfer at all.
        variables = {"params": state.params}
        if has_bs:
            variables["batch_stats"] = state.batch_stats
        self._variables = variables
        self._invalidate_compiled()

    def _save_ckpt(self, mgr, epoch: int, state, best_loss: float,
                   bad_epochs: int) -> None:
        leaves = device_get_tree(jax.tree.leaves(state))  # ONE pull
        arrays = {f"leaf_{i}": np.asarray(leaf)
                  for i, leaf in enumerate(leaves)}
        arrays["es_best_loss"] = np.asarray(best_loss, np.float64)
        arrays["es_bad_epochs"] = np.asarray(bad_epochs, np.int64)
        try:
            mgr.save(epoch, arrays)
        except OSError:
            # Checkpoints are an optimization, never the result: a
            # failed snapshot (disk full, or a sibling worker's
            # end-of-job sweep deleting a scoped dir mid-save) must not
            # error the trial that trained fine. Losing the snapshot
            # just means the next resume cold-starts — the documented
            # fallback.
            _log.warning("checkpoint save to %s failed; continuing "
                         "without it", mgr.ckpt_dir, exc_info=True)

    def _restore_ckpt(self, mgr, state):
        """Returns (state, start_epoch, best_loss, bad_epochs); falls back
        to a fresh start when the snapshot's structure doesn't match (e.g.
        the checkpoint is from a different knob config) or the dir was
        swept between latest_step() and the read (a sibling worker's
        end-of-job scoped cleanup)."""
        try:
            saved_epoch, arrays = mgr.restore()
        except OSError:
            _log.warning("checkpoint in %s vanished mid-restore; "
                         "starting fresh", mgr.ckpt_dir)
            return state, 0, float("inf"), 0
        leaves, treedef = jax.tree.flatten(state)
        n_saved = sum(1 for k in arrays if k.startswith("leaf_"))
        if n_saved != len(leaves):
            _log.warning("checkpoint in %s has %d leaves, model has %d; "
                         "starting fresh", mgr.ckpt_dir, n_saved,
                         len(leaves))
            return state, 0, float("inf"), 0
        # safetensors round-trips 0-d arrays as shape (1,); restore each
        # leaf to its exact aval so the AOT step accepts the state.
        try:
            new_leaves = [
                jax.device_put(
                    np.asarray(arrays[f"leaf_{i}"])
                    .reshape(leaf.shape).astype(leaf.dtype), leaf.sharding)
                for i, leaf in enumerate(leaves)]
        except ValueError:
            # Same leaf count, different shapes (checkpoint from another
            # knob config reusing the dir) — fresh start, as documented.
            _log.warning("checkpoint in %s has incompatible leaf shapes; "
                         "starting fresh", mgr.ckpt_dir)
            return state, 0, float("inf"), 0
        state = jax.tree.unflatten(treedef, new_leaves)
        logger.log(msg=f"resumed from checkpoint epoch {saved_epoch}")
        best_loss = np.asarray(
            arrays.get("es_best_loss", np.inf)).reshape(-1)[0]
        bad_epochs = np.asarray(
            arrays.get("es_bad_epochs", 0)).reshape(-1)[0]
        return state, saved_epoch + 1, float(best_loss), int(bad_epochs)

    def _merge_shared(self, variables, shared_params: Params):
        """Warm-start: overlay shared params whose path+shape match."""
        flat = traverse_util.flatten_dict(variables, sep="/")
        n = 0
        for k, v in shared_params.items():
            if k.startswith("_"):
                continue
            if k in flat and tuple(flat[k].shape) == tuple(v.shape):
                flat[k] = jnp.asarray(v, dtype=flat[k].dtype)
                n += 1
        logger.log(msg=f"warm-started {n} shared tensors")
        return traverse_util.unflatten_dict(flat, sep="/")

    # --- BaseModel: evaluate ---

    def evaluate(self, dataset_path: str) -> float:
        assert self._variables is not None, "train() or load_parameters() first"
        with _phases.span("load"):
            ds = load_image_dataset(dataset_path)
        self._ensure_module(ds.n_classes, ds.image_shape)
        mesh = self.mesh
        if self._sharded_vars is None:
            self._sharded_vars = shard_variables(self._variables, mesh)
        variables = self._sharded_vars
        extra = {k: jnp.asarray(v)
                 for k, v in self.extra_apply_inputs().items()}

        dp = mesh.shape["dp"]
        bs = max(dp, (min(1024, ds.size) // dp) * dp)
        stage_bytes = int(os.environ.get("RAFIKI_TPU_STAGE_BYTES",
                                         2 << 30))
        staged = ds.images.nbytes <= stage_bytes

        # The compiled step is looked up per call, not memoized on the
        # instance: the staged and oversized variants have different
        # signatures, and one model may evaluate datasets on both
        # sides of the staging threshold.
        cache_key = self._step_cache_key("eval", mesh, staged)
        cached = _step_cache_get(cache_key)
        if cached is not None:
            eval_step = cached["step"]
        else:
            module = self._module
            x_spec = batch_sharding(mesh)

            if staged:
                # Mirrors the train step's input pipeline: the batch
                # is gathered BY INDEX from the device-resident uint8
                # dataset and normalised in-graph, so the host ships
                # int32 indices (KB) instead of image data — and the
                # staged arrays come from the cross-trial cache, so
                # repeat evaluations pay no dataset H2D at all.
                @jax.jit
                def eval_step(variables, data, labels, sel, w, extra):
                    x = jnp.take(data, sel, axis=0) \
                        .astype(jnp.float32) / 255.0
                    x = jax.lax.with_sharding_constraint(x, x_spec)
                    y = jax.lax.with_sharding_constraint(
                        jnp.take(labels, sel, axis=0), x_spec)
                    logits = module.apply(variables, x, train=False,
                                          **extra)
                    correct = (logits.argmax(-1) == y) \
                        .astype(jnp.float32) * w
                    return correct.sum()
            else:
                # Oversized dataset (no device residency): the batch
                # itself ships dp-SHARDED like the pre-r9 eval path —
                # replicating a batch that is oversized by definition
                # would pay dp x the H2D — but still uint8 with
                # on-device normalisation (4x fewer bytes than the old
                # float path).
                @jax.jit
                def eval_step(variables, x, y, w, extra):
                    xf = x.astype(jnp.float32) / 255.0
                    logits = module.apply(variables, xf, train=False,
                                          **extra)
                    correct = (logits.argmax(-1) == y) \
                        .astype(jnp.float32) * w
                    return correct.sum()

            _step_cache_put(cache_key, {"step": eval_step})

        if staged:
            with _phases.span("stage"):
                data_dev, labels_dev = staged_dataset_arrays(
                    dataset_path, ds, mesh)
        rep = replicated(mesh)
        x_shard = batch_sharding(mesh)
        correct = 0.0
        for start in range(0, ds.size, bs):
            n = min(bs, ds.size - start)
            w = np.zeros((bs,), np.float32)
            w[:n] = 1.0
            if staged:
                # Padding rows re-read index 0; the weight mask zeroes
                # their contribution.
                sel = np.zeros((bs,), np.int32)
                sel[:n] = np.arange(start, start + n, dtype=np.int32)
                correct += float(eval_step(
                    variables, data_dev, labels_dev,
                    jax.device_put(sel, rep),
                    jax.device_put(w, rep), extra))
            else:
                xb = np.zeros((bs, *ds.image_shape), np.uint8)
                xb[:n] = ds.images[start:start + n]
                yb = np.zeros((bs,), np.int32)
                yb[:n] = ds.labels[start:start + n]
                correct += float(eval_step(
                    variables,
                    jax.device_put(np.ascontiguousarray(xb), x_shard),
                    jax.device_put(yb, x_shard),
                    jax.device_put(w, x_shard), extra))
        return float(correct / ds.size)

    # --- BaseModel: predict ---

    def predict(self, queries: List[Any]) -> List[Any]:
        assert self._variables is not None, "train() or load_parameters() first"
        assert self._meta.get("n_classes"), "model has no trained metadata"
        if not queries:
            return []
        probs = self.predict_proba(self._stack_queries(queries))
        return [p.tolist() for p in probs]

    def _stack_queries(self, queries: List[Any]) -> np.ndarray:
        """Stack queries for the device, keeping all-uint8 batches uint8:
        the serving host link then ships 1/4 the bytes, and the compiled
        predict bucket normalises on chip (see ``_predict_bucket_submit``).
        One host copy per query (site="stack") — the packed serving path
        skips this entirely via ``predict_staged_submit``.
        """
        shape = self._meta["image_shape"]
        raws = [self._query_to_raw(q, shape) for q in queries]
        _wire.count_copies("stack", len(raws))
        if all(r.dtype == np.uint8 for r in raws):
            return np.stack(raws)
        return np.stack([
            r.astype(np.float32) / 255.0 if r.dtype == np.uint8 else r
            for r in raws])

    @staticmethod
    def _query_to_raw(q: Any, expected_shape) -> np.ndarray:
        arr = np.asarray(q)
        if arr.ndim == 2:
            arr = arr[..., None]
        if tuple(arr.shape) != tuple(expected_shape):
            raise ValueError(
                f"query shape {arr.shape} != {tuple(expected_shape)}")
        if arr.dtype == np.uint8:
            return arr
        return arr.astype(np.float32)

    def predict_submit(self, queries: List[Any]):
        """Dispatch prediction to the device; return a zero-arg finisher.

        JAX dispatch is async: the compiled call returns device futures
        immediately, and only the finisher's host transfer blocks. A
        serving loop can therefore overlap burst N's D2H readback with
        burst N+1's compute (see InferenceWorker) — on a
        high-sync-latency transport this roughly doubles QPS.
        """
        if not queries:
            return lambda: []
        imgs = self._stack_queries(queries)
        n = imgs.shape[0]
        handles = []
        for start in range(0, n, self.max_predict_batch):
            chunk = imgs[start:start + self.max_predict_batch]
            handles.append(self._predict_bucket_submit(chunk))

        def finish() -> List[Any]:
            probs = np.concatenate(
                [np.asarray(dev)[:count] for dev, count in handles],
                axis=0)
            return [p.tolist() for p in probs]

        return finish

    def predict_proba(self, images: np.ndarray) -> np.ndarray:
        """Batched probability prediction with bucketed AOT compilation."""
        n = images.shape[0]
        if n == 0:
            return np.zeros((0, self._meta["n_classes"]), np.float32)
        out = []
        for start in range(0, n, self.max_predict_batch):
            chunk = images[start:start + self.max_predict_batch]
            dev, count = self._predict_bucket_submit(chunk)
            out.append(np.asarray(dev)[:count])
        return np.concatenate(out, axis=0)

    #: Staging-buffer dtypes ``predict_staged_submit`` accepts (the
    #: InferenceWorker's packed fast path asks via ``predict_bucket``).
    predict_staged_dtypes = (np.uint8, np.float32)

    def predict_bucket(self, n: int,
                       dtype: Any = np.float32) -> Optional[int]:
        """Leading dim a host staging buffer must have for an
        ``n``-query staged burst (the compiled bucket: dp-aligned power
        of two), or None when the staged path cannot take it — n over
        the single-dispatch cap, an unsupported dtype, or an unloaded
        model — and the caller must fall back to ``predict_submit``."""
        if self._variables is None or not self._meta.get("n_classes"):
            return None
        if n < 1 or n > self.max_predict_batch:
            return None
        if np.dtype(dtype) not in [np.dtype(d)
                                   for d in self.predict_staged_dtypes]:
            return None
        bucket = self.mesh.shape["dp"]
        while bucket < n:
            bucket *= 2
        return bucket

    def predict_staged_submit(self, buf: np.ndarray, n: int):
        """Dispatch one staged burst straight from a reusable host
        staging buffer: ``buf``'s leading dim is exactly
        ``predict_bucket(n, buf.dtype)`` and rows ``[n:]`` are padding
        (stale rows are fine — their outputs are sliced away). The
        device_put reads the buffer in place — no ``np.stack``, no
        pad-``concatenate``; this is the ``predict_into`` entry of the
        packed serving hot path. Returns a zero-arg finisher like
        ``predict_submit``."""
        assert self._variables is not None, \
            "train() or load_parameters() first"
        shape = tuple(self._meta["image_shape"])
        if buf.shape[1:] != shape:
            if int(np.prod(buf.shape[1:])) == int(np.prod(shape)):
                buf = buf.reshape((buf.shape[0], *shape))  # view
            else:
                raise ValueError(
                    f"staged rows {buf.shape[1:]} != {shape}")
        expect = self.predict_bucket(n, buf.dtype)
        if expect is None or buf.shape[0] != expect:
            raise ValueError(
                f"staging buffer leading dim {buf.shape[0]} != bucket "
                f"{expect} for n={n}")
        dev, count = self._dispatch_bucket(buf, n)

        def finish() -> List[Any]:
            return [p.tolist() for p in np.asarray(dev)[:count]]

        return finish

    def _predict_bucket_submit(self, chunk: np.ndarray):
        n = chunk.shape[0]
        dp = self.mesh.shape["dp"]
        bucket = dp
        while bucket < n:
            bucket *= 2
        if n < bucket:
            _wire.count_copies("pad", 1)
            chunk = np.concatenate(
                [chunk, np.zeros((bucket - n, *chunk.shape[1:]), chunk.dtype)])
        return self._dispatch_bucket(chunk, n)

    def _dispatch_bucket(self, chunk: np.ndarray, n: int):
        """``chunk``'s leading dim is exactly a bucket; look up (or
        build) the compiled runner for ``(bucket, dtype, quant)`` and
        dispatch. Returns ``(device future, n)``."""
        bucket = chunk.shape[0]
        is_u8 = chunk.dtype == np.uint8
        key = (bucket, is_u8, self._quant_mode)
        runner = self._predict_cache.get(key)
        if runner is None:
            runner = self._build_predict_runner(bucket, chunk.shape[1:],
                                                is_u8)
            self._predict_cache[key] = runner
        x = jax.device_put(chunk, batch_sharding(self.mesh))
        return runner(x), n  # device future + count

    def _build_predict_runner(self, bucket: int, feat_shape, is_u8: bool):
        """AOT-compile one predict executable and close over its
        device-resident weights: f32/bf16 apply by default, the
        ``(bucket, dtype, quant)`` int8 variant when serving
        quantization is enabled (weights enter the graph as int8 +
        per-channel scales; the module either runs its own dequant-free
        ``quantized_apply`` or falls back to in-graph dequantized f32
        weights per layer)."""
        mesh = self.mesh
        module = self._module
        if self._extra_dev is None:
            # Device-put once per compiled lifetime: this is the AOT
            # serving hot path and the extras are per-model constants.
            self._extra_dev = {
                k: jax.device_put(jnp.asarray(v), replicated(mesh))
                for k, v in self.extra_apply_inputs().items()}
        extra = self._extra_dev
        x_shape = jax.ShapeDtypeStruct(
            (bucket, *feat_shape), jnp.uint8 if is_u8 else jnp.float32,
            sharding=batch_sharding(mesh))
        struct = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=a.sharding)

        if self._quant_mode is not None:
            qvars, scales, fvars, _layers = self._quant_device_arrays()
            quantized_apply = self.quantized_apply

            def predict_fn(qvars, scales, fvars, x, extra):
                xf = x.astype(jnp.float32)
                if is_u8:
                    xf = xf / 255.0
                logits = quantized_apply(qvars, scales, fvars, xf, extra)
                if logits is None:
                    # Generic weight-only fallback: reconstruct each
                    # quantized kernel in-graph (one VPU multiply per
                    # layer) and run the module unchanged — int8
                    # resident weights, module-dtype matmuls.
                    flat = dict(fvars)
                    for k, wq in qvars.items():
                        flat[k] = wq.astype(jnp.float32) * scales[k]
                    variables = traverse_util.unflatten_dict(flat,
                                                             sep="/")
                    logits = module.apply(variables, xf, train=False,
                                          **extra)
                return jax.nn.softmax(
                    logits.astype(jnp.float32), axis=-1)

            compiled = jax.jit(predict_fn).lower(
                jax.tree.map(struct, qvars),
                jax.tree.map(struct, scales),
                jax.tree.map(struct, fvars),
                x_shape, jax.tree.map(struct, extra)).compile()
            return lambda x: compiled(qvars, scales, fvars, x, extra)

        # One sharded device copy of the parameters serves every bucket.
        if self._sharded_vars is None:
            self._sharded_vars = shard_variables(self._variables, mesh)
        variables = self._sharded_vars

        # uint8 batches ship raw (4x fewer bytes over the host link) and
        # normalise on chip — one compiled executable per (bucket, dtype).
        def predict_fn(variables, x, extra):
            xf = x.astype(jnp.float32)
            if is_u8:
                xf = xf / 255.0
            logits = module.apply(variables, xf, train=False, **extra)
            return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

        # AOT-compile for this bucket shape so serving never retraces.
        compiled = jax.jit(predict_fn).lower(
            jax.tree.map(struct, variables), x_shape,
            jax.tree.map(struct, extra)).compile()
        return lambda x: compiled(variables, x, extra)

    # --- Serving quantization (int8 ensemble mode) ---

    def enable_serving_quant(self, mode: str = "int8") -> Dict[str, Any]:
        """Post-training serving quantization: per-channel symmetric
        int8 scales over every 2-D ``kernel`` leaf, computed from the
        CURRENTLY loaded parameters (the InferenceWorker calls this at
        load time, so a promotion's fresh worker re-computes scales for
        the incoming bin by construction). Predict executables compile
        as additional ``(bucket, dtype, quant)`` variants; training and
        evaluation are untouched. Returns the per-layer report
        (``{"mode", "layers": {path: "int8"|"f32"}, ...}``).
        ``mode=None``/``""`` disables again."""
        if not mode:
            if self._quant_mode is not None:
                self._quant_mode = None
                self._quant_dev = None
                self._quant_host = None
                self._quant_layers = None
                self._predict_cache.clear()
            return {"mode": None, "layers": {}}
        if mode != "int8":
            raise ValueError(f"unsupported serving quant mode {mode!r}")
        assert self._variables is not None, \
            "train() or load_parameters() first"
        if self._quant_mode != mode:
            self._quant_mode = mode
            self._quant_dev = None
            self._quant_host = None
            self._quant_layers = None
            self._predict_cache.clear()
        return self.quant_report()

    def quant_report(self) -> Dict[str, Any]:
        if self._quant_mode is None or self._variables is None:
            return {"mode": None, "layers": {}}
        layers = self._quant_layers
        if layers is None:
            _, _, _, layers = self._quant_host_arrays()
        n_int8 = sum(1 for v in layers.values() if v == "int8")
        return {"mode": self._quant_mode, "layers": dict(layers),
                "n_int8": n_int8, "n_f32": len(layers) - n_int8}

    def _quant_host_arrays(self):
        """``(qvars, scales, fvars, layers)`` as flat ``path -> array``
        host dicts, computed ONCE per loaded parameters (the report at
        load time and the first compile share it). Eligible leaves —
        2-D dense and 4-D conv floating ``kernel``s — carry int8
        weights + per-output-channel symmetric scales
        (``max|W[..., j]| / 127`` over every non-output axis; the conv
        eligibility is the r13 carry that moves the conv zoo off the
        all-f32 path); everything else (biases, norms, batch_stats,
        expert stacks) passes through in f32: the per-layer fallback
        the wire contract promises."""
        if self._quant_host is not None:
            return self._quant_host
        flat = traverse_util.flatten_dict(self._variables, sep="/")
        qvars: Dict[str, np.ndarray] = {}
        scales: Dict[str, np.ndarray] = {}
        fvars: Dict[str, np.ndarray] = {}
        layers: Dict[str, str] = {}
        for k, v in flat.items():
            arr = np.asarray(v)
            if k.endswith("kernel") and arr.ndim in (2, 4) and \
                    np.issubdtype(arr.dtype, np.floating):
                w = arr.astype(np.float32)
                s = np.max(np.abs(w),
                           axis=tuple(range(w.ndim - 1))) / 127.0
                s = np.where(s <= 0, 1.0, s).astype(np.float32)
                qvars[k] = np.clip(np.round(w / s), -127, 127) \
                    .astype(np.int8)
                scales[k] = s
                layers[k] = "int8"
            else:
                fvars[k] = arr
                layers[k] = "f32"
        self._quant_host = (qvars, scales, fvars, layers)
        self._quant_layers = layers
        return self._quant_host

    def _quant_device_arrays(self):
        if self._quant_dev is None:
            qvars, scales, fvars, layers = self._quant_host_arrays()
            rep = replicated(self.mesh)
            put = lambda d: {k: jax.device_put(v, rep)  # noqa: E731
                             for k, v in d.items()}
            # Replicated on purpose: int8 serving targets small/medium
            # ensemble models; tensor-parallel int8 sharding is not
            # supported (the f32 path keeps shard_variables' rules).
            self._quant_dev = (put(qvars), put(scales), put(fvars),
                               layers)
            # The host tuple is a full second weight copy; once the
            # device arrays exist only the per-layer labels are needed
            # (quant_report) — a long-lived worker must not hold 2x.
            self._quant_host = None
        return self._quant_dev

    def quantized_apply(self, qvars: Dict[str, Any],
                        scales: Dict[str, Any], fvars: Dict[str, Any],
                        x: Any, extra: Dict[str, Any]) -> Optional[Any]:
        """Module-specific dequant-free int8 forward pass: return the
        logits built from int8 kernels (see ``dynamic_int8_matmul``),
        or None (the default) to use the generic dequantized-weights
        fallback. Called at TRACE time inside the compiled predict
        variant, so the choice is static per executable."""
        return None

    # --- Stacked-ensemble congruence metadata ---

    #: Whether members of this class may be vmap-stacked into one
    #: compiled program (``stack_members``). True for the JaxModel zoo
    #: by default — the structural probe still has the final word.
    stack_compatible: bool = True

    def stack_signature(self) -> Any:
        """Static family identity for the stacked-ensemble congruence
        probe: two members stack only if their signatures compare
        equal. The default — concrete class, the flax module (dataclass
        equality covers every static attr: supernet widths, depths,
        dtypes), and the served output contract — is sufficient for
        zoo models whose per-trial knobs are traced inputs; subclasses
        with extra static serving state must extend it."""
        return (type(self).__name__, self._module,
                int(self._meta.get("n_classes", 0)),
                tuple(self._meta.get("image_shape", ())))

    def warmup(self) -> None:
        """Pre-compile the smallest predict bucket (both the uint8 and
        float32 input variants — and, with serving quantization
        enabled, their ``(bucket, dtype, quant)`` variants, since the
        quant mode is part of the compile key) so a serving worker pays
        the XLA compiles before registering for traffic."""
        shape = self._meta.get("image_shape")
        if self._variables is None or not shape:
            return
        self.predict_proba(np.zeros((1, *shape), np.float32))
        finish = self._predict_bucket_submit(
            np.zeros((1, *shape), np.uint8))
        np.asarray(finish[0])

    # --- BaseModel: parameters ---

    def dump_parameters(self) -> Params:
        assert self._variables is not None
        flat = traverse_util.flatten_dict(self._variables, sep="/")
        # Device leaves pass through AS DEVICE ARRAYS — the ParamStore
        # write-behind (or any numpy consumer via np.asarray) decides
        # when bytes actually cross to the host; host leaves (a loaded
        # checkpoint) normalise to numpy as before.
        out: Params = {k: v if isinstance(v, jax.Array) else np.asarray(v)
                       for k, v in flat.items()}
        out["_meta/n_classes"] = np.asarray(self._meta["n_classes"])
        out["_meta/image_shape"] = np.asarray(self._meta["image_shape"])
        return out

    def load_parameters(self, params: Params) -> None:
        meta_n = params.get("_meta/n_classes")
        meta_shape = params.get("_meta/image_shape")
        assert meta_n is not None and meta_shape is not None, \
            "params missing _meta entries"
        # safetensors round-trips 0-d arrays as shape (1,); accept both.
        self._meta = {"n_classes": int(np.asarray(meta_n).reshape(-1)[0]),
                      "image_shape": [int(x) for x in np.asarray(meta_shape)]}
        flat = {k: np.asarray(v) for k, v in params.items()
                if not k.startswith("_meta/")}
        self._variables = traverse_util.unflatten_dict(flat, sep="/")
        self._module = None  # rebuild for the loaded checkpoint's shape
        self._ensure_module(self._meta["n_classes"], self._meta["image_shape"])
        self._invalidate_compiled()

    def _invalidate_compiled(self) -> None:
        self._predict_cache.clear()
        self._sharded_vars = None
        self._extra_dev = None
        # Derived quant data follows the parameters; the requested MODE
        # survives, so freshly loaded params re-quantize on first use.
        self._quant_dev = None
        self._quant_host = None
        self._quant_layers = None

    def destroy(self) -> None:
        self._invalidate_compiled()
        self._variables = None
        self._module = None


# --- Stacked ensembles (compiled megabatch serving) -------------------
#
# Same-family ensemble bins — the common AutoML case, where the best-N
# trials of one search all share a model family and differ only in
# weights — used to serve as N separately compiled runners time-slicing
# one chip group (_PackedEnsemble): one dispatch and one weight-set
# residency per member per burst. Here the member weights stack along a
# leading model axis at load time (ONE device_put of the stacked
# pytree) and ONE jax.vmap-over-the-model-axis program compiles per
# (bucket, dtype, quant) — a multi-bin burst on one chip becomes ONE
# device dispatch producing per-member probabilities, which the
# worker's _finish_members consumes unchanged (per-member confidence,
# __members__ envelopes, fault isolation via the member-validity
# mask). docs/serving.md "Stacked ensembles".


def stack_congruence(models: List[Any]) -> Optional[str]:
    """The congruence probe: None when ``models`` can serve as one
    vmap-stacked program, else a human-readable reason (the worker
    logs it and falls back to per-member runners). Congruent means:
    same concrete JaxModel family (``stack_signature`` equality — the
    flax module's static attrs included), shape/dtype-congruent param
    trees, same extra-input signature, and one serving quant mode."""
    if len(models) < 2:
        return "fewer than two members"
    for i, m in enumerate(models):
        if not isinstance(m, JaxModel):
            return (f"member {i} ({type(m).__name__}) is not a "
                    f"JaxModel (sk-style/sequence members serve "
                    f"per-member)")
        if not getattr(m, "stack_compatible", False):
            return (f"member {i} ({type(m).__name__}) opts out of "
                    f"stacking")
        if m._variables is None or m._module is None:
            return f"member {i} has no loaded parameters"
    m0 = models[0]
    sig0 = m0.stack_signature()
    flat0 = traverse_util.flatten_dict(m0._variables, sep="/")
    extra0 = m0.extra_apply_inputs()
    for i, m in enumerate(models[1:], start=1):
        if type(m) is not type(m0):
            return (f"member {i} is {type(m).__name__}, member 0 is "
                    f"{type(m0).__name__}")
        if m.stack_signature() != sig0:
            return f"member {i} has a different stack signature"
        if m._quant_mode != m0._quant_mode:
            return f"member {i} has a different serving quant mode"
        flat = traverse_util.flatten_dict(m._variables, sep="/")
        if set(flat) != set(flat0):
            return f"member {i} has a different parameter tree"
        for k, v0 in flat0.items():
            v = flat[k]
            if tuple(np.shape(v)) != tuple(np.shape(v0)) or \
                    np.asarray(v).dtype != np.asarray(v0).dtype:
                return (f"member {i} leaf {k}: "
                        f"{np.shape(v)}/{np.asarray(v).dtype} != "
                        f"{np.shape(v0)}/{np.asarray(v0).dtype}")
        extra = m.extra_apply_inputs()
        if set(extra) != set(extra0):
            return f"member {i} has different extra apply inputs"
        for k, v0 in extra0.items():
            if tuple(np.shape(extra[k])) != tuple(np.shape(v0)):
                return f"member {i} extra input {k} shape differs"
    return None


def stack_members(models: List[Any]) -> Optional["StackedMembers"]:
    """Build the stacked execution group for shape-congruent
    same-family members, or None (with the probe's reason logged)
    when the group must serve per-member."""
    reason = stack_congruence(models)
    if reason is not None:
        _log.info("ensemble not stackable (%s); serving per-member",
                  reason)
        return None
    return StackedMembers(models)


class StackedMembers:
    """N shape-congruent members as ONE device-resident stacked weight
    pytree plus vmapped-over-the-model-axis compiled runners.

    The member list is kept (host-side) for fallback serving and
    restacks; the device holds exactly one stacked copy of the weights
    (and, under int8 serving, one stacked copy of qvars/scales/fvars),
    uploaded with a single ``device_put`` of the stacked pytree.
    Runners read ``self._vars_dev`` at CALL time, so a promote-path
    restack (``update_member``: swap one member's slices in place)
    never recompiles and never re-uploads the other members.
    ``valid`` is the member-validity mask: a member whose restack
    failed mid-flight is masked out of the served votes (fault
    isolation) until a later restack lands."""

    def __init__(self, models: List[Any]):
        self.models = list(models)
        self.mesh = models[0].mesh
        self.valid: List[bool] = [True] * len(models)
        self._quant = models[0]._quant_mode
        self._runner_cache: Dict[Any, Any] = {}
        rep = replicated(self.mesh)
        stackf = lambda *xs: np.stack(  # noqa: E731
            [np.asarray(x) for x in xs])
        if self._quant:
            stacks = [m._quant_host_arrays() for m in models]
            qvars = {k: stackf(*[s[0][k] for s in stacks])
                     for k in stacks[0][0]}
            scales = {k: stackf(*[s[1][k] for s in stacks])
                      for k in stacks[0][1]}
            fvars = {k: stackf(*[s[2][k] for s in stacks])
                     for k in stacks[0][2]}
            self._vars_dev = jax.device_put(
                {"q": qvars, "s": scales, "f": fvars}, rep)
            for m in models:
                # The per-member host quant tuples are full extra
                # weight copies; the stacked device arrays are now the
                # serving truth (a fallback burst recomputes from
                # _variables).
                m._quant_host = None
        else:
            stacked = jax.tree.map(stackf,
                                   *[m._variables for m in models])
            self._vars_dev = jax.device_put(stacked, rep)
        extras = [m.extra_apply_inputs() for m in models]
        self._extra_dev = jax.device_put(
            {k: stackf(*[e[k] for e in extras]) for k in extras[0]},
            rep)

    @property
    def n_members(self) -> int:
        return len(self.models)

    @property
    def n_valid(self) -> int:
        return sum(1 for v in self.valid if v)

    def predict_bucket(self, n: int, dtype: Any = None) -> Optional[int]:
        """Same bucket ladder as the members (congruence guarantees
        they agree — one family, one mesh)."""
        return self.models[0].predict_bucket(n, dtype)

    # --- Dispatch ---

    def staged_submit(self, buf: np.ndarray, n: int):
        """One vmapped dispatch straight from the shared host staging
        buffer; returns the ``(M, bucket, n_classes)`` device future.
        Mirrors ``JaxModel.predict_staged_submit``'s contract (buffer
        leading dim is exactly the bucket, rows [n:] padding)."""
        m0 = self.models[0]
        shape = tuple(m0._meta["image_shape"])
        if buf.shape[1:] != shape:
            if int(np.prod(buf.shape[1:])) == int(np.prod(shape)):
                buf = buf.reshape((buf.shape[0], *shape))  # view
            else:
                raise ValueError(f"staged rows {buf.shape[1:]} != "
                                 f"{shape}")
        expect = self.predict_bucket(n, buf.dtype)
        if expect is None or buf.shape[0] != expect:
            raise ValueError(
                f"staging buffer leading dim {buf.shape[0]} != bucket "
                f"{expect} for n={n}")
        return self._dispatch(buf), n

    def submit(self, queries: List[Any]):
        """Per-query-object path (legacy frames / mixed bursts): stack
        on the host once, then ONE vmapped dispatch per
        max_predict_batch chunk. Returns ``[(device future, count)]``
        handles for ``member_finishers``."""
        m0 = self.models[0]
        imgs = m0._stack_queries(queries)
        handles = []
        for start in range(0, imgs.shape[0], m0.max_predict_batch):
            chunk = imgs[start:start + m0.max_predict_batch]
            n = chunk.shape[0]
            bucket = self.predict_bucket(n, chunk.dtype)
            if n < bucket:
                _wire.count_copies("pad", 1)
                chunk = np.concatenate(
                    [chunk, np.zeros((bucket - n, *chunk.shape[1:]),
                                     chunk.dtype)])
            handles.append((self._dispatch(chunk), n))
        return handles

    def _dispatch(self, chunk: np.ndarray):
        bucket = chunk.shape[0]
        is_u8 = chunk.dtype == np.uint8
        key = (bucket, is_u8, self._quant)
        runner = self._runner_cache.get(key)
        if runner is None:
            runner = self._build_runner(bucket, chunk.shape[1:], is_u8)
            self._runner_cache[key] = runner
        x = jax.device_put(chunk, batch_sharding(self.mesh))
        return runner(x)

    def member_finishers(self, handles) -> List[Any]:
        """Per-member zero-arg finishers over ONE shared device
        readback (the first finisher pays the D2H; the rest slice the
        fetched array) — the exact shape ``_finish_members`` consumes;
        per-handle counts come from the handles themselves. Invalid
        (masked) members are excluded up front: their votes drop
        without touching the healthy members' results."""
        if not isinstance(handles, list):
            handles = [handles]
        fetched: Dict[int, np.ndarray] = {}

        def fetch(j: int) -> np.ndarray:
            out = fetched.get(j)
            if out is None:
                out = np.asarray(handles[j][0])  # (M, bucket, C)
                fetched[j] = out
            return out

        fins = []
        for i, ok in enumerate(self.valid):
            if not ok:
                continue

            def fin(i=i) -> List[Any]:
                rows: List[Any] = []
                for j, (_, count) in enumerate(handles):
                    rows.extend(p.tolist() for p in fetch(j)[i, :count])
                return rows

            fins.append(fin)
        return fins

    def _build_runner(self, bucket: int, feat_shape, is_u8: bool):
        """AOT-compile ONE program for this (bucket, dtype, quant):
        the member forward vmapped over the leading model axis of the
        stacked weights (and stacked extras), the batch broadcast.
        The closure reads ``self._vars_dev`` per call so restacks swap
        weights without recompiling."""
        mesh = self.mesh
        m0 = self.models[0]
        module = m0._module
        x_shape = jax.ShapeDtypeStruct(
            (bucket, *feat_shape), jnp.uint8 if is_u8 else jnp.float32,
            sharding=batch_sharding(mesh))
        struct = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=a.sharding)

        if self._quant:
            quantized_apply = m0.quantized_apply

            def member_fn(packed, extra, x):
                qvars, scales, fvars = (packed["q"], packed["s"],
                                        packed["f"])
                xf = x.astype(jnp.float32)
                if is_u8:
                    xf = xf / 255.0
                logits = quantized_apply(qvars, scales, fvars, xf,
                                         extra)
                if logits is None:
                    flat = dict(fvars)
                    for k, wq in qvars.items():
                        flat[k] = wq.astype(jnp.float32) * scales[k]
                    variables = traverse_util.unflatten_dict(flat,
                                                             sep="/")
                    logits = module.apply(variables, xf, train=False,
                                          **extra)
                return jax.nn.softmax(logits.astype(jnp.float32),
                                      axis=-1)
        else:
            def member_fn(variables, extra, x):
                xf = x.astype(jnp.float32)
                if is_u8:
                    xf = xf / 255.0
                logits = module.apply(variables, xf, train=False,
                                      **extra)
                return jax.nn.softmax(logits.astype(jnp.float32),
                                      axis=-1)

        fn = jax.vmap(member_fn, in_axes=(0, 0, None))
        compiled = jax.jit(fn).lower(
            jax.tree.map(struct, self._vars_dev),
            jax.tree.map(struct, self._extra_dev), x_shape).compile()
        return lambda x: compiled(self._vars_dev, self._extra_dev, x)

    def warmup(self) -> None:
        """Pre-compile the smallest bucket's uint8 + float32 vmapped
        variants (the quant mode is part of the runner key by
        construction) and execute each once, so a stacked worker pays
        its XLA compiles before registering for traffic — the stacked
        counterpart of ``JaxModel.warmup``'s coverage."""
        shape = tuple(self.models[0]._meta["image_shape"])
        bucket = self.predict_bucket(1, np.float32)
        for dtype in (np.float32, np.uint8):
            np.asarray(self._dispatch(np.zeros((bucket, *shape),
                                               dtype)))

    # --- Promote-path restack ---

    def update_member(self, index: int, model: Any) -> None:
        """Swap member ``index``'s weights (and quant scales and
        extras) inside the stacked device arrays — the other members
        stay device-resident and every compiled runner stays valid
        (shapes unchanged; closures read the swapped tree per call).
        Raises on an incongruent incoming model BEFORE touching device
        state; a failure mid-update marks the member invalid (masked
        out of votes) rather than serving half-swapped weights."""
        if not (0 <= index < len(self.models)):
            raise IndexError(f"no stacked member {index}")
        ref = self.models[1] if index == 0 else self.models[0]
        reason = stack_congruence([ref, model])
        if reason is not None:
            raise ValueError(f"incoming member is not congruent with "
                             f"the stacked group: {reason}")
        # Fallible PREP first, before any device state moves: a
        # failure here (e.g. quantizing the incoming weights) raises
        # with the old member still fully valid — masking is reserved
        # for the genuinely half-swapped window below.
        if self._quant:
            q, s, f, _ = model._quant_host_arrays()
            new_host: Any = {"q": q, "s": s, "f": f}
        else:
            new_host = model._variables
        extra = model.extra_apply_inputs()
        try:
            setat = lambda st, new: st.at[index].set(  # noqa: E731
                jnp.asarray(np.asarray(new), dtype=st.dtype))
            self._vars_dev = jax.tree.map(
                lambda st, new: setat(st, new), self._vars_dev,
                new_host)
            self._extra_dev = {k: setat(st, extra[k])
                               for k, st in self._extra_dev.items()}
        except Exception:
            # Weights may be swapped while extras are not (or the
            # weight tree itself is part-updated): mask the member out
            # of votes rather than serve half-swapped state.
            self.valid[index] = False
            raise
        if self._quant:
            model._quant_host = None
        self.models[index] = model
        self.valid[index] = True

    def destroy(self) -> None:
        self._vars_dev = None
        self._extra_dev = None
        self._runner_cache.clear()
