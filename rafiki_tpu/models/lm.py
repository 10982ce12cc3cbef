"""JaxTransformerLM: flagship causal-LM — the compute-density proof.

Beyond-parity zoo model (upstream Rafiki has no language-modeling task
— SURVEY.md §2 "Example models" lists image/POS/tabular only). It
exists for a platform reason as much as a product one: the BASELINE
north star demands ≥90% chip utilization during training, and every
parity model (28×28/32×32 images, 2.4k-token corpora) is far too small
to put meaningful load on a 197-TFLOP/s MXU. This model is the zoo's
compute-dense citizen — the shape the benchmark's ``lm14-*`` cells
drive on one chip (``PERF.md`` §4, §5).

TPU-first design choices, all measured on a v5e-1 (2026-07-31):

- **Pallas flash attention, both passes** (``rafiki_tpu.ops``): the
  blockwise-XLA backward ran at ~5 TFLOP/s and dominated the step; the
  kernel backward moved the d_model=2048 step from 0.335 to 0.538
  spec-peak MFU.
- **Layers as a ``lax.scan`` over stacked params**: one compiled block
  regardless of depth — compile time stays ~10 s where an unrolled
  12-layer graph takes minutes.
- **Selective remat** (``remat`` knob): ``"dots"`` saves matmul
  outputs and recomputes elementwise ops in the backward —
  measurably better than full remat (0.538 vs 0.517 MFU) and 8×
  lighter than no remat (which OOMs 16 GB HBM at flagship shape).
- **K optimizer steps per dispatch** (``lax.scan`` in the train chunk,
  donated carry): amortizes per-dispatch host latency exactly like
  ``JaxModel``'s chunk dispatch (model/jax_model.py).
- **bf16 compute, f32 master params + Adam state**; logits and
  cross-entropy in f32.
- **Analytic MFU metering**: XLA's post-compile cost analysis cannot
  see through Pallas custom calls (it reported 0.63 of the real
  ~15 TFLOP/step at flagship shape), so ``chip_util`` uses the
  standard analytic count — ``6·N·tokens`` for the dense path plus
  the causal attention term — fed to the shared ``MfuMeter``.

Dataset: the packed token stream (``load_token_dataset``); queries are
token-id lists scored by mean next-token log-probability (a working
LM-scoring service through the ordinary Predictor path).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..model import (CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob,
                     PolicyKnob)
from ..model.base import BaseModel, Params
from ..model.dataset import load_token_dataset
from ..model.jax_model import (_stage_cache_budget, _step_cache_get,
                               _step_cache_put, staged_token_ids,
                               step_cache_key)
from ..model.logger import logger
from ..model.loop_ckpt import epoch_rng
from ..observe import MfuMeter
from ..observe import phases as _phases
from ..ops import batch_sharded_flash_attention
from ..parallel import DP_AXIS, batch_sharding, build_mesh, replicated
from ..parallel.chips import ChipGroup
from .transformer import _sinusoidal


@functools.lru_cache(maxsize=8)
def _jitted_param_init(v, d, L, mesh):
    """One jitted device-side initializer per shape and mesh
    (lru-cached: a fresh jit per model instance would re-trace ~2 s
    every AutoML trial). The whole tree is born
    replicated on ``mesh`` — the trial's own chip group — so nothing is
    staged through the process's default device."""
    shapes = {
        "embed": ((v, d), 0.02),
        "qkv": ((L, d, 3 * d), None),
        "proj": ((L, d, d), None),
        "w1": ((L, d, 4 * d), None),
        "w2": ((L, 4 * d, d), None),
    }

    @functools.partial(jax.jit, out_shardings=replicated(mesh))
    def init(seed):
        key = jax.random.key(seed)
        mats = {}
        for i, (name, (shape, scale)) in enumerate(shapes.items()):
            if scale is None:
                scale = 1.0 / math.sqrt(shape[-2])
            mats[name] = scale * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        return {
            "embed": mats.pop("embed"),
            "layers": {**mats,
                       "ln1": jnp.ones((L, d), jnp.float32),
                       "ln2": jnp.ones((L, d), jnp.float32)},
            "lnf": jnp.ones((d,), jnp.float32),
        }

    return init


@functools.lru_cache(maxsize=8)
def _jitted_eval_windows(n_win, t):
    """Cut ``n_win`` contiguous (t+1)-token windows from a resident
    stream into (inputs, targets), indices as in-graph iotas: they exist
    only on the stream's own devices (an eager ``jnp.arange`` or slice
    index is born on the default device)."""

    @jax.jit
    def windows(ids):
        sel = (jnp.arange(n_win, dtype=jnp.int32)[:, None] * t
               + jnp.arange(t + 1, dtype=jnp.int32)[None, :])
        wins = jnp.take(ids, sel, axis=0)  # (n_win, t+1)
        return wins[:, :-1], wins[:, 1:]   # inputs, targets

    return windows


def _layer_norm(x, g):
    xf = x.astype(jnp.float32)
    m = xf.mean(-1, keepdims=True)
    v = ((xf - m) ** 2).mean(-1, keepdims=True)
    return (xf - m) * jax.lax.rsqrt(v + 1e-6) * g


def _lm_block(x, lp, h_heads, mesh):
    d = x.shape[-1]
    h = _layer_norm(x, lp["ln1"]).astype(jnp.bfloat16)
    qkv = h @ lp["qkv"].astype(jnp.bfloat16)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(a):
        b, t, _ = a.shape
        return a.reshape(b, t, h_heads,
                         d // h_heads).transpose(0, 2, 1, 3)

    o = batch_sharded_flash_attention(
        heads(q), heads(k), heads(v), mesh, causal=True)
    b, nh, t, dh = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, t, nh * dh)
    x = x + (o @ lp["proj"].astype(jnp.bfloat16)).astype(x.dtype)
    h = _layer_norm(x, lp["ln2"]).astype(jnp.bfloat16)
    h = jax.nn.gelu(h @ lp["w1"].astype(jnp.bfloat16))
    return x + (h @ lp["w2"].astype(jnp.bfloat16)).astype(x.dtype)


def _remat(body, remat):
    """``body`` under the ``remat`` knob's backward-pass memory policy."""
    if remat == "full":
        return jax.checkpoint(body)
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies
            .dots_with_no_batch_dims_saveable)
    return body


def _lm_forward(params, ids, s, remat, mesh):
    """Logits (float32) of ``ids`` under ``params``: a function of its
    arguments alone (``s`` is ``_dims()``), so a program built on it
    holds no model instance."""
    # ×√d (Vaswani et al. §3.4): 0.02-scale embedding rows against
    # unit-scale sinusoidal PE would leave the token signal at ~2%
    # of the stream — below useful bf16 resolution after the first
    # residual add.
    x = params["embed"].astype(jnp.bfloat16)[ids] \
        * jnp.bfloat16(math.sqrt(s["d"]))
    pos = _sinusoidal(s["t"], s["d"])
    x = x + jnp.asarray(pos)[None, :ids.shape[1]].astype(x.dtype)

    body = _remat(functools.partial(_lm_block, h_heads=s["h"], mesh=mesh),
                  remat)

    def scan_body(x, lp):
        return body(x, lp), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    x = _layer_norm(x, params["lnf"]).astype(jnp.bfloat16)
    # Tied unembedding: logits in f32 for a stable softmax.
    return (x @ params["embed"].astype(jnp.bfloat16).T
            ).astype(jnp.float32)


def _lm_loss(weights, state, win, s, remat, mesh):
    """Next-token loss of one (B, t+1) window batch; input and target
    are shifted views. Returns ``(loss, (accuracy, counts, state))`` as
    every ``_loss_fn`` of this trainer does: ``counts`` is a vector the
    dispatch sums and hands the host beside loss and accuracy, ``state``
    what the step carries on without a gradient. The dense block has
    neither."""
    del state
    logits = _lm_forward(weights, win[:, :-1], s, remat, mesh)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, win[:, 1:]).mean()
    acc = (logits.argmax(-1) == win[:, 1:]).mean()
    return loss, (acc, None, None)


#: The top-level entry of a parameter tree that the optimizer never
#: sees: carried through the step, replaced by what the loss returns.
STATE = "state"


def _weights(params):
    """``params`` without its ``STATE`` entry: what takes gradients."""
    return {k: v for k, v in params.items() if k != STATE}


def _flat_names(tree, prefix=""):
    """A nested dict of arrays as {"a/b": leaf}, in the dict's order."""
    out = {}
    for key, value in tree.items():
        name = f"{prefix}{key}"
        if isinstance(value, dict):
            out.update(_flat_names(value, name + "/"))
        else:
            out[name] = value
    return out


class JaxTransformerLM(BaseModel):
    """Decoder-only causal transformer LM on the flash kernels.

    The trainer (``_train_setup`` / ``train`` / ``evaluate`` /
    ``predict`` / ``dump_parameters``) knows no block: a subclass with
    another architecture names its own module-level ``_forward_fn`` and
    ``_loss_fn`` (functions of (params, ids | window, dims, remat,
    mesh)) and overrides ``_dims``, ``_init_params`` and
    ``_flops_per_step`` (``models/lm_moe.py``)."""

    _forward_fn = staticmethod(_lm_forward)
    _loss_fn = staticmethod(_lm_loss)
    #: Why a deploy with generative serving on must refuse this class
    #: (``Admin.create_inference_job``); None: ``make_generator`` works.
    GENERATE_REFUSAL: Optional[str] = None

    @staticmethod
    def get_knob_config():
        return {
            # Flagship default shape: the smallest d_model whose
            # matmuls reach the chip's efficient regime (the measured
            # matmul roofline rises steeply with size on v5e).
            "d_model": CategoricalKnob([256, 512, 1024, 2048]),
            "n_layers": IntegerKnob(2, 16),
            "seq_len": CategoricalKnob([256, 512, 1024, 2048, 4096]),
            "batch_size": CategoricalKnob([2, 4, 8, 16]),
            "learning_rate": FloatKnob(1e-4, 1e-2, is_exp=True),
            # Optimizer steps, not epochs: an LM pass is windows over a
            # stream, so the budget is steps.
            "train_steps": IntegerKnob(20, 20000),
            "vocab_size": CategoricalKnob([512, 4096, 16384, 32768]),
            # Backward-pass memory policy: "dots" (save matmul outputs,
            # recompute elementwise — the measured best), "full"
            # (checkpoint whole blocks — smallest memory), "none"
            # (save everything — fastest when it fits).
            "remat": FixedKnob("dots"),
            # Optimizer steps fused into one device dispatch.
            "steps_per_dispatch": FixedKnob(8),
            # AutoML trial policy: the platform grants QUICK_TRAIN to
            # search trials, capping the budget at trial_steps.
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "trial_steps": FixedKnob(30),
            "seed": FixedKnob(0),
        }

    def __init__(self, **knobs: Any):
        super().__init__(**knobs)
        self._params = None  # f32 pytree (device-resident after train)
        self._predict_fn = None
        self._params_dev = None
        self._mesh = None
        self._module = None          # step_cache_key convention slot

    # --- shape plumbing ---

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = build_mesh(ChipGroup.current().devices())
        return self._mesh

    def _dims(self):
        d = int(self.knobs.get("d_model", 1024))
        return dict(
            d=d,
            h=max(1, d // 128),
            layers=int(self.knobs.get("n_layers", 8)),
            t=int(self.knobs.get("seq_len", 1024)),
            v=int(self.knobs.get("vocab_size", 32768)),
        )

    def _init_params(self) -> Dict[str, Any]:
        """Initialize ON the chip group's devices (jit + jax.random):
        host-RNG init of a flagship model is ~470M float64 draws (~20 s
        of host time) plus a ~1.9 GB host→device upload — device-side
        init costs milliseconds and transfers nothing."""
        s = self._dims()
        init = _jitted_param_init(s["v"], s["d"], s["layers"], self.mesh)
        return init(int(self.knobs.get("seed", 0)))

    def _block(self, x, lp, h_heads):
        return _lm_block(x, lp, h_heads, self.mesh)

    def _forward_spec(self):
        """What ``_forward`` reads of the instance, and all it reads:
        the dims, the remat policy and the mesh. A compiled program may
        close over this (and be keyed on it); never over the instance,
        whose parameters would then live as long as the program."""
        return self._dims(), str(self.knobs.get("remat", "dots")), self.mesh

    def _forward(self, params, ids):
        return self._forward_fn(params, ids, *self._forward_spec())

    def _count_dispatch(self, counts) -> None:
        """What ``_loss_fn``'s counts, summed over one dispatch, tell
        the host's metrics. The dense block counts nothing."""

    def _flops_per_step(self, b: int) -> float:
        """Analytic train-step FLOPs (fwd+bwd): 6·N·tokens for matmul
        params (the standard estimate; embedding GATHER excluded, tied
        unembed matmul included) plus the causal attention term. Used
        instead of XLA cost analysis, which cannot count inside the
        Pallas custom calls. ``b`` is the ACTUAL (dp-rounded) batch the
        step runs, not the raw knob."""
        s = self._dims()
        tokens = b * s["t"]
        n_mat = 12 * s["layers"] * s["d"] ** 2 + s["v"] * s["d"]
        attn = (2 * 2 * 3 * b * s["h"] * s["t"] ** 2
                * (s["d"] // s["h"]) * s["layers"] / 2)
        return 6 * n_mat * tokens + attn

    # --- BaseModel ---

    def _train_setup(self, dataset_path: str):
        """``train`` up to its loop (the ``step_setup`` span): the
        dataset, the step geometry, the initial state on the mesh and
        the chunk, from the step cache or freshly wrapped (a fresh one
        compiles in its first call, inside ``step_dispatch``)."""
        ds = load_token_dataset(dataset_path)
        s = self._dims()
        assert ds.vocab_size <= s["v"], (
            f"dataset vocab {ds.vocab_size} exceeds model vocab {s['v']}")
        t_need = int(self.knobs.get("seq_len", 1024)) + 2
        if ds.size < t_need:
            raise ValueError(
                f"token dataset has {ds.size} ids but seq_len="
                f"{t_need - 2} needs at least {t_need} (one full "
                f"input+target window)")
        mesh = self.mesh
        dp = mesh.shape[DP_AXIS]
        b = max(dp, (int(self.knobs.get("batch_size", 8)) // dp) * dp)
        t = s["t"]
        steps = int(self.knobs.get("train_steps", 100))
        if self.knobs.get("quick_train", False):
            steps = min(steps, int(self.knobs.get("trial_steps", 30)))
        k_disp = max(1, int(self.knobs.get("steps_per_dispatch", 8)))

        params = jax.device_put(self._params or self._init_params(),
                                replicated(mesh))
        # Compiled-step cache, shared convention with the whole zoo
        # (model/jax_model.py): repeated trials of one config reuse
        # ONE executable instead of re-paying the ~10 s flagship
        # compile per train() call. A search over lr does not: the
        # rate is a constant of the step (PERF.md §6, `lm14-search`).
        cache_key = step_cache_key(self, "train", mesh, steps, b, k_disp)
        cached = _step_cache_get(cache_key)
        lr = float(self.knobs.get("learning_rate", 3e-4))
        total = max(1, steps)
        if cached is not None:
            tx, train_chunk = cached["tx"], cached["step"]
            init_opt = cached["init_opt"]
        else:
            tx = optax.adamw(optax.warmup_cosine_decay_schedule(
                init_value=lr * 0.1, peak_value=lr,
                warmup_steps=max(1, total // 10), decay_steps=total,
                end_value=lr * 0.1))
            # Jitted optimizer-state init, cached with the step: eager
            # tx.init on 470M params re-traces ~3.5 s per trial. Born on
            # the mesh: its zeros depend on no input, so without
            # out_shardings jit would place 3.8 GB on the default device.
            init_opt = jax.jit(tx.init, out_shardings=replicated(mesh))
            train_chunk = self._make_train_chunk(tx)
            _step_cache_put(cache_key, {"tx": tx, "step": train_chunk,
                                        "init_opt": init_opt})
        opt_state = init_opt(_weights(params))
        return ds, steps, b, k_disp, train_chunk, params, opt_state

    def _make_train_chunk(self, tx):
        """The jitted train program: ``(params, opt_state, wins (K, B,
        t+1)) -> (params, opt_state, [mean loss, mean accuracy, summed
        counts...])``, K optimizer steps of ``_loss_fn`` under ``tx``,
        the carry donated. It closes over the forward's spec (dims,
        remat, mesh), never over the instance."""
        s, remat, mesh = self._forward_spec()
        x_shard = batch_sharding(mesh)
        loss_fn = functools.partial(self._loss_fn, s=s, remat=remat,
                                    mesh=mesh)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_chunk(params, opt_state, wins):
            def one(carry, win):
                params, opt_state = carry
                # win (B, t+1): input/target are shifted views.
                win = jax.lax.with_sharding_constraint(win, x_shard)
                weights = _weights(params)
                (loss, (acc, counts, state)), grads = \
                    jax.value_and_grad(loss_fn, has_aux=True)(
                        weights, params.get(STATE), win)
                updates, opt_state = tx.update(grads, opt_state, weights)
                params = optax.apply_updates(weights, updates)
                if state is not None:
                    params[STATE] = state
                return (params, opt_state), (loss, acc, counts)

            (params, opt_state), (losses, accs, counts) = jax.lax.scan(
                one, (params, opt_state), wins)
            metrics = jnp.stack([losses.mean(), accs.mean()])
            if counts is not None:
                metrics = jnp.concatenate([metrics, counts.sum(0)])
            return params, opt_state, metrics

        return train_chunk

    def train(self, dataset_path: str, **kwargs: Any) -> None:
        with _phases.span("step_setup"):
            ds, steps, b, k_disp, train_chunk, params, opt_state = \
                self._train_setup(dataset_path)
        mesh, t = self.mesh, self._dims()["t"]
        logger.define_plot("Training", ["loss", "token_acc", "chip_util"],
                           x_axis="step")
        meter = MfuMeter(self._flops_per_step(b), n_devices=mesh.size)
        rng = epoch_rng(int(self.knobs.get("seed", 0)), 0)
        hi = max(1, ds.size - (t + 1))
        done = 0
        first_dispatch = True
        # Windows are cut on the HOST and shipped per dispatch:
        # (K, B, t+1) int32 is ~¼ MB at flagship shape — negligible
        # next to the step's compute — whereas gathering the windows
        # in-graph from a device-resident stream lowers to a scalar
        # gather that runs ~35× slower than the whole train step on
        # TPU (measured: 8.1 s/step vs 0.23). The image zoo's
        # device-resident staging exists to avoid shipping megabytes of
        # pixels; a token stream has no such problem.
        while done < steps:
            k = min(k_disp, steps - done)
            with _phases.span("step_dispatch"):
                starts = rng.integers(0, hi, size=k * b)
                wins = np.stack([ds.ids[s:s + t + 1] for s in starts])
                params, opt_state, metrics = train_chunk(
                    params, opt_state,
                    jax.device_put(wins.reshape(k, b, t + 1),
                                   replicated(mesh)))
            done += k
            # The host blocked on the device; its count is the trial's
            # progress in dispatches. One D2H per chunk; this
            # sync must land BEFORE any meter.reset(): the dispatch
            # returns while the chunk is still executing, and a reset
            # taken then would start the fresh window mid-chunk with
            # zero steps credited (~4% systematic under-report).
            with _phases.span("step_wait"):
                loss_acc = np.asarray(metrics)
            self._count_dispatch(loss_acc[2:])
            meter.tick(k)
            if first_dispatch or k != k_disp:
                # Dispatches that paid an XLA compile (first chunk, tail
                # chunk) are excluded from the sustained-MFU window.
                first_dispatch = False
                meter.reset()
            util = ({"chip_util": round(meter.mfu, 6)}
                    if meter.mfu is not None else {})
            if meter.mfu is not None:
                from ..observe import metrics as _obs_metrics

                # rta: disable=RTA301 bound trial= labels; TrialRunner removes them at trial end (worker/runner.py)
                _obs_metrics.registry().gauge(
                    "rafiki_tpu_train_mfu_ratio",
                    "Model-FLOPs-utilization of the trial's chip group "
                    "(published per epoch)").set(
                        meter.mfu, **_obs_metrics.bound_labels())
            logger.log(step=done, loss=float(loss_acc[0]),
                       token_acc=float(loss_acc[1]), **util)
        # Params stay DEVICE-RESIDENT: dump_parameters hands them on
        # as device arrays, and they cross to the host only where
        # something (the persist stage, a checkpoint) needs the bytes.
        self._params = params
        self._invalidate_compiled()

    def _eval_count_step(self, n_win: int):
        """The evaluation's one program, from the step cache:
        ``(params, inputs, targets) -> int32`` count of positions whose
        arg-max logit is the target. Keyed on what the forward reads
        (class, dims, remat, mesh) and the number of windows, and on no
        other knob: trials that differ in ``learning_rate``,
        ``train_steps`` or ``seed`` share it."""
        s, remat, mesh = self._forward_spec()
        key = (type(self), "eval", tuple(sorted(s.items())), remat, mesh,
               n_win)
        cached = _step_cache_get(key)
        if cached is not None:
            return cached["step"]
        forward = self._forward_fn

        @jax.jit
        def eval_count(params, inputs, targets):
            # The barrier keeps the logits the ones ``jit(_forward)``
            # hands ``predict`` (and handed the host arg-max this
            # replaces). Without it the TPU compiler fuses the arg-max
            # into the unembedding and reduces over that matmul's bf16
            # output, where the matmul alone keeps its float32
            # accumulator: the arg-max then parts in 2 % of positions
            # (179 of 8192, chip run, PR 28). It costs the 1.65 GB of
            # logits one trip through HBM, a few milliseconds.
            logits = jax.lax.optimization_barrier(
                forward(params, inputs, s, remat, mesh))
            return (logits.argmax(-1) == targets).sum(dtype=jnp.int32)

        _step_cache_put(key, {"step": eval_count})
        return eval_count

    def evaluate(self, dataset_path: str) -> float:
        """Mean next-token accuracy over contiguous validation
        windows, reduced on the device: one step-cached program
        (``_eval_count_step``) takes the parameters, the input windows
        and the target windows and returns the NUMBER of positions
        where the arg-max of the logits is the target; that int32
        scalar is all that comes back, and the host divides it by the
        number of positions. No logits leave the device (at the
        flagship shape they are 1.65 GB, and a host arg-max over them
        took 2 s). ``jnp.argmax`` takes the first index of a tie, as
        ``np.argmax`` does, and the division is in Python floats, so
        the score is the one a host arg-max over ``jit(_forward)``'s
        logits gives (``_eval_count_step`` says what keeps them the
        same logits on the TPU).

        Where the windows come from is the only branch. The token
        stream rides the cross-trial device staging cache
        (``staged_token_ids``) and the windows are gathered from the
        resident int32 stream by DEVICE-COMPUTED iota indices, so eval
        2..N of a sub-train-job ships zero token bytes host->device
        (int32 indices from the host would be exactly as many bytes as
        the windows themselves). A stream over the staging budget has
        its windows stacked on the host and put on the mesh."""
        ds = load_token_dataset(dataset_path)
        t = self._dims()["t"]
        n_win = max(1, min(16, (ds.size - 1) // t))
        params = self._ensure_params_dev()
        stage_bytes = int(os.environ.get("RAFIKI_TPU_STAGE_BYTES",
                                         2 << 30))
        # Gated on the stream being CACHEABLE, not just stageable: with
        # the cross-trial cache disabled (or the stream over its
        # budget), staging would device_put the WHOLE stream uncached
        # on every eval — strictly worse than shipping 16 windows.
        cache_budget = _stage_cache_budget()
        if 0 < int(ds.ids.nbytes) <= min(stage_bytes, cache_budget) \
                and ds.size >= n_win * t + 1:
            ids_dev = staged_token_ids(dataset_path, ds, self.mesh)
            inputs, targets = _jitted_eval_windows(n_win, t)(ids_dev)
        else:
            ids = np.stack([ds.ids[i * t:i * t + t + 1]
                            for i in range(n_win)]).astype(np.int32)
            inputs, targets = jax.device_put(
                (ids[:, :-1], ids[:, 1:]), replicated(self.mesh))
        correct = self._eval_count_step(n_win)(params, inputs, targets)
        return int(jax.device_get(correct)) / (n_win * t)

    def predict(self, queries: List[Any]) -> List[Any]:
        """Scores token-id sequences: mean next-token log-probability
        per query (the LM-scoring service contract)."""
        if not queries:
            return []
        t = self._dims()["t"]
        fn = self._ensure_predict_fn()
        out = []
        for q in queries:
            ids = np.asarray(list(q), np.int32)[:t + 1]
            if ids.size < 2:
                out.append(0.0)
                continue
            pad = np.zeros((t + 1,), np.int32)
            pad[:ids.size] = ids
            # Logits stay on the chip group that computed them (a trip
            # through the host would land the softmax on the process's
            # default device, whichever chip this replica serves from);
            # only each target token's log-probability comes back.
            lp = jax.nn.log_softmax(
                fn(self._params_dev, pad[None, :-1]), -1)
            n = ids.size - 1
            token_lp = np.asarray(jnp.take_along_axis(
                lp, pad[None, 1:, None], axis=-1))[0, :n, 0]
            out.append(float(token_lp.mean()))
        return out

    def make_generator(self, **cfg: Any):
        """Token-level generation engine over this model's trained
        params: paged KV cache, AOT prefill/decode split, per-step
        admission. See :mod:`rafiki_tpu.models.lm_generate` — the
        serving plane (worker decode scheduler) is the intended
        caller; ``cfg`` passes through to :class:`LMGenerator`
        (``page_size``, ``n_pages``, ``decode_batch``, ...)."""
        from .lm_generate import LMGenerator
        assert self._params is not None, \
            "train() or load_parameters() first"
        return LMGenerator(self, **cfg)

    def _ensure_params_dev(self):
        assert self._params is not None, "train() or load_parameters() first"
        if self._params_dev is None:
            self._params_dev = jax.device_put(self._params,
                                              replicated(self.mesh))
        return self._params_dev

    def _ensure_predict_fn(self):
        """The logits program of ``predict`` and the generator's
        reference: per instance (``evaluate`` does not use it)."""
        self._ensure_params_dev()
        if self._predict_fn is None:
            self._predict_fn = jax.jit(self._forward)
        return self._predict_fn

    def dump_parameters(self) -> Params:
        """The parameter tree, nested to any depth, under flat
        ``a/b`` names (``layers/qkv``). A leaf on the device is
        returned as the ``jax.Array`` it is: whoever needs the bytes
        calls ``np.asarray`` (the trial runner's persist stage does,
        leaf by leaf behind the next trial's steps; ``model/dev.py``
        does at once). No copy is started here: with every leaf's
        ``copy_to_host_async`` queued at once, the next trial's set-up
        waited 0.3 s behind them on a v5e (PERF.md §6, PR 33)."""
        assert self._params is not None
        return {name: leaf if isinstance(leaf, jax.Array)
                else np.asarray(leaf)
                for name, leaf in _flat_names(self._params).items()}

    def load_parameters(self, params: Params) -> None:
        # Straight onto this model's chip group (never via the default
        # device: four one-chip replicas would all stage through chip 0).
        rep = replicated(self.mesh)
        tree: Dict[str, Any] = {}
        for name, value in params.items():
            *parents, leaf = name.split("/")
            node = tree
            for key in parents:
                node = node.setdefault(key, {})
            node[leaf] = jax.device_put(value, rep)
        self._params = tree
        self._invalidate_compiled()

    def _invalidate_compiled(self) -> None:
        self._predict_fn = None
        self._params_dev = None

    def destroy(self) -> None:
        self._invalidate_compiled()
        self._params = None

