"""JaxFeedForward: parity model for the reference's ``TfFeedForward``.

Parity: SURVEY.md §2 "Example models" — a small dense network for
fashion-MNIST-scale image classification, the platform's "CPU-runnable PR1
reference" config (BASELINE.json configs[0]). Knob space mirrors the
reference's (hidden layer count/size, learning rate, batch size, epochs),
expressed with the SDK's typed knobs.

TPU-first redesign — one executable for the whole search space: upstream
rebuilds a TF graph per hyperparameter assignment; on XLA that is a
multi-second recompile per trial, which dominates AutoML trial time. Here
the architecture knobs are *traced masks* over a fixed-size supernet
(``extra_apply_inputs``): every trial computes MAX_LAYERS x MAX_UNITS
dense layers, a width mask zeroes units beyond ``hidden_layer_units``
(masked activations feed zeros forward, so the function — and its
gradients — equal the exact small MLP), and inactive layers pass their
input through. The learning rate is a traced optimizer hyperparameter
(``traced_knobs``). Net effect: trials recompile only per
batch-size bucket, not per knob assignment — the propose->train->evaluate
loop runs at executed-step speed.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from ..model import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob
from ..model.jax_model import JaxModel, dynamic_int8_matmul

MAX_LAYERS = 3
MAX_UNITS = 128


class _FeedForward(nn.Module):
    """Dense net; static shape from attrs, or masked supernet when the
    ``hidden_layer_count`` / ``hidden_layer_units`` mask inputs are given
    (then the attrs must be MAX_LAYERS / MAX_UNITS)."""
    hidden_layer_count: int
    hidden_layer_units: int
    n_classes: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False, hidden_layer_count=None,
                 hidden_layer_units=None):
        masked = hidden_layer_count is not None
        h = x.reshape((x.shape[0], -1)).astype(self.dtype)
        for i in range(self.hidden_layer_count):
            y = nn.relu(nn.Dense(self.hidden_layer_units,
                                 dtype=self.dtype)(h))
            if not masked:
                h = y
                continue
            y = y * hidden_layer_units.astype(y.dtype)  # width mask
            # Layer 0 always runs (count >= 1); deeper layers blend to a
            # pass-through when masked off.
            h = y if i == 0 else jnp.where(
                hidden_layer_count[i].astype(y.dtype) > 0, y, h)
        return nn.Dense(self.n_classes, dtype=self.dtype)(h)


class JaxFeedForward(JaxModel):
    traced_knobs = frozenset({"learning_rate"})
    traced_knob_defaults = {"learning_rate": 1e-3}

    @staticmethod
    def get_knob_config():
        return {
            "hidden_layer_count": IntegerKnob(1, MAX_LAYERS),
            "hidden_layer_units": IntegerKnob(16, MAX_UNITS),
            "learning_rate": FloatKnob(1e-4, 1e-2, is_exp=True),
            "batch_size": CategoricalKnob([32, 64, 128]),
            "max_epochs": FixedKnob(5),
        }

    def create_module(self, n_classes: int, image_shape: Sequence[int]):
        # Fixed supernet shape: the knobs arrive as traced masks, so the
        # module (and its XLA graph) is identical across trials.
        return _FeedForward(
            hidden_layer_count=MAX_LAYERS,
            hidden_layer_units=MAX_UNITS,
            n_classes=n_classes,
        )

    def create_optimizer(self, steps_per_epoch: int, max_epochs: int):
        return self.traced_hyperparam_optimizer(steps_per_epoch,
                                                max_epochs)

    def extra_apply_inputs(self) -> Dict[str, np.ndarray]:
        count = int(self.knobs.get("hidden_layer_count", MAX_LAYERS))
        units = int(self.knobs.get("hidden_layer_units", MAX_UNITS))
        return {
            "hidden_layer_count":
                (np.arange(MAX_LAYERS) < count).astype(np.float32),
            "hidden_layer_units":
                (np.arange(MAX_UNITS) < units).astype(np.float32),
        }

    def stack_signature(self):
        # Congruence metadata for vmap-stacked serving: every trial
        # shares the fixed supernet, so same-family bins stack no
        # matter which width/depth masks their knobs trace in.
        return (*super().stack_signature(), MAX_LAYERS, MAX_UNITS)

    def quantized_apply(self, qvars, scales, fvars, x, extra):
        """Dequant-free int8 serving path: every Dense matmul runs
        int8 x int8 -> int32 on the MXU (``dynamic_int8_matmul``:
        weights statically quantized per output channel, activations
        dynamically per row — no calibration pass), mirroring
        ``_FeedForward.__call__``'s masked-supernet forward exactly. A
        kernel the quantizer left in f32 (none today, but the contract
        is per-layer) falls back to a plain matmul on that layer.
        ``tests/test_wire_codec.py::test_int8_quant_close_to_f32`` is
        the regression net for this hand-mirrored forward."""
        import jax.numpy as jnp

        def dense(h, i):
            k = f"params/Dense_{i}/kernel"
            b = fvars[f"params/Dense_{i}/bias"].astype(jnp.float32)
            if k in qvars:
                return dynamic_int8_matmul(h, qvars[k], scales[k]) + b
            return h @ fvars[k].astype(jnp.float32) + b  # f32 fallback

        count_mask = extra["hidden_layer_count"]
        units_mask = extra["hidden_layer_units"]
        h = x.reshape((x.shape[0], -1))
        for i in range(MAX_LAYERS):
            y = jnp.maximum(dense(h, i), 0.0)  # relu
            y = y * units_mask.astype(y.dtype)
            h = y if i == 0 else jnp.where(
                count_mask[i].astype(y.dtype) > 0, y, h)
        return dense(h, MAX_LAYERS)
