"""JaxCnn: VGG-style convolutional image classifier.

Parity: SURVEY.md §2 "Example models" — upstream's example zoo includes
plain deep CNNs (e.g. a VGG-16 template) between the tiny dense net and
the DenseNet flagship. This is that middle ground, TPU-first: NHWC
bfloat16 convs (MXU path), norm-free like the original VGG (the module
stays purely functional), and the same one-executable search design as
JaxFeedForward: the width knob is a traced channel mask over a
fixed-width supernet (masked channels feed zeros forward, so function
and gradients equal the exact narrower net) and lr/wd ride the optimizer
state (``traced_knobs``) — trials recompile only per batch-size bucket.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from ..model import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob
from ..model.jax_model import (JaxModel, dynamic_int8_conv,
                               dynamic_int8_matmul)

MAX_WIDTH = 64   # stage-0 channels; stage i uses MAX_WIDTH * 2**i
N_STAGES = 3


class _Cnn(nn.Module):
    """(conv-relu) x2 + 2x2 pool per stage, then flatten + FC head — the
    classic norm-free VGG recipe (normalisation layers stall this depth
    badly on small data).

    ``width_16ths`` (traced, a (16,) 0/1 mask) zeroes the trailing
    fraction of every stage's channels. Masked activations feed zeros
    forward and receive zero gradients, so the function and its
    gradients equal the exact narrower net while every trial shares ONE
    executable.
    """
    n_classes: int
    base_width: int = MAX_WIDTH
    n_stages: int = N_STAGES
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False, width_16ths=None):
        x = x.astype(self.dtype)
        for stage in range(self.n_stages):
            ch = self.base_width * (2 ** stage)  # multiple of 16
            mask = None if width_16ths is None else \
                jnp.repeat(width_16ths, ch // 16).astype(self.dtype)
            for _ in range(2):
                x = nn.Conv(ch, (3, 3), padding=1, dtype=self.dtype)(x)
                x = nn.relu(x)
                if mask is not None:
                    x = x * mask
            if min(x.shape[1], x.shape[2]) >= 2:
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
        # Classic VGG head: flatten + FC (position-preserving, unlike a
        # global average pool).
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(256, dtype=self.dtype)(x))
        return nn.Dense(self.n_classes, dtype=jnp.float32)(
            x.astype(jnp.float32))


class JaxCnn(JaxModel):
    """VGG-style CNN; width searched via a traced channel mask."""

    traced_knobs = frozenset({"learning_rate", "weight_decay"})
    traced_knob_defaults = {"learning_rate": 3e-3, "weight_decay": 1e-4}

    @staticmethod
    def get_knob_config():
        return {
            # Fraction of the supernet width actually used, searched in
            # sixteenths: 16/16 ..= 4/16. Traced -> no recompiles.
            "width_16ths": IntegerKnob(4, 16),
            "learning_rate": FloatKnob(3e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([64, 128, 256]),
            "weight_decay": FloatKnob(1e-5, 1e-3, is_exp=True),
            "max_epochs": IntegerKnob(3, 40),
            "early_stop_epochs": FixedKnob(5),
        }

    def create_module(self, n_classes: int, image_shape: Sequence[int]):
        return _Cnn(n_classes=n_classes)

    def create_optimizer(self, steps_per_epoch: int, max_epochs: int):
        return self.traced_hyperparam_optimizer(
            steps_per_epoch, max_epochs, opt="adam", weight_decay=True)

    def extra_apply_inputs(self) -> Dict[str, np.ndarray]:
        # Keyed by the KNOB name: that's what excludes width_16ths from
        # the compiled-step cache key (see JaxModel._step_cache_key).
        sixteenths = int(self.knobs.get("width_16ths", 16))
        return {"width_16ths":
                (np.arange(16) < sixteenths).astype(np.float32)}

    def stack_signature(self):
        # Congruence metadata for vmap-stacked serving: the supernet
        # constants pin the family (module dataclass equality already
        # carries n_classes/base_width; the explicit tuple keeps the
        # contract stated even if the module grows non-compared state).
        return (*super().stack_signature(), MAX_WIDTH, N_STAGES)

    def quantized_apply(self, qvars, scales, fvars, x, extra):
        """Dequant-free int8 serving path for the conv zoo (the r13
        carry): every stage conv runs int8 x int8 -> int32 via
        ``dynamic_int8_conv`` (4-D kernels carry per-output-channel
        scales since r16) and the head Denses via
        ``dynamic_int8_matmul``, mirroring ``_Cnn.__call__``'s
        masked-supernet forward exactly —
        ``tests/test_stacked.py::test_cnn_int8_close_to_f32`` is the
        regression net. A kernel
        the quantizer left in f32 falls back per layer, as the wire
        contract promises."""
        mask16 = extra["width_16ths"]
        h = x
        conv_i = 0
        for stage in range(N_STAGES):
            ch = MAX_WIDTH * (2 ** stage)
            mask = jnp.repeat(mask16, ch // 16)
            for _ in range(2):
                k = f"params/Conv_{conv_i}/kernel"
                b = fvars[f"params/Conv_{conv_i}/bias"] \
                    .astype(jnp.float32)
                if k in qvars:
                    h = dynamic_int8_conv(
                        h, qvars[k], scales[k],
                        padding=((1, 1), (1, 1))) + b
                else:  # per-layer f32 fallback
                    import jax

                    h = jax.lax.conv_general_dilated(
                        h, fvars[k].astype(jnp.float32), (1, 1),
                        ((1, 1), (1, 1)),
                        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
                h = jnp.maximum(h, 0.0)
                h = h * mask
                conv_i += 1
            if min(h.shape[1], h.shape[2]) >= 2:
                h = nn.max_pool(h, (2, 2), strides=(2, 2))
        h = h.reshape((h.shape[0], -1))

        def dense(v, i):
            k = f"params/Dense_{i}/kernel"
            b = fvars[f"params/Dense_{i}/bias"].astype(jnp.float32)
            if k in qvars:
                return dynamic_int8_matmul(v, qvars[k], scales[k]) + b
            return v @ fvars[k].astype(jnp.float32) + b
        h = jnp.maximum(dense(h, 0), 0.0)
        return dense(h, 1)
