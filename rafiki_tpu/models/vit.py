"""JaxViT: Vision Transformer image classifier on the framework's ops.

Beyond-parity zoo model (SURVEY.md §2 "Example models" lists only
dense/conv/ENAS image classifiers): patches → the same pre-LN encoder
blocks the sequence models use (``rafiki_tpu.ops`` flash attention on
TPU, blockwise fallback elsewhere) → CLS-token head. Connects the
attention-kernel layer to the flagship IMAGE_CLASSIFICATION task, and
inherits the whole ``JaxModel`` substrate: device-resident input
pipeline, scanned multi-step dispatch, traced lr/wd hyperparameters
(one executable per batch-size bucket), AOT bucketed predict, and
chip-utilization metering.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..model import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob
from ..model.jax_model import JaxModel
from ..ops import default_attention
from .transformer import _EncoderBlock

MAX_DEPTH = 6  # supernet depth; the depth knob masks trailing blocks


class _ViT(nn.Module):
    """Patchify-conv + CLS token + encoder blocks + linear head.

    ``depth`` (traced, a (MAX_DEPTH,) 0/1 mask — named for the knob
    that drives it, the compiled-step cache-key convention) blends each
    block's output with its input: a masked block is the identity, so
    the searched depth rides ONE executable like JaxCnn's width mask.
    """
    n_classes: int
    d_model: int
    n_heads: int
    patch: int
    n_tokens: int  # 1 + (H/patch)·(W/patch), fixed per dataset
    max_depth: int = MAX_DEPTH
    dtype: Any = jnp.bfloat16
    mesh: Any = None  # the model's mesh: the TPU kernel shard_maps over it

    @nn.compact
    def __call__(self, x, train: bool = False, depth=None):
        attn = default_attention(self.mesh, causal=False)

        x = nn.Conv(self.d_model, (self.patch, self.patch),
                    strides=(self.patch, self.patch),
                    dtype=self.dtype)(x.astype(self.dtype))
        b = x.shape[0]
        x = x.reshape(b, -1, self.d_model)          # (B, hw, D)
        # Params stay f32 (like every flax kernel; ``dtype`` is the
        # COMPUTE dtype) — bf16 params would leak into the optimizer
        # state and break the scanned train step's carry types.
        cls = self.param("cls", nn.initializers.zeros,
                         (1, 1, self.d_model), jnp.float32)
        x = jnp.concatenate(
            [jnp.tile(cls.astype(self.dtype), (b, 1, 1)), x], axis=1)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, self.n_tokens, self.d_model), jnp.float32)
        x = x + pos.astype(self.dtype)
        for i in range(self.max_depth):
            y = _EncoderBlock(self.n_heads, dropout=0.0,
                              dtype=self.dtype)(
                x, attn, None, deterministic=not train)
            if depth is not None:
                gate = depth[i].astype(y.dtype)
                y = x + gate * (y - x)   # masked block == identity
            x = y
        x = nn.LayerNorm(dtype=jnp.float32)(x[:, 0])  # CLS token
        return nn.Dense(self.n_classes, dtype=jnp.float32)(x)


class JaxViT(JaxModel):
    """Vision Transformer; depth searched via a traced block mask."""

    traced_knobs = frozenset({"learning_rate", "weight_decay"})
    traced_knob_defaults = {"learning_rate": 1e-3, "weight_decay": 1e-4}

    @staticmethod
    def get_knob_config():
        return {
            "depth": IntegerKnob(2, MAX_DEPTH),  # traced mask -> one exe
            "d_model": FixedKnob(128),
            "n_heads": FixedKnob(4),
            "patch": FixedKnob(4),
            "learning_rate": FloatKnob(1e-4, 1e-2, is_exp=True),
            "batch_size": CategoricalKnob([64, 128, 256]),
            "weight_decay": FloatKnob(1e-5, 1e-3, is_exp=True),
            "max_epochs": IntegerKnob(3, 40),
            "early_stop_epochs": FixedKnob(5),
        }

    def create_module(self, n_classes: int, image_shape: Sequence[int]):
        patch = int(self.knobs.get("patch", 4))
        h, w = int(image_shape[0]), int(image_shape[1])
        if h % patch or w % patch:
            raise ValueError(f"image {h}x{w} not divisible by "
                             f"patch {patch}")
        return _ViT(n_classes=n_classes,
                    d_model=int(self.knobs.get("d_model", 128)),
                    n_heads=int(self.knobs.get("n_heads", 4)),
                    patch=patch,
                    n_tokens=1 + (h // patch) * (w // patch),
                    mesh=self.mesh)

    def create_optimizer(self, steps_per_epoch: int, max_epochs: int):
        return self.traced_hyperparam_optimizer(
            steps_per_epoch, max_epochs, opt="adam", weight_decay=True)

    def extra_apply_inputs(self) -> Dict[str, Any]:
        import numpy as np

        # Keyed by the KNOB name: that's what excludes ``depth`` from
        # the compiled-step cache key (see step_cache_key).
        depth = int(self.knobs.get("depth", MAX_DEPTH))
        return {"depth":
                (np.arange(MAX_DEPTH) < depth).astype(np.float32)}

    def stack_signature(self):
        # Congruence metadata for vmap-stacked serving (module
        # dataclass equality already compares d_model/n_heads/patch/
        # n_tokens; the supernet depth is the family constant).
        return (*super().stack_signature(), MAX_DEPTH)

    def quantized_apply(self, qvars, scales, fvars, x, extra):
        """Dequant-free int8 serving for the transformer zoo (the r13
        carry): the patchify conv runs via ``dynamic_int8_conv``, each
        encoder block via the shared ``quantized_encoder_block``
        (models/transformer.py — int8 QKV/proj/FFN matmuls, f32
        LayerNorms), mirroring ``_ViT.__call__``'s depth-masked
        forward. A block the int8 path cannot take (MoE) or a kernel
        left f32 falls back per layer."""
        from ..model.jax_model import (dynamic_int8_conv,
                                       dynamic_int8_matmul)
        from .transformer import quantized_encoder_block

        module = self._module
        patch = module.patch
        k = "params/Conv_0/kernel"
        b = fvars["params/Conv_0/bias"].astype(jnp.float32)
        if k in qvars:
            h = dynamic_int8_conv(x, qvars[k], scales[k],
                                  strides=(patch, patch),
                                  padding="VALID") + b
        else:
            h = jax.lax.conv_general_dilated(
                x, fvars[k].astype(jnp.float32), (patch, patch),
                "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
        bsz = h.shape[0]
        h = h.reshape(bsz, -1, module.d_model)
        cls = fvars["params/cls"].astype(jnp.float32)
        h = jnp.concatenate([jnp.tile(cls, (bsz, 1, 1)), h], axis=1)
        h = h + fvars["params/pos_embed"].astype(jnp.float32)
        attn = default_attention(self.mesh, causal=False)
        depth = extra["depth"]
        for i in range(module.max_depth):
            y = quantized_encoder_block(
                qvars, scales, fvars, f"params/_EncoderBlock_{i}", h,
                attn, module.n_heads)
            if y is None:
                return None  # MoE block: generic fallback path
            gate = depth[i].astype(y.dtype)
            h = h + gate * (y - h)  # masked block == identity
        g = fvars["params/LayerNorm_0/scale"].astype(jnp.float32)
        bb = fvars["params/LayerNorm_0/bias"].astype(jnp.float32)
        hf = h[:, 0].astype(jnp.float32)
        m = hf.mean(-1, keepdims=True)
        v = ((hf - m) ** 2).mean(-1, keepdims=True)
        hf = (hf - m) * jax.lax.rsqrt(v + 1e-6) * g + bb
        k = "params/Dense_0/kernel"
        b = fvars["params/Dense_0/bias"].astype(jnp.float32)
        if k in qvars:
            return dynamic_int8_matmul(hf, qvars[k], scales[k]) + b
        return hf @ fvars[k].astype(jnp.float32) + b
