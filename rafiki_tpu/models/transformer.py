"""JaxTransformerTagger: Transformer encoder for sequence tagging.

Beyond-parity zoo model: the reference's POS_TAGGING task ships only a
BiLSTM (SURVEY.md §2 "Example models"); this adds a Transformer encoder
built on the framework's own attention ops (``rafiki_tpu.ops``) so long
sequences are first-class:

- single chip / chip group: Pallas ``flash_attention`` on TPU (blockwise
  XLA fallback elsewhere) — O(block) memory, so ``max_len`` can grow far
  past what a materialised T×T score matrix allows;
- ``sequence_parallel`` knob > 1: the sequence dimension shards over the
  ``sp`` mesh axis and attention runs context-parallel over ICI —
  a ``ppermute`` ring (``ring_attention``, the default) or the Ulysses
  all-to-all head re-sharding (``sp_schedule="alltoall"``, needs
  ``n_heads % sequence_parallel == 0``) — scaling context length with
  the chip group.

Same corpus-dataset contract, hashed vocabulary, and per-token
probability output as ``JaxPosTagger``, so the Advisor, TrainWorker, and
Predictor ensemble treat the two interchangeably.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import traverse_util

from ..model import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob
from ..model.base import BaseModel, Params
from ..model.dataset import (PAD_ID, hash_token_ids,
                             load_corpus_dataset)
from ..model.jax_model import (_step_cache_get, _step_cache_put,
                               step_cache_key)
from ..model.logger import logger
from ..model.loop_ckpt import LoopCheckpointer, epoch_rng, schedule_epochs
from ..ops import (default_attention, sequence_sharded_attention,
                   switch_moe)
from ..parallel import (DP_AXIS, SP_AXIS, batch_sharding, build_mesh,
                        device_get_tree,
                        replicated, shard_variables)
from ..parallel.chips import ChipGroup

def _sinusoidal(max_len: int, dim: int) -> np.ndarray:
    pos = np.arange(max_len)[:, None]
    div = np.exp(np.arange(0, dim, 2) * (-math.log(10000.0) / dim))
    pe = np.zeros((max_len, dim), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


class _EncoderBlock(nn.Module):
    """Pre-LN encoder block; attention is injected so the same module
    serves flash (single group) and sequence-parallel execution.

    ``moe_experts > 0`` replaces the dense FFN with a Switch-routed
    expert FFN (``rafiki_tpu.ops.switch_moe``); the expert-stacked
    parameters' names contain ``expert`` so the sharding rules place
    them over the ``ep`` mesh axis. The router's load-balance loss is
    sown into the ``losses`` collection for the train step to collect.
    """
    n_heads: int
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    moe_experts: int = 0
    # Inside the pipeline's shard_map (where GSPMD cannot partition for
    # us) the expert stack arrives pre-sliced: ``moe_local_experts`` is
    # this rank's slice size and ``ep_axis`` the mesh axis to psum the
    # partial expert outputs over. None/default = the GSPMD path
    # (full stack declared; PartitionSpec("ep", ...) does the rest).
    moe_local_experts: Optional[int] = None
    ep_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, attn_fn, kv_mask, *, deterministic: bool):
        d_model = x.shape[-1]
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        qkv = nn.Dense(3 * d_model, use_bias=False, dtype=self.dtype)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(a):  # (B, T, D) -> (B, H, T, Dh)
            b, t, _ = a.shape
            return a.reshape(b, t, self.n_heads,
                             d_model // self.n_heads).transpose(0, 2, 1, 3)

        o = attn_fn(heads(q), heads(k), heads(v), kv_mask)
        b, nh, t, dh = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, t, nh * dh)
        x = x + nn.Dense(d_model, use_bias=False, dtype=self.dtype)(o)

        h = nn.LayerNorm(dtype=jnp.float32)(x)
        if self.moe_experts > 0:
            e, f = self.moe_experts, 4 * d_model
            e_loc = self.moe_local_experts or e
            init = nn.initializers.lecun_normal()
            gate_w = self.param("moe_gate", init, (d_model, e),
                                jnp.float32)
            w1 = self.param("expert_w1", init, (e_loc, d_model, f),
                            self.dtype)
            b1 = self.param("expert_b1", nn.initializers.zeros, (e_loc, f),
                            self.dtype)
            w2 = self.param("expert_w2", init, (e_loc, f, d_model),
                            self.dtype)
            b2 = self.param("expert_b2", nn.initializers.zeros,
                            (e_loc, d_model), self.dtype)
            tokens = h.astype(self.dtype).reshape(b * t, d_model)
            out, aux = switch_moe(tokens, gate_w, w1, b1, w2, b2,
                                  token_mask=kv_mask.reshape(b * t),
                                  expert_axis=self.ep_axis)
            self.sow("losses", "moe_aux", aux)
            out = nn.Dropout(self.dropout,
                             deterministic=deterministic)(out)
            return x + out.reshape(b, t, d_model)
        h = nn.Dense(4 * d_model, dtype=self.dtype)(h)
        h = nn.gelu(h)
        h = nn.Dropout(self.dropout, deterministic=deterministic)(h)
        return x + nn.Dense(d_model, dtype=self.dtype)(h)


def quantized_encoder_block(qvars, scales, fvars, prefix: str, x,
                            attn_fn, n_heads: int, kv_mask=None):
    """Dequant-free int8 forward of ONE dense-FFN ``_EncoderBlock``
    (deterministic — the serving path never drops out): the four
    Dense matmuls run int8 x int8 -> int32 via ``dynamic_int8_matmul``
    from per-output-channel weight scales, LayerNorms stay f32,
    mirroring ``_EncoderBlock.__call__`` exactly. ``prefix`` is the
    block's flat param path (e.g. ``params/_EncoderBlock_0``). Returns
    None for a MoE block (3-D expert stacks sit outside the
    quantizer's 2-D/4-D kernel eligibility) so callers fall back to
    the generic dequantized path. Shared by the transformer zoo's
    ``quantized_apply`` implementations (models/vit.py);
    ``tests/test_stacked.py::test_vit_stacked_parity_and_int8_accuracy``
    is the regression net."""
    from ..model.jax_model import dynamic_int8_matmul

    if f"{prefix}/moe_gate" in fvars or f"{prefix}/moe_gate" in qvars:
        return None

    def ln(h, name):
        g = fvars[f"{prefix}/{name}/scale"].astype(jnp.float32)
        b = fvars[f"{prefix}/{name}/bias"].astype(jnp.float32)
        hf = h.astype(jnp.float32)
        m = hf.mean(-1, keepdims=True)
        v = ((hf - m) ** 2).mean(-1, keepdims=True)
        return (hf - m) * jax.lax.rsqrt(v + 1e-6) * g + b

    def dense(h, name):
        k = f"{prefix}/{name}/kernel"
        flat2d = h.reshape(-1, h.shape[-1])
        if k in qvars:
            out = dynamic_int8_matmul(flat2d, qvars[k], scales[k])
        else:  # per-layer f32 fallback
            out = flat2d @ fvars[k].astype(jnp.float32)
        out = out.reshape(*h.shape[:-1], out.shape[-1])
        bkey = f"{prefix}/{name}/bias"
        if bkey in fvars:
            out = out + fvars[bkey].astype(jnp.float32)
        return out

    d_model = x.shape[-1]
    x = x.astype(jnp.float32)
    h = ln(x, "LayerNorm_0")
    qkv = dense(h, "Dense_0")
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(a):  # (B, T, D) -> (B, H, T, Dh)
        b, t, _ = a.shape
        return a.reshape(b, t, n_heads,
                         d_model // n_heads).transpose(0, 2, 1, 3)

    o = attn_fn(heads(q), heads(k), heads(v), kv_mask)
    b, nh, t, dh = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, t, nh * dh)
    x = x + dense(o, "Dense_1")
    h = ln(x, "LayerNorm_1")
    h = nn.gelu(dense(h, "Dense_2"))
    return x + dense(h, "Dense_3")


class _TransformerTagger(nn.Module):
    vocab_size: int
    d_model: int
    n_heads: int
    n_layers: int
    n_tags: int
    max_len: int
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    moe_experts: int = 0

    @nn.compact
    def __call__(self, ids, attn_fn, *, train: bool = False):
        kv_mask = ids != PAD_ID  # hashed token ids are >= 1
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.dtype)(ids)
        pe = jnp.asarray(_sinusoidal(self.max_len, self.d_model))
        x = x + pe[None, :ids.shape[1]].astype(x.dtype)
        for _ in range(self.n_layers):
            x = _EncoderBlock(self.n_heads, dropout=self.dropout,
                              dtype=self.dtype,
                              moe_experts=self.moe_experts)(
                x, attn_fn, kv_mask, deterministic=not train)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        return nn.Dense(self.n_tags, dtype=jnp.float32)(x)


class JaxTransformerTagger(BaseModel):
    """Transformer token tagger; flash attention, optional sp ring."""

    #: Congruence metadata for the stacked-ensemble probe: sequence
    #: taggers serve variable-length token batches through their own
    #: predict path (no JaxModel bucket substrate), so same-family
    #: bins fall back to per-member runners by contract.
    stack_compatible = False

    @staticmethod
    def get_knob_config():
        return {
            "d_model": CategoricalKnob([64, 128, 256]),
            "n_heads": CategoricalKnob([2, 4, 8]),
            "n_layers": IntegerKnob(1, 6),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([16, 32, 64]),
            "max_epochs": IntegerKnob(3, 30),
            # Context length is searchable: flash/ring attention keep the
            # memory profile linear in max_len, so long contexts are a
            # knob, not a redesign.
            "max_len": CategoricalKnob([32, 64, 128, 256, 512]),
            "dropout": FloatKnob(0.0, 0.3),
            "vocab_size": FixedKnob(16384),
            # > 1 shards the sequence dim over sp chips.
            "sequence_parallel": FixedKnob(1),
            # Context-parallel schedule when sequence_parallel > 1:
            # "ring" (ppermute K/V rotation, T/n working set) or
            # "alltoall" (Ulysses head re-sharding, two collectives;
            # needs n_heads % sequence_parallel == 0).
            "sp_schedule": FixedKnob("ring"),
            # > 0 replaces each block's dense FFN with a Switch-routed
            # mixture of experts (top-1, capacity-dropped); experts
            # shard over the ep mesh axis set by expert_parallel.
            "moe_experts": FixedKnob(0),
            "expert_parallel": FixedKnob(1),
            # > 1 pipelines the encoder blocks over a pp mesh axis
            # (GPipe microbatch schedule; needs n_layers % pp == 0;
            # composes with sequence_parallel, dropout AND moe_experts/
            # expert_parallel — block params and optimizer state are
            # STORED stage-sharded (P("pp", ...)), expert stacks
            # additionally over ep (P("pp", "ep", ...)), ~1/pp per
            # chip).
            "pipeline_parallel": FixedKnob(1),
            # Microbatches per pipeline step; 0 = auto (~4·pp).
            "pp_microbatches": FixedKnob(0),
            # Deployment knob: pins init, dropout streams, and
            # per-epoch data order (and therefore checkpoint-resume
            # step identity) for reproducibility tests and re-runs.
            "seed": FixedKnob(0),
        }

    def __init__(self, **knobs: Any):
        super().__init__(**knobs)
        self._variables = None
        self._module: Optional[_TransformerTagger] = None
        self._meta: Dict[str, Any] = {}
        self._mesh = None
        self._predict_fn = None
        self._vars_dev = None

    # --- plumbing ---

    @property
    def mesh(self):
        if self._mesh is None:
            sp = int(self.knobs.get("sequence_parallel", 1))
            ep = int(self.knobs.get("expert_parallel", 1))
            pp = int(self.knobs.get("pipeline_parallel", 1))
            experts = int(self.knobs.get("moe_experts", 0))
            if ep > 1 and (experts == 0 or experts % ep != 0):
                # Silent fallback would pay the smaller dp axis while
                # the ep axis idles (dense model) or every expert
                # replicates (indivisible stack) — reject loudly.
                raise ValueError(
                    f"expert_parallel ({ep}) needs moe_experts set and "
                    f"divisible by it (got moe_experts={experts})")
            if pp > 1:
                n_layers = int(self.knobs.get("n_layers", 2))
                if n_layers % pp != 0:
                    raise ValueError(f"pipeline_parallel ({pp}) must "
                                     f"divide n_layers ({n_layers})")
            self._mesh = build_mesh(ChipGroup.current().devices(), sp=sp,
                                    ep=ep, pp=pp)
        return self._mesh

    def _attn_fn(self):
        """The attention the encoder blocks run, chosen by mesh shape.

        Bidirectional (non-causal) in all cases; tagging attends the
        whole sentence.
        """
        mesh = self.mesh
        if mesh.shape[SP_AXIS] > 1:
            mode = str(self.knobs.get("sp_schedule", "ring"))
            return lambda q, k, v, kv_mask: sequence_sharded_attention(
                q, k, v, mesh, causal=False, kv_mask=kv_mask, mode=mode)
        return default_attention(mesh, causal=False)

    # --- pipeline-parallel layout -------------------------------------
    #
    # With ``pipeline_parallel > 1`` the encoder blocks are STORED
    # stage-stacked: a ``{"outer": ..., "stages": {"stage{j}": ...}}``
    # tree whose stage leaves carry a leading pp axis that
    # ``shard_variables``' path rule places with ``P("pp", ...)`` —
    # each chip persistently holds only its own layer span (params AND
    # optimizer state drop ~1/pp per chip), not just pipelined compute.
    # ``self._variables`` keeps the ordinary flax layout so init /
    # dump_parameters / load_parameters / param sharing are unchanged;
    # the two helpers below convert at the train/predict boundary.

    def _pp_split(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Ordinary flax params → pp layout (host-side, cheap)."""
        pp = int(self.knobs.get("pipeline_parallel", 1))
        span = int(self.knobs.get("n_layers", 2)) // pp
        tmap = jax.tree_util.tree_map
        outer = {k: v for k, v in params.items()
                 if not k.startswith("_EncoderBlock_")}
        stages = {
            f"stage{j}": tmap(
                lambda *a: np.stack([np.asarray(x) for x in a]),
                *[params[f"_EncoderBlock_{s * span + j}"]
                  for s in range(pp)])
            for j in range(span)}
        return {"outer": outer, "stages": stages}

    def _pp_merge(self, pp_params: Dict[str, Any]) -> Dict[str, Any]:
        """pp layout → ordinary flax params (inverse of ``_pp_split``)."""
        pp = int(self.knobs.get("pipeline_parallel", 1))
        span = int(self.knobs.get("n_layers", 2)) // pp
        tmap = jax.tree_util.tree_map
        out = dict(pp_params["outer"])
        for j in range(span):
            for s in range(pp):
                out[f"_EncoderBlock_{s * span + j}"] = tmap(
                    lambda a, _s=s: a[_s], pp_params["stages"][f"stage{j}"])
        return out

    def _pp_logits_fn(self, n_tags: int, train: bool):
        """Assembled forward for ``pipeline_parallel > 1``: embed →
        GPipe-pipelined encoder blocks (``ops.pipeline_apply`` inside
        ``shard_map`` over pp, batch over dp, sequence over sp when
        ``sequence_parallel > 1``, experts over ep when
        ``moe_experts > 0``) → head, reading the pp param layout
        (see ``_pp_split``). Dropout is supported: the key is folded
        per (optimizer step, schedule tick, stage, sp shard), so every
        microbatch position draws an independent mask. MoE is
        supported: stage-stacked expert leaves enter the shard_map
        sharded ``P("pp", "ep", ...)`` so each rank holds its stage's
        slice of the expert stack, ``switch_moe`` runs in its
        collective form (route globally, compute local experts, psum
        partials over ep), and the router load-balance loss rides the
        pipeline in the microbatch carry.

        Returns ``logits_fn(pp_params, ids, step_i) -> (logits, aux)``
        where ``aux`` is the mean MoE load-balance loss (0.0 for dense
        models).
        """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..ops import pipeline_apply, ring_attention, ulysses_attention
        from ..parallel import EP_AXIS, PP_AXIS

        mesh = self.mesh
        pp = int(self.knobs.get("pipeline_parallel", 1))
        sp = mesh.shape[SP_AXIS]
        ep = mesh.shape[EP_AXIS]
        experts = int(self.knobs.get("moe_experts", 0))
        n_layers = int(self.knobs.get("n_layers", 2))
        span = n_layers // pp
        d_model = int(self.knobs.get("d_model", 128))
        vocab = int(self.knobs.get("vocab_size", 16384))
        max_len = int(self.knobs.get("max_len", 128))
        micro = int(self.knobs.get("pp_microbatches", 0))
        dropout = float(self.knobs.get("dropout", 0.0)) if train else 0.0
        seed = int(self.knobs.get("seed", 0))
        block = _EncoderBlock(
            int(self.knobs.get("n_heads", 4)), dropout=dropout,
            dtype=jnp.bfloat16, moe_experts=experts,
            moe_local_experts=(experts // ep) if ep > 1 else None,
            ep_axis=EP_AXIS if (ep > 1 and experts > 0) else None)
        if sp > 1:
            # Inside the pp shard_map the sequence dim is already the
            # local sp shard, so the attention must be the *collective*
            # form (ring/Ulysses over the sp axis of the SAME
            # shard_map), not sequence_sharded_attention's own wrapper.
            mode = str(self.knobs.get("sp_schedule", "ring"))
            inner = (ring_attention if mode == "ring"
                     else ulysses_attention)
            attn = (lambda q, k, v, kv_mask: inner(
                q, k, v, causal=False, axis_size=sp, kv_mask=kv_mask))
        else:
            # Already inside the pp shard_map (manual over the whole
            # mesh): the bare per-device attention, no second wrapper.
            attn = default_attention(causal=False)

        act_spec = P(DP_AXIS, SP_AXIS) if sp > 1 else P(DP_AXIS)

        def stage_leaf_spec(path, leaf):
            name = "/".join(str(getattr(p, "key", p))
                            for p in path).lower()
            if ep > 1 and experts > 0 and "expert" in name:
                return P(PP_AXIS, EP_AXIS)
            return P(PP_AXIS)

        def make_run_blocks(stages_tree):
            stage_specs = jax.tree_util.tree_map_with_path(
                stage_leaf_spec, stages_tree)

            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(stage_specs, act_spec, act_spec, P()),
                out_specs=(act_spec, P()), check_vma=False)
            def run_blocks(stages, x, mask, step_i):
                local = jax.tree_util.tree_map(lambda a: a[0], stages)

                def stage_fn(prm, xm, t):
                    xx, mm, aux = xm
                    det = dropout == 0.0
                    rngs = None
                    if not det:
                        key = jax.random.key(seed + 1)
                        for part in (step_i, t,
                                     jax.lax.axis_index(PP_AXIS)):
                            key = jax.random.fold_in(key, part)
                        if sp > 1:
                            key = jax.random.fold_in(
                                key, jax.lax.axis_index(SP_AXIS))
                    for j in range(span):
                        if not det:
                            rngs = {"dropout": jax.random.fold_in(key, j)}
                        # mutable=["losses"] is a no-op for dense blocks
                        # (empty collection, aux += 0), so one call
                        # covers both MoE and dense stages.
                        xx, mods = block.apply(
                            {"params": prm[f"stage{j}"]}, xx, attn,
                            mm, deterministic=det, rngs=rngs,
                            mutable=["losses"])
                        aux = aux + sum(jax.tree_util.tree_leaves(
                            mods.get("losses", {})))
                    return (xx, mm, aux)

                b = x.shape[0]
                if micro > 0:
                    if b % micro:
                        raise ValueError(
                            f"pp_microbatches ({micro}) must divide the "
                            f"per-dp-shard batch ({b})")
                    m = micro
                else:
                    m = min(b, 4 * pp)
                    while b % m:  # auto: largest divisor <= 4·pp
                        m -= 1
                xs = x.reshape(m, b // m, *x.shape[1:])
                ms = mask.reshape(m, b // m, *mask.shape[1:])
                # The aux accumulator rides the pipeline with the
                # activations: each stage adds its blocks' router
                # losses, so the collected last-stage value is the
                # microbatch's total across ALL layers.
                zeros = jnp.zeros((m, 1), jnp.float32)
                out, _, aux = pipeline_apply(
                    stage_fn, local, (xs, ms, zeros), axis_size=pp,
                    stage_takes_tick=True)
                aux = aux.mean()
                # Every rank must return the same replicated scalar
                # for out_specs=P(): average the data-shard axes.
                aux = jax.lax.pmean(aux, DP_AXIS)
                if sp > 1:
                    aux = jax.lax.pmean(aux, SP_AXIS)
                return out.reshape(b, *out.shape[2:]), aux

            return run_blocks

        def logits_fn(pp_params, ids, step_i):
            outer = pp_params["outer"]
            mask = ids != PAD_ID
            x = nn.Embed(vocab, d_model, dtype=jnp.bfloat16).apply(
                {"params": outer["Embed_0"]}, ids)
            pe = jnp.asarray(_sinusoidal(max_len, d_model))
            x = x + pe[None, :ids.shape[1]].astype(x.dtype)
            x, aux = make_run_blocks(pp_params["stages"])(
                pp_params["stages"], x, mask, step_i)
            x = nn.LayerNorm(dtype=jnp.float32).apply(
                {"params": outer["LayerNorm_0"]}, x)
            return nn.Dense(n_tags, dtype=jnp.float32).apply(
                {"params": outer["Dense_0"]}, x), aux

        return logits_fn

    def _ensure_module(self, n_tags: int) -> None:
        if self._module is None:
            self._module = _TransformerTagger(
                vocab_size=int(self.knobs.get("vocab_size", 16384)),
                d_model=int(self.knobs.get("d_model", 128)),
                n_heads=int(self.knobs.get("n_heads", 4)),
                n_layers=int(self.knobs.get("n_layers", 2)),
                n_tags=n_tags,
                max_len=int(self.knobs.get("max_len", 128)),
                dropout=float(self.knobs.get("dropout", 0.0)),
                moe_experts=int(self.knobs.get("moe_experts", 0)))

    def _encode(self, sentences: List[List[str]]):
        max_len = int(self.knobs.get("max_len", 128))
        vocab = int(self.knobs.get("vocab_size", 16384))
        ids = np.stack([hash_token_ids(s, vocab, max_len)
                        for s in sentences])
        lengths = np.asarray([min(len(s), max_len) for s in sentences],
                             np.int32)
        return ids, lengths

    # --- BaseModel ---

    def train(self, dataset_path: str, *,
              shared_params: Optional[Params] = None, **kwargs: Any) -> None:
        ds = load_corpus_dataset(dataset_path)
        n_tags = len(ds.tag_names)
        self._ensure_module(n_tags)
        self._meta = {"tag_names": list(ds.tag_names)}
        mesh = self.mesh
        dp = mesh.shape[DP_AXIS]
        max_len = int(self.knobs.get("max_len", 128))

        ids, lengths = self._encode(ds.sentences)
        tags = np.zeros((ds.size, max_len), np.int32)
        for i, t in enumerate(ds.tags):
            tags[i, :min(len(t), max_len)] = t[:max_len]

        batch_size = min(int(self.knobs.get("batch_size", 32)), ds.size)
        batch_size = max(dp, (batch_size // dp) * dp)
        max_epochs = int(self.knobs.get("max_epochs", 10))
        if self.knobs.get("quick_train", False):
            max_epochs = min(max_epochs,
                             int(self.knobs.get("trial_epochs", 1)))
        steps = max(1, ds.size // batch_size)

        rng = jax.random.key(int(self.knobs.get("seed", 0)))
        attn = self._attn_fn()
        module = self._module
        variables = jax.jit(
            lambda r, ids: module.init(r, ids, attn, train=False))(
            rng, jnp.zeros((dp, max_len), jnp.int32))
        if shared_params is not None:
            flat = traverse_util.flatten_dict(variables, sep="/")
            for kk, vv in shared_params.items():
                if kk in flat and tuple(flat[kk].shape) == tuple(vv.shape):
                    flat[kk] = jnp.asarray(vv)
            variables = traverse_util.unflatten_dict(flat, sep="/")
        # Expert-stacked leaves shard over ep, everything else
        # replicates (shard_variables' rules; with ep == 1 this is the
        # plain replicated placement). Under pp > 1 the blocks are
        # first re-laid stage-stacked so their leaves (and the optimizer
        # state derived from them) STORE sharded over pp — per-chip
        # param bytes drop ~1/pp, the point of pipeline parallelism.
        pp_mode = mesh.shape["pp"] > 1
        if pp_mode:
            params = shard_variables(
                self._pp_split(variables["params"]), mesh)
        else:
            params = shard_variables(variables, mesh)["params"]

        sched_epochs = schedule_epochs(kwargs, max_epochs)
        cache_key = step_cache_key(self, "train", mesh, steps, sched_epochs)
        cached = _step_cache_get(cache_key)
        if cached is not None:
            tx, train_step = cached["tx"], cached["step"]
        else:
            lr = float(self.knobs.get("learning_rate", 1e-3))
            total = max(1, steps * sched_epochs)
            sched = optax.warmup_cosine_decay_schedule(
                init_value=lr * 0.1, peak_value=lr,
                warmup_steps=max(1, total // 10), decay_steps=total,
                end_value=lr * 0.02)
            tx = optax.adamw(sched, weight_decay=1e-3)
            drop_key = jax.random.key(int(self.knobs.get("seed", 0)) + 1)
            pp_logits = (self._pp_logits_fn(n_tags, train=True)
                         if pp_mode else None)

            @jax.jit
            def train_step(params, opt_state, ids, lengths, tags, step_i):
                def loss_fn(p):
                    if pp_logits is not None:
                        # The pipelined forward carries the MoE router
                        # loss in the microbatch stream and returns it
                        # alongside the logits (0.0 for dense models).
                        logits, aux = pp_logits(p, ids, step_i)
                    else:
                        logits, mods = module.apply(
                            {"params": p}, ids, attn, train=True,
                            rngs={"dropout": jax.random.fold_in(
                                drop_key, step_i)},
                            mutable=["losses"])
                        # Router load-balance terms sown by MoE blocks
                        # (empty collection for dense models).
                        aux = sum(jax.tree_util.tree_leaves(
                            mods.get("losses", {})))
                    mask = (jnp.arange(logits.shape[1])[None, :]
                            < lengths[:, None]).astype(jnp.float32)
                    losses = optax.softmax_cross_entropy_with_integer_labels(
                        logits, tags)
                    loss = (losses * mask).sum() / jnp.maximum(mask.sum(),
                                                               1)
                    loss = loss + 0.01 * aux
                    correct = ((logits.argmax(-1) == tags) * mask).sum() \
                        / jnp.maximum(mask.sum(), 1)
                    return loss, correct
                (loss, acc), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                updates, opt_state = tx.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state,
                        loss, acc)

            _step_cache_put(cache_key, {"tx": tx, "step": train_step})

        opt_state = tx.init(params)
        logger.define_plot("Training", ["loss", "token_acc"],
                           x_axis="epoch")
        x_shard = batch_sharding(mesh)
        ckpt = LoopCheckpointer(kwargs)
        (params, opt_state), start_epoch = ckpt.restore((params, opt_state))
        seed = int(self.knobs.get("seed", 0))
        last_epoch = None
        # step_i drives the dropout fold_in (and the pp per-tick rng);
        # resuming it at the epoch boundary keeps the resumed run's rng
        # stream identical to an uninterrupted run's.
        step_i = start_epoch * steps
        for epoch in range(start_epoch, max_epochs):
            order = epoch_rng(seed, epoch).permutation(ds.size)
            ep_loss = ep_acc = 0.0
            for s in range(steps):
                sel = order[s * batch_size:(s + 1) * batch_size]
                if len(sel) < batch_size:
                    sel = np.resize(order, batch_size)
                params, opt_state, loss, acc = train_step(
                    params, opt_state,
                    jax.device_put(ids[sel], x_shard),
                    jax.device_put(lengths[sel], x_shard),
                    jax.device_put(tags[sel], x_shard),
                    jnp.int32(step_i))
                step_i += 1
                ep_loss += float(loss)
                ep_acc += float(acc)
            logger.log(epoch=epoch, loss=ep_loss / steps,
                       token_acc=ep_acc / steps)
            last_epoch = epoch
            ckpt.after_epoch(epoch, (params, opt_state), max_epochs)
        ckpt.after_loop(last_epoch, (params, opt_state))

        if pp_mode:
            params = self._pp_merge(params)
        self._variables = {"params": device_get_tree(params)}
        self._invalidate_compiled()

    def evaluate(self, dataset_path: str) -> float:
        assert self._variables is not None
        ds = load_corpus_dataset(dataset_path)
        max_len = int(self.knobs.get("max_len", 128))
        probs = self._predict_probs(ds.sentences)
        n_correct = n_total = 0
        for i, gold in enumerate(ds.tags):
            length = min(len(gold), max_len)
            pred = probs[i, :length].argmax(-1)
            n_correct += int((pred == np.asarray(gold[:length])).sum())
            n_total += length
        return n_correct / max(n_total, 1)

    def predict(self, queries: List[Any]) -> List[Any]:
        """Per-token tag distributions (the Predictor ensemble contract;
        see JaxPosTagger.predict)."""
        assert self._variables is not None
        if not queries:
            return []
        sentences = [list(q) for q in queries]
        probs = self._predict_probs(sentences)
        max_len = int(self.knobs.get("max_len", 128))
        return [probs[i, :min(len(s), max_len)].tolist()
                for i, s in enumerate(sentences)]

    def _predict_probs(self, sentences: List[List[str]]) -> np.ndarray:
        self._ensure_module(len(self._meta["tag_names"]))
        dp = self.mesh.shape[DP_AXIS]
        pp_mode = self.mesh.shape["pp"] > 1
        if self._vars_dev is None:
            # Same placement rules as training: expert stacks shard
            # over ep, stage stacks over pp (replicating either would
            # cost ep×/pp× HBM at inference), everything else
            # replicates.
            if pp_mode:
                self._vars_dev = {"params": shard_variables(
                    self._pp_split(self._variables["params"]),
                    self.mesh)}
            else:
                self._vars_dev = shard_variables(self._variables,
                                                 self.mesh)
        if self._predict_fn is None:
            if pp_mode:
                pp_logits = self._pp_logits_fn(
                    len(self._meta["tag_names"]), train=False)
                self._predict_fn = jax.jit(
                    lambda v, ids: jax.nn.softmax(
                        pp_logits(v["params"], ids, jnp.int32(0))[0], -1))
            else:
                module, attn = self._module, self._attn_fn()
                self._predict_fn = jax.jit(
                    lambda v, ids: jax.nn.softmax(
                        module.apply(v, ids, attn, train=False), -1))
        ids, _ = self._encode(sentences)
        n = len(sentences)
        bucket = dp
        while bucket < n:
            bucket *= 2
        if n < bucket:
            ids = np.concatenate(
                [ids, np.zeros((bucket - n, ids.shape[1]), ids.dtype)])
        out = np.asarray(self._predict_fn(
            self._vars_dev, jax.device_put(ids, batch_sharding(self.mesh))))
        return out[:n]

    def dump_parameters(self) -> Params:
        assert self._variables is not None
        flat = traverse_util.flatten_dict(self._variables, sep="/")
        out: Params = {k: np.asarray(v) for k, v in flat.items()}
        out["_meta/tag_names_json"] = np.frombuffer(
            json.dumps(self._meta["tag_names"]).encode(), np.uint8)
        return out

    def load_parameters(self, params: Params) -> None:
        blob = params.get("_meta/tag_names_json")
        assert blob is not None, "params missing _meta/tag_names_json"
        self._meta = {"tag_names": json.loads(
            np.asarray(blob).tobytes().decode())}
        flat = {k: np.asarray(v) for k, v in params.items()
                if not k.startswith("_meta/")}
        self._variables = traverse_util.unflatten_dict(flat, sep="/")
        self._module = None
        self._invalidate_compiled()
        self._ensure_module(len(self._meta["tag_names"]))

    def _invalidate_compiled(self) -> None:
        self._predict_fn = None
        self._vars_dev = None

    def destroy(self) -> None:
        self._invalidate_compiled()
        self._variables = None
        self._module = None
