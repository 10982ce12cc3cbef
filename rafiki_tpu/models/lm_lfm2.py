"""JaxLfm2MoeLM: an LFM2-family hybrid block stack on the LM's trainer.

Two kinds of sequence operator in one model, in the order a
``layer_types`` knob gives: a gated short convolution
(``ops/short_conv.py``) and grouped-query softmax attention (fewer
key-value heads than query heads, through the three flash kernels of
``ops/attention.py``); and two kinds of feed-forward: a dense SwiGLU in
the first ``n_dense_layers`` blocks, after them sigmoid-routed top-k
experts with a selection bias, of which THIS chip holds a stated share
(``ops/moe.py``; no shared expert). RMSNorm, rotary positions, a head
tied to the embedding. The first user is LFM2-8B-A1B at its published
widths (``benchmarks/configs/lfm2-8b-a1b-L5-E8.json``); every size is a
knob.

The trainer is ``JaxTransformerLM``'s (``models/lm.py``): this class
gives it dims, an initialiser, a forward, a loss and a FLOP count, as
``models/lm_moe.py`` does.

Equations (x (B, T, d); RMSNorm(x) = x / sqrt(mean(x²) + eps) · g;
SwiGLU(x) = W_down(silu(W_gate x) ⊙ W_up x); no biases):

- block: h = x + Op(RMSNorm₁(x)); y = h + FFN(RMSNorm₂(h)).
- ``conv`` Op: [b ‖ c ‖ u] = z W_in; v_t = Σ_j w_j ⊙ (b ⊙ u)_{t-(L-1)+j}
  (depth-wise, causal, L taps); out = (c ⊙ v) W_out.
- ``full_attention`` Op: q, k, v = z W_q, z W_k, z W_v (h / hk / hk
  heads); RMSNorm over each head's lanes of q and of k (own gains),
  then rotary on the whole head (half-split pairs); causal softmax
  attention, query head i reading key-value head i // (h / hk); W_o.
- sparse FFN: s = sigmoid(z W_r) in float32; a token's k experts are
  the top-k of s + b; gates = the chosen s / (their sum + 1e-6) ·
  scale; Σ over the chosen AND held experts of gate · SwiGLU_e(z).
  After each step b ← b + γ · sign(mean(c) − c), c the step's tokens
  per expert over all experts; b takes no gradient.
- head: logits = RMSNorm_f(y_L) W_embedᵀ, float32.

The stack. Layers are stacked by KIND, ``conv`` or ``attn`` with
``dense`` or ``sparse`` (``blocks/conv_sparse/in`` holds every
convolution-and-experts layer's input projection, in the model's
order), and the model runs its RUNS: each maximal stretch of
consecutive layers of one kind is one ``lax.scan`` over that stretch of
its kind's stack, in the order ``layer_types`` gives. One compiled body
a run, whatever its length; no Python-unrolled layer and no ``cond`` on
the kind inside a scan. A kind that comes back later in the pattern
(the whole model: conv-sparse after every attention layer) scans a
static slice of its stack.

Precision: bfloat16 matmul operands and attention; float32 masters,
Adam state, residual stream, router, norm statistics, logits and loss.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import optax

from ..model import FixedKnob, FloatKnob, IntegerKnob, PolicyKnob
from ..observe import phases as _phases
from ..ops import (batch_sharded_flash_attention, gated_short_conv,
                   held_experts_swiglu, sigmoid_topk_gates)
from ..parallel import replicated
from . import lm_moe as _moe
from .lm import STATE, JaxTransformerLM, _remat
from .lm_moe import _mm, _rms_norm, _swiglu

#: ``layer_types`` entries -> the operator's short name in a kind.
OPS = {"conv": "conv", "full_attention": "attn"}
#: The family adds this to the chosen scores' sum (DeepSeek-V3: 1e-20).
GATE_EPS = 1e-6


def kinds_of(s):
    """The kind (``conv_dense``, ``attn_sparse``, ...) of every layer,
    in the model's order."""
    return [f"{OPS[op]}_{'dense' if i < s['dense'] else 'sparse'}"
            for i, op in enumerate(s["pattern"])]


def runs_of(s):
    """[(kind, first, last)]: the maximal stretches of consecutive
    layers of one kind, ``first:last`` their rows in the kind's stack."""
    runs, seen = [], {}
    for kind in kinds_of(s):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        if runs and runs[-1][0] == kind and runs[-1][2] == at:
            runs[-1] = (kind, runs[-1][1], at + 1)
        else:
            runs.append((kind, at, at + 1))
    return runs


def shapes_of(s, kind):
    """({matrix: shape}, {gain: shape}) of one layer of ``kind``, the
    matrices in the order the initialiser draws them."""
    d, f, fm = s["d"], s["ffn"], s["moe_ffn"]
    op, ffn = kind.split("_")
    if op == "conv":
        mats = {"in": (d, 3 * d), "filter": (s["taps"], d), "out": (d, d)}
        norms = {"ln1": (d,), "ln2": (d,)}
    else:
        mats = {"q": (d, s["h"] * s["hd"]), "k": (d, s["hk"] * s["hd"]),
                "v": (d, s["hk"] * s["hd"]), "o": (s["h"] * s["hd"], d)}
        norms = {"ln1": (d,), "ln2": (d,), "q_norm": (s["hd"],),
                 "k_norm": (s["hd"],)}
    if ffn == "dense":
        mats.update({"gate": (d, f), "up": (d, f), "down": (f, d)})
    else:
        mats.update({"router": (d, s["experts"]),
                     "e_gate": (s["held"], d, fm),
                     "e_up": (s["held"], d, fm),
                     "e_down": (s["held"], fm, d)})
    return mats, norms


@functools.lru_cache(maxsize=8)
def _jitted_lfm2_init(dims_items, mesh):
    """One jitted device-side initialiser per dims and mesh, the tree
    born replicated on the trial's chip group. Matrices are normal(0,
    1/sqrt(fan_in)) (the filter's fan-in is its taps), the embedding
    0.02, gains one, the routers' biases zero. Matrix i of the fixed
    order embed, then the kinds in the order the pattern first meets
    them, each kind's stack drawn whole, draws from
    ``fold_in(key(seed), i)``."""
    s = dict(dims_items)
    kinds = kinds_of(s)

    @functools.partial(jax.jit, out_shardings=replicated(mesh))
    def init(seed):
        key = jax.random.key(seed)
        count = iter(range(10 ** 6))

        def mat(shape, scale=None):
            scale = scale or 1.0 / math.sqrt(shape[-2])
            return scale * jax.random.normal(
                jax.random.fold_in(key, next(count)), shape, jnp.float32)

        tree = {"embed": mat((s["v"], s["d"]), 0.02),
                "lnf": jnp.ones((s["d"],), jnp.float32), "blocks": {},
                STATE: {}}
        for kind in dict.fromkeys(kinds):
            stack = (kinds.count(kind),)
            mats, norms = shapes_of(s, kind)
            tree["blocks"][kind] = {
                **{n: mat(stack + shape) for n, shape in mats.items()},
                **{n: jnp.ones(stack + shape, jnp.float32)
                   for n, shape in norms.items()}}
            if kind.endswith("_sparse"):
                tree[STATE][f"{kind}_bias"] = jnp.zeros(
                    stack + (s["experts"],), jnp.float32)
        return tree

    return init


def _rope(x, theta):
    """Rotary positions on the whole head of (B, T, heads, r), float32:
    half-split pairs (i, i + r/2), rotate-half."""
    t, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.tile(jnp.cos(ang), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(ang), 2)[None, :, None, :]
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + rot * sin


def _conv_op(u, p):
    """The gated short convolution between its two projections. ``u``
    normalised."""
    b, c, x = jnp.split(_mm(u, p["in"]), 3, axis=-1)
    return _mm(gated_short_conv(b, c, x, p["filter"]), p["out"])


def _gqa_op(u, p, s, mesh):
    """Grouped-query attention, training form (no cache). ``u``
    normalised. k and v keep their ``hk`` heads all the way: the flash
    kernels read a group's key-value block through their block maps."""
    b, t, _ = u.shape
    with jax.named_scope("gqa_attention"):
        q = _mm(u, p["q"]).reshape(b, t, s["h"], s["hd"])
        k = _mm(u, p["k"]).reshape(b, t, s["hk"], s["hd"])
        v = _mm(u, p["v"]).reshape(b, t, s["hk"], s["hd"])
        q = _rope(_rms_norm(q, p["q_norm"], s["eps"]), s["theta"])
        k = _rope(_rms_norm(k, p["k_norm"], s["eps"]), s["theta"])
        o = batch_sharded_flash_attention(
            *(a.astype(_moe.COMPUTE).transpose(0, 2, 1, 3)
              for a in (q, k, v)), mesh, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, s["h"] * s["hd"])
        return _mm(o, p["o"])


def _experts(u, p, bias, s):
    """(the held experts' part, tokens per expert over ALL experts).
    ``u`` normalised, float32."""
    b, t, d = u.shape
    x = u.reshape(b * t, d)
    with jax.named_scope("moe_ffn"):
        gates, chosen = sigmoid_topk_gates(
            x, p["router"], bias, k=s["k"], scale=s["scale"], eps=GATE_EPS)
        held = slice(s["first"], s["first"] + s["held"])
        routed = held_experts_swiglu(
            x.astype(_moe.COMPUTE), gates[:, held], chosen[:, held],
            p["e_gate"], p["e_up"], p["e_down"])
    return routed.reshape(b, t, d), chosen.sum(0, dtype=jnp.float32)


def _block(x, p, bias, kind, s, mesh):
    """One block of ``kind``; ``bias`` is the router's (None in a dense
    block). Returns (y, tokens per expert or None)."""
    op, ffn = kind.split("_")
    u = _rms_norm(x, p["ln1"], s["eps"])
    x = x + (_conv_op(u, p) if op == "conv"
             else _gqa_op(u, p, s, mesh)).astype(x.dtype)
    u = _rms_norm(x, p["ln2"], s["eps"])
    if ffn == "dense":
        return x + _swiglu(u, p["gate"], p["up"], p["down"]
                           ).astype(x.dtype), None
    y, counts = _experts(u, p, bias, s)
    return x + y, counts


def _rows(tree, first, last):
    """Rows ``first:last`` of every stacked leaf; the whole stack is
    handed on as it is (a slice would copy it)."""
    return jax.tree.map(
        lambda a: a if (first, last) == (0, a.shape[0]) else a[first:last],
        tree)


def _lfm2_hidden(params, ids, s, remat, mesh):
    """(y_L before the final norm, float32; {bias name: tokens per
    expert of that kind's layers, (layers of the kind, E)}): one scan a
    run of layers of one kind, in the pattern's order."""
    x = params["embed"][ids]
    counts: Dict[str, list] = {}
    for kind, first, last in runs_of(s):
        block = _remat(functools.partial(_block, kind=kind, s=s, mesh=mesh),
                       remat)
        stack = _rows(params["blocks"][kind], first, last)
        if kind.endswith("_sparse"):
            name = f"{kind}_bias"
            x, c = jax.lax.scan(
                lambda x, pb: block(x, *pb), x,
                (stack, _rows(params[STATE][name], first, last)))
            counts.setdefault(name, []).append(c)
        else:
            x, _ = jax.lax.scan(lambda x, p: (block(x, p, None)[0], None),
                                x, stack)
    return x, {name: jnp.concatenate(parts) for name, parts
               in counts.items()}


def _head(y, params, s):
    return _mm(_rms_norm(y, params["lnf"], s["eps"]), params["embed"].T
               ).astype(jnp.float32)


def _lfm2_forward(params, ids, s, remat, mesh):
    """Logits (float32) of ``ids``."""
    y, _ = _lfm2_hidden(params, ids, s, remat, mesh)
    return _head(y, params, s)


def _lfm2_loss(weights, state, win, s, remat, mesh):
    """Next-token loss of one (B, t+1) window batch. ``counts`` =
    [assignments routed to held experts, to absent ones, Σ over sparse
    blocks of the busiest held expert's] (``phases.moe_routed``);
    ``state`` = the routers' biases after this step's update."""
    params = {**weights, STATE: state}
    y, counts = _lfm2_hidden(params, win[:, :-1], s, remat, mesh)
    logits = _head(y, params, s)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, win[:, 1:]).mean()
    acc = (logits.argmax(-1) == win[:, 1:]).mean()
    new_state = {
        name: state[name] + s["gamma"] * jnp.sign(
            c.mean(-1, keepdims=True) - c) for name, c in counts.items()}
    every = jnp.concatenate(list(counts.values()))
    here = every[:, s["first"]:s["first"] + s["held"]]
    held = here.sum()
    return loss, (acc, jnp.stack([held, every.sum() - held,
                                  here.max(-1).sum()]), new_state)


class JaxLfm2MoeLM(JaxTransformerLM):
    """Hybrid short-convolution / grouped-query-attention LM over
    sparse experts, one chip's share of them, on ``JaxTransformerLM``'s
    trainer."""

    _forward_fn = staticmethod(_lfm2_forward)
    _loss_fn = staticmethod(_lfm2_loss)
    GENERATE_REFUSAL = (
        "JaxLfm2MoeLM cannot serve /generate: models/lm_generate.py "
        "keeps no convolution state beside its paged K/V, has no "
        "grouped-query page layout and no sparse-expert block in its "
        "prefill and decode layers; deploy it for /predict (scoring) "
        "with RAFIKI_TPU_SERVING_GENERATE off")

    @staticmethod
    def get_knob_config():
        return {
            # A small default shape; a deployment pins every size
            # (benchmarks/templates/lfm2_knobs.py.tmpl).
            "d_model": FixedKnob(256),
            "n_heads": FixedKnob(4),
            "n_kv_heads": FixedKnob(2),
            "n_layers": FixedKnob(5),
            # The sequence operator of every layer, in order; the first
            # n_dense_layers carry a dense feed-forward, the others
            # experts.
            "layer_types": FixedKnob(["conv", "full_attention", "conv",
                                      "conv", "conv"]),
            "n_dense_layers": FixedKnob(1),
            "conv_taps": FixedKnob(3),
            "seq_len": FixedKnob(512),
            "vocab_size": FixedKnob(4096),
            "ffn_dense": FixedKnob(1024),
            "ffn_expert": FixedKnob(256),
            # The router's width, the experts a token takes, and the
            # share of them this rank holds and computes.
            "n_experts": FixedKnob(8),
            "experts_per_token": FixedKnob(2),
            "experts_held": FixedKnob(8),
            "first_expert": FixedKnob(0),
            "routed_scaling": FixedKnob(1.0),
            "rope_theta": FixedKnob(1000000.0),
            "rms_eps": FixedKnob(1e-5),
            "bias_rate": FixedKnob(0.001),
            "batch_size": FixedKnob(2),
            "learning_rate": FloatKnob(1e-4, 1e-2, is_exp=True),
            "train_steps": IntegerKnob(20, 20000),
            "remat": FixedKnob("dots"),
            "steps_per_dispatch": FixedKnob(8),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "trial_steps": FixedKnob(30),
            "seed": FixedKnob(0),
        }

    def _dims(self):
        defaults = {name: knob.value for name, knob
                    in self.get_knob_config().items()
                    if isinstance(knob, FixedKnob)}

        def knob(name, kind=int):
            return kind(self.knobs.get(name, defaults[name]))

        s = dict(
            d=knob("d_model"), h=knob("n_heads"), hk=knob("n_kv_heads"),
            layers=knob("n_layers"), pattern=knob("layer_types", tuple),
            dense=knob("n_dense_layers"), taps=knob("conv_taps"),
            t=knob("seq_len"), v=knob("vocab_size"), ffn=knob("ffn_dense"),
            moe_ffn=knob("ffn_expert"), experts=knob("n_experts"),
            k=knob("experts_per_token"), held=knob("experts_held"),
            first=knob("first_expert"),
            scale=knob("routed_scaling", float),
            theta=knob("rope_theta", float), eps=knob("rms_eps", float),
            gamma=knob("bias_rate", float))
        s["hd"] = s["d"] // s["h"]
        assert len(s["pattern"]) == s["layers"] > s["dense"] >= 0, s
        assert set(s["pattern"]) <= set(OPS) and s["hd"] % 2 == 0, s
        assert s["h"] % s["hk"] == 0 and s["d"] % s["h"] == 0, s
        assert s["first"] + s["held"] <= s["experts"] >= s["k"], s
        return s

    def _init_params(self) -> Dict[str, Any]:
        init = _jitted_lfm2_init(tuple(sorted(self._dims().items())),
                                 self.mesh)
        return init(int(self.knobs.get("seed", 0)))

    def _train_setup(self, dataset_path: str):
        ds, steps, b, k_disp, train_chunk, params, opt_state = \
            super()._train_setup(dataset_path)
        # Which pattern this trial trains, from the stacks it built.
        for kind, stack in params["blocks"].items():
            _phases.lm_layers(*kind.split("_"), stack["ln1"].shape[0])
        return ds, steps, b, k_disp, train_chunk, params, opt_state

    def _flops_per_step(self, b: int) -> float:
        """Useful train-step FLOPs (fwd+bwd): 6 x the matmul parameters
        a token touches (the filter's taps counted as such; routed
        experts by the EXPECTED assignments to held experts, k · held /
        experts a token) plus causal attention at the head's own lanes.
        ``benchmarks/flops_lfm2.py`` is the benchmark's copy (it takes
        the assignments really held)."""
        s = self._dims()
        d = s["d"]
        conv = 4 * d * d + s["taps"] * d
        attn = 2 * d * s["hd"] * (s["h"] + s["hk"])
        expert = 3 * d * s["moe_ffn"]
        per_token = s["v"] * d
        for kind in kinds_of(s):
            op, ffn = kind.split("_")
            per_token += conv if op == "conv" else attn
            per_token += 3 * d * s["ffn"] if ffn == "dense" else (
                d * s["experts"] + expert * s["k"] * s["held"]
                / s["experts"])
        n_attn = sum(op == "full_attention" for op in s["pattern"])
        attention = 3 * b * s["h"] * 2 * s["hd"] * s["t"] * s["t"]
        return 6 * b * s["t"] * per_token + n_attn * attention

    def _count_dispatch(self, counts) -> None:
        _phases.moe_routed(*(float(c) for c in counts))

    def make_generator(self, **cfg: Any):
        raise NotImplementedError(self.GENERATE_REFUSAL)
