"""JaxLatentMoELM: a DeepSeek-V3-family block on the LM's trainer.

Latent attention (MLA: two low-rank projections, rotary positions on a
slice of each head, q·k wider than v), a leading dense SwiGLU layer
beside a stack of sparse ones (256-way sigmoid router, top-k dropless
experts of which THIS chip holds a stated share, a shared expert,
bias-corrected selection whose bias is carried through the step without
a gradient), RMSNorm, an untied head and one multi-token-prediction
module with its second loss. The first user is JoyAI-LLM-Flash at its
published widths (``benchmarks/configs/joyai-llm-flash-L5-E8.json``);
every size is a knob.

What is NOT here is the trainer: ``JaxTransformerLM`` (``models/lm.py``)
owns ``_train_setup`` / ``train`` / ``evaluate`` / ``predict`` /
``dump_parameters`` / the step cache, and this class gives it dims, an
initialiser, a forward, a loss and a FLOP count. The block is written
once, as module-level functions of (params, ids, dims, remat, mesh),
and train, evaluate and predict all run it.

Equations (x (B, T, d); RMSNorm(x) = x / sqrt(mean(x²) + eps) · g;
SwiGLU_f(x) = W_down(silu(W_gate x) ⊙ W_up x); no biases):

- block: h = x + MLA(RMSNorm₁(x)); y = h + FFN(RMSNorm₂(h)); FFN is
  SwiGLU in the first ``n_dense_layers`` blocks, MoE after.
- MLA: c_q = RMSNorm(u W_qa); q = c_q W_qb, per head q_nope ‖ q_rope;
  [c_kv ‖ k_r] = u W_kva; c_kv ← RMSNorm(c_kv); [k_nope ‖ v] = c_kv
  W_kvb per head; rotary (interleaved pairs, de-interleaved then
  rotate-half, as HF's ``apply_rotary_pos_emb_interleave``) on q_rope
  and on k_r, one head shared by all; o = softmax(causal(q kᵀ /
  √(nope + rope))) v; out = concat(o) W_o.
- MoE: ``ops/moe.py``: gates over all experts, products over the held
  range [first_expert, first_expert + experts_held), plus the shared
  expert. After each step b ← b + γ · sign(mean(c) − c), c the step's
  tokens per expert.
- head: logits = RMSNorm_f(y_L) W_headᵀ, float32.
- multi-token module (depth 1, DeepSeek-V3 report §2.2): h′ᵢ = W_eh
  [RMSNorm_e(Emb(t_{i+1})) ‖ RMSNorm_h(y_L,ᵢ)]; h″ = sparse block(h′);
  logits′ = RMSNorm_s(h″) W_headᵀ; loss = CE(logits, t_{i+1}) over T
  positions + λ · CE(logits′, t_{i+2}) over T − 1.

Precision: bfloat16 matmul operands and attention; float32 masters,
Adam state, residual stream, router, norms, logits and losses.
Attention runs on the existing flash kernels with v zero-padded to
q's width and o sliced back (kernels with d_v ≠ d_qk: ROADMAP).
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
from ..ops import (batch_sharded_flash_attention, held_experts_swiglu,
                   sigmoid_topk_gates)
from ..parallel import replicated
from .lm import STATE, JaxTransformerLM, _remat


def _shapes(s):
    """{group: {name: shape}} of every matrix and every norm gain of
    one block of each group (the stacked groups get their leading layer
    dimension in ``_jitted_moe_init``)."""
    d, h = s["d"], s["h"]
    attn = {"q_a": (d, s["q_rank"]),
            "q_b": (s["q_rank"], h * (s["nope"] + s["rope"])),
            "kv_a": (d, s["kv_rank"] + s["rope"]),
            "kv_b": (s["kv_rank"], h * (s["nope"] + s["vd"])),
            "o": (h * s["vd"], d)}
    norms = {"ln1": (d,), "ln2": (d,), "q_norm": (s["q_rank"],),
             "kv_norm": (s["kv_rank"],)}
    f, fm, fs = s["ffn"], s["moe_ffn"], s["moe_ffn"] * s["shared"]
    dense = {"gate": (d, f), "up": (d, f), "down": (f, d)}
    moe = {"router": (d, s["experts"]),
           "e_gate": (s["held"], d, fm), "e_up": (s["held"], d, fm),
           "e_down": (s["held"], fm, d),
           "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d)}
    return {"dense": ({**attn, **dense}, norms),
            "sparse": ({**attn, **moe}, norms),
            "mtp": ({"eh": (2 * d, d), **attn, **moe},
                    {**norms, "ln_e": (d,), "ln_h": (d,), "lnf": (d,)})}


@functools.lru_cache(maxsize=8)
def _jitted_moe_init(dims_items, mesh):
    """One jitted device-side initialiser per dims and mesh, the tree
    born replicated on the trial's chip group. Matrices are
    normal(0, 1/sqrt(fan_in)), the embedding 0.02, gains one, biases of
    the routers zero; matrix i of the fixed order embed, head, dense,
    sparse, multi-token module draws from ``fold_in(key(seed), i)``."""
    s = dict(dims_items)
    shapes = _shapes(s)
    stacks = {"dense": (s["dense"],), "sparse": (s["layers"] - s["dense"],),
              "mtp": ()}
    groups = ["dense", "sparse"] + (["mtp"] if s["mtp"] else [])

    @functools.partial(jax.jit, out_shardings=replicated(mesh))
    def init(seed):
        key = jax.random.key(seed)
        count = iter(range(10 ** 6))

        def mat(shape, scale=None):
            scale = scale or 1.0 / math.sqrt(shape[-2])
            return scale * jax.random.normal(
                jax.random.fold_in(key, next(count)), shape, jnp.float32)

        tree = {"embed": mat((s["v"], s["d"]), 0.02),
                "head": mat((s["v"], s["d"]), 1.0 / math.sqrt(s["d"])),
                "lnf": jnp.ones((s["d"],), jnp.float32), "blocks": {},
                STATE: {}}
        for group in groups:
            mats, norms = shapes[group]
            tree["blocks"][group] = {
                **{n: mat(stacks[group] + shape)
                   for n, shape in mats.items()},
                **{n: jnp.ones(stacks[group] + shape, jnp.float32)
                   for n, shape in norms.items()}}
            if group != "dense":
                tree[STATE][f"{group}_bias"] = jnp.zeros(
                    stacks[group] + (s["experts"],), jnp.float32)
        return tree

    return init


def _rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps) * g


#: The type of matmul operands and of attention. (The tier-1 tests set
#: float32 to hold the equations to the reference leaf by leaf, where
#: bfloat16 flips near-tied top-k choices.)
COMPUTE = jnp.bfloat16


def _mm(x, w):
    return x.astype(COMPUTE) @ w.astype(COMPUTE)


def _rope(x, theta):
    """Rotary positions on (B, T, heads, r), float32: the projection's
    pairs (2i, 2i+1) are the rotated pairs, de-interleaved to
    half-split and then rotate-half."""
    b, t, h, r = x.shape
    x = x.astype(jnp.float32).reshape(b, t, h, r // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(b, t, h, r)
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.tile(jnp.cos(ang), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(ang), 2)[None, :, None, :]
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + rot * sin


def _mla(u, p, s, mesh):
    """Latent attention, training form (no cache). ``u`` normalised."""
    b, t, _ = u.shape
    h, nope, rope, vd = s["h"], s["nope"], s["rope"], s["vd"]
    c_q = _rms_norm(_mm(u, p["q_a"]), p["q_norm"], s["eps"])
    q = _mm(c_q, p["q_b"]).reshape(b, t, h, nope + rope)
    c_kv, k_r = jnp.split(_mm(u, p["kv_a"]), [s["kv_rank"]], axis=-1)
    c_kv = _rms_norm(c_kv, p["kv_norm"], s["eps"])
    k_nope, v = jnp.split(
        _mm(c_kv, p["kv_b"]).reshape(b, t, h, nope + vd), [nope], axis=-1)
    q_r = _rope(q[..., nope:], s["theta"]).astype(COMPUTE)
    k_r = _rope(k_r[:, :, None, :], s["theta"]).astype(COMPUTE)
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, t, h, rope))], -1)
    # Past 128 lanes of (padded) head the dkv kernel's 1024 x 1024
    # blocks overflow the 16 MiB of scoped VMEM (18.8 MiB at 256 lanes:
    # the chip's compiler refuses it), so the kv block is halved.
    blocks = {"block_kv": 512} if nope + rope > 128 else {}
    o = batch_sharded_flash_attention(
        *(a.transpose(0, 2, 1, 3) for a in (q, k, v)), mesh, causal=True,
        **blocks)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h * vd)
    return _mm(o, p["o"])


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _moe_ffn(u, p, bias, s, live=None):
    """(the held experts' part + the shared expert, tokens per expert
    over ALL experts). ``u`` normalised, float32. ``live`` (B, T) bool
    marks the real tokens: the others are routed nowhere and counted
    nowhere (the multi-token module's last position, below)."""
    b, t, d = u.shape
    x = u.reshape(b * t, d)
    gates, chosen = sigmoid_topk_gates(x, p["router"], bias, k=s["k"],
                                       scale=s["scale"])
    if live is not None:
        chosen = chosen & live.reshape(b * t, 1)
        gates = jnp.where(chosen, gates, 0.0)
    held = slice(s["first"], s["first"] + s["held"])
    xb = x.astype(COMPUTE)
    routed = held_experts_swiglu(xb, gates[:, held], chosen[:, held],
                                 p["e_gate"], p["e_up"], p["e_down"])
    shared = _swiglu(xb, p["s_gate"], p["s_up"], p["s_down"])
    return ((routed + shared.astype(jnp.float32)).reshape(b, t, d),
            chosen.sum(0, dtype=jnp.float32))


def _dense_block(x, p, s, mesh):
    x = x + _mla(_rms_norm(x, p["ln1"], s["eps"]), p, s, mesh
                 ).astype(x.dtype)
    u = _rms_norm(x, p["ln2"], s["eps"])
    return x + _swiglu(u, p["gate"], p["up"], p["down"]).astype(x.dtype)


def _sparse_block(x, p, bias, s, mesh, live=None):
    x = x + _mla(_rms_norm(x, p["ln1"], s["eps"]), p, s, mesh
                 ).astype(x.dtype)
    y, counts = _moe_ffn(_rms_norm(x, p["ln2"], s["eps"]), p, bias, s,
                         live)
    return x + y, counts


def _moe_lm_hidden(params, ids, s, remat, mesh):
    """(y_L before the final norm, float32; tokens per expert of every
    sparse block, (n_sparse, E)): two scans, the dense group then the
    sparse one."""
    dense = _remat(functools.partial(_dense_block, s=s, mesh=mesh), remat)
    sparse = _remat(functools.partial(_sparse_block, s=s, mesh=mesh),
                    remat)
    x = params["embed"][ids]
    x, _ = jax.lax.scan(lambda x, p: (dense(x, p), None), x,
                        params["blocks"]["dense"])
    return jax.lax.scan(lambda x, pb: sparse(x, *pb), x,
                        (params["blocks"]["sparse"],
                         params[STATE]["sparse_bias"]))


def _head(y, gain, params, s):
    return _mm(_rms_norm(y, gain, s["eps"]), params["head"].T
               ).astype(jnp.float32)


def _moe_lm_forward(params, ids, s, remat, mesh):
    """The main head's logits (float32) of ``ids``."""
    y, _ = _moe_lm_hidden(params, ids, s, remat, mesh)
    return _head(y, params["lnf"], params, s)


def _cross_entropy(logits, targets, live=None):
    nll = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return nll.mean() if live is None else (nll * live).sum() / live.sum()


def _moe_lm_loss(weights, state, win, s, remat, mesh):
    """Main loss + λ · the multi-token module's, from one (B, t+1)
    window batch. ``counts`` = [assignments routed to held experts, to
    absent ones, Σ over sparse blocks of the busiest held expert's];
    ``state`` = the routers' biases after this step's update."""
    params = {**weights, STATE: state}
    y, counts = _moe_lm_hidden(params, win[:, :-1], s, remat, mesh)
    logits = _head(y, params["lnf"], params, s)
    loss = _cross_entropy(logits, win[:, 1:])
    acc = (logits.argmax(-1) == win[:, 1:]).mean()

    def stepped(bias, c):
        return bias + s["gamma"] * jnp.sign(
            c.mean(-1, keepdims=True) - c)

    new_state = {"sparse_bias": stepped(state["sparse_bias"], counts)}
    if s["mtp"]:
        # The module pairs Emb(t_{i+1}) with y_i and predicts t_{i+2}:
        # T - 1 positions. It runs on all T (a length that is not a
        # multiple of the TPU's tiles costs relayout loops over the
        # logits), the last one dead: causal attention lets it reach no
        # other, and it is routed nowhere, counted nowhere and carries
        # no loss.
        m = weights["blocks"]["mtp"]
        t = win.shape[1] - 1
        live = jnp.broadcast_to(jnp.arange(t) < t - 1, (win.shape[0], t))
        joined = jnp.concatenate(
            [_rms_norm(params["embed"][win[:, 1:]], m["ln_e"], s["eps"]),
             _rms_norm(y, m["ln_h"], s["eps"])], -1)
        block = _remat(functools.partial(_sparse_block, s=s, mesh=mesh),
                       remat)
        y2, c2 = block(_mm(joined, m["eh"]).astype(jnp.float32), m,
                       state["mtp_bias"], live=live)
        loss = loss + s["lam"] * _cross_entropy(
            _head(y2, m["lnf"], params, s),
            jnp.pad(win[:, 2:], ((0, 0), (0, 1))), live)
        new_state["mtp_bias"] = stepped(state["mtp_bias"], c2)
        counts = jnp.concatenate([counts, c2[None]])
    here = counts[:, s["first"]:s["first"] + s["held"]]
    held = here.sum()
    return loss, (acc, jnp.stack([held, counts.sum() - held,
                                  here.max(-1).sum()]), new_state)


class JaxLatentMoELM(JaxTransformerLM):
    """Latent-attention + sparse-expert + multi-token-prediction LM, one
    chip's share of the experts, on ``JaxTransformerLM``'s trainer."""

    _forward_fn = staticmethod(_moe_lm_forward)
    _loss_fn = staticmethod(_moe_lm_loss)
    GENERATE_REFUSAL = (
        "JaxLatentMoELM cannot serve /generate: models/lm_generate.py "
        "has no latent (MLA) KV cache and no sparse-expert block in its "
        "prefill and decode layers; deploy it for /predict (scoring) "
        "with RAFIKI_TPU_SERVING_GENERATE off")

    @staticmethod
    def get_knob_config():
        return {
            # A small default shape; a deployment pins every size
            # (benchmarks/templates/joyai_knobs.py.tmpl).
            "d_model": FixedKnob(256),
            "n_heads": FixedKnob(4),
            "n_layers": IntegerKnob(2, 8),      # dense + sparse blocks
            "n_dense_layers": FixedKnob(1),
            "seq_len": FixedKnob(512),
            "vocab_size": FixedKnob(4096),
            "q_lora_rank": FixedKnob(192),
            "kv_lora_rank": FixedKnob(64),
            "qk_nope_head_dim": FixedKnob(64),
            "qk_rope_head_dim": FixedKnob(32),
            "v_head_dim": FixedKnob(64),
            "ffn_dense": FixedKnob(1024),
            "ffn_expert": FixedKnob(128),
            # The router's width, the experts a token takes, and the
            # share of them this rank holds and computes.
            "n_experts": FixedKnob(16),
            "experts_per_token": FixedKnob(4),
            "experts_held": FixedKnob(16),
            "first_expert": FixedKnob(0),
            "n_shared_experts": FixedKnob(1),
            "routed_scaling": FixedKnob(2.5),
            "rope_theta": FixedKnob(10000.0),
            "rms_eps": FixedKnob(1e-6),
            "mtp_depth": FixedKnob(1),          # 0 or 1
            "mtp_weight": FixedKnob(0.3),
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
        defaults["n_layers"] = 4  # the one searched size

        def knob(name, kind=int):
            return kind(self.knobs.get(name, defaults[name]))

        s = dict(
            d=knob("d_model"), h=knob("n_heads"), layers=knob("n_layers"),
            dense=knob("n_dense_layers"), t=knob("seq_len"),
            v=knob("vocab_size"), q_rank=knob("q_lora_rank"),
            kv_rank=knob("kv_lora_rank"), nope=knob("qk_nope_head_dim"),
            rope=knob("qk_rope_head_dim"), vd=knob("v_head_dim"),
            ffn=knob("ffn_dense"), moe_ffn=knob("ffn_expert"),
            experts=knob("n_experts"), k=knob("experts_per_token"),
            held=knob("experts_held"), first=knob("first_expert"),
            shared=knob("n_shared_experts"),
            scale=knob("routed_scaling", float),
            theta=knob("rope_theta", float), eps=knob("rms_eps", float),
            mtp=knob("mtp_depth"), lam=knob("mtp_weight", float),
            gamma=knob("bias_rate", float))
        assert 0 < s["dense"] < s["layers"] and s["mtp"] in (0, 1), s
        assert s["first"] + s["held"] <= s["experts"] >= s["k"], s
        assert s["vd"] <= s["nope"] + s["rope"] and s["rope"] % 2 == 0, s
        return s

    def _init_params(self) -> Dict[str, Any]:
        init = _jitted_moe_init(tuple(sorted(self._dims().items())),
                                self.mesh)
        return init(int(self.knobs.get("seed", 0)))

    def _flops_per_step(self, b: int) -> float:
        """Useful train-step FLOPs (fwd+bwd): 6 x the matmul parameters
        a token touches (routed experts by the EXPECTED assignments to
        held experts, k · held / experts a token) plus causal attention
        with q·k of nope + rope and p·v of v_head_dim; the multi-token
        module runs on t − 1 tokens. ``benchmarks/flops_moe.py`` is the
        benchmark's copy (it takes the assignments really held)."""
        s = self._dims()
        d, h, fm = s["d"], s["h"], s["moe_ffn"]
        attn = (d * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
                + d * (s["kv_rank"] + s["rope"])
                + s["kv_rank"] * h * (s["nope"] + s["vd"])
                + h * s["vd"] * d)
        sparse = (attn + d * s["experts"] + 3 * d * fm * s["shared"]
                  + 3 * d * fm * s["k"] * s["held"] / s["experts"])
        n_sparse = s["layers"] - s["dense"]
        main = (s["dense"] * (attn + 3 * d * s["ffn"]) + n_sparse * sparse
                + s["v"] * d)
        mtp = s["mtp"] * (2 * d * d + sparse + s["v"] * d)

        def attention(t):
            return 3 * b * h * (s["nope"] + s["rope"] + s["vd"]) * t * t

        return (6 * b * (main * s["t"] + mtp * (s["t"] - 1))
                + s["layers"] * attention(s["t"])
                + s["mtp"] * attention(s["t"] - 1))

    def _count_dispatch(self, counts) -> None:
        _phases.moe_routed(*(float(c) for c in counts))

    def make_generator(self, **cfg: Any):
        raise NotImplementedError(self.GENERATE_REFUSAL)
