"""Plain reference of one chip's share of a JoyAI-LLM-Flash train step
(a DeepSeek-V3-family block: latent attention, a leading dense layer
and a stack of sparse-expert layers, a multi-token-prediction module):
``jax.numpy``, float32, every matmul at ``highest`` precision, naive
causal attention in row blocks, the held experts as a masked sum over
ALL tokens, hand-written AdamW and router-bias update. It imports
nothing of the program under test and is given nothing the program
made: weights, windows and the learning-rate schedule are rebuilt here
from the seed and the configuration's recipe. It follows the published
equations (configs/joyai-llm-flash-L5-E8.json ``equations``), not the
program: no kernels, no sorting, no cache.

x (rows, T, d); RMSNorm(x) = x / sqrt(mean(x²) + eps) · g;
SwiGLU(x) = W_down(silu(W_gate x) ⊙ W_up x); no biases.

* block: h = x + MLA(RMSNorm₁ x); y = h + FFN(RMSNorm₂ h); FFN is
  SwiGLU in the leading dense layers, MoE after.
* MLA: c_q = RMSNorm(u W_qa); q = c_q W_qb = per head q_nope ‖ q_rope;
  [c_kv ‖ k_r] = u W_kva; c_kv ← RMSNorm(c_kv); [k_nope ‖ v] = c_kv
  W_kvb per head; rotary positions on q_rope and k_r (``rope_interleave``:
  pairs (2i, 2i+1) de-interleaved to half-split, then rotate-half; k_r is
  one head shared by all); o = softmax(causal(q kᵀ / sqrt(nope + rope)))
  v; out = concat(o) W_o.
* MoE: s = sigmoid(x W_g); chosen = top-k of (s + b); w = s[chosen];
  w ← scale · w / (Σw + 1e-20); out = Σ_{e chosen AND held} w_e
  SwiGLU_e(x) + SwiGLU_shared(x). Held = [first, first + held): what the
  absent experts would add is left out. After each step b ← b + γ ·
  sign(mean(c) − c), c the step's tokens per expert over all experts.
* head: logits = RMSNorm_f(y_L) W_headᵀ (untied).
* multi-token module (depth 1): h′ᵢ = W_eh [RMSNorm_e(Emb(t_{i+1})) ‖
  RMSNorm_h(y_L,ᵢ)]; h″ = sparse block(h′); logits′ = RMSNorm_s(h″)
  W_headᵀ; loss = CE(logits, t_{i+1}) over T positions + λ ·
  CE(logits′, t_{i+2}) over T − 1 positions.

``mode`` picks the arithmetic of the matmul operands: ``"f32"`` the
reference itself; ``"bf16"`` operands rounded to bfloat16 (tests);
``"fp8"`` operands rounded to float8_e4m3 with one scale per tensor,
gradients straight through: the control, the nearest precision below
the stated one. The router's product stays float32 in every mode, as
the configuration states. ``fault`` plants a fault for the limit
readings: ``"half_batch"`` drops the second half of every window's
positions from both losses (the micro-batch is one row).

Flat parameter names are the program's dumped names after the first
``/``: ``embed``, ``head``, ``lnf``, ``dense/<leaf>``, ``sparse/<leaf>``
(stacked over the sparse layers), ``mtp/<leaf>``, ``sparse_bias``,
``mtp_bias``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import load_module

# The configuration's ``recipe`` and ``feed`` are the dense LM's: one
# statement of the learning-rate schedule, the window draws and the
# operand rounding of the control serves both references.
_lm = load_module("reference", "lm")
lr_schedule, windows, _quantizer = (_lm.lr_schedule, _lm.windows,
                                    _lm._quantizer)

HIGHEST = jax.lax.Precision.HIGHEST
ATTN = ("q_a", "q_b", "kv_a", "kv_b", "o")
DENSE = ATTN + ("gate", "up", "down")
SPARSE = ATTN + ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
                 "s_down")
NORMS = ("ln1", "ln2", "q_norm", "kv_norm")
ROW_BLOCK = 512  # attention rows computed at a time


def dims_of(config: dict) -> dict:
    """Shapes from the published keys of a configuration file. The
    router keeps its published width (``router_experts``);
    ``n_routed_experts`` is what this chip holds, from ``first_expert``.
    ``layers`` is the sparse stack's depth (what compare.py cuts
    stacked leaves by)."""
    dense = int(config["first_k_dense_replace"])
    return dict(
        d=int(config["hidden_size"]), h=int(config["num_attention_heads"]),
        layers=int(config["num_hidden_layers"]) - dense, dense=dense,
        t=int(config["max_position_embeddings"]),
        v=int(config["vocab_size"]), q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]), vd=int(config["v_head_dim"]),
        ffn=int(config["intermediate_size"]),
        moe_ffn=int(config["moe_intermediate_size"]),
        experts=int(config["router_experts"]),
        k=int(config["num_experts_per_tok"]),
        held=int(config["n_routed_experts"]),
        first=int(config["first_expert"]),
        shared=int(config["n_shared_experts"]),
        scale=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        mtp=int(config["num_nextn_predict_layers"]),
        lam=float(config["mtp_loss_weight"]),
        gamma=float(config["bias_update_rate"]))


def _shapes(s, names):
    d, h, fm = s["d"], s["h"], s["moe_ffn"]
    table = {
        "q_a": (d, s["q_rank"]),
        "q_b": (s["q_rank"], h * (s["nope"] + s["rope"])),
        "kv_a": (d, s["kv_rank"] + s["rope"]),
        "kv_b": (s["kv_rank"], h * (s["nope"] + s["vd"])),
        "o": (h * s["vd"], d),
        "gate": (d, s["ffn"]), "up": (d, s["ffn"]), "down": (s["ffn"], d),
        "router": (d, s["experts"]),
        "e_gate": (s["held"], d, fm), "e_up": (s["held"], d, fm),
        "e_down": (s["held"], fm, d),
        "s_gate": (d, fm * s["shared"]), "s_up": (d, fm * s["shared"]),
        "s_down": (fm * s["shared"], d), "eh": (2 * d, d),
        "ln1": (d,), "ln2": (d,), "q_norm": (s["q_rank"],),
        "kv_norm": (s["kv_rank"],), "ln_e": (d,), "ln_h": (d,),
        "lnf": (d,)}
    return [(name, table[name]) for name in names]


def init_params(seed: int, dims: dict):
    """The configuration's ``init`` recipe: matrix i of the order embed,
    head, the dense group, the sparse group, the multi-token module
    (``eh`` first) is scale x normal(fold_in(key(seed), i)), scale
    1/sqrt(fan_in), 0.02 for the embedding; stacked groups are drawn
    whole; gains one; router biases zero."""
    s = dims
    groups = [("dense", (s["dense"],), DENSE, NORMS),
              ("sparse", (s["layers"],), SPARSE, NORMS)]
    if s["mtp"]:
        groups.append(("mtp", (), ("eh",) + SPARSE,
                       NORMS + ("ln_e", "ln_h", "lnf")))

    @jax.jit
    def make(seed):
        key = jax.random.key(seed)
        index = [0]

        def mat(shape, scale=None):
            scale = scale or 1.0 / math.sqrt(shape[-2])
            out = scale * jax.random.normal(
                jax.random.fold_in(key, index[0]), shape, jnp.float32)
            index[0] += 1
            return out

        p = {"embed": mat((s["v"], s["d"]), 0.02),
             "head": mat((s["v"], s["d"]), 1.0 / math.sqrt(s["d"])),
             "lnf": jnp.ones((s["d"],), jnp.float32)}
        for group, stack, mats, norms in groups:
            for name, shape in _shapes(s, mats):
                p[f"{group}/{name}"] = mat(stack + shape)
            for name, shape in _shapes(s, norms):
                p[f"{group}/{name}"] = jnp.ones(stack + shape, jnp.float32)
            if group != "dense":
                p[f"{group}_bias"] = jnp.zeros(stack + (s["experts"],),
                                               jnp.float32)
        return p

    return make(int(seed))


def is_state(name: str) -> bool:
    """Leaves that take no gradient and no optimizer step."""
    return name.endswith("_bias")


def is_routed(name: str) -> bool:
    """Leaves behind the top-k choice: the routed experts and their
    routers. A choice that flips between two sound runs moves these
    leaves and hardly any other, so their update is compared on its
    own (``compare.py``: ``routed_gap``)."""
    part = name.rsplit("/", 1)[-1]
    return part == "router" or part.startswith("e_")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope(x, theta):
    """(rows, T, heads, r): de-interleave the pairs, then rotate-half."""
    b, t, h, r = x.shape
    x = jnp.swapaxes(x.reshape(b, t, h, r // 2, 2), -1, -2
                     ).reshape(b, t, h, r)
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + half * sin


def attention(q, k, v, dot):
    """Naive causal attention of (rows, heads, T, ·), ROW_BLOCK query
    rows at a time so that T x T scores never exist whole."""
    t = q.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    n_blocks = -(-t // ROW_BLOCK)
    pad = n_blocks * ROW_BLOCK - t
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kt = k.transpose(0, 1, 3, 2)
    cols = jnp.arange(t)

    @jax.checkpoint
    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * ROW_BLOCK, ROW_BLOCK, 2)
        scores = dot(qb, kt) * scale
        mask = cols[None, :] <= (i * ROW_BLOCK
                                 + jnp.arange(ROW_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return dot(p, v)

    out = jax.lax.map(rows, jnp.arange(n_blocks))  # (blocks, b, h, R, dv)
    out = jnp.moveaxis(out, 0, 2).reshape(
        q.shape[0], q.shape[1], n_blocks * ROW_BLOCK, v.shape[-1])
    return out[:, :, :t]


def mla(u, p, s, dot):
    b, t, _ = u.shape
    h, nope, rp, vd = s["h"], s["nope"], s["rope"], s["vd"]
    c_q = rms_norm(dot(u, p["q_a"]), p["q_norm"], s["eps"])
    q = dot(c_q, p["q_b"]).reshape(b, t, h, nope + rp)
    ckv = dot(u, p["kv_a"])
    c_kv = rms_norm(ckv[..., :s["kv_rank"]], p["kv_norm"], s["eps"])
    k_r = rope(ckv[..., None, s["kv_rank"]:], s["theta"])
    kv = dot(c_kv, p["kv_b"]).reshape(b, t, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], s["theta"])],
                        -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, h, rp))], -1)
    o = attention(*(a.transpose(0, 2, 1, 3)
                    for a in (q, k, kv[..., nope:])), dot)
    return dot(o.transpose(0, 2, 1, 3).reshape(b, t, h * vd), p["o"])


def swiglu(x, gate, up, down, dot):
    return dot(jax.nn.silu(dot(x, gate)) * dot(x, up), down)


def route(x, router, bias, s):
    """(gates (.., E) float32, zero off the chosen; chosen (.., E))."""
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    _, idx = jax.lax.top_k(scores + bias, s["k"])
    chosen = jnp.zeros(scores.shape, bool)
    for j in range(s["k"]):
        chosen = chosen | (idx[..., j:j + 1] == jnp.arange(s["experts"]))
    w = jnp.where(chosen, scores, 0.0)
    return s["scale"] * w / (w.sum(-1, keepdims=True) + 1e-20), chosen


def moe(u, p, bias, s, dot, first=None, held=None):
    """(held experts' part + shared expert, tokens per expert over all
    experts). ``first`` / ``held`` default to the configuration's
    share; expert e of the share is row e - first of ``p["e_*"]``."""
    first = s["first"] if first is None else first
    held = s["held"] if held is None else held
    gates, chosen = route(u, p["router"], jax.lax.stop_gradient(bias), s)

    def add_expert(out, expert):
        i, gate, up, down = expert
        mine = jnp.take(gates, first + i, axis=-1)[..., None]
        return out + mine * swiglu(u, gate, up, down, dot), None

    # One traced expert, scanned over the held ones (a Python loop
    # would compile each expert's products anew: 8 x 3 x 3 of them).
    out, _ = jax.lax.scan(
        add_expert, swiglu(u, p["s_gate"], p["s_up"], p["s_down"], dot),
        (jnp.arange(held), p["e_gate"][:held], p["e_up"][:held],
         p["e_down"][:held]))
    return out, chosen.sum(tuple(range(chosen.ndim - 1))
                           ).astype(jnp.float32)


def block(x, p, bias, s, dot):
    """One block; ``bias`` None = a dense layer. Returns (y, counts)."""
    x = x + mla(rms_norm(x, p["ln1"], s["eps"]), p, s, dot)
    u = rms_norm(x, p["ln2"], s["eps"])
    if bias is None:
        return x + swiglu(u, p["gate"], p["up"], p["down"], dot), None
    y, counts = moe(u, p, bias, s, dot)
    return x + y, counts


def _group(params, group):
    prefix = group + "/"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _dot(mode):
    """The matmul of every product but the router's: operands rounded
    as ``mode`` says, ``highest`` precision."""
    q = _quantizer(mode)
    return lambda a, b: jnp.matmul(q(a), q(b), precision=HIGHEST)


def hidden(params, ids, dims, mode="f32"):
    """(y_L before the final norm, counts (sparse layers, E))."""
    dot = _dot(mode)
    x = params["embed"][ids]
    dense = jax.checkpoint(lambda x, p: block(x, p, None, dims, dot)[0])
    sparse = jax.checkpoint(lambda x, p, b: block(x, p, b, dims, dot))
    x, _ = jax.lax.scan(lambda x, p: (dense(x, p), None), x,
                        _group(params, "dense"))
    return jax.lax.scan(lambda x, pb: sparse(x, *pb), x,
                        (_group(params, "sparse"), params["sparse_bias"]))


def forward(params, ids, dims, mode="f32"):
    """Main-head logits (rows, T, vocab) of token ids (rows, T)."""
    y, _ = hidden(params, ids, dims, mode)
    return _dot(mode)(rms_norm(y, params["lnf"], dims["eps"]),
                      params["head"].T)


def _cross_entropy(logits, targets, keep):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll[:, :keep].mean()


def loss_and_counts(params, win, dims, mode="f32", fault=""):
    """(total loss, {bias name: tokens per expert}) of windows
    (rows, T+1)."""
    s = dims
    dot = _dot(mode)
    t = win.shape[1] - 1
    keep = t // 2 if fault == "half_batch" else t
    y, counts = hidden(params, win[:, :-1], s, mode)
    head_t = params["head"].T
    loss = _cross_entropy(
        dot(rms_norm(y, params["lnf"], s["eps"]), head_t), win[:, 1:], keep)
    out = {"sparse_bias": counts}
    if s["mtp"]:
        m = _group(params, "mtp")
        joined = jnp.concatenate(
            [rms_norm(params["embed"][win[:, 1:-1]], m["ln_e"], s["eps"]),
             rms_norm(y[:, :-1], m["ln_h"], s["eps"])], -1)
        y2, c2 = jax.checkpoint(
            lambda x, p, b: block(x, p, b, s, dot))(
                dot(joined, m["eh"]), m, params["mtp_bias"])
        keep2 = (t - 1) // 2 if fault == "half_batch" else t - 1
        loss = loss + s["lam"] * _cross_entropy(
            dot(rms_norm(y2, m["lnf"], s["eps"]), head_t), win[:, 2:],
            keep2)
        out["mtp_bias"] = c2
    return loss, out


@functools.lru_cache(maxsize=8)
def _step_fn(dims_items, recipe_items, mode: str, fault: str):
    dims, recipe = dict(dims_items), dict(recipe_items)
    b1, b2 = recipe["b1"], recipe["b2"]
    eps, wd = recipe["eps"], recipe["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, win, lr, count):
        weights = {k: v for k, v in params.items() if not is_state(k)}
        state = {k: v for k, v in params.items() if is_state(k)}
        (loss, counts), grads = jax.value_and_grad(
            lambda w: loss_and_counts({**w, **state}, win, dims, mode,
                                      fault), has_aux=True)(weights)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu,
                          grads)
        c1 = 1 - b1 ** count
        c2 = 1 - b2 ** count
        weights = jax.tree.map(
            lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                      + wd * p), weights, mu, nu)
        state = {k: v + dims["gamma"] * jnp.sign(
            counts[k].mean(-1, keepdims=True) - counts[k])
            for k, v in state.items()}
        return {**weights, **state}, mu, nu, loss

    return step


def _to_host(params: dict, dtype) -> dict:
    """Leaf by leaf to the host, each device leaf freed as it goes."""
    out = {}
    for name in sorted(params):
        leaf = params.pop(name)
        out[name] = np.asarray(leaf, dtype)
        leaf.delete()
    return out


def train(ids: np.ndarray, seed: int, dims: dict, recipe: dict, *,
          steps: int, batch: int, per_dispatch: int, learning_rate: float,
          mode: str = "f32", fault: str = "", host_dtype=np.float64):
    """One trial of ``steps`` optimizer steps from the seed. Returns
    ``(initial params, final params, per-step losses)`` as host numpy,
    the parameters in ``host_dtype``. The benchmark asks for float32,
    what the values are: ``compare.py`` widens one leaf at a time, and
    at 491 M parameters a float64 set is 4 GB. The default stays
    float64 only because a test outside the benchmark pins it. The
    initial state is drawn again from the seed once the trial is over,
    so that no host copy of it is held through the step's compile and
    the training."""
    params = init_params(seed, dims)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()
          if not is_state(k)}
    nu = jax.tree.map(jnp.zeros_like, mu)
    wins = windows(ids, seed, steps, batch, dims["t"], per_dispatch)
    lrs = lr_schedule(recipe, learning_rate, steps)
    step = _step_fn(tuple(sorted(dims.items())),
                    tuple(sorted(recipe.items())), mode, fault)
    losses = []
    for i in range(steps):
        params, mu, nu, loss = step(
            params, mu, nu, jnp.asarray(wins[i], jnp.int32),
            jnp.float32(lrs[i]), jnp.float32(i + 1))
        losses.append(loss)
    losses = np.asarray(jnp.stack(losses), np.float64)
    del mu, nu
    final = _to_host(params, host_dtype)
    first = _to_host(init_params(seed, dims), host_dtype)
    return first, final, losses
