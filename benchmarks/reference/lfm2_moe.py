"""Plain reference of one chip's share of an LFM2-8B-A1B train step (the
LFM2 family's hybrid block: gated short convolutions and grouped-query
attention in one layer pattern, a leading dense layer and sparse-expert
layers after it): ``jax.numpy``, float32, every matmul at ``highest``
precision, naive causal attention in row blocks with each key-value
head repeated for its group, the convolution as an explicit sum of
shifted products, the held experts as a masked sum over ALL tokens,
hand-written AdamW and router-bias update. It imports nothing of the
program under test and is given nothing the program made: weights,
windows and the learning-rate schedule are rebuilt here from the seed
and the configuration's recipe. It follows the published equations
(configs/lfm2-8b-a1b-L5-E8.json ``equations``), not the program: no
kernels, no sorting, no stacks scanned by kind (the layers are a plain
loop in the pattern's order).

x (rows, T, d); RMSNorm(x) = x / sqrt(mean(x²) + eps) · g;
SwiGLU(x) = W_down(silu(W_gate x) ⊙ W_up x); no biases.

* block: h = x + Op(RMSNorm₁ x); y = h + FFN(RMSNorm₂ h); FFN is SwiGLU
  in the leading dense layers, experts after.
* ``conv`` Op: [b ‖ c ‖ u] = z W_in; v_t = Σ_{j<L} w_j ⊙ (b ⊙ u)_{t-(L-1)+j}
  (zeros before the sequence); out = (c ⊙ v) W_out.
* ``full_attention`` Op: q, k, v = z W_q, z W_k, z W_v (h / hk / hk heads
  of d / h lanes); RMSNorm over each head's lanes of q and of k, then
  rotary on the whole head (half-split pairs (i, i + r/2), theta as
  published); o = softmax(causal(q kᵀ / sqrt(r))) v, query head i with
  key-value head i // (h / hk); out = concat(o) W_o.
* experts: s = sigmoid(z W_r); chosen = top-k of (s + b); w = s[chosen];
  w ← scale · w / (Σw + 1e-6); out = Σ_{e chosen AND held} w_e
  SwiGLU_e(z). Held = [first, first + held): what the absent experts
  would add is left out. After each step b ← b + γ · sign(mean(c) − c),
  c the step's tokens per expert over all experts.
* head: logits = RMSNorm_f(y_L) W_embedᵀ (tied).

``mode`` picks the arithmetic of the matmul operands: ``"f32"`` the
reference itself; ``"bf16"`` operands rounded to bfloat16 (tests);
``"fp8"`` operands rounded to float8_e4m3 with one scale per tensor,
gradients straight through: the control, the nearest precision below
the stated one. The router's product stays float32 in every mode, as
the configuration states; the convolution's element-wise products are
no matmul and stay float32 too. ``fault`` plants a fault for the limit
readings: ``"half_batch"`` drops the second half of every window's
positions from the loss (the micro-batch is one row).

Flat parameter names are the program's dumped names after the first
``/``: ``embed``, ``lnf``, ``<kind>/<leaf>`` stacked over the layers of
one kind (``conv_dense``, ``attn_sparse``, ``conv_sparse``, ...) in the
model's order, ``<kind>_bias``.
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
# operand rounding of the control serves every reference.
_lm = load_module("reference", "lm")
lr_schedule, windows, _quantizer = (_lm.lr_schedule, _lm.windows,
                                    _lm._quantizer)

HIGHEST = jax.lax.Precision.HIGHEST
OPS = {"conv": "conv", "full_attention": "attn"}
ROW_BLOCK = 512  # attention rows computed at a time
GATE_EPS = 1e-6


def kinds_of(dims: dict):
    """The kind of every layer in the model's order: its operator
    (``conv`` | ``attn``) and its feed-forward (``dense`` | ``sparse``)."""
    return [f"{OPS[op]}_{'dense' if i < dims['dense'] else 'sparse'}"
            for i, op in enumerate(dims["pattern"])]


def dims_of(config: dict) -> dict:
    """Shapes from the published keys of a configuration file. The
    router keeps its published width (``router_experts``);
    ``num_experts`` is what this chip holds, from ``first_expert``.
    ``layers`` is the depth of the deepest stack of one kind (what
    compare.py cuts stacked leaves by: a kind of another depth over one
    would be compared as one leaf)."""
    s = dict(
        d=int(config["hidden_size"]), h=int(config["num_attention_heads"]),
        hk=int(config["num_key_value_heads"]),
        pattern=tuple(config["layer_types"]),
        dense=int(config["num_dense_layers"]),
        taps=int(config["conv_L_cache"]),
        t=int(config["max_position_embeddings"]),
        v=int(config["vocab_size"]), ffn=int(config["intermediate_size"]),
        moe_ffn=int(config["moe_intermediate_size"]),
        experts=int(config["router_experts"]),
        k=int(config["num_experts_per_tok"]),
        held=int(config["num_experts"]), first=int(config["first_expert"]),
        scale=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=float(config["norm_eps"]),
        gamma=float(config["bias_update_rate"]))
    assert len(s["pattern"]) == int(config["num_hidden_layers"])
    kinds = kinds_of(s)
    s["layers"] = max(kinds.count(kind) for kind in kinds)
    return s


def _shapes(s, kind):
    """[(matrix, shape)], [(gain, shape)] of one layer of ``kind``."""
    d, hd = s["d"], s["d"] // s["h"]
    op, ffn = kind.split("_")
    if op == "conv":
        mats = [("in", (d, 3 * d)), ("filter", (s["taps"], d)),
                ("out", (d, d))]
        norms = [("ln1", (d,)), ("ln2", (d,))]
    else:
        mats = [("q", (d, s["h"] * hd)), ("k", (d, s["hk"] * hd)),
                ("v", (d, s["hk"] * hd)), ("o", (s["h"] * hd, d))]
        norms = [("ln1", (d,)), ("ln2", (d,)), ("q_norm", (hd,)),
                 ("k_norm", (hd,))]
    if ffn == "dense":
        mats += [("gate", (d, s["ffn"])), ("up", (d, s["ffn"])),
                 ("down", (s["ffn"], d))]
    else:
        fm = s["moe_ffn"]
        mats += [("router", (d, s["experts"])),
                 ("e_gate", (s["held"], d, fm)), ("e_up", (s["held"], d, fm)),
                 ("e_down", (s["held"], fm, d))]
    return mats, norms


def init_params(seed: int, dims: dict):
    """The configuration's ``init`` recipe: matrix i of the order embed,
    then the kinds in the order the pattern first meets them (each
    kind's matrices: the operator's, then the feed-forward's), is scale
    x normal(fold_in(key(seed), i)), scale 1/sqrt(fan_in) (the filter's
    fan-in is its taps), 0.02 for the embedding; a kind's stack is
    drawn whole; gains one; router biases zero."""
    s = dims
    kinds = kinds_of(s)

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
             "lnf": jnp.ones((s["d"],), jnp.float32)}
        for kind in dict.fromkeys(kinds):
            stack = (kinds.count(kind),)
            mats, norms = _shapes(s, kind)
            for name, shape in mats:
                p[f"{kind}/{name}"] = mat(stack + shape)
            for name, shape in norms:
                p[f"{kind}/{name}"] = jnp.ones(stack + shape, jnp.float32)
            if kind.endswith("_sparse"):
                p[f"{kind}_bias"] = jnp.zeros(stack + (s["experts"],),
                                              jnp.float32)
        return p

    return make(int(seed))


def is_state(name: str) -> bool:
    """Leaves that take no gradient and no optimizer step."""
    return name.endswith("_bias")


def is_routed(name: str) -> bool:
    """Leaves behind the top-k choice: the routed experts and their
    routers (``compare.py``: ``routed_gap``)."""
    part = name.rsplit("/", 1)[-1]
    return part == "router" or part.startswith("e_")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope(x, theta):
    """(rows, T, heads, r): rotate-half over half-split pairs."""
    t, r = x.shape[1], x.shape[-1]
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


def gqa(z, p, s, dot):
    b, t, _ = z.shape
    h, hk = s["h"], s["hk"]
    q = dot(z, p["q"]).reshape(b, t, h, -1)
    k = dot(z, p["k"]).reshape(b, t, hk, -1)
    v = dot(z, p["v"]).reshape(b, t, hk, -1)
    q = rope(rms_norm(q, p["q_norm"], s["eps"]), s["theta"])
    k = rope(rms_norm(k, p["k_norm"], s["eps"]), s["theta"])
    # Query head i reads key-value head i // (h / hk): each key-value
    # head written out once for every query head of its group.
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    o = attention(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)), dot)
    return dot(o.transpose(0, 2, 1, 3).reshape(b, t, -1), p["o"])


def short_conv(z, p, s, dot):
    b, c, u = jnp.split(dot(z, p["in"]), 3, axis=-1)
    x = b * u
    taps, t = s["taps"], x.shape[1]
    v = jnp.zeros_like(x)
    for j in range(taps):  # tap j weighs position t - (taps - 1) + j
        back = taps - 1 - j
        v = v + p["filter"][j] * jnp.pad(
            x, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return dot(c * v, p["out"])


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
    return s["scale"] * w / (w.sum(-1, keepdims=True) + GATE_EPS), chosen


def experts(u, p, bias, s, dot, first=None, held=None):
    """(held experts' part, tokens per expert over all experts).
    ``first`` / ``held`` default to the configuration's share; expert e
    of the share is row e - first of ``p["e_*"]``."""
    first = s["first"] if first is None else first
    held = s["held"] if held is None else held
    gates, chosen = route(u, p["router"], jax.lax.stop_gradient(bias), s)

    @jax.checkpoint
    def add_expert(out, expert):
        i, gate, up, down = expert
        mine = jnp.take(gates, first + i, axis=-1)[..., None]
        return out + mine * swiglu(u, gate, up, down, dot), None

    # One traced expert, scanned over the held ones.
    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.arange(held), p["e_gate"][:held], p["e_up"][:held],
         p["e_down"][:held]))
    return out, chosen.sum(tuple(range(chosen.ndim - 1))
                           ).astype(jnp.float32)


def block(x, p, bias, kind, s, dot):
    """One block of ``kind``; ``bias`` None in a dense block. Returns
    (y, tokens per expert or None)."""
    op, ffn = kind.split("_")
    z = rms_norm(x, p["ln1"], s["eps"])
    x = x + (short_conv(z, p, s, dot) if op == "conv"
             else gqa(z, p, s, dot))
    z = rms_norm(x, p["ln2"], s["eps"])
    if ffn == "dense":
        return x + swiglu(z, p["gate"], p["up"], p["down"], dot), None
    y, counts = experts(z, p, bias, s, dot)
    return x + y, counts


def _layer(params, kind, row):
    prefix = kind + "/"
    return {k[len(prefix):]: v[row] for k, v in params.items()
            if k.startswith(prefix)}


def _dot(mode):
    """The matmul of every product but the router's: operands rounded
    as ``mode`` says, ``highest`` precision."""
    q = _quantizer(mode)
    return lambda a, b: jnp.matmul(q(a), q(b), precision=HIGHEST)


def hidden(params, ids, dims, mode="f32"):
    """(y_L before the final norm, {bias name: counts (layers of the
    kind, E)}): the layers one after another, in the pattern's order."""
    dot = _dot(mode)
    x = params["embed"][ids]
    seen, counts = {}, {}
    for kind in kinds_of(dims):
        row = seen.get(kind, 0)
        seen[kind] = row + 1
        sparse = kind.endswith("_sparse")
        bias = params[f"{kind}_bias"][row] if sparse else None
        x, c = jax.checkpoint(
            lambda x, p, b, kind=kind: block(x, p, b, kind, dims, dot))(
                x, _layer(params, kind, row), bias)
        if sparse:
            counts.setdefault(f"{kind}_bias", []).append(c)
    return x, {name: jnp.stack(rows) for name, rows in counts.items()}


def forward(params, ids, dims, mode="f32"):
    """Logits (rows, T, vocab) of token ids (rows, T)."""
    y, _ = hidden(params, ids, dims, mode)
    return _dot(mode)(rms_norm(y, params["lnf"], dims["eps"]),
                      params["embed"].T)


def loss_and_counts(params, win, dims, mode="f32", fault=""):
    """(loss, {bias name: tokens per expert}) of windows (rows, T+1)."""
    t = win.shape[1] - 1
    keep = t // 2 if fault == "half_batch" else t
    y, counts = hidden(params, win[:, :-1], dims, mode)
    logits = _dot(mode)(rms_norm(y, params["lnf"], dims["eps"]),
                        params["embed"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, win[:, 1:, None], axis=-1)[..., 0]
    return nll[:, :keep].mean(), counts


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
          mode: str = "f32", fault: str = "", host_dtype=np.float32):
    """One trial of ``steps`` optimizer steps from the seed. Returns
    ``(initial params, final params, per-step losses)`` as host numpy,
    the parameters in ``host_dtype`` (float32: what the values are;
    ``compare.py`` widens one leaf at a time). The initial state is
    drawn again from the seed once the trial is over, so that no host
    copy of it is held through the step's compile and the training."""
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
