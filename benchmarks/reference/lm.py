"""Plain reference of the decoder-only LM train step: ``jax.numpy``,
float32, every matmul at ``highest`` precision, naive causal attention,
hand-written AdamW. It imports nothing of the program under test and is
given nothing the program made: weights, windows and the learning-rate
schedule are rebuilt here from the seed and the configuration's recipe.

The block is the repo's own (configs/*.json ``departures``): pre-LN,
sequential residual, sinusoidal positions added to sqrt(d)-scaled
embeddings, tanh-GELU, LayerNorm with a gain and no bias (eps 1e-6), no
biases anywhere, output head tied to the embedding.

``mode`` picks the arithmetic of the matmul operands:

* ``"f32"``  — the reference itself.
* ``"bf16"`` — operands rounded to bfloat16 (what the configuration
  states the program does); used by the selftest only.
* ``"fp8"``  — operands rounded to float8_e4m3 with one scale per
  tensor, gradients straight through: the control, the nearest
  precision below the stated one. It stands in the program's place and
  has to come out as not correct.

``fault`` plants a fault for the limit readings: ``"half_batch"`` drops
the second half of every batch and takes the mean over the rest.

Memory: gradients are accumulated row by row and every layer is
recomputed in the backward pass, so a step at 4 x 2048 x 50304 holds
one layer's activations and one row's logits beside params, Adam state
and the gradient sum.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MAT_NAMES = ("embed", "qkv", "proj", "w1", "w2")


def dims_of(config: dict) -> dict:
    """Shapes from the published keys of a configuration file."""
    d = int(config["hidden_size"])
    return dict(d=d, heads=int(config["num_attention_heads"]),
                ffn=int(config["intermediate_size"]),
                layers=int(config["num_hidden_layers"]),
                t=int(config["max_position_embeddings"]),
                v=int(config["vocab_size"]))


def init_params(seed: int, dims: dict):
    """Normal(0, 1/sqrt(fan_in)) matrices, 0.02 for the embedding, unit
    LayerNorm gains; one fold of the seed's key per matrix, in
    MAT_NAMES order (the configuration's ``init`` recipe)."""
    d, L, v, f = dims["d"], dims["layers"], dims["v"], dims["ffn"]
    shapes = {"embed": ((v, d), 0.02), "qkv": ((L, d, 3 * d), None),
              "proj": ((L, d, d), None), "w1": ((L, d, f), None),
              "w2": ((L, f, d), None)}

    @jax.jit
    def make(seed):
        key = jax.random.key(seed)
        mats = {}
        for i, name in enumerate(MAT_NAMES):
            shape, scale = shapes[name]
            if scale is None:
                scale = 1.0 / math.sqrt(shape[-2])
            mats[name] = scale * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        mats["ln1"] = jnp.ones((L, d), jnp.float32)
        mats["ln2"] = jnp.ones((L, d), jnp.float32)
        mats["lnf"] = jnp.ones((d,), jnp.float32)
        return mats

    return make(int(seed))


def sinusoidal(t: int, d: int) -> np.ndarray:
    pos = np.arange(t)[:, None]
    div = np.exp(np.arange(0, d, 2) * (-math.log(10000.0) / d))
    pe = np.zeros((t, d), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


def _quantizer(mode: str):
    if mode == "f32":
        return lambda x: x
    if mode == "bf16":
        def q(x):
            return x + jax.lax.stop_gradient(
                x.astype(jnp.bfloat16).astype(jnp.float32) - x)
        return q
    if mode == "fp8":
        def q(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return x + jax.lax.stop_gradient(r * scale - x)
        return q
    raise ValueError(f"unknown reference mode {mode!r}")


def _layer_norm(x, g):
    m = x.mean(-1, keepdims=True)
    var = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(var + 1e-6) * g


def forward(params, ids, dims: dict, mode: str = "f32"):
    """Logits (rows, T, vocab) of token ids (rows, T)."""
    q = _quantizer(mode)

    def dot(a, b):
        return jnp.matmul(q(a), q(b), precision=HIGHEST)

    d, h = dims["d"], dims["heads"]
    dh = d // h
    t = ids.shape[1]
    x = params["embed"][ids] * math.sqrt(d) \
        + jnp.asarray(sinusoidal(dims["t"], d))[None, :t]
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def block(x, lp):
        b = x.shape[0]
        hx = _layer_norm(x, lp["ln1"])
        qkv = dot(hx, lp["qkv"])
        qh, kh, vh = (a.reshape(b, t, h, dh).transpose(0, 2, 1, 3)
                      for a in jnp.split(qkv, 3, axis=-1))
        s = dot(qh, kh.transpose(0, 1, 3, 2)) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = dot(p, vh).transpose(0, 2, 1, 3).reshape(b, t, d)
        x = x + dot(o, lp["proj"])
        hx = jax.nn.gelu(dot(_layer_norm(x, lp["ln2"]), lp["w1"]))
        return x + dot(hx, lp["w2"])

    layers = {k: params[k] for k in ("qkv", "proj", "w1", "w2",
                                     "ln1", "ln2")}
    x, _ = jax.lax.scan(lambda x, lp: (block(x, lp), None), x, layers)
    return dot(_layer_norm(x, params["lnf"]), params["embed"].T)


def lr_schedule(recipe: dict, peak: float, steps: int) -> np.ndarray:
    """Learning rate of optimizer steps 0..steps-1: linear warm-up from
    ``start_factor * peak`` over ``max(1, steps // warmup_div)`` steps,
    then a cosine to ``end_factor * peak`` at step ``steps``."""
    warm = max(1, steps // int(recipe["warmup_div"]))
    lo, hi = recipe["start_factor"] * peak, peak
    end = recipe["end_factor"] * peak
    out = np.empty((steps,), np.float64)
    for i in range(steps):
        if i < warm:
            out[i] = lo + (hi - lo) * i / warm
        else:
            frac = min(i - warm, steps - warm) / max(1, steps - warm)
            cos = 0.5 * (1.0 + math.cos(math.pi * frac))
            out[i] = hi * ((1.0 - end / hi) * cos + end / hi)
    return out.astype(np.float32)


def windows(ids: np.ndarray, seed: int, steps: int, batch: int, t: int,
            per_dispatch: int) -> np.ndarray:
    """The (steps, batch, t+1) training windows in the order the
    configuration's ``feed`` recipe states: one numpy Generator seeded
    ``(seed + 1) * 100003``, one draw of ``k * batch`` uniform starts
    per dispatch of ``k`` steps."""
    rng = np.random.default_rng((int(seed) + 1) * 100003)
    hi = max(1, ids.shape[0] - (t + 1))
    out, done = [], 0
    while done < steps:
        k = min(per_dispatch, steps - done)
        starts = rng.integers(0, hi, size=k * batch)
        wins = np.stack([ids[s:s + t + 1] for s in starts])
        out.append(wins.reshape(k, batch, t + 1))
        done += k
    return np.concatenate(out)


@functools.lru_cache(maxsize=8)
def _step_fn(dims_items, recipe_items, mode: str, fault: str):
    dims, recipe = dict(dims_items), dict(recipe_items)
    b1, b2 = recipe["b1"], recipe["b2"]
    eps, wd = recipe["eps"], recipe["weight_decay"]

    def row_loss(params, row):
        logits = forward(params, row[None, :-1], dims, mode)[0]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).sum()

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, win, lr, count):
        if fault == "half_batch":
            win = win[:win.shape[0] // 2]
        n_tok = win.shape[0] * (win.shape[1] - 1)

        def add_row(carry, row):
            loss, grads = jax.value_and_grad(row_loss)(params, row)
            return (carry[0] + loss,
                    jax.tree.map(jnp.add, carry[1], grads)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(add_row, (0.0, zeros), win)
        loss = loss / n_tok
        grads = jax.tree.map(lambda g: g / n_tok, grads)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu,
                          grads)
        c1 = 1 - b1 ** count
        c2 = 1 - b2 ** count
        params = jax.tree.map(
            lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                      + wd * p), params, mu, nu)
        return params, mu, nu, loss

    return step


def train(ids: np.ndarray, seed: int, dims: dict, recipe: dict, *,
          steps: int, batch: int, per_dispatch: int, learning_rate: float,
          mode: str = "f32", fault: str = "", host_dtype=np.float32):
    """One trial of ``steps`` optimizer steps from the seed. Returns
    ``(initial params, final params, per-step losses)`` as host numpy,
    the parameters in ``host_dtype``."""
    params = init_params(seed, dims)
    first = jax.tree.map(lambda x: np.asarray(x, host_dtype), params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
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
    final = jax.tree.map(lambda x: np.asarray(x, host_dtype), params)
    del params, mu, nu
    return first, final, np.asarray(jnp.stack(losses), np.float64)
