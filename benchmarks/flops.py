"""Work counts from shapes alone: what the algorithm needs, never what a
particular kernel did. A later PR that replaces a kernel is read against
the same work. Recomputed operations (remat, the flash kernels'
regenerated softmax) are not counted.

``shapes`` everywhere is the dict ``shapes_of`` returns.

Self-check: ``python benchmarks/flops.py`` compares ``train_step_flops``
with the arithmetic of ``rafiki_tpu/models/lm.py:_flops_per_step`` at
every configuration under ``configs/`` and exits non-zero on a drift.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def shapes_of(config: dict) -> dict:
    """Sizes the counts need, from a configuration file's own keys."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return dict(d=d, heads=heads, head_dim=d // heads,
                ffn=int(config["intermediate_size"]),
                layers=int(config["num_hidden_layers"]),
                t=int(config["max_position_embeddings"]),
                v=int(config["vocab_size"]),
                batch=int(config["knobs"]["batch_size"]))


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmarks/peaks.json: no peak, no share")
    return table[device_kind]


def matmul_params(s: dict) -> int:
    """Parameters that sit in matmuls: qkv, proj, w1, w2 per layer and
    the (tied) output head. The embedding gather is not a matmul."""
    per_layer = 4 * s["d"] ** 2 + 2 * s["d"] * s["ffn"]
    return s["layers"] * per_layer + s["v"] * s["d"]


def attention_matmul_flops(s: dict, n_matmuls: int) -> float:
    """``n_matmuls`` (T x T x head_dim) matmuls per head and row, the
    causal half of each, for one layer."""
    return (n_matmuls * 2 * s["batch"] * s["heads"] * s["t"] ** 2
            * s["head_dim"]) / 2


def train_step_flops(s: dict) -> float:
    """Forward + backward of one optimizer step: 6 x matmul params x
    tokens, plus causal attention (2 matmuls forward, 4 backward)."""
    tokens = s["batch"] * s["t"]
    return (6 * matmul_params(s) * tokens
            + s["layers"] * attention_matmul_flops(s, 6))


def attention_io_bytes(s: dict, n_tensors: int, itemsize: int = 2) -> float:
    """``n_tensors`` (batch, heads, T, head_dim) tensors of one layer's
    attention read or written once, in the compute type (bf16)."""
    return (n_tensors * s["batch"] * s["heads"] * s["t"] * s["head_dim"]
            * itemsize)


def least_seconds(flops: float, n_bytes: float, peaks: dict):
    """(seconds, which bound) of the roofline: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    by_flops = flops / peaks["flops_per_s_bf16"]
    by_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "memory"))


def attention_fwd_least(s: dict, peaks: dict):
    """One layer's causal attention forward: q k^T and p v; q, k, v read
    and o written."""
    return least_seconds(attention_matmul_flops(s, 2),
                         attention_io_bytes(s, 4), peaks)


def attention_bwd_least(s: dict, peaks: dict):
    """One layer's backward: dv, dp, dq, dk; q, k, v, o, do read and dq,
    dk, dv written."""
    return least_seconds(attention_matmul_flops(s, 4),
                         attention_io_bytes(s, 8), peaks)


def _self_check() -> int:
    """Drift against the program's own arithmetic (models/lm.py), over
    the configurations of the dense class: those whose ``reference`` is
    ``lm`` (``flops_moe.py`` checks the sparse class itself)."""
    import sys

    sys.path.insert(0, os.path.dirname(HERE))
    from rafiki_tpu.models import JaxTransformerLM

    bad = 0
    roots = [os.path.join(HERE, "configs"),
             os.path.join(HERE, "selftest", "configs")]
    for root in roots:
        for name in sorted(os.listdir(root)):
            with open(os.path.join(root, name)) as f:
                config = json.load(f)
            if config["reference"] != "lm":
                continue
            s = shapes_of(config)
            model = JaxTransformerLM(
                d_model=s["d"], n_layers=s["layers"], seq_len=s["t"],
                vocab_size=s["v"])
            theirs = model._flops_per_step(s["batch"])
            mine = train_step_flops(s)
            ok = abs(mine - theirs) <= 1e-9 * theirs \
                and s["heads"] == max(1, s["d"] // 128) \
                and s["ffn"] == 4 * s["d"]
            print(f"{name}: flops.py {mine:.6e}  models/lm.py "
                  f"{theirs:.6e}  {'ok' if ok else 'DRIFT'}")
            bad += not ok
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(_self_check())
