"""Seeded token stream: an order-1 Markov chain in which every token has
``branching`` equally likely successors, so a working LM's loss falls
toward log(branching). A copy of the generator that the program's own
smoke run uses (rafiki_tpu/datasets/synth.py), kept here so that the
benchmark's inputs cannot change under it. The same seed gives the same
two streams; files are the ``.npz`` layout the platform's
LANGUAGE_MODELING datasets have (``ids`` int32, ``vocab_size``).
"""

from __future__ import annotations

import os

import numpy as np


def streams(seed: int, *, vocab_size: int, n_train: int, n_val: int,
            branching: int = 4):
    """(train ids, val ids) as int32 arrays."""
    rng = np.random.default_rng(seed)
    successors = rng.integers(0, vocab_size, size=(vocab_size, branching),
                              dtype=np.int32)

    def walk(n, seed2):
        r = np.random.default_rng(seed2)
        cols = r.integers(0, branching, size=n, dtype=np.int32)
        ids = np.empty((n,), np.int32)
        cur = np.int32(r.integers(0, vocab_size))
        for i in range(n):
            ids[i] = cur
            cur = successors[cur, cols[i]]
        return ids

    return walk(n_train, seed + 1), walk(n_val, seed + 2)


def make(out_dir: str, seed: int, spec: dict, shapes: dict):
    """Write both streams under ``out_dir``; returns
    ``(train_path, val_path, train_ids)``. ``spec`` is the
    configuration's ``data`` block, ``shapes`` its published sizes."""
    t = int(shapes["max_position_embeddings"])
    vocab = int(shapes["vocab_size"])
    train, val = streams(seed, vocab_size=vocab,
                         n_train=int(spec["n_train"]),
                         n_val=int(spec["val_windows"]) * t + 1,
                         branching=int(spec.get("branching", 4)))
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for name, ids in (("train", train), ("val", val)):
        path = os.path.join(out_dir, f"tokens_{name}.npz")
        np.savez(path, ids=ids, vocab_size=np.int64(vocab))
        paths.append(path)
    return paths[0], paths[1], train
