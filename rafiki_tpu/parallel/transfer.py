"""Packed device→host transfer for pytrees.

``jax.device_get`` on a pytree transfers LEAF BY LEAF, and every
readback pays a fixed synchronisation cost. For a ~220-leaf supernet
that per-leaf cost dominated ``dump_parameters`` — and with it an ENAS
trial (r5 profile: most of the trial's wall time inside
``Array._value``; the per-leaf cost on today's directly attached chip
is not measured).

``device_get_tree`` packs instead: one jitted concat per dtype group
(compiled once per tree signature, cached), ONE readback per dtype,
then a host-side split. The same ~30 MB moves in 1-3 transfers instead
of hundreds.

``make_host_stager`` is the host→device counterpart for the generative
decode loop's per-step token upload: it probes whether the runtime can
route the hop through a genuinely pinned (page-locked) host staging
buffer (TPU runtimes expose it as the ``pinned_host`` memory kind;
a pageable source forces the runtime to bounce through its own pinned
pool first) and falls back silently to a plain ``device_put`` where
the memory space doesn't exist. The worker records which path is live
in its bus registration (``staging``) so a reader of a run can tell
what was measured.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_PACK_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_PACK_CACHE_MAX = 32


def device_get_tree(tree: Any) -> Any:
    """Device→host for a whole pytree in one transfer per dtype group.

    Returns a tree of numpy arrays with identical structure/shapes.
    Host-side (numpy) leaves pass through unchanged.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    dev_idx = [i for i, leaf in enumerate(leaves)
               if isinstance(leaf, jax.Array)]
    if not dev_idx:
        return jax.tree.map(np.asarray, tree)
    sig = tuple((tuple(leaves[i].shape), str(leaves[i].dtype))
                for i in dev_idx)
    # WHICH leaves are device-resident is part of the signature: two
    # trees with the same treedef and coinciding device-leaf
    # (shape, dtype) sequences but a different device/host mix must not
    # share a cached pack plan (the cached groups would pack the wrong
    # leaves, leaving None holes in the output tree).
    key = (treedef, tuple(dev_idx), sig)
    entry = _PACK_CACHE.get(key)
    if entry is None:
        groups: Dict[str, List[int]] = {}
        for i in dev_idx:
            groups.setdefault(str(leaves[i].dtype), []).append(i)

        def pack_fn(ls):
            return {dt: jnp.concatenate(
                        [ls[i].reshape(-1) for i in idxs])
                    for dt, idxs in groups.items()}

        entry = (jax.jit(pack_fn), groups)
        _PACK_CACHE[key] = entry
        _PACK_CACHE.move_to_end(key)
        while len(_PACK_CACHE) > _PACK_CACHE_MAX:
            _PACK_CACHE.popitem(last=False)
    pack_fn, groups = entry
    packed = pack_fn(leaves)
    out: List[Any] = [np.asarray(leaf) if i not in set(dev_idx)
                      else None for i, leaf in enumerate(leaves)]
    for dt, idxs in groups.items():
        flat = np.asarray(packed[dt])  # ONE readback per dtype
        offset = 0
        for i in idxs:
            shape: Tuple[int, ...] = tuple(leaves[i].shape)
            n = int(np.prod(shape)) if shape else 1
            out[i] = flat[offset:offset + n].reshape(shape)
            offset += n
    return jax.tree.unflatten(treedef, out)


def make_host_stager(sharding) -> Tuple[Any, str]:
    """Build the host→device staging callable for small per-step
    uploads (the decode loop's next-token ids).

    Returns ``(stage, mode)``: ``stage(np_array)`` places the array
    under ``sharding``; ``mode`` is ``"pinned"`` when the hop rides a
    page-locked host buffer (``pinned_host`` memory kind, probed once
    here with a real round-trip so a runtime that ADVERTISES the space
    but can't transfer through it still falls back) or ``"pageable"``
    for the plain ``device_put`` path. The probe is deliberately
    silent on failure — CPU meshes and older runtimes simply don't
    have the memory space, and that is not an error.
    """
    try:
        pinned = sharding.with_memory_kind("pinned_host")
        probe = jax.device_put(
            jax.device_put(np.zeros((4,), np.int32), pinned), sharding)
        jax.block_until_ready(probe)

        def stage(arr):
            return jax.device_put(jax.device_put(arr, pinned), sharding)

        return stage, "pinned"
    except Exception:
        def stage(arr):
            return jax.device_put(arr, sharding)

        return stage, "pageable"
