"""From a profiler trace (``.xplane.pb``) to the few numbers the
per-layer metrics read. Nothing here knows a model, a kernel or a cell:
readers under ``metrics/`` look their events up by name.

A TPU's plane is ``/device:TPU:<n>``. Its ``XLA Modules`` line has one
event per execution of a compiled program (``jit_<name>(<id>)``), its
``XLA Ops`` line one event per operation that ran on the device.

``reduce_file`` returns::

    {"busy_s":   union of the op intervals, averaged over the chips used,
     "span_s":   first op start to last op end on the device's clock,
     "programs": {name: [device seconds of each execution]},
     "ops":      {text: {"n": events, "seconds": device time,
                         "short": name, opcode and result shape}},
                 keyed by the op's whole text, which on this runtime is
                 the HLO instruction, ``%fusion.3 = bf16[..] fusion(..)``
     "gaps":     the longest idle gaps of chip 0, [start_ns, end_ns],
     "first_ns", "last_ns": the device span on the trace's clock}

``window_s`` (the host's clock around the traced slice) is the caller's.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
N_GAPS = 32


def union(intervals):
    """Merged, sorted [start, end] of (start, end) pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def program_name(event_name: str) -> str:
    """``jit_train_chunk(1234)`` -> ``jit_train_chunk``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def short_name(event_name: str) -> str:
    """``%fusion.3 = bf16[4,8]{..} fusion(..)`` -> ``fusion.3 fusion
    bf16[4,8]``: the instruction's own name, its opcode and the shape
    of its (first) result, without layouts and operands."""
    head, _, rest = event_name.partition(" = ")
    if not rest:
        return event_name[:120]
    flat = re.sub(r"\{[^{}]*\}", "", rest)  # layouts hold parentheses
    opcode = re.search(r"(?:^|[\s)])([a-z][\w\-]*)\(", flat)
    shape = re.match(r"\(?([a-z]+\d+\[[\d,]*\])", flat)
    parts = [head.lstrip("%"), opcode.group(1) if opcode else "",
             shape.group(1) if shape else ""]
    return " ".join(part for part in parts if part)[:120]


def reduce_planes(planes, chips: int) -> dict:
    """``planes``: objects with ``name`` and ``lines`` (each with
    ``name`` and ``events`` of ``name``, ``start_ns``, ``duration_ns``),
    as ``jax.profiler.ProfileData`` gives them."""
    devices = sorted(
        (int(DEVICE_PLANE.match(p.name).group(1)), p) for p in planes
        if DEVICE_PLANE.match(p.name))[:chips]
    if not devices:
        raise SystemExit("the trace holds no /device:TPU:<n> plane")
    busy, programs, ops, gaps = [], {}, {}, []
    first = last = None
    for index, (_, plane) in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        intervals = []
        for event in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            start, dur = event.start_ns, event.duration_ns
            intervals.append((start, start + dur))
            # Keyed by the whole text: two programs' ``%fusion.3`` are
            # one entry only where they are the same instruction.
            entry = ops.get(event.name)
            if entry is None:
                entry = ops[event.name] = {"n": 0, "seconds": 0.0,
                                           "short": short_name(event.name)}
            entry["n"] += 1
            entry["seconds"] += dur / 1e9
        for event in (lines[MODULES_LINE].events
                      if MODULES_LINE in lines else ()):
            programs.setdefault(program_name(event.name), []).append(
                event.duration_ns / 1e9)
        merged = union(intervals)
        busy.append(sum(end - start for start, end in merged) / 1e9)
        if merged:
            first = merged[0][0] if first is None \
                else min(first, merged[0][0])
            last = merged[-1][1] if last is None \
                else max(last, merged[-1][1])
        if index == 0:
            idle = [[a[1], b[0]] for a, b in zip(merged, merged[1:])]
            gaps = sorted(idle, key=lambda g: g[0] - g[1])[:N_GAPS]
    if not any(busy):
        raise SystemExit("no operation ran on the device in the trace")
    return {"busy_s": sum(busy) / len(busy),
            "span_s": (last - first) / 1e9, "first_ns": first,
            "last_ns": last, "programs": programs, "ops": ops,
            "gaps": gaps}


def reduce_file(path: str, chips: int = 1) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, chips)


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    """The one ``.xplane.pb`` that ``jax.profiler.start_trace`` left
    under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise SystemExit(f"{len(files)} .xplane.pb files under "
                         f"{trace_dir}, expected 1")
    return reduce_file(files[0], chips)


SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
CONTAINERS = ("while", "conditional", "call")  # their time is their ops'


def custom_calls(reduced: dict, target: str) -> list:
    """The ops that are custom calls to ``target`` (a Pallas kernel is
    ``tpu_custom_call``), each with the shapes the instruction states:
    ``{"n", "seconds", "operands": [(dtype, dims)], "results": [...]}``.
    A kernel that carries no name of its own is told by these."""
    found = []
    for text, op in reduced["ops"].items():
        if f'custom_call_target="{target}"' not in text:
            continue
        flat = re.sub(r"\{[^{}]*\}", "", text.partition(" = ")[2])
        results, _, rest = flat.partition(" custom-call(")
        operands = rest.partition("), custom_call_target")[0]

        def shapes(part):
            return [(dtype, tuple(int(d) for d in dims.split(",") if d))
                    for dtype, dims in SHAPE.findall(part)]

        found.append({"n": op["n"], "seconds": op["seconds"],
                      "operands": shapes(operands),
                      "results": shapes(results)})
    return found


def total(entries) -> dict:
    entries = list(entries)
    return {"n": sum(e["n"] for e in entries),
            "seconds": sum(e["seconds"] for e in entries)}


def breakdown(reduced: dict, host_spans, n: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took
    most time (loops and calls left out: their time is their ops'), and
    the longest idle gaps by what the host was doing. ``host_spans`` are
    (label, start_ns, end_ns) on the trace's clock, recorded by the
    benchmark itself, the most telling first: a gap takes the label of
    the first span that covers a tenth of it or more."""
    by_name = {}
    for op in reduced["ops"].values():
        if op["short"].split(" ")[1:2] in ([c] for c in CONTAINERS):
            continue
        by_name[op["short"]] = by_name.get(op["short"], 0.0) + op["seconds"]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])
    # The slice opens and closes at trial completions, where the chip
    # may sit idle (the next trial compiling): the two edges are gaps too.
    edges = [[0, reduced["first_ns"]],
             [reduced["last_ns"], reduced["window_s"] * 1e9]] \
        if "window_s" in reduced else []
    by_label = {}
    for start, end in reduced["gaps"] + [e for e in edges if e[1] > e[0]]:
        label = "host, not attributed"
        for name, s0, s1 in host_spans:
            if min(end, s1) - max(start, s0) >= 0.1 * (end - start):
                label = name
                break
        by_label[label] = by_label.get(label, 0.0) + (end - start) / 1e9
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])
    return {"device_ops": [[name, seconds] for name, seconds in top[:n]],
            "idle_gaps": [[label, seconds] for label, seconds in gaps[:n]]}
