"""From a profiler trace to the benchmark's per-layer numbers.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes into a
plain event list: each chip's operations (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the benchmark's own host spans (trace
annotations whose names start with ``bench.``).  On a TPU an operation's
name is its HLO instruction (``%name = f32[M,N]{...} opcode(operands...)``),
which carries the instruction's name, its opcode and its shapes.

``reduce`` then takes, within the traced window:

* busy seconds per chip: the union of the intervals in which an
  operation ran, averaged over the chips used (loop and call containers,
  which span their bodies' operations, are left out); idle is the rest;
* the calls of each kernel family: custom calls (or custom fusions, as
  XLA makes of a kernel whose output it writes into a stacked buffer)
  whose instruction name holds one of the family's patterns, each with
  its kind, its (M, K, N) read from its shapes and its duration, from
  which ``bench/lib/flops.py`` counts its operations and bytes;
* the device operations that took most time, by instruction name
  without its numeric suffix;
* the longest idle gaps, each named by the innermost benchmark span the
  host was in at the gap's midpoint ("outside spans" where none).

Events are ``(name, start_ns, dur_ns)``, all on the host's clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

SPAN_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9_.-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")
_SUFFIX = re.compile(r"(\.\d+)+$")


def load_xplane(path: str) -> dict:
    """{"devices": {plane: [(name, start, dur)]}, "spans": [...]}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                evs.extend((e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    if not any(devices.values()):
        raise RuntimeError("no device operations in the trace; planes: "
                           + ", ".join(p.name for p in pd.planes))
    return {"devices": devices, "spans": spans}


def parse_op(text: str):
    """(instruction name, opcode, output shapes, operand shapes) of an HLO
    instruction's text; opcode None where the text is not one."""
    if " = " not in text:
        return text.lstrip("%"), None, [], []
    name, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    if m is None:
        return name.lstrip("%"), None, [], []
    dims = lambda s: [tuple(int(d) for d in g.split(",") if d)  # noqa: E731
                      for g in _SHAPE.findall(s)]
    return (name.lstrip("%"), m.group(1), dims(rest[:m.start()]),
            dims(rest[m.end():]))


def call_mkn(kind: str, outs, args):
    """(M, K, N) of an MF-MAC kernel call from its shapes: ``fwd`` writes
    (M, N) from A (M, K); ``da`` writes dA (M, K) from G (M, N); ``dw``
    writes dW (K, N), perhaps into a stacked (L, K, N), from Aq (M, K)."""
    mats = [a for a in args if len(a) == 2]
    r, c = outs[0][-2:]
    if kind == "fwd":
        return r, next(a[1] for a in mats if a[0] == r), c
    if kind == "da":
        return r, c, next(a[1] for a in mats if a[0] == r)
    if kind == "dw":
        return next(a[0] for a in mats if a[1] == r), r, c
    raise ValueError(kind)


def union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float):
    """Sorted disjoint union of [start, end) intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace: dict, window: Tuple[float, float],
           families: Dict[str, Dict[str, str]], top: int = 10) -> dict:
    """``families``: {family: {instruction-name pattern: kind}}."""
    lo, hi = window
    if hi <= lo:
        raise ValueError("empty trace window")
    busy_ns = []
    kernels: Dict[str, list] = defaultdict(list)
    kernel_ns: Dict[str, float] = defaultdict(float)
    op_ns: Dict[str, float] = defaultdict(float)
    gaps = []
    for evs in trace["devices"].values():
        leaves = []
        for text, s, d in evs:
            if s >= hi or s + d <= lo:
                continue
            name, opcode, outs, args = parse_op(text)
            if opcode in CONTAINERS:
                continue
            leaves.append((s, s + d))
            d = min(s + d, hi) - max(s, lo)
            op_ns[_SUFFIX.sub("", name)] += d
            if opcode != "custom-call" and not (
                    opcode == "fusion" and "kind=kCustom" in text):
                continue
            for fam, patterns in families.items():
                kind = next((k for p, k in patterns.items() if p in name),
                            None)
                if kind is not None:
                    kernels[fam].append((kind, *call_mkn(kind, outs, args),
                                         d * 1e-9))
                    kernel_ns[fam] += d
                    break
        cover = union(leaves, lo, hi)
        busy_ns.append(sum(e - s for s, e in cover))
        edges = [lo] + [x for iv in cover for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    nchips = max(len(trace["devices"]), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns) / nchips * 1e-9,
        "kernel_s": {k: v / nchips * 1e-9 for k, v in kernel_ns.items()},
        "kernels": dict(kernels),
        "device_ops": [[n, v / nchips * 1e-9] for n, v in ops],
        "idle_gaps": [[span_at(trace["spans"], (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }


def span_at(spans, t: float) -> str:
    """The innermost (shortest) benchmark span that contains time t."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside spans"


def window_of(trace: dict, span_name: str) -> Tuple[float, float]:
    """[first start, last end] of the host spans called ``span_name``."""
    hits = [(s, s + d) for n, s, d in trace["spans"] if n == span_name]
    if not hits:
        raise ValueError(f"no host span {span_name!r} in the trace")
    return min(s for s, _ in hits), max(e for _, e in hits)
