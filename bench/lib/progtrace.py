"""The program's own marks in a profiler trace, for the per-layer metrics
that read them.

Two kinds of mark, on the host's clock like everything in the trace:

* host spans: the ``serve.*`` trace annotations of ``PoolEngine.run``
  (``serve.step``, ``serve.admit``, ``serve.wait``, ...), each with the
  arguments the program gave it (``step``, ``uid``, ``requests``);
* name scopes: ``jax.named_scope`` leaves its name in the ``op_name`` of
  every HLO instruction traced under it.  On a TPU the profiler writes
  that name as the ``tf_op`` stat of each ``XLA Ops`` event's metadata
  (``jit(step)/while/body/paged_kv/gather:``).  ``ProfileData`` does not
  expose event metadata, so ``_op_names`` reads it from the file's
  protobuf wire format directly: an operation's metadata name is the HLO
  text that ``ProfileData`` gives as the event's name.

``load`` reads the newest ``.xplane.pb`` under ``bench_out/trace/`` (the
run's own: ``Run.start_window`` clears its cell's directory first) once
per process.  Every reader works inside the ``bench.window`` span, and
returns None where the trace holds none of the marks it reads, as in a
program that does not make them.  Events are ``(name, start_ns, dur_ns)``
as in ``tracereduce``.
"""
from __future__ import annotations

import functools
import glob
import math
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.lib.tracereduce import CONTAINERS, parse_op, union, window_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_ROOT = os.path.join(ROOT, "bench_out", "trace")
HOST_PREFIXES = ("bench.", "serve.")
WAIT = "serve.wait"
OUTSIDE = "outside serve spans"


def load(path: Optional[str] = None) -> Optional[dict]:
    """The run's trace (``path``, else the newest under ``TRACE_ROOT``),
    or None without one."""
    if path is None:
        paths = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        path = max(paths, key=os.path.getmtime)
    return _load(path)


@functools.lru_cache(maxsize=1)
def _load(path: str) -> Optional[dict]:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        names = _op_names(f.read())
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans, args = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
                        args.append(dict(e.stats))
    return from_events(devices, spans, args, names)


def from_events(devices: Dict[str, list], spans: list, args: list,
                op_names: Dict[str, str]) -> Optional[dict]:
    """The trace as the readers see it: per device its leaf operations
    ``(start, end, op_name)`` (op_name "" where the trace gives none) and
    the host spans with their arguments; None without ``bench.window``."""
    trace = {"spans": spans}
    try:
        lo, hi = window_of(trace, "bench.window")
    except ValueError:
        return None
    ops = {}
    for dev, evs in devices.items():
        leaves = []
        for text, s, d in evs:
            if s >= hi or s + d <= lo or parse_op(text)[1] in CONTAINERS:
                continue
            leaves.append((max(s, lo), min(s + d, hi), op_names.get(text,
                                                                    "")))
        ops[dev] = leaves
    return {"window": (lo, hi), "ops": ops, "spans": spans, "args": args}


# -- name scopes ------------------------------------------------------------


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``op_name`` lies under ``scope``; transformations wrap a
    scope's name (``transpose(jvp(als_potq))``)."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)",
                     op_name) is not None


def scope_share(prog: Optional[dict], scope: str) -> Optional[float]:
    """Device time of the leaf operations under ``scope`` over busy time,
    in %, averaged over the devices; None where no operation is under it."""
    if prog is None:
        return None
    lo, hi = prog["window"]
    shares = []
    for leaves in prog["ops"].values():
        busy = _length(union([(s, e) for s, e, _ in leaves], lo, hi))
        mine = _length(union([(s, e) for s, e, n in leaves
                              if in_scope(n, scope)], lo, hi))
        if busy > 0:
            shares.append((mine, busy))
    if not any(m for m, _ in shares):
        return None
    return 100.0 * sum(m / b for m, b in shares) / len(shares)


# -- host spans ---------------------------------------------------------------


def serve_spans(prog: Optional[dict]) -> List[Tuple[str, float, float,
                                                    dict]]:
    """The ``serve.*`` spans that start inside the window, with their
    arguments: ``(name, start, end, args)``."""
    if prog is None:
        return []
    lo, hi = prog["window"]
    return [(n, s, s + d, a) for (n, s, d), a in zip(prog["spans"],
                                                     prog["args"])
            if n.startswith("serve.") and lo <= s < hi]


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the time the spans
    ``(name, start, end, ...)`` cover, each named by the innermost span
    open over it (spans of one thread nest)."""
    pieces: list = []
    stack: list = []
    cur = -math.inf

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                pieces.append((cur, end, name))
                cur = end

    for name, s, e, *_ in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        close_until(s)
        if stack and s > cur:
            pieces.append((cur, s, stack[-1][0]))
        stack.append((name, e))
        cur = s
    close_until(math.inf)
    return pieces


def idle_by_span(prog: Optional[dict]) -> Dict[str, float]:
    """Seconds of the window in which a device runs no leaf operation,
    by the innermost ``serve.*`` span open on the host ("outside serve
    spans" where none), averaged over the devices."""
    if prog is None:
        return {}
    lo, hi = prog["window"]
    pieces = innermost(serve_spans(prog))
    out: Dict[str, float] = defaultdict(float)
    for leaves in prog["ops"].values():
        busy = union([(s, e) for s, e, _ in leaves], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        named = 0.0
        j = 0
        for s, e in idle:
            while j < len(pieces) and pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < e:
                ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
                out[pieces[k][2]] += ov
                named += ov
                k += 1
        out[OUTSIDE] += _length(idle) - named
    n = max(len(prog["ops"]), 1)
    return {k: v / n * 1e-9 for k, v in out.items()}


def idle_host_share(prog: Optional[dict]) -> Optional[float]:
    """% of the window in which the device idles while the host is inside
    a ``serve.*`` span other than ``serve.wait`` (innermost)."""
    if not serve_spans(prog):
        return None
    lo, hi = prog["window"]
    idle = idle_by_span(prog)
    host = sum(v for k, v in idle.items()
               if k not in (WAIT, OUTSIDE))
    return 100.0 * host / ((hi - lo) * 1e-9)


def step_host_ms(prog: Optional[dict]) -> List[float]:
    """Per ``serve.step`` span: its duration less its ``serve.wait``
    children, in ms."""
    spans = serve_spans(prog)
    waits = sorted((s, e) for n, s, e, _ in spans if n == WAIT)
    out = []
    for n, s, e, _ in spans:
        if n != "serve.step":
            continue
        inner = sum(min(e, we) - max(s, ws) for ws, we in waits
                    if ws < e and we > s)
        out.append((e - s - inner) * 1e-6)
    return out


def report(prog: Optional[dict]) -> dict:
    """What the spans show beyond the metrics, for ``PERF.md``: idle
    seconds by span, the share of the window that ``serve.step`` holds
    with no child span open, and the share of each ``bench.serve_run``
    that ``serve.*`` spans cover (the least)."""
    spans = serve_spans(prog)
    if not spans:
        return {}
    lo, hi = prog["window"]
    pieces = innermost(spans)
    bare = sum(e - s for s, e, n in pieces if n == "serve.step")
    covered = union([(s, e) for _, s, e, _ in spans], lo, hi)
    cover = []
    for n, s, d in prog["spans"]:
        if n == "bench.serve_run" and d > 0:
            cover.append(_length(union(covered, s, s + d)) / d)
    return {"idle_s_by_span": idle_by_span(prog),
            "step_uncovered_share": bare / (hi - lo),
            "serve_run_cover_min": min(cover) if cover else None}


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


# -- the file's event metadata, from the protobuf wire format ------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entries:
# key = 1, value = 2), stat_metadata = 5; XEventMetadata: name = 2,
# stats = 5; XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7 (the id of a stat metadata whose name is
# the string).  tsl/profiler/protobuf/xplane.proto.


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    pos = lo
    while pos < hi:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, pos = None, pos + n
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, val


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _op_names(buf: bytes) -> Dict[str, str]:
    """{operation text: its ``tf_op`` (op_name)} over the device planes."""
    out: Dict[str, str] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, ev_meta, stat_names = "", [], {}
        for f, val in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, val)
            elif f == 4:
                ev_meta.append(val)
            elif f == 5:
                entry = dict(_fields(buf, *val))
                if 2 in entry:
                    sm = dict(_fields(buf, *entry[2]))
                    stat_names[sm.get(1, 0)] = _text(buf, sm[2]) \
                        if 2 in sm else ""
        if not name.startswith("/device:TPU:"):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        if not tf_op:
            continue
        for entry in ev_meta:
            em = dict(_fields(buf, *entry)).get(2)
            if em is None:
                continue
            text, op = "", None
            for f, val in _fields(buf, *em):
                if f == 2:
                    text = _text(buf, val)
                elif f == 5:
                    st = dict(_fields(buf, *val))
                    if st.get(1) in tf_op:
                        op = (_text(buf, st[5]) if 5 in st
                              else stat_names.get(st.get(7), ""))
            if op is not None:
                out[text] = op
    return out
