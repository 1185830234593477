"""Reduce a profiler trace of the window to the numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Host
spans are the benchmark's own ``bench.*`` annotations; ``bench.window``
encloses the measured window.  Device activity is the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane (its ``XLA Modules`` line where a
plane has no op line).

* busy: the union of device op intervals inside the window, averaged
  over the chips that ran anything; idle share is ``1 - busy / window``;
* ``device_ops``: device time by kind of op, largest first: the HLO
  opcode, with the fusion kind for fusions (``fusion:kCustom``);
* ``idle_gaps``: the longest stretches inside the window in which no op
  ran, each named by the ``bench.*`` span the host was in at its middle,
  or ``sleep`` when it was in none (the loop waiting for an arrival or a
  coalescing deadline);
* ``spans``: count and total seconds of each ``bench.*`` span inside the
  window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")
TOP = 10
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def op_kind(name: str) -> str:
    """``fusion:kCustom``, ``copy``, ... from an HLO instruction's text
    (``%fusion.8 = f32[...] fusion(...), kind=kCustom, ...``)."""
    head, sep, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if sep else None
    if m is None:
        return head.lstrip("%")
    kind = _KIND.search(rest) if m.group(1) == "fusion" else None
    return m.group(1) + (":" + kind.group(1) if kind else "")


@dataclass
class Summary:
    window_s: float
    busy_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    spans: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    chips: int = 0

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or self.chips == 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def span_s(self, name: str) -> float:
        return self.spans.get(name, (0, 0.0))[1]

    def span_count(self, name: str) -> int:
        return self.spans.get(name, (0, 0.0))[0]

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted copy of half-open ``(start, end)`` intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[int, int]], lo: int,
         hi: int) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi)`` that merged ``busy`` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class _SpanIndex:
    """Which ``bench.*`` span (other than the window) holds a time."""

    def __init__(self, spans: Sequence[Tuple[int, int, str]]):
        self._spans = sorted(spans)
        self._starts = [s for s, _, _ in self._spans]

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self._starts, t) - 1
        while i >= 0:
            s, e, name = self._spans[i]
            if e > t:
                return name
            if t - s > 10**9:       # spans are short; stop looking back
                break
            i -= 1
        return "sleep"


def reduce(host_spans: Sequence[Tuple[int, int, str]],
           device_events: Dict[str, Sequence[Tuple[int, int, str]]]
           ) -> Summary:
    """``host_spans``: ``(start_ns, end_ns, name)`` of the ``bench.*``
    spans; ``device_events``: per device plane, ``(start_ns, end_ns,
    op name)`` of its ops."""
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = windows[0]
    inner = [(s, e, n) for s, e, n in host_spans
             if n != WINDOW and e > lo and s < hi]
    spans: Dict[str, Tuple[int, float]] = {}
    for s, e, n in inner:
        c, t = spans.get(n, (0, 0.0))
        spans[n] = (c + 1, t + (min(e, hi) - max(s, lo)) * 1e-9)
    index = _SpanIndex(inner)
    ops: Dict[str, float] = {}
    kinds: Dict[str, str] = {}      # op text -> kind, parsed once
    busy_total, chips = 0.0, 0
    idle: List[Tuple[str, float]] = []
    for plane in sorted(device_events):
        evs = [(s, e, n) for s, e, n in device_events[plane]
               if e > lo and s < hi]
        if not evs:
            continue
        chips += 1
        for s, e, n in evs:
            k = kinds.get(n)
            if k is None:
                k = kinds[n] = op_kind(n)
            ops[k] = ops.get(k, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
        busy = union(clip([(s, e) for s, e, _ in evs], lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        idle += [(index.at((s + e) // 2), (e - s) * 1e-9)
                 for s, e in gaps(busy, lo, hi)]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    idle.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total / chips if chips else 0.0,
                   device_ops=top_ops, idle_gaps=idle[:TOP], spans=spans,
                   chips=chips)


def read_xplane(path: str):
    """``(host_spans, device_events)`` from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, dev = [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is not None:
                dev[plane.name] = [(int(e.start_ns), int(e.end_ns), e.name)
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        host.append((int(e.start_ns), int(e.end_ns), name))
    return host, dev


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]


def reduce_file(path: str) -> Summary:
    return reduce(*read_xplane(path))


def reduce_dir(trace_dir: str) -> Summary:
    return reduce_file(find_xplane(trace_dir))
