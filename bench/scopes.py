"""Device time by planned layer and executor phase, and idle time by the
program's own host spans, from a profiler trace of the window.

The program names its work (``src/repro/launch/tracing.py``): every op
of a planned forward runs under its layer's scope (``L03.CNN8-5``) and,
inside it, under one phase (``weights``, ``patches``, ``macro``,
``place``, ``glue``); the JAX op path holding them is the ``tf_op`` stat
of the op's event metadata on the device plane.  The program's calls
are host spans under ``repro.`` (``repro.pop``, ``repro.plan_for``,
``repro.execute_plan``), beside the benchmark's ``bench.*`` spans.
``jax.profiler.ProfileData`` does not expose event metadata stats, so
this module reads the trace's protobuf wire format itself.

Inside ``bench.window``:

* ``device_by_layer`` / ``device_by_phase``: seconds of device op time
  by layer scope and by phase (an op's own time: what an enclosing op
  shares with ops inside it counts once).  An op the compiler made
  (layout copies, async copies, zero buffers, fused scatters) carries
  no op path; it takes the scope of the first op it reads that has one,
  found through other compiler-made ops, else of the first op that reads
  it (``attribute``: the HLO operands in each op's text on the device
  plane).  Ops left outside any layer scope, or in a layer but in no
  phase, count as ``unscoped``;
* ``idle_by_span``: idle stretches of the device, each named by the
  innermost ``bench.*`` or ``repro.*`` span open at its middle
  (``sleep`` when none is), as count and seconds per name;
* ``attributed``: the compiler-made device time given a scope by data
  flow, by ``xla:<kind>``; ``unscoped``: the rest by what it is:
  ``xla:<kind>`` for compiler-made ops with no scoped neighbour (loop
  counters), ``named`` for ops with a path outside any layer (the
  program's entry);
* ``spans``: count and seconds of each ``repro.*`` span;
* ``modules``: the ``XLA Modules`` runs, for checks of the clock.

From these, ``array_pass_share`` is the ``macro`` phase's share of device
op time (the array passes against everything the executors do around
them), and ``dispatch_ms_per_batch`` the mean ``repro.execute_plan``
span (the benchmark's metric of that name reads the ``bench.dispatch``
span around it).  ``bench/run.py`` does not read this module yet; run a
cell traced through it to print the tables on standard error::

    python3 bench/scopes.py --workload cnn8.backlog --seed 7 \\
        --seconds 20 [--keep trace.xplane.pb]

It runs ``bench/run.py --trace 1`` unchanged and, before the trace
directory is removed, prints ``[layers]``, ``[phases]``, ``[idle]`` and
``[program]`` and keeps the trace file where ``--keep`` says.  It exits
non-zero, with a warning, when the program dispatched batches
(``repro.execute_plan`` spans) but its ops carry no layer scope: an
executable loaded from a compile cache written by a program that did
not name its work (the cache key leaves op names out).
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
PROGRAM = "repro."
HOST_PREFIXES = ("bench.", PROGRAM)
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
PHASES = ("weights", "patches", "macro", "place", "glue")
UNSCOPED = "unscoped"
DISPATCH = "repro.execute_plan"
COMPILER = "xla:"
_LAYER = re.compile(r"L\d+\.")
_INSTR = re.compile(r"%([\w.\-]+)")
#: below this share of device time in a layer scope by the ops' own
#: paths, a traced program that dispatched batches ran unnamed code
STALE_BELOW = 0.5


# ---------------------------------------------------------------------------
# The XSpace protobuf, read from its wire format
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for varint and
    fixed-width fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


@dataclass(frozen=True)
class Meta:
    name: str          # on a device plane, the HLO instruction's text
    path: str = ""     # JAX op path; "" for an op the compiler made
    program: int = 0   # the XLA program the instruction belongs to


@dataclass
class Plane:
    name: str
    ops: Dict[int, Meta] = field(default_factory=dict)
    #: line name -> [(start_ns, end_ns, metadata id)]
    lines: Dict[str, List[Tuple[int, int, int]]] = field(
        default_factory=dict)


def _plane(buf) -> Plane:
    name, lines, metas, stat_names = "", [], [], {}
    for num, v in _fields(buf):
        if num == 2:
            name = _str(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            metas.append(v)
        elif num == 5:
            sid, sm = _map_entry(v)
            stat_names[sid] = next((_str(x) for n, x in _fields(sm)
                                    if n == 2), "")
    plane = Plane(name)
    ids = {n: sid for sid, n in stat_names.items()}
    tf_op, program_id = ids.get("tf_op"), ids.get("program_id")
    for entry in metas:
        mid, meta = _map_entry(entry)
        ev_name, path, program = "", "", 0
        for num, v in _fields(meta):
            if num == 2:
                ev_name = _str(v)
            elif num == 5:
                stat = dict(_fields(v))
                if stat.get(1) is None:
                    continue
                if stat[1] == tf_op:
                    if 5 in stat:
                        path = _str(stat[5])
                    elif 7 in stat:
                        path = stat_names.get(stat[7], "")
                elif stat[1] == program_id:
                    program = stat.get(3, stat.get(4, 0))
        plane.ops[mid] = Meta(ev_name, path, program)
    for ln in lines:
        lname, ts, events = "", 0, []
        for num, v in _fields(ln):
            if num == 2:
                lname = _str(v)
            elif num == 3:
                ts = v
            elif num == 4:
                events.append(v)
        out = []
        for ev in events:
            mid = off = dur = 0
            for num, v in _fields(ev):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
            start_ps = ts * 1000 + off
            out.append((start_ps // 1000, (start_ps + dur) // 1000, mid))
        plane.lines[lname] = plane.lines.get(lname, []) + out
    return plane


def read_planes(path: str) -> List[Plane]:
    """The device planes and the host planes of an ``.xplane.pb``."""
    buf = memoryview(Path(path).read_bytes())
    planes = []
    for num, v in _fields(buf):
        if num != 1:
            continue
        name = next((_str(x) for n, x in _fields(v) if n == 2), "")
        if name.startswith(DEVICE_PREFIX) or name.startswith("/host:"):
            planes.append(_plane(v))
    return planes


def hlo_operands(text: str) -> Tuple[str, List[str]]:
    """``(instruction name, operand names)`` of an HLO instruction's text
    (``%copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.1)``)."""
    head, sep, rest = text.partition(" = ")
    name = head.strip().split(" ")[-1].lstrip("%")
    if not sep:
        return name, []
    import trace_reduce
    s = " " + rest
    m = trace_reduce._OPCODE.search(s)
    if m is None:
        return name, []
    depth, j = 0, m.end() - 1
    for j in range(m.end() - 1, len(s)):
        depth += {"(": 1, ")": -1}.get(s[j], 0)
        if depth == 0:
            break
    return name, _INSTR.findall(s[m.end():j])


def attribute(ops: Dict[int, Meta]) -> Dict[int, str]:
    """The op path each device op's time counts under: its own, or, for
    an op the compiler made, that of the first op it reads which has one
    (found through other compiler-made ops), else that of the first op
    that reads it; ``""`` where neither exists.  Operands are matched by
    name within the op's program."""
    parsed = {mid: hlo_operands(m.name) for mid, m in ops.items()}
    names = {(ops[mid].program, name): mid
             for mid, (name, _) in parsed.items()}
    reads = {mid: [names[(ops[mid].program, o)] for o in operands
                   if (ops[mid].program, o) in names]
             for mid, (_, operands) in parsed.items()}
    read_by: Dict[int, List[int]] = {mid: [] for mid in ops}
    for mid, rs in reads.items():
        for r in rs:
            read_by[r].append(mid)
    path = {mid: m.path for mid, m in ops.items()}
    todo = sorted(mid for mid, p in path.items() if not p)
    changed = True
    while changed and todo:
        changed = False
        for links in (reads, read_by):
            while True:
                got = {}
                for mid in todo:
                    p = next((path[r] for r in links[mid] if path[r]), "")
                    if p:
                        got[mid] = p
                if not got:
                    break
                path.update(got)
                todo = [mid for mid in todo if mid not in got]
                changed = True
    return path


def read_trace(path: str):
    """``(host_spans, device_ops, modules)``: ``(start_ns, end_ns, name)``
    of the ``bench.*`` and ``repro.*`` host spans; per device plane,
    ``(start_ns, end_ns, op path, made)`` of its ops, where ``made`` is
    ``""`` for an op with its own path and ``xla:<kind>`` (``xla:copy``,
    ``xla:custom-call``, ...) for one the compiler made, whose path is
    the one :func:`attribute` gives it; per device plane, ``(start_ns,
    end_ns, module name)`` of its module runs."""
    import trace_reduce
    host, dev, mods = [], {}, {}
    blank = Meta("")
    for p in read_planes(path):
        if p.name.startswith(DEVICE_PREFIX):
            line = p.lines.get(OPS_LINE) or p.lines.get(MODULES_LINE)
            if line:
                paths = attribute(p.ops)
                dev[p.name] = [
                    (s, e, paths.get(m, ""),
                     "" if p.ops.get(m, blank).path else
                     COMPILER + trace_reduce.op_kind(p.ops.get(m, blank).name))
                    for s, e, m in line]
            mods[p.name] = [(s, e, p.ops.get(m, blank).name)
                            for s, e, m in p.lines.get(MODULES_LINE, ())]
        else:
            for evs in p.lines.values():
                for s, e, m in evs:
                    n = p.ops.get(m, blank).name
                    if n.startswith(HOST_PREFIXES):
                        host.append((s, e, n))
    return host, dev, mods


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

def op_scope(path: str) -> Tuple[str, str]:
    """``(layer, phase)`` of a JAX op path: the component that starts
    ``L<digits>.``, then the first phase name after it; ``unscoped``
    for what is missing.  An op merged from several names them all,
    ``;``-joined: the first one counts."""
    parts = path.split(";", 1)[0].split("/")
    for i, p in enumerate(parts):
        if _LAYER.match(p):
            phase = next((q for q in parts[i + 1:] if q in PHASES),
                         UNSCOPED)
            return p, phase
    return UNSCOPED, UNSCOPED


def self_times(events: Sequence[Tuple[int, int, str]]
               ) -> List[Tuple[int, int, str, int]]:
    """``(start, end, name, own ns)`` of events on one line: an event
    enclosing others keeps only the time none of them covers."""
    out: List[list] = []
    stack: List[list] = []
    for s, e, n in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        row = [s, e, n, e - s]
        if stack:
            parent = stack[-1]
            parent[3] -= min(e, parent[1]) - s
        stack.append(row)
        out.append(row)
    return [(s, e, n, max(own, 0)) for s, e, n, own in out]


@dataclass
class Scopes:
    window_s: float
    device_s: float                        # device op time in the window
    device_by_layer: Dict[str, float]
    device_by_phase: Dict[str, float]
    idle_by_span: List[Tuple[str, int, float]]   # name, gaps, seconds
    spans: Dict[str, Tuple[int, float]]          # repro.* count, seconds
    #: compiler-made device time given a layer scope by data flow, by kind
    attributed: Dict[str, float]
    #: unscoped device time: ``xla:<kind>`` for ops the compiler made,
    #: ``named`` for ops with an op path outside any layer scope
    unscoped: Dict[str, float]
    modules: Dict[str, List[Tuple[int, int, str]]]

    def share(self, phase: str) -> Optional[float]:
        if self.device_s <= 0:
            return None
        return self.device_by_phase.get(phase, 0.0) / self.device_s

    @property
    def scoped_share(self) -> Optional[float]:
        """Share of device op time that resolves to a layer scope."""
        if self.device_s <= 0:
            return None
        return 1.0 - self.device_by_layer.get(UNSCOPED, 0.0) / self.device_s

    @property
    def named_share(self) -> Optional[float]:
        """Share of device op time in a layer scope by the ops' own op
        paths, before compiler-made ops are attributed."""
        if self.device_s <= 0:
            return None
        made = sum(self.attributed.values())
        return self.scoped_share - made / self.device_s

    def stale_names(self) -> Optional[str]:
        """A warning when batches were dispatched but the device ran
        ops without layer scopes: the executable came from a compile
        cache written by a program that did not name its work."""
        if (self.spans.get(DISPATCH, (0, 0.0))[0] and self.device_s > 0
                and self.named_share < STALE_BELOW):
            return (f"{self.named_share:.3f} of device op time carries a "
                    f"layer scope although {DISPATCH} spans ran: the "
                    f"device ran executables without the program's names "
                    f"(loaded from a compile cache written before them?)")
        return None

    @property
    def array_pass_share(self) -> Optional[float]:
        return self.share("macro")

    @property
    def dispatch_ms_per_batch(self) -> Optional[float]:
        n, s = self.spans.get(DISPATCH, (0, 0.0))
        return s / n * 1e3 if n else None


def reduce(host_spans, device_ops, modules=None) -> Scopes:
    """``host_spans``: ``(start_ns, end_ns, name)`` of the ``bench.*``
    and ``repro.*`` spans; ``device_ops``: per device plane,
    ``(start_ns, end_ns, op path, made)`` of its ops (``made`` as
    :func:`read_trace` gives it)."""
    import trace_reduce as tr
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = windows[0]
    inner = [(s, e, n) for s, e, n in host_spans
             if n != WINDOW and e > lo and s < hi]
    spans: Dict[str, Tuple[int, float]] = {}
    for s, e, n in inner:
        if n.startswith(PROGRAM):
            c, t = spans.get(n, (0, 0.0))
            spans[n] = (c + 1, t + (min(e, hi) - max(s, lo)) * 1e-9)
    by_layer: Dict[str, float] = {}
    by_phase: Dict[str, float] = {}
    attributed: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    # spans on one thread nest: the latest to start of those open at a
    # time is the innermost, which is what the index finds
    index = tr._SpanIndex(inner)
    idle: Dict[str, Tuple[int, float]] = {}
    parsed: Dict[str, Tuple[str, str]] = {}
    total = 0.0
    for plane in sorted(device_ops):
        evs = [(max(s, lo), min(e, hi), (n, made))
               for s, e, n, made in device_ops[plane] if e > lo and s < hi]
        if not evs:
            continue
        for s, e, (n, made), own in self_times(evs):
            sc = parsed.get(n)
            if sc is None:
                sc = parsed[n] = op_scope(n)
            sec = own * 1e-9
            total += sec
            by_layer[sc[0]] = by_layer.get(sc[0], 0.0) + sec
            by_phase[sc[1]] = by_phase.get(sc[1], 0.0) + sec
            if sc[0] == UNSCOPED:
                k = made or "named"
                unscoped[k] = unscoped.get(k, 0.0) + sec
            elif made:
                attributed[made] = attributed.get(made, 0.0) + sec
        busy = tr.union([(s, e) for s, e, _ in evs])
        for s, e in tr.gaps(busy, lo, hi):
            name = index.at((s + e) // 2)
            c, t = idle.get(name, (0, 0.0))
            idle[name] = (c + 1, t + (e - s) * 1e-9)
    mods = {p: [(s, e, n) for s, e, n in evs if e > lo and s < hi]
            for p, evs in (modules or {}).items()}
    return Scopes(
        window_s=(hi - lo) * 1e-9, device_s=total,
        device_by_layer=dict(sorted(by_layer.items())),
        device_by_phase=dict(sorted(by_phase.items(),
                                    key=lambda kv: -kv[1])),
        idle_by_span=sorted(((n, c, t) for n, (c, t) in idle.items()),
                            key=lambda r: -r[2]),
        spans=spans, modules=mods,
        attributed=dict(sorted(attributed.items(), key=lambda kv: -kv[1])),
        unscoped=dict(sorted(unscoped.items(), key=lambda kv: -kv[1])))


def reduce_file(path: str) -> Scopes:
    return reduce(*read_trace(path))


# ---------------------------------------------------------------------------
# A traced run that prints the tables
# ---------------------------------------------------------------------------

def tables(sc: Scopes) -> List[str]:
    """The ``[layers]``, ``[phases]``, ``[idle]`` and ``[program]``
    lines of one reduction."""
    d = sc.device_s or float("nan")
    out = [f"[layers] device op time {sc.device_s!r} s in a "
           f"{sc.window_s!r} s window; in a layer scope "
           f"{sc.scoped_share!r} (by own op path {sc.named_share!r})"]
    out += [f"[layers] {n} {t!r} s {t / d!r}"
            for n, t in sc.device_by_layer.items()]
    out += [f"[layers] attributed {n} {t!r} s {t / d!r}"
            for n, t in sc.attributed.items()]
    out += [f"[layers] unscoped {n} {t!r} s {t / d!r}"
            for n, t in sc.unscoped.items()]
    out += [f"[phases] {n} {t!r} s {t / d!r}"
            for n, t in sc.device_by_phase.items()]
    out += [f"[idle] {n} gaps={c} {t!r} s {t / sc.window_s!r}"
            for n, c, t in sc.idle_by_span]
    out += [f"[program] {n} count={c} {t!r} s"
            for n, (c, t) in sorted(sc.spans.items())]
    out.append(f"[program] array_pass_share={sc.array_pass_share!r} "
               f"dispatch_ms_per_batch={sc.dispatch_ms_per_batch!r}")
    return out


def main(argv=None) -> int:
    import argparse
    import shutil
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import run
    import trace_reduce
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keep", help="copy the trace's .xplane.pb here")
    args, rest = ap.parse_known_args(argv)
    reduce_dir = trace_reduce.reduce_dir
    stale = []

    def reduce_and_print(trace_dir: str):
        path = trace_reduce.find_xplane(trace_dir)
        if args.keep:
            shutil.copyfile(path, args.keep)
        sc = reduce_file(path)
        for line in tables(sc):
            run.log(line)
        if sc.stale_names():
            stale.append(sc.stale_names())
        return reduce_dir(trace_dir)

    check_outputs = run.harness.check_outputs

    def log_and_check(server, sched, win, limit):
        # what the untraced run would report, for the cost of tracing
        p95 = win.latency_ms(95)
        run.log(f"[window] traced images_per_s={win.images_per_s!r} "
                f"latency_p95_ms={None if p95 is None else float(p95)!r}")
        return check_outputs(server, sched, win, limit)

    trace_reduce.reduce_dir = reduce_and_print
    run.harness.check_outputs = log_and_check
    rc = run.main(rest + ["--trace", "1"])
    for msg in stale:
        run.log(f"scopes: WARNING: {msg}")
    return rc or (4 if stale else 0)


if __name__ == "__main__":
    sys.exit(main())
