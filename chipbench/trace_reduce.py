"""Reduces a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The traced span is the host annotation ``chipbench_span`` that the
benchmark's tracer holds open from just after the profiler starts to
just before it stops; everything is clipped to it. On each device plane
(``/device:TPU:<n>``):

* busy time is the union of the intervals of the operations on the
  ``XLA Ops`` line; the idle share is 1 minus busy over the span;
* a program's device time is the sum of its executions on the
  ``XLA Modules`` line, found by the jitted function's name;
* a kernel's device time is the sum of the operations whose HLO text
  names it, within the executions of the program that calls it.

Idle gaps are attributed to what the host was doing in them: the host
event (the benchmark's own annotations, JAX's dispatch events) that
covers most of the gap. Busy and idle are averaged over the chips.
"""
from __future__ import annotations

import collections
import pathlib
import re
from typing import Dict, List, Tuple

SPAN = "chipbench_span"
DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 1000


def latest(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The parts of [lo, hi) that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(ops) -> List[Tuple[str, int]]:
    """(name, self time) of each op of ``ops`` (``(start, end, name, ...)``
    sorted by start): its duration less that of the ops it directly
    encloses."""
    out: List[List] = []
    stack: List[int] = []
    for s, e, name, *_ in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack and out[stack[-1]][2] >= e:
            out[stack[-1]][1] -= e - s
        out.append([name, e - s, e])
        stack.append(len(out) - 1)
    return [(name, ns) for name, ns, _ in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _program(module_name: str) -> str:
    """``jit__decode_chunk_fn(1234)`` -> ``_decode_chunk_fn``."""
    name = module_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def _short(op_name: str) -> str:
    """An XLA op event is named by its HLO text; keep the instruction's
    name (``%fusion.12``)."""
    return op_name.split(" = ")[0].strip()


def _in_programs(ops, modules):
    """Tag each op (sorted by start) with the program whose execution on
    the same chip encloses its start."""
    out, j = [], 0
    mods = sorted(modules)
    for s, e, text in sorted(ops):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        prog = (_program(mods[j][2]) if j < len(mods) and mods[j][0] <= s
                else "")
        out.append((s, e, f"{prog}:{_short(text)}", text, prog))
    return out


class Reduction:
    """What the metrics read from one trace (times in seconds)."""

    def __init__(self, span_ns, devices, host):
        self.lo, self.hi = span_ns
        self.window_s = (self.hi - self.lo) / 1e9
        self.devices = devices            # per chip: ops, modules, busy
        self.host = host                  # [(start, end, name)]
        n = max(len(devices), 1)
        self.busy_s = sum(sum(e - s for s, e in d["busy"])
                          for d in devices) / n / 1e9

    def program_time(self, fn_name: str) -> float:
        """Device seconds of the executions of the jitted function
        ``fn_name`` inside the span, summed over chips."""
        return sum(e - s for d in self.devices
                   for s, e, name in d["modules"] if fn_name in name) / 1e9

    def programs(self) -> Dict[str, float]:
        """Device seconds of each jitted program inside the span, summed
        over chips."""
        tot: Dict[str, float] = collections.Counter()
        for d in self.devices:
            for s, e, name in d["modules"]:
                tot[_program(name)] += (e - s) / 1e9
        return dict(tot)

    def kernel_time(self, pattern: str, program: str = "") -> float:
        """Device seconds of the operations whose HLO text matches
        ``pattern`` (in executions of ``program`` only, when given),
        inside the span, summed over chips."""
        rx = re.compile(pattern)
        return sum(e - s for d in self.devices
                   for s, e, _, text, prog in d["ops"]
                   if program in prog and rx.search(text)) / 1e9

    def top_ops(self, n: int) -> List[List]:
        """Device self time by operation (``program:instruction``), longest
        first, averaged over chips: an operation that encloses others (a
        loop, a call) keeps only the time none of them covers."""
        tot: Dict[str, int] = collections.Counter()
        for d in self.devices:
            for name, ns in self_times(d["ops"]):
                tot[name] += ns
        k = max(len(self.devices), 1)
        return [[name, ns / k / 1e9] for name, ns in tot.most_common(n)]

    def top_gaps(self, n: int) -> List[List]:
        """Idle time by what the host was doing, longest first: the host
        event covering most of each gap names it."""
        tot: Dict[str, int] = collections.Counter()
        host = sorted(self.host)
        for d in self.devices:
            i, active = 0, []
            for g0, g1 in gaps(d["busy"], self.lo, self.hi):
                while i < len(host) and host[i][0] < g1:
                    active.append(host[i])
                    i += 1
                active = [h for h in active if h[1] > g0]
                if g1 - g0 < MIN_GAP_NS:
                    continue
                best, cover = "no host event", 0
                for s, e, name in active:
                    ov = min(e, g1) - max(s, g0)
                    if ov > cover:
                        best, cover = name, ov
                tot[best] += g1 - g0
        k = max(len(self.devices), 1)
        return [[name, ns / k / 1e9] for name, ns in tot.most_common(n)]


def reduce(trace_dir, span=None) -> Reduction:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(latest(trace_dir)))
    planes = list(pd.planes)
    host, span_ns = [], None
    for p in planes:
        if DEVICE.match(p.name):
            continue
        for line in p.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name == SPAN:
                    span_ns = (s, e)
                else:
                    host.append((s, e, ev.name[:80]))
    if span_ns is None:
        raise ValueError(f"no {SPAN!r} annotation in the trace")
    lo, hi = span_ns
    host = [(max(s, lo), min(e, hi), n) for s, e, n in host
            if e > lo and s < hi]
    devices = []
    for p in planes:
        if not DEVICE.match(p.name):
            continue
        ops, modules = [], []
        for line in p.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                s, e = _clip(s, s + int(ev.duration_ns), lo, hi)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    ops.append((s, e, ev.name))
                else:
                    modules.append((s, e, ev.name))
        devices.append({"ops": _in_programs(ops, modules),
                        "modules": modules,
                        "busy": union([(s, e) for s, e, _ in ops])})
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    return Reduction(span_ns, devices, host)
