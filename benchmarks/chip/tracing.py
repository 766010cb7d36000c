"""Profiler capture, and the reduction from a device trace to numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU each chip is a plane ``/device:TPU:<i>`` whose line
``XLA Modules`` holds one event per program execution and whose line
``XLA Ops`` holds one event per HLO instruction executed, named by the
instruction's text (``%fusion.12 = f32[20,1020,3000]{...} fusion(...)``).
A ``while`` instruction appears as one event spanning its whole loop,
with its body's instructions as separate events inside it.  Host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane's ``python``
lines, on the same clock.

The program carries no named scopes, so stages are told apart by
structure, never by a name the compiler chose:

- the ADMM scan is a ``while`` inside which some instruction runs exactly
  K times (K ADMM iterations per layer program; the blocked
  factorization's loops run once per 128-row block);
- a layer program is a module execution that holds such a scan;
- the propagate + Gram stage is every instruction of a layer program,
  outside its scan, that reads or writes an array with the per-worker
  sample count among its dimensions: propagation, the Gram, ``T Y^T``
  and the copies of those arrays; the factorization and the solves only
  touch n x n and Q x n arrays;
- a collective is an instruction named after a collective opcode.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from collections import Counter, defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import NamedTuple

#: Host span that marks the measured window in a traced run.
WINDOW_SPAN = "bench.window"
COLLECTIVE_OPCODES = (
    "collective-permute", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all",
)
_SHAPE = re.compile(r"\b[a-z]+\d*\[([0-9,]*)\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


class Event(NamedTuple):
    name: str
    start: float   # ns, the trace's common clock
    end: float


@contextmanager
def capture(directory: str):
    """Trace the device and the host's own spans into ``directory``; the
    Python call tracer stays off, so tracing adds little to the host."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def op_class(name: str) -> str:
    """``%custom-call.57 = ... custom_call_target="Cholesky"`` ->
    ``custom-call:Cholesky``; ``%fusion.642 = ...`` -> ``fusion``."""
    inst = name.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"\.\d+$", "", inst)
    if base == "custom-call":
        m = _TARGET.search(name)
        if m:
            return f"custom-call:{m.group(1)}"
    return base


def is_collective(name: str) -> bool:
    base = op_class(name)
    return any(base.startswith(op) for op in COLLECTIVE_OPCODES)


def touches_dim(name: str, dim: int) -> bool:
    """Whether an instruction's text has an array with ``dim`` among its
    dimensions."""
    want = str(dim)
    return any(want in m.group(1).split(",") for m in _SHAPE.finditer(name))


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals within
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    gaps, cursor = [], lo
    for s, e in sorted(intervals):
        if e <= cursor:
            continue
        if s > cursor:
            gaps.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    return [(s, e) for s, e in gaps if e > s]


def leaves(ops: list[Event]) -> list[Event]:
    """Ops that hold no other op: a ``while`` event is dropped, its body's
    ops kept, so that summed durations count each instant once."""
    ordered = sorted(ops, key=lambda o: (o.start, -o.end))
    out = []
    for i, o in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end:
            continue
        out.append(o)
    return out


@dataclass
class Chip:
    """One chip's events: all ops, leaf ops (sorted by start) and modules."""

    ops: list[Event]
    leaves: list[Event]
    modules: list[Event]
    _starts: list[float] = field(default_factory=list)

    def __post_init__(self):
        self._starts = [o.start for o in self.leaves]

    def leaves_in(self, lo: float, hi: float) -> list[Event]:
        i = bisect.bisect_left(self._starts, lo)
        j = bisect.bisect_left(self._starts, hi)
        return [o for o in self.leaves[i:j] if o.end <= hi]


@dataclass
class Trace:
    chips: list[Chip]
    host: list[Event]          # host spans of the python threads

    def spans(self, name: str) -> list[Event]:
        return sorted((e for e in self.host if e.name == name),
                      key=lambda e: e.start)

    @property
    def window(self) -> tuple[float, float]:
        spans = self.spans(WINDOW_SPAN)
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        return spans[0].start, spans[-1].end


def latest_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = latest_xplane(path)
    data = ProfileData.from_file(path)
    chips, host = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            ops = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines["XLA Ops"].events] if "XLA Ops" in lines else []
            mods = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in lines["XLA Modules"].events] if "XLA Modules" in lines else []
            chips[int(m.group(1))] = Chip(ops, leaves(ops), sorted(
                mods, key=lambda e: e.start))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                if ln.name.startswith("python"):
                    host.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in ln.events)
    return Trace([chips[i] for i in sorted(chips)], host)


@dataclass
class LayerProgram:
    module: Event
    scan: Event
    gram_s: float
    collective_s: float
    train: int | None       # index of the host train span it ran in


def layer_programs(chip: Chip, trains: list[Event], *, scan_repeats: int,
                   sample_dim: int) -> list[LayerProgram]:
    """Every layer-program execution on ``chip``, with its scan and the
    device time of its propagate + Gram stage and of its collectives."""
    whiles = sorted((o for o in chip.ops if op_class(o.name) == "while"),
                    key=lambda o: o.start)
    out = []
    for mod in chip.modules:
        scan = None
        for w in whiles:
            if w.start < mod.start or w.end > mod.end:
                continue
            counts = Counter(o.name for o in chip.leaves_in(w.start, w.end))
            if scan_repeats in counts.values():
                scan = w if scan is None or w.end - w.start > scan.end - scan.start else scan
        if scan is None:
            continue
        inside = chip.leaves_in(mod.start, mod.end)
        gram = sum(o.end - o.start for o in inside
                   if not (scan.start <= o.start < scan.end)
                   and touches_dim(o.name, sample_dim))
        coll = sum(o.end - o.start for o in inside if is_collective(o.name))
        train = next((i for i, t in enumerate(trains)
                      if t.start <= mod.start and mod.end <= t.end), None)
        out.append(LayerProgram(mod, scan, gram * 1e-9, coll * 1e-9, train))
    return out


def interlayer_idle_s(chip: Chip, programs: list[LayerProgram]) -> list[float]:
    """Device-idle seconds between consecutive layer programs of one train."""
    out = []
    for a, b in zip(programs, programs[1:]):
        if a.train is None or a.train != b.train:
            continue
        lo, hi = a.module.end, b.module.start
        busy = union_length(((o.start, o.end) for o in chip.leaves_in(lo, hi)), lo, hi)
        out.append(max(hi - lo, 0.0) * 1e-9 - busy * 1e-9)
    return out


def busy_s(chip: Chip, lo: float, hi: float) -> float:
    return union_length(((o.start, o.end) for o in chip.ops), lo, hi) * 1e-9


def module_busy_s(chip: Chip, prefix: str) -> list[float]:
    """Device-busy seconds of each execution of modules named ``prefix...``."""
    return [
        union_length(((o.start, o.end) for o in chip.leaves_in(m.start, m.end)),
                     m.start, m.end) * 1e-9
        for m in chip.modules if m.name.startswith(prefix)
    ]


def top_device_ops(trace: Trace, lo: float, hi: float, *, scans=(), n: int = 10):
    """The op classes that took most device time in ``[lo, hi]``, summed
    over chips and divided by their number; ops inside one of ``scans``
    (per chip, ``(start, end)`` of each ADMM scan) are marked ``scan/``."""
    total = defaultdict(float)
    for i, chip in enumerate(trace.chips):
        spans = scans[i] if scans else []
        for o in chip.leaves_in(lo, hi):
            inside = any(s <= o.start < e for s, e in spans)
            total[("scan/" if inside else "") + op_class(o.name)] += (o.end - o.start) * 1e-9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / len(trace.chips)] for name, secs in ranked]


#: Idle gaps shorter than this lie between two instructions of one
#: program; the host is not what they wait for.
SHORT_GAP_NS = 10_000.0


def top_idle_gaps(trace: Trace, lo: float, hi: float, *, n: int = 10):
    """The device-idle time of chip 0 in ``[lo, hi]`` by what the host was
    doing: each gap of 10 us or more is named after the innermost host
    span covering its middle, prefixed by the benchmark's own span there;
    shorter gaps are summed under one name."""
    chip = trace.chips[0]
    host = sorted(trace.host, key=lambda e: e.start)
    total = defaultdict(float)
    gaps = idle_gaps(((o.start, o.end) for o in chip.ops), lo, hi)
    active: list[tuple[float, int]] = []   # heap of (end, index into host)
    nxt = 0
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        if e - s < SHORT_GAP_NS:
            total["between instructions (gaps under 10 us)"] += (e - s) * 1e-9
            continue
        mid = (s + e) / 2
        while nxt < len(host) and host[nxt].start <= mid:
            heapq.heappush(active, (host[nxt].end, nxt))
            nxt += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        covering = [host[i] for _, i in active]
        own = [h for h in covering if h.name.startswith("bench.")
               and h.name != WINDOW_SPAN]
        other = [h for h in covering if not h.name.startswith("bench.")]
        parts = []
        if own:
            parts.append(min(own, key=lambda h: h.end - h.start).name)
        if other:
            parts.append(min(other, key=lambda h: h.end - h.start).name)
        total[" > ".join(parts) or "no host span"] += (e - s) * 1e-9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]
