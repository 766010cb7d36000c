"""The program's own scopes and span arguments in a traced run.

``tracing.load`` keeps each event's name and times.  The program's named
scopes and spans (their names are ``repro.profiling``'s) need two more
things from the same ``.xplane.pb``:

- the scope path of each device op.  XLA writes every instruction's
  ``op_name`` (``jit(f)/.../dssfn.admm/while/body/admm.solve/...``) into
  the ``tf_op`` stat of the op's event metadata on each device plane.
  ``jax.profiler.ProfileData`` does not expose event metadata, so it is
  read here with a short protobuf wire reader and keyed by the op's name,
  which is what ``tracing.Event`` carries;
- the arguments of host spans (``TraceAnnotation(name, **args)``), which
  ``ProfileData`` gives as each host event's ``stats``.

A traced run of ``run.py`` writes its profile under ``.bench_trace/``
and removes it only after the readers ran, so the newest ``.xplane.pb``
there is the current run's.  Against a program without these names
(``repro.profiling`` missing) the readers find nothing and return None.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import warnings
from dataclasses import dataclass, field
from pathlib import Path
from statistics import mean, median

from benchmarks.chip import tracing

ROOT = Path(__file__).resolve().parents[2]
#: Where ``run.py`` traces into (``run.TRACE_DIR``).
TRACE_ROOT = ROOT / ".bench_trace"

# XSpace / XPlane / XEventMetadata / XStat field numbers
# (tsl/profiler/protobuf/xplane.proto).
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_META_NAME, _META_STATS = 2, 5
_STAT_METADATA_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_META_ID, _STAT_META_NAME = 1, 2
_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")


def names():
    """The program's scope and span names, or None for a program that
    has none."""
    try:
        from repro import profiling
    except ImportError:
        return None
    return profiling


# ---------------------------------------------------------------------------
# Protobuf wire format: just what the fields above need.
# ---------------------------------------------------------------------------
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field, value)`` of each field in ``buf[lo:hi]``: an int for a
    varint, a ``(start, end)`` slice for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, entry):
    for num, value in _fields(buf, *entry):
        if num == _MAP_VALUE:
            yield value


def _plane_tf_ops(buf: bytes, plane) -> dict[str, frozenset[str]]:
    """Op name -> the ``tf_op`` scope paths of the event metadata of that
    name in one plane (one name can stand for instructions of several
    programs)."""
    stat_names, metas = {}, []
    for num, value in _fields(buf, *plane):
        if num == _PLANE_STAT_METADATA:
            for meta in _map_values(buf, value):
                got = dict(_fields(buf, *meta))
                if _STAT_META_NAME in got:
                    stat_names[got.get(_STAT_META_ID, 0)] = _text(buf, got[_STAT_META_NAME])
        elif num == _PLANE_EVENT_METADATA:
            metas.extend(_map_values(buf, value))
    tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
    if not tf_op:
        return {}
    out: dict[str, set[str]] = {}
    for meta in metas:
        name = path = None
        for num, value in _fields(buf, *meta):
            if num == _META_NAME:
                name = _text(buf, value)
            elif num == _META_STATS:
                stat = dict(_fields(buf, *value))
                if stat.get(_STAT_METADATA_ID) != tf_op[0]:
                    continue
                if _STAT_STR in stat:
                    path = _text(buf, stat[_STAT_STR])
                elif _STAT_REF in stat:
                    path = stat_names.get(stat[_STAT_REF])
        if name and path:
            out.setdefault(name, set()).add(path)
    return {k: frozenset(v) for k, v in out.items()}


def device_tf_ops(buf: bytes) -> list[dict[str, frozenset[str]]]:
    """Per device plane ``/device:TPU:<i>``, in ``i`` order (the order of
    ``tracing.Trace.chips``): op name -> its ``tf_op`` paths."""
    planes = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != _SPACE_PLANES:
            continue
        name = next((_text(buf, v) for n, v in _fields(buf, *plane) if n == _PLANE_NAME), "")
        m = _DEVICE_PLANE.fullmatch(name)
        if m:
            planes[int(m.group(1))] = _plane_tf_ops(buf, plane)
    return [planes[i] for i in sorted(planes)]


def in_scope(path: str, scope: str) -> bool:
    """Whether the ``tf_op`` path ``a/b/c:type`` passes through ``scope``.
    A transformation wraps the first scope inside it in its own name
    (``vmap(dssfn.admm)``), so a component matches with such wrappers."""
    wrapped = re.compile(r"(?:[\w.]+\()*" + re.escape(scope) + r"\)*")
    return any(wrapped.fullmatch(c) for c in path.rsplit(":", 1)[0].split("/"))


# ---------------------------------------------------------------------------
# One traced run
# ---------------------------------------------------------------------------
@dataclass
class Span:
    name: str
    start: float       # ns, the trace's common clock
    end: float
    args: dict = field(default_factory=dict)


@dataclass
class ProgramTrace:
    tf_ops: list[dict[str, frozenset[str]]]     # per chip
    host: dict[str, list[Span]]                 # span name -> spans by start

    def under(self, chip: int, op_name: str, scope: str) -> bool:
        """Whether every instruction named ``op_name`` on ``chip`` lies
        under ``scope``."""
        paths = self.tf_ops[chip].get(op_name)
        return bool(paths) and all(in_scope(p, scope) for p in paths)

    def spans(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> list[Span]:
        """Host spans ``name`` that lie within ``[lo, hi]``."""
        return [s for s in self.host.get(name, []) if lo <= s.start and s.end <= hi]


@functools.lru_cache(maxsize=1)
def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    tf_ops = device_tf_ops(Path(path).read_bytes())
    host: dict[str, list[Span]] = {}
    with warnings.catch_warnings():
        # jaxlib's stats type warns that it names no module when read.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for e in line.events:
                    host.setdefault(e.name, []).append(Span(
                        e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    for spans in host.values():
        spans.sort(key=lambda s: s.start)
    return ProgramTrace(tf_ops, host)


def current(root: Path | None = None) -> ProgramTrace | None:
    """The newest traced run's trace under ``root`` (default
    ``.bench_trace/``), or None where there is none."""
    root = TRACE_ROOT if root is None else root
    paths = glob.glob(os.path.join(str(root), "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    return load(max(paths, key=os.path.getmtime))


# ---------------------------------------------------------------------------
# What the per-layer readers compute
# ---------------------------------------------------------------------------
def overlap_ns(a, b) -> float:
    """Total overlap of two lists of ``(start, end)`` intervals, each
    sorted by start and without overlaps of its own."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def scope_ms_per_program(r, scope: str) -> float | None:
    """Device ms per layer program in ops under ``scope``, as a mean over
    the layer programs and chips (``r.counters["programs"]``); None where
    no op of any program lies under it."""
    pt = current()
    if pt is None or len(pt.tf_ops) != len(r.trace.chips):
        return None
    per_program = []
    for i, (chip, progs) in enumerate(zip(r.trace.chips, r.counters["programs"])):
        for p in progs:
            ops = chip.leaves_in(p.module.start, p.module.end)
            per_program.append(sum(o.end - o.start for o in ops
                                   if pt.under(i, o.name, scope)) * 1e-6)
    if not any(per_program):
        return None
    return mean(per_program)


def host_spans(r, name: str) -> list[tracing.Event]:
    """Host spans ``name`` of the traced window, by start."""
    lo, hi = r.window
    return [s for s in r.trace.spans(name) if lo <= s.start and s.end <= hi]


def sync_idle_ms_per_train(r, sync: str) -> float | None:
    """Device-idle ms per train under the host spans ``sync``, as a mean
    over the chips."""
    syncs = [(s.start, s.end) for s in host_spans(r, sync)]
    if not syncs or not r.trace.chips:
        return None
    lo, hi = r.window
    idle = [overlap_ns(tracing.idle_gaps(((o.start, o.end) for o in chip.ops), lo, hi),
                       syncs)
            for chip in r.trace.chips]
    return mean(idle) * 1e-6 / r.counters["trains"]


def self_ms(r, outer: str, inner: str) -> float | None:
    """Mean ms of each span ``outer`` outside the spans ``inner`` it holds."""
    outers = host_spans(r, outer)
    if not outers:
        return None
    inners = [(s.start, s.end) for s in host_spans(r, inner)]
    return mean((o.end - o.start - overlap_ns([(o.start, o.end)], inners)) * 1e-6
                for o in outers)


def mean_span_ms(r, name: str) -> float | None:
    spans = host_spans(r, name)
    return mean((s.end - s.start) * 1e-6 for s in spans) if spans else None


def queue_wait_ms(r, submit: str, batch: str) -> float | None:
    """Median over requests: the start of the batch span whose request id
    range holds the request, less the end of the request's submit span
    (0 where the request's own submit ran the batch).  Batches whose
    range has ids of requests shed before they were served are left
    out: their range does not say which requests they held."""
    pt = current()
    if pt is None:
        return None
    lo, hi = r.window
    ends = {s.args["request"]: s.end for s in pt.spans(submit, lo, hi)
            if "request" in s.args}
    waits = []
    for b in pt.spans(batch, lo, hi):
        first, last = b.args.get("first"), b.args.get("last")
        if first is None or last is None or last - first + 1 != b.args.get("requests"):
            continue
        waits.extend(max(b.start - ends[i], 0.0) * 1e-6
                     for i in range(first, last + 1) if i in ends)
    return median(waits) if waits else None
