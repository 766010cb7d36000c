"""What a run of one cell needs, found by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own under this directory, so a
later change adds a cell by adding files and an entry:

- ``BENCHMARK.json`` ``configs[].file``: the configuration's sizes;
- ``traffic/<traffic>.json``: the mix's parameters; its ``kind`` names
  the general generator, ``generators/<kind>.py``, that reads them;
- ``limits/<cell>.json``: the limit of each number the cell compares to
  decide ``correct``;
- ``metrics/<metric>.py``: the reader of one per-layer metric, a
  ``read(readings)`` that returns the value, or ``None`` where the run
  has nothing to read.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class BenchError(Exception):
    """The benchmark's own files are missing or malformed."""


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise BenchError(f"cannot read {path}: {e}") from None


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str, bench_dir: Path = HERE) -> dict:
    """The traffic mix ``name``: ``<bench_dir>/traffic/<name>.json``, with
    a ``kind`` whose generator exists and whose required keys are present."""
    traffic = load_json(bench_dir / "traffic" / f"{name}.json")
    kind = traffic.get("kind")
    generator = load_generator(kind)
    missing = [k for k in generator.TRAFFIC_KEYS if k not in traffic]
    if missing:
        raise BenchError(f"traffic {name!r} ({kind}) lacks {missing}")
    return traffic


def load_generator(kind) -> ModuleType:
    if not isinstance(kind, str) or not kind.isidentifier():
        raise BenchError(f"traffic kind {kind!r} is not a generator name")
    return load_module(HERE / "generators" / f"{kind}.py", f"bench_generator_{kind}")


def load_reader(metric: str, bench_dir: Path = HERE):
    """``read`` of the per-layer metric ``metric``."""
    return load_module(bench_dir / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_")).read


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files, which
    lie under ``<root>/benchmarks/chip``."""
    bench_dir = root / HERE.relative_to(ROOT)
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise BenchError(f"workload {name!r} names unknown config {w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    limits = load_json(bench_dir / "limits" / f"{name}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=load_traffic(w["traffic"], bench_dir),
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)],
    )


@dataclass
class Readings:
    """What the per-layer readers read: the reduced trace of the traced
    window and the counters the run kept."""

    trace: object                      # tracing.Trace
    window: tuple[float, float]        # ns, on the trace's clock
    counters: dict
    peak: object                       # peaks.Peak


@dataclass
class Outcome:
    """One run's answer, before the metrics are named."""

    attempted: int
    failed: int
    values: dict                       # end-to-end metrics by name
    compared: dict                     # number -> value, for ``correct``
    memory_peak_bytes: int
    readings: Readings | None = None
    busy_s: float | None = None
    window_s: float | None = None
    breakdown: dict | None = None


class CompileCounter:
    """Counts JAX traces and backend compiles in this process, so that a
    window can show it compiled nothing."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(self.EVENTS, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self) -> tuple[int, int]:
        return tuple(self.counts[e] for e in self.EVENTS)


def memory_peak_bytes(devices) -> int:
    """The peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics, as the CPU)."""
    stats = [d.memory_stats() for d in devices]
    return max((s["peak_bytes_in_use"] for s in stats if s), default=0)


def settle() -> None:
    """End of set-up: collect its garbage and freeze what is left, so that
    collections in the window scan only what the window allocates."""
    gc.collect()
    gc.freeze()


def log(*parts) -> None:
    """A progress line on standard error."""
    print(*parts, file=sys.stderr, flush=True)
