#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` ``workloads``; its
configuration, traffic mix, limits and per-layer readers are files found
by name (``benchmarks/chip/harness.py``).  Inputs and weights are made
from ``--seed``.  Set-up warms every shape the window uses; the window
then runs for ``--seconds`` (``--trace 0``: end-to-end metrics) or is
traced (``--trace 1``: per-layer metrics).  Afterwards what the window
produced is compared with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``compared``, each compared number beside its
limit; those numbers are also the last lines of standard error.  Without
an accelerator, with fewer chips than the cell asks for, or on a chip
with no published peaks, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, peaks  # noqa: E402

#: JAX's persistent compilation cache, at a fixed path in the checkout.
CACHE_DIR = ROOT / ".jax_cache"
#: Where a traced run writes its profile; removed once read.
TRACE_DIR = ROOT / ".bench_trace"


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(count: int):
    """The accelerator devices, or ``SystemExit`` without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(f"run: no accelerator; JAX found {len(devices)} CPU device(s)")
    if len(devices) < count:
        raise SystemExit(f"run: the cell needs {count} chips, JAX sees {len(devices)}")
    try:
        peaks.peak_for(devices[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"run: {e}") from None
    return devices


def import_program() -> None:
    """The program under test, from this checkout's ``src``."""
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    try:
        import repro
    except ImportError as e:
        raise SystemExit(f"run: the program is not in this checkout: {e}") from None
    where = [Path(p).resolve() for p in repro.__path__]
    if where != [src / "repro"]:
        raise SystemExit(f"run: imported repro from {where}, not {src}")


def enable_compile_cache() -> None:
    """Every program, the small ones too, into the cache in the checkout
    (``repro.launch.compile_cache`` keeps the same directory), with no size
    limit: a limit turns on eviction, which here failed every write."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def verdict(compared: dict, limits: dict) -> tuple[bool, dict]:
    """Each limited number beside its limit; correct when every one is
    finite and within it."""
    missing = [k for k in limits if k not in compared]
    if missing:
        raise harness.BenchError(f"the run read no value for {missing}")
    shown = {k: {"value": compared[k], "limit": lim} for k, lim in limits.items()}
    correct = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
                  for v in shown.values())
    return correct, shown


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        generator = harness.load_generator(cell.traffic["kind"])
    except harness.BenchError as e:
        raise SystemExit(f"run: {e}") from None
    devices = find_chips(cell.chips)
    import_program()
    enable_compile_cache()
    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR / f"{cell.name}.{args.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        outcome = generator.run(cell, seed=args.seed, seconds=args.seconds,
                             trace_dir=trace_dir, t_start=T_START)
        if args.trace:
            metrics = {}
            for m in cell.per_layer:
                value = harness.load_reader(m["name"])(outcome.readings)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            metrics = {m["name"]: {"value": outcome.values[m["name"]], "unit": m["unit"]}
                       for m in cell.end_to_end}
        correct, shown = verdict(outcome.compared, cell.limits)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": outcome.memory_peak_bytes,
    }
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics}
    if args.trace:
        device["busy_s"] = outcome.busy_s
        device["window_s"] = outcome.window_s
        result["breakdown"] = outcome.breakdown
    result["device"] = device
    result["compared"] = shown
    for k, v in outcome.compared.items():
        if k not in shown:
            harness.log(f"read (not compared) {k} = {v!r}")
    for k, v in shown.items():
        harness.log(f"compared {k} = {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
