"""dSSFN serving launcher: load an exported artifact, serve a request
stream through the compile-once engine + micro-batcher.

The paper's centralized equivalence makes a stack trained across M
workers a single deployable model; ``train_dssfn --export-artifact``
writes it, this launcher serves it::

    python -m repro.launch.train_dssfn --workers 4 --layers 2 \
        --export-artifact /tmp/stack
    python -m repro.launch.serve_dssfn --artifact /tmp/stack \
        --requests 200 --request-size 1 --batch-bucket 1,8,32 \
        --max-wait-us 200

The launcher drives a synthetic open-loop request stream (seeded, so
runs are reproducible) through :class:`repro.serve.MicroBatcher` and
reports per-request p50/p99 latency, throughput, coalescing stats, and
the engine's compile counts — one lowering per (bucket, dtype) actually
used, asserted at exit.

``--runtime`` swaps the bare batcher for the hardened
:class:`repro.serve.ServeRuntime`: bounded admission, deadlines, retry +
circuit breaker, lifecycle with ``drain()``.  Combined with
``--manual-clock``, ``--chaos`` (a ``repro.serve.parse_chaos`` spec) and
``--poison-rate`` it is the CI chaos-drill entry point — the run reports
shed/expired/completed counts, breaker transitions, and the final
lifecycle state, and asserts every handle reached a terminal state::

    python -m repro.launch.serve_dssfn --artifact /tmp/stack --runtime \
        --manual-clock --requests 400 --max-pending-samples 64 \
        --deadline-ms 50 --chaos fail=0.3:burst=4:seed=7

``--features`` overrides nothing: the artifact records its own frozen
extractor spec and the engine applies it; the flag only *verifies* the
artifact matches what the operator expects (a deploy-time guard against
pointing the fleet at the wrong artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--artifact", required=True,
        help="artifact directory written by export_artifact / "
        "train_dssfn --export-artifact",
    )
    ap.add_argument(
        "--batch-bucket",
        default=None,
        help="comma-separated shape-bucket ladder (e.g. 1,8,32); request "
        "batches pad to the smallest fitting bucket so the whole stream "
        "costs one lowering per bucket used (default: powers of two "
        "up to 128)",
    )
    ap.add_argument(
        "--max-wait-us",
        type=float,
        default=0.0,
        help="micro-batching admission: flush once the oldest queued "
        "request has waited this long (0 = never hold, flush on every "
        "submit)",
    )
    ap.add_argument(
        "--max-batch",
        type=int,
        default=None,
        help="micro-batching admission: flush once this many samples are "
        "queued (default: the largest bucket)",
    )
    ap.add_argument(
        "--features",
        default=None,
        help="expected feature-extractor spec; serving refuses to start "
        "if the artifact records a different one (deploy-time guard)",
    )
    ap.add_argument(
        "--requests", type=int, default=100,
        help="synthetic request count to drive through the batcher",
    )
    ap.add_argument(
        "--request-size", type=int, default=1,
        help="samples per request (columns; 1 = single-sample requests)",
    )
    ap.add_argument(
        "--use-kernels",
        action="store_true",
        help="route propagation through the matmul_relu Pallas kernel on "
        "128-aligned shapes (einsum fallback otherwise, like training)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="optional JSON results path")

    rt = ap.add_argument_group("hardened runtime (--runtime)")
    rt.add_argument(
        "--runtime", action="store_true",
        help="serve through ServeRuntime (bounded admission, deadlines, "
        "retry + circuit breaker, drain) instead of the bare batcher",
    )
    rt.add_argument(
        "--manual-clock", action="store_true",
        help="drive the runtime on a deterministic ManualClock (ticks "
        "between submits) — the reproducible chaos-drill mode",
    )
    rt.add_argument(
        "--max-pending-samples", type=int, default=None,
        help="admission bound: load-shed submits beyond this many queued "
        "samples (default: 8x max_batch)",
    )
    rt.add_argument(
        "--max-pending-requests", type=int, default=None,
        help="admission bound on queued request count",
    )
    rt.add_argument(
        "--deadline-ms", type=float, default=None,
        help="default per-request deadline; expired requests are shed "
        "pre-flush, never served",
    )
    rt.add_argument(
        "--flush-every-us", type=float, default=None,
        help="wall-clock timer thread flush interval (ignored with "
        "--manual-clock; ticks are explicit there)",
    )
    rt.add_argument("--retries", type=int, default=2,
                    help="engine retries per batch before failure handling")
    rt.add_argument("--breaker-threshold", type=int, default=3,
                    help="consecutive batch failures that open the breaker")
    rt.add_argument("--breaker-cooldown-ms", type=float, default=250.0,
                    help="open -> half-open cooldown")
    rt.add_argument(
        "--chaos", default=None,
        help="seeded fault-injection spec, e.g. fail=0.3:burst=4:seed=7 "
        "(see repro.serve.parse_chaos)",
    )
    rt.add_argument(
        "--poison-rate", type=float, default=0.0,
        help="fraction of synthetic requests poisoned with NaN (must be "
        "rejected at admission)",
    )
    rt.add_argument(
        "--arrival-us", type=float, default=0.0,
        help="inter-arrival time of the synthetic stream (manual clock "
        "advances by this per submit; wall clock sleeps)",
    )
    rt.add_argument(
        "--tick-every", type=int, default=4,
        help="manual-clock mode: call runtime.tick() every N submits",
    )
    return ap.parse_args(argv)


def _percentile(sorted_vals: list[float], p: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(p / 100.0 * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _write_out(args, results: dict) -> None:
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)


def _drive_runtime(args, engine, xs, rng) -> dict:
    """The hardened-runtime drive path: synthetic open-loop stream with
    optional poison, chaos, and deadlines; every handle must end
    terminal and the runtime must drain cleanly."""
    import numpy as np

    from repro.serve import ManualClock, ServeRuntime, WallClock, parse_chaos

    clock = ManualClock() if args.manual_clock else WallClock()
    chaos = parse_chaos(args.chaos) if args.chaos else None
    runtime = ServeRuntime(
        engine,
        clock=clock,
        max_batch=args.max_batch,
        max_pending_samples=args.max_pending_samples,
        max_pending_requests=args.max_pending_requests,
        default_deadline_s=(
            args.deadline_ms * 1e-3 if args.deadline_ms is not None else None
        ),
        flush_interval_s=(
            args.flush_every_us * 1e-6
            if args.flush_every_us is not None else None
        ),
        max_retries=args.retries,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown_ms * 1e-3,
        chaos=chaos,
    ).start()
    if chaos is not None:
        print(chaos.describe(), flush=True)

    t0 = time.perf_counter()
    handles = []
    for i, x in enumerate(xs):
        if args.poison_rate and rng.random() < args.poison_rate:
            x = x.copy()
            x[0, 0] = np.nan
        handles.append(runtime.submit(x))
        if args.arrival_us:
            clock.sleep(args.arrival_us * 1e-6)
        if args.manual_clock and args.tick_every and (i + 1) % args.tick_every == 0:
            runtime.tick()
    runtime.drain()
    wall = time.perf_counter() - t0

    assert all(h.done() for h in handles), "non-terminal handles after drain"
    snap = runtime.snapshot()
    assert snap["state"] == "STOPPED", f"drain left state {snap['state']}"

    completed = sorted(h.latency_s for h in handles if h.ok())
    info = engine.cache_info()
    # Bisection may lower smaller buckets mid-stream; the bound that
    # must hold is still one lowering per (bucket, dtype).
    assert info["lowerings"] <= 2 * len(engine.buckets), (
        f"{info['lowerings']} lowerings for {len(engine.buckets)} buckets"
    )
    results = {
        "artifact": engine.artifact.describe(),
        "mode": "runtime",
        "clock": "manual" if args.manual_clock else "wall",
        "chaos": args.chaos,
        "requests": args.requests,
        "request_size": args.request_size,
        "wall_time_s": wall,
        "completed": sum(h.ok() for h in handles),
        "failed": sum(h.status == "failed" for h in handles),
        "rejected": sum(h.status == "rejected" for h in handles),
        "expired": sum(h.status == "expired" for h in handles),
        "latency_ms": {
            "p50": _percentile(completed, 50) * 1e3,
            "p99": _percentile(completed, 99) * 1e3,
        },
        "snapshot": snap,
        "compile": info,
    }
    s = snap["stats"]
    print(
        f"runtime drill: {results['completed']} completed / "
        f"{results['failed']} failed / {results['rejected']} rejected / "
        f"{results['expired']} expired of {args.requests} "
        f"(shed_rate={snap['shed_rate']:.3f} "
        f"deadline_hit_rate={snap['deadline_hit_rate']:.3f}) "
        f"breaker opens={s['breaker_opens']} closes={s['breaker_closes']} "
        f"retries={s['retries']} quarantined={s['quarantined']} "
        f"final_state={snap['state']}",
        flush=True,
    )
    _write_out(args, results)
    return results


def main(argv=None) -> dict:
    args = parse_args(argv)

    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import MicroBatcher, ServeEngine, load_artifact

    enable_compile_cache()

    artifact = load_artifact(args.artifact)
    if args.features is not None:
        expect = None if args.features == "identity" else args.features
        if artifact.features != expect:
            raise SystemExit(
                f"artifact records features="
                f"{(artifact.features or 'identity')!r}, operator "
                f"expected {args.features!r} — refusing to serve"
            )

    buckets = None
    if args.batch_bucket:
        buckets = tuple(int(b) for b in args.batch_bucket.split(","))
    engine = ServeEngine(
        artifact, buckets=buckets, use_kernels=args.use_kernels
    )
    print(engine.describe(), flush=True)

    max_batch = args.max_batch if args.max_batch else engine.max_batch

    # Synthetic requests arrive in raw request space.  Without an
    # extractor that is the stack's input dim; with one, the raw dim is a
    # free choice (frozen extractors bind to whatever dim the first
    # request carries), so the stack dim doubles as a reasonable default.
    rng = np.random.default_rng(args.seed)
    p_req = (
        engine.request_dim
        if engine.request_dim is not None
        else artifact.input_dim
    )
    xs = [
        rng.standard_normal((p_req, args.request_size)).astype(np.float32)
        for _ in range(args.requests)
    ]

    # Warmup: compile every bucket the coalescer can produce, off the
    # clock — the fleet pattern (compile at deploy, serve hot).
    import jax

    for b in engine.buckets:
        if b <= max_batch or b == engine.bucket_for(args.request_size):
            jax.block_until_ready(
                engine.forward(np.zeros((p_req, b), np.float32))
            )
    warm_lowerings = engine.lowerings

    if args.runtime:
        return _drive_runtime(args, engine, xs, rng)

    batcher = MicroBatcher(
        engine, max_batch=args.max_batch, max_wait_us=args.max_wait_us
    )
    warm_stats = dict(batcher.stats)

    t0 = time.perf_counter()
    handles = [batcher.submit(x) for x in xs]
    batcher.flush()
    wall = time.perf_counter() - t0
    assert all(h.done() for h in handles)

    lats = sorted(h.latency_s for h in handles)
    total_samples = args.requests * args.request_size
    info = engine.cache_info()
    # The compile-once contract, asserted: warmup lowered every reachable
    # bucket once; the timed stream itself must not lower anything.
    assert info["lowerings"] == warm_lowerings, (
        f"timed stream triggered {info['lowerings'] - warm_lowerings} "
        f"extra lowerings (compile-once contract broken)"
    )
    assert info["lowerings"] <= len(engine.buckets), (
        f"{info['lowerings']} lowerings for {len(engine.buckets)} buckets"
    )

    results = {
        "artifact": artifact.describe(),
        "buckets": list(engine.buckets),
        "max_wait_us": args.max_wait_us,
        "requests": args.requests,
        "request_size": args.request_size,
        "wall_time_s": wall,
        "throughput_samples_per_s": total_samples / max(wall, 1e-12),
        "latency_ms": {
            "p50": _percentile(lats, 50) * 1e3,
            "p99": _percentile(lats, 99) * 1e3,
            "max": lats[-1] * 1e3,
        },
        "batches": batcher.stats["batches"] - warm_stats["batches"],
        "mean_batch_size": batcher.mean_batch_size(since=warm_stats),
        "compile": info,
    }
    print(
        f"served {args.requests} requests ({total_samples} samples) in "
        f"{wall * 1e3:.1f} ms: p50={results['latency_ms']['p50']:.3f} ms "
        f"p99={results['latency_ms']['p99']:.3f} ms "
        f"throughput={results['throughput_samples_per_s']:.0f} samples/s "
        f"batches={results['batches']} "
        f"(mean size {results['mean_batch_size']:.1f}) "
        f"lowerings={info['lowerings']}",
        flush=True,
    )

    _write_out(args, results)
    return results


if __name__ == "__main__":
    main()
