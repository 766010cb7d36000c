"""Open-loop serving traffic through ``repro.serve.ServeRuntime``.

Requests arrive on a schedule fixed before the window opens and are sent
when due, whether or not earlier ones were answered.  Latency runs from
when a request was due, not from when the generator got round to sending
it, so a stall also delays what was due during it; how late the
generator ran is reported on its own line.  A request that is shed,
rejected, expired or failed has no answer and counts as ``+inf``.

Every seed gets the same requests in another order: the inter-arrival
gaps are the exponential distribution's quantiles at ``(i + 1/2)/N`` and
the sizes the truncated Zipf distribution's, both shuffled by the seed,
so the window's work does not change with the seed.

Traffic keys: ``rate_per_s`` (Poisson arrivals), ``zipf_a`` and
``max_request`` (request sizes in samples, Zipf truncated to
1..max_request), ``buckets``, ``max_pending_samples``, ``deadline_s`` and
``flush_interval_s`` (the runtime as deployed).
"""
from __future__ import annotations

import time
from contextlib import nullcontext

import numpy as np

from benchmarks.chip import data, harness, peaks, reference, stats, tracing

TRAFFIC_KEYS = ("rate_per_s", "zipf_a", "max_request", "buckets",
                "max_pending_samples", "deadline_s", "flush_interval_s")
#: How many answered requests the comparison draws, besides the largest.
CHECK_REQUESTS = 64
#: How long after the last due time a run waits for answers.
DRAIN_WAIT_S = 60.0


def schedule(seed: int, traffic: dict, seconds: float):
    """Inter-arrival gaps (s) and request sizes of one window."""
    rate = float(traffic["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u) / rate
    k = np.arange(1, traffic["max_request"] + 1)
    pmf = k ** -float(traffic["zipf_a"])
    cdf = np.cumsum(pmf) / pmf.sum()
    sizes = np.searchsorted(cdf, u) + 1
    rng = np.random.default_rng(seed & (2 ** 64 - 1))
    return rng.permutation(gaps), rng.permutation(sizes)


def batch_fill(runtime_stats: dict, buckets) -> float | None:
    """Samples per engine call over the bucket each call ran in (%).  The
    runtime histograms batch sizes by the next power of two, which lands
    in the same bucket as the size itself for power-of-two buckets."""
    hist = runtime_stats["batch_size_hist"]
    if not hist:
        return None
    buckets = sorted(buckets)
    slots = sum(n * next(b for b in buckets if b >= size) for size, n in hist.items())
    return 100.0 * runtime_stats["batch_samples"] / slots


def make_pool(cfg: dict, traffic: dict, seed: int) -> np.ndarray:
    """The inputs requests are cut from: the test split, on the host."""
    _, _, x_test, _ = data.make_dataset(
        data.seed_key(seed, 0), num_train=traffic["max_request"],
        num_test=cfg["num_test"], input_dim=cfg["input_dim"],
        num_classes=cfg["num_classes"], workers=1,
    )
    return np.asarray(x_test)


def make_weights(cfg: dict, seed: int):
    """The served stack's readouts and random matrices, from the seed."""
    return data.make_stack(
        data.seed_key(seed, 3), input_dim=cfg["input_dim"],
        num_classes=cfg["num_classes"], hidden=cfg["hidden"],
        layers=cfg["num_layers"], eps_radius=cfg["eps_scale"] * 2.0 * cfg["num_classes"],
    )


def make_engine(cfg: dict, traffic: dict, seed: int):
    from repro.core import ssfn
    from repro.serve import ServeEngine
    from repro.serve.export import ServeArtifact

    readouts, rmats = make_weights(cfg, seed)
    artifact = ServeArtifact(
        params=ssfn.SSFNParams(o=tuple(readouts), r=tuple(rmats)),
        num_classes=cfg["num_classes"], input_dim=cfg["input_dim"],
        activation="relu", features=None, version=1, manifest={},
    )
    return ServeEngine(artifact, buckets=tuple(traffic["buckets"])), readouts, rmats


def warm(engine, traffic: dict, pool: np.ndarray) -> None:
    """Run every batch size the window can coalesce, each with a request of
    every size at its start and at its end, through a runtime of its own:
    the bucket programs, the padding and the result slicing all compile
    here and not in the window."""
    from repro.serve.runtime import ServeRuntime

    rt = ServeRuntime(engine, max_pending_samples=traffic["max_pending_samples"]).start()
    for total in range(1, traffic["max_request"] + 1):
        rt.submit(pool[:, :total])
        rt.flush()
        for first in range(1, total):
            rt.submit(pool[:, :first])
            rt.submit(pool[:, :total - first])
            rt.flush()
    rt.drain()


def runtime(engine, traffic: dict):
    """The runtime as the traffic deploys it, started."""
    from repro.serve.runtime import ServeRuntime

    return ServeRuntime(
        engine, max_pending_samples=traffic["max_pending_samples"],
        default_deadline_s=traffic["deadline_s"],
        flush_interval_s=traffic["flush_interval_s"],
    ).start()


def open_loop(rt, requests, gaps):
    """Send each request when due; wait for every answer (at most
    ``DRAIN_WAIT_S`` past the last due time).  Returns the handles, the
    due times and the generator's lateness (``time.monotonic``), and the
    window's opening on both clocks."""
    handles, late = [], np.empty(len(gaps))
    with tracing.span(tracing.WINDOW_SPAN):
        t_open = time.monotonic()
        opened = time.perf_counter()
        due = t_open + np.cumsum(gaps)
        for i, x in enumerate(requests):
            wait = due[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.monotonic() - due[i]
            with tracing.span("bench.submit"):
                handles.append(rt.submit(x))
        give_up = due[-1] + DRAIN_WAIT_S
        while not all(h.done() for h in handles) and time.monotonic() < give_up:
            time.sleep(0.001)
    rt.drain()
    return handles, due, late, t_open, opened


def requests_of(pool: np.ndarray, sizes, max_request: int):
    """Each request's columns, taken in turn from the pool of inputs."""
    starts = np.cumsum(np.concatenate([[0], sizes[:-1]])) % (pool.shape[1] - max_request)
    return [pool[:, s:s + k] for s, k in zip(starts, sizes)]


def latency_summary(handles, due, sizes, window_s: float) -> dict:
    """The end-to-end serving numbers of one window."""
    ok = [h.ok() for h in handles]
    lat = stats.latencies_from_due(
        due, [h.completed_at if o else None for h, o in zip(handles, ok)])
    answered = int(sum(s for s, o in zip(sizes, ok) if o))
    return {
        "serve_p50_ms": float(stats.percentile(lat, 50) * 1e3),
        "serve_p95_ms": float(stats.percentile(lat, 95) * 1e3),
        "serve_samples_per_s": answered / window_s,
    }


def check_sample(seed: int, answered, sizes) -> list[int]:
    """The requests the comparison judges: ``CHECK_REQUESTS`` of the
    answered ones, drawn from the seed, and the largest."""
    rng = np.random.default_rng((seed + 1) & (2 ** 64 - 1))
    take = min(CHECK_REQUESTS, len(answered))
    sample = set(rng.choice(answered, size=take, replace=False).tolist())
    sample.add(max(answered, key=lambda i: sizes[i]))
    return sorted(sample)


def compare(cfg: dict, readouts, rmats, xs, got) -> dict:
    """The numbers served answers are judged by, against the reference's
    forward of the same weights.  ``xs`` are the requests' inputs and
    ``got`` their answers side by side, in the same order.

    ``logit_gap`` is the widest gap of any served logit over the sample's
    largest reference logit: an answer altered or left out.  Each
    request's own gap is its widest over its own largest reference logit;
    ``logit_gap_p10`` is the tenth percentile of those.  One bfloat16
    rounding that falls the other way in a request's first layers moves
    that request's logits by as much as a lower precision does, but a
    lower precision moves every request, and the tenth percentile reads
    only what moves nine requests in ten."""
    import jax.numpy as jnp

    ops = reference.operand_dtype(cfg["matmul_operands"])
    ref = np.asarray(reference.forward(
        readouts, rmats, jnp.asarray(np.concatenate(xs, axis=1)), operands=ops))
    diff = np.abs(np.asarray(got, np.float32) - ref)
    ends = np.cumsum([x.shape[1] for x in xs])[:-1]
    per_request = [float(np.max(d) / np.max(np.abs(r)))
                   for d, r in zip(np.split(diff, ends, axis=1), np.split(ref, ends, axis=1))]
    harness.log("per-request logit gap: " + ", ".join(
        f"p{q} {stats.percentile(per_request, q):.3e}" for q in (0, 10, 25, 50, 75, 100)))
    return {
        "logit_gap": float(np.max(diff) / np.max(np.abs(ref))),
        "logit_gap_p10": stats.percentile(per_request, 10),
    }


def run(cell: harness.Cell, *, seed: int, seconds: int, trace_dir,
        t_start: float) -> harness.Outcome:
    import jax

    cfg, traffic = cell.config, cell.traffic
    device = jax.devices()[0]
    compiles = harness.CompileCounter()
    pool = make_pool(cfg, traffic, seed)
    engine, readouts, rmats = make_engine(cfg, traffic, seed)
    warm(engine, traffic, pool)
    gaps, sizes = schedule(seed, traffic, seconds)
    requests = requests_of(pool, sizes, traffic["max_request"])

    rt = runtime(engine, traffic)
    lowerings0 = engine.cache_info()["lowerings"]
    compiles0 = compiles.snapshot()
    harness.settle()
    tracer = tracing.capture(str(trace_dir)) if trace_dir else nullcontext()
    with tracer:
        handles, due, late, t_open, opened = open_loop(rt, requests, gaps)
    setup_s = opened - t_start
    window_s = max([due[-1]] + [h.completed_at for h in handles if h.done()]) - t_open
    lowerings = engine.cache_info()["lowerings"] - lowerings0
    traced, built = (a - b for a, b in zip(compiles.snapshot(), compiles0))
    harness.log(f"window: {len(handles)} requests in {window_s:.6f} s; bucket "
                f"lowerings {lowerings}, jaxpr traces {traced}, backend compiles {built}")
    harness.log(f"generator lateness: median {np.median(late) * 1e3:.6f} ms, "
                f"p95 {stats.percentile(late, 95) * 1e3:.6f} ms, "
                f"max {late.max() * 1e3:.6f} ms")
    memory = harness.memory_peak_bytes([device])

    ok = [h.ok() for h in handles]
    counts = {k: rt.stats[k] for k in ("completed", "failed", "expired", "rejected")}
    harness.log(f"requests: {counts}; max queue depth {rt.stats['max_queue_depth']} samples")
    values = dict(latency_summary(handles, due, sizes, window_s), setup_s=setup_s)
    harness.log(f"latency from due: p50 {values['serve_p50_ms']:.6f} ms, "
                f"p95 {values['serve_p95_ms']:.6f} ms")
    outcome = harness.Outcome(
        attempted=len(handles), failed=len(handles) - sum(ok),
        values=values,
        compared={}, memory_peak_bytes=memory,
    )
    if trace_dir is not None:
        trace = tracing.load(str(trace_dir))
        lo, hi = trace.window
        outcome.readings = harness.Readings(
            trace=trace, window=(lo, hi), peak=peaks.peak_for(device.device_kind),
            counters={"batch_fill": batch_fill(rt.stats, engine.buckets)},
        )
        outcome.window_s = (hi - lo) * 1e-9
        outcome.busy_s = tracing.busy_s(trace.chips[0], lo, hi)
        outcome.breakdown = {
            "device_ops": tracing.top_device_ops(trace, lo, hi),
            "idle_gaps": tracing.top_idle_gaps(trace, lo, hi),
        }

    # Judge a sample of the answered requests, drawn from the seed and
    # holding the largest, against the reference forward.
    answered = [i for i, o in enumerate(ok) if o]
    if not answered:
        outcome.compared = {"logit_gap": float("inf"), "logit_gap_p10": float("inf")}
        return outcome
    sample = check_sample(seed, answered, sizes)
    got = np.concatenate([np.asarray(handles[i].result()) for i in sample], axis=1)
    xs = [requests[i] for i in sample]
    del handles, rt, engine
    outcome.compared = compare(cfg, readouts, rmats, xs, got)
    harness.log(f"checked {len(sample)} requests ({got.shape[1]} samples, largest "
                f"{max(sizes[i] for i in sample)})")
    return outcome
