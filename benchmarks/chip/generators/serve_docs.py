"""Open-loop serving of documents: token requests through
``repro.serve.ServeRuntime`` to an engine whose artifact names a frozen
Granite-4.0-H-Micro backbone (``granite-h-micro:<seed>``) in front of
the SSFN stack.

The schedule and the latency accounting are the ``serve`` generator's
(imported): requests arrive open-loop at a fixed rate, latency runs
from each request's due time, and a request without an answer counts
as ``+inf``.  The cell reports ``serve_p50_ms`` and ``setup_s``; its
rate is fixed, so the texts answered a second would only read back the
offered load.

A request carries 1..``max_texts`` texts (Zipf ``zipf_a``), each a
column of token ids drawn uniformly from the vocabulary without the pad
id, right-padded with the pad id to the request's longest text.  Text lengths are lognormal (median
``length_median``, sigma ``length_sigma``) clipped to
``min_length``..``max_length``.  Every seed gets the same gaps, text
counts and lengths (the distributions' quantiles) in the same order:
a window holds about a hundred requests of 0.1-1 s each, and the order
alone moved the median latency by a fifth between seeds.  The seed
draws the token ids, the weights and the sample that is checked.

The answers are judged against ``reference_granite`` (the plain
backbone in float32, on weights it draws itself) and
``reference.forward`` (the stack), on a sample of the answered requests
drawn from the seed and the longest one: the logits, the pooled
features, and the first Mamba2 layer's scan alone (``ssd_gap``),
on inputs the program and the sequential recurrence share, where the
scan's state precision shows above the bfloat16 activations of the
forty layers.

Traffic keys: ``rate_per_s``, ``zipf_a``, ``max_texts``,
``length_median``, ``length_sigma``, ``min_length``, ``max_length``,
``buckets`` (``[texts, length]`` bucket programs; a batch may take the
largest one's tokens), ``max_pending_samples`` (texts), ``deadline_s``
and ``flush_interval_s``.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from statistics import NormalDist

import numpy as np

from benchmarks.chip import (
    data,
    harness,
    peaks,
    reference,
    reference_granite,
    stats,
    tracing,
    work_granite,
)
from benchmarks.chip.generators import serve

TRAFFIC_KEYS = ("rate_per_s", "zipf_a", "max_texts", "length_median",
                "length_sigma", "min_length", "max_length", "buckets",
                "max_pending_samples", "deadline_s", "flush_interval_s")
#: The id the program pads token columns with (``repro.serve.features``).
PAD_ID = 0
#: The seed of the schedule's order, the same in every run.
ORDER_SEED = 0


def schedule(traffic: dict, seconds: float):
    """Inter-arrival gaps (s), texts per request and the length of every
    text, in request order: the same for every seed."""
    gaps, sizes = serve.schedule(ORDER_SEED,
                                 dict(traffic, max_request=traffic["max_texts"]), seconds)
    total = int(sizes.sum())
    normal = NormalDist(np.log(traffic["length_median"]), traffic["length_sigma"])
    lengths = np.array([normal.inv_cdf((i + 0.5) / total) for i in range(total)])
    lengths = np.clip(np.rint(np.exp(lengths)), traffic["min_length"],
                      traffic["max_length"]).astype(np.int64)
    rng = np.random.default_rng(ORDER_SEED + 2)
    return gaps, sizes, rng.permutation(lengths)


def make_requests(seed: int, sizes, lengths, vocab: int) -> list[np.ndarray]:
    """Each request's ``(longest, texts)`` int32 ids, right-padded."""
    rng = np.random.default_rng((seed + 3) & (2 ** 64 - 1))
    out, at = [], 0
    for k in sizes:
        lens = lengths[at:at + k]
        at += k
        ids = np.full((int(lens.max()), int(k)), PAD_ID, np.int32)
        for j, n in enumerate(lens):
            ids[:n, j] = rng.integers(1, vocab, n)
        out.append(ids)
    return out


def texts_of(request: np.ndarray) -> list[np.ndarray]:
    """A request's texts, each its real tokens."""
    real = request != PAD_ID
    ends = request.shape[0] - np.argmax(real[::-1], axis=0)
    return [request[:n, j] for j, n in enumerate(ends)]


def backbone_seed(seed: int) -> int:
    """The extractor's weight seed, a non-negative 31-bit number."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


def make_engine(cfg: dict, traffic: dict, seed: int):
    """The engine over an artifact whose features spec selects the
    backbone; the stack's weights from the seed."""
    from repro.core import ssfn
    from repro.serve import ServeEngine
    from repro.serve.export import ServeArtifact

    readouts, rmats = data.make_stack(
        data.seed_key(seed, 3), input_dim=cfg["hidden_size"],
        num_classes=cfg["num_classes"], hidden=cfg["stack_hidden"],
        layers=cfg["stack_layers"], eps_radius=cfg["eps_scale"] * 2.0 * cfg["num_classes"],
    )
    artifact = ServeArtifact(
        params=ssfn.SSFNParams(o=tuple(readouts), r=tuple(rmats)),
        num_classes=cfg["num_classes"], input_dim=cfg["hidden_size"],
        activation="relu", features=f"granite-h-micro:{backbone_seed(seed)}",
        version=1, manifest={},
    )
    engine = ServeEngine(artifact, buckets=tuple(map(tuple, traffic["buckets"])))
    return engine, readouts, rmats


def warm(engine, traffic: dict, vocab: int) -> None:
    """Every bucket program once, full, and the runtime's path once."""
    rng = np.random.default_rng(0)
    for texts, length in engine.buckets:
        engine.forward_features(rng.integers(1, vocab, (length, texts)).astype(np.int32))
    rt = runtime(engine, dict(traffic, deadline_s=None, flush_interval_s=None))
    rt.submit(rng.integers(1, vocab, (64, 2)).astype(np.int32))
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


def logit_gaps(got: np.ndarray, ref: np.ndarray, widths) -> dict:
    """``logit_gap`` and ``logit_gap_p10`` as the ``serve`` cell defines
    them: the widest gap over the sample's largest reference logit, and
    the tenth percentile of each request's widest gap over its own
    largest reference logit."""
    diff = np.abs(np.asarray(got, np.float64) - ref)
    ends = np.cumsum(widths)[:-1]
    per_request = [float(np.max(d) / np.max(np.abs(r)))
                   for d, r in zip(np.split(diff, ends, axis=1), np.split(ref, ends, axis=1))]
    harness.log("per-request logit gap: " + ", ".join(
        f"p{q} {stats.percentile(per_request, q):.3e}" for q in (0, 10, 25, 50, 75, 100)))
    return {"logit_gap": float(np.max(diff) / np.max(np.abs(ref))),
            "logit_gap_p10": stats.percentile(per_request, 10)}


def feature_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """The largest ``||phi - phi_ref|| / ||phi_ref||`` over the texts."""
    num = np.linalg.norm(np.asarray(got, np.float64) - ref, axis=0)
    return float(np.max(num / np.linalg.norm(ref, axis=0)))


def program_scan(engine):
    """The program's state-space scan of one text, as its Mamba2 layers
    run it (``blocks.ssd_scan``): ``(x, dt, A, B, C) -> y`` before the D
    skip, (S, d_inner)."""
    import jax

    from repro.models import blocks

    model = engine.extractor.model
    scan = jax.jit(lambda xs, dt, a, bm, cm: blocks.ssd_scan(
        xs[None], dt[None], a, bm[None], cm[None], model,
        pallas=model.use_pallas_kernels)[0][0])
    return scan


def reference_answers(cfg: dict, seed: int, readouts, rmats, texts, *, low=None,
                      scan=None) -> dict:
    """The reference's pooled features of ``texts`` (``reference_granite.
    features``, on the backbone weights of ``backbone_seed(seed)``; with
    ``scan``, the first layer's scan check), and the stack's logits of
    the last token's and of the one before."""
    import jax.numpy as jnp

    sz = reference_granite.Sizes.from_config(cfg)
    ref = reference_granite.features(backbone_seed(seed), texts, sz, PAD_ID, low=low,
                                     scan=scan)
    ops = reference.operand_dtype(cfg["matmul_operands"])

    def logits(phi):
        return np.asarray(reference.forward(readouts, rmats, jnp.asarray(phi), operands=ops),
                          np.float64)

    ref["features"] = ref.pop("last")
    return dict(ref, logits=logits(ref["features"]), logits_before=logits(ref["before"]))


#: The lower precision a control run reads besides the program
#: (``control_docs.py``): name -> ``reference_answers``'s ``low``.
CONTROLS = {"control_state": "bfloat16"}


def readings(got, phi, ref: dict, widths) -> dict:
    """The numbers of logits ``got`` and features ``phi`` against the
    reference's."""
    return dict(logit_gaps(got, ref["logits"], widths),
                feature_gap=feature_gap(phi, ref["features"]))


def run(cell: harness.Cell, *, seed: int, seconds: int, trace_dir,
        t_start: float, controls: bool = False) -> harness.Outcome:
    """One run of the cell; with ``controls`` the outcome also carries
    ``controls``: the readings of each lower precision of ``CONTROLS``
    and of the planted fault (each text's feature taken one token early)
    against the same reference, on the same sample."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    device = jax.devices()[0]
    compiles = harness.CompileCounter()
    engine, readouts, rmats = make_engine(cfg, traffic, seed)
    warm(engine, traffic, cfg["vocab_size"])
    gaps, sizes, lengths = schedule(traffic, seconds)
    requests = make_requests(seed, sizes, lengths, cfg["vocab_size"])
    tokens = [int(n) for n in np.add.reduceat(lengths, np.cumsum(sizes) - sizes)]

    rt = runtime(engine, traffic)
    lowerings0 = engine.cache_info()["lowerings"]
    compiles0 = compiles.snapshot()
    harness.settle()
    tracer = tracing.capture(str(trace_dir)) if trace_dir else nullcontext()
    with tracer:
        handles, due, late, t_open, opened = serve.open_loop(rt, requests, gaps)
    setup_s = opened - t_start
    window_s = max([due[-1]] + [h.completed_at for h in handles if h.done()]) - t_open
    lowerings = engine.cache_info()["lowerings"] - lowerings0
    traced, built = (a - b for a, b in zip(compiles.snapshot(), compiles0))
    harness.log(f"window: {len(handles)} requests, {int(sizes.sum())} texts, "
                f"{sum(tokens)} tokens in {window_s:.6f} s; bucket lowerings "
                f"{lowerings}, jaxpr traces {traced}, backend compiles {built}")
    harness.log(f"generator lateness: median {np.median(late) * 1e3:.6f} ms, "
                f"p95 {stats.percentile(late, 95) * 1e3:.6f} ms, "
                f"max {late.max() * 1e3:.6f} ms")
    memory = harness.memory_peak_bytes([device])

    ok = [h.ok() for h in handles]
    counts = {k: rt.stats[k] for k in ("completed", "failed", "expired", "rejected",
                                       "batches", "batch_tokens", "bucket_tokens")}
    harness.log(f"requests: {counts}; max queue depth {rt.stats['max_queue_depth']} texts")
    values = dict(serve.latency_summary(handles, due, sizes, window_s), setup_s=setup_s)
    del values["serve_samples_per_s"]
    harness.log(f"latency from due: p50 {values['serve_p50_ms']:.6f} ms, "
                f"p95 {values['serve_p95_ms']:.6f} ms")
    outcome = harness.Outcome(
        attempted=len(handles), failed=len(handles) - sum(ok),
        values=values, compared={}, memory_peak_bytes=memory,
    )
    if trace_dir is not None:
        trace = tracing.load(str(trace_dir))
        lo, hi = trace.window
        sz = work_granite.Sizes.from_config(cfg)
        starts = np.cumsum(sizes) - sizes
        answered_lengths = [n for i, o in enumerate(ok) if o
                            for n in lengths[starts[i]:starts[i] + sizes[i]]]
        outcome.readings = harness.Readings(
            trace=trace, window=(lo, hi), peak=peaks.peak_for(device.device_kind),
            counters={"sizes": sz, "real_tokens": rt.stats["batch_tokens"],
                      "bucket_tokens": rt.stats["bucket_tokens"],
                      "real_flops": sum(work_granite.text_flops(sz, int(n))
                                        for n in answered_lengths)},
        )
        outcome.window_s = (hi - lo) * 1e-9
        outcome.busy_s = tracing.busy_s(trace.chips[0], lo, hi)
        outcome.breakdown = {
            "device_ops": tracing.top_device_ops(trace, lo, hi),
            "idle_gaps": tracing.top_idle_gaps(trace, lo, hi),
        }

    # Judge a sample of the answered requests, drawn from the seed and
    # holding the longest, against the reference.
    answered = [i for i, o in enumerate(ok) if o]
    if not answered:
        outcome.compared = dict.fromkeys(("logit_gap", "logit_gap_p10", "feature_gap",
                                          "ssd_gap"), float("inf"))
        return outcome
    sample = serve.check_sample(seed, answered, tokens)
    got = np.concatenate([np.asarray(handles[i].result()) for i in sample], axis=1)
    widths = [requests[i].shape[1] for i in sample]
    texts = [t for i in sample for t in texts_of(requests[i])]
    rows = max(requests[i].shape[0] for i in sample)
    ids = np.concatenate([np.pad(requests[i], ((0, rows - requests[i].shape[0]), (0, 0)),
                                 constant_values=PAD_ID) for i in sample], axis=1)
    # The backbone's features of the sample, from the window's bucket programs.
    _, phi = engine.forward_features(ids)
    del handles, rt
    t0 = time.perf_counter()
    ref = reference_answers(cfg, seed, readouts, rmats, texts, scan=program_scan(engine))
    outcome.compared = dict(readings(got, phi, ref, widths), ssd_gap=ref["ssd_gap"],
                            ssd_gap_control=ref["ssd_gap_control"])
    harness.log(f"checked {len(sample)} requests ({len(texts)} texts, "
                f"{sum(len(t) for t in texts)} tokens, longest request "
                f"{max(tokens[i] for i in sample)} tokens); reference "
                f"{time.perf_counter() - t0:.1f} s")
    if controls:
        import jax.numpy as jnp

        # The wrong token leaves the scan as the program runs it.
        outcome.controls = {"wrong_token": dict(readings(
            ref["logits_before"], ref["before"], ref, widths), ssd_gap=ref["ssd_gap"])}
        for name, low in CONTROLS.items():
            lower = reference_answers(cfg, seed, readouts, rmats, texts,
                                      low=jnp.dtype(low).type)
            outcome.controls[name] = dict(readings(lower["logits"], lower["features"], ref,
                                                   widths), ssd_gap=ref["ssd_gap_control"])
    return outcome
