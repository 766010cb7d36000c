#!/usr/bin/env python3
"""Chip smoke test: the dSSFN train -> serve path on a TPU, at paper width.

    python chip_smoke.py             # one chip: phases 1-3
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

One chip, all in this one process:

1. The paper's MNIST deployment (Table I and section III-B: P=784, Q=10,
   J=60,000/10,000, M=20 workers, L=20, n=2Q+1000=1020, K=100), trained
   through ``repro.dssfn.train`` on ``SimulatedBackend(20)`` — twenty
   workers vmapped on one chip — under exact consensus and under gossip
   on the paper's degree-4 ring, each against
   ``layerwise.train_centralized_ssfn`` on the same data and key.
2. The kernel path: the same deployment at the 128-aligned width n=1024
   (J_m=3072, L=3) with ``use_kernels=True``, whose lowered layer
   programs must hold the Pallas kernels, against ``use_kernels=False``.
3. Serving: both stacks exported with ``repro.serve.export_artifact``
   and answered by ``repro.serve.ServeEngine``.

Four chips: ``MeshBackend`` with one worker per chip (M=4) against
``SimulatedBackend(4)`` on one chip, the compiled collectives against
the declared ones, and where each worker's shard lives.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.  Without a TPU, with fewer than ``--chips``
devices, or when a phase fails, the script exits non-zero and prints no
result.  Data is synthetic (``repro.data``) and made from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.abspath(__file__))

#: What a compiled Pallas TPU kernel looks like in program text.
KERNEL_MARKER = "tpu_custom_call"

#: Phase 1, the paper's deployment: workers and depth (its widths are
#: ``paper_dataset("mnist")`` and the ``SSFNConfig`` defaults).
PAPER_WORKERS, PAPER_LAYERS = 20, 20
#: Phase 2, the kernel path: the 128-aligned width, the samples per
#: worker, and L=3 so that layer 1 runs ``gram`` and layers >= 2 run
#: ``propagate_gram``.
KERNEL_HIDDEN, KERNEL_PER_WORKER, KERNEL_LAYERS = 1024, 3072, 3
#: Phase 3: the serving bucket that runs ``matmul_relu``.
KERNEL_BUCKET = 128
#: Four chips: one worker per chip.
MESH_WORKERS = 4

# Bounds, each set from its own comparison's readings on a v5e
# (CHANGES.md and PERF.md record them).  A readout gap is
# ||O_l - O_l^ref||_F / ||O_l^ref||_F at layer l.

#: Layer-0 readout gap, decentralized vs centralized and mesh vs
#: simulated.  Layer 0 is where equivalence shows: with K=100 its ADMM
#: (mu0=1e-3) has converged, and both sides round the same inputs to bf16
#: (a TPU's default f32 matmul is one bf16 pass with f32 accumulation),
#: so only the f32 summation order differs: 4.0e-7 decentralized vs
#: centralized, 1.6e-7 mesh vs simulated, 4.9e-6 after a 1e-7 input
#: perturbation.  A bf16 split between the two sides would show as ~4e-3.
LAYER0_GAP_BOUND = 1e-3
#: Max readout gap, decentralized vs centralized.  Deeper layers are not
#: converged at K=100 (mu=1): M=20 workers and one worker take different
#: iterates, a gap of 0.8175 at layer 20 (0.820 at highest precision).
#: The bound is that gap plus 10%.
CENTRALIZED_GAP_BOUND = 0.9
#: Max readout gap, mesh vs simulated.  Both run the same iterates in
#: another summation order, and at the default precision every f32-level
#: difference grows through the 20 layers: a 1e-7 input perturbation
#: moves the layer-20 readout by 0.316 (3.8e-3 at highest precision),
#: and mesh and simulated differ by 0.311.  The bound is that floor plus
#: 10%.
MESH_GAP_BOUND = 0.35
#: Max readout gap, kernels vs einsum at L=3.  Both run the same math
#: (measured gap: 0.0).  A 1e-7 input perturbation moves layers 1-3 by
#: 6.6e-3 to 1.3e-2, so a summation order of the kernel's own may cost
#: that much; the bound sits above it and far below the other two.  A
#: Gram that leaves out one of its J tiles moves the readouts by 0.60
#: (CPU, n=128, J_m=384).
KERNEL_GAP_BOUND = 3e-2
#: Test accuracy points (as a fraction) a run may sit from its reference.
ACCURACY_BOUND = 0.005
#: max |engine - ssfn.predict| over max |ssfn.predict|, serving: the same
#: column-wise forward in another GEMM shape (chip: 0.0).
SERVE_LOGIT_BOUND = 1e-3


def _log(*parts) -> None:
    print(*parts, flush=True)


def check_platform(chips: int):
    """The TPU check, made before any other work."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {len(devices)} {platform} "
            "device(s)"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} but JAX sees {len(devices)} TPU "
            "device(s)"
        )
    return devices


# ----------------------------------------------------------------------
# Helpers
# ----------------------------------------------------------------------


def distinct_layer_programs(num_layers: int) -> int:
    """Layer 0 (no W), layer 1 (W is n x P, no donation) and layers >= 2
    (W is n x n, donated carry) — one lowering each."""
    return 1 + (num_layers >= 1) + (num_layers >= 2)


def timed_train(spec, xw, tw, key) -> dict:
    """``dssfn.train`` twice through one backend: the first call compiles,
    the second is warm and must neither recompile nor change a bit."""
    import jax
    import numpy as np

    from repro import dssfn

    t0 = time.perf_counter()
    cold = dssfn.train(spec, xw, tw, key)
    jax.block_until_ready(cold.params)
    t_cold = time.perf_counter() - t0
    lowerings = cold.backend.cache_info()["lowerings"]

    t0 = time.perf_counter()
    warm = dssfn.train(replace(spec, backend=cold.backend), xw, tw, key)
    jax.block_until_ready(warm.params)
    t_warm = time.perf_counter() - t0

    info = warm.backend.cache_info()
    want = distinct_layer_programs(spec.cfg.num_layers)
    if not lowerings == info["lowerings"] == want:
        raise AssertionError(
            f"lowerings {lowerings} (cold) / {info['lowerings']} (warm), "
            f"expected {want} distinct layer programs"
        )
    for a, b in zip(cold.params.o, warm.params.o):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError("warm train differs from the cold one")
    return {
        "result": warm,
        "cold_s": t_cold,
        "warm_s": t_warm,
        "cache": {k: info[k] for k in ("entries", "lowerings", "cache_hits")},
    }


def layer_gaps(params, ref) -> list[float]:
    """||O_l - O_l^ref||_F / ||O_l^ref||_F for every layer l."""
    import jax.numpy as jnp

    return [
        float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        for a, b in zip(params.o, ref.o)
    ]


def check_gaps(what: str, params, ref, bound: float,
               layer0_bound: float | None = None) -> None:
    """Print the per-layer readout gaps of ``params`` against ``ref`` and
    hold them to ``bound`` (every layer) and ``layer0_bound`` (layer 0)."""
    gaps = layer_gaps(params, ref)
    gap = max(gaps)
    _log(f"{what}: max_readout_gap={gap:.6e} per layer: "
         + " ".join(f"{g:.3e}" for g in gaps))
    if layer0_bound is not None:
        require(gaps[0] < layer0_bound,
                f"{what}: layer-0 readout gap {gaps[0]:.3e} >= {layer0_bound}")
    require(gap < bound, f"{what}: readout gap {gap:.3e} >= {bound}")


def check_finite(params, what: str) -> None:
    import jax.numpy as jnp

    for i, o in enumerate(params.o):
        if not bool(jnp.all(jnp.isfinite(o))):
            raise AssertionError(f"{what}: readout O_{i} is not finite")


def require(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


# ----------------------------------------------------------------------
# Phase 1: the paper's deployment at full width
# ----------------------------------------------------------------------


def paper_phase(seed: int) -> dict:
    """Exact and ring-gossip dSSFN against the centralized reference, in
    the paper's MNIST deployment."""
    import jax

    from repro import dssfn
    from repro.core import equivalence, layerwise, ssfn
    from repro.core.topology import Ring
    from repro.data.synthetic import paper_dataset, partition_workers

    workers, layers = PAPER_WORKERS, PAPER_LAYERS
    data = paper_dataset("mnist", jax.random.PRNGKey(seed))
    cfg = ssfn.SSFNConfig(
        input_dim=data.input_dim, num_classes=data.num_classes,
        num_layers=layers,
    )
    key = jax.random.PRNGKey(seed + 1)
    xw, tw = partition_workers(data.x_train, data.t_train, workers)
    q = cfg.num_classes
    _log(
        f"phase1 deployment: P={cfg.input_dim} Q={q} J={xw.shape[0] * xw.shape[2]}"
        f"/{data.x_test.shape[1]} M={workers} J_m={xw.shape[2]} L={layers} "
        f"n={cfg.n} K={cfg.admm_iters} mu0={cfg.mu0} mul={cfg.mul}"
    )

    t0 = time.perf_counter()
    params_c, _ = layerwise.train_centralized_ssfn(
        data.x_train[:, : xw.shape[0] * xw.shape[2]],
        data.t_train[:, : xw.shape[0] * xw.shape[2]], cfg, key,
    )
    jax.block_until_ready(params_c)
    t_c = time.perf_counter() - t0
    check_finite(params_c, "centralized")
    acc_c = layerwise.accuracy(params_c, data.x_test, data.y_test, q)
    _log(f"phase1 centralized: wall={t_c:.3f}s (first call, compile included) "
         f"test_acc={acc_c:.4f}")

    rounds = Ring(4).rounds_for_tolerance(workers, 1e-6)
    out = {"data": data, "centralized": params_c}
    for name, policy in (("exact", "exact"), ("gossip", f"gossip:{rounds}@ring:4")):
        spec = dssfn.TrainSpec(
            cfg=cfg, backend="simulated", workers=workers, policy=policy
        )
        run = timed_train(spec, xw, tw, key)
        res = run["result"]
        check_finite(res.params, f"phase1 {name}")
        rep = equivalence.compare(params_c, res.params, data.x_test, q)
        acc = dssfn.evaluate(res, data.x_test, data.y_test)
        jitter = int((res.log.jitter_levels > 0).sum())
        _log(
            f"phase1 {name} [{res.policy.describe()}]: cold={run['cold_s']:.3f}s "
            f"warm={run['warm_s']:.3f}s "
            f"prediction_gap={rep.prediction_gap:.6e} "
            f"argmax_agreement={rep.agreement:.4f} "
            f"test_acc={acc:.4f} centralized_acc={acc_c:.4f} "
            f"jitter_events={jitter} cache={run['cache']} "
            f"final_cost={res.log.layer_costs[-1]:.6e}"
        )
        check_gaps(f"phase1 {name} vs centralized", res.params, params_c,
                   CENTRALIZED_GAP_BOUND, LAYER0_GAP_BOUND)
        require(abs(acc - acc_c) <= ACCURACY_BOUND,
                f"{name}: test accuracy {acc:.4f} vs centralized {acc_c:.4f}")
        out[name] = res
    return out


# ----------------------------------------------------------------------
# Phase 2: the Pallas kernel path at the aligned width
# ----------------------------------------------------------------------


def kernel_phase(seed: int) -> dict:
    """The paper's workers at the aligned width, Pallas kernels against
    einsum on the same data and key."""
    import jax

    from repro import dssfn
    from repro.core import engine, ssfn
    from repro.data.synthetic import make_classification, partition_workers

    workers, hidden = PAPER_WORKERS, KERNEL_HIDDEN
    per_worker, layers = KERNEL_PER_WORKER, KERNEL_LAYERS
    data = make_classification(
        jax.random.PRNGKey(seed + 2), num_train=workers * per_worker,
        num_test=10000, input_dim=784, num_classes=10,
    )
    xw, tw = partition_workers(data.x_train, data.t_train, workers)
    key = jax.random.PRNGKey(seed + 3)
    runs = {}
    for use_kernels in (True, False):
        cfg = ssfn.SSFNConfig(
            input_dim=784, num_classes=10, num_layers=layers, hidden=hidden,
            use_kernels=use_kernels,
        )
        spec = dssfn.TrainSpec(
            cfg=cfg, backend="simulated", workers=workers, policy="exact"
        )
        run = timed_train(spec, xw, tw, key)
        check_finite(run["result"].params, f"kernels={use_kernels}")
        run["acc"] = dssfn.evaluate(run["result"], data.x_test, data.y_test)
        runs[use_kernels] = run
        _log(
            f"phase2 use_kernels={use_kernels}: M={workers} n={hidden} "
            f"J_m={per_worker} L={layers} cold={run['cold_s']:.3f}s "
            f"warm={run['warm_s']:.3f}s cache={run['cache']} "
            f"test_acc={run['acc']:.4f}"
        )

    # The layer programs the kernel run executed, lowered again through
    # the same executable cache (no new entry: the same keys, the same
    # programs): layer 1 routes its Gram through ``gram`` (P=784 keeps
    # the propagation on einsum), layers >= 2 through the fused
    # ``propagate_gram``.
    res = runs[True]["result"]
    cfg = res.spec.cfg
    entries = res.backend.cache_info()["entries"]
    sds = jax.ShapeDtypeStruct
    for layer, (n_in, donate) in ((1, (784, False)), (2, (hidden, True))):
        texts = engine.layer_program(
            res.backend,
            sds((workers, n_in, per_worker), xw.dtype),
            sds((workers, 10, per_worker), tw.dtype),
            sds((hidden, n_in), xw.dtype),
            mu=cfg.mul, eps_radius=cfg.eps_radius, num_iters=cfg.admm_iters,
            use_kernels=True, donate_y=donate, policy=res.policy,
            trace_every=res.spec.trace_every,
        ).lowering_texts()
        calls = texts["hlo"].count(KERNEL_MARKER)
        _log(f"phase2 layer {layer} program: {KERNEL_MARKER} x{calls}")
        require(calls > 0, f"layer {layer} program runs no Pallas kernel")
    require(res.backend.cache_info()["entries"] == entries,
            "the lowered layer programs are not the ones the run executed")

    check_gaps("phase2 kernels vs einsum", res.params,
               runs[False]["result"].params, KERNEL_GAP_BOUND)
    require(abs(runs[True]["acc"] - runs[False]["acc"]) <= ACCURACY_BOUND,
            f"kernels: test accuracy {runs[True]['acc']:.4f} vs einsum "
            f"{runs[False]['acc']:.4f}")
    return {"data": data, "result": res}


# ----------------------------------------------------------------------
# Phase 3: serving
# ----------------------------------------------------------------------


def _check_logits(name: str, out, ref) -> None:
    """Engine logits against ``ssfn.predict`` of the training-time params:
    close, and the same class wherever the gap cannot flip the argmax."""
    import numpy as np

    out, ref = np.asarray(out), np.asarray(ref)
    delta = float(np.max(np.abs(out - ref)))
    rel = delta / float(np.max(np.abs(ref)))
    top2 = np.sort(ref, axis=0)[-2:]
    decided = (top2[1] - top2[0]) > 2 * delta
    agree = np.argmax(out, axis=0) == np.argmax(ref, axis=0)
    _log(f"phase3 {name}: max|engine-predict|={delta:.6e} (rel {rel:.6e}), "
         f"argmax agrees on {int(agree.sum())}/{agree.size} columns, "
         f"{int(decided.sum())} decided beyond the gap")
    require(rel < SERVE_LOGIT_BOUND, f"{name}: logit gap {rel:.3e}")
    require(bool(np.all(agree[decided])), f"{name}: argmax flipped")


def serve_phase(paper: dict, kern: dict) -> None:
    """Both stacks exported and answered by ``ServeEngine``: bit-identical
    within a bucket, and equal to ``ssfn.predict`` of the trained params."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ssfn
    from repro.serve import ServeEngine, export_artifact

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        # The paper stack (n=1020): bucketed, padded execution.
        path = export_artifact(os.path.join(tmp, "paper"), paper["exact"])
        eng = ServeEngine(path, buckets=(1, 8, 32))
        x_test = paper["data"].x_test
        sizes = [1, 3, 8, 5, 32, 17, 2, 12] * 5
        t0 = time.perf_counter()
        col, outs = 0, []
        for s in sizes:
            outs.append((col, s, eng.forward(x_test[:, col:col + s])))
            col += s
        jax.block_until_ready([o for _, _, o in outs])
        t_first = time.perf_counter() - t0
        info = eng.cache_info()
        t0 = time.perf_counter()
        jax.block_until_ready(
            [eng.forward(x_test[:, c:c + s]) for c, s, _ in outs]
        )
        t_warm = time.perf_counter() - t0
        require(eng.cache_info()["lowerings"] == info["lowerings"] == 3,
                f"serving lowered {eng.cache_info()['lowerings']}x for 3 buckets")
        # Within a bucket the engine is column-wise: a request padded into
        # bucket b returns the bits of an unpadded b-column batch.
        for c, s, out in outs:
            b = eng.bucket_for(s)
            full = eng.forward(x_test[:, c:c + b])
            require(np.array_equal(np.asarray(out), np.asarray(full)[:, :s]),
                    f"request of {s} at column {c} differs within bucket {b}")
        _log(f"phase3 paper stack: {len(sizes)} requests, {col} samples, "
             f"buckets={list(eng.buckets)} first={t_first:.3f}s (compile "
             f"included) warm={t_warm:.3f}s lowerings={info['lowerings']} "
             "bit-identical within each bucket")
        x = x_test[:, :col]
        _check_logits("paper stack", eng.forward(x),
                      ssfn.predict(paper["exact"].params, x, eng.num_classes))

        # The kernel stack (n=1024): matmul_relu in the 128 bucket.
        path = export_artifact(os.path.join(tmp, "kernel"), kern["result"])
        keng = ServeEngine(path, buckets=(KERNEL_BUCKET,), use_kernels=True)
        calls = keng.lowering_texts(bucket=KERNEL_BUCKET)["hlo"].count(
            KERNEL_MARKER)
        _log(f"phase3 kernel stack bucket {KERNEL_BUCKET}: "
             f"{KERNEL_MARKER} x{calls}")
        require(calls > 0, "serving bucket program runs no Pallas kernel")
        xk = kern["data"].x_test[:, :KERNEL_BUCKET]
        out = keng.forward(xk)
        part = keng.forward(xk[:, :KERNEL_BUCKET // 2])
        require(np.array_equal(np.asarray(part),
                               np.asarray(out)[:, :KERNEL_BUCKET // 2]),
                "kernel stack differs within its bucket")
        _check_logits("kernel stack", out, ssfn.predict(
            kern["result"].params, jnp.asarray(xk), keng.num_classes))


# ----------------------------------------------------------------------
# Four chips: the mesh backend
# ----------------------------------------------------------------------


def mesh_phase(seed: int) -> None:
    """``MeshBackend`` (one worker per chip) against ``SimulatedBackend``
    on one chip, at the paper's widths with J_m = J/M."""
    import jax
    import numpy as np

    from repro import analysis, dssfn
    from repro.core import engine, ssfn
    from repro.core.topology import Ring
    from repro.data.synthetic import paper_dataset, partition_workers

    workers, layers = MESH_WORKERS, PAPER_LAYERS
    data = paper_dataset("mnist", jax.random.PRNGKey(seed))
    cfg = ssfn.SSFNConfig(
        input_dim=data.input_dim, num_classes=data.num_classes,
        num_layers=layers,
    )
    key = jax.random.PRNGKey(seed + 1)
    xw, tw = partition_workers(data.x_train, data.t_train, workers)
    _log(f"mesh deployment: M={workers} J_m={xw.shape[2]} L={layers} "
         f"n={cfg.n} K={cfg.admm_iters} trace_every=0 (the hot path)")
    rounds = Ring(1).rounds_for_tolerance(workers, 1e-6)
    for policy in ("exact", f"gossip:{rounds}@ring:1"):
        runs = {}
        for kind in ("mesh", "simulated"):
            spec = dssfn.TrainSpec(cfg=cfg, backend=kind, workers=workers,
                                   policy=policy, trace_every=0)
            runs[kind] = run = timed_train(spec, xw, tw, key)
            check_finite(run["result"].params, f"{kind} {policy}")
            acc = dssfn.evaluate(run["result"], data.x_test, data.y_test)
            run["acc"] = acc
            _log(f"mesh {policy} {run['result'].backend.describe()}: "
                 f"cold={run['cold_s']:.3f}s warm={run['warm_s']:.3f}s "
                 f"test_acc={acc:.4f} cache={run['cache']}")
        mesh = runs["mesh"]["result"]
        check_gaps(f"mesh {policy}: mesh vs simulated", mesh.params,
                   runs["simulated"]["result"].params, MESH_GAP_BOUND,
                   LAYER0_GAP_BOUND)
        require(abs(runs["mesh"]["acc"] - runs["simulated"]["acc"])
                <= ACCURACY_BOUND, "sim-vs-mesh accuracy")

        # Collectives in the compiled layer program (layers >= 2; the same
        # cache entry the run used) beside the policy's declared
        # exchanges: the hot path holds nothing else.
        backend = mesh.backend
        entries = backend.cache_info()["entries"]
        y = backend.shard_workers(xw)
        prog = engine.layer_program(
            backend, backend.shard_workers(
                np.zeros((workers, cfg.n, xw.shape[2]), np.float32)),
            backend.shard_workers(tw), np.zeros((cfg.n, cfg.n), np.float32),
            mu=cfg.mul, eps_radius=cfg.eps_radius, num_iters=cfg.admm_iters,
            donate_y=True, policy=mesh.policy, trace_every=0,
        )
        counts = prog.lowering_stats()["collective_counts"]
        per_mix = analysis.expected_mix_collectives(mesh.policy, workers)
        expected = {op: cfg.admm_iters * c for op, c in per_mix.items()}
        _log(f"mesh {policy}: compiled collectives {counts}, declared "
             f"{per_mix} per mix x K={cfg.admm_iters} = {expected}")
        require(counts == expected, "compiled collectives differ from declared")
        require(backend.cache_info()["entries"] == entries,
                "the counted program is not the one the run executed")

        # Each worker's shard on its own device, inputs and results alike.
        step = engine.fused_layer_step(
            backend, y, backend.shard_workers(tw), None, mu=cfg.mu0,
            eps_radius=cfg.eps_radius, num_iters=cfg.admm_iters,
            policy=mesh.policy, trace_every=0,
        )
        for name, arr in (("x_workers", y), ("o_workers", step.o_workers)):
            placed = sorted(
                (s.index[0].start, s.device.id) for s in arr.addressable_shards
            )
            _log(f"mesh {policy}: {name} (worker, device id) {placed}")
            require([w for w, _ in placed] == list(range(workers))
                    and len({d for _, d in placed}) == workers,
                    f"{name} is not one worker per device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: paper, kernel and serving phases; 4: the mesh "
                    "phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = check_platform(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache "
         f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(args.seed)
    else:
        paper = paper_phase(args.seed)
        kern = kernel_phase(args.seed)
        serve_phase(paper, kern)
    _log(f"total wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
