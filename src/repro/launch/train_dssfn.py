"""Distributed dSSFN training launcher: the paper's Algorithm 1 on a real
``workers`` mesh.

Runs layer-wise consensus-ADMM training through a ``ConsensusBackend``:

- ``--backend mesh``       one ADMM worker per mesh device slot (SPMD via
                           shard_map; per-worker data shards device-local)
- ``--backend simulated``  the vmap worker-axis simulation on one device
- ``--backend both``       run both and report their parity — the
                           mesh-native form of the paper's centralized-
                           equivalence experiment

Consensus is a pluggable policy (``repro.core.policy``), selected by
spec string::

    --consensus exact           one all-reduce (the default)
    --consensus gossip:10:2     10 rounds of degree-2 ring gossip
    --consensus quantized:4     4-bit stochastically-quantized links
    --consensus lossy:0.1       ring gossip with 10% link drops
    --consensus stale:2         peers see 2-rounds-stale values

Byzantine-resilient policies pair a robust aggregator with a seeded
attack injected into the transmitted payload (README "Byzantine
resilience & numerical self-healing")::

    --consensus trimmed:f=1:attack=signflip@torus:2x4
    --consensus median:byz=3:attack=nanbomb
    --consensus clipped:tau=0.5:attack=scale:10

``--guard-divergence`` adds the numerical self-healing layer on top:
a diverging layer solve rolls back to the last complete checkpoint
with a perturbed RNG key (pair it with ``--checkpoint-dir``).

(``--consensus gossip`` with no args keeps honouring the legacy
``--degree``/``--rounds`` flags.)

Wire efficiency (see README "Performance guide")::

    --wire-dtype bf16    16-bit link payloads, f32 accumulation (halves
                         eq.-15 bytes for every gossip-family policy)
    --trace-every 0      drop the per-iteration trace collectives — the
                         lowered program runs ONLY the policy's own
                         exchanges (0 = hot path, N>1 = subsample)
    --no-compress        B serial gossip rounds instead of the default
                         ONE compressed H^B schedule (bit-exact legacy)

The communication graph is a first-class axis (``repro.core.topology``)::

    --topology ring:2           the paper's degree-2 circular graph
    --topology torus:2x4        2x4 wraparound grid (ICI-mesh native)
    --topology hypercube        log2(M)-dimensional hypercube
    --topology geometric:0.5    random geometric graph, Metropolis weights
    --topology full             complete graph (one round == exact mean)
    --topology ring:1+hypercube time-varying: alternate per round

With the default ``--consensus exact`` a ``--topology`` implies gossip
over that graph (``--rounds`` rounds); with an explicit gossip-family
policy it swaps that policy's graph.  ``--partition iid|noniid[:alpha]``
controls worker-shard label skew, so topology sweeps can run against
non-IID shards (centralized equivalence is distribution-free).

Under ``JAX_PLATFORMS=cpu`` the mesh is faked with XLA host devices:
the launcher sets ``XLA_FLAGS=--xla_force_host_platform_device_count=M``
BEFORE jax initializes (which is why every jax import in this module is
deferred).  On TPU the worker slots are real chips and gossip-family
policies map each degree-k hop onto an ICI collective_permute; the mesh
backend needs one chip per worker, so a one-chip host runs the paper's
deployment as ``--backend simulated --workers 20 --no-host-mesh``.

Usage::

    JAX_PLATFORMS=cpu python -m repro.launch.train_dssfn --workers 8 \
        --backend both
    python -m repro.launch.train_dssfn --workers 8 --consensus gossip \
        --degree 2 --rounds 10
    python -m repro.launch.train_dssfn --workers 8 --backend mesh \
        --consensus quantized:8
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workers", type=int, default=8, help="M, ADMM workers")
    ap.add_argument(
        "--backend", default="both", choices=["simulated", "mesh", "both"]
    )
    ap.add_argument(
        "--consensus",
        default="exact",
        help="consensus spec (dssfn.parse_spec grammar): exact | "
        "gossip[:B[:d]] | quantized:bits | lossy:p[:B[:d]] | stale:delay "
        "| async[:key=value...] | trimmed[:f=F] | median | clipped:tau, "
        "each optionally '@topology'; robust policies take fault keys "
        "(byz=i, attack=signflip|scale:c|noise:s|nanbomb|replay:d), e.g. "
        "trimmed:f=1:attack=signflip@torus:2x4",
    )
    ap.add_argument(
        "--topology",
        default=None,
        help="communication graph for gossip-family policies: ring[:d] | "
        "torus:RxC | hypercube | geometric:r[:seed] | full "
        "('+'-joined specs cycle round-by-round).  With the default "
        "--consensus exact this implies gossip over the graph "
        "(--rounds rounds).",
    )
    ap.add_argument(
        "--partition",
        default="iid",
        help="worker data partition: iid | noniid[:alpha] (alpha in (0,1] "
        "= label-skew fraction per shard)",
    )
    # default=None so build_policy can tell an explicit --degree from the
    # implicit 2 and reject the --degree + --topology combination instead
    # of silently ignoring one of them.
    ap.add_argument(
        "--degree", type=int, default=None,
        help="gossip ring degree d (default 2; incompatible with --topology)",
    )
    ap.add_argument("--rounds", type=int, default=10, help="gossip rounds B")
    ap.add_argument(
        "--wire-dtype",
        default=None,
        choices=["float32", "bfloat16", "float16", "f32", "bf16", "f16"],
        help="link payload width for gossip-family policies: messages are "
        "cast once before the wire and accumulated in f32 (halves eq.-15 "
        "bytes at 16-bit widths); default keeps the policy's own wire",
    )
    ap.add_argument(
        "--no-compress",
        action="store_true",
        help="run gossip rounds as B serial exchange schedules instead of "
        "the default ONE compressed H^B schedule (power_schedule)",
    )
    ap.add_argument(
        "--trace-every",
        type=int,
        default=1,
        help="ADMM convergence-trace stride: 1 traces every iteration "
        "(default), 0 disables traces AND their psum/pmax collectives "
        "(the production hot path), N>1 traces every N-th iteration",
    )
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--admm-iters", type=int, default=100)
    ap.add_argument("--classes", type=int, default=6)
    ap.add_argument("--input-dim", type=int, default=16)
    ap.add_argument("--train", type=int, default=960)
    ap.add_argument("--test", type=int, default=240)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--use-kernels",
        action="store_true",
        help="route propagation/Gram through the Pallas kernels "
        "(matmul_relu, gram, fused propagate_gram); needs 128-aligned "
        "--hidden/--input-dim and per-worker sample counts, else each "
        "misaligned op falls back to the einsum path",
    )
    ap.add_argument(
        "--membership",
        default=None,
        help="active-worker slot mask as a 1/0 string (e.g. 11011101): "
        "masks the consensus graph to the active workers (elastic "
        "membership; inactive slots keep identity mixing rows)",
    )
    ap.add_argument(
        "--checkpoint-dir",
        default=None,
        help="directory for elastic-resume checkpoints (state saved after "
        "each --checkpoint-every layers); default: no checkpointing",
    )
    ap.add_argument(
        "--checkpoint-every",
        type=int,
        default=1,
        help="checkpoint after every N completed layers (with "
        "--checkpoint-dir)",
    )
    ap.add_argument(
        "--resume",
        action="store_true",
        help="restore the latest --checkpoint-dir checkpoint and continue "
        "from its next layer (bit-exact vs the uninterrupted run)",
    )
    ap.add_argument(
        "--stop-after-layer",
        type=int,
        default=None,
        help="complete this layer index, checkpoint, and exit (the crash "
        "half of a kill/resume drill)",
    )
    ap.add_argument(
        "--guard-divergence",
        action="store_true",
        help="monitor each layer solve for divergence (non-finite or "
        "exploding objective) and roll back to the last complete "
        "checkpoint with a perturbed RNG key instead of training on",
    )
    ap.add_argument(
        "--max-rollbacks",
        type=int,
        default=2,
        help="divergence-rollback budget before the run raises "
        "(with --guard-divergence)",
    )
    ap.add_argument(
        "--export-artifact",
        default=None,
        metavar="PATH",
        help="after training, export the trained stack as a serving "
        "artifact directory (repro.serve.export_artifact); with "
        "--backend both the simulated run is exported (centralized "
        "equivalence makes the choice immaterial)",
    )
    ap.add_argument(
        "--export-features",
        default=None,
        help="frozen feature-extractor spec recorded in the exported "
        "artifact (identity | rff:D[:seed] | relu:D[:seed]); the engine "
        "applies it to raw requests before the stack — only meaningful "
        "when training ran on pre-extracted features",
    )
    ap.add_argument("--out", default=None, help="optional JSON results path")
    ap.add_argument(
        "--no-host-mesh",
        action="store_true",
        help="never fake CPU devices (use whatever devices exist)",
    )
    return ap.parse_args(argv)


def ensure_devices(num_workers: int, *, allow_fake: bool = True) -> None:
    """Fake an M-device CPU host mesh when the run is pinned to the CPU.

    Only under ``JAX_PLATFORMS=cpu``: anywhere else the flag would let a
    host whose accelerator failed to initialize fall back to fake CPU
    devices and train on them quietly.  XLA reads the flag at first
    backend initialization, so this works as long as no
    ``jax.devices()``/computation has run yet — hence the deferred jax
    imports throughout this module.  No-op when the flag is already set.
    """
    if not allow_fake or os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={num_workers}".strip()
        )


def report_devices() -> None:
    """Print the devices the run will use; say so in words when JAX fell
    back to the CPU without being asked to."""
    import jax

    devices = jax.devices()
    print(
        f"devices: {len(devices)} ({devices[0].platform}, "
        f"{devices[0].device_kind})",
        flush=True,
    )
    if devices[0].platform == "cpu" and not os.environ.get("JAX_PLATFORMS"):
        print(
            "warning: JAX found no accelerator and runs on the CPU; set "
            "JAX_PLATFORMS=cpu to run there on purpose (with a fake host "
            "mesh for --backend mesh)",
            file=sys.stderr,
            flush=True,
        )


def build_policy(args):
    """--consensus + --topology -> ConsensusPolicy via the unified
    ``dssfn.parse_spec`` grammar.  The legacy --degree/--rounds flags
    fill any segment the spec leaves out (so ``gossip`` and ``lossy:0.1``
    both honour them); --topology (or the spec's own ``@graph`` half)
    swaps the gossip-family graph, and with the default ``--consensus
    exact`` it implies ``gossip`` over that graph."""
    from repro.dssfn import parse_spec
    from repro.core.policy import parse_policy
    from repro.core.topology import parse_topology

    consensus, sep, spec_topo = args.consensus.partition("@")
    if sep and args.topology:
        raise ValueError(
            f"--consensus {args.consensus!r} already names an '@topology'; "
            "drop --topology"
        )
    topo_spec = spec_topo if sep else args.topology
    topo = parse_topology(topo_spec) if topo_spec else None
    if topo is not None and args.degree is not None:
        raise ValueError(
            "--degree configures the default ring; pass either --degree or "
            "--topology (ring degree spells ring:d), not both"
        )
    if topo is not None and consensus == "exact":
        consensus = "gossip"
    kw = dict(
        degree=args.degree if args.degree is not None else 2,
        rounds=args.rounds,
    )
    if sep:
        policy = parse_spec(f"{consensus}@{spec_topo}", **kw)
    else:
        policy = parse_policy(consensus, topology=topo, **kw)
    if getattr(args, "no_compress", False):
        from dataclasses import fields, replace

        if any(f.name == "compress" for f in fields(policy)):
            policy = replace(policy, compress=False)
    return policy


def train_one(kind: str, args, data, xw, tw, cfg, key) -> dict:
    import jax

    from repro import dssfn
    from repro.core import layerwise

    ckpt_dir = args.checkpoint_dir
    if ckpt_dir is not None and args.backend == "both":
        # Parallel simulated/mesh runs must not clobber each other's state.
        ckpt_dir = os.path.join(ckpt_dir, kind)
    spec = dssfn.TrainSpec(
        cfg=cfg, backend=kind, workers=args.workers, policy=build_policy(args),
        wire_dtype=args.wire_dtype, trace_every=args.trace_every,
        membership=args.membership,
        checkpoint_dir=ckpt_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
        stop_after_layer=args.stop_after_layer,
        guard_divergence=args.guard_divergence,
        max_rollbacks=args.max_rollbacks,
    )
    t0 = time.perf_counter()
    result = dssfn.train(spec, xw, tw, key)
    params, log, backend = result.params, result.log, result.backend
    jax.block_until_ready(params.o[-1])
    wall = time.perf_counter() - t0
    acc = layerwise.accuracy(params, data.x_test, data.y_test, cfg.num_classes)
    return {
        "backend": backend.describe(),
        "kind": kind,
        "policy": result.policy.describe(),
        "wire_bits": result.policy.wire_bits,
        "trace_every": args.trace_every,
        "wall_time_s": wall,
        "test_accuracy": acc,
        # trace_every=0 runs collective-free: no objective to report.
        "final_objective": log.layer_costs[-1] if log.layer_costs else None,
        "comm_scalars": log.comm_scalars,
        # Self-healing telemetry: guarded-Cholesky jitter escalations and
        # divergence rollbacks taken (README "Byzantine resilience").
        "jitter_events": int((log.jitter_levels > 0).sum()),
        "rollbacks": log.rollbacks,
        # Compile-once layer engine: lowerings == distinct layer shapes,
        # not layer solves (the compile-count regression test's invariant).
        "executable_cache": backend.cache_info(),
        "params": params,
    }


def main(argv=None) -> dict:
    args = parse_args(argv)
    ensure_devices(args.workers, allow_fake=not args.no_host_mesh)

    import jax
    import jax.numpy as jnp

    from repro.core import ssfn
    from repro.data import make_classification, partition_by_spec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    report_devices()

    data = make_classification(
        jax.random.PRNGKey(args.seed),
        num_train=args.train,
        num_test=args.test,
        input_dim=args.input_dim,
        num_classes=args.classes,
    )
    xw, tw = partition_by_spec(
        data.x_train, data.t_train, args.workers, args.partition
    )
    cfg = ssfn.SSFNConfig(
        input_dim=args.input_dim,
        num_classes=args.classes,
        num_layers=args.layers,
        hidden=args.hidden,
        admm_iters=args.admm_iters,
        use_kernels=args.use_kernels,
    )
    key = jax.random.PRNGKey(args.seed + 1)

    kinds = ["simulated", "mesh"] if args.backend == "both" else [args.backend]
    results: dict = {"config": vars(args), "runs": []}
    # Predicted mixing behaviour of the selected graph (paper §III):
    # what BENCH_mesh.json's "topologies" section measures end to end.
    policy = build_policy(args)
    topo = getattr(policy, "topology", None)
    if topo is not None:
        results["topology"] = {
            "spec": topo.describe(),
            "spectral_gap": topo.spectral_gap(args.workers),
            "edges_per_node": topo.edges_per_node(args.workers),
            "rounds_for_tolerance_1e6": topo.rounds_for_tolerance(
                args.workers, 1e-6
            ),
        }
        print(
            f"topology {topo.describe()}: gap="
            f"{results['topology']['spectral_gap']:.3f} "
            f"edges/node={results['topology']['edges_per_node']} "
            f"B*(1e-6)={results['topology']['rounds_for_tolerance_1e6']}",
            flush=True,
        )
    params_by_kind = {}
    for kind in kinds:
        run = train_one(kind, args, data, xw, tw, cfg, key)
        params_by_kind[kind] = run.pop("params")
        results["runs"].append(run)
        obj = run["final_objective"]
        obj_str = f"{obj:.4f}" if obj is not None else "n/a (trace_every=0)"
        print(
            f"{run['backend']}: wall={run['wall_time_s']:.2f}s "
            f"acc={run['test_accuracy']:.3f} obj={obj_str} "
            f"comm={run['comm_scalars']} scalars",
            flush=True,
        )

    if len(kinds) == 2:
        gaps = [
            float(
                jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(a), 1e-30)
            )
            for a, b in zip(
                params_by_kind["simulated"].o, params_by_kind["mesh"].o
            )
        ]
        objs = [r["final_objective"] for r in results["runs"]]
        results["parity"] = {"max_readout_rel_gap": max(gaps)}
        if None not in objs:  # trace_every=0 has no objective to compare
            results["parity"]["rel_objective_gap"] = abs(objs[0] - objs[1]) / max(
                abs(objs[0]), 1e-30
            )
        obj_str = (
            f"{results['parity']['rel_objective_gap']:.2e}"
            if "rel_objective_gap" in results["parity"] else "n/a"
        )
        print(
            f"parity simulated-vs-mesh: max readout gap={max(gaps):.2e}, "
            f"objective gap={obj_str}",
            flush=True,
        )

    if args.export_artifact:
        from repro.serve import export_artifact

        source_kind = kinds[0]
        params = params_by_kind[source_kind]
        export_artifact(
            args.export_artifact,
            params,
            features=args.export_features,
            source={
                "trained_by": "repro.launch.train_dssfn",
                "backend": source_kind,
                "consensus": args.consensus,
                "workers": args.workers,
                "seed": args.seed,
            },
        )
        results["export"] = {
            "path": args.export_artifact,
            "source_kind": source_kind,
            "num_layers": len(params.o) - 1,
        }
        print(
            f"exported serving artifact -> {args.export_artifact} "
            f"(from {source_kind} run, {len(params.o) - 1} layers)",
            flush=True,
        )

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    return results


if __name__ == "__main__":
    main()
