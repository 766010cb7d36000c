"""Closed-loop training traffic: back-to-back warm ``dssfn.train`` calls.

One train is the whole ``repro.dssfn.train`` of layers 0..L, ending in
``block_until_ready`` on its readouts; the next starts when it ends.
Every train of the window goes through the backend that set-up built and
warmed, with a key folded from the seed and the train's index, on the
data that set-up made from the seed.

Traffic keys: ``policy`` (``gossip``: B rounds on the configuration's
degree-d ring, B the fewest that bring the mix within ``tolerance`` of
the exact mean; ``exact``: the exact mean), ``trace_every`` (the
program's convergence-trace stride; 0 is its hot path).
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import replace

import numpy as np

from benchmarks.chip import data, harness, peaks, reference, tracing, work

TRAFFIC_KEYS = ("policy", "tolerance", "trace_every")
#: Depth of the warm-up train.  Layers 0, 1 and >= 2 are the only distinct
#: layer programs (layer 1's weight reads the input width, deeper ones the
#: hidden width), and their cache key leaves out the depth, so two layers
#: past the first compile every program a train of any depth runs.
WARMUP_LAYERS = 2
#: How many trains a ``--trace 1`` run traces.
TRACE_TRAINS = 2


def mixing(cfg: dict, traffic: dict) -> tuple[np.ndarray, str]:
    """The (M, M) consensus matrix and the program's policy spec."""
    m = cfg["workers"]
    if traffic["policy"] == "exact":
        return np.full((m, m), 1.0 / m, np.float32), "exact"
    if traffic["policy"] == "gossip":
        mix, rounds = reference.gossip_matrix(m, cfg["ring_degree"],
                                              traffic["tolerance"])
        return mix, f"gossip:{rounds}@ring:{cfg['ring_degree']}"
    raise harness.BenchError(f"unknown training policy {traffic['policy']!r}")


def model_config(cfg: dict):
    from repro.core import ssfn

    return ssfn.SSFNConfig(
        input_dim=cfg["input_dim"], num_classes=cfg["num_classes"],
        num_layers=cfg["num_layers"], hidden=cfg["hidden"], mu0=cfg["mu0"],
        mul=cfg["mul"], admm_iters=cfg["admm_iters"],
        eps_scale=cfg["eps_scale"],
    )


def make_data(cfg: dict, seed: int):
    return data.make_dataset(
        data.seed_key(seed, 0), num_train=cfg["num_train"],
        num_test=cfg["num_test"], input_dim=cfg["input_dim"],
        num_classes=cfg["num_classes"], workers=cfg["workers"],
    )


class Trainer:
    """The program under test, built and warmed once: its backend (with
    the compiled layer programs) and the data placed where it runs."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, xw, tw):
        import jax

        from repro import dssfn
        from repro.core import ssfn

        self.mix, policy = mixing(cfg, traffic)
        model = model_config(cfg)
        spec = dssfn.TrainSpec(
            cfg=replace(model, num_layers=min(WARMUP_LAYERS, model.num_layers)),
            backend=cfg["backend"], workers=cfg["workers"], policy=policy,
            trace_every=traffic["trace_every"],
        )
        self.backend = spec.resolve_backend()
        self.xw = self.backend.shard_workers(xw)
        self.tw = self.backend.shard_workers(tw)
        spec = replace(spec, backend=self.backend)
        warm = dssfn.train(spec, self.xw, self.tw, data.seed_key(seed, 1))
        jax.block_until_ready(warm.params.o)
        # The train's own prologue draws R_1..R_L for the full depth.
        jax.block_until_ready(ssfn.init_random_matrices(data.seed_key(seed, 1), model))
        self.spec = replace(spec, cfg=model)
        self.train_key = data.seed_key(seed, 2)

    def key(self, index: int):
        import jax

        return jax.random.fold_in(self.train_key, index)

    def train(self, index: int):
        """One whole train; returns its readouts O_0..O_L once computed."""
        import jax

        from repro import dssfn

        res = dssfn.train(self.spec, self.xw, self.tw, self.key(index))
        jax.block_until_ready(res.params.o)
        return res.params.o


def compare(cfg: dict, prog_o, ref_o, rmats, x_test, y_test) -> dict:
    """The numbers a train is judged by: the readout gap of every layer,
    their maximum (NaN where any layer's is), and the gap in test accuracy
    between the program's readouts and the reference's, both through the
    reference's forward."""
    import jax.numpy as jnp

    ops = reference.operand_dtype(cfg["matmul_operands"])
    gaps = reference.readout_gaps(prog_o, ref_o)
    acc = [
        float(jnp.mean(jnp.argmax(reference.forward(list(o), rmats, x_test,
                                                    operands=ops), axis=0)
                       == y_test))
        for o in (prog_o, ref_o)
    ]
    out = {f"gap_l{i}": g for i, g in enumerate(gaps)}
    out["gap_max"] = float(np.max(gaps))
    out["accuracy_gap"] = abs(acc[0] - acc[1])
    out["accuracy"] = acc[0]
    return out


def reference_train(cfg: dict, xw, tw, key, mix, **kw):
    """The reference's readouts on one chip, from the benchmark's data."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    ops = reference.operand_dtype(cfg["matmul_operands"])
    return reference.train(jax.device_put(xw, dev), jax.device_put(tw, dev),
                           key, cfg, mix, operands=ops, **kw)


def run(cell: harness.Cell, *, seed: int, seconds: int, trace_dir,
        t_start: float) -> harness.Outcome:
    import jax

    cfg, traffic = cell.config, cell.traffic
    devices = jax.devices()[:cell.chips]
    compiles = harness.CompileCounter()
    xw, tw, x_test, y_test = make_data(cfg, seed)
    trainer = Trainer(cfg, traffic, seed, xw, tw)

    lowerings0 = trainer.backend.cache_info()["lowerings"]
    compiles0 = compiles.snapshot()
    readouts = []
    harness.settle()
    tracer = tracing.capture(str(trace_dir)) if trace_dir else nullcontext()
    with tracer, tracing.span(tracing.WINDOW_SPAN):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        while True:
            with tracing.span("bench.train"):
                readouts.append(trainer.train(len(readouts)))
            elapsed = time.perf_counter() - t0
            if trace_dir is not None and len(readouts) >= TRACE_TRAINS:
                break
            if trace_dir is None and elapsed >= seconds:
                break
    trains = len(readouts)
    lowerings = trainer.backend.cache_info()["lowerings"] - lowerings0
    traced, built = (a - b for a, b in zip(compiles.snapshot(), compiles0))
    harness.log(f"window: {trains} trains in {elapsed:.6f} s; layer-program "
                f"lowerings {lowerings}, jaxpr traces {traced}, backend "
                f"compiles {built}")
    memory = harness.memory_peak_bytes(devices)

    sizes = work.Sizes.from_config(cfg)
    outcome = harness.Outcome(
        attempted=trains, failed=0,
        values={"setup_s": setup_s, "train_s": elapsed / trains},
        compared={}, memory_peak_bytes=memory,
    )
    if trace_dir is not None:
        trace = tracing.load(str(trace_dir))
        lo, hi = trace.window
        programs = [
            tracing.layer_programs(chip, trace.spans("bench.train"),
                                   scan_repeats=cfg["admm_iters"],
                                   sample_dim=sizes.per_worker)
            for chip in trace.chips
        ]
        outcome.readings = harness.Readings(
            trace=trace, window=(lo, hi), peak=peaks.peak_for(devices[0].device_kind),
            counters={"trains": trains, "sizes": sizes, "programs": programs,
                      "chips": cell.chips},
        )
        outcome.window_s = (hi - lo) * 1e-9
        outcome.busy_s = sum(tracing.busy_s(c, lo, hi)
                             for c in trace.chips) / len(trace.chips)
        outcome.breakdown = {
            "device_ops": tracing.top_device_ops(
                trace, lo, hi, scans=[[(p.scan.start, p.scan.end) for p in progs]
                                      for progs in programs]),
            "idle_gaps": tracing.top_idle_gaps(trace, lo, hi),
        }

    # Judge one train of the window, drawn from the seed, against the
    # reference, once the program's state is freed.
    pick = int(np.random.default_rng(seed & (2 ** 64 - 1)).integers(trains))
    prog_o = [np.asarray(o) for o in readouts[pick]]
    key = trainer.key(pick)
    xw, tw, mix = trainer.xw, trainer.tw, trainer.mix
    del readouts, trainer
    ref_o, rmats = reference_train(cfg, xw, tw, key, mix)
    outcome.compared = compare(cfg, prog_o, ref_o, rmats, x_test, y_test)
    harness.log(f"checked train {pick} of {trains}: readout gap per layer "
                + " ".join(f"{outcome.compared[f'gap_l{i}']:.3e}"
                           for i in range(len(prog_o)))
                + f"; test accuracy {outcome.compared['accuracy']:.4f}")
    return outcome
