#!/usr/bin/env python3
"""Upper readings for a cell's limits: the control and the planted faults,
read at the cell's own size on one chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11,12,13

The benchmark's runs do not run this.  For each seed it makes the cell's
data (or weights) from the seed, computes the plain reference as the run
does, and reads the numbers ``correct`` compares for what stands in the
program's place:

- ``control``: the reference with every array in bfloat16, the nearest
  precision below the configuration's float32 arrays (``reference.py``,
  ``low``);
- training faults: ``half_batch`` (each worker's second half of samples
  left out, the first half counted twice, i.e. the mean over the rest),
  ``no_exchange`` (the consensus step mixes nothing: identity instead of
  the gossip matrix), ``altered`` (the final readout produced with two
  classes' rows swapped); a state left unchanged reads a gap of exactly
  1 at every layer and needs no run;
- serving faults: ``altered`` (two classes' logits swapped in the
  largest request's answer) and ``half_batch`` (half of each request's
  columns left out and given the mean of the rest).

For serving it also reads the program itself: a window of
``SERVE_SECONDS`` at the cell's own load, through the run's own code,
whose sample of requests is the one the control and the faults answer.

It prints one line per seed and variant, and last a JSON object with
every reading.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import data, harness, reference  # noqa: E402

#: The serving window whose answers stand beside the control's: long
#: enough for a few thousand requests, more than a sample draws.
SERVE_SECONDS = 4


def train_readings(cell: harness.Cell, seed: int) -> dict:
    import jax.numpy as jnp

    from benchmarks.chip.generators import train as drv

    cfg = cell.config
    xw, tw, x_test, y_test = drv.make_data(cfg, seed)
    mix, _ = drv.mixing(cfg, cell.traffic)
    key = data.seed_key(seed, 2)
    ref_o, rmats = drv.reference_train(cfg, xw, tw, key, mix)

    def read(got):
        return drv.compare(cfg, [jnp.asarray(o) for o in got], ref_o, rmats,
                           x_test, y_test)

    out = {"reference": read(ref_o)}
    out["control"] = read(drv.reference_train(cfg, xw, tw, key, mix, low=jnp.bfloat16)[0])
    half = xw.shape[2] // 2
    xh = jnp.concatenate([xw[:, :, :half]] * 2, axis=2)
    th = jnp.concatenate([tw[:, :, :half]] * 2, axis=2)
    out["half_batch"] = read(drv.reference_train(cfg, xh, th, key, mix)[0])
    eye = jnp.eye(cfg["workers"], dtype=jnp.float32)
    out["no_exchange"] = read(drv.reference_train(cfg, xw, tw, key, eye)[0])
    swapped = list(ref_o)
    swapped[-1] = ref_o[-1][jnp.array([1, 0] + list(range(2, cfg["num_classes"])))]
    out["altered"] = read(swapped)
    return out


def serve_readings(cell: harness.Cell, seed: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip.generators import serve as drv

    cfg, traffic = cell.config, cell.traffic
    program = drv.run(cell, seed=seed, seconds=SERVE_SECONDS, trace_dir=None,
                      t_start=time.perf_counter())
    out = {"program": dict(program.compared, failed=program.failed)}
    # The requests that window sent and the sample its comparison drew.
    pool = drv.make_pool(cfg, traffic, seed)
    readouts, rmats = drv.make_weights(cfg, seed)
    _, sizes = drv.schedule(seed, traffic, SERVE_SECONDS)
    requests = drv.requests_of(pool, sizes, traffic["max_request"])
    sample = drv.check_sample(seed, list(range(len(sizes))), sizes)
    xs = [requests[i] for i in sample]
    x = jnp.asarray(np.concatenate(xs, axis=1))
    ops = reference.operand_dtype(cfg["matmul_operands"])
    ref = np.asarray(reference.forward(readouts, rmats, x, operands=ops))

    def read(got):
        return drv.compare(cfg, readouts, rmats, xs, got)

    out["control"] = read(np.asarray(reference.forward(
        readouts, rmats, x, operands=ops, low=jnp.bfloat16)))
    ends = np.cumsum([r.shape[1] for r in xs])
    largest = max(range(len(xs)), key=lambda i: xs[i].shape[1])
    altered = ref.copy()
    cols = slice(ends[largest] - xs[largest].shape[1], ends[largest])
    altered[[0, 1], cols] = ref[[1, 0], cols]
    out["altered"] = read(altered)
    half = ref.copy()
    for end, r in zip(ends, xs):
        width = r.shape[1]
        if width > 1:
            first = slice(end - width, end - width + width // 2)
            half[:, end - width + width // 2:end] = ref[:, first].mean(axis=1, keepdims=True)
    out["half_batch"] = read(half)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from benchmarks.chip.run import enable_compile_cache, find_chips

    find_chips(1)
    enable_compile_cache()
    readings = {}
    reader = train_readings if cell.traffic["kind"] == "train" else serve_readings
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        readings[seed] = reader(cell, seed)
        for variant, numbers in readings[seed].items():
            shown = {k: v for k, v in numbers.items() if not k.startswith("gap_l")}
            harness.log(f"seed {seed} {variant}: {shown}")
        harness.log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"workload": args.workload, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
