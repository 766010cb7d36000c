#!/usr/bin/env python3
"""Record the two small traces ``SCOPED.txt`` describes, on one chip.

    python3 benchmarks/chip/testdata/record_scoped.py [--out DIR]

Writes ``scoped_train.xplane.pb`` (one warm tiny ``dssfn.train``) and
``scoped_serve.xplane.pb`` (two seconds of open-loop serving of a tiny
stack) into ``DIR`` (default ``chiprun_out/scoped``), each traced as the
benchmark's generators trace their windows, so that the per-layer
readers of the program's scopes and spans can be tested on them.
"""
import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for path in (ROOT, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from benchmarks.chip import tracing  # noqa: E402
from benchmarks.chip.generators import serve, train  # noqa: E402

SEED = 20261017
#: The tiny train: 4 simulated workers, n=128, J_m=256, L=2, K=4.
TRAIN_CONFIG = {
    "input_dim": 784, "num_classes": 10, "num_train": 1024, "num_test": 256,
    "num_layers": 2, "hidden": 128, "admm_iters": 4, "mu0": 1e-3, "mul": 1.0,
    "eps_scale": 1.0, "workers": 4, "ring_degree": 1, "backend": "simulated",
    "matmul_operands": "bfloat16",
}
TRAIN_TRAFFIC = {"policy": "gossip", "tolerance": 1e-6, "trace_every": 0}
#: The tiny served stack: L=2, n=128, the serving cell's runtime settings
#: at 100 requests/s.
SERVE_CONFIG = {"input_dim": 784, "num_classes": 10, "num_test": 512,
                "num_layers": 2, "hidden": 128, "eps_scale": 1.0}
SERVE_TRAFFIC = {"rate_per_s": 100, "zipf_a": 1.5, "max_request": 32,
                 "buckets": [1, 8, 32], "max_pending_samples": 256,
                 "deadline_s": 0.05, "flush_interval_s": 0.001}
SERVE_SECONDS = 2


def record_train(trace_dir: str) -> None:
    xw, tw, _, _ = train.make_data(TRAIN_CONFIG, SEED)
    trainer = train.Trainer(TRAIN_CONFIG, TRAIN_TRAFFIC, SEED, xw, tw)
    with tracing.capture(trace_dir), tracing.span(tracing.WINDOW_SPAN):
        with tracing.span("bench.train"):
            trainer.train(0)


def record_serve(trace_dir: str) -> None:
    pool = serve.make_pool(SERVE_CONFIG, SERVE_TRAFFIC, SEED)
    engine, _, _ = serve.make_engine(SERVE_CONFIG, SERVE_TRAFFIC, SEED)
    serve.warm(engine, SERVE_TRAFFIC, pool)
    gaps, sizes = serve.schedule(SEED, SERVE_TRAFFIC, SERVE_SECONDS)
    requests = serve.requests_of(pool, sizes, SERVE_TRAFFIC["max_request"])
    rt = serve.runtime(engine, SERVE_TRAFFIC)
    with tracing.capture(trace_dir):
        handles, *_ = serve.open_loop(rt, requests, gaps)
    print(f"serve: {sum(h.ok() for h in handles)} of {len(handles)} requests answered")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "scoped"))
    out = Path(ap.parse_args().out)
    out.mkdir(parents=True, exist_ok=True)
    for name, record in (("train", record_train), ("serve", record_serve)):
        with tempfile.TemporaryDirectory() as tmp:
            t0 = time.perf_counter()
            record(tmp)
            dest = out / f"scoped_{name}.xplane.pb"
            shutil.copyfile(tracing.latest_xplane(tmp), dest)
        print(f"{dest}: {dest.stat().st_size} bytes, {time.perf_counter() - t0:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
