#!/usr/bin/env python3
"""Find the rate a document-serving cell sustains: one open-loop window
per rate, all in one process on one chip.

    python3 benchmarks/chip/sweep_docs.py --workload <cell> --seed 1 \\
        --seconds 20 --rates 4,6,8

The benchmark's runs do not run this; the traffic file records the rate
chosen from a sweep (``PERF.md`` keeps the sweep).  The knee is the
highest rate whose tail stays within the traffic's deadline with
nothing shed and no queue that grows through the window (the last tenth
of the requests waits no longer than the first).
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from benchmarks.chip.generators import serve, serve_docs
    from benchmarks.chip.run import enable_compile_cache, find_chips, import_program

    find_chips(1)
    import_program()
    enable_compile_cache()
    import numpy as np

    cfg, traffic = cell.config, cell.traffic
    t0 = time.perf_counter()
    engine, _, _ = serve_docs.make_engine(cfg, traffic, args.seed)
    serve_docs.warm(engine, traffic, cfg["vocab_size"])
    harness.log(f"set-up {time.perf_counter() - t0:.1f} s")
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(traffic, rate_per_s=rate)
        gaps, sizes, lengths = serve_docs.schedule(mix, args.seconds)
        requests = serve_docs.make_requests(args.seed, sizes, lengths, cfg["vocab_size"])
        rt = serve_docs.runtime(engine, mix)
        handles, due, late, t_open, _ = serve.open_loop(rt, requests, gaps)
        window_s = max([due[-1]] + [h.completed_at for h in handles if h.done()]) - t_open
        lat = [h.completed_at - d for h, d in zip(handles, due) if h.ok()]
        tenth = max(1, len(lat) // 10)
        row = dict(serve.latency_summary(handles, due, sizes, window_s),
                   rate=rate, requests=len(handles),
                   shed=len(handles) - sum(h.ok() for h in handles),
                   max_queue_texts=rt.stats["max_queue_depth"],
                   first_tenth_ms=1e3 * float(np.mean(lat[:tenth])) if lat else None,
                   last_tenth_ms=1e3 * float(np.mean(lat[-tenth:])) if lat else None,
                   late_p95_ms=1e3 * float(np.percentile(late, 95)),
                   batches=rt.stats["batches"], texts=rt.stats["batch_samples"],
                   token_fill=rt.stats["batch_tokens"] / max(rt.stats["bucket_tokens"], 1),
                   tokens_per_s=rt.stats["batch_tokens"] / window_s)
        harness.log(json.dumps(row))
        rows.append(row)
        time.sleep(0.5)
    print(json.dumps({"workload": args.workload, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
