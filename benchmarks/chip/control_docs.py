#!/usr/bin/env python3
"""Readings for the limits of a document-serving cell: the program's own,
the controls' and the planted fault's, on one sample, on one chip.

    python3 benchmarks/chip/control_docs.py --workload <cell> \\
        --seeds 11,12 --seconds 4

The benchmark's runs do not run this.  For each seed it runs the cell's
generator (``generators/serve_docs.py``) for a short window at the
cell's own load, and on the sample its comparison draws reads, against
the same float32 reference: the program; ``control_state`` (the SSM
state, decays and dt in bfloat16); ``wrong_token`` (each text's feature
taken one token before its last).  It prints one line per seed and
variant, and last a JSON object with every reading.
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
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from benchmarks.chip.generators import serve_docs
    from benchmarks.chip.run import enable_compile_cache, find_chips, import_program

    find_chips(1)
    import_program()
    enable_compile_cache()
    out = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = serve_docs.run(cell, seed=seed, seconds=args.seconds, trace_dir=None,
                             t_start=t0, controls=True)
        out[seed] = dict(res.controls, program=dict(res.compared, failed=res.failed))
        for variant, numbers in out[seed].items():
            harness.log(f"seed {seed} {variant}: {numbers}")
        harness.log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"workload": args.workload, "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
