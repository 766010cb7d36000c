"""spmdlint CLI: statically verify SPMD programs against their contracts.

Usage::

    python -m repro.launch.lint_dssfn --all-grammar
    python -m repro.launch.lint_dssfn --spec gossip:3 --spec exact
    python -m repro.launch.lint_dssfn --all-grammar --format=json --out findings.json
    python -m repro.launch.lint_dssfn --checks schedule,source --all-grammar

Per spec the linter runs (lowering only — nothing executes):

- ``schedule``  exchange-schedule algebra (doubly-stochastic, weights,
                inverse-closure under faults, compressed H**B)
- ``retrace``   cache-key completeness (field perturbation, value level)
- ``wire``      lowered collective counts / payload widths vs the
                declared eq.-15 budget (needs an M-device mesh; under
                ``JAX_PLATFORMS=cpu`` the CLI fakes one, exactly like
                ``train_dssfn``)
- ``numerics``  StableHLO accumulation-dtype + guarded-cholesky lint of
                the lowered hot program
- ``source``    AST rules over ``src/repro`` (once, not per spec)
- ``serve``     ServeEngine bucket programs: zero collectives + dtype
                discipline through the feature extractors (once, not
                per spec; single-device — no mesh needed)

Exit status is the number of findings (0 = clean), capped at 125.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

CHECKS = ("schedule", "retrace", "wire", "numerics", "source", "serve")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="lint_dssfn", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--spec", action="append", default=[],
        help="policy[@topology] spec to lint (repeatable)",
    )
    ap.add_argument(
        "--all-grammar", action="store_true",
        help="lint every entry of repro.analysis.grammar.ALL_GRAMMAR",
    )
    ap.add_argument("--num-workers", type=int, default=8)
    ap.add_argument(
        "--iters", type=int, default=8,
        help="ADMM iterations in the lowered wire probe",
    )
    ap.add_argument(
        "--checks", default=",".join(CHECKS),
        help=f"comma-separated subset of {CHECKS}",
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--out", default=None, help="also write JSON findings here")
    ap.add_argument(
        "--no-host-mesh", action="store_true",
        help="never fake CPU devices (skips the wire/numerics probes "
        "unless real devices exist)",
    )
    return ap.parse_args(argv)


def lint(args) -> list:
    """Run the selected checks; returns the findings list."""
    # Fake the M-device host platform BEFORE anything imports jax —
    # the wire probe needs real HLO collectives (MeshBackend).
    from repro.launch.train_dssfn import ensure_devices

    checks = [c.strip() for c in args.checks.split(",") if c.strip()]
    unknown = sorted(set(checks) - set(CHECKS))
    if unknown:
        raise SystemExit(f"unknown checks {unknown}; pick from {CHECKS}")
    mesh_checks = {"wire", "numerics"} & set(checks)
    if mesh_checks:
        ensure_devices(args.num_workers, allow_fake=not args.no_host_mesh)

    from repro import analysis, dssfn

    specs = list(args.spec)
    if args.all_grammar or not specs:
        specs += analysis.grammar_specs()
    entry_by_spec = {e.spec: e for e in analysis.ALL_GRAMMAR}

    findings: list[analysis.LintFinding] = []
    m = args.num_workers

    policies = []
    for spec in specs:
        try:
            policy = dssfn.parse_spec(spec)
            policy.validate(m)
        except (ValueError, TypeError) as e:
            findings.append(analysis.LintFinding(
                check="grammar-parse",
                subject=spec,
                message=f"grammar entry does not parse/validate: {e}",
            ))
            continue
        policies.append((spec, policy))

    if "schedule" in checks:
        for spec, policy in policies:
            findings.extend(
                analysis.check_policy_schedules(policy, m, subject=spec)
            )
    if "retrace" in checks:
        for spec, policy in policies:
            findings.extend(
                analysis.check_policy_cache_key(policy, m, subject=spec)
            )

    if {"wire", "numerics"} & set(checks):
        from repro.core.backend import MeshBackend
        from repro.launch.mesh import make_worker_mesh

        import jax

        if len(jax.devices()) < m:
            findings.append(analysis.LintFinding(
                check="wire-environment",
                subject=f"{len(jax.devices())} device(s)",
                message=(
                    f"wire/numerics probes need {m} devices; set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{m} (or drop --no-host-mesh)"
                ),
                severity="warning",
            ))
        else:
            backend = MeshBackend(make_worker_mesh(m))
            for spec, policy in policies:
                entry = entry_by_spec.get(spec)
                if entry is not None and not entry.wire_check:
                    continue
                texts = analysis.hot_program_texts(
                    backend, policy,
                    num_iters=analysis.wire.probe_iters(policy, args.iters),
                )
                if "wire" in checks:
                    findings.extend(analysis.check_wire_contract(
                        policy, backend, num_iters=args.iters,
                        subject=spec, texts=texts,
                    ))
                if "numerics" in checks:
                    findings.extend(analysis.lint_stablehlo_text(
                        texts["stablehlo"], subject=spec,
                    ))

    if "source" in checks:
        src_root = Path(__file__).resolve().parents[2] / "repro"
        findings.extend(analysis.lint_source_tree(src_root))
    if "serve" in checks:
        findings.extend(analysis.check_serve_surface())
    return findings


def main(argv=None) -> int:
    args = parse_args(argv)
    findings = lint(args)

    from repro.analysis import findings_to_json, render_report

    payload = findings_to_json(findings)
    if args.out:
        Path(args.out).write_text(payload + os.linesep)
    if args.format == "json":
        print(payload)
    else:
        print(render_report(findings))
    return min(len(findings), 125)


if __name__ == "__main__":
    sys.exit(main())
