"""repro.analysis (spmdlint): every checker must fire on a seeded
mutation and stay silent on the clean tree.

The wire-payload / wire-count mesh mutations need an 8-device worker
mesh and live in tests/test_multidevice.py; everything here runs on a
single host device (value-level, vmap, or pure-text checks).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro import analysis, dssfn
from repro.core import admm, engine
from repro.core import policy as policy_lib
from repro.core.backend import SimulatedBackend
from repro.core.policy import (
    AsyncGossip,
    ExactMean,
    Gossip,
    QuantizedGossip,
    StaleMixing,
)
from repro.core.topology import (
    ExchangeSchedule,
    Hypercube,
    Ring,
    cached_exchange_schedule,
)

M = 8


def _checks(findings):
    return sorted({f.check for f in findings})


# ---------------------------------------------------------------- findings


def test_finding_schema_and_rendering():
    f = analysis.LintFinding(
        check="wire-count", subject="gossip:3", message="mismatch",
        details={"expected": 3},
    )
    d = f.to_dict()
    assert d == {
        "check": "wire-count", "severity": "error", "subject": "gossip:3",
        "message": "mismatch", "details": {"expected": 3},
    }
    assert "ERROR [wire-count] gossip:3: mismatch" in f.render()
    assert "expected = 3" in f.render()
    with pytest.raises(ValueError, match="severity"):
        analysis.LintFinding(
            check="x", subject="y", message="z", severity="fatal"
        )
    # details are evidence, not identity.
    g = dataclasses.replace(f, details={})
    assert g == f

    payload = json.loads(analysis.findings_to_json([f, g]))
    assert payload["count"] == 2 and payload["errors"] == 2
    assert payload["findings"][0]["check"] == "wire-count"
    assert analysis.render_report([]) == "spmdlint: no findings"
    assert "2 finding(s), 2 error(s)" in analysis.render_report([f, g])


# ---------------------------------------------------------------- schedule


def test_schedule_checker_clean_on_library_schedules():
    sched = cached_exchange_schedule(Hypercube(), M)
    assert analysis.check_schedule(
        sched, subject="hypercube",
        expect_inverse_closed=True, expect_symmetric=True,
    ) == []


def test_schedule_inverse_closure_mutation():
    # A directed ring IS doubly stochastic — only closure catches it.
    directed = ExchangeSchedule(
        num_workers=4,
        perms=(tuple((i, (i + 1) % 4) for i in range(4)),),
        weights=(0.5,), self_weight=0.5,
    )
    clean = analysis.check_schedule(directed, subject="directed-ring")
    assert clean == []  # without the fault-rerouting expectation
    found = analysis.check_schedule(
        directed, subject="directed-ring", expect_inverse_closed=True
    )
    assert _checks(found) == ["schedule-inverse-closure"]


def test_schedule_weight_mutations():
    perms = (tuple((i, (i + 1) % 4) for i in range(4)),)
    overweight = ExchangeSchedule(
        num_workers=4, perms=perms, weights=(0.7,), self_weight=0.5
    )
    assert _checks(analysis.check_schedule(overweight, subject="ow")) == [
        "schedule-doubly-stochastic", "schedule-weight-sum",
    ]
    negative = ExchangeSchedule(
        num_workers=4, perms=perms, weights=(-0.2,), self_weight=1.2
    )
    assert _checks(analysis.check_schedule(negative, subject="neg")) == [
        "schedule-nonnegative", "schedule-weights",
    ]
    asym = ExchangeSchedule(
        num_workers=4, perms=perms, weights=(0.5,), self_weight=0.5
    )
    assert _checks(analysis.check_schedule(
        asym, subject="asym", expect_symmetric=True
    )) == ["schedule-symmetry"]


def test_policy_schedules_clean_across_grammar():
    for entry, policy in analysis.grammar.parse_all(M):
        assert analysis.check_policy_schedules(
            policy, M, subject=entry.spec
        ) == [], entry.spec


# ---------------------------------------------------------------- numerics


def test_numerics_accum_mutation_fires():
    def f16_prog(a, b):
        return (a.astype(jnp.float16) @ b.astype(jnp.float16)).astype(
            jnp.float32
        )

    a = jnp.zeros((4, 8), jnp.float32)
    b = jnp.zeros((8, 4), jnp.float32)
    found = analysis.lint_jax_callable(f16_prog, a, b, subject="f16-accum")
    assert "numerics-accum" in _checks(found)
    assert any(f.details.get("dtype") == "f16" for f in found)
    # The f32 form of the same program is clean.
    assert analysis.lint_jax_callable(
        lambda a, b: a @ b, a, b, subject="f32-accum"
    ) == []


def test_numerics_cholesky_guard_detection():
    g = jnp.eye(6) * 2.0
    raw = analysis.lint_jax_callable(
        jnp.linalg.cholesky, g, subject="raw-cholesky"
    )
    assert _checks(raw) == ["numerics-cholesky"]
    guarded = analysis.lint_jax_callable(
        lambda m: admm.guarded_cholesky(m)[0], g, subject="guarded"
    )
    assert "numerics-cholesky" not in _checks(guarded)


def test_numerics_backend_program_clean():
    backend = SimulatedBackend(4)
    x = jnp.ones((4, 3, 5))

    def worker(x_m):
        return x_m @ x_m.T

    assert analysis.lint_backend_program(
        backend, worker, x, subject="sim-worker"
    ) == []


# ---------------------------------------------------------------- retrace


@dataclasses.dataclass(frozen=True)
class LeakyGossip(Gossip):
    """Mutation: a config field excluded from equality/hash — two
    distinct configurations share one cached executable."""

    hidden: int = dataclasses.field(default=1, compare=False)


def test_retrace_value_level_clean_across_grammar():
    for entry, policy in analysis.grammar.parse_all(M):
        assert analysis.check_policy_cache_key(
            policy, M, subject=entry.spec
        ) == [], entry.spec


def test_retrace_key_collision_mutation_fires():
    found = analysis.check_policy_cache_key(
        LeakyGossip(rounds=2), M, subject="leaky"
    )
    assert _checks(found) == ["retrace-key-collision"]
    assert any(f.details.get("field") == "hidden" for f in found)


def test_perturb_policy_varies_every_constructible_field():
    base = AsyncGossip(
        interval=2, rounds=2, topology=Ring(2),
        faults=policy_lib.FaultModel(drop=0.1, seed=3),
    )
    variants = dict(analysis.perturb_policy(base, M))
    for field_name in ("interval", "rounds", "topology", "faults"):
        assert field_name in variants
        assert variants[field_name] != base
        variants[field_name].validate(M)


def test_backend_retrace_probe_clean():
    backend = SimulatedBackend(4)
    assert analysis.check_backend_retrace(
        backend, Gossip(rounds=2), 4, subject="gossip:2"
    ) == []
    # The probe itself populated the cache: base + 2 perturbed variants.
    info = backend.cache_info()
    assert info["entries"] == 3 and info["cache_hits"] >= 1


def test_cache_info_schema_checker():
    ok = {"entries": 1, "lowerings": 2, "cache_hits": 0, "keys": ["k"]}
    assert analysis.check_cache_info_schema(ok, subject="s") == []
    missing = analysis.check_cache_info_schema(
        {"entries": 1}, subject="s"
    )
    assert _checks(missing) == ["retrace-cache-schema"]
    skewed = analysis.check_cache_info_schema(
        {**ok, "keys": []}, subject="s"
    )
    assert _checks(skewed) == ["retrace-cache-schema"]


# ---------------------------------------------------------------- wire model


def test_expected_mix_collectives_model():
    assert analysis.expected_mix_collectives(ExactMean(), M) == {
        "all-reduce": 1
    }
    # pmean forms: no topology -> one physical all-reduce per mix.
    assert analysis.expected_mix_collectives(QuantizedGossip(bits=8), M) == {
        "all-reduce": 1
    }
    g = Gossip(rounds=3)
    assert analysis.expected_mix_collectives(g, M) == {
        "collective-permute": g.hops_for(M)
    }
    stale = StaleMixing(1, topology=Ring(2))
    hops = len(cached_exchange_schedule(Ring(2), M).perms)
    assert analysis.expected_mix_collectives(stale, M) == {
        "collective-permute": hops
    }


def test_wire_probe_is_the_engine_layer_program():
    """The wire lint checks the program training runs: hot_program_texts
    lowers the engine's trace_every=0 layer program, through the same
    executable-cache entry."""
    backend = SimulatedBackend(4)
    policy = Gossip(rounds=2, topology=Ring(1))
    texts = analysis.hot_program_texts(backend, policy, num_iters=8)
    assert backend.lowerings == 1, backend.cache_info()
    program = engine.layer_program(
        backend,
        jax.ShapeDtypeStruct((4, 16, 8), jnp.float32),
        jax.ShapeDtypeStruct((4, 3, 8), jnp.float32),
        None, mu=1e-2, eps_radius=6.0, num_iters=8, policy=policy,
        trace_every=0,
    )
    assert program.lowering_texts() == texts
    assert backend.lowerings == 1, backend.cache_info()
    assert backend.cache_hits == 1


def test_probe_iters_rounds_to_interval():
    assert analysis.wire.probe_iters(ExactMean(), 8) == 8
    sparse = AsyncGossip(interval=4)
    assert analysis.wire.probe_iters(sparse, 6) == 8
    assert analysis.wire.probe_iters(sparse, 1) == 4


# ---------------------------------------------------------------- source


_BAD_SOURCE = """
import time
import jax


def make_key():
    return jax.random.PRNGKey(int(time.time()))


class P:
    def mix(self, x, state, ctx):
        if x.sum() > 0:
            return x, state
        return -x, state
"""

_CLEAN_SOURCE = """
import jax


def make_key():
    return jax.random.PRNGKey(0)


class P:
    rounds = 2

    def mix(self, x, state, ctx):
        if state is None:
            state = 0
        if self.rounds > 0:
            return x, state
        return -x, state
"""


def test_source_lint_mutations_fire():
    found = analysis.lint_source_text(_BAD_SOURCE, filename="bad.py")
    assert _checks(found) == ["source-prng-seed", "source-traced-branch"]
    assert analysis.lint_source_text(_CLEAN_SOURCE, filename="ok.py") == []
    broken = analysis.lint_source_text("def f(:\n", filename="broken.py")
    assert _checks(broken) == ["source-syntax"]


def test_source_lint_clean_over_repo():
    from pathlib import Path

    src_root = Path(__file__).resolve().parents[1] / "src" / "repro"
    assert analysis.lint_source_tree(src_root) == []


# ---------------------------------------------------------------- grammar


def test_grammar_table_parses_and_validates():
    parsed = analysis.grammar.parse_all(M)
    assert len(parsed) == len(analysis.ALL_GRAMMAR)
    # Every supported mode appears at least once.
    heads = {
        e.spec.split("@")[0].split(":")[0] for e in analysis.ALL_GRAMMAR
    }
    assert heads == set(policy_lib._MODES)
    wire = set(analysis.grammar_specs(wire_only=True))
    assert wire < set(analysis.grammar_specs())
    assert "async:rounds=2@ring:1+hypercube" not in wire
    assert "gossip:2@geometric:0.9" not in wire


def test_malformed_specs_rejected():
    # Full round-trip lives in test_dssfn.py; here: table shape only.
    assert len(analysis.MALFORMED_SPECS) >= 20
    assert len({s for s, _ in analysis.MALFORMED_SPECS}) == len(
        analysis.MALFORMED_SPECS
    )


# ---------------------------------------------------------------- CLI


def test_cli_clean_on_device_free_checks(tmp_path, capsys):
    from repro.launch import lint_dssfn

    args = lint_dssfn.parse_args(
        ["--checks", "schedule,retrace,source", "--all-grammar"]
    )
    assert lint_dssfn.lint(args) == []

    out = tmp_path / "findings.json"
    rc = lint_dssfn.main([
        "--checks", "schedule,source", "--all-grammar",
        "--format", "json", "--out", str(out),
    ])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["count"] == 0 and payload["findings"] == []
    assert json.loads(capsys.readouterr().out)["errors"] == 0


def test_cli_reports_grammar_parse_failure():
    from repro.launch import lint_dssfn

    rc = lint_dssfn.main(
        ["--spec", "bogus", "--checks", "schedule", "--format", "json"]
    )
    assert rc == 1


def test_cli_rejects_unknown_check():
    from repro.launch import lint_dssfn

    with pytest.raises(SystemExit, match="unknown checks"):
        lint_dssfn.lint(lint_dssfn.parse_args(["--checks", "vibes"]))


def test_dssfn_exports_analysis_surface():
    assert dssfn.parse_spec("exact") == ExactMean()
    for name in ("ALL_GRAMMAR", "check_wire_contract", "LintFinding"):
        assert hasattr(analysis, name)


# ---------------------------------------------------------------- serve

def test_serve_surface_clean():
    """The serve lint over the real engine across the feature grammar:
    zero findings on a healthy tree (no collectives leak into the
    single-device bucket programs, f32 accumulation throughout)."""
    assert analysis.check_serve_surface(buckets=(1, 4)) == []


def test_serve_lint_fires_on_bf16_engine():
    """Mutation: a half-precision engine accumulates its propagate dots
    in bf16 — the dtype-discipline rule must fire."""
    engine = analysis.synthetic_serve_engine(
        dtype=jnp.bfloat16, buckets=(1,)
    )
    findings = analysis.check_serve_contract(engine, subject="serve:bf16")
    assert "numerics-accum" in {f.check for f in findings}


def test_serve_lint_fires_on_collective():
    """Mutation: a bucket program whose compiled HLO carries a
    collective means SPMD machinery leaked into the request path."""
    hlo = "\n".join([
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  ROOT %ar = f32[8]{0} all-reduce(f32[8]{0} %p), "
        "replica_groups={{0,1,2,3}}, to_apply=%add",
        "}",
    ])
    findings = analysis.check_serve_texts(
        {"stablehlo": "", "hlo": hlo}, subject="serve:mutated"
    )
    assert [f.check for f in findings] == ["serve-collective"]
    assert findings[0].details["collective_counts"] == {"all-reduce": 1}


def test_serve_lint_probe_is_compile_only():
    """The lint must not touch the serving executable cache: lowerings
    and entries are unchanged after a full contract check."""
    engine = analysis.synthetic_serve_engine(buckets=(1, 4))
    x = jnp.zeros((engine.request_dim, 1))
    engine.forward(x)                       # one real lowering
    before = engine.cache_info()
    findings = analysis.check_serve_contract(engine, subject="serve:purity")
    assert findings == []
    assert engine.cache_info() == before


def test_serve_check_registered_in_cli():
    from repro.launch import lint_dssfn

    assert "serve" in lint_dssfn.CHECKS
    args = lint_dssfn.parse_args(["--checks", "serve", "--spec", "exact"])
    assert lint_dssfn.lint(args) == []
