"""The wire-budget check: lowered collectives vs declared eq.-15 wire.

For each policy the checker lowers (never executes) the production hot
program — the layer engine's program with ``trace_every=0`` — on a
real worker mesh and compares what the compiled HLO actually contains
against what the policy declares:

- **wire-count**: the program must contain EXACTLY the policy's own
  exchanges — ``K_comm x hops`` collective-permutes for gossip schedules
  (``hops == Gossip.hops_for(M)`` for compressed ``H**B`` mixes, the
  serial round x edges product otherwise), ``K_comm`` all-reduces for
  the pmean-form policies, where ``K_comm = K // communication_interval``.
- **wire-hot-path**: ``trace_every=0`` admits zero NON-consensus
  collectives (no trace psums, no stray all-gathers) — any op kind
  outside the expected set is a finding.
- **wire-payload**: every ``collective_permute`` payload in the
  pre-optimization StableHLO must carry the consensus message shape in
  the dtype the policy's ``wire_bits`` declares (32 -> f32, 16 -> bf16 /
  f16).  StableHLO is used because the CPU compiler upcasts 16-bit
  collectives, hiding the wire width post-optimization.  Policies whose
  ``wire_bits`` is a logical packed width over f32 lanes
  (``QuantizedGossip``) are exempt and noted.
- **wire-declaration**: ``comm_scalars`` / ``wire_bytes`` must equal
  the closed form ``S x exchanges_for(M) x K_comm`` (and its
  ``wire_bits/8`` byte scaling) — a policy overriding one without the
  other is misdeclared.

Collectives resolve to HLO ops only under ``MeshBackend`` (vmap's
named-axis collectives trace away), so callers must pass a mesh-backed
backend; ``launch/lint_dssfn.py`` fakes an M-device host platform
before importing jax, the same way ``train_dssfn`` does.
"""
from __future__ import annotations

import re

from repro.core import policy as policy_lib
from repro.core import topology as topology_lib

from .findings import LintFinding


def expected_mix_collectives(policy, num_workers: int) -> dict:
    """Collective ops ONE communicating ``mix`` lowers to, derived from
    the policy's declared structure (never from the program)."""
    topo = getattr(policy, "topology", None)
    if topo is None:
        # ExactMean and the pmean forms of quantized/stale mixing.
        return {"all-reduce": 1}
    if isinstance(policy, policy_lib.Gossip):
        return {"collective-permute": policy.hops_for(num_workers)}
    phases = topo.cycle()
    per_phase = [
        len(topology_lib.cached_exchange_schedule(t, num_workers).perms)
        for t in phases
    ]
    if isinstance(policy, policy_lib.StaleMixing):
        # One schedule application per mix (validated single-phase).
        return {"collective-permute": per_phase[0]}
    rounds = getattr(policy, "rounds", 1)
    hops = sum(per_phase[b % len(per_phase)] for b in range(rounds))
    return {"collective-permute": hops}


def probe_iters(policy, num_iters: int) -> int:
    """K rounded up to a multiple of the communication interval (the
    chunked scan requires divisibility)."""
    interval = policy.communication_interval
    return interval * max(1, -(-num_iters // interval))


def hot_program_texts(
    backend, policy, *, num_iters: int, n: int = 16, q: int = 3,
    j_per: int = 8,
):
    """Lower the engine's ``trace_every=0`` layer program (l=0, no
    propagation) under ``policy`` and return the backend's
    ``{"stablehlo", "hlo"}`` texts."""
    import jax

    from repro.core import engine

    m = backend.num_workers
    ky, kt = jax.random.split(jax.random.PRNGKey(0))
    yw = jax.random.normal(ky, (m, n, j_per))
    tw = jax.random.normal(kt, (m, q, j_per))
    return engine.layer_program(
        backend, yw, tw, None, mu=1e-2, eps_radius=6.0, num_iters=num_iters,
        policy=policy, trace_every=0,
    ).lowering_texts()


def _stablehlo_permute_payloads(text: str) -> list[tuple[str, int]]:
    """(dtype, scalar count) of every collective_permute in the
    pre-optimization program text."""
    out = []
    for line in text.splitlines():
        if "stablehlo.collective_permute" not in line:
            continue
        types = re.findall(r"tensor<([^>]*)>", line)
        if not types:
            continue
        parts = types[-1].split("x")
        dtype = parts[-1]
        scalars = 1
        for p in parts[:-1]:
            scalars *= int(p)
        out.append((dtype, scalars))
    return out


_WIDTH_DTYPES = {32: ("f32",), 16: ("bf16", "f16")}


def check_wire_contract(
    policy, backend, *, num_iters: int = 8, subject: str, texts=None,
) -> list[LintFinding]:
    from repro.launch.hlo_analysis import analyze_module

    m = backend.num_workers
    findings: list[LintFinding] = []
    k = probe_iters(policy, num_iters)
    k_comm = k // policy.communication_interval
    if texts is None:
        texts = hot_program_texts(backend, policy, num_iters=k)

    per_mix = expected_mix_collectives(policy, m)
    expected = {op: c * k_comm for op, c in per_mix.items()}
    analysis = analyze_module(texts["hlo"])
    counts = analysis.collective_counts()

    extra_ops = sorted(set(counts) - set(expected))
    if extra_ops:
        findings.append(LintFinding(
            check="wire-hot-path",
            subject=subject,
            message=(
                "trace_every=0 program contains collectives outside the "
                f"policy's own exchanges: {extra_ops}"
            ),
            details={"counts": counts, "expected_ops": sorted(expected)},
        ))
    mismatched = {
        op: (counts.get(op, 0), want)
        for op, want in expected.items()
        if counts.get(op, 0) != want
    }
    if mismatched:
        findings.append(LintFinding(
            check="wire-count",
            subject=subject,
            message=(
                "lowered collective counts disagree with the declared "
                "schedule structure (measured, expected) per op"
            ),
            details={
                "mismatched": mismatched, "counts": counts,
                "expected": expected, "num_iters": k,
                "communicating_iters": k_comm, "num_workers": m,
            },
        ))

    # ---- payload width (StableHLO, dtypes preserved) -----------------
    quantized = isinstance(policy, policy_lib.QuantizedGossip)
    if expected.get("collective-permute"):
        payloads = _stablehlo_permute_payloads(texts["stablehlo"])
        widths = _WIDTH_DTYPES.get(policy.wire_bits)
        if quantized or widths is None:
            # Logical packed bits over f32 lanes: physical width is not
            # wire_bits/8 by design; nothing to check, note it instead.
            widths = ("f32",)
        bad = [
            (dtype, scalars) for dtype, scalars in payloads
            if dtype not in widths
        ]
        if bad:
            findings.append(LintFinding(
                check="wire-payload",
                subject=subject,
                message=(
                    f"collective_permute payload dtype disagrees with "
                    f"declared wire_bits={policy.wire_bits} "
                    f"(expected one of {widths})"
                ),
                details={"bad_payloads": sorted(set(bad)),
                         "declared_wire_bits": policy.wire_bits,
                         "logical_packing": quantized},
            ))

    # ---- declaration arithmetic (no program needed) ------------------
    s = 64  # any per-exchange scalar count exercises the closed form
    declared = policy.comm_scalars(
        scalars=s, num_consensus=k, num_workers=m
    )
    closed_form = s * policy.exchanges_for(m) * k_comm
    if declared != closed_form:
        findings.append(LintFinding(
            check="wire-declaration",
            subject=subject,
            message=(
                "comm_scalars disagrees with "
                "scalars x exchanges_for(M) x (K / interval)"
            ),
            details={"declared": declared, "closed_form": closed_form,
                     "exchanges_for": policy.exchanges_for(m),
                     "interval": policy.communication_interval},
        ))
    declared_bytes = policy.wire_bytes(
        scalars=s, num_consensus=k, num_workers=m
    )
    if declared_bytes * 8 != declared * policy.wire_bits:
        findings.append(LintFinding(
            check="wire-declaration",
            subject=subject,
            message="wire_bytes disagrees with comm_scalars x wire_bits / 8",
            details={"declared_bytes": declared_bytes,
                     "comm_scalars": declared,
                     "wire_bits": policy.wire_bits},
        ))
    return findings
