"""Paper Fig. 4: training time vs circular-network degree d (M=20).

Two views:
  1. measured wall-time of the gossip-consensus simulation (B rounds per
     consensus, B from the spectral gap — the paper's transition jump
     appears because B(d) collapses once the graph mixes fast);
  2. the analytic exchange count B(d)*K per layer (hardware-independent).
"""
from __future__ import annotations

import jax

from benchmarks.common import (
    ADMM_ITERS, DATA_SCALE, HIDDEN_EXTRA, csv_row, timed,
)
from repro.core import layerwise, ssfn, topology
from repro.core.policy import Gossip
from repro.data import paper_dataset, partition_workers

M = 20
# A ring of degree d needs 2d+1 <= M distinct neighbours.
DEGREES = [1, 2, 3, 4, 6, 8, 9]


def run(verbose: bool = True) -> list[str]:
    rows = []
    data = paper_dataset("satimage", jax.random.PRNGKey(1), scale=DATA_SCALE)
    q = data.num_classes
    cfg = ssfn.SSFNConfig(
        input_dim=data.input_dim, num_classes=q,
        num_layers=3, hidden=2 * q + HIDDEN_EXTRA,
        mu0=1e-3, mul=1e-2, admm_iters=ADMM_ITERS,
    )
    xw, tw = partition_workers(data.x_train, data.t_train, M)
    for d in DEGREES:
        h = topology.circular_mixing_matrix(M, d)
        rounds = topology.gossip_rounds_for_tolerance(h, 1e-8)
        policy = Gossip(rounds=rounds, topology=topology.Ring(d))
        (_, log), t = timed(
            layerwise.train_decentralized_ssfn, xw, tw, cfg,
            jax.random.PRNGKey(0), policy=policy,
        )
        derived = (
            f"degree={d};B={rounds};exchanges_per_layer={rounds * ADMM_ITERS};"
            f"spectral_gap={topology.spectral_gap(h):.4f};"
            f"comm_scalars={log.comm_scalars}"
        )
        rows.append(csv_row(f"fig4_degree{d}", t * 1e6, derived))
        if verbose:
            print(rows[-1], flush=True)
    return rows


if __name__ == "__main__":
    run()
