"""Paper Table II: classification performance, centralized SSFN vs
decentralized SSFN on a degree-4 circular network (M=20 nodes).

Synthetic stand-ins with the paper's (P, Q) geometry (DESIGN.md §8):
absolute accuracies are not comparable to the paper's, the
centralized-vs-decentralized *gap* is the reproduced quantity.
"""
from __future__ import annotations

import jax

from benchmarks.common import (
    ADMM_ITERS, DATA_SCALE, HIDDEN_EXTRA, NUM_LAYERS, NUM_WORKERS, csv_row, timed,
)
from repro.core import equivalence, layerwise, ssfn, topology
from repro.core.policy import Gossip
from repro.data import paper_dataset, partition_workers

DATASETS = ["vowel", "satimage", "letter", "mnist"]
# (mu0, mul, data_scale, hidden_extra) — tuned per dataset, exactly as the
# paper tunes mu0/mul per dataset (Table II lists different values per row).
# vowel is tiny (528 samples over 20 workers): full scale + narrower layers
# keep the per-worker Gram better conditioned.
SETTINGS = {
    "vowel": (1e-2, 1e-1, 1.0, 100),
    "satimage": (1e-3, 1e-2, DATA_SCALE, HIDDEN_EXTRA),
    # letter needs J_m >= n per worker for well-conditioned local Grams.
    "letter": (1e-3, 1e-2, 0.4, HIDDEN_EXTRA),
    "mnist": (1e-3, 1e-2, DATA_SCALE, HIDDEN_EXTRA),
}


def run(verbose: bool = True) -> list[str]:
    rows = []
    for name in DATASETS:
        mu0, mul, scale, hidden_extra = SETTINGS[name]
        data = paper_dataset(name, jax.random.PRNGKey(hash(name) % 2**31), scale=scale)
        q = data.num_classes
        cfg = ssfn.SSFNConfig(
            input_dim=data.input_dim, num_classes=q,
            num_layers=NUM_LAYERS, hidden=2 * q + hidden_extra,
            mu0=mu0, mul=mul, admm_iters=ADMM_ITERS,
        )
        key = jax.random.PRNGKey(0)
        (params_c, _), t_cen = timed(
            layerwise.train_centralized_ssfn, data.x_train, data.t_train, cfg, key
        )
        xw, tw = partition_workers(data.x_train, data.t_train, NUM_WORKERS)
        h = topology.circular_mixing_matrix(NUM_WORKERS, 4)  # paper: d=4
        rounds = topology.gossip_rounds_for_tolerance(h, 1e-8)
        policy = Gossip(rounds=rounds, topology=topology.Ring(4))
        (params_d, log_d), t_dec = timed(
            layerwise.train_decentralized_ssfn, xw, tw, cfg, key, policy=policy,
        )
        accs = {
            "cen_train": layerwise.accuracy(params_c, data.x_train, data.y_train, q),
            "cen_test": layerwise.accuracy(params_c, data.x_test, data.y_test, q),
            "dec_train": layerwise.accuracy(params_d, data.x_train, data.y_train, q),
            "dec_test": layerwise.accuracy(params_d, data.x_test, data.y_test, q),
        }
        rep = equivalence.compare(params_c, params_d, data.x_test, q)
        derived = (
            f"cen_test={accs['cen_test']:.3f};dec_test={accs['dec_test']:.3f};"
            f"gap={abs(accs['cen_test'] - accs['dec_test']):.3f};"
            f"agree={rep.agreement:.3f};B={rounds}"
        )
        rows.append(csv_row(f"tableII_{name}", t_dec * 1e6, derived))
        if verbose:
            print(rows[-1], flush=True)
    return rows


if __name__ == "__main__":
    run()
