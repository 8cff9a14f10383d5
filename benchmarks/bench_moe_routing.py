"""MoE routing: BinomialHash router vs learned top-k — load balance without
aux loss, elastic expert scaling, and routing overhead (the multi-K hash
router is ONE broadcast-salted lookup dispatch per layer — DESIGN.md §9).

``--smoke`` shrinks token counts and the expert sweep for the CI bench-smoke
job: the full fused-K routing datapath still runs end to end, in seconds.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, enable_compile_cache, rows_to_csv, time_loop
from repro.configs import reduced_config
from repro.core.binomial_jax import binomial_lookup_vec, mix32
from repro.models.layers.moe import init_moe, route


def _cfg(router, E, k):
    cfg = reduced_config("qwen3-moe-235b-a22b")
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, router=router, num_experts=E, top_k=k)
    )


def main(argv: list[str] | None = None) -> list[list]:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="small sizes for CI: full routing datapath exercised, in seconds",
    )
    # run.py calls main() programmatically — don't inherit its sys.argv
    args = ap.parse_args([] if argv is None else argv)
    shape = (4, 512) if args.smoke else (16, 4096)
    sweep = ((64, 8),) if args.smoke else ((64, 8), (128, 8), (256, 8))
    elastic = (64,) if args.smoke else (64, 128, 256)
    overhead_iters = 3 if args.smoke else 5

    rows = []
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 150000, shape), jnp.int32)
    n_tokens = shape[0] * shape[1]

    for E, k in sweep:
        # hash router: balance with zero aux loss, freshly initialised
        cfg = _cfg("hash", E, k)
        eids, gates, aux = route({}, None, tokens, 5, cfg)
        counts = np.bincount(np.asarray(eids).reshape(-1), minlength=E)
        hash_rel_std = counts.std() / counts.mean()
        hash_max_over = counts.max() / counts.mean()

        # learned top-k at INIT (before any balancing pressure): the contrast
        cfg2 = _cfg("topk", E, k)
        p = init_moe(jax.random.PRNGKey(0), cfg2)
        x = jax.random.normal(jax.random.PRNGKey(1), (*shape, cfg2.d_model)) * 0.5
        eids2, _, aux2 = route(p, x, tokens, 5, cfg2)
        c2 = np.bincount(np.asarray(eids2).reshape(-1), minlength=E)
        topk_rel_std = c2.std() / c2.mean()
        topk_max_over = c2.max() / c2.mean()

        rows.append([E, k, round(hash_rel_std, 4), round(hash_max_over, 3),
                     round(topk_rel_std, 4), round(topk_max_over, 3)])
        emit(
            f"moe-balance/E={E}", 0.0,
            f"hash_rel_std={hash_rel_std:.4f};topk_init_rel_std={topk_rel_std:.4f};"
            f"hash_max/mean={hash_max_over:.3f};topk_max/mean={topk_max_over:.3f}",
        )

    # elastic expert scaling: movement when E grows (paper's monotonicity)
    keys = mix32(tokens.astype(jnp.uint32).reshape(-1))
    for E in elastic:
        a = np.asarray(binomial_lookup_vec(keys, E))
        b = np.asarray(binomial_lookup_vec(keys, E + 16))
        moved = float((a != b).mean())
        only_new = bool((np.asarray(b)[a != b] >= E).all())
        rows.append([E, E + 16, round(moved, 4), round(16 / (E + 16), 4), only_new, ""])
        emit(
            f"moe-elastic/E={E}->+16", 0.0,
            f"moved={moved:.4f};ideal={16/(E+16):.4f};moves_only_to_new={only_new}",
        )

    # routing overhead: the full multi-K hash route — since the fused (B,S,K)
    # router this is ONE lookup dispatch per layer, not top_k of them
    E = sweep[-1][0]
    cfg = _cfg("hash", E, 8)
    f = lambda: route({}, None, tokens, 5, cfg)[0].block_until_ready()
    us = time_loop(f, overhead_iters)
    emit(f"moe-route-overhead/E={E}/k=8", us, f"{n_tokens/(us*1e-6):.3e}_tokens_per_s")
    rows_to_csv(
        "bench_moe_routing",
        ["E_or_E0", "k_or_E1", "hash_rel_std_or_moved", "topk_or_ideal", "extra1", "extra2"],
        rows,
    )
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main(sys.argv[1:])
