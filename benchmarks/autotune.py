"""Block-size autotuner for the Pallas data-plane kernels.

Sweeps ``block_m``/``block_n`` for the semijoin probe and the fused relalg
kernels (expand, bucket_by_dest; unique_compact is a single-block kernel
with no block parameters), then persists the per-platform winners to the
table consulted at dispatch time (``repro.kernels.tuning`` ->
``src/repro/kernels/tuned/<platform>.json``).  Closes the "untuned
defaults" ROADMAP item: any engine on a tuned platform picks the winners up
transparently.

On TPU the kernels are compiled and the sweep uses production-sized shards;
off-TPU they run in interpret mode, so the sweep shrinks to keep wall time
sane — the resulting table is then mostly a record of the harness having
run (the off-TPU data plane uses the fused jnp mirrors, which have no block
sizes), but it exercises the persist/lookup path end to end.

Usage:
    python -m benchmarks.autotune            # sweep + write the table
    python -m benchmarks.autotune --dry-run  # sweep + print only
"""
from __future__ import annotations

import argparse
import itertools
import time
from functools import partial

import numpy as np

import repro.core  # noqa: F401  (x64 on, as in production)
import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.relalg_ops.bucket import bucket_by_dest_pallas
from repro.kernels.relalg_ops.expand import expand_pallas
from repro.kernels.semijoin.semijoin import semijoin_probe


def _time_call(fn, *args, iters: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)  # compile / first interpret pass
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e6 / iters


def _sweep(name, make_fn, grid, args, iters):
    """Time every block config; returns (best_cfg, trajectory rows)."""
    best_cfg, best_us, rows = None, float("inf"), []
    keys = sorted(grid)
    for combo in itertools.product(*(grid[k] for k in keys)):
        cfg = dict(zip(keys, combo))
        try:
            us = _time_call(jax.jit(make_fn(**cfg)), *args, iters=iters)
        except Exception as e:  # e.g. block too large for the shape
            rows.append((f"autotune/{name}/" + "_".join(
                f"{k}{v}" for k, v in cfg.items()), -1.0, f"error={type(e).__name__}"))
            continue
        rows.append((f"autotune/{name}/" + "_".join(
            f"{k}{v}" for k, v in cfg.items()), us, ""))
        if us < best_us:
            best_cfg, best_us = cfg, us
    for i, (n, us, d) in enumerate(rows):
        if best_cfg and n.endswith("_".join(
                f"{k}{v}" for k, v in best_cfg.items())) and us == best_us:
            rows[i] = (n, us, "winner")
    return best_cfg, best_us, rows


def run(write: bool = True, iters: int | None = None
        ) -> list[tuple[str, float, str]]:
    on_tpu = jax.default_backend() == "tpu"
    iters = iters or (20 if on_tpu else 3)
    rng = np.random.default_rng(0)
    rows: list[tuple[str, float, str]] = []
    winners: dict[str, dict[str, int]] = {}

    # ---- semijoin probe: (N keys, M probes) per worker shard
    n, m = ((1 << 20, 1 << 13) if on_tpu else (1 << 12, 1 << 9))
    keys = jnp.asarray(np.sort(rng.integers(0, 1 << 40, n)))
    probes = jnp.asarray(rng.integers(0, 1 << 40, m))
    grid = {  # whole (8, 128) int32 tiles
        "block_m": [1024, 2048, 4096] if on_tpu else [1024, 2048],
        "block_n": [1024, 2048, 4096] if on_tpu else [1024, 2048],
    }
    cfg, us, r = _sweep(
        "semijoin_probe",
        lambda **c: partial(semijoin_probe, **c),
        grid, (keys, probes), iters,
    )
    rows += r
    if cfg:
        winners["semijoin_probe"] = cfg

    # ---- expand: per-row ranges -> flat row list
    n, cap = ((1 << 18, 1 << 19) if on_tpu else (1 << 11, 1 << 12))
    lo = jnp.asarray(rng.integers(0, 1000, n).astype(np.int32))
    hi = lo + jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    grid = {
        "block_m": [1024, 2048, 4096] if on_tpu else [1024, 2048],
        "block_n": [1024, 2048, 4096] if on_tpu else [1024, 2048],
    }
    cfg, us, r = _sweep(
        "relalg_expand",
        lambda **c: partial(expand_pallas, out_cap=cap, **c),
        grid, (lo, hi), iters,
    )
    rows += r
    if cfg:
        winners["relalg_expand"] = cfg

    # ---- bucket_by_dest: per-destination send-buffer layout
    n, w, cap_peer = ((1 << 17, 32, 1 << 12) if on_tpu else (1 << 10, 4, 128))
    vals = jnp.asarray(rng.integers(0, 1 << 20, (n, 3)).astype(np.int32))
    dest = jnp.asarray(rng.integers(0, w, n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) > 0.1)
    grid = {"block_n": [1024, 2048, 4096] if on_tpu else [1024, 2048]}
    cfg, us, r = _sweep(
        "relalg_bucket",
        lambda **c: partial(bucket_by_dest_pallas, n_dest=w,
                            cap_peer=cap_peer, **c),
        grid, (vals, dest, valid), iters,
    )
    rows += r
    if cfg:
        winners["relalg_bucket"] = cfg

    if write and winners:
        path = tuning.save_tuned(
            winners,
            meta={"interpret": not on_tpu, "iters": iters},
        )
        rows.append((f"autotune/table_written", 0.0, str(path)))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="sweep and print, do not write the table")
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(write=not args.dry_run, iters=args.iters):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
