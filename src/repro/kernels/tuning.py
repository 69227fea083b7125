"""Per-platform table of block sizes for the Pallas data-plane kernels,
and the semijoin probe's work envelope.

The semijoin probe and the relalg kernels (expand / bucket_by_dest) take
grid block sizes that trade VMEM and SMEM footprint against grid overhead.
The probe also has a *work envelope*: the largest shape for which the
``pallas`` backend runs the kernel rather than the jnp binary search
(``repro.core.backend`` records every such choice).  The in-code
``DEFAULTS`` compile for TPU v5e; measured numbers come from
``python -m benchmarks.autotune``, which sweeps the block space on the
current platform and persists the winners here:

    src/repro/kernels/tuned/<platform>.json      (checked in per platform)

``block_config(kernel)`` is consulted at dispatch time whenever a caller does
not pass explicit block sizes, so a tuned platform transparently runs the
tuned configuration.  ``ADHASH_TUNED_DIR`` overrides the table directory
(e.g. to test a fresh autotune run without overwriting the checked-in one).
A table that exists but cannot be read is an error, never a silent return
to the defaults.
"""
from __future__ import annotations

import functools
import json
import os
from pathlib import Path

import jax

__all__ = [
    "DEFAULTS",
    "block_config",
    "tuned_table",
    "tuned_path",
    "save_tuned",
]

# Untuned defaults.  Block sizes are whole (8, 128) int32 tiles and compile
# for TPU v5e (tests/test_tpu_compile.py).  ``max_pairs`` bounds the
# semijoin probe's O(keys x probes) compare work per worker; past it the
# jnp binary search runs.  On one v5e chip at 2^27 pairs (32,768 int32 keys
# x 4,096 probes, 8 workers) the kernel took 2.2 ms against 15.2 ms for
# ``jnp.searchsorted``, which grows only with log(keys), so 2^28 stays
# well inside the crossover.
DEFAULTS: dict[str, dict[str, int]] = {
    "semijoin_probe": {"block_m": 2048, "block_n": 2048,
                       "max_pairs": 1 << 28},
    "relalg_expand": {"block_m": 2048, "block_n": 2048},
    "relalg_bucket": {"block_n": 2048},
}


def tuned_path(platform: str | None = None) -> Path:
    """Location of the per-platform tuned table (JSON)."""
    platform = platform or jax.default_backend()
    base = os.environ.get("ADHASH_TUNED_DIR")
    root = Path(base) if base else Path(__file__).parent / "tuned"
    return root / f"{platform}.json"


def tuned_table(platform: str | None = None) -> dict[str, dict[str, int]]:
    """DEFAULTS overlaid with the platform's persisted autotune results.

    The env-dependent path is resolved on every call (so a late
    ``ADHASH_TUNED_DIR`` override is honored); only the file load is
    cached, keyed by the resolved path."""
    return _load_table(str(tuned_path(platform)))


@functools.lru_cache(maxsize=None)
def _load_table(path_str: str) -> dict[str, dict[str, int]]:
    cfg = {k: dict(v) for k, v in DEFAULTS.items()}
    path = Path(path_str)
    if path.exists():
        try:
            data = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            raise RuntimeError(f"unreadable kernel table {path}: {e}") from e
        for kernel, blocks in data.get("kernels", {}).items():
            cfg.setdefault(kernel, {}).update(
                {k: int(v) for k, v in blocks.items()}
            )
    return cfg


def block_config(kernel: str, platform: str | None = None) -> dict[str, int]:
    """Tuned (or default) block sizes for one kernel on this platform."""
    table = tuned_table(platform)
    if kernel not in table:
        raise KeyError(
            f"unknown kernel {kernel!r}; known: {sorted(table)}"
        )
    return dict(table[kernel])


def save_tuned(
    kernels: dict[str, dict[str, int]],
    platform: str | None = None,
    meta: dict | None = None,
) -> Path:
    """Persist autotune winners for ``platform`` and drop the lookup cache."""
    path = tuned_path(platform)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {"platform": platform or jax.default_backend(),
               "kernels": kernels}
    if meta:
        payload["meta"] = meta
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    _load_table.cache_clear()
    return path
