"""Faults planted under the timed path, to show that the check catches them.

The benchmark's runs never plant one: ``bench/tests`` do on the CPU, and
``bench/sweep.py --faults`` reads them on the chip, one window each.

  alter       every answer produced loses one row where it is produced
              (the pattern-index route and every bucket)
  half_batch  in every bucket of two or more requests, the second half of
              the members get an empty answer
"""
from __future__ import annotations

FAULTS = ("alter", "half_batch")


def _drop_first_row(rel):
    import jax.numpy as jnp

    valid = jnp.asarray(rel.valid).reshape(-1)
    first = jnp.argmax(valid)
    valid = valid.at[first].set(False).reshape(rel.valid.shape)
    return type(rel)(rel.cols, valid, rel.vars)


def _empty(rel):
    import jax.numpy as jnp

    return type(rel)(rel.cols, jnp.zeros_like(rel.valid), rel.vars)


def plant(engine, name: str):
    """Break ``engine`` (an ``AdHashEngine``) in place; returns a function
    that mends it."""
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    bucket_run = engine.execute_bucket
    pi_run = engine.parallel_exec.execute

    def restore():
        engine.execute_bucket = bucket_run
        engine.parallel_exec.execute = pi_run

    if name == "alter":
        def pi_altered(*a, **kw):
            rel, stats = pi_run(*a, **kw)
            return _drop_first_row(rel), stats

        def bucket_altered(bucket, results):
            bucket_run(bucket, results)
            for tag in bucket.tags:
                rel, stats, dt = results[tag]
                results[tag] = (_drop_first_row(rel), stats, dt)

        engine.parallel_exec.execute = pi_altered
        engine.execute_bucket = bucket_altered
    else:
        def bucket_halved(bucket, results):
            bucket_run(bucket, results)
            tags = list(bucket.tags)
            for tag in tags[(len(tags) + 1) // 2:]:
                rel, stats, dt = results[tag]
                results[tag] = (_empty(rel), stats, dt)

        engine.execute_bucket = bucket_halved
    return restore
