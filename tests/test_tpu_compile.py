"""The data-plane Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  The semijoin probe, which the ``pallas`` backend runs inside its
work envelope (``max_pairs`` in ``repro.kernels.tuning``), is compiled at
that bound for three keys-to-probes ratios; the relalg kernels, which the
backend does not run (``repro.kernels.relalg_ops`` says why), at the sizes
they were measured at on the chip.  Each is vmapped over the 8-worker axis
exactly as the engine's stages call a kernel, with the TPU block table (not
the host's) and the engine's global x64 setting.  The compiled program must
contain the Mosaic kernel (``tpu_custom_call``) — a refusal from the chip's
compiler fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
from __future__ import annotations

import os
from functools import partial

import pytest

import repro.core  # noqa: F401  (x64 on, as in production)
import jax
import jax.numpy as jnp

from repro.kernels.tuning import block_config

W = 8  # engine workers: every kernel runs vmapped over them


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` vmapped over W workers for the described chip."""
    args = [jax.ShapeDtypeStruct((W,) + s.shape, s.dtype, sharding=sharding)
            for s in shapes]
    text = jax.jit(jax.vmap(fn)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _env(kernel: str) -> dict[str, int]:
    return block_config(kernel, platform="tpu")


@pytest.mark.parametrize("n,m", [(1 << 18, 1 << 10), (1 << 16, 1 << 12),
                                 (1 << 13, 1 << 15)],
                         ids=["keys-heavy", "square-ish", "probes-heavy"])
@pytest.mark.parametrize("dtype", [jnp.int64, jnp.int32],
                         ids=["int64-index", "int32-candidates"])
def test_semijoin_probe_compiles(one_chip, dtype, n, m):
    from repro.kernels.semijoin.semijoin import probe_fits, semijoin_probe

    cfg = _env("semijoin_probe")
    assert n * m == cfg["max_pairs"]
    assert probe_fits(n, m) and not probe_fits(n + cfg["block_n"], m)
    fn = partial(semijoin_probe, block_m=cfg["block_m"],
                 block_n=cfg["block_n"], interpret=False)
    _compile(fn, one_chip, jax.ShapeDtypeStruct((n,), dtype),
             jax.ShapeDtypeStruct((m,), dtype))


def test_expand_compiles(one_chip):
    from repro.kernels.relalg_ops.expand import expand_pallas

    cfg = _env("relalg_expand")
    n, out_cap = 8 * 4096, 4096  # probe/reply gather: flat probes -> cap
    fn = partial(expand_pallas, out_cap=out_cap, block_m=cfg["block_m"],
                 block_n=cfg["block_n"], interpret=False)
    s = jax.ShapeDtypeStruct((n,), jnp.int32)
    _compile(fn, one_chip, s, s)


@pytest.mark.parametrize("k", [1, 3], ids=["hash-exchange", "reply"])
def test_bucket_by_dest_compiles(one_chip, k):
    from repro.kernels.relalg_ops.bucket import bucket_by_dest_pallas

    n = cap_peer = 8192
    fn = partial(bucket_by_dest_pallas, n_dest=W, cap_peer=cap_peer,
                 block_n=_env("relalg_bucket")["block_n"], interpret=False)
    _compile(fn, one_chip, jax.ShapeDtypeStruct((n, k), jnp.int32),
             jax.ShapeDtypeStruct((n,), jnp.int32),
             jax.ShapeDtypeStruct((n,), jnp.bool_))


def test_unique_compact_compiles(one_chip):
    from repro.kernels.relalg_ops.compact import unique_compact_pallas

    n = 1 << 14  # the whole array sorts in VMEM
    fn = partial(unique_compact_pallas, out_cap=n, pad=2**31 - 1,
                 interpret=False)
    _compile(fn, one_chip, jax.ShapeDtypeStruct((n,), jnp.int32),
             jax.ShapeDtypeStruct((n,), jnp.bool_))
