"""Pallas kernel validation (interpret mode) vs pure-jnp oracles:
shape/dtype sweeps with assert_allclose (deliverable c)."""
from __future__ import annotations

import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on, as in production)
import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.semijoin.ops import batched_semijoin_probe
from repro.kernels.semijoin.ref import semijoin_probe_ref
from repro.kernels.semijoin.semijoin import semijoin_probe


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("t,s", [(128, 128), (256, 256), (128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(t, s, dtype, causal):
    if causal and t != s:
        pytest.skip("causal requires square here")
    b, h, d = 2, 3, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, t, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, d)
    ref = attention_ref(qf, kf, vf, causal=causal)
    ref = jnp.moveaxis(ref.reshape(b, h, t, d), 1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("block_q,block_kv", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_sweep(block_q, block_kv):
    b, h, t, d = 1, 2, 256, 32
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_kv=block_kv, interpret=True)
    base = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-5)


# ----------------------------------------------------------------- semijoin
@pytest.mark.parametrize("n,m", [(100, 37), (2048, 256), (5000, 1000)])
@pytest.mark.parametrize("seed", [0, 1])
def test_semijoin_probe_matches_searchsorted(n, m, seed):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, 10 * n, n)).astype(np.int64)
    probes = rng.integers(-5, 10 * n + 5, m).astype(np.int64)
    lo, hi = semijoin_probe(jnp.asarray(keys), jnp.asarray(probes),
                            interpret=True)
    rlo, rhi = semijoin_probe_ref(jnp.asarray(keys), jnp.asarray(probes))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))


def test_semijoin_probe_padded_keys():
    """INT64_MAX padding (the triple-store convention) never matches."""
    keys = jnp.asarray(
        np.concatenate([np.arange(10), [np.iinfo(np.int64).max] * 6]),
        jnp.int64,
    )
    probes = jnp.asarray([0, 5, 9, 100], jnp.int64)
    lo, hi = semijoin_probe(keys, probes, interpret=True)
    np.testing.assert_array_equal(np.asarray(hi - lo), [1, 1, 1, 0])


@pytest.mark.parametrize("w", [1, 3])
def test_batched_semijoin_probe(w):
    rng = np.random.default_rng(2)
    keys = np.sort(rng.integers(0, 1000, (w, 512)), axis=1).astype(np.int64)
    probes = rng.integers(0, 1000, (w, 100)).astype(np.int64)
    lo, hi = batched_semijoin_probe(jnp.asarray(keys), jnp.asarray(probes))
    for i in range(w):
        rlo, rhi = semijoin_probe_ref(
            jnp.asarray(keys[i]), jnp.asarray(probes[i])
        )
        np.testing.assert_array_equal(np.asarray(lo[i]), np.asarray(rlo))
        np.testing.assert_array_equal(np.asarray(hi[i]), np.asarray(rhi))


def test_semijoin_against_triple_store_probe():
    """Kernel agrees with the engine's probe_values on real composite keys."""
    from repro.core.partition import partition_by_subject
    from repro.core.triples import ShardedTripleStore, probe_values

    rng = np.random.default_rng(3)
    triples = np.unique(
        np.stack(
            [rng.integers(0, 50, 400), 50 + rng.integers(0, 4, 400),
             rng.integers(0, 50, 400)], axis=1
        ).astype(np.int64),
        axis=0,
    )
    w = 4
    store = ShardedTripleStore.build(
        triples, partition_by_subject(triples, w), w
    )
    p_const = jnp.int32(51)
    vals = jnp.asarray(rng.integers(0, 50, (w, 32)), jnp.int32)
    valid = jnp.ones((w, 32), bool)
    lo_ref, hi_ref = probe_values(store, p_const, vals, valid, col=0,
                                  nid=store.n_ids)
    nid = store.n_ids
    probes = jnp.int64(51) * nid + vals.astype(jnp.int64)
    lo_k, hi_k = batched_semijoin_probe(store.keys_ps, probes)
    counts = jnp.minimum(hi_k, store.counts[:, None]) - jnp.minimum(
        lo_k, store.counts[:, None]
    )
    np.testing.assert_array_equal(
        np.asarray(hi_ref - lo_ref), np.asarray(counts)
    )


def test_tuned_table_overlays_defaults_and_unreadable_table_raises(
        tmp_path, monkeypatch):
    """A platform table overrides block sizes and keeps the envelopes; a
    table that cannot be read is an error, not a silent fallback."""
    from repro.kernels.tuning import DEFAULTS, block_config, save_tuned

    monkeypatch.setenv("ADHASH_TUNED_DIR", str(tmp_path / "ok"))
    save_tuned({"semijoin_probe": {"block_m": 1024}}, platform="cpu")
    cfg = block_config("semijoin_probe", platform="cpu")
    assert cfg["block_m"] == 1024
    assert cfg["max_pairs"] == DEFAULTS["semijoin_probe"]["max_pairs"]

    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "cpu.json").write_text("{not json")
    monkeypatch.setenv("ADHASH_TUNED_DIR", str(bad))
    with pytest.raises(RuntimeError, match="unreadable kernel table"):
        block_config("semijoin_probe", platform="cpu")
