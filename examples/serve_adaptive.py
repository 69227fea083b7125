"""Serve a small *language model* with batched decode + adaptive embedding.

Demonstrates the paper's pay-as-you-go loop on the LM side: the controller
watches request token ids, replicates the hot rows, and the embedding's
cold-exchange capacity shrinks — the LM equivalent of queries flipping
from distributed to parallel mode.  For the RDF engine's own online
serving front-end (continuous batching under an SLO, admission control,
load shedding — :mod:`repro.serving`), see ``examples/serve_rdf.py``.

Run:  PYTHONPATH=src python examples/serve_adaptive.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.core.adaptive import AdaptiveShardingController
from repro.data.tokens import zipf_tokens
from repro.launch.train import make_serve_step
from repro.models.model_zoo import build_model


def main() -> None:
    enable_compile_cache()
    cfg = get_smoke_config("qwen1.5-4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    serve = jax.jit(make_serve_step(model), donate_argnums=(1,))

    batch_size, max_len = 8, 64
    ctrl = AdaptiveShardingController(cfg.vocab_size, budget=64,
                                      threshold=0.5)
    rng = np.random.default_rng(0)

    for round_ in range(4):
        cache = model.init_cache(batch_size, max_len)
        prompt = zipf_tokens(rng, cfg.vocab_size, (batch_size, 1))
        tok = jnp.asarray(prompt, jnp.int32)
        generated = [tok]
        t0 = time.perf_counter()
        for pos in range(12):
            ctrl.observe(np.asarray(tok))
            tok, cache = serve(
                params, cache, {"tokens": tok, "pos": jnp.int32(pos)}
            )
            tok = tok[:, None]
            generated.append(tok)
        plan = ctrl.replan()
        cold = ctrl.cold_capacity(batch_size)
        print(
            f"round {round_}: decoded {len(generated) - 1} steps x "
            f"{batch_size} streams in {time.perf_counter() - t0:.2f}s | "
            f"hot rows={plan.n_hot} coverage={plan.coverage:.2f} "
            f"cold-exchange capacity={cold}/{batch_size}"
        )
    print("adaptive plan converged; hot ids:", plan.hot_ids[:10], "...")


if __name__ == "__main__":
    main()
