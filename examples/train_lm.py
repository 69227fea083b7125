"""End-to-end training driver: train a ~130M-param model for a few hundred
steps on CPU with the full stack — sharded params, AdamW, checkpointing,
and the AdHash-style adaptive embedding controller in the loop.

Run (quick):   PYTHONPATH=src python examples/train_lm.py --steps 30
Run (full):    PYTHONPATH=src python examples/train_lm.py \
                   --arch mamba2-130m --steps 300 --batch 8 --seq 512
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.core.adaptive import AdaptiveShardingController
from repro.checkpoint.checkpoint import CheckpointManager
from repro.data.tokens import synthetic_batches
from repro.launch.mesh import make_local_mesh
from repro.launch.shardings import named, param_specs
from repro.launch.train import make_train_step
from repro.models.model_zoo import build_model
from repro.optim.adamw import AdamWConfig, adamw_init


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = make_local_mesh()
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.0f}M "
          f"mesh={dict(mesh.shape)}")

    params = model.init(jax.random.key(0))
    params = jax.device_put(params, named(mesh, param_specs(params, mesh)))
    opt = adamw_init(params)
    step_fn = jax.jit(make_train_step(model, AdamWConfig(lr=3e-4)),
                      donate_argnums=(0, 1))

    # the paper's controller, watching token access (Zipf -> hot rows)
    ctrl = AdaptiveShardingController(
        cfg.vocab_size,
        budget=cfg.adaptive.embedding_hot_budget if cfg.adaptive else 1024,
    )
    ckpt = CheckpointManager(args.ckpt, async_save=True)

    t0 = time.perf_counter()
    losses = []
    for step, batch in enumerate(
        synthetic_batches(cfg, args.batch, args.seq, args.steps)
    ):
        ctrl.observe(np.asarray(batch["tokens"]))
        params, opt, metrics = step_fn(params, opt, batch)
        losses.append(float(metrics["loss"]))
        if step % 10 == 0:
            plan = ctrl.replan()
            print(
                f"step {step:4d} loss {losses[-1]:.4f} "
                f"gnorm {float(metrics['grad_norm']):.2f} "
                f"hot={plan.n_hot} coverage={plan.coverage:.2f} "
                f"({time.perf_counter() - t0:.0f}s)"
            )
        if (step + 1) % 50 == 0:
            ckpt.save(params, opt, step + 1)
    ckpt.wait()
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f}); "
          f"checkpoint at step {ckpt.latest_step()}")
    assert losses[-1] < losses[0], "training must reduce loss"


if __name__ == "__main__":
    main()
