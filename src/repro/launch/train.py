"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Single-host it trains a reduced (or full, if you have the silicon) config
end-to-end with the fault-tolerant Trainer: sharded across whatever mesh
fits the local devices, restart-from-checkpoint on relaunch, synthetic or
token-shard data.  On a real multi-host pod the same file runs under
``jax.distributed.initialize()`` (flag --distributed); the mesh builder and
sharding rules are the ones the dry-run proves out at (2, 16, 16).
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.data.sharded import TokenShardDataset, write_synthetic_shards
from repro.data.synthetic import make_batch
from repro.distributed import annotate, sharding
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.registry import get_model
from repro.train.loop import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-dir", default=None, help="token shards (else synthetic)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--distributed", action="store_true", help="multi-host init")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    if args.distributed:
        jax.distributed.initialize()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    model = get_model(cfg)
    print(f"arch={cfg.name} params={model.n_params/1e6:.1f}M "
          f"active={model.n_active_params/1e6:.1f}M")

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = make_local_mesh(data=len(jax.devices()), model=1)

    tcfg = TrainConfig(
        peak_lr=args.lr,
        warmup_steps=max(1, args.steps // 10),
        total_steps=args.steps,
        microbatches=args.microbatches,
        remat=args.remat,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
    )

    with mesh, annotate.annotations(mesh):
        key = jax.random.PRNGKey(args.seed)
        params = model.init(key)
        params = jax.device_put(params, sharding.param_shardings(params, mesh))

        trainer = Trainer(model, tcfg, params)
        if args.resume and trainer.try_resume():
            print(f"resumed from step {trainer.step}")

        if args.data_dir:
            ds = TokenShardDataset(
                args.data_dir,
                seq_len=args.seq,
                global_batch=args.batch,
                codebooks=cfg.n_codebooks if cfg.frontend == "audio_codec" else 0,
            )
            def batches():
                step = trainer.step
                while True:
                    b = {k: jnp.asarray(v) for k, v in ds.batch_at(step).items()}
                    if cfg.frontend == "vit":
                        b["patch_embeds"] = jnp.zeros(
                            (args.batch, cfg.n_patches, cfg.vit_dim),
                            jnp.dtype(cfg.dtype),
                        )
                    yield b
                    step += 1
        else:
            def batches():
                step = trainer.step
                while True:
                    yield make_batch(
                        cfg, batch=args.batch, seq=args.seq, kind="train",
                        seed=args.seed + step,
                    )
                    step += 1

        metrics = trainer.run(batches(), args.steps)
        print({k: float(v) for k, v in metrics.items()})


if __name__ == "__main__":
    main()
