"""End-to-end training launcher.

Single-host: ``python -m repro.launch.train --arch smollm-360m --smoke``
trains a reduced config on CPU; on a real cluster the same entry point uses
``jax.distributed.initialize`` + the production mesh and shards params/opt
state with the launch/mesh.py rules.
"""

from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLMDataset
from repro.launch import mesh as M
from repro.launch.compile_cache import enable_compile_cache
from repro.models import sharding as SH
from repro.train import CheckpointManager, TrainConfig, Trainer
from repro.train.trainer import init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--posit-division", action="store_true")
    ap.add_argument("--attn-backend", choices=["xla", "fused"], default="xla",
                    help="'fused' trains with posit division on the fused "
                         "Pallas backend and attention (fwd + recompute "
                         "bwd) through the posit flash kernel")
    ap.add_argument("--grad-compress", type=str, default=None,
                    choices=[None, "posit16", "posit8"])
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed + production mesh")
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    cfg = get_config(args.arch, smoke=args.smoke,
                     fused=args.attn_backend == "fused")
    if args.posit_division or args.grad_compress:
        cfg = cfg.with_numerics(
            posit_division=(args.posit_division
                            or cfg.numerics.posit_division),
            grad_compress_format=args.grad_compress)

    tc = TrainConfig(steps=args.steps, microbatches=args.microbatches,
                     lr=args.lr, ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
                     ckpt_dir=args.ckpt_dir)
    ds = SyntheticLMDataset(DataConfig(args.global_batch, args.seq_len), cfg,
                            host_id=jax.process_index(),
                            num_hosts=jax.process_count())

    if args.distributed:
        jax.distributed.initialize()
        mesh = M.make_production_mesh(multi_pod=jax.device_count() > 256)
        rules = M.arch_rules(cfg, mesh)
        state = init_train_state(cfg, tc, jax.random.PRNGKey(tc.seed))
        s_shard = M.named(mesh, M.state_pspecs(cfg, state, mesh))
        state = jax.device_put(state, s_shard)
        raw = make_train_step(cfg, tc)

        def step(s, b):
            with SH.use_rules(rules):
                return raw(s, b)

        with mesh:
            step_fn = jax.jit(step, in_shardings=(s_shard, None),
                              donate_argnums=0)
            ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
            trainer = Trainer(cfg, tc, ds, ckpt, train_step=step_fn, state=state)
            res = trainer.run()
    else:
        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        trainer = Trainer(cfg, tc, ds, ckpt)
        res = trainer.run()

    last = res["history"][-1]
    print(f"final: step {last['step']} loss {last['loss']:.4f} "
          f"({len(res['stragglers'])} straggler events)")


if __name__ == "__main__":
    main()
