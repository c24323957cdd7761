"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --steps 200 \
      --batch 8 --seq 256 --reduced --ckpt /tmp/ckpt

``--reduced`` runs the CPU-sized variant of the arch (the full configs are
for the production mesh; this container has one device). On a real cluster
the same entry point runs with ``--mesh-data/--mesh-model`` spanning the
pod; the Trainer, sharding rules and checkpoint format are identical.
"""
from __future__ import annotations

import argparse


from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.optim.adamw import OptConfig
from repro.runtime.trainer import FaultPlan, TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject synthetic faults at these steps (recovery demo)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_debug_mesh(args.mesh_data, args.mesh_model)
    tc = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt, global_batch=args.batch,
                     seq_len=args.seq, n_micro=args.n_micro)
    opt = OptConfig(lr=args.lr, total_steps=args.steps,
                    warmup_steps=max(args.steps // 20, 5))
    tr = Trainer(cfg, tc, mesh, opt)
    plan = FaultPlan(args.fail_at) if args.fail_at else None
    out = tr.run(fault_plan=plan)
    print(f"done: final_loss={out['final_loss']:.4f} "
          f"stragglers={out['stragglers']} events={out['events']}")


if __name__ == "__main__":
    main()
