"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --seq 512 --batch 32 --quant swis --n-shifts 3 \
      --workdir results/run1 [--smoke] [--mesh-data 2 --mesh-model 4]

On a real TPU fleet this runs one process per host (jax.distributed
initializes from the TPU environment); device meshes come from
``repro.launch.mesh.make_production_mesh``. On CPU it runs single-process
(optionally with a forced host-device mesh for integration testing).
"""
from __future__ import annotations

import argparse
import json

import repro.configs as C
from repro.configs.base import QuantPolicy
from repro.core.swis import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.train.loop import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(C.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--quant", default="none",
                    choices=["none", "swis", "swis_c", "trunc"])
    ap.add_argument("--n-shifts", type=float, default=4)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--mesh-data", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = C.get_smoke(args.arch) if args.smoke else C.get_config(args.arch)
    if args.quant != "none":
        cfg = cfg.replace(quant=QuantPolicy(
            cfg=QuantConfig(method=args.quant, n_shifts=args.n_shifts,
                            group_size=args.group_size),
            mode="qat"))
    mesh = None
    if args.mesh_data and args.mesh_model:
        mesh = make_mesh((args.mesh_data, args.mesh_model), ("data", "model"))

    tr = Trainer(cfg, seq_len=args.seq, global_batch=args.batch,
                 workdir=args.workdir, total_steps=args.steps,
                 ckpt_every=args.ckpt_every, warmup=args.warmup,
                 peak_lr=args.lr, mesh=mesh)
    out = tr.run(args.steps)
    print(json.dumps({"arch": cfg.name, "steps": args.steps,
                      "first_loss": out["first_loss"],
                      "last_loss": out["last_loss"],
                      "stragglers": out["straggler_events"]}, indent=1))


if __name__ == "__main__":
    main()
