"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --smoke --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/run1

On this CPU container ``--smoke`` selects the reduced config; on a real
cluster the same driver takes the full config + production mesh.  The
loop is restart-safe: rerunning with the same --ckpt-dir resumes from the
last checkpoint (fault tolerance / elasticity path).
"""

from __future__ import annotations

import argparse
import json
import time


from repro import configs
from repro.data import data_iterator
from repro.distributed.sharding import BASELINE_RULES, prune_to_mesh, \
    adapt_rules_for
from repro.launch.mesh import make_host_mesh
from repro.training import Trainer, TrainConfig, OptimizerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    mesh = make_host_mesh()
    rules = adapt_rules_for(
        prune_to_mesh(BASELINE_RULES, mesh), mesh, n_kv=cfg.n_kv,
        n_experts=cfg.n_experts, n_heads=cfg.n_heads, d_ff=cfg.d_ff,
        vocab=cfg.padded_vocab)

    tcfg = TrainConfig(
        num_microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, log_every=args.log_every,
        opt=OptimizerConfig(lr=args.lr, warmup_steps=min(100, args.steps),
                            total_steps=args.steps))
    trainer = Trainer(cfg, rules, tcfg, mesh=None)
    start = trainer.init(args.seed)
    print(f"training {cfg.name} from step {start} "
          f"(batch={args.batch} seq={args.seq})")
    it = data_iterator(cfg, args.batch, args.seq, start_step=start,
                       seed=args.seed)
    t0 = time.time()
    hist = trainer.run(it, args.steps - start)
    dt = time.time() - t0
    steps_done = args.steps - start
    print(f"{steps_done} steps in {dt:.1f}s "
          f"({steps_done / max(dt, 1e-9):.2f} steps/s)")
    for h in hist:
        print({k: round(v, 4) for k, v in h.items()})
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(hist, f, indent=1)


if __name__ == "__main__":
    from repro.backend import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    main()
