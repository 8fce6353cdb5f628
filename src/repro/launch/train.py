"""Training launcher: mesh-aware pjit training with checkpoint/auto-resume.

On this host it runs real steps on the (n,1) host mesh with any smoke-scale
arch; on a pod the same code paths take the production mesh (the dry-run
proves every full-scale cell compiles).

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 100 --batch 16 --seq 64 --ckpt-dir /tmp/run1
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import get_config, smoke_config
from repro.core.precision import PrecisionConfig
from repro.core.softmax_variants import SoftmaxSpec
from repro.data.sharding import shard_batch
from repro.distributed.straggler import StragglerMonitor, mitigate
from repro.data.synthetic import SyntheticCorpus, family_batch
from repro.distributed.sharding import ShardingRules, use_mesh
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import Model
from repro.training.optimizer import AdamW, cosine_schedule
from repro.training.step import init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--softmax", default="fp", choices=["fp", "int", "fp_lowp"])
    ap.add_argument("--M", type=int, default=6)
    ap.add_argument("--N", type=int, default=16)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    spec = SoftmaxSpec(args.softmax, PrecisionConfig(M=args.M, N=args.N)) \
        if args.softmax == "int" else SoftmaxSpec(args.softmax)
    cfg = (smoke_config(args.arch, softmax=spec) if args.smoke
           else get_config(args.arch, softmax=spec))
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    rules = ShardingRules(cfg.sharding_overrides)
    model = Model(cfg, rules=rules, mesh=mesh)
    opt = AdamW(lr=cosine_schedule(args.lr, max(args.steps // 10, 1),
                                   args.steps))
    step_fn = jax.jit(make_train_step(model, opt,
                                      grad_compress=args.grad_compress))
    corpus = SyntheticCorpus(cfg.vocab, seed=1234)

    def cold():
        return init_state(model, opt, jax.random.PRNGKey(0),
                          grad_compress=args.grad_compress)

    mgr = CheckpointManager(args.ckpt_dir, interval=args.ckpt_every) \
        if args.ckpt_dir else None
    state, start = mgr.restore_or_init(cold) if mgr else (cold(), 0)
    if start:
        print(f"auto-resumed at step {start}")
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"softmax={cfg.softmax.kind}")

    monitor = StragglerMonitor()
    t0 = time.time()
    with use_mesh(mesh):
        for i in range(start, args.steps):
            t_step = time.time()
            batch = family_batch(cfg, args.batch, args.seq, seed=i,
                                 corpus=corpus)
            batch = shard_batch(batch, mesh, rules)
            state, met = step_fn(state, batch)
            jax.block_until_ready(met["loss"])
            rec = monitor.observe(time.time() - t_step)
            if rec.level >= 2:
                acted = mitigate(rec, mgr, state, i)
                print(f"[straggler] {rec.reason} -> {acted}")
            if mgr:
                mgr.maybe_save(i, state)
            if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss={float(met['loss']):.4f} "
                      f"acc={float(met['accuracy']):.3f} "
                      f"lr={float(met['lr']):.2e} "
                      f"{(time.time()-t0)/max(i-start+1,1):.2f}s/step")
    if mgr:
        mgr.maybe_save(args.steps, state, force=True)
        print(f"final checkpoint at {args.ckpt_dir}")


if __name__ == "__main__":
    main()
