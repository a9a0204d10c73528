"""Production training launcher.

On a real pod slice this runs the sharded train step; on this CPU
container use --dry-run (equivalent to repro.launch.dryrun) or --local to
execute a reduced config for a few real steps on host devices.

  python -m repro.launch.train --arch qwen2-0.5b --shape train_4k --dry-run
  python -m repro.launch.train --arch qwen2-0.5b --local --steps 10
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--local", action="store_true",
                    help="run a reduced config for real on host devices")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    if args.dry_run:
        from repro.launch import dryrun
        rec = dryrun.run_one(args.arch, args.shape, multi_pod=args.multi_pod)
        print(rec)
        return 0

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.shapes import get_shape
    from repro.launch.steps import make_train_step
    from repro.models import backbone as bb

    cfg = get_config(args.arch)
    if args.local:
        cfg = cfg.reduced()
    shape = get_shape(args.shape)
    key = jax.random.PRNGKey(0)
    params = bb.init_params(cfg, key)
    opt = {"momentum": jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p), params)}
    B, T = (4, 32) if args.local else (shape.global_batch, shape.seq_len)
    import dataclasses
    local_shape = dataclasses.replace(shape, global_batch=B, seq_len=T)
    step = jax.jit(make_train_step(cfg, local_shape, lr=1e-3))

    for i in range(args.steps):
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(i), (B, T), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(i), (B, T), 0, cfg.vocab)}
        if cfg.vlm is not None:
            batch["patches"] = jax.random.normal(
                key, (B, cfg.vlm.n_patches, cfg.vlm.vision_dim))
        if cfg.encdec is not None:
            batch["frames"] = jax.random.normal(
                key, (B, cfg.encdec.n_frames, cfg.d_model))
        t0 = time.time()
        params, opt, metrics = step(params, opt, batch)
        print(f"step {i} loss {float(metrics['loss']):.4f} "
              f"({time.time() - t0:.2f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
