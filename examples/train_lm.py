"""End-to-end training driver: train a ~100M-class reduced model for a few
hundred steps on CPU with the full production loop — sharded(1×1) params,
microbatch accumulation, async checkpointing, fault-tolerant supervision.

    PYTHONPATH=src python examples/train_lm.py --arch qwen2.5-3b --steps 200
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import AsyncCheckpointer, latest_step_dir, restore
from repro.configs.registry import ARCHS
from repro.models import Model
from repro.training.data import DataConfig, SyntheticLM
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import TrainConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--accum", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/fame_train_ckpt")
    args = ap.parse_args()

    base = ARCHS[args.arch]
    n_layers = (args.layers if len(base.pattern) == 1
                else len(base.pattern) * max(1, args.layers // len(base.pattern)))
    cfg = base.reduced(dtype="float32", param_dtype="float32",
                       d_model=args.d_model, num_heads=8, head_dim=32,
                       d_ff=4 * args.d_model if base.d_ff else 0,
                       vocab_size=2048, num_layers=n_layers,
                       rglru_dim=args.d_model if base.rglru_dim else 0)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M layers={cfg.num_layers}")

    data = SyntheticLM(DataConfig(global_batch=args.batch, seq_len=args.seq,
                                  vocab_size=cfg.vocab_size), cfg)
    tcfg = TrainConfig(opt=AdamWConfig(lr=3e-3, warmup_steps=20,
                                       total_steps=args.steps),
                       accum_steps=args.accum)
    step_fn = jax.jit(make_train_step(cfg, tcfg))
    opt = init_opt_state(params)
    ckpt = AsyncCheckpointer(args.ckpt_dir)

    start = 0
    if latest_step_dir(args.ckpt_dir):
        (params, opt), start = restore(args.ckpt_dir, (params, opt))
        print(f"resumed from checkpoint at step {start}")

    t0 = time.time()
    for step in range(start, args.steps):
        batch = data.batch_at(step)
        params, opt, metrics = step_fn(params, opt, batch)
        if step % 20 == 0 or step == args.steps - 1:
            toks = args.batch * args.seq * (step - start + 1)
            print(f"step {step:4d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"grad_norm={float(metrics['grad_norm']):.3f} "
                  f"tok/s={toks / (time.time() - t0):.0f}")
        if step and step % 50 == 0:
            ckpt.save(step, (params, opt))
    ckpt.save(args.steps, (params, opt))
    ckpt.wait()
    print(f"done; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
