"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

Uses the deepseek-coder family config scaled to ~100M params (the brief's
"train ~100M model for a few hundred steps" deliverable), the production
train_step (ZeRO specs no-op on one device), deterministic token stream,
and async checkpointing with restart.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointConfig, Checkpointer
from repro.configs.base import ModelConfig
from repro.data.tokens import TokenStreamConfig, batch_at_step
from repro.distributed.sharding import NULL_LAYOUT
from repro.models import transformer as tfm
from repro.optim import OptConfig, opt_init
from repro.train.train_step import TrainHParams, TrainState, make_train_step

# ~100M params: 12L x 512 with a 32k vocab
CFG = ModelConfig(
    name="repro-110m", family="dense", n_layers=12, d_model=512, n_heads=8,
    n_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32_768, act="silu",
    dtype="float32",
)


def main():
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--ckpt", default="results/ckpt_train_lm")
    args = ap.parse_args()

    print(f"params: {CFG.param_count()/1e6:.1f}M")
    hp = TrainHParams(peak_lr=3e-4, warmup=20, total_steps=args.steps,
                      opt=OptConfig(name="adamw", weight_decay=0.01))
    params, _ = tfm.init_model(jax.random.PRNGKey(0), CFG)
    state = TrainState(params=params, opt=opt_init(params, hp.opt),
                       step=jnp.zeros((), jnp.int32))
    ckpt = Checkpointer(CheckpointConfig(directory=args.ckpt, keep=2))
    if ckpt.latest_step() is not None:
        state = ckpt.restore(state)
        print(f"resumed at step {int(state.step)}")

    step_fn = jax.jit(make_train_step(CFG, NULL_LAYOUT, hp))
    ds = TokenStreamConfig(vocab_size=CFG.vocab_size, seq_len=args.seq_len,
                           global_batch=args.batch, seed=0)
    t0 = time.perf_counter()
    first = None
    start_step = int(state.step)  # snapshot: state is reassigned in the loop
    if start_step >= args.steps:
        print(f"already trained to step {start_step}; nothing to do")
        return
    for step in range(start_step, args.steps):
        batch = jax.tree.map(jnp.asarray, batch_at_step(ds, step))
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        first = loss if first is None else first
        if step % 20 == 0 or step == args.steps - 1:
            tput = args.batch * args.seq_len / max((time.perf_counter() - t0) / (step - start_step + 1), 1e-9)
            print(f"step {step:4d}  loss {loss:.4f}  gnorm "
                  f"{float(metrics['grad_norm']):7.2f}  lr {float(metrics['lr']):.2e}",
                  flush=True)
        if step and step % 100 == 0:
            ckpt.save(step, state)  # async
    ckpt.save(args.steps, state, blocking=True)
    print(f"done: loss {first:.3f} -> {loss:.3f} in {time.perf_counter()-t0:.0f}s")
    assert loss < first, "loss did not improve"


if __name__ == "__main__":
    main()
