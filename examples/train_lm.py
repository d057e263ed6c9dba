"""End-to-end LM training driver (deliverable (b) e2e): trains a ~100M
decoder on synthetic token streams with the full production loop —
step-seeded data, AdamW, checkpoint/restore. On this 1-core CPU
container the default is a scaled-down model and step count so the
example finishes in minutes; ``--full`` selects the ~100M config (the
same code path, sized for a TPU host).

    PYTHONPATH=src python examples/train_lm.py --steps 40
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data.synthetic import lm_batch_stream
from repro.models.transformer import TransformerConfig, init_params
from repro.training.optim import AdamWConfig, adamw_update, \
    train_state_init
from repro.configs.base import LMArch

SMALL = TransformerConfig(          # ~2M params: CPU-friendly demo
    name="demo-2m", n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
    d_ff=512, vocab=2048, dtype="float32", remat=False)

FULL_100M = TransformerConfig(      # ~100M params: TPU-host scale
    name="demo-100m", n_layers=12, d_model=768, n_heads=12,
    n_kv_heads=4, d_ff=3072, vocab=32768, dtype="bfloat16")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = FULL_100M if args.full else SMALL
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.1f}M params)")
    arch = LMArch(cfg.name, cfg, cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = train_state_init(params)
    opt = AdamWConfig(lr=3e-4, warmup_steps=10, total_steps=args.steps)

    from repro.models.transformer import loss_fn

    @jax.jit
    def step_fn(state, tokens, labels):
        (l, ce), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, tokens, labels),
            has_aux=True)(state.params)
        new_state, gnorm = adamw_update(state, grads, opt)
        return new_state, l

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    stream = lm_batch_stream(args.batch, args.seq, cfg.vocab)
    losses, t0 = [], time.time()
    for i in range(args.steps):
        b = next(stream)
        state, loss = step_fn(state, jnp.asarray(b["tokens"]),
                              jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if i % 10 == 0:
            print(f"step {i:4d} loss {losses[-1]:.4f}")
        if ckpt and (i + 1) % 20 == 0:
            ckpt.save_async(i + 1, state)
    if ckpt:
        ckpt.wait()
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} in "
          f"{time.time()-t0:.1f}s")
    assert losses[-1] < losses[0]
    print("train_lm OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
