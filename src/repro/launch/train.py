"""Training driver (deliverable b/e): LoRA fine-tuning with
checkpoint/restart fault tolerance, NaN guards, and optional elastic
restore onto a different mesh.

Reduced configs run end-to-end on CPU (this container); full configs
target the production mesh (same code path — pjit re-lowers per mesh).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt /tmp/ck
  ... --restore            # resume from the latest checkpoint
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.engine import make_engine
from repro.data.synthetic import SyntheticDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.grad_noise import NoiseScaleEMA


def run_training(arch: str, *, smoke: bool = True, steps: int = 100,
                 batch: int = 8, seq: int = 64,
                 ckpt_dir: Optional[str] = None, restore: bool = False,
                 ckpt_every: int = 25, lr: float = 3e-3,
                 seed: int = 0, log_every: int = 10,
                 inject_nan_at: int = -1, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.scaled()
    engine = make_engine(cfg, lr=lr)
    model = engine.model
    key = jax.random.key(seed)
    params = model.init(key)
    lora = model.init_lora(jax.random.key(seed + 1))
    opt_state = engine.optimizer.init(lora)
    data = SyntheticDataset("alpaca", vocab_size=cfg.vocab_size,
                            seq_len=seq, seed=seed)
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if ckpt and restore:
        lat = ckpt.latest_step()
        if lat is not None:
            (lora, opt_state), extra = ckpt.restore(
                jax.eval_shape(lambda: (lora, opt_state)))
            start_step = lat
            if verbose:
                print(f"restored step {lat}")

    jit_step = jax.jit(engine.train_step, donate_argnums=(1, 2))

    def _snap(tree):
        # the rollback snapshot must own its buffers: jit_step DONATES
        # lora/opt_state, so an aliasing snapshot would hold deleted
        # device memory on any backend that honors donation
        return jax.tree.map(jnp.copy, tree)

    noise = NoiseScaleEMA()
    losses = []
    last_good = (_snap(lora), _snap(opt_state), start_step)
    t0 = time.time()
    step = start_step
    while step < steps:
        b = {k: jnp.asarray(v) for k, v in data.batch(batch).items()}
        if cfg.family.value == "vlm":
            b["vision"] = jnp.zeros((batch, cfg.vision_tokens, cfg.d_model),
                                    jnp.float32)
        if cfg.encoder_only:
            b["embeds"] = jax.random.normal(
                jax.random.key(step), (batch, seq, cfg.d_model))
        new_lora, new_opt, metrics = jit_step(params, lora, opt_state, b)
        loss = float(metrics["ce_loss"])
        if inject_nan_at == step:
            loss = float("nan")   # fault-injection hook for tests
        if not np.isfinite(loss):
            # fault tolerance: roll back to the last good state
            if verbose:
                print(f"step {step}: non-finite loss; restoring "
                      f"step {last_good[2]}")
            lora, opt_state, step = last_good
            if ckpt:
                lat = ckpt.latest_step()
                if lat is not None:
                    (lora, opt_state), _ = ckpt.restore(
                        jax.eval_shape(lambda: (lora, opt_state)))
                    step = lat
            inject_nan_at = -1
            continue
        lora, opt_state = new_lora, new_opt
        losses.append(loss)
        step += 1
        if ckpt and step % ckpt_every == 0:
            ckpt.save(step, (lora, opt_state),
                      extra={"arch": arch, "loss": loss})
            last_good = (_snap(lora), _snap(opt_state), step)
        if verbose and step % log_every == 0:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"{(time.time() - t0) / max(step - start_step, 1):.3f}"
                  f" s/step")
    if ckpt:
        ckpt.save(steps, (lora, opt_state), extra={"arch": arch})
        ckpt.wait()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "lora": lora, "steps": step}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    enable_compile_cache()
    out = run_training(args.arch, smoke=args.smoke, steps=args.steps,
                       batch=args.batch, seq=args.seq,
                       ckpt_dir=args.ckpt, restore=args.restore,
                       lr=args.lr)
    fl = out["final_loss"]
    # final_loss is None when a restore lands at step >= --steps (no
    # new step runs, so there is no loss to report)
    print(f"done: {out['steps']} steps, final loss "
          + (f"{fl:.4f}" if fl is not None else "n/a (already complete)"))


if __name__ == "__main__":
    main()
