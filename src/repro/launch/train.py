"""Training launcher.

Two modes:
  * generic arch training on synthetic LM data (reduced configs run on
    CPU; full configs are for the dry-run only):
      python -m repro.launch.train --arch phi4-mini-3.8b --reduced \
          --steps 50 --batch 8 --seq 128
  * the AVERY offline phase (lisa-mini + fine-tune + bottleneck tiers +
    LUT), producing checkpoints consumed by serve.py / benchmarks:
      python -m repro.launch.train --lisa --steps 300
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro import optim
from repro.checkpoint import save_pytree
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data import lm
from repro.models import init_params, make_train_step


def train_arch(arch: str, reduced: bool, steps: int, batch: int, seq: int,
               lr: float, out: str) -> None:
    cfg = get_reduced(arch) if reduced else get_config(arch)
    print(f"[train] {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"params={cfg.param_count()/1e6:.1f}M")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = optim.adamw(optim.cosine_with_warmup(lr, max(1, steps // 10), steps))
    state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt))
    stream = lm.lm_stream(0, cfg, batch, seq)
    t0 = time.time()
    for i in range(steps):
        batch_np = next(stream)
        params, state, metrics = step_fn(
            params, state, {k: jax.numpy.asarray(v)
                            for k, v in batch_np.items()})
        if i % max(1, steps // 10) == 0 or i == steps - 1:
            print(f"  step {i:5d} loss={float(metrics['loss']):.4f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    if out:
        save_pytree(os.path.join(out, cfg.name), params)
        print(f"[train] saved params to {out}/{cfg.name}")


def train_lisa_system(steps: int, bn_steps: int, ft_steps: int, out: str
                      ) -> None:
    from repro.configs.lisa_mini import CONFIG as pcfg
    from repro.core import profile as prof
    params, params_ft, bns = prof.train_full_system(
        pcfg, steps=steps, bn_steps=bn_steps, ft_steps=ft_steps)
    lut = prof.build_lut(pcfg, params, params_ft, bns)
    os.makedirs(out, exist_ok=True)
    save_pytree(os.path.join(out, "lisa_mini_original"), params)
    save_pytree(os.path.join(out, "lisa_mini_finetuned"), params_ft)
    for r, bp in bns.items():
        save_pytree(os.path.join(out, f"bottleneck_r{r}"), bp)
    lut.save(os.path.join(out, "lut.json"))
    print("[train] LUT:")
    for t in lut.tiers:
        print(f"  {t.name:16s} r={t.ratio:<5} base={t.acc_base:.4f} "
              f"ft={t.acc_finetuned:.4f} payload={t.payload_mb:.2f}MB")
    print(f"[train] artifacts saved under {out}/")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--lisa", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--bn-steps", type=int, default=200)
    ap.add_argument("--ft-steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--out", default="benchmarks/artifacts/checkpoints")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    if args.lisa:
        train_lisa_system(args.steps, args.bn_steps, args.ft_steps, args.out)
    elif args.arch:
        train_arch(args.arch, args.reduced, args.steps, args.batch, args.seq,
                   args.lr, args.out)
    else:
        ap.error("pass --arch <id> or --lisa")


if __name__ == "__main__":
    main()
