"""End-to-end training launcher.

Composes every substrate: deterministic data pipeline → (optionally
*adaptive*) gradient accumulation → AdamW → async checkpointing → failure
injection/recovery.  CPU-runnable with the reduced configs; the same loop
drives the production mesh on real hardware (the step fn is the one the
dry-run lowers).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m-reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --ckpt-every 20

``--adaptive`` switches gradient accumulation to the paper's ADS engine
(stop drawing microbatches once the gradient-variance bound holds).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data import DataCursor, TokenStream
from repro.models import Model, get_config
from repro.optim import (AdamWConfig, AdaptiveAccumConfig, adamw_init,
                         adaptive_accumulate)
from repro.optim.adamw import adamw_update
from repro.runtime import FailureEvent, FailureInjector, Heartbeat


def _resolve_config(name: str):
    if name.endswith("-reduced"):
        import importlib
        mod = name[: -len("-reduced")].replace("-", "_")
        return importlib.import_module(f"repro.configs.{mod}").reduced()
    return get_config(name)


def make_adaptive_step(model: Model, opt_cfg: AdamWConfig,
                       acc_cfg: AdaptiveAccumConfig):
    def loss_and_grad(params, batch):
        return jax.value_and_grad(model.train_loss)(params, batch)

    def step(params, opt_state, micro_batches):
        grads, loss, n_used, rel = adaptive_accumulate(
            lambda p, b: loss_and_grad(p, b), params, micro_batches, acc_cfg)
        params, opt_state, gnorm = adamw_update(grads, opt_state, params,
                                                opt_cfg)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "micro_used": n_used, "rel_sem": rel}

    return step


def make_fixed_step(model: Model, opt_cfg: AdamWConfig):
    from repro.launch.steps import make_train_step
    return make_train_step(model, opt_cfg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m-reduced")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--adaptive", action="store_true",
                    help="ADS-driven gradient accumulation")
    ap.add_argument("--rtol", type=float, default=0.3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failures", action="store_true")
    ap.add_argument("--preempt-at", type=int, default=-1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = _resolve_config(args.arch)
    cfg = dataclasses.replace(cfg, grad_accum=1)
    model = Model(cfg, None)
    opt_cfg = AdamWConfig(lr=args.lr)
    acc_cfg = AdaptiveAccumConfig(rtol=args.rtol,
                                  min_micro=min(2, args.micro),
                                  max_micro=args.micro)

    params = model.init(jax.random.key(args.seed))
    opt_state = adamw_init(params)
    stream = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                         batch=args.batch, seed=args.seed)
    cursor = DataCursor(step=0, seed=args.seed)

    manager = None
    if args.ckpt_dir:
        manager = CheckpointManager(Path(args.ckpt_dir), keep=2)
        if args.resume:
            restored = manager.restore_latest({"params": params,
                                               "opt": opt_state})
            if restored:
                step0, tree, meta = restored
                params, opt_state = tree["params"], tree["opt"]
                cursor = DataCursor.from_meta(meta)
                print(f"[train] resumed at step {step0} "
                      f"(data cursor {cursor.step})")

    injector = FailureInjector(
        seed=args.seed + 1,
        crash_prob=0.02 if args.inject_failures else 0.0,
        straggler_prob=0.05 if args.inject_failures else 0.0,
        preempt_at_step=args.preempt_at if args.preempt_at >= 0 else None)
    heartbeat = Heartbeat(deadline_s=120.0, on_late=lambda dt: print(
        f"[train] WARN slow step: {dt:.1f}s (straggler suspect)"))

    step_fn = jax.jit(make_adaptive_step(model, opt_cfg, acc_cfg)
                      if args.adaptive else make_fixed_step(model, opt_cfg),
                      donate_argnums=(0, 1))

    t_start = time.time()
    step = cursor.step
    losses = []
    while step < args.steps:
        heartbeat.start()
        event = injector.poll(step)
        if event == FailureEvent.WORKER_CRASH and manager is not None:
            print(f"[train] step {step}: injected WORKER_CRASH — "
                  f"restoring from last checkpoint")
            restored = manager.restore_latest({"params": params,
                                               "opt": opt_state})
            if restored:
                _, tree, meta = restored
                params, opt_state = tree["params"], tree["opt"]
                cursor = DataCursor.from_meta(meta)
                step = cursor.step
        if event == FailureEvent.PREEMPTION and manager is not None:
            print(f"[train] step {step}: PREEMPTION — checkpoint + exit")
            manager.save({"params": params, "opt": opt_state}, step,
                         meta=DataCursor(step=step, seed=args.seed).as_meta())
            manager.wait()
            return 0

        batch = stream.micro_batches(jnp.int32(step), args.micro)
        if not args.adaptive:
            if args.micro == 1:
                batch = jax.tree.map(lambda x: x[0], batch)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = heartbeat.stop()
        if step % args.log_every == 0 or step == args.steps - 1:
            extra = ""
            if args.adaptive:
                extra = (f" micro={int(metrics['micro_used'])}"
                         f" rel_sem={float(metrics['rel_sem']):.3f}")
            print(f"[train] step {step:5d} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"{dt*1e3:6.0f}ms{extra}")
        step += 1
        if manager is not None and step % args.ckpt_every == 0:
            manager.save({"params": params, "opt": opt_state}, step,
                         meta=DataCursor(step=step, seed=args.seed).as_meta())
    if manager is not None:
        manager.save({"params": params, "opt": opt_state}, step,
                     meta=DataCursor(step=step, seed=args.seed).as_meta())
        manager.wait()
    n = max(len(losses) // 10, 1)
    print(f"[train] done in {time.time()-t_start:.1f}s; "
          f"loss {sum(losses[:n])/n:.4f} → {sum(losses[-n:])/n:.4f}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
