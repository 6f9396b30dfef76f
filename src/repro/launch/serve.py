"""Serving launcher: the adaptive-query pool (the serving subsystem's CLI),
batched prefill + decode, and **adaptive metric evaluation** — the paper's
ADS engine estimating a serve-side metric (mean per-token loss over a
prompt distribution) to (ε,δ) with empirical-Bernstein stopping instead of
a fixed eval-set sweep.

    # epoch-granular continuous batching over a mixed query stream
    PYTHONPATH=src python -m repro.launch.serve --pool \
        --queries wrs:shared:4,triangles:local:2:1 --max-in-flight 2 \
        [--checkpoint-dir CKPT [--resume] [--checkpoint-every 2]]
    # placement-aware: disjoint submeshes + pressure-driven elasticity
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --pool --substrate shard_map \
        --topology auto --pressure-policy shrink-regrow \
        --queries reachability:shared:4,reachability:shared:4:1,wrs:local:2
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m-reduced \
        --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m-reduced \
        --adaptive-eval --eps 0.1 --delta 0.1
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.epoch import EpochConfig, run_worker
from repro.core.frames import FrameStrategy, StateFrame, sequential_collectives
from repro.core.stopping import EmpiricalBernsteinCondition
from repro.data import TokenStream
from repro.models import Model


def _resolve_config(name: str):
    from repro.launch.train import _resolve_config as r
    return r(name)


def generate(model: Model, params, prompts: jax.Array, gen: int):
    """Greedy decode ``gen`` tokens for a (B, P) prompt batch."""
    cfg = model.cfg
    B, P = prompts.shape
    capacity = P + gen
    cache = model.init_cache(B, capacity)

    @partial(jax.jit, donate_argnums=(0,))
    def one(cache, tok, pos):
        return model.decode_step(params, cache, {"tokens": tok, "pos": pos})

    toks = prompts[:, 0]
    out = [toks]
    for t in range(capacity - 1):
        pos = jnp.full((B,), t, jnp.int32)
        cache, logits = one(cache, toks, pos)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = jnp.where(t + 1 < P, prompts[:, min(t + 1, P - 1)], nxt)
        out.append(toks)
    return jnp.stack(out, axis=1)  # (B, P+gen)


def adaptive_eval(model: Model, params, stream: TokenStream, *,
                  eps: float, delta: float, batch: int, seq: int,
                  max_epochs: int = 200):
    """(ε,δ)-estimate of mean per-token loss via the epoch engine."""
    cond = EmpiricalBernsteinCondition(eps=eps, delta=delta, value_range=15.0)

    @jax.jit
    def loss_of(params, tokens, labels):
        return model.train_loss(params, {"tokens": tokens, "labels": labels})

    def sample_fn(key, carry):
        step = jax.random.randint(key, (), 0, 1 << 30)
        b = stream.batch_at(step)
        l = loss_of(params, b["tokens"], b["labels"])
        return StateFrame(num=jnp.int32(1),
                          data={"s1": l, "s2": jnp.square(l)}), carry

    template = {"s1": jnp.zeros((), jnp.float32),
                "s2": jnp.zeros((), jnp.float32)}
    cfg = EpochConfig(strategy=FrameStrategy.LOCAL_FRAME, rounds_per_epoch=2,
                      max_epochs=max_epochs)
    st = run_worker(sample_fn, cond, template, None, jax.random.key(0), cfg,
                    colls=sequential_collectives())
    tau = float(st.total.num)
    mean = float(st.total.data["s1"]) / max(tau, 1.0)
    return mean, tau, bool(st.stop)


DEFAULT_POOL_QUERIES = "wrs:local:2,triangles:local:2:1"


def serve_pool(args) -> int:
    """Drive the epoch-granular scheduler over a query stream."""
    from repro.launch.mesh import make_device_pool
    from repro.serve import EpochScheduler, PressurePolicy, SessionSpec

    # --resume restores the checkpointed stream; the default query list only
    # applies to fresh pools (explicit --queries adds to a resumed one).
    queries = args.queries if args.queries is not None \
        else ("" if args.resume else DEFAULT_POOL_QUERIES)

    pool = make_device_pool(args.topology) if args.topology else None
    pressure = PressurePolicy.parse(args.pressure_policy)
    if pressure is not None and pool is None:
        print("[serve] --pressure-policy needs --topology (a device pool)")
        return 2
    if pool is not None:
        print(f"[serve] device pool: {pool.capacity} slot(s) in "
              f"{len(pool.topology.groups)} group(s)"
              + (f", pressure={args.pressure_policy}" if pressure else ""))

    if args.resume:
        if not args.checkpoint_dir:
            print("[serve] --resume needs --checkpoint-dir")
            return 2
        sched = EpochScheduler.resume(
            args.checkpoint_dir, max_in_flight=args.max_in_flight,
            substrate=args.substrate, pool=pool, pressure=pressure,
            checkpoint_every=args.checkpoint_every)
        print(f"[serve] resumed {sched.pending} session(s) from "
              f"{args.checkpoint_dir}")
    else:
        sched = EpochScheduler(max_in_flight=args.max_in_flight,
                               substrate=args.substrate,
                               pool=pool, pressure=pressure,
                               checkpoint_dir=args.checkpoint_dir or None,
                               checkpoint_every=args.checkpoint_every)
    for q in (s for s in queries.split(",") if s):
        sched.submit(SessionSpec.parse(q))

    t0 = time.time()
    while not sched.idle:
        ev = sched.tick()
        for qid, old_w, new_w in ev.resharded:
            word = "shrunk" if new_w < old_w else "regrown"
            print(f"[serve] tick {ev.tick}: {word} {qid} "
                  f"W={old_w} → {new_w} (pressure)")
        for qid in ev.retired:
            r = sched.results[qid]
            est = np.array2string(r.estimate, precision=4)
            place = f" dev={r.devices_leased}" \
                f" pwait={r.placement_wait_ticks}" if pool else ""
            print(f"[serve] tick {ev.tick}: retired {qid} "
                  f"τ={r.tau} epochs={r.epochs} wait={r.wait_ticks}"
                  f"{place} est={est}")
    dt = time.time() - t0
    n = len(sched.results)
    taus = sum(r.tau for r in sched.results.values())
    print(f"[serve] pool drained: {n} queries, {sched.tick_count} ticks, "
          f"{taus} samples in {dt:.1f}s ({taus / max(dt, 1e-9):.0f} "
          f"samples/s, {len(sched.cache)} compiled steppers)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m-reduced")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--adaptive-eval", action="store_true")
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--delta", type=float, default=0.1)
    ap.add_argument("--seq", type=int, default=64)
    # ----- adaptive-query pool (repro.serve scheduler) -----
    ap.add_argument("--pool", action="store_true",
                    help="run the adaptive-query pool scheduler")
    ap.add_argument("--queries", default=None,
                    help="comma-separated instance:strategy:world[:seed] "
                         f"(default for fresh pools: {DEFAULT_POOL_QUERIES}; "
                         "--resume defaults to the restored stream only)")
    ap.add_argument("--max-in-flight", type=int, default=2)
    ap.add_argument("--substrate", default=None)
    ap.add_argument("--topology", default="",
                    help="device pool topology: 'auto' (live JAX runtime), "
                         "'N' (one group of N), or 'GxN' (G groups of N); "
                         "empty = no placement pool (legacy sharing)")
    ap.add_argument("--pressure-policy", default="none",
                    help="none | shrink | shrink-regrow[:min=N] — resize "
                         "SHARED_FRAME sessions under queued load "
                         "(needs --topology)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore sessions from --checkpoint-dir")
    args = ap.parse_args(argv)

    if args.pool:
        return serve_pool(args)

    cfg = _resolve_config(args.arch)
    model = Model(cfg, None)
    params = model.init(jax.random.key(args.seed))

    if args.adaptive_eval:
        stream = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                             batch=args.batch, seed=args.seed)
        t0 = time.time()
        mean, tau, stopped = adaptive_eval(
            model, params, stream, eps=args.eps, delta=args.delta,
            batch=args.batch, seq=args.seq)
        print(f"[serve] adaptive eval: mean loss = {mean:.4f} ± {args.eps} "
              f"(p ≥ {1-args.delta}) after {tau:.0f} samples "
              f"(stopped={stopped}, {time.time()-t0:.1f}s)")
        return 0

    stream = TokenStream(vocab=cfg.vocab, seq_len=args.prompt_len,
                         batch=args.batch, seed=args.seed)
    prompts = stream.batch_at(jnp.int32(0))["tokens"]
    t0 = time.time()
    out = generate(model, params, prompts, args.gen)
    dt = time.time() - t0
    n_new = args.batch * args.gen
    print(f"[serve] generated {n_new} tokens in {dt:.1f}s "
          f"({n_new/dt:.1f} tok/s); sample row: "
          f"{np.asarray(out[0, -args.gen:]).tolist()}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
