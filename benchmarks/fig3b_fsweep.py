"""Fig. 3b analog: shared-frame F parameter sweep.

The paper varies the number F of shared SF pairs on 36 cores: small F
minimizes memory bandwidth at the cost of atomics contention.  Our TPU
mapping (DESIGN.md §2): F = number of frame shards; F = W is a plain
reduce-scatter, F < W adds a cross-group all-reduce of n/F-sized partials.
We measure wall time AND report the per-worker frame memory, reproducing the
paper's memory/time trade-off axis."""

from __future__ import annotations

from benchmarks.common import emit, instances, timeit
from repro.core.epoch import EpochConfig, run_virtual
from repro.core.frames import FrameStrategy, shard_frame_pad
from repro.core.stopping import KadabraCondition
from repro.graphs import frame_template, make_sample_fn, preprocess
from repro.graphs.kadabra import init_counters


def run() -> None:
    g = instances()["er-social-s"]()
    pre = preprocess(g, eps=0.05, delta=0.1)
    W = 8
    for F in (1, 2, 4, 8):
        pad = shard_frame_pad(g.n, F)
        sample_fn = make_sample_fn(g, pre, batch=16, pad_to=pad)
        cond = KadabraCondition(eps=0.05, delta=0.1, omega=pre.omega,
                                n_vertices=g.n)
        cfg = EpochConfig(strategy=FrameStrategy.SHARED_FRAME,
                          rounds_per_epoch=4, max_epochs=3000)
        t = timeit(lambda F=F, pad=pad, s=sample_fn, c=cond, cf=cfg:
                   run_virtual(s, c, frame_template(g, pad), init_counters(),
                               0, W, cf,
                               frame_shards=F).total.num,
                   warmup=1, iters=2)
        mem_per_worker = pad // F * 4  # int32 shard bytes
        emit(f"fig3b/shared_frame/W={W}/F={F}", t,
             f"frame_bytes_per_worker={mem_per_worker}")


if __name__ == "__main__":
    run()
