"""A run of a cell at a tiny size on the CPU, with the chip look skipped
and, on request, a fault planted in the program underneath it.  The
harness lays the workers out from the cell's chips, one per device: on four
virtual CPU devices the one-chip cell's query runs as four workers under
``shard_map``, the paper's layout.

    python -m bench.tests.tiny <chips> <fault> <graph>   # prints JSON

``<fault>`` is one of FAULTS, or ``control`` for the control in the
program's place; ``<graph>`` one of GRAPHS.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax.numpy as jnp

FAULTS = ("none", "frozen", "half_batch", "no_exchange", "altered")


def plant(fault: str, patch) -> None:
    """Break the timed path: ``patch(obj, name, value)`` sets an attribute
    (pytest's ``monkeypatch.setattr`` or plain ``setattr``)."""
    import repro.core.frames as frames
    import repro.core.substrate as substrate
    import repro.graphs.kadabra as kadabra

    if fault == "frozen":           # a step that returns its state unchanged
        patch(substrate.EpochStepper, "step", lambda self, state, seed: state)
    elif fault == "half_batch":     # half the lanes, scaled up to the batch
        make = kadabra.make_sample_fn

        def half(g, pre, batch, *, pad_to=None):
            fn = make(g, pre, batch // 2, pad_to=pad_to)

            def sample_fn(key, carry):
                frame, carry = fn(key, carry)
                return dataclasses.replace(frame, num=jnp.int32(batch),
                                           data=2 * frame.data), carry
            return sample_fn
        patch(kadabra, "make_sample_fn", half)
    elif fault == "no_exchange":    # frames never leave their chip
        colls = frames.axis_collectives

        def local(*a, **k):
            return dataclasses.replace(colls(*a, **k),
                                       reduce_frames=lambda f: f)
        patch(frames, "axis_collectives", local)
    elif fault == "altered":        # each sampled path shifted by one vertex
        walk = kadabra.sample_path

        def shifted(*a, **k):
            return jnp.roll(walk(*a, **k), 1)
        patch(kadabra, "sample_path", shifted)
    elif fault != "none":
        raise ValueError(fault)


RGG = {"generator": "rgg", "radius_factor": 0.55}   # DIMACS10 rgg_n_2_<k>_s0
# the tiny graphs, as ``values``'s keyword arguments
GRAPHS = {"g500": {}, "rgg": {"graph": RGG, "scale": 10}}


def values(chips: int = 1, *, graph: dict | None = None,
           control: bool = False, seed: int = 2**31 + 17,
           scale: int = 9) -> dict:
    """The numbers the check compares for a run of the one-chip cell at a
    tiny scale on ``chips`` devices, its graph's parameters replaced by
    ``graph`` where given; the harness lays the workers out, one a chip."""
    import jax

    from bench import cell as cellmod, check, harness

    graph = graph or {}
    c = cellmod.load("g500-bc.1chip")
    c = dataclasses.replace(c, chips=chips, config=dict(
        c.config, scale=scale, name=f"tiny-{graph.get('generator', 'g500')}"
        f"-w{chips}", **graph))
    win = harness.run(c, jax.devices()[:c.chips], seed=seed, seconds=1.0,
                      t_process=time.perf_counter())
    return check.compare(win, check.Reference(win), control=control)


if __name__ == "__main__":
    chips, fault, graph = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    if fault != "control":
        plant(fault, setattr)
    print(json.dumps(values(chips, **GRAPHS[graph],
                            control=fault == "control")))
