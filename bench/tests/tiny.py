"""A run of a cell at a tiny size on the CPU, with the chip look skipped
and, on request, a fault planted in the program underneath it.  With four
workers it is the one-chip cell's query laid out as the paper's parallel
algorithm, one worker per device under ``shard_map``.

    python -m bench.tests.tiny <world> <fault>    # prints the numbers as JSON

``<fault>`` is one of FAULTS, or ``control`` for the control in the
program's place.
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


def values(world: int, *, control: bool = False, seed: int = 2**31 + 17,
           scale: int = 9) -> dict:
    import jax

    from bench import cell as cellmod, check, harness

    cell = cellmod.load("g500-bc.1chip")
    layout = {} if world == 1 else {"substrate": "shard_map"}
    cell = dataclasses.replace(cell, chips=world, config=dict(
        cell.config, scale=scale, name=f"tiny-w{world}", world=world,
        **layout))
    win = harness.run(cell, jax.devices(), seed=seed, seconds=1.0,
                      t_process=time.perf_counter())
    return check.compare(win, check.Reference(win), control=control)


if __name__ == "__main__":
    world, fault = int(sys.argv[1]), sys.argv[2]
    if fault != "control":
        plant(fault, setattr)
    print(json.dumps(values(world, control=fault == "control")))
