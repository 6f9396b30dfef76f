"""One run of a cell: set-up, the measured window and what the check reads.

The window drives the program's per-epoch serving path: an
``AdaptiveSession`` over ``SessionSpec(<config>, strategy, W, seed)``, W
workers one per chip of the cell, with its cached compiled
``EpochStepper``, stepped one epoch at a time until ``seconds`` have
passed; the window ends when the epoch in flight at that moment has
finished.  The traffic is a closed loop of one query at a time:
when a query retires, the next starts at the next seed on the same stepper.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.instances import KadabraInstance, register_instance
from repro.graphs.csr import Graph, from_edges
from repro.graphs.kadabra import Preprocessed, preprocess
from repro.serve.session import AdaptiveSession, SessionSpec, StepperCache

from . import cell as cellmod

SEED_MOD = 1 << 32          # the program keys its streams by a uint32 seed

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile_or_load",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


@dataclasses.dataclass(frozen=True)
class GeneratedKadabra(KadabraInstance):
    """``KadabraInstance`` on the configuration's graph.  Only where the
    graph comes from differs: preprocessing, the sample function, the
    stopping condition and ``rounds_per_epoch`` are the program's own."""

    graph: Graph = dataclasses.field(default=None, compare=False, repr=False)
    pre: Preprocessed = dataclasses.field(default=None, compare=False,
                                          repr=False)

    def _graph(self):
        return self.graph, self.pre, np.full((self.graph.n,), np.nan)


class HostSpans:
    """The harness's host spans, kept in memory and written into the
    profiler's trace as ``bench.<name>`` annotations."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def seconds(self, name: str) -> float:
        return sum(b - a for n, a, b in self.spans if n == name)


class CompileLog:
    """Seconds JAX spent tracing, lowering and compiling or loading from
    the persistent cache, by phase, and how many programs it compiled or
    loaded."""

    def __init__(self):
        self.secs: collections.Counter = collections.Counter()
        self.programs = 0

    def _on(self, event: str, secs: float, **_):
        what = _COMPILE_EVENTS.get(event)
        if what is not None:
            self.secs[what] += secs
            self.programs += what == "compile_or_load"

    @contextlib.contextmanager
    def listening(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class Window:
    """What a run hands to the check and to the metrics."""

    cell: cellmod.Cell
    seed: int
    n: int
    edges: np.ndarray
    instance: GeneratedKadabra
    setup: dict                 # seconds by part, "setup_s" the whole
    seconds: float              # the window's wall time
    samples: int                # samples added to τ in the window
    epochs: int                 # epochs stepped in the window
    compiles_in_window: int
    memory_peak_bytes: int
    memory_stats: dict          # the fullest device's runtime counters
    queries: list               # per query: seed, frames (host), final state
    window_frames: list         # (query index, frame index) made in the window
    hlo_text: str = ""          # the step program's, in traced runs
    # the compiled step's own account of its bytes, in traced runs
    step_memory: dict = dataclasses.field(default_factory=dict)


def _block(tree):
    jax.block_until_ready(tree)
    return tree


def _peak(stats: dict) -> int:
    """HBM held at the peak: the buffers in use, and the runtime's
    reservation for the programs' temporaries, which the buffers' counter
    leaves out."""
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def _fullest(devices) -> dict:
    """The runtime's memory counters of the device with the highest peak."""
    return max((d.memory_stats() or {} for d in devices), key=_peak,
               default={})


_STEP_MEMORY = ("argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes",
                "generated_code_size_in_bytes")


@dataclasses.dataclass
class Setup:
    """The cell's graph, registered instance and stepper cache: what every
    query of a process shares."""

    cell: cellmod.Cell
    n: int
    edges: np.ndarray
    instance: GeneratedKadabra
    cache: StepperCache
    spans: HostSpans
    compiles: CompileLog


def set_up(cell: cellmod.Cell) -> Setup:
    """Build the configuration's graph, register its instance and run the
    program's preprocessing."""
    cfg, traffic = cell.config, cell.traffic
    if (traffic.get("loop"), traffic.get("clients")) != ("closed", 1):
        raise SystemExit(f"traffic {traffic}: only a closed loop of one "
                         "client is defined")
    if cfg.get("algorithm") != "kadabra":
        raise SystemExit(f"algorithm {cfg.get('algorithm')!r} is not defined")
    spans, compiles = HostSpans(), CompileLog()
    with compiles.listening():
        with spans("graph"):
            n, edges = cellmod.edges(cfg)
            g = _block(from_edges(n, edges))
        eps, delta = float(cfg["eps"]), float(cfg["delta"])
        with spans("preprocess"):
            pre = preprocess(g, eps, delta)   # as KadabraInstance calls it
            _block(pre.components)
        inst = GeneratedKadabra(
            name=cfg["name"], n_vertices=g.n, n_edges=g.m_arcs // 2,
            graph_seed=int(cfg["graph_seed"]), eps=eps, delta=delta,
            batch=int(cfg["batch"]), compute_oracle=False, graph=g, pre=pre)
        register_instance(inst, overwrite=True)
    return Setup(cell=cell, n=n, edges=edges, instance=inst,
                 cache=StepperCache(), spans=spans, compiles=compiles)


def measure(setup: Setup, devices: list, *, seed: int, seconds: float,
            trace_dir=None, t_process: float) -> Window:
    """Start a query at ``seed``, warm it up by one epoch, measure
    ``seconds`` of the traffic, and gather what the check reads.
    ``t_process`` is the process's start on the ``time.perf_counter``
    clock."""
    cell, inst, spans = setup.cell, setup.instance, setup.spans
    cfg = cell.config

    def start(qseed: int) -> dict:
        # one worker per chip
        spec = SessionSpec(inst.name, cfg["strategy"], cell.chips, qseed,
                           substrate=cell.substrate)
        session = AdaptiveSession.create(spec, setup.cache).start()
        return {"seed": qseed, "session": session,
                "frames": [session.state.pending], "tau0": 0}

    def step(q: dict) -> None:
        q["session"].step()
        q["frames"].append(q["session"].state.pending)

    with setup.compiles.listening():
        with spans("start"):
            q = start(seed % SEED_MOD)
            _block(q["session"].state)
        with spans("warmup"):
            step(q)
            _block(q["session"].state)
    q["tau0"] = q["session"].tau
    queries = [q]

    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    window_frames, samples, epochs = [], 0, 0
    window_compiles = CompileLog()
    t_start = time.perf_counter()
    setup_parts = {
        "setup_s": t_start - t_process,
        **{name: spans.seconds(name)
           for name in ("graph", "preprocess", "start", "warmup")},
        **{f"jax_{k}": v for k, v in setup.compiles.secs.items()},
        "programs": setup.compiles.programs}
    deadline = t_start + seconds
    with window_compiles.listening(), spans("window"):
        while True:
            if q["session"].done:
                samples += q["session"].tau - q["tau0"]
                with spans("next_query"):
                    q = start((q["seed"] + 1) % SEED_MOD)
                    queries.append(q)
            # the session's step dispatches the epoch and waits to read its
            # stop verdict back
            with spans("step"):
                step(q)
            epochs += 1
            window_frames.append((len(queries) - 1, len(q["frames"]) - 1))
            if time.perf_counter() >= deadline:
                break
        _block(q["session"].state)
    t_end = time.perf_counter()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    samples += q["session"].tau - q["tau0"]
    stats = _fullest(devices[:cell.chips])
    hlo_text, step_memory = "", {}
    if trace_dir is not None:
        # the trace names ops by HLO instruction; their name stacks are in
        # the compiled step's metadata (a persistent-cache hit)
        session = q["session"]
        compiled = session.stepper.step_fn.lower(
            session.state, jnp.asarray(session.spec.seed, jnp.uint32)
        ).compile()
        hlo_text = compiled.as_text()
        analysis = compiled.memory_analysis()
        step_memory = {k: int(getattr(analysis, k)) for k in _STEP_MEMORY
                       if hasattr(analysis, k)}

    # the program's outputs, on the host; then its state is let go
    for q in queries:
        state = q.pop("session").state
        q["frames"] = jax.device_get(
            [(f.num, f.data) for f in q["frames"]])
        q["final"] = jax.device_get({
            "num": state.total.num, "data": state.total.data,
            "stop": state.stop, "max_f": state.aux["max_f"],
            "max_g": state.aux["max_g"]})
    return Window(cell=cell, seed=seed, n=setup.n, edges=setup.edges,
                  instance=inst, setup=setup_parts, seconds=t_end - t_start,
                  samples=samples, epochs=epochs,
                  compiles_in_window=window_compiles.programs,
                  memory_peak_bytes=_peak(stats),
                  memory_stats=stats, queries=queries,
                  window_frames=window_frames, hlo_text=hlo_text,
                  step_memory=step_memory)


def run(cell: cellmod.Cell, devices: list, *, seed: int, seconds: float,
        trace_dir=None, t_process: float) -> Window:
    """Set up the cell and measure one query's window."""
    return measure(set_up(cell), devices, seed=seed, seconds=seconds,
                   trace_dir=trace_dir, t_process=t_process)
