"""Host spans and counters (``repro.runtime.spans``), the named scopes inside
the step program, and the KADABRA sampler's counters."""

import collections
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs import from_edges
from repro.graphs.kadabra import init_counters, make_sample_fn, preprocess
from repro.runtime import spans
from repro.serve import AdaptiveSession, SessionSpec, StepperCache

ROOT = Path(__file__).resolve().parents[1]
SCOPES = ("bfs_level", "path_step", "stop_check")


@pytest.fixture(autouse=True)
def fresh_ring():
    spans.clear()
    yield
    spans.clear()


def _named(name):
    return [r for r in spans.records() if r.name == name]


def test_nesting_and_parents():
    with spans.span("outer") as outer:
        with spans.span("inner") as inner:
            pass
        with spans.span("inner") as second:
            pass
    assert [r.name for r in spans.records()] == ["inner", "inner", "outer"]
    assert inner.parent == outer.id and second.parent == outer.id
    assert outer.parent is None
    assert outer.start <= inner.start <= inner.end <= second.start
    assert second.end <= outer.end


def test_query_is_inherited_unless_given():
    with spans.span("a", query="q1"):
        with spans.span("b") as b:
            with spans.span("c", query="q2") as c:
                pass
    with spans.span("d") as d:
        pass
    assert (b.query, c.query, d.query) == ("q1", "q2", None)


def test_ring_keeps_the_last_spans():
    for i in range(spans.RING + 10):
        with spans.span("s", i=i):
            pass
    kept = spans.records()
    assert len(kept) == spans.RING
    assert [r.attrs["i"] for r in kept[:2]] == [10, 11]


def test_counters_go_to_the_innermost_span_and_its_total():
    with spans.span("outer") as outer:
        spans.count("x", 2)
        with spans.span("inner") as inner:
            spans.count("x", 3)
    spans.count("x", 7)
    assert inner.counts["x"] == inner.total["x"] == 3
    assert outer.counts["x"] == 2 and outer.total["x"] == 5
    assert spans.outside["x"] == 7


def test_compile_is_charged_to_the_innermost_span():
    fn = jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)
    x = jnp.arange(7.0).block_until_ready()
    with spans.span("outer") as outer:
        with spans.span("inner") as inner:
            fn(x).block_until_ready()
        fn(x).block_until_ready()      # compiled already: nothing new
    assert inner.counts["programs"] == 1
    assert inner.counts["compile_s"] > 0
    assert "programs" not in outer.counts
    assert outer.total["programs"] == 1


@pytest.fixture(scope="module")
def session():
    """A KADABRA query on the small default graph, past its compile."""
    s = AdaptiveSession.create(SessionSpec("kadabra", "local", 1, 5),
                               StepperCache()).start()
    s.step()
    return s


def _op_names(hlo_text):
    return {m.group(1).lstrip("%"): m.group(2) for m in re.finditer(
        r'^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=.*?op_name="([^"]*)"', hlo_text,
        re.M)}


def _step_hlo(s):
    return s.stepper.step_fn.lower(
        s.state, jnp.asarray(s.spec.seed, jnp.uint32)).compile().as_text()


def test_scopes_in_the_compiled_step(session):
    stacks = _op_names(_step_hlo(session)).values()
    for scope in SCOPES:
        assert any(scope in st.split("/") for st in stacks), scope
    # the sampler's functions keep their own names around the scopes
    assert any("jit(bfs_sssp)" in st and "bfs_level" in st for st in stacks)
    assert any("jit(sample_path)" in st and "path_step" in st
               for st in stacks)


def test_spans_and_scopes_in_a_cpu_trace(session, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    session.step()
    jax.profiler.stop_trace()
    names = _op_names(_step_hlo(session))
    xplane = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    host, scopes = set(), collections.Counter()
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    host.add(ev.name)
                for scope in SCOPES:
                    if scope in names.get(ev.name, "").split("/"):
                        scopes[scope] += 1
    assert {"repro.session.step", "repro.session.dispatch",
            "repro.session.readback"} <= host
    assert set(scopes) == set(SCOPES)


def test_session_spans_count_the_sampler(session):
    before = session._readback()[1]
    session.step()
    after = session._readback()[1]
    step, = _named("session.step")
    dispatch, readback = _named("session.dispatch"), _named("session.readback")
    assert dispatch[0].parent == step.id and readback[0].parent == step.id
    assert step.query == "kadabra:5"
    assert set(after) == {"rounds", "bfs_levels", "path_live_steps",
                          "path_steps"}
    for name in after:
        assert step.total[name] == after[name] - before[name]
    assert step.total["rounds"] == session.built.rounds_per_epoch


def test_frame_exchange_on_four_devices():
    """The all-reduce of the frames between four workers under
    ``shard_map`` carries the scope ``frame_exchange``; a process of its
    own, since the device count is fixed when JAX starts."""
    code = textwrap.dedent("""
        import re
        import jax.numpy as jnp
        from repro.serve import AdaptiveSession, SessionSpec, StepperCache
        s = AdaptiveSession.create(
            SessionSpec("kadabra", "local", 4, 1, substrate="shard_map"),
            StepperCache()).start()
        text = s.stepper.step_fn.lower(
            s.state, jnp.asarray(1, jnp.uint32)).compile().as_text()
        stacks = re.findall(r'all-reduce.*?op_name="([^"]*)"', text)
        print(sum("frame_exchange" in st.split("/") for st in stacks))
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert int(r.stdout.split()[-1]) >= 1


def _numpy_round(adj, pre, batch, key):
    """What one round's batched BFS loop and path walk do, from the same
    (s, t) draws, by a plain level-synchronous BFS: (the loop's trip count,
    Σ dist(s, t) over the lanes whose t is reachable)."""
    n = len(adj)
    trips, live = [], 0
    for k in jax.random.split(key, batch):
        ks, kt, _ = jax.random.split(k, 3)
        s = int(jax.random.randint(ks, (), 0, n, dtype=jnp.int32))
        t = (s + 1 + int(jax.random.randint(kt, (), 0, n - 1, jnp.int32))) % n
        dist = {s: 0}
        frontier, level = [s], 0
        while frontier and level < pre.diam_levels and t not in dist:
            nxt = []
            for u in frontier:
                for v in adj[u]:
                    if v not in dist:
                        dist[v] = level + 1
                        nxt.append(v)
            frontier, level = nxt, level + 1
        trips.append(level)
        live += dist.get(t, 0)
    return max(trips), live


def test_counters_match_a_numpy_bfs():
    # a path of 9 vertices, a cycle of 5 and an isolated edge: lanes whose t
    # lies in another component run their BFS until the frontier empties
    edges = [(i, i + 1) for i in range(8)]
    edges += [(9 + i, 9 + (i + 1) % 5) for i in range(5)] + [(14, 15)]
    n = 16
    g = from_edges(n, np.asarray(edges))
    adj = [[] for _ in range(n)]
    for u, v in edges:
        adj[u].append(v)
        adj[v].append(u)
    pre = preprocess(g, eps=0.1, delta=0.1)
    batch = 12
    sample = jax.jit(make_sample_fn(g, pre, batch))
    carry = init_counters()
    levels = live = 0
    for i in range(3):
        key = jax.random.key(100 + i)
        _, carry = sample(key, carry)
        trip, lanes = _numpy_round(adj, pre, batch, key)
        levels, live = levels + trip, live + lanes
    carry = jax.device_get(carry)
    assert carry == {"rounds": 3, "bfs_levels": levels,
                     "path_live_steps": live,
                     "path_steps": 3 * batch * pre.vd_upper}
