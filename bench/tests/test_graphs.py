"""The graph generators reproduce their definitions."""

import numpy as np

from bench import cell as cellmod

KRONECKER = {"scale": 12, "edgefactor": 16,
             "initiator": [0.57, 0.19, 0.19, 0.05]}


def test_kronecker_sizes():
    gen = cellmod.load_module("graphs", "kronecker")
    n, edges = gen.generate(KRONECKER, 3)
    assert n == 1 << 12
    assert edges.shape == (16 << 12, 2)
    assert edges.min() >= 0 and edges.max() < n


def test_kronecker_initiator_frequencies():
    """Each bit level of each tuple picks quadrant (0,0), (0,1), (1,0),
    (1,1) with probability A, B, C, D."""
    gen = cellmod.load_module("graphs", "kronecker")
    i, j = gen.tuples(KRONECKER, np.random.default_rng(4))
    bits = np.arange(KRONECKER["scale"])
    ib = (i[:, None] >> bits) & 1
    jb = (j[:, None] >> bits) & 1
    freq = np.bincount((2 * ib + jb).ravel(), minlength=4) / ib.size
    # 786,432 draws: one standard error is under 6e-4
    np.testing.assert_allclose(freq, KRONECKER["initiator"], atol=3e-3)


def test_kronecker_labels_are_a_permutation():
    """The label permutation keeps the degree sequence of the tuples."""
    gen = cellmod.load_module("graphs", "kronecker")
    n, edges = gen.generate(KRONECKER, 5)
    i, j = gen.tuples(KRONECKER, np.random.default_rng(5))
    deg = np.sort(np.bincount(edges.ravel(), minlength=n))
    np.testing.assert_array_equal(deg, np.sort(np.bincount(
        np.concatenate([i, j]), minlength=n)))


def test_rgg_radius_rule_matches_the_published_instance():
    """rgg_n_2_17_s0 of DIMACS10 has 728,753 edges; points drawn from
    another seed land within 1% of it."""
    gen = cellmod.load_module("graphs", "rgg")
    n, edges = gen.generate({"scale": 17, "radius_factor": 0.55}, 0)
    assert n == 1 << 17
    assert abs(len(edges) - 728_753) < 0.01 * 728_753
    assert np.all(edges[:, 0] < edges[:, 1])


def test_largest_component_is_connected_and_relabelled():
    gen = cellmod.load_module("graphs", "kronecker")
    n, edges = gen.generate(KRONECKER, 6)
    m, e = cellmod.largest_component(n, edges)
    assert m < n and set(np.unique(e)) == set(range(m))
