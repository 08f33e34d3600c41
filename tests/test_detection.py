"""Routing, EM link inference and detector unit behaviour."""

import numpy as np
import pytest
from proptest import given, settings, st

from repro.core.detection import em_link_inverse_bw, gamma_sf
from repro.core.routing import DetourTopology, Mesh2D, Torus2D

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 8), st.integers(2, 8), st.data())
def test_xy_route_properties(w, h, data):
    mesh = Mesh2D(w, h)
    src = data.draw(st.integers(0, mesh.n_cores - 1))
    dst = data.draw(st.integers(0, mesh.n_cores - 1))
    path = mesh.route(src, dst)
    # length = manhattan distance
    assert len(path) == mesh.hops(src, dst)
    # path is connected src → dst over adjacent links
    cur = src
    for lid in path:
        u, v = mesh.links[lid]
        assert u == cur
        cur = v
    assert cur == dst
    # deterministic
    assert path == mesh.route(src, dst)


def test_link_ids_bijective():
    mesh = Mesh2D(4)
    assert mesh.n_links == 2 * (2 * 4 * 3)   # 2 directions × edges
    seen = set()
    for lid, (u, v) in enumerate(mesh.links):
        assert mesh.link_id(u, v) == lid
        assert (u, v) not in seen
        seen.add((u, v))


def test_em_recovers_slow_link():
    """Synthetic tomography: events over known paths with one slow link."""
    mesh = Mesh2D(4)
    rng = np.random.default_rng(0)
    theta_true = np.full(mesh.n_links, 1e-9)
    slow = 20
    theta_true[slow] = 1e-8
    pairs = [(int(rng.integers(16)), int(rng.integers(16)))
             for _ in range(400)]
    pairs = [p for p in pairs if p[0] != p[1]]
    A = mesh.path_matrix(pairs)
    rows, cols = mesh.path_links(pairs)
    V = rng.uniform(1e3, 1e5, len(pairs))
    T = (A * V[:, None]) @ theta_true
    T *= rng.gamma(64, 1 / 64, len(T))       # mild noise
    th = em_link_inverse_bw(rows, cols, mesh.n_links, T, V, np.ones(len(T)))
    seen = A.sum(axis=0) > 0
    assert seen[slow]
    ranked = np.argsort(-np.where(seen, th, 0))
    assert ranked[0] == slow
    assert th[slow] > 4 * np.median(th[seen])


def _dense_em(A, T, V, w, hop_latency=0.0, iters=60):
    """The EM as a product with the dense events x links matrix: the
    oracle for the route-list form."""
    T = np.maximum(T - hop_latency * A.sum(axis=1), 1e-12)
    traffic = (A * (w * V)[:, None]).sum(axis=0)
    seen = traffic > 0
    theta = np.full(A.shape[1], float(
        (T / np.maximum((A * V[:, None]).sum(axis=1), 1e-12)).mean()))
    for _ in range(iters):
        ratio = T / np.maximum((A * V[:, None]) @ theta, 1e-300)
        num = theta * (A * (w * V * ratio)[:, None]).sum(axis=0)
        theta = np.where(seen, num / np.maximum(traffic, 1e-300), theta)
    if seen.any():
        n = (A > 0).sum(axis=0)
        lam = n / (n + 3.0)
        theta = np.where(seen, lam * theta
                         + (1 - lam) * np.median(theta[seen]), theta)
    return theta


def _detour16():
    base = Mesh2D(16)
    return DetourTopology(base, base.route(0, 255)[:3])


@pytest.mark.parametrize("make,hop_latency", [
    (lambda: Mesh2D(16), 0.0),
    (lambda: Mesh2D(16), 50e-9),
    (lambda: Torus2D(8, 6), 50e-9),
    (_detour16, 50e-9),
], ids=["mesh16-hop0", "mesh16-hop50ns", "torus8x6", "detour16"])
def test_em_over_route_lists_matches_dense(make, hop_latency):
    topo = make()
    rng = np.random.default_rng(7)
    ends = rng.integers(0, topo.n_cores, (2000, 2))
    pairs = [(int(s), int(d)) for s, d in ends if s != d]
    A = topo.path_matrix(pairs)
    theta_true = rng.gamma(4.0, 0.25e-9, topo.n_links)
    theta_true[rng.integers(topo.n_links)] *= 10
    V = rng.uniform(1e3, 1e5, len(pairs))
    T = V * (A @ theta_true) * rng.gamma(64, 1 / 64, len(pairs)) \
        + hop_latency * A.sum(axis=1)
    w = rng.integers(1, 50, len(pairs)).astype(np.float64)
    rows, cols = topo.path_links(pairs)
    got = em_link_inverse_bw(rows, cols, topo.n_links, T, V, w, hop_latency)
    want = _dense_em(A, T, V, w, hop_latency)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want) / np.abs(want)) <= 1e-12


def test_gamma_sf_properties():
    assert gamma_sf(0.0, 2.0, 1.0) == pytest.approx(1.0)
    assert gamma_sf(1e9, 2.0, 1.0) == pytest.approx(0.0, abs=1e-9)
    # monotone decreasing
    vals = [gamma_sf(x, 3.0, 0.5) for x in (0.1, 0.5, 1.0, 3.0, 10.0)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))
    # median of Gamma(1,1) ≈ ln 2
    assert gamma_sf(np.log(2), 1.0, 1.0) == pytest.approx(0.5, abs=1e-6)
