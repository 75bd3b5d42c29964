"""Flow frontend oracles (DESIGN.md §Beyond-paper): closed-form ring and
contention times."""
import pytest

from repro.core import PolicyConfig, simulate
from repro.core.flows import Flow, flows_setup
from repro.core.topology import torus_2d


def test_ring_allreduce_closed_form():
    """2(n-1) rounds of B/n on an n-ring at bw == analytic ring time."""
    n, bw, gbits = 4, 1e9, 4.0
    topo = torus_2d(n, 1, bw=bw)
    flows = [Flow(i, (i + 1) % n, gbits / n, round=r)
             for r in range(2 * (n - 1)) for i in range(n)]
    s = simulate(flows_setup(topo, flows), PolicyConfig())
    want = 2 * (n - 1) * (gbits / n) * 1e9 / bw
    assert float(s.time) == pytest.approx(want, rel=1e-4)


def test_flows_contention_vs_diverse():
    """4 flows onto one link vs 4 disjoint neighbor flows: 4x slower."""
    topo = torus_2d(4, 4, bw=1e9)
    idx = lambda x, y: x * 4 + y
    same = [Flow(idx(0, 0), idx(1, 0), 1.0) for _ in range(4)]
    t_same = float(simulate(flows_setup(topo, same), PolicyConfig()).time)
    disjoint = [Flow(idx(x, 0), idx(x, 1), 1.0) for x in range(4)]
    t_dis = float(simulate(flows_setup(topo, disjoint),
                           PolicyConfig()).time)
    assert t_same == pytest.approx(4 * t_dis, rel=1e-3)
