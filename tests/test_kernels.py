"""The min-plus Pallas kernel vs pure-jnp oracles (interpret mode), shape sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tropical_apsp.kernel import minplus_matmul
from repro.kernels.tropical_apsp.ops import apsp
from repro.kernels.tropical_apsp.ref import apsp_ref, minplus_matmul_ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("m,k,n,block", [
    (8, 8, 8, 8), (32, 16, 24, 16), (100, 64, 50, 32), (130, 130, 130, 64)])
def test_minplus_matmul(m, k, n, block):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.uniform(k1, (m, k), jnp.float32, 0, 10)
    y = jax.random.uniform(k2, (k, n), jnp.float32, 0, 10)
    got = minplus_matmul(x, y, bm=block, bn=block, bk=block, interpret=True)
    want = minplus_matmul_ref(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("n,density,block", [(17, 0.2, 8), (64, 0.1, 32),
                                             (90, 0.05, 64)])
def test_apsp_vs_ref(n, density, block):
    rng = np.random.RandomState(n)
    adj = np.full((n, n), np.inf, np.float32)
    np.fill_diagonal(adj, 0)
    mask = rng.rand(n, n) < density
    adj[mask] = rng.uniform(0.1, 5.0, mask.sum()).astype(np.float32)
    np.fill_diagonal(adj, 0)
    got = np.asarray(apsp(jnp.asarray(adj), interpret=True, block=block))
    want = np.asarray(apsp_ref(jnp.asarray(adj)))
    finite = np.isfinite(want)
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-5)
    assert np.all(got[~finite] > 1e30)


def test_apsp_on_paper_topology():
    """Kernel APSP == host-side routing distances on the paper's fat-tree."""
    from repro.core.routing import hop_distances_np
    from repro.core.topology import paper_fat_tree
    topo = paper_fat_tree()
    adj = topo.hop_matrix()
    got = np.asarray(apsp(jnp.asarray(adj), interpret=True, block=64))
    want = hop_distances_np(adj)
    finite = np.isfinite(want)
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-6)
