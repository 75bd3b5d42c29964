"""Compile the simulator's programs for a TPU v5e that is described, not
attached: the serial runner and the fleet chunk at ``leaf-spine-xl`` shapes
(with Eq. 3's Pallas lookup, the path a TPU program takes), and the
min-plus and Eq. 3 Pallas kernels without the interpreter.  Nothing runs; a
pass means the chip's compiler accepts the program and it fits one chip.

The topology is described inside a fixture (never at import time): only
one process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import Experiment, as_policy_arrays, runners
from repro.api.fleet import STATIC_FIELDS
from repro.core import fairshare
from repro.core.engine import init_fleet_carry, make_fleet_chunk

V5E_HBM_BYTES = 16 * 2**30
FLEET_WIDTH = 4
CHUNK_STEPS = 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def xl():
    """leaf-spine-xl consts, meta and the default policy, built on the
    host (only their shapes go to the compiler)."""
    consts, meta = Experiment("leaf-spine-xl").build()
    return consts, meta, as_policy_arrays()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total


def _lowers_eq3_kernel(lower):
    """``lower()``'s program takes Eq. 3's Pallas lookup, not the gather:
    the branch kept is the one of the platform it is lowered for."""
    before = fairshare.lookup_counts()
    compiled = lower().compile()
    after = fairshare.lookup_counts()
    assert after["kernel"] > before["kernel"]
    assert after["gather"] == before["gather"]
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_serial_runner_compiles_for_v5e(one_chip, no_persistent_cache, xl):
    consts, meta, pol = xl
    fn, init = runners._make_fn(meta, "single", counted=False)
    s0 = jax.eval_shape(init, consts, pol)
    args = _on(one_chip, (consts, pol, s0))
    _fits_one_chip(_lowers_eq3_kernel(lambda: jax.jit(
        fn, donate_argnums=runners.DONATE_ARGNUMS).lower(*args)))


def test_fleet_chunk_compiles_for_v5e(one_chip, no_persistent_cache, xl):
    consts, meta, pol = xl
    static_pol = {f: int(pol[f]) for f in STATIC_FIELDS}
    chunk = make_fleet_chunk(meta, static_pol, CHUNK_STEPS)
    lane_pol = {k: jnp.broadcast_to(v, (FLEET_WIDTH,))
                for k, v in pol.items() if k not in STATIC_FIELDS}
    carry = jax.eval_shape(
        lambda c: init_fleet_carry(c, meta, FLEET_WIDTH), consts)
    args = _on(one_chip, (consts, lane_pol, carry))
    _fits_one_chip(_lowers_eq3_kernel(lambda: jax.jit(
        chunk, donate_argnums=runners.DONATE_ARGNUMS).lower(*args)))


def test_minplus_kernel_compiles_for_v5e(one_chip, no_persistent_cache):
    from repro.kernels.tropical_apsp.kernel import minplus_matmul
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip)
    lowered = jax.jit(lambda a, b: minplus_matmul(
        a, b, bm=128, bn=128, bk=128, interpret=False)).lower(x, x)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


# the Eq. 3 bottleneck kernel at both configurations' widths, vmapped as
# their cells run it: paper-usecase (130 links, 460 packets; 16 fleet
# lanes, 2 policies) and fat-tree-k16 (6,146 links, 5,152 packets; 2 lanes)
@pytest.mark.parametrize("lanes,n_links,n_pkts", [
    (16, 130, 460), (2, 130, 460), (2, 6146, 5152)])
def test_eq3_kernel_compiles_for_v5e(one_chip, no_persistent_cache, lanes,
                                     n_links, n_pkts):
    from repro.kernels.eq3_bottleneck.ops import eq3_bottleneck
    share = jax.ShapeDtypeStruct((lanes, n_links), jnp.float32,
                                 sharding=one_chip)
    links = jax.ShapeDtypeStruct((lanes, n_pkts, 6), jnp.int32,
                                 sharding=one_chip)
    compiled = jax.jit(jax.vmap(eq3_bottleneck)).lower(share, links).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_eq3_path_follows_the_lowering_platform(one_chip,
                                                no_persistent_cache):
    """One Eq. 3 function, lowered in one process for the CPU and for the
    described v5e: the CPU program keeps the gather, the TPU program the
    kernel, whatever the default backend."""
    from repro.core.fairshare import eq3_rates
    f = jax.jit(jax.vmap(lambda bw, r, a: eq3_rates(r, a, bw, 1e10)))
    cpu = SingleDeviceSharding(jax.devices("cpu")[0])
    shapes = ((2, 130), jnp.float32), ((2, 460, 6), jnp.int32), \
        ((2, 460), jnp.bool_)
    for sharding, path, op in ((cpu, "gather", "gather("),
                               (one_chip, "kernel", "tpu_custom_call")):
        args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                for s, d in shapes]
        before = fairshare.lookup_counts()
        text = f.lower(*args).compile().as_text()
        after = fairshare.lookup_counts()
        assert {k: after[k] - before[k] for k in after} == {
            k: int(k == path) for k in after}
        assert op in text
