"""The device paths compile for a TPU v5e chip, described, not attached.

The TPU compiler is installed with JAX, so these tests compile the k-means
kernels at the fleet probe's size and the f64 ensemble scan at the quick
bench's size for one chip of a described ``v5e:2x2`` topology.  They
catch what interpret mode cannot (block shapes the chip refuses, VMEM
over-use, programs that do not fit) at no chip time.  Nothing runs, so
they say nothing about results or speed: ``chip_smoke.py`` is the run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.engine_bench import fleet_cluster, fleet_workflow
from repro.core.scheduler import make_scheduler
from repro.kernels import kmeans
from repro.workflow import ensemble

FLEET_N = 100_000          # engine_bench's choose_k probe: 10^5 profiles,
FLEET_F = 3                # 3 features (not a multiple of the 1024 block)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("step", ["lloyd", "assign"])
def test_kmeans_kernel_compiles_at_fleet_size(one_chip, step):
    x = _sds((FLEET_N, FLEET_F), jnp.float32, one_chip)
    c = _sds((4, FLEET_F), jnp.float32, one_chip)
    fn = kmeans.kmeans_lloyd_step if step == "lloyd" else kmeans.kmeans_assign
    compiled = fn.lower(x, c, block_n=1024).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_grouping_program_compiles_with_the_kernel(one_chip, monkeypatch):
    """The whole k-means++ program ``choose_k`` runs per (k, restart), on
    the kernel path, at the fleet probe's point count."""
    from repro.core import clustering
    from repro.kernels import ops
    # this process sees only the CPU, where the kernel wrappers interpret
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    x = _sds((FLEET_N, FLEET_F), jnp.float32, one_chip)
    key = _sds((), jax.random.key(0).dtype, one_chip)
    compiled = clustering._kmeans_pp.lower(x, k=3, key=key, iters=32,
                                           use_kernel=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_phase_one_grouping_compiles_with_the_kernel(one_chip, monkeypatch):
    """Phase 1 of a Tarema run groups its partition's profiles on the chip:
    256 nodes of 6 features, every k that ``choose_k`` sweeps."""
    from repro.core import clustering
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    x = _sds((256, 6), jnp.float32, one_chip)
    key = _sds((), jax.random.key(0).dtype, one_chip)
    for k in range(2, 7):
        compiled = clustering._kmeans_pp.lower(x, k=k, key=key, iters=32,
                                               use_kernel=True).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("sched", ["fair", "sjfn"])
def test_f64_scan_compiles(one_chip, sched):
    """The ensemble bench's quick scale: 64 nodes x 500 instances x 16
    replicas, in f64."""
    specs = fleet_cluster(64)
    subs = [ensemble.Submission(fleet_workflow(500, 128), seed=11)]
    top = ensemble._Topology(specs, subs, make_scheduler(sched, specs, seed=0),
                             None, 16, 1)
    with jax.enable_x64(True):
        scan, args = ensemble._build_scan(top)
        shapes = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), args)
        compiled = scan.lower(*shapes).compile()
    assert compiled.memory_analysis() is not None
