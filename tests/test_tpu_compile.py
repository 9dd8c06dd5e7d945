"""The serving kernels compile for a v5e at the widths the 100k×10k daemon
runs, with no chip attached: the TPU compiler is installed here and
compiles for a described ``v5e:2x2`` topology. Inputs are shapes only
(``ShapeDtypeStruct``); nothing runs, so these tests say nothing about
results or times — they catch what interpret mode cannot (tiling,
VMEM, HBM fit, partitioning) before a chip call does.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under xdist every worker
imports this file. The persistent compilation cache is off around the
compiles (an entry written for a described chip cannot be read back).

Also here: where the persistent compilation cache goes
(utils/platform.py).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from kube_throttler_tpu.ops.schema import DimRegistry, encode_pods, encode_throttle_state
from kube_throttler_tpu.utils import platform

# the daemon's padded capacities at 100k pods × 10k throttles (ladder rungs)
PCAP, TCAP = 131072, 16384
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _widen(tree, lead, sharding):
    """``tree``'s leaves as shapes whose leading dim is ``lead``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((lead,) + a.shape[1:], a.dtype, sharding=sharding),
        tree,
    )


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _small():
    """Tiny encoded (state, pods): the field structure and dtypes the
    daemon's device planes use, to widen into full-size shapes."""
    dims = DimRegistry()
    return encode_throttle_state([], dims, capacity=8), encode_pods([], dims, capacity=8)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB does not fit one v5e"


@pytest.mark.parametrize("k", [64, 2048])
def test_check_pods_gather_compiles(one_chip, k):
    """The batch-triage kernel at the shapes that once ran a v5e out of
    HBM: [131072, 64], and [131072, 2048], the top rung prewarm walks."""
    from kube_throttler_tpu.ops.check import check_pods_gather

    state, pods = _small()
    compiled = check_pods_gather.lower(
        _widen(state, TCAP, one_chip),
        _widen(pods, PCAP, one_chip),
        _shape((PCAP, k), jnp.int32, one_chip),
        on_equal=False,
        step3_on_equal=True,
    ).compile()
    _fits(compiled)


def test_check_pods_compiles(one_chip):
    """check_pod's dense fallback above indexed_check_max: one pod row
    against every throttle column."""
    from kube_throttler_tpu.ops.check import check_pods

    state, pods = _small()
    compiled = check_pods.lower(
        _widen(state, TCAP, one_chip),
        _widen(pods, 1, one_chip),
        _shape((1, TCAP), jnp.bool_, one_chip),
        on_equal=False,
        step3_on_equal=True,
    ).compile()
    _fits(compiled)


def test_check_pods_compact_compiles(one_chip):
    """The dense batch check, taken when the sparse [P,K] companion is not
    the better shape, over the whole [P,T] mask."""
    from kube_throttler_tpu.ops.check import check_pods_compact

    state, pods = _small()
    compiled = check_pods_compact.lower(
        _widen(state, TCAP, one_chip),
        _widen(pods, PCAP, one_chip),
        _shape((PCAP, TCAP), jnp.bool_, one_chip),
        on_equal=False,
        step3_on_equal=True,
    ).compile()
    _fits(compiled)


def _tick_args(pods_sharding, thr_sharding, scalar_sharding):
    """Shapes of the tick program's arguments at the daemon's widths (K=32:
    20 throttles a pod)."""
    from kube_throttler_tpu.ops.overrides import encode_override_schedule

    state, pods = _small()
    R = state.thr_req.shape[1]
    sched = encode_override_schedule([None] * 8, DimRegistry(), throttle_capacity=8,
                                     override_capacity=1)
    return (
        _widen(sched, TCAP, thr_sharding),
        _widen(pods, PCAP, pods_sharding),
        _shape((PCAP, 32), jnp.int32, pods_sharding),
        _shape((PCAP,), jnp.bool_, pods_sharding),
        _shape((TCAP,), jnp.int64, thr_sharding),
        _shape((TCAP,), jnp.bool_, thr_sharding),
        _shape((TCAP, R), jnp.int64, thr_sharding),
        _shape((TCAP, R), jnp.bool_, thr_sharding),
        _shape((TCAP,), jnp.bool_, thr_sharding),
        _shape((), jnp.int64, scalar_sharding),
    )


def test_full_update_step_gather_compiles(one_chip):
    """The /v1/tick program on one chip at [131072, 32], T 16384, R 8: the
    sort of its 4M slots by target and the prefix sums that rebuild
    ``used``, then the gather."""
    from kube_throttler_tpu.parallel.sharded import full_update_step_gather

    compiled = full_update_step_gather.lower(
        *_tick_args(one_chip, one_chip, one_chip), on_equal=False, step3_on_equal=True
    ).compile()
    _fits(compiled)
    assert " sort(" in compiled.as_text()


@pytest.mark.parametrize("mesh_shape", [(2, 2), (4, 1)])
def test_sharded_full_update_gather_compiles(topo, mesh_shape):
    """The /v1/tick program over a ("pods","throttles") mesh of four
    described chips, at the daemon's widths (K=32: 20 throttles a pod)."""
    from kube_throttler_tpu.parallel.sharded import sharded_full_update_gather

    mesh = Mesh(np.array(topo.devices[:4]).reshape(mesh_shape), ("pods", "throttles"))
    step = sharded_full_update_gather(mesh, on_equal=False, step3_on_equal=True)
    compiled = step.lower(
        *_tick_args(NamedSharding(mesh, P("pods")), NamedSharding(mesh, P("throttles")),
                    NamedSharding(mesh, P()))
    ).compile()
    _fits(compiled)
    assert "all-reduce" in compiled.as_text()


class TestCompilationCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config updates instead of applying them."""
        seen = {}
        monkeypatch.setattr(jax.config, "update", lambda k, v: seen.__setitem__(k, v))
        monkeypatch.setattr(jax.monitoring, "register_event_listener", lambda cb: None)
        monkeypatch.setattr(platform, "_listening", [])
        return seen

    def test_env_set_wins_and_no_dir_is_set_in_code(self, monkeypatch, tmp_path, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert platform.compilation_cache_dir() == str(tmp_path)
        assert platform.enable_compilation_cache("tpu") == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates

    def test_env_unset_uses_checkout_dir(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert platform.compilation_cache_dir() == want
        assert platform.enable_compilation_cache("cpu") is None
        assert updates == {}
        assert platform.enable_compilation_cache("tpu") == want
        assert updates["jax_compilation_cache_dir"] == want
