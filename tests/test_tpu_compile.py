"""Compile the on-chip path for a described TPU v5e, with no chip.

Interpret mode (every other Pallas test here) checks results, not
whether the chip's compiler accepts the program.  These tests compile
the ``mapscore`` kernel and the device partition engine for a v5e that
is described, not attached, through the installed TPU compiler, so a
tile misalignment, a primitive Mosaic cannot lower, a 64-bit type or a
blown memory budget fails here instead of on the chip.

The topology is described in a module-scoped fixture (never at import),
which skips the file where no TPU compiler is installed.  Only the
worker that runs this file loads the TPU library.
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import SingleDeviceSharding  # noqa: E402

# importing the device partitioner turns x64 on for the process, as it
# does in a serving process: the kernel must compile under it
from repro.core import partition_jax  # noqa: E402
from repro.kernels.mapscore.kernel import acc_shapes, mapscore_call  # noqa: E402

HBM_BYTES = 16 * 10**9       # one v5e chip
TEMP_SHARE = 0.02            # kernel temporaries stay under 2% of HBM
# largest partition-engine bucket that compiles for v5e in a few
# seconds (about 6 s on a CPU host; the cost is the while loop and its
# per-level sort, and grows slowly with the bucket)
ENGINE_POINTS = 4096
ENGINE_CANDIDATES = 2

# (dims, wrap, core_dims, candidates, messages) per machine:
# the sparse XK7 of the 2^17-task MiniGhost request (4 rotation
# candidates, 768000 directed edges bucketed to 2^20) and a 16x16 v5e
# torus under a mesh builder's candidate stack
MACHINES = {
    "xk7": ((64, 16, 16, 16), (True, True, True, False), 1, 4, 1 << 20),
    "v5e16x16": ((16, 16), (True, True), 0, 8, 1 << 12),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("traffic", [False, True], ids=["hops", "traffic"])
@pytest.mark.parametrize("machine", sorted(MACHINES))
def test_mapscore_compiles_for_v5e(one_chip, machine, traffic):
    assert jax.config.jax_enable_x64
    dims, wrap, core_dims, nb, ne = MACHINES[machine]
    ncols = len(dims)
    args = [_spec((nb, ncols, ne), np.int32, one_chip),
            _spec((nb, ncols, ne), np.int32, one_chip),
            _spec((1, ne), np.float32, one_chip)]
    if traffic:
        rows = sum(sp for sp, _ in acc_shapes(dims, core_dims))
        args.append(_spec((rows, 1), np.float32, one_chip))

    def score(*a):
        return mapscore_call(*a, dims=dims, wrap=wrap, core_dims=core_dims,
                             traffic=traffic, tile=512)

    compiled = jax.jit(score).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < TEMP_SHARE * HBM_BYTES, \
        mem.temp_size_in_bytes


def test_mapscore_kernel_is_named_on_v5e(one_chip):
    """The kernel's custom call is named ``mapscore`` in the compiled
    program, the name its operation carries in a device trace."""
    dims, wrap, core_dims, nb, ne = MACHINES["v5e16x16"]
    args = [_spec((nb, len(dims), ne), np.int32, one_chip),
            _spec((nb, len(dims), ne), np.int32, one_chip),
            _spec((1, ne), np.float32, one_chip)]

    def score(*a):
        return mapscore_call(*a, dims=dims, wrap=wrap, core_dims=core_dims,
                             traffic=False, tile=512)

    text = jax.jit(score).lower(*args).compile().as_text()
    assert re.search(rf"%mapscore\.\d+ = \(f32\[{nb},8,128\]\S*, "
                     rf"s32\[{nb},8,128\]\S*\) custom-call\(", text)


@pytest.mark.parametrize("sfc", ["FZ", "H"])
def test_partition_engine_compiles_for_v5e(one_chip, sfc):
    n, d = ENGINE_POINTS, 3
    coords = np.random.default_rng(0).random((n, d))
    dim_orders = np.stack([np.arange(d), np.arange(d)[::-1]])
    args, (npts_b, nb_b, tab_b, cut_b, bits, lat) = partition_jax._prepare(
        coords, n, sfc, dim_orders[:ENGINE_CANDIDATES], None, False)
    assert (npts_b, nb_b) == (ENGINE_POINTS, ENGINE_CANDIDATES)
    specs = [_spec(np.shape(a), np.asarray(a).dtype, one_chip)
             for a in args]
    specs += [_spec((), np.int32, one_chip)] * 3
    engine = partition_jax._engine(d, sfc, True, False, npts_b, nb_b,
                                   tab_b, cut_b, bits, lat)
    compiled = engine.lower(*specs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < \
        TEMP_SHARE * HBM_BYTES


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_lattice_engine_compiles_for_v5e(one_chip, weighted):
    """The MJ engine on integer coordinates (int32 extents, no value
    table) compiles for the chip, and unweighted it holds no float64."""
    n, d = ENGINE_POINTS, 3
    rng = np.random.default_rng(0)
    coords = (rng.integers(0, 25, size=(n, d)) * 2).astype(float)
    weights = rng.random(n) if weighted else None
    dim_orders = np.stack([np.arange(d), np.arange(d)[::-1]])
    args, (npts_b, nb_b, tab_b, cut_b, bits, lat) = partition_jax._prepare(
        coords, n, "FZ", dim_orders[:ENGINE_CANDIDATES], weights, False)
    assert lat == (True,) * d
    specs = [_spec(np.shape(a), np.asarray(a).dtype, one_chip)
             for a in args]
    specs += [_spec((), np.int32, one_chip)] * 3
    engine = partition_jax._engine(d, "FZ", True, weighted, npts_b, nb_b,
                                   tab_b, cut_b, bits, lat)
    compiled = engine.lower(*specs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < \
        TEMP_SHARE * HBM_BYTES
    if not weighted:
        assert "f64" not in compiled.as_text()
