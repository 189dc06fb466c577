"""Distributed CC labeling over spatially-sharded mosaics vs scipy golden.

The cross-shard case the per-site pipeline never hits: one object spanning
several row shards must converge to one id, and the dense numbering must
be bit-identical to ``scipy.ndimage.label`` on the gathered mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.ndimage as ndi
from jax.sharding import Mesh

from tmlibrary_tpu.errors import ShardingError
from tmlibrary_tpu.parallel.label import (
    distributed_connected_components,
    segment_mosaic,
)


@pytest.fixture
def mesh(devices):
    return Mesh(np.asarray(devices), ("rows",))


def _golden(mask, connectivity):
    structure = ndi.generate_binary_structure(2, 1 if connectivity == 4 else 2)
    return ndi.label(mask, structure)


@pytest.mark.parametrize("connectivity", [4, 8])
def test_random_mask_matches_scipy(mesh, rng, connectivity):
    mask = rng.random((64, 48)) > 0.65
    labels, count = distributed_connected_components(
        mask, mesh, connectivity=connectivity
    )
    golden, n = _golden(mask, connectivity)
    assert int(count) == n
    assert np.array_equal(np.asarray(labels), golden)


def test_object_spanning_all_shards(mesh):
    """A single vertical bar crossing every shard gets ONE id."""
    mask = np.zeros((64, 32), bool)
    mask[:, 10] = True  # crosses all 8 row-shards
    mask[5, 20] = True  # plus an isolated pixel
    labels, count = distributed_connected_components(mask, mesh)
    golden, n = _golden(mask, 8)
    assert int(count) == n == 2
    assert np.array_equal(np.asarray(labels), golden)


def test_serpentine_component_converges(mesh):
    """A component snaking up and down across shards needs several outer
    rounds — the worst case for seam merging."""
    mask = np.zeros((64, 40), bool)
    # vertical strands connected alternately at top/bottom
    for i, x in enumerate(range(2, 38, 4)):
        mask[:, x] = True
        joint_row = 63 if i % 2 == 0 else 0
        if x + 4 < 40:
            mask[joint_row, x : x + 4] = True
    labels, count = distributed_connected_components(mask, mesh)
    golden, n = _golden(mask, 8)
    assert int(count) == n == 1
    assert np.array_equal(np.asarray(labels), golden)


def test_rows_must_divide(mesh):
    with pytest.raises(ShardingError):
        distributed_connected_components(np.zeros((63, 8), bool), mesh)


def test_root_overflow_detected(mesh):
    """A shard denser than the static root table raises instead of
    silently corrupting ranks."""
    mask = np.zeros((64, 64), bool)
    mask[::2, ::2] = True  # 32x32 = 1024 isolated pixels, 128/shard
    with pytest.raises(ShardingError):
        distributed_connected_components(mask, mesh, max_roots_per_shard=64)


def test_segment_mosaic_end_to_end(mesh, rng):
    """Giant-mosaic demo path: smooth + otsu + distributed CC equals the
    single-device chain on the gathered image."""
    from tmlibrary_tpu.ops.label import connected_components
    from tmlibrary_tpu.ops.smooth import gaussian_smooth
    from tmlibrary_tpu.ops.threshold import otsu_value

    yy, xx = np.mgrid[0:64, 0:64]
    img = rng.normal(200, 15, (64, 64)).astype(np.float32)
    for cy, cx in ((10, 12), (30, 40), (52, 20), (33, 33)):
        img += 3000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / 18.0)

    labels, count, _ = segment_mosaic(img, mesh, sigma=1.5)

    sm = gaussian_smooth(jnp.asarray(img), 1.5)
    golden_mask = np.asarray(sm > otsu_value(sm))
    golden, n = _golden(golden_mask, 8)
    assert int(count) == n > 0
    assert np.array_equal(np.asarray(labels), golden)


def test_single_row_shards(mesh):
    """rows == mesh size: every shard holds ONE row — both seam joins must
    land in the same row without livelocking the outer loop."""
    mask = np.zeros((8, 16), bool)
    mask[:, 5] = True
    labels, count = distributed_connected_components(mask, mesh)
    golden, n = _golden(mask, 8)
    assert int(count) == n == 1
    assert np.array_equal(np.asarray(labels), golden)


def test_distributed_watershed_bit_identical(mesh, rng):
    """Sharded watershed == single-device watershed on the gathered image,
    including tie-breaks (every adopt step exchanges 1-row halos)."""
    from tmlibrary_tpu.ops.label import connected_components
    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds
    from tmlibrary_tpu.parallel.label import watershed_mosaic

    yy, xx = np.mgrid[0:64, 0:48]
    img = rng.normal(100, 10, (64, 48)).astype(np.float32)
    for cy, cx in ((8, 10), (30, 30), (52, 12), (36, 36)):
        img += 2000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / 30.0)
    seeds_mask = img > 1500
    seeds = np.asarray(connected_components(jnp.asarray(seeds_mask))[0])
    mask = img > 300

    golden = np.asarray(
        watershed_from_seeds(jnp.asarray(img), jnp.asarray(seeds),
                             jnp.asarray(mask), n_levels=8, method="xla")
    )
    sharded = np.asarray(
        watershed_mosaic(img, seeds, mask, mesh, n_levels=8)[0]
    )
    assert np.array_equal(sharded, golden)
    assert sharded.max() > 0


def test_single_device_mesh_takes_native_shortcut(rng):
    """A 1-device CPU mesh routes CC and watershed through the native
    host kernels (the XLA fixpoint is pathological on CPU) and must be
    bit-identical to the 8-shard distributed result."""
    import scipy.ndimage as ndi
    from jax.sharding import Mesh

    from tmlibrary_tpu.parallel.label import (
        _native_cc_available,
        distributed_connected_components,
        distributed_connected_components_2d,
        watershed_mosaic,
    )

    if not _native_cc_available():
        # without this gate the test would silently re-test the XLA path
        pytest.skip("native library unavailable: shortcut cannot engage")

    mask = rng.random((64, 48)) > 0.7
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("rows",))
    mesh8 = Mesh(np.asarray(jax.devices()[:8]), ("rows",))
    l1, c1 = distributed_connected_components(mask, mesh1)
    l8, c8 = distributed_connected_components(mask, mesh8)
    assert int(c1) == int(c8)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l8))
    golden, n = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    assert int(c1) == n
    np.testing.assert_array_equal(np.asarray(l1), golden)

    intensity = rng.random((64, 48)).astype(np.float32) * 100
    seeds = np.where(np.asarray(l1) <= 3, np.asarray(l1), 0)
    grow = mask | (rng.random((64, 48)) > 0.5)
    w1, _ = watershed_mosaic(intensity, seeds, grow, mesh1)
    w8, _ = watershed_mosaic(intensity, seeds, grow, mesh8)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w8))

    # the degenerate 1x1 2-D mesh hits the same pathology: same shortcut
    mesh11 = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                  ("rows", "cols"))
    l11, c11 = distributed_connected_components_2d(mask, mesh11)
    assert int(c11) == int(c1)
    np.testing.assert_array_equal(np.asarray(l11), np.asarray(l1))

    w11, _ = watershed_mosaic(intensity, seeds, grow, mesh11)
    np.testing.assert_array_equal(np.asarray(w11), np.asarray(w1))
