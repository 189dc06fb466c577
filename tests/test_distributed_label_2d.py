"""2-D spatially-sharded distributed CC + halo ops vs scipy golden.

The mosaic path sharded over BOTH spatial axes (mesh rows x cols): one
object may now cross horizontal seams, vertical seams, and — the case a
1-D layout never hits — the corner where four shards meet, touching only
diagonally.  Everything must stay bit-identical to ``scipy.ndimage.label``
/ the single-device ops on the gathered mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.ndimage as ndi
from jax.sharding import Mesh

from tmlibrary_tpu.errors import ShardingError
from tmlibrary_tpu.parallel.halo import (
    sharded_gaussian_smooth_2d,
    sharded_halo_map_2d,
)
from tmlibrary_tpu.parallel.label import (
    distributed_connected_components,
    distributed_connected_components_2d,
    segment_mosaic,
)


@pytest.fixture
def mesh42(devices):
    return Mesh(np.asarray(devices).reshape(4, 2), ("rows", "cols"))


@pytest.fixture
def mesh24(devices):
    return Mesh(np.asarray(devices).reshape(2, 4), ("rows", "cols"))


def _golden(mask, connectivity):
    structure = ndi.generate_binary_structure(2, 1 if connectivity == 4 else 2)
    return ndi.label(mask, structure)


@pytest.mark.parametrize("connectivity", [4, 8])
def test_random_mask_matches_scipy_2d(mesh42, rng, connectivity):
    mask = rng.random((64, 48)) > 0.65
    labels, count = distributed_connected_components_2d(
        mask, mesh42, connectivity=connectivity
    )
    golden, n = _golden(mask, connectivity)
    assert int(count) == n
    assert np.array_equal(np.asarray(labels), golden)


def test_corner_diagonal_adjacency(mesh42):
    """Two pixels touching ONLY diagonally across the four-shard corner:
    one component under 8-connectivity, two under 4 — the case that
    requires the corner-extended seam exchange."""
    mask = np.zeros((64, 48), bool)
    # shard tiles are 16x24: (15, 23) is the bottom-right pixel of tile
    # (0, 0); (16, 24) the top-left pixel of tile (1, 1)
    mask[15, 23] = mask[16, 24] = True
    labels, count = distributed_connected_components_2d(mask, mesh42, 8)
    assert int(count) == 1
    lab = np.asarray(labels)
    assert lab[15, 23] == lab[16, 24] == 1
    labels4, count4 = distributed_connected_components_2d(mask, mesh42, 4)
    assert int(count4) == 2
    # the anti-diagonal corner too: (16, 23) bottom-left of tile (1, 0)
    # up-right to (15, 24)? use fresh pixels inside the same tiles
    mask = np.zeros((64, 48), bool)
    mask[16, 23] = mask[15, 24] = True
    labels, count = distributed_connected_components_2d(mask, mesh42, 8)
    assert int(count) == 1


def test_object_spanning_all_eight_shards(mesh42):
    """A plus-shaped band crossing every seam converges to one id."""
    mask = np.zeros((64, 48), bool)
    mask[:, 22:26] = True
    mask[30:34, :] = True
    labels, count = distributed_connected_components_2d(mask, mesh42)
    assert int(count) == 1
    assert set(np.unique(np.asarray(labels))) == {0, 1}


def test_mesh_shape_invariance(mesh42, mesh24, devices, rng):
    """The same mask labels identically on (4,2), (2,4), 1-D (8,) and a
    single device — the layout is an implementation detail."""
    mask = rng.random((64, 64)) > 0.6
    golden, n = _golden(mask, 8)
    l42, c42 = distributed_connected_components_2d(mask, mesh42)
    l24, c24 = distributed_connected_components_2d(mask, mesh24)
    mesh1d = Mesh(np.asarray(devices), ("rows",))
    l1d, c1d = distributed_connected_components(mask, mesh1d)
    assert int(c42) == int(c24) == int(c1d) == n
    assert np.array_equal(np.asarray(l42), golden)
    assert np.array_equal(np.asarray(l24), golden)
    assert np.array_equal(np.asarray(l1d), golden)


def test_dims_must_divide(mesh42):
    with pytest.raises(ShardingError):
        distributed_connected_components_2d(np.zeros((64, 47), bool), mesh42)
    with pytest.raises(ShardingError):
        distributed_connected_components_2d(np.zeros((63, 48), bool), mesh42)


def test_root_overflow_detected_2d(mesh42):
    mask = np.zeros((64, 48), bool)
    mask[::2, ::2] = True  # 16x24/4 = isolated pixels per shard > bound
    with pytest.raises(ShardingError):
        distributed_connected_components_2d(
            mask, mesh42, max_roots_per_shard=64
        )


def test_sharded_gaussian_smooth_2d_bit_identical(mesh42, rng):
    from tmlibrary_tpu.ops.smooth import gaussian_smooth

    img = rng.random((64, 48)).astype(np.float32)
    out = sharded_gaussian_smooth_2d(img, mesh42, sigma=1.5)
    ref = jax.jit(lambda x: gaussian_smooth(x, 1.5))(jnp.asarray(img))
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_sharded_halo_map_2d_dims_must_divide(mesh42):
    with pytest.raises(ShardingError):
        sharded_halo_map_2d(lambda x: x, np.zeros((64, 45)), mesh42, 1)


def test_distributed_watershed_2d_bit_identical(mesh42, mesh24, rng):
    """2-D-sharded watershed == single-device watershed on the gathered
    mosaic, tie-breaks included (zero-filled 1-pixel halos per adopt
    step, corners carried by the two-step exchange)."""
    from tmlibrary_tpu.ops.label import connected_components
    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds
    from tmlibrary_tpu.parallel.label import watershed_mosaic

    yy, xx = np.mgrid[0:64, 0:48]
    img = rng.normal(100, 10, (64, 48)).astype(np.float32)
    # one basin dead on the center four-shard corner (tiles are 16x24)
    for cy, cx in ((8, 10), (32, 24), (52, 12), (36, 40)):
        img += 2000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / 30.0)
    seeds_mask = img > 1500
    seeds = np.asarray(connected_components(jnp.asarray(seeds_mask))[0])
    mask = img > 300

    golden = np.asarray(
        watershed_from_seeds(jnp.asarray(img), jnp.asarray(seeds),
                             jnp.asarray(mask), n_levels=8, method="xla")
    )
    for mesh in (mesh42, mesh24):
        sharded = np.asarray(
            watershed_mosaic(img, seeds, mask, mesh, n_levels=8)[0]
        )
        assert np.array_equal(sharded, golden)
    assert golden.max() > 0
    # and the 1-D path agrees on the same inputs
    mesh1d = Mesh(np.asarray(mesh42.devices).reshape(-1), ("rows",))
    one_d = np.asarray(
        watershed_mosaic(img, seeds, mask, mesh1d, n_levels=8)[0]
    )
    assert np.array_equal(one_d, golden)


def test_distributed_watershed_2d_dims_must_divide(mesh42):
    from tmlibrary_tpu.parallel.label import watershed_mosaic

    bad = np.zeros((63, 48), np.float32)
    with pytest.raises(ShardingError):
        watershed_mosaic(
            bad, np.zeros((63, 48), np.int32), np.zeros((63, 48), bool),
            mesh42,
        )


def test_segment_mosaic_2d_end_to_end(mesh42, mesh24, rng):
    """Blob mosaic: smooth + global otsu + 2-D CC matches the 1-D sharded
    path (itself scipy-golden-tested) exactly."""
    img = np.zeros((64, 64), np.float32)
    yy, xx = np.mgrid[:64, :64]
    for cy, cx in [(10, 12), (31, 33), (50, 20), (18, 52), (32, 0)]:
        img += np.exp(-(((yy - cy) ** 2 + (xx - cx) ** 2) / 18.0))
    img += rng.normal(0, 0.02, img.shape).astype(np.float32)
    l2d, c2d, _ = segment_mosaic(img, mesh42, sigma=1.5)
    mesh1d = Mesh(np.asarray(mesh42.devices).reshape(-1), ("rows",))
    l1d, c1d, _ = segment_mosaic(img, mesh1d, sigma=1.5)
    assert int(c2d) == int(c1d) > 0
    assert np.array_equal(np.asarray(l2d), np.asarray(l1d))
    l24, c24, _ = segment_mosaic(img, mesh24, sigma=1.5)
    assert int(c24) == int(c2d)
    assert np.array_equal(np.asarray(l24), np.asarray(l2d))
