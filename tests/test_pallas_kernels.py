"""Pallas kernel twins vs the XLA implementations (interpret mode on CPU).

The pallas kernels must reach the IDENTICAL fixpoint as the XLA paths —
same min-linear-index CC labeling, same watershed schedule/tie-breaking —
so the dispatch in ``connected_components``/``watershed_from_seeds`` can
switch per backend without changing results (BASELINE bit-identical gate).
"""

import numpy as np
import pytest
import scipy.ndimage as ndi

from tmlibrary_tpu.ops.label import connected_components
from tmlibrary_tpu.ops.pallas_kernels import (
    BIG,
    cc_min_propagate,
    watershed_flood,
)
from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds


def blobs(rng, shape=(64, 64), n=6, r=4):
    img = np.zeros(shape, np.float32)
    yy, xx = np.mgrid[0 : shape[0], 0 : shape[1]]
    for _ in range(n):
        y, x = rng.integers(r, shape[0] - r, 2)
        img += np.exp(-((yy - y) ** 2 + (xx - x) ** 2) / (2 * (r / 2) ** 2))
    return img


@pytest.mark.parametrize("connectivity", [4, 8])
def test_cc_min_propagate_matches_xla(rng, connectivity):
    img = blobs(rng)
    mask = img > 0.3

    got = np.asarray(cc_min_propagate(mask, connectivity, interpret=True))
    labels_xla, count = connected_components(mask, connectivity, method="xla")
    # reconstruct the min-linear-index fixpoint from the compacted XLA
    # output: pixels of the same component share the component's min index
    h, w = mask.shape
    linear = np.arange(h * w).reshape(h, w)
    want = np.full((h, w), int(BIG), np.int32)
    lx = np.asarray(labels_xla)
    for lab in range(1, int(count) + 1):
        m = lx == lab
        want[m] = linear[m].min()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("chunk", [1, 4, 16, 32])
def test_chunk_is_output_invariant(rng, chunk):
    """The convergence-check interval (the tune_tpu ``pallas_chunk``
    sweep dimension) is purely a performance knob: the propagation
    fixpoint is idempotent, so every chunk value must produce
    BIT-identical labels — CC and watershed both."""
    img = blobs(rng, n=8)
    mask = img > 0.3

    base = np.asarray(cc_min_propagate(mask, 8, interpret=True))
    got = np.asarray(cc_min_propagate(mask, 8, interpret=True, chunk=chunk))
    np.testing.assert_array_equal(got, base)

    seeds_src = connected_components(img > 0.6, 8, method="xla")[0]
    ws_base = np.asarray(watershed_flood(
        img, seeds_src, mask, n_levels=8, interpret=True))
    ws_got = np.asarray(watershed_flood(
        img, seeds_src, mask, n_levels=8, interpret=True, chunk=chunk))
    np.testing.assert_array_equal(ws_got, ws_base)


def test_tuned_chunk_resolution(monkeypatch):
    """Env override beats the committed sweep beats the default."""
    from tmlibrary_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_tuning_results", lambda: {"pallas_chunk": 16})
    monkeypatch.delenv("TMX_PALLAS_CHUNK", raising=False)
    assert pk._tuned_chunk() == 16
    monkeypatch.setenv("TMX_PALLAS_CHUNK", "4")
    assert pk._tuned_chunk() == 4
    monkeypatch.setattr(pk, "_tuning_results", lambda: {})
    monkeypatch.delenv("TMX_PALLAS_CHUNK", raising=False)
    assert pk._tuned_chunk() == pk.CHUNK


def test_cc_pallas_through_dispatch(rng):
    """connected_components(method='pallas') — the real dispatch branch,
    kernel via interpret mode on CPU — compacts to scipy order."""
    img = blobs(rng, n=8)
    mask = img > 0.3
    labels_p, count_p = connected_components(mask, 8, method="pallas")
    lab_sp, n_sp = ndi.label(np.asarray(mask), ndi.generate_binary_structure(2, 2))
    assert int(count_p) == n_sp
    np.testing.assert_array_equal(np.asarray(labels_p), lab_sp)


def test_watershed_pallas_through_dispatch(rng):
    """watershed_from_seeds(method='pallas') equals the XLA twin through
    the public dispatch."""
    img = blobs(rng, n=4, r=6)
    seeds, _ = connected_components(img > 0.6, 8, method="xla")
    mask = img > 0.1
    got = np.asarray(
        watershed_from_seeds(img, seeds, mask, n_levels=8, method="pallas")
    )
    want = np.asarray(
        watershed_from_seeds(img, seeds, mask, n_levels=8, method="xla")
    )
    np.testing.assert_array_equal(got, want)


def test_cc_min_propagate_edge_cases():
    # empty mask
    empty = np.zeros((16, 16), bool)
    out = np.asarray(cc_min_propagate(empty, 8, interpret=True))
    assert (out == int(BIG)).all()
    # full mask: one component rooted at pixel 0
    full = np.ones((16, 16), bool)
    out = np.asarray(cc_min_propagate(full, 8, interpret=True))
    assert (out == 0).all()
    # single pixel at a corner
    single = np.zeros((16, 16), bool)
    single[15, 15] = True
    out = np.asarray(cc_min_propagate(single, 4, interpret=True))
    assert out[15, 15] == 15 * 16 + 15


def test_cc_serpentine_converges():
    """A serpentine 1-px path — worst case for plain neighbor propagation —
    must still converge exactly."""
    h, w = 24, 24
    mask = np.zeros((h, w), bool)
    for r in range(0, h, 4):
        mask[r, :] = True
        if (r // 4) % 2 == 0 and r + 4 < h:
            mask[r : r + 5, w - 1] = True
        elif r + 4 < h:
            mask[r : r + 5, 0] = True
    got = np.asarray(cc_min_propagate(mask, 8, interpret=True))
    lab_sp, n = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    assert n == 1
    assert (got[mask] == np.flatnonzero(mask.ravel()).min()).all()


def test_watershed_flood_matches_xla(rng):
    dapi = blobs(rng, n=5, r=3)
    actin = blobs(rng, n=5, r=8) + 0.05
    seed_mask = dapi > 0.5
    seeds, _ = connected_components(seed_mask, 8, method="xla")
    mask = actin > 0.15

    got = np.asarray(
        watershed_flood(actin, seeds, mask, n_levels=8, interpret=True)
    )
    want = np.asarray(
        watershed_from_seeds(actin, seeds, mask, n_levels=8, method="xla")
    )
    np.testing.assert_array_equal(got, want)


def test_distance_transform_matches_xla(rng):
    from tmlibrary_tpu.ops.pallas_kernels import distance_transform
    from tmlibrary_tpu.ops.segment_primary import distance_transform_approx

    img = blobs(rng, n=5, r=8)
    mask = img > 0.2
    got = np.asarray(distance_transform(mask, interpret=True))
    want = np.asarray(distance_transform_approx(mask, method="xla"))
    np.testing.assert_array_equal(got, want)
    # chessboard distance golden (interior): erosion counting equals
    # chebyshev distance-to-background.  Image-border pixels differ by
    # design: erosion treats outside-of-image as foreground (reflect),
    # cdt does not.
    dist_cheb = ndi.distance_transform_cdt(mask, metric="chessboard")
    interior = np.zeros_like(mask)
    interior[8:-8, 8:-8] = True
    np.testing.assert_array_equal(got[interior], dist_cheb[interior])


def test_distance_transform_border_touching_mask(rng):
    """Masks touching the image border must not erode from the edge side:
    both paths treat out-of-image neighbors as foreground."""
    from tmlibrary_tpu.ops.pallas_kernels import distance_transform
    from tmlibrary_tpu.ops.segment_primary import distance_transform_approx

    mask = np.zeros((64, 64), bool)
    mask[0:12, 0:12] = True      # corner blob
    mask[50:64, 20:40] = True    # bottom-edge blob
    mask[:, 60:64] = True        # full-height right stripe
    got = np.asarray(distance_transform(mask, interpret=True))
    want = np.asarray(distance_transform_approx(mask, method="xla"))
    np.testing.assert_array_equal(got, want)
    # the corner pixel is insulated by the border on two sides: its
    # distance must reflect only the in-image background
    assert got[0, 0] == min(12, 12)


def test_distance_transform_through_dispatch(rng):
    from tmlibrary_tpu.ops.segment_primary import distance_transform_approx

    img = blobs(rng, n=3, r=6)
    mask = img > 0.3
    got = np.asarray(distance_transform_approx(mask, method="pallas"))
    want = np.asarray(distance_transform_approx(mask, method="xla"))
    np.testing.assert_array_equal(got, want)


def test_watershed_flood_seeds_kept(rng):
    img = blobs(rng, n=4, r=6)
    seed_mask = img > 0.6
    seeds, count = connected_components(seed_mask, 8, method="xla")
    mask = img > 0.1
    out = np.asarray(
        watershed_flood(img, seeds, mask, n_levels=4, interpret=True)
    )
    s = np.asarray(seeds)
    np.testing.assert_array_equal(out[s > 0], s[s > 0])
    # labels only appear inside the (mask | seeds) region
    m = np.asarray(mask) | (s > 0)
    assert (out[~m] == 0).all()


def test_pallas_enabled_resolution_order(monkeypatch):
    """Dispatch resolution: env override beats the committed tuning
    verdict beats off; CPU/GPU backends never use pallas."""
    from tmlibrary_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    pk._tuning_results.cache_clear()
    monkeypatch.setattr(pk, "_tuning_results", lambda: {"pallas_wins": True})
    monkeypatch.delenv("TMX_PALLAS", raising=False)
    assert pk.pallas_enabled() is True
    monkeypatch.setattr(pk, "_tuning_results", lambda: {"pallas_wins": False})
    assert pk.pallas_enabled() is False
    monkeypatch.setattr(pk, "_tuning_results", lambda: {})
    assert pk.pallas_enabled() is False  # no verdict -> off
    monkeypatch.setenv("TMX_PALLAS", "1")
    assert pk.pallas_enabled() is True  # env beats everything
    monkeypatch.setattr(pk, "_tuning_results", lambda: {"pallas_wins": True})
    monkeypatch.setenv("TMX_PALLAS", "0")
    assert pk.pallas_enabled() is False
    # non-TPU backends: always the XLA twins
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv("TMX_PALLAS", "1")
    assert pk.pallas_enabled() is False


def test_pallas_enabled_per_kernel(monkeypatch):
    """The measured per-kernel shootout beats the aggregate verdict: a
    split TUNING.json (cc faster in pallas, watershed faster in xla)
    must dispatch each kernel to its own winner."""
    from tmlibrary_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("TMX_PALLAS", raising=False)
    split = {
        "pallas_wins": True,
        "kernels_ms": {
            "cc_pallas": 88.8, "cc_xla": 186.9,
            "watershed_pallas": 53.4, "watershed_xla": 47.4,
            "distance_pallas": None, "distance_xla": 68.2,  # failed kernel
        },
    }
    monkeypatch.setattr(pk, "_tuning_results", lambda: split)
    assert pk.pallas_enabled("cc") is True
    assert pk.pallas_enabled("watershed") is False
    # null timing (kernel FAILED on hardware during the shootout) ->
    # never auto-dispatch to the failed kernel
    assert pk.pallas_enabled("distance") is False
    # unknown/unmeasured kernel name (no shootout entry at all) -> NEVER
    # auto-dispatch: only the trio the aggregate was computed from may
    # ride it (a stale file must not route through an unmeasured kernel)
    assert pk.pallas_enabled("nope") is False
    # env override still beats the per-kernel data, both directions
    monkeypatch.setenv("TMX_PALLAS", "0")
    assert pk.pallas_enabled("cc") is False
    monkeypatch.setenv("TMX_PALLAS", "1")
    assert pk.pallas_enabled("watershed") is True


def test_glcm_method_resolution(monkeypatch):
    """GLCM accumulation: scatter on CPU, the contraction elsewhere — a
    constant since the chip decided (PR 27), no tuning verdict."""
    import tmlibrary_tpu.ops.measure as measure
    from tmlibrary_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(measure.jax, "default_backend", lambda: "cpu")
    assert measure._resolve_glcm_method("auto") == "scatter"
    assert measure._resolve_glcm_method("matmul") == "matmul"

    monkeypatch.setattr(measure.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pk, "_tuning_results", lambda: {"glcm_matmul_wins": False})
    assert measure._resolve_glcm_method("auto") == "matmul"
    monkeypatch.setattr(pk, "_tuning_results", lambda: {})
    assert measure._resolve_glcm_method("auto") == "matmul"


# ------------------------------------------------------------- 3-D twins
def _vol(rng, nz=8, size=48, n=5):
    zz, yy, xx = np.mgrid[0:nz, 0:size, 0:size].astype(np.float32)
    vol = rng.normal(0.0, 0.05, (nz, size, size)).astype(np.float32)
    for _ in range(n):
        z, y, x = rng.integers(2, nz - 2), *rng.integers(6, size - 6, 2)
        vol += np.exp(-(((zz - z) * 2.0) ** 2 + (yy - y) ** 2
                        + (xx - x) ** 2) / 8.0)
    return vol


@pytest.mark.parametrize("connectivity", [6, 18, 26])
def test_cc3d_pallas_matches_xla(rng, connectivity):
    """connected_components_3d(method='pallas') — the real dispatch
    branch, kernel via interpret mode on CPU — is bit-identical to the
    xla path (labels AND count)."""
    from tmlibrary_tpu.ops.volume import connected_components_3d

    mask = _vol(rng) > 0.35
    lab_x, n_x = connected_components_3d(mask, connectivity, method="xla")
    lab_p, n_p = connected_components_3d(mask, connectivity, method="pallas")
    assert int(n_p) == int(n_x)
    np.testing.assert_array_equal(np.asarray(lab_p), np.asarray(lab_x))


def test_watershed3d_pallas_matches_xla(rng):
    from tmlibrary_tpu.ops.volume import (
        connected_components_3d,
        watershed_from_seeds_3d,
    )

    vol = _vol(rng, n=6)
    seeds = connected_components_3d(vol > 0.6, 26, method="xla")[0]
    mask = vol > 0.25
    want = np.asarray(watershed_from_seeds_3d(vol, seeds, mask, 8,
                                              method="xla"))
    got = np.asarray(watershed_from_seeds_3d(vol, seeds, mask, 8,
                                             method="pallas"))
    np.testing.assert_array_equal(got, want)


def test_cc3d_chunk_output_invariant(rng):
    from tmlibrary_tpu.ops.pallas_kernels import cc3d_min_propagate

    mask = _vol(rng) > 0.35
    base = np.asarray(cc3d_min_propagate(mask, 26, interpret=True))
    for chunk in (1, 16):
        got = np.asarray(cc3d_min_propagate(mask, 26, interpret=True,
                                            chunk=chunk))
        np.testing.assert_array_equal(got, base)


@pytest.mark.parametrize("connectivity", [4, 8])
def test_fill_holes_pallas_matches_xla_and_scipy(rng, connectivity):
    """fill_holes(method='pallas') — VMEM border flood via interpret mode
    — is bit-identical to the XLA flood; at background connectivity 4 it
    also equals scipy.binary_fill_holes (the complement of 8-connected
    foreground, the jtmodules fill semantics)."""
    from tmlibrary_tpu.ops.label import fill_holes

    img = blobs(rng, n=6, r=7)
    mask = img > 0.25
    # punch interior holes so there is something to fill
    mask[20:24, 20:24] = False
    mask[40:43, 10:12] = False

    got = np.asarray(fill_holes(mask, connectivity, method="pallas"))
    want = np.asarray(fill_holes(mask, connectivity, method="xla"))
    np.testing.assert_array_equal(got, want)
    if connectivity == 4:
        np.testing.assert_array_equal(
            got, ndi.binary_fill_holes(mask))


def test_fill_holes_chunk_output_invariant(rng):
    from tmlibrary_tpu.ops.pallas_kernels import fill_holes_flood

    img = blobs(rng, n=6, r=7)
    mask = img > 0.25
    mask[30:33, 30:33] = False
    base = np.asarray(fill_holes_flood(mask, interpret=True))
    for chunk in (1, 16):
        got = np.asarray(fill_holes_flood(mask, interpret=True, chunk=chunk))
        np.testing.assert_array_equal(got, base)


def test_unmeasured_kernel_never_rides_aggregate(monkeypatch):
    """A stale pre-fill/pre-3D TUNING.json with pallas_wins=true must not
    auto-dispatch the kernels it never measured."""
    from tmlibrary_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("TMX_PALLAS", raising=False)
    stale = {
        "pallas_wins": True,
        "kernels_ms": {"cc_pallas": 80.0, "cc_xla": 180.0,
                       "watershed_pallas": 50.0, "watershed_xla": 45.0},
    }
    monkeypatch.setattr(pk, "_tuning_results", lambda: stale)
    assert pk.pallas_enabled("cc") is True          # measured win
    assert pk.pallas_enabled("watershed") is False  # measured loss
    assert pk.pallas_enabled("distance") is True    # trio rides aggregate
    for newer in ("fill", "cc3d", "watershed3d"):
        assert pk.pallas_enabled(newer) is False, newer
