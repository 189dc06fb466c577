"""3-D (z-stack) segmentation ops.

Reference parity: the reference's 3-D path — ``generate_volume_image``
(builds a z-stack volume per site) and 3-D variants of segmentation in
``jtlib`` (SURVEY.md §3 lists ``generate_volume_image`` [L]; BASELINE
config 5 names "3D z-stack segmentation" as the stretch benchmark).

TPU design: the same gather-free machinery as 2-D labeling — segmented
run-min scans along each of the three axes plus diagonal neighbor
min-propagation inside ``lax.while_loop`` — and level-ordered flooding for
3-D watershed.  Volumes are (Z, Y, X), static shapes, vmap-safe over sites.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tmlibrary_tpu.ops import named
from tmlibrary_tpu.ops.label import _run_min_scan

_BIG = jnp.iinfo(jnp.int32).max


def shift3d(arr: jax.Array, dz: int, dy: int, dx: int, fill) -> jax.Array:
    """``out[z,y,x] = arr[z+dz, y+dy, x+dx]`` with ``fill`` at borders."""
    z, h, w = arr.shape
    padded = jnp.pad(arr, ((1, 1), (1, 1), (1, 1)), constant_values=fill)
    return lax.dynamic_slice(padded, (1 + dz, 1 + dy, 1 + dx), (z, h, w))


def _diag_shifts_3d(connectivity: int) -> list[tuple[int, int, int]]:
    """Neighbor offsets NOT covered by the three axis run-scans."""
    if connectivity == 6:
        return []
    out = []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                nonzero = (dz != 0) + (dy != 0) + (dx != 0)
                if nonzero < 2:
                    continue  # axis neighbors (or self) — scans cover them
                if connectivity == 18 and nonzero == 3:
                    continue  # corner neighbors excluded at conn 18
                out.append((dz, dy, dx))
    return out


def _native_3d() -> bool:
    from tmlibrary_tpu import native

    return native.cpu_native_enabled() and native.has_3d_kernels()


@named("label")
def connected_components_3d(
    mask: jax.Array, connectivity: int = 26, method: str = "auto",
    chunk: "int | None" = None,
) -> tuple[jax.Array, jax.Array]:
    """Label 3-D connected components; scipy scan order, like the 2-D op.

    ``connectivity``: 6 (faces), 18 (faces+edges), 26 (full).
    ``method="auto"`` resolution order (same as the 2-D ops): the native
    union-find (``tm_cc_label3d``) on the cpu backend → the VMEM pallas
    kernel (``pallas_kernels.cc3d_min_propagate``) on TPU when the
    hardware shootout says it wins (``pallas_enabled("cc3d")``) → xla.
    All three produce the identical scipy-scan-order labeling.
    """
    mask = jnp.asarray(mask, bool)
    z, h, w = mask.shape
    if connectivity not in (6, 18, 26):
        # validate BEFORE dispatch: the xla diag-shift enumeration would
        # silently treat e.g. the 2-D habit value 8 as 26-connectivity
        # while the native kernel rejects it — backend-dependent behavior
        raise ValueError("3-D connectivity must be 6, 18 or 26")
    if method == "auto":
        if _native_3d():
            method = "native"
        else:
            from tmlibrary_tpu.ops.pallas_kernels import pallas_enabled

            method = (
                "pallas" if pallas_enabled("cc3d", mask.shape) else "xla"
            )
    if method == "native":
        import numpy as np

        from tmlibrary_tpu import native

        @native.batch_sites(3)
        def _cc3d_host(m):
            labels, count = native.cc_label3d_host(np.asarray(m), connectivity)
            return labels, np.int32(count)

        return jax.pure_callback(
            _cc3d_host,
            (
                jax.ShapeDtypeStruct((z, h, w), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
            ),
            mask,
            vmap_method=native.callback_vmap_method(),
        )
    linear = jnp.arange(z * h * w, dtype=jnp.int32).reshape(z, h, w)

    if method == "pallas":
        from tmlibrary_tpu.ops.pallas_kernels import cc3d_min_propagate

        # identical min-linear-index fixpoint in VMEM; compaction to
        # scipy scan order below is shared with the xla path
        labels = cc3d_min_propagate(
            mask, connectivity, interpret=jax.default_backend() == "cpu",
            chunk=chunk,
        )
        labels = jnp.where(mask, labels, _BIG)
    else:
        shifts = _diag_shifts_3d(connectivity)
        init = jnp.where(mask, linear, _BIG)

        def cond(state):
            return state[1]

        def body(state):
            labels, _ = state
            new = labels
            if shifts:
                for s in shifts:
                    new = jnp.minimum(new, shift3d(labels, *s, _BIG))
                new = jnp.where(mask, new, _BIG)
            new = _run_min_scan(new, mask, axis=2)
            new = _run_min_scan(new, mask, axis=1)
            new = _run_min_scan(new, mask, axis=0)
            return new, jnp.any(new != labels)

        labels, _ = lax.while_loop(cond, body, (init, jnp.bool_(True)))

    is_root = mask & (labels == linear)
    ranks = jnp.cumsum(is_root.reshape(-1).astype(jnp.int32))
    count = ranks[-1]
    root_rank = ranks.reshape(-1)[jnp.clip(labels.reshape(-1), 0, z * h * w - 1)]
    out = jnp.where(mask, root_rank.reshape(z, h, w), 0).astype(jnp.int32)
    return out, count


def _adopt_step_3d(labels: jax.Array, allowed: jax.Array) -> jax.Array:
    neigh_max = jnp.zeros_like(labels)
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                if dz == dy == dx == 0:
                    continue
                neigh_max = jnp.maximum(neigh_max, shift3d(labels, dz, dy, dx, 0))
    return jnp.where((labels == 0) & allowed, neigh_max, labels)


def propagate_labels_3d(labels: jax.Array, allowed: jax.Array) -> jax.Array:
    labels = jnp.asarray(labels, jnp.int32)
    allowed = jnp.asarray(allowed, bool)

    def cond(state):
        return state[1]

    def body(state):
        lab, _ = state
        new = _adopt_step_3d(lab, allowed)
        return new, jnp.any(new != lab)

    out, _ = lax.while_loop(cond, body, (labels, jnp.bool_(True)))
    return out


@named("watershed")
def watershed_from_seeds_3d(
    intensity: jax.Array,
    seeds: jax.Array,
    mask: jax.Array,
    n_levels: int = 16,
    method: str = "auto",
    chunk: "int | None" = None,
) -> jax.Array:
    """3-D level-ordered flooding (same scheme as the 2-D watershed).

    ``method="auto"`` routes to the native frontier flood
    (``tm_watershed_levels3d``) on the cpu backend, the VMEM pallas
    kernel on TPU per ``pallas_enabled("watershed3d")``, else xla; the
    level thresholds are computed by the same expression every way, so
    band membership is decided by exact float comparisons
    (bit-identical)."""
    intensity = jnp.asarray(intensity, jnp.float32)
    seeds = jnp.asarray(seeds, jnp.int32)
    mask = jnp.asarray(mask, bool) | (seeds > 0)

    if method == "auto":
        if _native_3d():
            method = "native"
        else:
            from tmlibrary_tpu.ops.pallas_kernels import pallas_enabled

            method = "xla"
            if pallas_enabled("watershed3d", intensity.shape):
                method = "pallas"
    if method == "pallas":
        from tmlibrary_tpu.ops.pallas_kernels import watershed3d_flood

        # the kernel computes lo/hi/span in VMEM itself
        return watershed3d_flood(
            intensity, seeds, mask, n_levels=n_levels,
            interpret=jax.default_backend() == "cpu",
            chunk=chunk,
        )

    lo = jnp.min(jnp.where(mask, intensity, jnp.inf))
    hi = jnp.max(jnp.where(mask, intensity, -jnp.inf))
    span = jnp.maximum(hi - lo, 1e-6)

    if method == "native":
        import numpy as np

        from tmlibrary_tpu import native

        i = jnp.arange(n_levels, dtype=jnp.int32)
        levels = hi - span * (i + 1) / n_levels
        return jax.pure_callback(
            native.batch_sites(3, 3, 3, 1)(
                lambda im, sd, mk, lv: native.watershed_levels3d_host(
                    np.asarray(im), np.asarray(sd), np.asarray(mk),
                    np.asarray(lv),
                )
            ),
            jax.ShapeDtypeStruct(intensity.shape, jnp.int32),
            intensity, seeds, mask, levels,
            vmap_method=native.callback_vmap_method(),
        )

    def level_body(i, labels):
        level = hi - span * (i + 1) / n_levels
        allowed = mask & (intensity >= level)
        return propagate_labels_3d(labels, allowed)

    labels = lax.fori_loop(0, n_levels, level_body, seeds)
    labels = propagate_labels_3d(labels, mask)
    return jnp.where(mask, labels, 0)


def volume_features(
    labels: jax.Array, intensity: jax.Array, max_objects: int
) -> dict[str, jax.Array]:
    """Per-object 3-D measurements: volume, centroid, intensity stats."""
    from tmlibrary_tpu.ops.measure import grouped_sums

    labels = jnp.asarray(labels, jnp.int32)
    img = jnp.asarray(intensity, jnp.float32)
    z, h, w = labels.shape
    ones = jnp.ones((z, h, w), jnp.float32)
    zz, yy, xx = jnp.meshgrid(
        jnp.arange(z, dtype=jnp.float32),
        jnp.arange(h, dtype=jnp.float32),
        jnp.arange(w, dtype=jnp.float32),
        indexing="ij",
    )
    sums = grouped_sums(labels, [ones, zz, yy, xx, img, img * img], max_objects)
    vol = sums[:, 0]
    safe = jnp.maximum(vol, 1.0)
    total = sums[:, 4]
    mean = total / safe
    var = jnp.maximum(sums[:, 5] / safe - mean * mean, 0.0)
    present = vol > 0

    def m(v):
        return jnp.where(present, v, 0.0)

    return {
        "Volume_voxels": vol,
        "Volume_centroid_z": m(sums[:, 1] / safe),
        "Volume_centroid_y": m(sums[:, 2] / safe),
        "Volume_centroid_x": m(sums[:, 3] / safe),
        "Volume_intensity_mean": m(mean),
        "Volume_intensity_sum": total,
        "Volume_intensity_std": m(jnp.sqrt(var)),
    }
