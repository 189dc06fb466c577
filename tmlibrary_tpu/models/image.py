"""Image classes: thin metadata wrappers over ``jax.Array`` pixel buffers.

Reference parity: ``tmlib/image.py`` — ``Image``, ``ChannelImage``
(``correct``/``align``/``clip``/``scale``/``smooth``), ``SegmentationImage``
(label array ↔ polygons), ``IllumstatsContainer``, ``PyramidTile``.

Design (per BASELINE north star): pixel buffers are ``jax.Array``; every
method delegates to a pure function in :mod:`tmlibrary_tpu.ops` and returns a
new instance, so chains of methods trace into a single fused XLA program.
The classes are registered as pytrees, making them transparent to
``jit``/``vmap``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tmlibrary_tpu.ops import image_ops
from tmlibrary_tpu.ops.smooth import gaussian_smooth, median_smooth


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Image:
    """A 2-D pixel plane plus site metadata (reference ``tmlib.image.Image``)."""

    array: jax.Array
    metadata: dict = dataclasses.field(default_factory=dict)

    def tree_flatten(self):
        # aux_data must be hashable for jit's PyTreeDef cache: flatten the
        # metadata dict to a sorted item tuple (values must be hashable —
        # site/channel/tpoint scalars and names are)
        return (self.array,), tuple(sorted(self.metadata.items()))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], dict(aux))

    @property
    def shape(self) -> tuple:
        return self.array.shape

    @property
    def dtype(self):
        return self.array.dtype

    def _like(self, array: jax.Array) -> "Image":
        return type(self)(array, dict(self.metadata))

    def extract(self, y: int, x: int, height: int, width: int) -> "Image":
        return self._like(image_ops.extract(self.array, y, x, height, width))

    def insert(self, patch: "Image", y: int, x: int) -> "Image":
        return self._like(image_ops.insert(self.array, patch.array, y, x))

    def pad(self, top: int, bottom: int, left: int, right: int, value=0) -> "Image":
        return self._like(image_ops.pad(self.array, top, bottom, left, right, value))

    def numpy(self) -> np.ndarray:
        return np.asarray(self.array)

    @classmethod
    def join(cls, tiles: "list[Image] | jax.Array", grid_rows: int, grid_cols: int) -> "Image":
        """Assemble a row-major grid of equally-sized tiles into one mosaic
        (reference ``tmlib.image.Image.join``)."""
        if isinstance(tiles, (list, tuple)):
            if not tiles:
                raise ValueError("Image.join requires at least one tile")
            meta = dict(tiles[0].metadata)
            stack = jnp.stack([t.array for t in tiles])
        else:
            meta = {}
            stack = jnp.asarray(tiles)
        return cls(image_ops.join_grid(stack, grid_rows, grid_cols), meta)


@jax.tree_util.register_pytree_node_class
class ChannelImage(Image):
    """Intensity image of one channel at one site
    (reference ``tmlib.image.ChannelImage``)."""

    def correct(self, stats: "IllumstatsContainer") -> "ChannelImage":
        """Illumination-correct using corilla statistics."""
        return self._like(
            image_ops.correct_illumination(self.array, stats.mean_log, stats.std_log)
        )

    def align(self, dy, dx, window: tuple[int, int, int, int] | None = None) -> "ChannelImage":
        return self._like(image_ops.align(self.array, dy, dx, window))

    def clip(self, lower, upper) -> "ChannelImage":
        return self._like(image_ops.clip_values(self.array, lower, upper))

    def scale(self, lower, upper) -> "ChannelImage":
        return self._like(image_ops.rescale(self.array, lower, upper))

    def smooth(self, sigma: float = 1.0, method: str = "gaussian") -> "ChannelImage":
        if method == "gaussian":
            return self._like(gaussian_smooth(self.array, sigma))
        if method == "median":
            return self._like(median_smooth(self.array, int(sigma)))
        raise ValueError(f"unknown smoothing method '{method}'")


@jax.tree_util.register_pytree_node_class
class SegmentationImage(Image):
    """Labeled object image (reference ``tmlib.image.SegmentationImage``).

    ``array`` is int32; 0 = background, 1..N = object labels.
    """

    @property
    def n_objects(self) -> jax.Array:
        return jnp.max(self.array)

    def labels_host(self) -> np.ndarray:
        return np.asarray(self.array)

    def extract_polygons(self) -> list[tuple[int, np.ndarray]]:
        """Trace object outlines host-side → [(label, (K,2) y/x contour)].

        The reference stores PostGIS polygons per mapobject
        (``tmlib/models/mapobject.py`` ``MapobjectSegmentation``); polygon
        extraction is inherently ragged so it stays off-device here, using
        cv2 contour tracing on the host copy.
        """
        from tmlibrary_tpu.ops.polygons import labels_to_polygons

        return labels_to_polygons(self.labels_host())


@dataclasses.dataclass
class IllumstatsContainer:
    """Per-channel illumination statistics (reference
    ``tmlib.image.IllumstatsContainer`` / ``IllumstatsImage``).

    Statistics live in the log10 domain (matching corilla): per-pixel mean
    and std over all sites of a channel, plus intensity percentiles used for
    clipping/rescale at display time, and the site count.
    """

    mean_log: jax.Array
    std_log: jax.Array
    percentiles: dict[float, float]
    n: int

    def smooth(self, sigma: float = 5.0) -> "IllumstatsContainer":
        """Pre-smooth the statistic fields (the reference smooths stats
        before applying them so single-pixel noise doesn't amplify)."""
        return IllumstatsContainer(
            mean_log=gaussian_smooth(self.mean_log, sigma),
            std_log=gaussian_smooth(self.std_log, sigma),
            percentiles=self.percentiles,
            n=self.n,
        )

    def closest_percentile(self, q: float) -> float | None:
        """The stored percentile whose key is nearest to ``q`` (reference
        ``IllumstatsContainer.get_closest_percentile``), accepted only
        within 1e-4 — what float32 rounding needs: stores written before
        the keys were float64 hold float32 keys (99.9 reads back as
        99.90000152...), and 99.0 must never answer for 99.9.  ``None``
        when corilla computed no such percentile."""
        key = min(self.percentiles, key=lambda k: abs(k - q), default=None)
        if key is None or abs(key - q) > 1e-4:
            return None
        return self.percentiles[key]

    @classmethod
    def from_store(cls, d: dict[str, Any]) -> "IllumstatsContainer":
        pct_keys = d.get("percentile_keys")
        pct_vals = d.get("percentile_values")
        percentiles = (
            {float(k): float(v) for k, v in zip(pct_keys, pct_vals)}
            if pct_keys is not None
            else {}
        )
        return cls(
            mean_log=jnp.asarray(d["mean_log"]),
            std_log=jnp.asarray(d["std_log"]),
            percentiles=percentiles,
            n=int(d["n"]),
        )

    def to_store(self) -> dict[str, np.ndarray]:
        keys = sorted(self.percentiles)
        return {
            "mean_log": np.asarray(self.mean_log),
            "std_log": np.asarray(self.std_log),
            "percentile_keys": np.asarray(keys, np.float64),
            "percentile_values": np.asarray([self.percentiles[k] for k in keys]),
            "n": np.asarray(self.n),
        }


class PyramidTile:
    """A 256x256 display tile (reference ``tmlib.image.PyramidTile``)."""

    TILE_SIZE = 256

    def __init__(self, array: np.ndarray):
        arr = np.asarray(array)
        if arr.shape != (self.TILE_SIZE, self.TILE_SIZE):
            raise ValueError(f"tile must be {self.TILE_SIZE}px square, got {arr.shape}")
        self.array = arr

    def encode_png(self) -> bytes:
        """Encode as 8-bit grayscale PNG (host-side)."""
        import cv2

        arr = self.array
        if arr.dtype != np.uint8:
            arr = np.clip(arr, 0, 255).astype(np.uint8)
        ok, buf = cv2.imencode(".png", arr)
        if not ok:
            raise RuntimeError("PNG encoding failed")
        return bytes(buf.tobytes())
