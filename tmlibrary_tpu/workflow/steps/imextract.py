"""imextract: extract pixel planes into the canonical store.

Reference parity: ``tmlib/workflow/imextract/api.py`` ``ImageExtractor`` —
reads planes out of vendor files via Bio-Formats and writes
``ChannelImageFile``s, batched over file mappings.  Here: cv2 host reads of
the metaconfig file mapping, written as contiguous site stacks
(the TPU feed format) in batched slices.
"""

from __future__ import annotations

import numpy as np

from tmlibrary_tpu import telemetry
from tmlibrary_tpu.errors import MetadataError
from tmlibrary_tpu.utils import create_partitions
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.args import Argument, ArgumentCollection
from tmlibrary_tpu.workflow.registry import register_step


@register_step("imextract")
class ImageExtractor(Step):
    batch_args = ArgumentCollection(
        Argument("batch_size", int, default=64, help="files per batch"),
    )

    def create_batches(self, args):
        from tmlibrary_tpu.workflow.steps.metaconfig import MetadataConfigurator

        mapping = MetadataConfigurator(self.store).load_mapping()
        return [
            {"files": chunk}
            for chunk in create_partitions(mapping, args["batch_size"])
        ]

    @staticmethod
    def _read_plane(path: str, page: int | None, height: int, width: int):
        """One grayscale plane as uint16: first-party native TIFF reader
        (classic strip TIFF, none/LZW/PackBits — the native data-loader)
        with the Python paged fallback (BigTIFF, deflate strips), the
        first-party ND2 chunk-map reader for ``.nd2`` containers
        (``page`` encodes sequence * n_components + component, as written
        by the nd2 metaconfig handler), cv2 for everything else (PNG,
        tiled TIFF, RGB, ...).

        ``TMX_INGEST_THROTTLE_MS`` sleeps that long per plane read in
        the WORKER, simulating a slow/cold source (network filestore
        latency) deterministically: sleeps release the GIL, so the pool
        can overlap them exactly like real blocked IO — the measurable
        reason the decode pool exists (bench ``ingest`` cold rows)."""
        import os as _os

        throttle = _os.environ.get("TMX_INGEST_THROTTLE_MS")
        if throttle:
            import time as _time

            _time.sleep(float(throttle) / 1e3)
        from tmlibrary_tpu.readers import read_container_plane

        container = read_container_plane(path, page or 0)
        if container is not None:
            return container

        from tmlibrary_tpu.native import tiff_read

        img = tiff_read(path, page or 0, height, width)
        if img is not None:
            return img

        if path.lower().endswith((".tif", ".tiff")):
            from tmlibrary_tpu.readers import read_tiff_page_py

            img = read_tiff_page_py(path, page or 0)
            if img is not None:
                return img

        import cv2

        if page is not None:
            # multi-page OME-TIFF: decode only the declared page (caching
            # whole files across a batch risks host OOM on large z/t stacks)
            ok, pages = cv2.imreadmulti(
                path, start=page, count=1, flags=cv2.IMREAD_UNCHANGED
            )
            if not ok or not pages:
                raise MetadataError(f"cannot read page {page} of {path}")
            img = pages[0]
        else:
            img = cv2.imread(path, cv2.IMREAD_UNCHANGED)
        if img is None:
            raise MetadataError(f"cannot read image {path}")
        if img.ndim == 3:
            img = cv2.cvtColor(img, cv2.COLOR_BGR2GRAY)
        return img

    def run_batch(self, batch: dict) -> dict:
        import concurrent.futures as cf
        import os

        exp = self.store.experiment
        # group by target plane so each plane's sites write in one slice
        by_plane: dict[tuple, list[dict]] = {}
        for f in batch["files"]:
            key = (f["cycle"], f["channel"], f["tpoint"], f["zplane"])
            by_plane.setdefault(key, []).append(f)

        # plane decode is the data-loader hot loop and is IO/decompress
        # bound; the native TIFF reader and cv2 both release the GIL, so a
        # thread pool loads one plane-group's files concurrently (the
        # reference fanned per-file-mapping batches out to cluster jobs)
        # TMX_INGEST_WORKERS pins the pool (bench.py's ingest config uses
        # 1 as its single-thread denominator); anything unparseable or
        # non-positive falls back to the default rather than failing
        # every ingest batch
        try:
            workers = int(os.environ.get("TMX_INGEST_WORKERS", ""))
        except ValueError:
            workers = 0
        if workers < 1:
            # IO-bound sizing, NOT cpu_count-bound: the pool exists to
            # overlap storage stalls (cold network filestores), where
            # threads spend most of their life blocked outside the GIL —
            # a 1-core host still wants several in flight.  The floor of
            # 4 is what makes the cold-source bench rows meaningful.
            workers = max(4, min(8, os.cpu_count() or 1))
        n_written = 0
        with cf.ThreadPoolExecutor(max_workers=workers) as pool:
            # submit every decode up front (concurrency spans plane
            # groups — a mapping with one file per plane would otherwise
            # serialize), then drain and write group by group
            futures = {
                (key, i): pool.submit(
                    self._read_plane, f["path"], f.get("page"),
                    exp.site_height, exp.site_width,
                )
                for key, files in by_plane.items()
                for i, f in enumerate(files)
            }
            for key, files in by_plane.items():
                cycle, channel, tpoint, zplane = key
                pixels = []
                indices = []
                # the main thread's wait for this plane group's decodes
                # (the pool decodes every group at once)
                with telemetry.span(
                    "decode", files=len(files),
                    pixels=len(files) * exp.site_height * exp.site_width,
                ):
                    for i, f in enumerate(files):
                        img = futures[(key, i)].result()
                        if img.shape != (exp.site_height, exp.site_width):
                            raise MetadataError(
                                f"{f['path']}: shape {img.shape} != site shape "
                                f"({exp.site_height}, {exp.site_width})"
                            )
                        pixels.append(np.asarray(img, np.uint16))
                        indices.append(f["site_index"])
                with telemetry.span("write"):
                    self.store.write_sites(
                        np.stack(pixels), indices,
                        cycle=cycle, channel=channel, tpoint=tpoint,
                        zplane=zplane,
                    )
                n_written += len(files)
        return {"n_written": n_written}
