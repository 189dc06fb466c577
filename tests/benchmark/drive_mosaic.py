"""Drives one whole rehearsal run of the ``cp3-mosaic.x4`` cell with a
fault planted in the program underneath, for ``test_mosaic_cell.py``: the
harness's own ``run.main`` from its first line to its last, at the
configuration's rehearsal size on four host devices.

    python tests/benchmark/drive_mosaic.py <fault> <run.py's arguments>

``none``: the program as it is.  ``seam_join_off``: the seam join of the
sharded connected components does nothing where the program calls it
(``parallel.label._seam_join_2d_axis`` returns its labels unchanged), so
an object across a mesh seam keeps one id a shard.  ``fold_keeps_one_shard``:
corilla's sharded fold merges nothing (``parallel.stats.welford_merge``
returns its first argument), so the stored statistics are those of the
first shard's two fields and of the ninth, which corilla scans alone.
``watershed_halo_off``: no chip hands its neighbour the labels at its edge
in the watershed's adopt step (``parallel.label._halo1_zero_2d`` pads with
zeros), so every cell stops at the mesh seam."""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def plant(fault: str) -> None:
    if fault == "none":
        return
    if fault == "fold_keeps_one_shard":
        from tmlibrary_tpu.parallel import stats

        stats.welford_merge = lambda a, b: a
        return
    import jax.numpy as jnp

    from tmlibrary_tpu.parallel import label

    if fault == "watershed_halo_off":
        label._halo1_zero_2d = lambda x, row_axis, col_axis: jnp.pad(x, 1)
        return
    if fault != "seam_join_off":
        raise SystemExit(f"unknown fault {fault!r}")

    def no_join(labels, mask, axis_name, other_axis, connectivity):
        return labels, jnp.bool_(False)

    label._seam_join_2d_axis = no_join


if __name__ == "__main__":
    from benchmark import harness, run

    harness.prepare_environment()     # before JAX is imported
    plant(sys.argv[1])
    sys.exit(run.main(sys.argv[2:]))
