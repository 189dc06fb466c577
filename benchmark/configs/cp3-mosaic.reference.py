"""The plain reference of ``cp3-mosaic``: one well's nine fields analysed
as ONE mosaic, single-threaded numpy/scipy on the whole unsharded plane.
It shares no code with the system under test; it reads the store through
its public readers and lays the fields out itself (field ``f`` at row
``f // sites_per_well_x``, column ``f % sites_per_well_x``).

The chain is ``tmx workflow submit`` with ``layout: spatial`` as the
repository documents it, NOT BASELINE config 3's pipe on four chips.
Its departures from config 3, each held here as the program has it:

- every plane is illumination-corrected before anything else, because
  corilla's statistics exist.  The reference makes its OWN statistics
  (``statistics`` below: per pixel, float64 mean and population std of
  ``log10(1 + raw)`` over the well's nine fields, which is what corilla
  states it computes at ``smooth_sigma`` 0), holds the tables the program
  stored against them, and corrects with its own (``corrected``: the
  formula ``ops/image_ops.correct_illumination`` documents, written out
  in float64) — so a fold that drops a shard or merges in a wrong order
  is not read back as the truth;
- nuclei: Gaussian 1.5, 256-bin Otsu, 8-connected labels in scan order —
  **no hole fill and no minimum area**;
- cells: grown from the nuclei through ``Actin > 0.8 x Otsu(Actin)`` with
  every seed kept in its cell's mask, ids preserved: the cells are as
  many as the nuclei by construction, so the cell count is held through
  the structure of every cell (its nucleus' id, containing it, connected);
  the PARTITION is held against the reference's own plain level-ordered
  flood of its own plane (``flood``, ``partition_guarantee``): sound
  cells that stop at a mesh seam, because no chip handed its neighbour
  its edge, pass every structural number;
- every stain is measured for both object types, on the host.

Otsu's threshold is an argmax, and the criterion is flat at its top:
neighbouring bins lie 2e-5 to 5e-5 apart (relative), and in one well of
some tens two lie within 1e-6 — under what float32 arithmetic resolves
(PERF.md section 6, "PR 33, refused": seed 1996743581, bins 129 and 130
6.8e-7 apart, the cuts 1.28 apart, three times the band).  Each such bin
IS Otsu's threshold.  So the program says which cut it used
(``batch_done.result``'s ``otsu_cut``) and is held to three things:
(a) that cut is the centre of a bin of the reference's OWN float64
histogram whose criterion is within ``otsu_tie_rel`` of the reference's
maximum; (b) the stored mask is the reference's smoothed plane over THAT
cut, pixel for pixel outside the band; (c) with equal masks the count is
scipy's.

``check(store, sites, config, program)`` returns ``compared`` — the
numbers that decide, each beside its limit, a number within its limit
when it is not above it — ``checks``, each the verdict of the numbers
``DECIDES`` gives it, and ``info``, which only informs.  ``program`` is
what the unit's ``batch_done`` said and what the driver read of the
program's Otsu (``otsu_cut`` a stain; ``otsu_reading`` a stain, optional:
the program's ``between``, ``lo``, ``hi``).  ``LIMITS`` gives each
tolerance and its reason.  The driver's ledger keeps 15 numbers of a
line, no name over 32 characters and no limit that is not a number:
``compared`` keeps to that with the driver's own two."""

import time

import numpy as np

#: name -> (limit, why).  Counts, ids, areas and boxes are exact and are
#: not listed: their limit is 0 differences.
LIMITS = {
    "stored_tables_abs": (
        5e-4,
        "corilla's stored per-pixel mean and population std of log10(1 + "
        "raw) against the reference's own, float64, over the same nine "
        "fields (the larger of the two; a stain without stored tables "
        "reads 1.0).  The device takes the log10 in float32 and folds the "
        "fields' Welford states in float32; the TPU's log10 is up to "
        "4.6e-5 off numpy's and nine samples of one pixel do not average "
        "that away: mean 2.5e-5 to 2.6e-5, std 2.1e-5 to 2.2e-5 on the "
        "chip (control's `stated` unit, one chip, 6480 x 6480, seeds "
        "3000000951-952, PR 33; the four-chip fold in PERF.md section 6), "
        "2.9e-7 and 1.3e-7 on XLA's CPU; the statistics in bfloat16 read "
        "1.37e-2 to 1.38e-2 and 6.6e-3 to 6.8e-3 (same seeds and size), a "
        "fold that merges nothing 0.69 and 0.55 (the fault test, 64 x 64 "
        "fields)"),
    "otsu_center_bins": (
        0.05,
        "how far, in bin widths, the program's cut may lie from the "
        "centre of a bin of the reference's own histogram.  Both lay 256 "
        "bins over [min, max] of the plane, so the program's centres are "
        "the reference's moved by the error of the two pixels that decide "
        "the range, at most the larger of the two: the smoothed DAPI "
        "plane's maximum lies 7.6e-3 to 7.7e-3 of a bin from the "
        "reference's on the chip and its minimum 2.6e-4 to 3.9e-4 (the "
        "TPU's log10, see threshold_band_rel), the cut 3.8e-3 to 4.0e-3 "
        "off its bin's centre, Actin's 5.4e-4 to 5.6e-4 (control's "
        "`stated` unit, one chip, 6480 x 6480, seeds 1996743581 and 1, PR "
        "34); on four chips, ten seeds, 2.5e-3 to 4.7e-3 and 3.6e-4 to "
        "1.2e-3 (PERF.md section 6); 2.0e-4 on XLA's CPU at that size; with the correction in bfloat16 0.46 to 0.49 "
        "(DAPI) and 0.27 to 0.48 (Actin), same seeds; a cut that no "
        "256-bin histogram over the plane's range gives reads up to 0.5"),
    "otsu_tie_rel": (
        2e-5,
        "how far under the reference's maximum, as a share of it, the "
        "criterion of the program's bin may lie in the reference's own "
        "float64 histogram.  The program picks the argmax of ITS "
        "criterion (float32 prefix sums of 1.4e10, its own range and "
        "histogram); a bin it prefers to the reference's lies under the "
        "reference's maximum by no more than the two criteria differ from "
        "bin to bin near the top: the spread, over the bins within 3 of "
        "the reference's argmax, of program / reference - 1 (what all "
        "bins share, as a range 1e-4 wider, moves no argmax).  That "
        "spread reads 7.9e-6 to 8.6e-6 for DAPI and 1.2e-6 to 2.9e-6 for "
        "Actin on one chip and 3.4e-6 to 1.00e-5 and 9.0e-7 to 3.5e-6 on "
        "four (same runs as otsu_center_bins; the program's histogram is "
        "fetched once a run, outside the window), 9.7e-6 and 1.5e-6 on "
        "XLA's CPU at 6480 x 6480; the limit is twice the largest "
        "(1.00e-5, seed 270355284).  Seed 1996743581 ties bins 129 and 130 to "
        "6.8e-7 (both pass; the TPU took 129 on one chip and 130 on four, "
        "XLA's CPU 130) and holds bin 128 5.0e-5 under (fails); "
        "neighbouring bins lie 1.1e-5 to 5e-5 apart, so a wrong cut one "
        "bin beside the top may pass this number where the criterion is "
        "flattest and is then held by the mask alone; two bins beside "
        "fail it.  The control in bfloat16 reads 3.8e-4 to 5.0e-4"),
    "threshold_band_rel": (
        1e-3,
        "a mask pixel may differ from the reference's only where the "
        "reference's smoothed plane is within this share of the Otsu cut. "
        "The program corrects and smooths in float32 on the device, and "
        "the correction divides a log10 by the pixel's std_log: an error "
        "dL of the log10 becomes a relative error ln(10) * dL * "
        "mean(std_log) / std_log of the pixel.  The TPU's log10 is up to "
        "4.6e-5 off numpy's (XLA's CPU: 2.4e-7), so a corrected pixel "
        "differs by 1.2e-5 in the median and 1.4e-3 at most (builder's "
        "chip run, PR 33, seed 3000000921); 28 to 62 of 42 M mask pixels "
        "differed on the chip (seven seeds, PR 33; 33 to 46 on ten seeds "
        "at the program's own cut, PR 34), all inside 1e-4 of the cut "
        "(XLA's CPU: none); with the correction in bfloat16 "
        "(benchmark/control.py, one chip, 6480 x 6480, seeds "
        "3000000951-952) 15,847 to 19,267 pixels lie outside the band; a "
        "wrong halo, a wrong bin or a lost seam row moves pixels by whole "
        "intensity units of a cut near 420"),
    "cells_unlike_flood_rel": (
        4e-3,
        "the share of cell pixels whose label is not the one the "
        "reference's own flood gives them (`flood`: 16 levels over its own "
        "float64-corrected plane, from the stored nuclei, through the "
        "plane over the program's cut): the larger of the share over the "
        "whole mosaic and the share over the flood's cells that lie across "
        "a mesh seam.  The program floods ITS corrected plane (float32 on "
        "the device, see threshold_band_rel): a pixel within 1e-5 of a "
        "level or of the cut is admitted a level earlier or later, and "
        "where two cells meet the border moves.  On the chip 647 and 604 "
        "of 8.2 M and 8.0 M cell pixels over the mosaic (7.9e-5, 7.6e-5) "
        "and 1 of 140,797 and 30 of 162,383 at the seams (7e-6, 1.85e-4; "
        "control's `stated` unit, one chip, 6480 x 6480, seeds 2147483647 "
        "and 4294967295, PR 34; one chip's labels are four chips' bit for "
        "bit); 22 of 8.1 M on XLA's CPU at that size (2.7e-6; 2 of 173,953 "
        "at the seams, 1.1e-5) and 0 at the rehearsal size.  With the halo of the watershed's adopt step left "
        "out of the program (no chip hands its neighbour its edge: "
        "tests/benchmark/drive_mosaic.py watershed_halo_off; four host "
        "devices, 6480 x 6480, seed 1996743581) 75 cells stop at the mesh "
        "seams: 7,946 pixels, 9.8e-4 over the mosaic — within ten times "
        "the chip's own reading, which is why the seam cells are read "
        "apart — and 7,926 of 173,953 at the seams, 4.56e-2, with 553 "
        "pixels over the cut left beside a cell (`cell_faults`) and every "
        "other number inside its limit; 1.2e-2 and 3.3e-2 at the rehearsal "
        "size.  The control in bfloat16 reads 5.2e-2 and 5.7e-2 (5.0e-2 "
        "and 5.7e-2 over the mosaic), with 164,477 and 18,513 pixels left "
        "beside a cell (same chip run)"),
    "centroid_abs_px": (
        1e-6,
        "sums of integer coordinates in float64 on both sides; only the "
        "order of the additions differs (reads 0.0)"),
    "intensity_mean_sum_rel": (
        1e-3,
        "float64 accumulators on both sides, but the corrected plane is "
        "made on the device in float32 (see threshold_band_rel): an "
        "object's pixels differ from the reference's by about 1e-5 each, "
        "in one direction, so the mean and the sum do too: 1.5e-5 on the "
        "chip against the reference's own tables (control's `stated` "
        "unit, seeds 3000000951-952), 2.1e-5 to 2.5e-5 when the reference "
        "still corrected with the stored tables in float32 (four seeds, "
        "PR 33), 3e-6 on XLA's CPU; the control in bfloat16 reads 2.1e-2 "
        "to 2.4e-2.  cp3-plate holds 1e-5 because its pipeline measures "
        "uncorrected pixels, which this chain cannot"),
    "intensity_min_max_rel": (
        1e-2,
        "one pixel decides a min or a max, and a cell's mask reaches "
        "background pixels whose nine samples nearly agree: mean(std_log) "
        "/ std_log reaches 20-40 there, so the TPU's 1e-5 of log10 reads "
        "4.5e-4 to 5.1e-4 on the chip against the reference's own tables "
        "(seeds 3000000951-952), 5.1e-4 to 8.2e-4 against the stored ones "
        "(five seeds, PR 33; the worst single pixel of a plane 1.4e-3), "
        "7.5e-6 on XLA's CPU; the control in bfloat16 reads 0.30 to 0.37. "
        "cp3-plate holds min and max exact on uncorrected pixels"),
    "std_over_mean": (
        1e-3,
        "std is a difference of squares of the size of the mean, so its "
        "error is held against the object's mean, not against itself (a "
        "one-pixel object has std 0); 1.3e-5 to 1.5e-5 on the chip "
        "(seeds 3000000951-952; 2.6e-5 to 3.0e-5 against the stored "
        "tables, five seeds), 2.6e-6 on XLA's CPU, 1.1e-2 to 1.3e-2 in "
        "the control"),
}


#: check -> the numbers of ``compared`` that decide it
DECIDES = {
    "nuclei_are_scipy_labels_of_their_foreground": ("seam_faults",),
    "stored_statistics_are_the_nine_fields": ("stored_tables_abs",),
    "cut_is_an_otsu_threshold": ("otsu_cut_off_bin_center",
                                 "otsu_cut_below_max_rel"),
    "mask_is_the_plane_over_the_cut": ("mask_faults",),
    "cells_hold_their_nuclei": ("cell_faults",),
    "cells_are_the_flood_of_their_nuclei": ("cells_unlike_flood_rel",),
    "objects_lie_across_borders_and_seams_with_one_id": (
        "seam_objects_missing", "seam_faults"),
    "features_within_limits": (
        "table_faults", "centroid_abs_px", "intensity_mean_sum_rel",
        "intensity_min_max_rel", "std_over_mean"),
}


def verdicts(compared: dict, decides: dict) -> dict:
    """Each check of ``decides``: is every one of its numbers within its
    limit (not above it)?"""
    return {check: all(compared[name][0] <= compared[name][1]
                       for name in names)
            for check, names in decides.items()}


def between_class(hist: np.ndarray, centers: np.ndarray) -> np.ndarray:
    """Otsu's between-class criterion of every bin: the cut after bin
    ``k`` parts bins ``0..k`` from the rest (``cp3-plate.reference.py``'s
    ``otsu`` up to its argmax, in float64)."""
    hist = np.asarray(hist, np.float64)
    w0 = np.cumsum(hist)
    w1 = w0[-1] - w0
    sum0 = np.cumsum(hist * centers)
    mu0 = sum0 / np.maximum(w0, 1e-12)
    mu1 = (sum0[-1] - sum0) / np.maximum(w1, 1e-12)
    return np.where((w0 > 0) & (w1 > 0), w0 * w1 * (mu0 - mu1) ** 2, -1.0)


def criterion(img: np.ndarray, bins: int = 256) -> tuple:
    """``(between, lo, width)``: the criterion of every bin of a
    ``bins``-bin histogram over ``[min, max]`` of ``img``, the range's low
    end and a bin's width.  A cut is a bin's centre, ``lo + (k + 0.5) *
    width``."""
    img = np.asarray(img, np.float64)
    lo, hi = float(img.min()), float(img.max())
    span = max(hi - lo, 1e-6)
    idx = np.clip(((img - lo) / span * bins).astype(np.int32), 0, bins - 1)
    hist = np.bincount(idx.ravel(), minlength=bins)
    centers = lo + (np.arange(bins) + 0.5) / bins * span
    return between_class(hist, centers), lo, span / bins


def held_cut(img: np.ndarray, cut: float, reading: dict = None) -> dict:
    """Where a program's ``cut`` lies in the reference's own histogram of
    ``img``: the bin whose centre is nearest, how far off that centre (in
    bin widths), and how far that bin's criterion lies under the maximum
    (as a share of it; 1.0 for a cut outside the range).  The rest
    informs: the reference's own argmax and cut, how far under the
    maximum the runner-up lies (the tie gap) and, with the program's
    ``reading``, how far its range and its criterion lie from the
    reference's."""
    between, lo, width = criterion(img)
    best = int(np.argmax(between))
    top = float(between[best])
    at = (cut - lo) / width - 0.5
    k = int(np.rint(at))
    inside = 0 <= k < len(between)
    out = {
        "cut": cut, "bin": k, "off_center_bins": abs(at - k),
        "below_max_rel": (top - float(between[k])) / top if inside else 1.0,
        "reference_cut": lo + (best + 0.5) * width, "reference_bin": best,
        "bin_width": width,
        "runner_up_below_max_rel":
            (top - float(np.partition(between, -2)[-2])) / top,
    }
    if reading:
        hi = lo + width * len(between)
        out["range_apart_bins"] = [abs(reading["lo"] - lo) / width,
                                   abs(reading["hi"] - hi) / width]
        near = slice(max(best - 3, 0), best + 4)
        ratio = np.asarray(reading["between"], np.float64)[near] \
            / between[near] - 1.0
        out["criterion_apart_rel"] = float(ratio.max() - ratio.min())
        out["program_bin"] = int(np.argmax(reading["between"]))
    return out


def stitch(stack: np.ndarray, fields_x: int) -> np.ndarray:
    """``(fields, h, w)`` -> one plane, row-major."""
    n, h, w = stack.shape
    out = np.empty((n // fields_x * h, fields_x * w), stack.dtype)
    for f in range(n):
        y, x = divmod(f, fields_x)
        out[y * h:(y + 1) * h, x * w:(x + 1) * w] = stack[f]
    return out


def statistics(raw: np.ndarray) -> tuple:
    """``(mean_log, std_log)`` of a stain: per pixel, over the fields
    (axis 0), the mean and the population standard deviation of
    ``log10(1 + raw)``, float64."""
    logs = np.log10(1.0 + raw.astype(np.float64))
    return logs.mean(axis=0), logs.std(axis=0)


def corrected(raw: np.ndarray, mean_log: np.ndarray,
              std_log: np.ndarray) -> np.ndarray:
    """``10 ** ((log10(1 + img) - mean_log) / std_log * mean(std_log) +
    mean(mean_log)) - 1``, clipped to the uint16 range, per field, in
    float64; a pixel whose ``std_log`` is under 1e-6 divides by 1."""
    std_safe = np.where(std_log > 1e-6, std_log, 1.0)
    z = (np.log10(1.0 + raw.astype(np.float64)) - mean_log) / std_safe
    log = z * std_log.mean() + mean_log.mean()
    return np.clip(np.power(10.0, log) - 1.0, 0.0, 65535.0)


def bf(a) -> np.ndarray:
    """``a`` rounded to bfloat16, the nearest precision below the float32
    the program's statistics and correction are made in."""
    import ml_dtypes

    return np.asarray(a, np.float32).astype(
        ml_dtypes.bfloat16).astype(np.float32)


def control_statistics(raw: np.ndarray) -> tuple:
    """The control of the stored tables: ``statistics`` with the log10
    and the tables rounded to bfloat16."""
    logs = bf(np.log10(bf(np.float32(1.0) + bf(raw))))
    return bf(logs.mean(axis=0)), bf(logs.std(axis=0))


def control_corrected(raw: np.ndarray, mean_log: np.ndarray,
                      std_log: np.ndarray) -> np.ndarray:
    """The control: ``corrected`` with every operand and every result
    rounded to bfloat16.  ``benchmark/control.py`` puts it in the
    program's place; what ``check`` then reads has to come out as not
    correct (``LIMITS`` gives those readings)."""
    std = bf(std_log)
    std_safe = np.where(std > 1e-6, std, np.float32(1.0))
    z = bf(bf(bf(np.log10(bf(np.float32(1.0) + bf(raw)))) - bf(mean_log))
           / std_safe)
    log = bf(bf(z * bf(std_log.mean())) + bf(mean_log.mean()))
    return np.clip(bf(bf(np.power(np.float32(10.0), log)) - np.float32(1.0)),
                   0.0, 65535.0).astype(np.float32)


def plane(store, channel: str, fields_x: int, worst: dict) -> np.ndarray:
    """One stain's mosaic as the chain has to see it: stored pixels,
    corrected with the reference's own statistics of them, stitched.
    ``worst`` collects how far the tables corilla stored lie from the
    reference's (a stain without stored tables reads 1.0)."""
    index = store.experiment.channel_index(channel)
    raw = store.read_sites(None, channel=index)
    mean_log, std_log = statistics(raw)
    apart = {"stored_mean_log_abs": 1.0, "stored_std_log_abs": 1.0}
    if store.has_illumstats(channel=index):
        stored = store.read_illumstats(channel=index)
        for name, own in (("mean_log", mean_log), ("std_log", std_log)):
            apart[f"stored_{name}_abs"] = float(np.abs(
                np.asarray(stored[name], np.float64) - own).max())
    for key, value in apart.items():
        worst[key] = max(worst.get(key, 0.0), value)
    return stitch(corrected(raw, mean_log, std_log).astype(np.float32),
                  fields_x)


def crossing(boxes: list, lines: list) -> np.ndarray:
    """Per object (a ``find_objects`` box or None): does it have pixels
    on both sides of one of the ``lines`` (rows, then columns)?"""
    out = np.zeros(len(boxes), bool)
    for i, box in enumerate(boxes):
        if box is not None:
            out[i] = any(s.start < at < s.stop
                         for s, ats in zip(box, lines) for at in ats)
    return out


def grouped(labels_fg: np.ndarray, n: int) -> tuple:
    """``(order, starts)`` to reduce foreground pixels by label with
    ``reduceat``: labels 1..n, every one present."""
    order = np.argsort(labels_fg, kind="stable")
    starts = np.searchsorted(labels_fg[order], np.arange(1, n + 1))
    return order, starts


def check_features(table, labels: np.ndarray, planes: dict, boxes: list,
                   worst: dict) -> bool:
    """Area, centroid, bounding box and the five intensity statistics of
    every stain against numpy float64 on ``labels`` and ``planes``;
    ``worst`` collects the largest error per limit.  Returns whether the
    exact ones (ids, area, box) held."""
    n = int(labels.max())
    rows = table.sort_values("label")
    exact = (len(rows) == n and np.array_equal(
        rows["label"].to_numpy(), np.arange(1, n + 1)))
    if n == 0 or not exact or len(boxes) != n or None in boxes:
        return bool(exact and n == 0)    # an id without a pixel: not exact
    fg = labels > 0
    yy, xx = np.nonzero(fg)
    lab = labels[fg]
    order, starts = grouped(lab, n)
    area = np.bincount(lab, minlength=n + 1)[1:].astype(np.float64)
    exact &= np.array_equal(rows["Morphology_area"].to_numpy(), area)
    cy = np.bincount(lab, weights=yy, minlength=n + 1)[1:] / area
    cx = np.bincount(lab, weights=xx, minlength=n + 1)[1:] / area
    worst["centroid_abs_px"] = max(
        worst.get("centroid_abs_px", 0.0),
        float(np.abs(rows["Morphology_centroid_y"].to_numpy() - cy).max()),
        float(np.abs(rows["Morphology_centroid_x"].to_numpy() - cx).max()))
    height = np.array([b[0].stop - b[0].start for b in boxes], np.float64)
    width = np.array([b[1].stop - b[1].start for b in boxes], np.float64)
    exact &= np.array_equal(rows["Morphology_bbox_height"].to_numpy(),
                            height)
    exact &= np.array_equal(rows["Morphology_bbox_width"].to_numpy(), width)
    for name, img in planes.items():
        vals = img[fg].astype(np.float64)[order]
        total = np.add.reduceat(vals, starts)
        squares = np.add.reduceat(vals * vals, starts)
        mean = total / area
        std = np.sqrt(np.maximum(squares / area - mean * mean, 0.0))
        want = {"mean": mean, "sum": total,
                "min": np.minimum.reduceat(vals, starts),
                "max": np.maximum.reduceat(vals, starts)}
        for stat, ref in want.items():
            got = rows[f"Intensity_{stat}_{name}"].to_numpy()
            err = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-12)
            key = ("intensity_mean_sum_rel" if stat in ("mean", "sum")
                   else "intensity_min_max_rel")
            worst[key] = max(worst.get(key, 0.0), float(err.max()))
        got = rows[f"Intensity_std_{name}"].to_numpy()
        worst["std_over_mean"] = max(
            worst.get("std_over_mean", 0.0),
            float((np.abs(got - std) / np.maximum(mean, 1e-12)).max()))
    return bool(exact)


def flood(intensity: np.ndarray, seeds: np.ndarray, allowed: np.ndarray,
          n_levels: int) -> np.ndarray:
    """The level-ordered flood the chain's documentation states, written
    out plainly on the whole plane: ``n_levels`` equal bands from the
    brightest allowed pixel down to the dimmest, the bands admitted one
    after the other from the top (and at last every allowed pixel); within
    what is admitted, in synchronous steps until nothing changes, every
    unlabelled pixel with a labelled 8-neighbour takes the LARGEST label
    among its neighbours; a seed keeps its label.  Only the frontier is
    visited: a step's candidates are the admitted, unlabelled neighbours
    of the pixels the step before labelled."""
    import scipy.ndimage as ndi

    h, w = seeds.shape
    width = w + 2
    lab = np.zeros((h + 2, width), np.int32)
    lab[1:-1, 1:-1] = seeds
    ok = np.zeros((h + 2, width), bool)
    ok[1:-1, 1:-1] = allowed | (seeds > 0)
    val = np.full((h + 2, width), -np.inf, np.float64)
    val[1:-1, 1:-1] = intensity
    hi, lo = float(val[ok].max()), float(val[ok].min())
    span = max(hi - lo, 1e-6)
    around = np.array([-width - 1, -width, -width + 1, -1, 1,
                       width - 1, width, width + 1])
    labf, okf, valf = lab.ravel(), ok.ravel(), val.ravel()
    for i in range(n_levels + 1):
        level = hi - span * (i + 1) / n_levels if i < n_levels else -np.inf
        front = np.flatnonzero(
            (ok & (lab == 0) & (val >= level)
             & ndi.binary_dilation(lab > 0, np.ones((3, 3), bool))).ravel())
        while front.size:
            labf[front] = labf[front[:, None] + around].max(axis=1)
            near = (front[:, None] + around).ravel()
            near = near[okf[near] & (labf[near] == 0) & (valf[near] >= level)]
            front = np.unique(near)
    return lab[1:-1, 1:-1]


def threshold_guarantee(smooth: np.ndarray, mask: np.ndarray, cut: float,
                        n_objects: int, reading: dict = None) -> tuple:
    """``(compared, info)`` of the three things the program's ``cut`` and
    the ``mask`` it stored (the foreground of ``n_objects`` labels) are
    held to against ``smooth``, the reference's own smoothed plane."""
    import scipy.ndimage as ndi

    held = held_cut(smooth, cut, reading)
    chain = smooth > cut
    differ = chain != mask
    band = LIMITS["threshold_band_rel"][0] * abs(cut)
    outside = int(np.count_nonzero(differ & (np.abs(smooth - cut) > band)))
    n_differ = int(np.count_nonzero(differ))
    n_chain = int(ndi.label(chain, ndi.generate_binary_structure(2, 2))[1])
    # identical masks give identical counts (the labels are scipy's own);
    # a pixel inside the band may split or join an object
    compared = {
        "otsu_cut_off_bin_center": (held["off_center_bins"],
                                    LIMITS["otsu_center_bins"][0]),
        "otsu_cut_below_max_rel": (held["below_max_rel"],
                                   LIMITS["otsu_tie_rel"][0]),
        "mask_faults": (
            outside + (abs(n_objects - n_chain) if n_differ == 0 else 0), 0),
    }
    return compared, {
        "otsu": held, "mask_pixels_outside_band": outside,
        "mask_pixels_differing": n_differ,
        "nuclei_minus_chain": n_objects - n_chain, "chain_count": n_chain}


def partition_guarantee(stain: np.ndarray, nuclei: np.ndarray,
                        cells: np.ndarray, second_cut: float, n_levels: int,
                        seams: list) -> tuple:
    """``(pixels left beside a cell, share unlike the flood, info)`` of
    the two things the cells' partition of ``stain`` over ``second_cut``
    is held to.  A cell stops only where the stain is not over the cut:
    no pixel over it (by more than one pixel may differ by) that touches
    a cell is left unclaimed.  And the cells are the reference's own
    ``flood`` of its own plane from the stored ``nuclei``, pixel for
    pixel: over the whole mosaic, and over the flood's cells that lie
    across one of ``seams`` (rows, then columns), where a chip that does
    not hand its neighbour its edge stops every cell and every structural
    number of ``check`` still passes; the larger share decides."""
    import scipy.ndimage as ndi

    one_pixel = LIMITS["intensity_min_max_rel"][0]
    left = int(np.count_nonzero(
        (cells == 0) & (stain > second_cut * (1.0 + one_pixel))
        & ndi.binary_dilation(cells > 0, np.ones((3, 3), bool))))
    flooded = flood(stain, nuclei, stain > second_cut, n_levels)
    unlike = flooded != cells
    at_seam = np.flatnonzero(crossing(
        ndi.find_objects(flooded, max_label=max(int(nuclei.max()), 1)),
        seams)) + 1
    theirs = np.isin(flooded, at_seam) | np.isin(cells, at_seam)
    info = {
        "cell_pixels": int(np.count_nonzero((flooded > 0) | (cells > 0))),
        "pixels_unlike": int(np.count_nonzero(unlike)),
        "cells_of_the_flood_across_mesh_seams": int(len(at_seam)),
        "their_pixels": int(np.count_nonzero(theirs)),
        "their_pixels_unlike": int(np.count_nonzero(unlike & theirs)),
    }
    share = max(info["pixels_unlike"] / max(info["cell_pixels"], 1),
                info["their_pixels_unlike"] / max(info["their_pixels"], 1))
    return left, share, info


def check(store, sites, config, program) -> dict:
    """``sites`` is not used: the whole mosaic is checked, unsharded."""
    import scipy.ndimage as ndi

    t_start = time.perf_counter()
    args = config["jterator"]
    fields_x = config["sites_per_well_x"]
    eight = ndi.generate_binary_structure(2, 2)
    names = (args["spatial_objects"], args["spatial_secondary_objects"])
    stains = (args["spatial_channel"], args["spatial_secondary_channel"])
    nuclei = stitch(store.read_labels(None, names[0]), fields_x)
    cells = stitch(store.read_labels(None, names[1]), fields_x)
    tables = {name: store.read_features(name) for name in names}
    size = nuclei.shape[0] // fields_x
    info, compared, worst = {}, {}, {}
    cuts = {stain: float(program["otsu_cut"][stain]) for stain in stains}
    readings = program.get("otsu_reading") or {}

    # ---- seams, bit-exact: the stored stacks are scipy's labels of their
    # own foreground, id for id in scan order (the table's rows: below)
    own, n_own = ndi.label(nuclei > 0, eight)
    n_nuclei = int(nuclei.max())
    seam_faults = int(np.count_nonzero(own != nuclei)) \
        + abs(n_nuclei - int(n_own))

    # ---- corilla's tables against the reference's own statistics of the
    # stored pixels; from here on the reference corrects with its own
    stats = {}
    planes = {c: plane(store, c, fields_x, stats)
              for c in config["channels"]}
    compared["stored_tables_abs"] = (max(stats.values()),
                                     LIMITS["stored_tables_abs"][0])
    info["stored_tables"] = stats

    # ---- the threshold: the program's cut is an Otsu threshold of the
    # reference's own smoothed plane, the mask is that plane over it
    smooth = ndi.gaussian_filter(
        planes[stains[0]].astype(np.float64), args["spatial_sigma"],
        mode="reflect")
    held, told = threshold_guarantee(smooth, nuclei > 0, cuts[stains[0]],
                                     n_nuclei, readings.get(stains[0]))
    del smooth
    # the second stain's cut, held the same way on its unsmoothed plane
    second = held_cut(planes[stains[1]], cuts[stains[1]],
                      readings.get(stains[1]))
    for key, now in (("otsu_cut_off_bin_center", second["off_center_bins"]),
                     ("otsu_cut_below_max_rel", second["below_max_rel"])):
        held[key] = (max(held[key][0], now), held[key][1])
    compared.update(held)
    n_chain = told.pop("chain_count")
    info.update(told, otsu={stains[0]: told["otsu"], stains[1]: second},
                reference_counts={name: n_chain for name in names})

    # ---- cells: every cell carries its nucleus' id, contains it, is
    # 8-connected; no cell lacks a nucleus, no nucleus a cell; a cell
    # reaches beyond its nucleus only where the second stain is over its
    # cut (times the factor), to what one pixel may differ by; and the
    # partition itself (``partition_guarantee``)
    seeded = nuclei > 0
    ids = np.unique(cells)
    ids = ids[ids > 0]
    cell_boxes = ndi.find_objects(cells, max_label=max(n_nuclei, 1))
    pieces = sum(
        int(ndi.label(cells[box] == i + 1, eight)[1] != 1)
        for i, box in enumerate(cell_boxes) if box is not None)
    second_cut = cuts[stains[1]] * args["spatial_secondary_factor"]
    side = nuclei.shape[0]
    mesh_rows, mesh_cols = config["mesh"]
    seams = [[side * k // m for k in range(1, m)]
             for m in (mesh_rows, mesh_cols)]
    left, share, info["flood"] = partition_guarantee(
        planes[stains[1]], nuclei, cells, second_cut,
        args["spatial_secondary_levels"], seams)
    parts = {
        "cells_not_containing_their_nucleus_pixels":
            int(np.count_nonzero(cells[seeded] != nuclei[seeded])),
        "cells_minus_nuclei": len(ids) - n_nuclei,
        "cells_not_connected": pieces,
        "cell_pixels_under_the_second_cut": int(np.count_nonzero(
            (cells > 0) & ~seeded & (planes[stains[1]] <= second_cut * (
                1.0 - LIMITS["intensity_min_max_rel"][0])))),
        "pixels_over_the_second_cut_left_beside_a_cell": left,
    }
    compared["cell_faults"] = (sum(abs(v) for v in parts.values()), 0)
    compared["cells_unlike_flood_rel"] = (
        share, LIMITS["cells_unlike_flood_rel"][0])
    info["cell_faults"] = parts

    # ---- the mechanism was worked: objects across a field border and
    # across a mesh seam, each with ONE id on both sides
    nucleus_boxes = ndi.find_objects(nuclei, max_label=max(n_nuclei, 1))
    borders = [size * k for k in range(1, fields_x)]
    own_boxes = ndi.find_objects(own, max_label=max(int(n_own), 1))
    across = {}
    for what, lines in (("field_borders", [borders, borders]),
                        ("mesh_seams", seams)):
        for kind, boxes in (("nuclei", nucleus_boxes),
                            ("cells", cell_boxes)):
            across[f"{kind}_across_{what}"] = int(
                crossing(boxes, lines).sum())
        # a component of the foreground that lies across a line holds
        # exactly one of the program's ids
        seam_faults += sum(
            int(len(np.unique(nuclei[box][own[box] == i + 1])) != 1)
            for i, box in enumerate(own_boxes)
            if box is not None and crossing([box], lines)[0])
    compared["seam_faults"] = (seam_faults, 0)
    compared["seam_objects_missing"] = (int(min(across.values()) == 0), 0)
    info["objects_across"] = across

    # ---- features of both object types, every stain
    exact = True
    for name, labels, boxes in ((names[0], nuclei, nucleus_boxes),
                                (names[1], cells, cell_boxes)):
        exact &= check_features(tables[name], labels, planes, boxes, worst)
    for key in ("centroid_abs_px", "intensity_mean_sum_rel",
                "intensity_min_max_rel", "std_over_mean"):
        # a table that could not be read against its labels reads 1.0
        compared[key] = (worst.get(key, 1.0), LIMITS[key][0])
    compared["table_faults"] = (int(not exact), 0)

    info.update(object_counts={name: int(len(t))
                               for name, t in tables.items()},
                reference_s=time.perf_counter() - t_start)
    return {"checks": verdicts(compared, DECIDES), "info": info,
            "compared": compared}
