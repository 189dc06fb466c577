"""The plain reference of ``cp3-multiplex``: a multiplexed (4i) well held
to what the ``multiplexing`` workflow guarantees, in numpy float64 and
scipy.  It shares no code with the system under test.

* ``register(ref, tgt)``: the normalised cross-power spectrum of two
  fields with ``np.fft.fft2`` in float64, its argmax as a signed integer
  shift, stated as the CORRECTION the store keeps (``ref[y, x] ~
  tgt[y - dy, x - dx]``);
* ``intersection(shifts)`` and ``window(shifts, quantum)``: from their
  definitions — the margins that shifting every field of every cycle by
  its correction exposes, and the stored window (the largest of them, up
  to the next multiple of ``quantum``, on all four sides);
* ``aligned(plane, dy, dx, window)``: the shifted, cropped plane by
  slicing, zero where nothing was imaged;
* ``reference_site``: ``cp3-plate.reference.py``'s scipy chain (copied;
  cells grow from the nuclei that are kept, see there), here on cycle 0's
  DAPI and Actin cropped to the window;
* per-object mean, sum, min and max of every stain of every cycle, each
  aligned by the reference's OWN shift, on the stored label stacks.

``check(store, sites, config, program)`` gives ``checks``, ``info`` and
``compared``: every number that decides, beside its limit (a number is
within its limit when it is not above it)."""

import time

import numpy as np

#: name -> (limit, why)
LIMITS = {
    "shift_entries_unlike_reference": (
        0, "a stored (dy, dx) entry that is not the reference's float64 "
        "registration of the same two stored planes; integers, so nothing "
        "rounds: the program's float32 transform moves the correlation "
        "surface by 1e-6 of its peak, the peak stands 8 % over its "
        "neighbours at 2160 x 2160 without chromatin and 0.6 over 0.15 "
        "with it (numpy, PERF.md section 6)"),
    "shift_entries_unlike_planted": (
        0, "a stored entry that is not the offset the generator cropped "
        "that field of that cycle at"),
    "window_margins_unlike_reference": (
        0, "of the four stored margins, those that are not the "
        "reference's window of the reference's shifts"),
    "align_failed_sites": (
        0, "fields the align step zeroed (over max_shift, or under its "
        "quality floor): the drift is at most 24 px under a max_shift of "
        "50, so none"),
    "label_pixels_outside_window": (
        0, "labelled pixels of a stored stack outside the stored window: "
        "the stacks live in the site frame, zero where the window crops"),
    "counts_unlike_scipy_chain": (
        0, "summed absolute difference of nuclei and cell counts from "
        "the scipy chain on cycle 0's cropped planes, over the sampled "
        "sites (uncorrected planes: program and chain differ by float32 "
        "rounding alone, as cp3-plate's; an Otsu tie would show here, "
        "PERF.md section 7)"),
    "feature_columns_unlike_30": (
        0, "object types whose table does not hold exactly the 30 "
        "Intensity_ columns (five statistics of six stains), or whose "
        "rows of a sampled site are not the stack's labels"),
    "intensity_mean_sum_rel": (
        1e-5, "worst relative error of Intensity_mean and Intensity_sum "
        "over six stains, two object types and the sampled sites against "
        "float64 sums of the aligned uint16 planes: an integer shift "
        "moves no value and the planes are uncorrected, so what is left "
        "is the program's float32 accumulation (cp3-plate's limit): 1.38e-7 "
        "to 1.60e-7 on the chip at 2160 x 2160 (nine seeds, PR 36), 5.5e-8 "
        "on XLA's CPU at the rehearsal size; the control, one cycle's "
        "stored shifts a pixel off in x, reads 3.29e-2 on the chip (seed "
        "3000003609, with 4,052 objects' min or max unlike) and 0.108 at "
        "the rehearsal size"),
    "intensity_minmax_unlike": (
        0, "objects whose Intensity_min or Intensity_max of some stain is "
        "not exactly the aligned plane's (uint16 values, exact in "
        "float32)"),
    "pyramid_layers_missing": (
        0, "channel-cycles (nine) without a pyramids/<layer>/layer.json"),
}

STATS = ("max", "mean", "min", "std", "sum")
OBJECTS = ("nuclei", "cells")


# ---------------------------------------------------------------- alignment
def register(ref: np.ndarray, tgt: np.ndarray, ref_spectrum=None) -> tuple:
    """``(dy, dx)``: the correction that lays ``tgt`` on ``ref``."""
    fa = np.fft.fft2(ref.astype(np.float64)) if ref_spectrum is None \
        else ref_spectrum
    cross = fa * np.conj(np.fft.fft2(tgt.astype(np.float64)))
    cross /= np.maximum(np.abs(cross), 1e-12)
    corr = np.fft.ifft2(cross).real
    h, w = corr.shape
    dy, dx = divmod(int(np.argmax(corr)), w)
    return (dy - h if dy > h // 2 else dy, dx - w if dx > w // 2 else dx)


def intersection(shifts: np.ndarray) -> dict:
    """Margins of the region every field of every cycle covers once it is
    shifted by its correction: moving a field down by dy > 0 leaves its
    top dy rows empty, up by dy < 0 its bottom -dy rows; columns alike.
    The reference cycle moves nowhere (margin 0 at least)."""
    s = np.asarray(shifts, np.int64).reshape(-1, 2)
    if not len(s):
        return {"top": 0, "bottom": 0, "left": 0, "right": 0}
    return {"top": max(0, int(s[:, 0].max())),
            "bottom": max(0, int(-s[:, 0].min())),
            "left": max(0, int(s[:, 1].max())),
            "right": max(0, int(-s[:, 1].min()))}


def window(shifts: np.ndarray, quantum: int) -> dict:
    """The stored window: the intersection's largest margin, up to the
    next multiple of ``quantum``, on all four sides."""
    widest = max(intersection(shifts).values())
    margin = (widest + quantum - 1) // quantum * quantum
    return {side: margin for side in ("top", "bottom", "left", "right")}


def aligned(plane: np.ndarray, dy: int, dx: int, win: dict) -> np.ndarray:
    """``plane`` moved by (dy, dx) and cropped to ``win``, by slicing:
    ``out[y, x] = plane[y + top - dy, x + left - dx]``, zero where that
    lies outside the plane."""
    h, w = plane.shape
    out = np.zeros((h - win["top"] - win["bottom"],
                    w - win["left"] - win["right"]), plane.dtype)
    y0, x0 = win["top"] - dy, win["left"] - dx      # source of out[0, 0]
    ys = slice(max(0, -y0), min(out.shape[0], h - y0))
    xs = slice(max(0, -x0), min(out.shape[1], w - x0))
    out[ys, xs] = plane[ys.start + y0:ys.stop + y0,
                        xs.start + x0:xs.stop + x0]
    return out


# ------------------------------------------------- cp3-plate's scipy chain
def otsu(img: np.ndarray, bins: int = 256) -> float:
    lo, hi = float(img.min()), float(img.max())
    span = max(hi - lo, 1e-6)
    idx = np.clip(((img - lo) / span * bins).astype(np.int32), 0, bins - 1)
    hist = np.bincount(idx.ravel(), minlength=bins).astype(np.float64)
    centers = lo + (np.arange(bins) + 0.5) / bins * span
    w0 = np.cumsum(hist)
    w1 = w0[-1] - w0
    sum0 = np.cumsum(hist * centers)
    mu0 = sum0 / np.maximum(w0, 1e-12)
    mu1 = (sum0[-1] - sum0) / np.maximum(w1, 1e-12)
    between = np.where((w0 > 0) & (w1 > 0), w0 * w1 * (mu0 - mu1) ** 2, -1.0)
    return float(centers[int(np.argmax(between))])


def reference_site(dapi: np.ndarray, actin: np.ndarray) -> tuple:
    """``(n_nuclei, n_cells)`` of one field, float32 pixels in."""
    import scipy.ndimage as ndi

    sm = ndi.gaussian_filter(dapi, 1.5, mode="reflect")
    mask = ndi.binary_fill_holes(sm > otsu(sm))
    labels, _ = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    sizes = np.bincount(labels.ravel())
    kept = np.flatnonzero(sizes >= 20)[1:]
    # cp3-plate's chain grows cells from every component; its fields keep
    # their cells a twentieth of the field from the border, so none is
    # small.  A window cuts nuclei wherever it falls, and a cut one under
    # 20 px is no nucleus and seeds no cell: grow from the kept ones
    labels = np.where(np.isin(labels, kept), labels, 0)
    cell_mask = actin > otsu(actin) * 0.8
    _, (iy, ix) = ndi.distance_transform_edt(labels == 0,
                                             return_indices=True)
    cells = np.where(cell_mask, labels[iy, ix], 0)
    return len(kept), len(np.unique(cells)) - 1


# ------------------------------------------------------------- intensities
def intensity(lab: np.ndarray, img: np.ndarray, n: int) -> dict:
    v = img.astype(np.float64).ravel()
    flat = lab.ravel()
    area = np.bincount(flat, minlength=n + 1)[1:n + 1]
    total = np.bincount(flat, weights=v, minlength=n + 1)[1:n + 1]
    order = np.argsort(flat, kind="stable")
    # a label with no pixel takes its neighbour's start: read, not used
    starts = np.minimum(np.searchsorted(flat[order], np.arange(1, n + 1)),
                        len(flat) - 1)
    return {"present": area > 0,
            "min": np.minimum.reduceat(v[order], starts),
            "max": np.maximum.reduceat(v[order], starts),
            "sum": total, "mean": total / np.maximum(area, 1)}


def stain_cycles(config: dict) -> dict:
    """stain -> the cycle the pipeline reads it from."""
    return {c["name"]: c["cycle"]
            for c in config["pipeline"]["input"]["channels"]}


def verdicts(compared: dict, decides: dict) -> dict:
    """Each check of ``decides``: is every one of its numbers within its
    limit (not above it)?"""
    return {check: all(compared[name][0] <= compared[name][1]
                       for name in names)
            for check, names in decides.items()}


#: check -> the numbers of ``compared`` that decide it
DECIDES = {
    "shifts_are_the_references_and_the_planted": (
        "shift_entries_unlike_reference", "shift_entries_unlike_planted",
        "align_failed_sites"),
    "window_is_the_references": (
        "window_margins_unlike_reference", "label_pixels_outside_window"),
    "counts_equal_scipy_chain": ("counts_unlike_scipy_chain",),
    "intensity_within_tolerance": (
        "feature_columns_unlike_30", "intensity_mean_sum_rel",
        "intensity_minmax_unlike"),
    "a_pyramid_for_every_channel_cycle": ("pyramid_layers_missing",),
}


def check(store, sites, config, program) -> dict:
    """``program``: ``planted`` (cycle -> (sites, 2) corrections the
    generator drew), ``align`` (the align step's ``step_done.collected``),
    ``quantum`` (the configuration's ``window_quantum``)."""
    exp = store.experiment
    n_sites = store.n_sites
    ref_cycle = config["ref_cycle"]
    dapi = exp.channel_index(config["ref_channel"])
    t0 = time.perf_counter()

    # ---- shifts: stored against the reference's and against the planted
    own = {c: np.zeros((n_sites, 2), np.int64)
           for c in range(exp.n_cycles) if c != ref_cycle}
    for s in range(n_sites):
        ref = store.read_sites([s], cycle=ref_cycle, channel=dapi)[0]
        spectrum = np.fft.fft2(ref.astype(np.float64))
        for c in own:
            own[c][s] = register(
                ref, store.read_sites([s], cycle=c, channel=dapi)[0],
                spectrum)
    stored = {c: (store.read_shifts(c).astype(np.int64)
                  if store.has_shifts(c) else np.full((n_sites, 2), 1 << 20))
              for c in own}
    unlike_reference = sum(int((stored[c] != own[c]).sum()) for c in own)
    unlike_planted = sum(
        int((stored[c] != np.asarray(program["planted"][c])).sum())
        for c in own)
    register_s = time.perf_counter() - t0

    # ---- the window, and the stacks' frame
    want_window = window(np.concatenate([own[c] for c in sorted(own)]),
                         program["quantum"])
    try:
        got_window = store.read_intersection()
    except Exception:
        got_window = {}
    margins_unlike = sum(int(got_window.get(k) != v)
                         for k, v in want_window.items())
    h, w = exp.site_height, exp.site_width
    inside = np.zeros((h, w), bool)
    inside[want_window["top"]:h - want_window["bottom"],
           want_window["left"]:w - want_window["right"]] = True
    outside = sum(int(np.count_nonzero(store.read_labels([s], name)[0]
                                       [~inside]))
                  for name in OBJECTS for s in range(n_sites))

    # ---- counts and intensities on the sampled sites
    cycles = stain_cycles(config)
    tables = {name: store.read_features(name) for name in OBJECTS}
    wanted_columns = sorted(f"Intensity_{stat}_{stain}"
                            for stat in STATS
                            for stain in config["stains_measured"])
    columns_unlike = sum(
        int(sorted(c for c in tables[name].columns
                   if c.startswith("Intensity_")) != wanted_columns)
        for name in OBJECTS)
    indexed = {name: t.set_index(["site_index", "label"])
               for name, t in tables.items()}
    rows_per_site = {name: t.groupby("site_index").size()
                     for name, t in tables.items()}
    counts_unlike, worst, minmax_unlike = 0, 0.0, 0
    got_counts = {name: [] for name in OBJECTS}
    want_counts = {name: [] for name in OBJECTS}
    chain_s = 0.0
    zero = (0, 0)
    for s in sites:
        def plane(stain):
            c = cycles[stain]
            dy, dx = zero if c == ref_cycle else own[c][s]
            return aligned(
                store.read_sites([s], cycle=c,
                                 channel=exp.channel_index(stain))[0],
                int(dy), int(dx), want_window)

        planes = {stain: plane(stain)
                  for stain in ("DAPI", *config["stains_measured"])}
        t1 = time.perf_counter()
        n_nuclei, n_cells = reference_site(
            planes["DAPI"].astype(np.float32),
            planes["Actin"].astype(np.float32))
        chain_s += time.perf_counter() - t1
        for name, n_want in zip(OBJECTS, (n_nuclei, n_cells)):
            got_counts[name].append(int(rows_per_site[name].get(s, 0)))
            want_counts[name].append(n_want)
            counts_unlike += abs(got_counts[name][-1] - n_want)
        for name in OBJECTS:
            lab = store.read_labels([s], name)[0][
                want_window["top"]:h - want_window["bottom"],
                want_window["left"]:w - want_window["right"]].astype(np.int64)
            n = int(lab.max())
            if n == 0:
                continue
            index = indexed[name].index
            rows = indexed[name].loc[s].sort_index() \
                if s in index.get_level_values(0) else None
            if rows is None or not np.array_equal(
                    rows.index.to_numpy(), np.arange(1, n + 1)):
                columns_unlike += 1
                continue
            for stain in config["stains_measured"]:
                ref = intensity(lab, planes[stain], n)
                here = ref["present"]
                for stat in ("mean", "sum"):
                    col = f"Intensity_{stat}_{stain}"
                    if col not in rows:
                        continue
                    want = ref[stat][here]
                    err = np.abs(rows[col].to_numpy(np.float64)[here]
                                 - want) / np.maximum(np.abs(want), 1e-12)
                    worst = max(worst, float(err.max(initial=0.0)))
                for stat in ("min", "max"):
                    col = f"Intensity_{stat}_{stain}"
                    if col not in rows:
                        continue
                    minmax_unlike += int(np.count_nonzero(
                        rows[col].to_numpy(np.float64)[here]
                        != ref[stat][here]))

    # ---- pyramids: a layer for every (cycle, channel) that holds planes
    pairs = [(c, exp.channel_index(stain))
             for c, stains in enumerate(config["cycles"]) for stain in stains]
    layers = {p.parent.name for p in
              (store.root / "pyramids").glob("*/layer.json")}
    want_layers = {(f"channel{ch:02d}" if c == 0
                    else f"cycle{c:02d}_channel{ch:02d}")
                   for c, ch in pairs}

    numbers = {
        "shift_entries_unlike_reference": unlike_reference,
        "shift_entries_unlike_planted": unlike_planted,
        "window_margins_unlike_reference": margins_unlike,
        "align_failed_sites": int(
            (program.get("align") or {}).get("failed_sites", 1 << 20)),
        "label_pixels_outside_window": outside,
        "counts_unlike_scipy_chain": counts_unlike,
        "feature_columns_unlike_30": columns_unlike,
        "intensity_mean_sum_rel": worst,
        "intensity_minmax_unlike": minmax_unlike,
        "pyramid_layers_missing": len(want_layers - layers),
    }
    compared = {name: (value, LIMITS[name][0])
                for name, value in numbers.items()}
    exact = intersection(np.concatenate([own[c] for c in sorted(own)]))
    return {
        "checks": verdicts(compared, DECIDES),
        "compared": compared,
        "info": {
            "sampled_sites": list(sites),
            "object_counts": got_counts, "reference_counts": want_counts,
            "stored_window": got_window, "reference_window": want_window,
            "reference_intersection": exact,
            "intersection_lost_share": 100.0 * (
                1.0 - (h - want_window["top"] - want_window["bottom"])
                * (w - want_window["left"] - want_window["right"])
                / (h * w)),
            "reference_shifts": {str(c): own[c].tolist() for c in own},
            "max_abs_shift": int(max(np.abs(own[c]).max() for c in own)),
            "reference_register_s": register_s,
            "scipy_chain_s_per_site": chain_s / max(len(sites), 1),
        },
    }
