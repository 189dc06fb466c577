#!/usr/bin/env python
"""Chaos smoke run: the canonical workflow under injected faults.

Builds a tiny synthetic experiment, runs the full canonical pipeline
three ways and checks convergence:

1. **reference** — fault-free run; final labels + features recorded.
2. **chaotic** — same inputs with a deterministic fault plan armed
   (device loss on one jterator batch, an IO fault on another, both
   outlasting every retry).  The run must *survive* by quarantining the
   two batches under the 0.5 failure budget.
3. **resume** — the plan cleared (the "device came back" moment),
   ``resume=True``.  The store must now equal the reference bit-for-bit.

Exit code 0 and ``CHAOS PASS`` on convergence; 1 otherwise.  This is
the operational counterpart of ``tests/test_chaos.py`` — runnable on a
box without pytest, and the quickest way to sanity-check the resilience
layer after touching the engine:

    python scripts/chaos_run.py [--keep DIR]

A custom plan can be armed instead via ``TMX_FAULT_PLAN`` (inline JSON
or a path); the built-in plan is only installed when that variable is
unset.
"""

import argparse
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# a CI smoke is a CPU run, whatever is attached
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

DEFAULT_PLAN = {
    "seed": 7,
    "faults": [
        {"site": "batch_run", "kind": "device_loss", "step": "jterator",
         "batch": 1, "times": 99},
        {"site": "batch_run", "kind": "io_error", "step": "jterator",
         "batch": 3, "times": 99},
    ],
}

PIPE_YAML = """\
description: chaos smoke pipeline
input:
  channels: [{name: DAPI, correct: true, align: false}]
pipeline:
- handles:
    module: smooth
    input:
    - {name: intensity_image, type: IntensityImage, key: DAPI}
    - {name: sigma, type: Numeric, value: 1.5}
    output:
    - {name: smoothed_image, type: IntensityImage, key: sm}
- handles:
    module: segment_primary
    input:
    - {name: intensity_image, type: IntensityImage, key: sm}
    - {name: threshold_method, type: Character, value: otsu}
    - {name: smooth_sigma, type: Numeric, value: 0.0}
    - {name: min_area, type: Numeric, value: 10}
    output:
    - {name: objects, type: SegmentedObjects, key: nuclei, objects: nuclei}
- handles:
    module: measure_intensity
    input:
    - {name: objects_image, type: LabelImage, key: nuclei}
    - {name: intensity_image, type: IntensityImage, key: DAPI}
    output:
    - {name: measurements, type: Measurement, objects: nuclei, channel: DAPI}
output:
  objects: [{name: nuclei}]
"""


def make_source(root: Path) -> Path:
    """16 synthetic DAPI sites (4 wells x 4 sites), seeded."""
    import cv2

    rng = np.random.default_rng(42)
    src = root / "microscope"
    src.mkdir()
    yy, xx = np.mgrid[0:64, 0:64]
    for well in ("A01", "A02", "B01", "B02"):
        for site in range(4):
            img = rng.normal(300, 20, (64, 64))
            for _ in range(6):
                y, x = rng.integers(8, 56, 2)
                img += 4000 * np.exp(
                    -((yy - y) ** 2 + (xx - x) ** 2) / (2 * 3.0**2)
                )
            img = np.clip(img, 0, 65535).astype(np.uint16)
            cv2.imwrite(str(src / f"{well}_s{site}_DAPI.png"), img)
    return src


def make_store(root: Path, name: str, source: Path):
    from tmlibrary_tpu.models.experiment import Experiment
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.workflow.engine import WorkflowDescription

    store = ExperimentStore.create(
        root / name,
        Experiment(name=name, plates=[], channels=[],
                   site_height=1, site_width=1),
    )
    (store.root / "nuclei.pipe.yaml").write_text(PIPE_YAML)
    desc = WorkflowDescription.canonical({
        "metaconfig": {"source_dir": str(source)},
        "imextract": {},
        "corilla": {"chunk_size": 8, "n_devices": 1},
        # batch_size 4 -> 4 jterator batches; 0.5 budget tolerates 2
        "jterator": {"pipe": "nuclei.pipe.yaml", "batch_size": 4,
                     "max_objects": 64, "n_devices": 1},
    })
    return store, desc


def resilience():
    from tmlibrary_tpu.resilience import ResilienceConfig, RetryPolicy

    return ResilienceConfig(
        policy=RetryPolicy(max_attempts=2, base_delay=0.0, jitter=0.0),
        max_batch_failures=0.5,
        guard=None,  # the smoke run exercises quarantine, not the probe
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--keep", metavar="DIR", default=None,
                        help="run inside DIR and keep the artifacts "
                             "(default: a temp dir, removed afterwards)")
    args = parser.parse_args(argv)

    from tmlibrary_tpu import faults
    from tmlibrary_tpu.workflow.engine import Workflow

    with tempfile.TemporaryDirectory() as tmp:
        root = Path(args.keep) if args.keep else Path(tmp)
        root.mkdir(parents=True, exist_ok=True)
        source = make_source(root)

        print("[1/3] reference run (fault-free)")
        ref, desc = make_store(root, "reference", source)
        Workflow(ref, desc, resilience=resilience()).run()
        ref_labels = ref.read_labels(None, "nuclei")
        ref_feats = ref.read_features("nuclei").sort_values(
            ["site_index", "label"]).reset_index(drop=True)

        print("[2/3] chaotic run (fault plan armed)")
        if os.environ.get("TMX_FAULT_PLAN"):
            faults._ENV_CHECKED = False  # let the env plan load
        else:
            faults.install(faults.FaultPlan.from_dict(DEFAULT_PLAN))
        chaotic, desc = make_store(root, "chaotic", source)
        summary = Workflow(chaotic, desc, resilience=resilience()).run()
        quarantined = {s: v["quarantined"] for s, v in summary.items()
                       if "quarantined" in v}
        print(f"      survived; quarantined batches: {quarantined or '{}'}")
        print(f"      faults fired: {faults.active().fire_counts()}")
        if not quarantined:
            print("CHAOS FAIL: the fault plan injected nothing — "
                  "hook sites or plan matching are broken")
            return 1

        print("[3/3] faults cleared; resume")
        faults.clear()
        summary = Workflow(chaotic, desc, resilience=resilience()).run(
            resume=True)
        if any("quarantined" in v for v in summary.values()):
            print("CHAOS FAIL: quarantined batches survived a clean resume")
            return 1

        labels_ok = np.array_equal(
            chaotic.read_labels(None, "nuclei"), ref_labels)
        got = chaotic.read_features("nuclei").sort_values(
            ["site_index", "label"]).reset_index(drop=True)
        feats_ok = got.equals(ref_feats)
        print(f"      labels converged:   {labels_ok}")
        print(f"      features converged: {feats_ok}")
        if labels_ok and feats_ok:
            print("CHAOS PASS: faulted run + resume == fault-free run")
            return 0
        print("CHAOS FAIL: resumed store diverges from the reference")
        return 1


if __name__ == "__main__":
    sys.exit(main())
