"""``atomicio``'s temp file is a writer THREAD's own, and the alignment
tables go through it: two threads writing one path lose no file (PERF.md
section 7's second race, as its deterministic witness), and a writer that
dies between the last byte and the rename leaves the old complete
``shifts_cycleNN.npy`` / ``intersection.json`` — or none — and never half
of one (a ``faults.py`` plan at the ``atomic_rename`` site)."""

import json
import threading

import numpy as np
import pytest

from tmlibrary_tpu import atomicio, faults
from tmlibrary_tpu.errors import FaultInjected, StoreError
from tmlibrary_tpu.models.experiment import grid_experiment
from tmlibrary_tpu.models.store import ExperimentStore


@pytest.fixture(autouse=True)
def _no_plan():
    faults.clear()
    yield
    faults.clear()


def test_temp_name_is_the_threads_own_and_hidden(tmp_path):
    target = tmp_path / "heartbeat.json"
    names = {}

    def note(key):
        names[key] = atomicio.temp_path(target)

    note("main")
    other = threading.Thread(target=note, args=("other",))
    other.start()
    other.join()
    assert names["main"] != names["other"]
    for path in names.values():
        assert path.parent == target.parent
        assert path.name.startswith(".") and path.name.endswith(".tmp")
    assert atomicio.temp_path(target) == names["main"]


def test_two_threads_writing_one_heartbeat_lose_no_file(tmp_path):
    """The lease renewer and the main loop of one daemon both publish
    ``heartbeat.json``.  Under one temp name a process, one thread's
    ``os.replace`` took the other's temp file away and the second
    ``replace`` raised ``[Errno 2]``: `tmx serve run` exited 1."""
    target = tmp_path / "heartbeat.json"
    errors: list = []
    start = threading.Barrier(2)

    def publish(who: int) -> None:
        start.wait()
        try:
            for turn in range(400):
                atomicio.atomic_write_json(
                    target, {"who": who, "turn": turn, "pad": "x" * 2048})
        except Exception as exc:  # the race's own symptom
            errors.append(repr(exc))

    threads = [threading.Thread(target=publish, args=(who,))
               for who in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert json.loads(target.read_text())["turn"] == 399
    assert [p.name for p in tmp_path.iterdir()] == ["heartbeat.json"]


def test_bytes_and_text_share_the_discipline(tmp_path):
    atomicio.atomic_write_bytes(tmp_path / "a.bin", b"\x00\x01\x02")
    atomicio.atomic_write_text(tmp_path / "a.txt", "abc")
    assert (tmp_path / "a.bin").read_bytes() == b"\x00\x01\x02"
    assert (tmp_path / "a.txt").read_text() == "abc"
    assert sorted(p.name for p in tmp_path.iterdir()) == ["a.bin", "a.txt"]


def store_of(tmp_path) -> ExperimentStore:
    exp = grid_experiment("mx", well_rows=1, well_cols=1,
                          sites_per_well=(1, 2), site_shape=(8, 8),
                          n_cycles=2)
    return ExperimentStore.create(tmp_path / "exp", exp)


OLD_SHIFTS = np.asarray([[1, -2], [3, 4]], np.int32)
NEW_SHIFTS = np.asarray([[5, 5], [-6, 0]], np.int32)
OLD_WINDOW = {"top": 16, "bottom": 16, "left": 16, "right": 16}
NEW_WINDOW = {"top": 32, "bottom": 32, "left": 32, "right": 32}

WRITES = {
    "shifts": ("shifts_cycle01.npy",
               lambda s, new: s.write_shifts(NEW_SHIFTS if new
                                             else OLD_SHIFTS, 1),
               lambda s: s.read_shifts(1).tolist(), OLD_SHIFTS.tolist()),
    "intersection": ("intersection.json",
                     lambda s, new: s.write_intersection(
                         NEW_WINDOW if new else OLD_WINDOW),
                     lambda s: s.read_intersection(), OLD_WINDOW),
}


@pytest.mark.parametrize("overwrite", [False, True],
                         ids=["first_write", "overwrite"])
@pytest.mark.parametrize("what", list(WRITES))
def test_writer_killed_before_the_rename_leaves_no_partial_file(
        tmp_path, what, overwrite):
    name, write, read, old = WRITES[what]
    store = store_of(tmp_path)
    if overwrite:
        write(store, False)
    faults.install(faults.FaultPlan([faults.FaultSpec(
        site="atomic_rename", kind="crash", event=name)]))
    with pytest.raises(FaultInjected):
        write(store, True)
    faults.clear()
    directory = store.root / "alignment"
    if overwrite:
        assert read(store) == old          # the old table, whole
        assert [p.name for p in directory.iterdir()] == [name]
    else:
        assert list(directory.iterdir()) == []
        with pytest.raises(StoreError):
            read(store)
    write(store, True)                     # and the next writer succeeds
    assert read(store) != old
    assert [p.name for p in directory.iterdir()] == [name]


def test_the_fault_names_its_target(tmp_path):
    """A plan aimed at one table leaves the other writes alone."""
    store = store_of(tmp_path)
    faults.install(faults.FaultPlan([faults.FaultSpec(
        site="atomic_rename", kind="crash", event="intersection.json")]))
    store.write_shifts(NEW_SHIFTS, 1)
    np.testing.assert_array_equal(store.read_shifts(1), NEW_SHIFTS)
    with pytest.raises(FaultInjected):
        store.write_intersection(NEW_WINDOW)


def test_read_intersection_gives_the_four_margins(tmp_path):
    store = store_of(tmp_path)
    store.write_intersection({**NEW_WINDOW, "intersection": OLD_WINDOW})
    assert store.read_intersection() == NEW_WINDOW
