"""Crash-consistent file writes: the tmp+rename discipline in one place.

Several subsystems persist small JSON artifacts next to the run ledger
(metrics snapshots, heartbeats, QC profiles, perf attribution, tuning
verdicts).  A reader racing a writer — ``tmx top`` polling a live run,
or a resumed process inspecting the artifacts a killed one left behind —
must never observe a half-written file, and a hard kill mid-write must
never corrupt the previous good version.  POSIX ``rename(2)`` within a
directory is atomic, so every writer here follows the same protocol:
write the full payload to a sibling temp file, then rename over the
target.  Readers either see the old complete file or the new complete
file, nothing in between.

The temp name embeds the writer's PID and thread id, so neither two
processes targeting the same path (two fleet hosts mis-configured onto
one file) nor two threads of one process (a daemon's lease renewer and
its main loop writing one heartbeat, the executor's workers) share a
temp file: with the PID alone one thread's ``rename`` took the other's
half-written file away.  The last rename wins, which is the same
last-write-wins semantics whole-file writes always had.  The name starts
with a dot, so no glob over a directory's artifacts (``*.claim.*``,
``*.json``) takes a temp file for one of them.
"""

from __future__ import annotations

import json
import os
import threading
from pathlib import Path
from typing import Any

from tmlibrary_tpu import faults

#: what every temp file's name ends in
TMP_SUFFIX = ".tmp"


def temp_path(path: Path) -> Path:
    """The calling thread's own temp file beside ``path``."""
    return path.with_name(
        f".{path.name}.{os.getpid()}.{threading.get_ident()}{TMP_SUFFIX}")


def atomic_write_bytes(path: Path | str, data: bytes,
                       fsync: bool = False) -> None:
    """``atomic_write_text`` for a binary payload."""
    _atomic_write(Path(path), data, "wb", fsync)


def atomic_write_text(path: Path | str, text: str,
                      fsync: bool = False) -> None:
    """Write ``text`` to ``path`` atomically (tmp + rename).

    With ``fsync=True`` the payload is flushed to stable storage before
    the rename, making the write crash-*durable* as well as
    crash-consistent — the ledger-adjacent artifacts default to
    consistency only, matching the ledger's own ``ledger_fsync`` knob.
    """
    _atomic_write(Path(path), text, "w", fsync)


def _atomic_write(path: Path, payload, mode: str, fsync: bool) -> None:
    tmp = temp_path(path)
    try:
        with open(tmp, mode) as f:
            f.write(payload)
            if fsync:
                f.flush()
                os.fsync(f.fileno())
        # a writer dying here leaves the old complete target (or none) and
        # its own temp file: never a partial target
        faults.maybe_fire("atomic_rename", event=path.name)
        os.replace(tmp, path)
    finally:
        # a failure between open and replace must not litter temp files
        if tmp.exists():
            tmp.unlink(missing_ok=True)


def atomic_write_json(path: Path | str, obj: Any,
                      fsync: bool = False, **dumps_kwargs: Any) -> None:
    """``atomic_write_text`` for a JSON payload (serialized first, so a
    serialization error can never leave a partial file either)."""
    atomic_write_text(path, json.dumps(obj, **dumps_kwargs), fsync=fsync)


def claim_rename(src: Path | str, dst: Path | str) -> bool:
    """Atomically move ``src`` to ``dst``; returns whether *this caller*
    won the move.

    This is the fleet spool protocol's claim arbiter (DESIGN.md §25):
    several hosts polling one spool directory race to ``rename(2)`` the
    same source file, POSIX guarantees exactly one rename observes the
    source, and every loser gets ``ENOENT`` — converted here to a plain
    ``False`` so "someone else claimed it" is a decision, not an error.
    The destination may already exist (a stale copy left by a crashed
    reaper); rename atomically replaces it, which is exactly the
    last-write-wins recovery those torn sweeps need.
    """
    try:
        os.replace(src, dst)
        return True
    except FileNotFoundError:
        return False
