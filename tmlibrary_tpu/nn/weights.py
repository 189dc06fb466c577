"""Named U-Net checkpoint store: ``.npz`` pytrees + content digests.

Checkpoints follow ``models/store.py`` conventions — flat ``np.savez``
archives written atomically (tmp + ``os.replace``) with failures raised
as :class:`~tmlibrary_tpu.errors.StoreError` — and every load returns a
**content digest** alongside the parameters.  The digest is the weight
identity the rest of the system keys on:

- ``jterator/pipeline.program_digest_extras`` folds it into the
  compiled-program cache key and the perf program digest, so swapping a
  checkpoint file under an unchanged name can never serve a stale
  compiled program (the PR-8 QC-gate digest lesson, generalized);
- ``bench.py``'s ``dl`` config stamps it into ``timing_methodology``
  provenance so the regression sentinel never compares runs across
  checkpoints;
- ``tmx weights list|digest`` surfaces it for humans.

Weight specs
------------
``resolve_weights`` accepts three spellings:

``seed:<int>[:base=<C>][:depth=<D>][:in=<N>]``
    Deterministic He-initialized random weights (``nn/unet.py``) — no
    file involved.  The CI smoke, the decoder-determinism tests and the
    ``dl`` bench config run on these, so every environment can exercise
    the full DL path without shipping a trained checkpoint.
``<name>``
    ``<name>.npz`` inside the weights directory (``TMX_WEIGHTS_DIR``
    env, default ``<checkout>/.cache/weights``).
``<path ending in .npz>``
    An explicit filesystem path.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import re
import threading
from pathlib import Path

import numpy as np

from tmlibrary_tpu.errors import StoreError

#: reserved npz key carrying the JSON-encoded architecture metadata
_META_KEY = "__meta__"

_SEED_SPEC = re.compile(r"^seed:(?P<seed>\d+)(?P<opts>(?::[a-z]+=\d+)*)$")

#: resolved-weights memo: spec -> (file identity, params, digest, config).
#: File-backed entries key on (mtime_ns, size) so an overwritten
#: checkpoint re-resolves — the digest MUST track file content, it is
#: what keeps the compiled-program cache honest.
_RESOLVE_CACHE: dict = {}
_RESOLVE_LOCK = threading.Lock()
_RESOLVE_CACHE_MAX = 8


def weights_dir() -> Path:
    """The named-checkpoint directory (created on access, like the
    experiment store's ``tools_dir``)."""
    from tmlibrary_tpu.utils import checkout_cache_dir

    path = Path(os.environ.get("TMX_WEIGHTS_DIR")
                or checkout_cache_dir("weights"))
    path.mkdir(parents=True, exist_ok=True)
    return path


def params_digest(params: dict) -> str:
    """Content digest of a parameter pytree: sha1 over sorted names,
    shapes, dtypes and raw bytes (12 hex chars — same width as the
    description digest family)."""
    h = hashlib.sha1()
    for name in sorted(params):
        arr = np.ascontiguousarray(np.asarray(params[name]))
        h.update(name.encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()[:12]


def save_weights(
    name: str, params: dict, meta: dict | None = None,
    directory: "Path | str | None" = None,
) -> Path:
    """Write a checkpoint atomically; returns the ``.npz`` path.

    ``meta`` (architecture, provenance) embeds as a JSON-encoded
    ``__meta__`` entry so the archive stays self-describing.
    """
    path = _spec_path(name, directory)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {k: np.asarray(v) for k, v in params.items()}
    if meta:
        payload[_META_KEY] = np.frombuffer(
            json.dumps(meta, sort_keys=True).encode(), np.uint8
        )
    buf = io.BytesIO()
    np.savez(buf, **payload)
    tmp = path.with_name(path.name + ".tmp")
    try:
        tmp.write_bytes(buf.getvalue())
        tmp.replace(path)
    except OSError as e:
        tmp.unlink(missing_ok=True)
        raise StoreError(f"cannot write weights '{name}': {e}") from e
    return path


def load_weights(
    name: str, directory: "Path | str | None" = None
) -> tuple[dict, dict]:
    """Load a checkpoint; returns ``(params, meta)``."""
    path = _spec_path(name, directory)
    if not path.exists():
        raise StoreError(f"no such weights checkpoint: {path}")
    try:
        with np.load(path) as npz:
            params = {k: npz[k] for k in npz.files if k != _META_KEY}
            meta = {}
            if _META_KEY in npz.files:
                meta = json.loads(bytes(npz[_META_KEY].tobytes()).decode())
    except (OSError, ValueError) as e:
        raise StoreError(f"cannot read weights '{name}': {e}") from e
    return params, meta


def list_weights(directory: "Path | str | None" = None) -> list[dict]:
    """Inventory of the weights directory: one row per checkpoint with
    name, path, array/parameter counts and the content digest."""
    root = Path(directory) if directory else weights_dir()
    rows = []
    for path in sorted(root.glob("*.npz")):
        params, meta = load_weights(path.stem, root)
        rows.append({
            "name": path.stem,
            "path": str(path),
            "n_arrays": len(params),
            "n_params": int(sum(np.asarray(v).size for v in params.values())),
            "digest": params_digest(params),
            "meta": meta,
        })
    return rows


def resolve_weights(spec: str):
    """Resolve a weight spec to ``(params, digest, config)``.

    Memoized per process (file-backed entries invalidate on mtime/size
    change) — the jterator module fns call this at trace time, so a
    bucket ladder of programs over one checkpoint reads the file once.
    """
    from tmlibrary_tpu.nn import unet

    spec = str(spec).strip()
    if not spec:
        raise StoreError("empty weights spec")
    path = None if _SEED_SPEC.match(spec) else _spec_path(spec, None)
    ident = None
    if path is not None:
        try:
            st = path.stat()
            ident = (st.st_mtime_ns, st.st_size)
        except OSError as e:
            raise StoreError(f"no such weights checkpoint: {path}") from e
    with _RESOLVE_LOCK:
        hit = _RESOLVE_CACHE.get(spec)
        if hit is not None and hit[0] == ident:
            return hit[1], hit[2], hit[3]
    if path is None:
        m = _SEED_SPEC.match(spec)
        opts = dict(
            kv.split("=") for kv in m.group("opts").split(":") if kv
        )
        config = unet.UNetConfig(
            in_channels=int(opts.get("in", 1)),
            base_channels=int(opts.get("base", 8)),
            depth=int(opts.get("depth", 2)),
        )
        params = unet.init_unet_params(int(m.group("seed")), config)
    else:
        params, _meta = load_weights(spec)
        config = unet.infer_config(params)
    digest = params_digest(params)
    with _RESOLVE_LOCK:
        while len(_RESOLVE_CACHE) >= _RESOLVE_CACHE_MAX:
            _RESOLVE_CACHE.pop(next(iter(_RESOLVE_CACHE)))
        _RESOLVE_CACHE[spec] = (ident, params, digest, config)
    return params, digest, config


def weights_digest(spec: str) -> str:
    """The content digest a spec resolves to (cached via
    :func:`resolve_weights`)."""
    return resolve_weights(spec)[1]


def _spec_path(spec: str, directory: "Path | str | None") -> Path:
    if spec.endswith(".npz") or os.sep in spec:
        p = Path(spec)
        return p if p.suffix == ".npz" else p.with_suffix(".npz")
    root = Path(directory) if directory else weights_dir()
    return root / f"{spec}.npz"
