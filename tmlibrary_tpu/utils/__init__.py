"""General utilities.

Reference parity: ``tmlib/utils.py`` — notably ``create_partitions`` (batch
chunking used by every step's ``create_run_batches``), ``flatten``, and the
type-assertion helpers.
"""

from __future__ import annotations

import itertools
import os
from typing import Any, Iterable, Sequence


def create_partitions(items: Sequence[Any], size: int) -> list[list[Any]]:
    """Split ``items`` into consecutive chunks of at most ``size`` elements.

    This is the batching primitive every workflow step uses to plan its run
    jobs (reference: ``tmlib.utils.create_partitions``).  In the TPU rebuild a
    "partition" becomes a ``vmap`` batch rather than a cluster job.
    """
    if size < 1:
        raise ValueError("partition size must be >= 1")
    items = list(items)
    return [items[i : i + size] for i in range(0, len(items), size)]


def flatten(nested: Iterable[Iterable[Any]]) -> list[Any]:
    """Flatten one level of nesting."""
    return list(itertools.chain.from_iterable(nested))


def assert_type(value: Any, name: str, *types: type) -> None:
    """Raise ``TypeError`` unless ``value`` is an instance of one of ``types``."""
    if not isinstance(value, tuple(types)):
        expected = " or ".join(t.__name__ for t in types)
        raise TypeError(
            f"argument '{name}' must be of type {expected}, "
            f"got {type(value).__name__}"
        )


def pad_to(values: Sequence[Any], length: int, fill: Any) -> list[Any]:
    """Pad ``values`` with ``fill`` up to ``length`` (static-shape helper)."""
    values = list(values)
    if len(values) > length:
        raise ValueError(f"got {len(values)} values, more than length={length}")
    return values + [fill] * (length - len(values))


def next_power_of_two(n: int) -> int:
    """Smallest power of two >= n (shape bucketing for XLA compile caching)."""
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def checkout_cache_dir(name: str) -> str:
    """``<checkout>/.cache/<name>``: the fixed in-tree home of everything
    the program caches when the environment names no place for it.  The
    path is part of the compile cache's key, so it never carries a temp
    name, pid or timestamp, and it is never under ``$HOME``."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".cache", name)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache so repeated CLI/bench
    invocations skip recompiling the fused pipeline, and return its
    directory.  The directory is the environment's to choose: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and no
    directory is set here; otherwise the cache lives at
    ``<checkout>/.cache/xla``.  Only the thresholds are set in code
    (cache everything, not only long compiles)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = checkout_cache_dir("xla")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
