"""Ops library: JAX twins of the reference's pixel-math stack.

Reference parity map (see SURVEY.md §3):

- ``jtmodules``/``jtlib`` (smooth, threshold, segment, measure, register) →
  the modules in this package, all pure ``jnp``/``lax`` and jit/vmap-safe.
- cv2 / mahotas / scipy.ndimage native kernels → XLA ops (separable convs,
  window gathers, ``segment_sum`` reductions, one-hot matmul GLCMs), Pallas
  where XLA's lowering is not enough.
- host-only raggedness (polygon tracing, PNG encode) stays host-side in
  :mod:`tmlibrary_tpu.ops.polygons`.
"""

import functools

import jax


def named(stage: str):
    """Decorator: trace the function under ``jax.named_scope(stage)``, so
    every HLO instruction it emits carries ``stage`` in its op name — the
    name a device trace attributes the instruction's time to.  Metadata
    only: the computation is unchanged.  ``jax.named_scope`` is looked up
    at trace time (a test builds the same program with it patched out)."""

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(stage):
                return fn(*args, **kwargs)

        return scoped

    return deco
