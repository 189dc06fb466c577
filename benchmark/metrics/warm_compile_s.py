"""caches: seconds JAX reports in backend compiles during set-up
(``/jax/core/compile/backend_compile_duration``; a persistent-cache hit
is a short one, a store import is none)."""

UNIT = "s"


def read(run):
    return run.compile["setup"]["compile_s"]
