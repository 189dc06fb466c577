"""caches: backend compiles inside the window that JAX's persistent cache
did not serve.  Must be 0; anything else also makes ``correct`` false."""

from benchmark.harness import real_compiles

UNIT = "count"


def read(run):
    if run.kind != "serve":
        return None
    return real_compiles(run.compile["window"])
