"""Ask the chip's compiler, without the chip.

Every kernel of ``ops/pallas_kernels.py`` and the whole-site jterator
batch programs are compiled NON-interpreted for a
*described* TPU v5e (``jax.experimental.topologies``): what the chip's
compiler refuses — a block shape off the (8, 128) tiling, a kernel over
its VMEM budget, a program that does not fit HBM or does not finish
compiling — fails here, at no chip time.  Interpret mode, which every
other test uses, shows none of that.  Nothing runs: a compile that passes
is not a chip run (``chip_smoke.py`` is).

One file on purpose: only one process may load the TPU's library, so the
topology is described inside a module-scoped fixture — after a test of
this file has started, never at import — and every case lives with it.
"""

import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: the field ``chip_smoke.py`` feeds the workflow phase, and its capacity
SMOKE_FIELD = 2160
SMOKE_CAPACITY = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip executable is written to the persistent cache but
    cannot be read back without a chip, so the cache is off around these
    compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch, one_chip, no_compile_cache):
    """Shapes placed on the described chip, and the program's own
    backend-name dispatch steered to its ``tpu`` branch (the process's
    real backend is the CPU's, so unsteered code would compile its CPU
    twin — scatter reductions, native callbacks — for the chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return shape


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


# ------------------------------------------------------- whole-site kernels
def _kernel_case(name, S):
    from tmlibrary_tpu.ops import pallas_kernels as pk

    site = (256, 256)
    vol = (16, 128, 128)
    cases = {
        "cc4": (lambda m: pk.cc_min_propagate(m, 4, interpret=False),
                [S(site, jnp.bool_)]),
        "cc8": (lambda m: pk.cc_min_propagate(m, 8, interpret=False),
                [S(site, jnp.bool_)]),
        "watershed": (
            lambda i, s, m: pk.watershed_flood(i, s, m, interpret=False),
            [S(site, jnp.float32), S(site, jnp.int32), S(site, jnp.bool_)]),
        "fill": (lambda m: pk.fill_holes_flood(m, interpret=False),
                 [S(site, jnp.bool_)]),
        "distance": (lambda m: pk.distance_transform(m, interpret=False),
                     [S(site, jnp.bool_)]),
        "cc3d": (lambda m: pk.cc3d_min_propagate(m, 26, interpret=False),
                 [S(vol, jnp.bool_)]),
        "watershed3d": (
            lambda i, s, m: pk.watershed3d_flood(i, s, m, 8, interpret=False),
            [S(vol, jnp.float32), S(vol, jnp.int32), S(vol, jnp.bool_)]),
    }
    return cases[name]


@pytest.mark.parametrize(
    "kernel",
    ["cc4", "cc8", "watershed", "fill", "distance", "cc3d", "watershed3d"])
def test_whole_site_kernel_compiles_for_v5e(kernel, on_tpu):
    fn, args = _kernel_case(kernel, on_tpu)
    compiled, _ = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in there


def test_whole_site_kernels_stay_out_of_auto_dispatch_at_a_full_field(
        monkeypatch):
    """A 2160x2160 plane neither fits VMEM nor finishes compiling as a
    whole-site kernel: ``method="auto"`` keeps the XLA twin there even
    when the kernels are switched on, and takes the kernel at 256x256."""
    from tmlibrary_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("TMX_PALLAS", "1")
    assert pk.pallas_enabled("cc", (256, 256))
    assert pk.pallas_enabled("cc3d", (16, 128, 128))
    assert not pk.pallas_enabled("cc", (SMOKE_FIELD, SMOKE_FIELD))
    assert not pk.pallas_enabled("watershed", (1080, 1080))
    assert pk.pallas_enabled("cc")  # no shape given: the verdict alone


# ---------------------------------------------------- XLA twins, full field
@pytest.mark.parametrize("op", ["cc", "fill"])
def test_xla_twin_compiles_at_the_full_field(op, on_tpu):
    """The fixpoints the full field runs (the whole-site kernels are out
    of dispatch there).  Both used to take the TPU compiler longer than
    25 minutes at 2160x2160 — even/odd scan recursion along the minor
    axis, a flat 4.7-Mpixel cumsum, a bool carry between the scans."""
    from tmlibrary_tpu.ops import label

    fn = {"cc": lambda m: label.connected_components(m, 8, method="xla"),
          "fill": lambda m: label.fill_holes(m, method="xla")}[op]
    _, seconds = _compile(fn, on_tpu((SMOKE_FIELD, SMOKE_FIELD), jnp.bool_))
    assert seconds < 300, f"{op} took {seconds:.0f}s to compile"


# ------------------------------------- --layout spatial, one well's mosaic
@pytest.mark.parametrize("n_devices", [4, 1])
@pytest.mark.parametrize("program", ["smooth", "cc"])
def test_spatial_layout_compiles_at_the_smokes_mosaic(program, n_devices,
                                                      topo, on_tpu):
    """The row-sharded programs of ``chip_smoke.py --chips 4``'s spatial
    phase at its 4320x4320 mosaic (2x2 full fields), on four described
    devices and on one.  The four-device CC program was still compiling
    on four chips when the call was killed after 23 minutes: its 1080x4320
    shard is not square, and the compile time of the former run scans
    blew up with that (PERF.md, PR 21)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from tmlibrary_tpu.ops.smooth import gaussian_radius
    from tmlibrary_tpu.parallel import halo
    from tmlibrary_tpu.parallel import label as plabel

    side = 2 * SMOKE_FIELD
    mesh = Mesh(np.asarray(topo.devices[:n_devices]), ("rows",))
    rows = NamedSharding(mesh, PartitionSpec("rows"))
    if program == "smooth":
        # the lru-cached builder's own function: a described mesh must
        # not stay in the process's cache
        fn = halo._cached_gaussian_halo.__wrapped__(
            mesh, 1.5, gaussian_radius(1.5), "rows")
        arg = jax.ShapeDtypeStruct((side, side), jnp.float32, sharding=rows)
    else:
        fn = plabel._cc_1d_program(
            mesh, side // n_devices, side, 8, 4096, "rows")
        arg = jax.ShapeDtypeStruct((side, side), jnp.bool_, sharding=rows)
    _, seconds = _compile(fn, arg)
    assert seconds < 300, (
        f"{program} on {n_devices} devices took {seconds:.0f}s to compile")


#: the ``cp3-mosaic.x4`` cell: one well's 3 x 3 fields as one mosaic, on
#: the four-chip host's own 2 x 2 mesh, root table as the cell sets it
MOSAIC_SIDE = 3 * SMOKE_FIELD
MOSAIC_ROOTS = 8192


@pytest.mark.parametrize("program", ["smooth", "otsu", "cc", "watershed"])
def test_spatial_grid_programs_compile_at_the_cells_mosaic(program, topo,
                                                           on_tpu, capsys):
    """Every sharded program of ``--layout spatial`` under ``spatial_grid:
    grid`` at the cell's 6480 x 6480 mosaic on a described ``v5e:2x2``
    (shards of 3240 x 3240): the 2-D halo smooth, the sharded Otsu, the
    2-D connected components with their seam join and root table, the 2-D
    watershed with its ``psum`` a step.  None had been compiled for the
    chip at this size before PR 33; a program over 5 minutes is a fault."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from tmlibrary_tpu.ops.smooth import gaussian_radius
    from tmlibrary_tpu.parallel import halo
    from tmlibrary_tpu.parallel import label as plabel

    side, axes = MOSAIC_SIDE, ("rows", "cols")
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), axes)
    tiles = NamedSharding(mesh, PartitionSpec(*axes))
    replicated = NamedSharding(mesh, PartitionSpec())

    def plane(dtype):
        return jax.ShapeDtypeStruct((side, side), dtype, sharding=tiles)

    # the lru-cached builders' own functions: a described mesh must not
    # stay in the process's cache
    if program == "smooth":
        fn = halo._cached_gaussian_halo_2d.__wrapped__(
            mesh, 1.5, gaussian_radius(1.5), *axes)
        args = [plane(jnp.float32)]
    elif program == "otsu":
        fn = plabel._otsu_program(mesh, axes, 256)
        args = [plane(jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)]
    elif program == "cc":
        fn = plabel._cc_2d_program(mesh, side // 2, side // 2, side, 8,
                                   MOSAIC_ROOTS, *axes)
        args = [plane(jnp.bool_)]
    else:
        fn = plabel._watershed_program(mesh, 16, 8, axes)
        args = [plane(jnp.float32), plane(jnp.int32), plane(jnp.bool_)]
    compiled, seconds = _compile(fn, *args)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    with capsys.disabled():
        print(f"\nmosaic {program} {side}x{side} on 2x2: compiled in "
              f"{seconds:.1f} s, args {mem.argument_size_in_bytes / 1e6:.0f}"
              f" MB, out {mem.output_size_in_bytes / 1e6:.0f} MB, temp "
              f"{mem.temp_size_in_bytes / 1e6:.0f} MB a device; "
              f"collective-permute {text.count('collective-permute(')}"
              f"+{text.count('collective-permute-start(')}, all-reduce "
              f"{text.count('all-reduce(')}+"
              f"{text.count('all-reduce-start(')}, all-gather "
              f"{text.count('all-gather(')}+"
              f"{text.count('all-gather-start(')}")
    assert seconds < 300, f"{program} took {seconds:.0f}s to compile"
    # a chip holds its tile and the program's temporaries, never the well
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert resident < 4e9, f"{resident / 1e9:.1f} GB a device"
    assert "callback" not in text


# ------------------------------------------------------- whole-site programs
def _program_case(config):
    from tmlibrary_tpu import benchmarks

    if config == "2":
        return benchmarks.smooth_threshold_description(), ("DAPI",)
    if config == "3":
        return benchmarks.cell_painting_description(), ("DAPI", "Actin")
    if config == "4x5":  # as the cp4-plate cell runs it: all five stains
        return (benchmarks.full_feature_description(),
                benchmarks.FULL_STACK_CHANNELS)
    channels = benchmarks.FULL_STACK_CHANNELS[:3]
    return benchmarks.full_feature_description(channels=channels), channels


@pytest.mark.parametrize("config,size,batch,capacity", [
    ("2", 256, 8, 64),
    ("3", 256, 8, 64),
    ("4", 256, 8, 64),
    # the smoke's workflow phase: one acquisition-geometry field per batch
    # (what the engine's batch resolver gives a 2160x2160 site on device)
    ("3", SMOKE_FIELD, 1, SMOKE_CAPACITY),
    # the cp4-plate cell's top rung (PR 27): 34 s, 172 MB of code and
    # 1.2 GB of temporaries here; a rung over 5 minutes is a fault
    ("4x5", SMOKE_FIELD, 1, SMOKE_CAPACITY),
])
def test_whole_site_program_compiles_for_v5e(config, size, batch, capacity,
                                             on_tpu):
    from tmlibrary_tpu.jterator.pipeline import ImageAnalysisPipeline

    S = on_tpu
    desc, channels = _program_case(config)
    fn = ImageAnalysisPipeline(desc, max_objects=capacity).build_batch_fn()
    raw = {c: S((batch, size, size), jnp.float32) for c in channels}
    t0 = time.perf_counter()
    compiled = fn.lower(raw, {}, S((batch, 2), jnp.int32)).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    print(f"config {config} {size}x{size} batch {batch} capacity {capacity}: "
          f"compiled in {seconds:.1f} s, args "
          f"{mem.argument_size_in_bytes / 1e6:.0f} MB, out "
          f"{mem.output_size_in_bytes / 1e6:.0f} MB, temp "
          f"{mem.temp_size_in_bytes / 1e6:.0f} MB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.0f} MB")
    # one program next to a pipelined window of its own inputs/outputs
    assert resident < 8e9, f"{resident / 1e9:.1f} GB of a 16 GB chip"
    assert seconds < 300, f"{seconds:.0f} s to compile one rung"
    # no hidden CPU: the measure families' host routes (zernike's "host",
    # GLCM's and the reductions' "native") are pure_callbacks, and none
    # may be in a program built for the chip
    assert "callback" not in compiled.as_text()


# ------------------------------- the multiplexed plate's programs (PR 37)
# (no limit on the compile's seconds here: the suite's six workers share
# the host, and a guessed time fails on a loaded one; the suite's own
# time limit ends a compile that never does)
def test_registration_compiles_at_the_full_field(on_tpu):
    """The align step's one program at a unit's launch: two wells'
    eighteen pairs of 2160 x 2160 uint16 fields (2160 = 2^4 x 3^3 x 5 is
    no power of two; nine pairs: 19.5 s and 764 MB of temporaries here).
    What ``pairs_in_flight`` plans a pair with (16 float32 planes) has to
    cover what the compiler takes."""
    from tmlibrary_tpu.ops import registration

    S = on_tpu
    pairs = 18
    stack = S((pairs, SMOKE_FIELD, SMOKE_FIELD), jnp.uint16)
    t0 = time.perf_counter()
    compiled = registration._batch_pcq_jit().lower(stack, stack).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    print(f"registration, {pairs} pairs: compiled in {seconds:.1f} s, "
          f"temp {mem.temp_size_in_bytes / 1e6:.0f} MB")
    assert resident <= pairs * 16 * 4 * SMOKE_FIELD * SMOKE_FIELD
    assert "phase_correlation" in compiled.as_text()


def test_multiplex_program_compiles_in_the_windows_frame(on_tpu):
    """``cp3-multiplex``'s batch program at its top rung: seven uint16
    planes, each under its own shift row, cropped to the stored window
    (32 px a side: a 2096 x 2096 frame), twelve measure calls."""
    import json
    from pathlib import Path

    from tmlibrary_tpu.jterator.description import PipelineDescription
    from tmlibrary_tpu.jterator.pipeline import (ImageAnalysisPipeline,
                                                 aligned_channels)

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                         / "configs" / "cp3-multiplex.json").read_text())
    desc = PipelineDescription.from_dict(config["pipeline"])
    names = aligned_channels(desc)
    assert len(names) == 7
    S = on_tpu
    fn = ImageAnalysisPipeline(desc, SMOKE_CAPACITY).build_batch_fn(
        window=(32, 32, 32, 32))
    raw = {c: S((1, SMOKE_FIELD, SMOKE_FIELD), jnp.uint16) for c in names}
    t0 = time.perf_counter()
    compiled = fn.lower(raw, {}, S((1, len(names), 2), jnp.int32)).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(f"cp3-multiplex rung {SMOKE_CAPACITY}: compiled in {seconds:.1f} s,"
          f" temp {mem.temp_size_in_bytes / 1e6:.0f} MB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.0f} MB")
    assert mem.temp_size_in_bytes < 4e9
