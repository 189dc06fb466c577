"""Compile smoke tests for scripts/ — nothing imports these at test time,
so a syntax error there ships silently (round-2 advisor finding: a stray
indent made ``tune_tpu.py`` unrunnable while CI stayed green)."""
import pathlib
import py_compile

import pytest

SCRIPTS = sorted(
    (pathlib.Path(__file__).resolve().parent.parent / "scripts").glob("*.py")
)


@pytest.mark.parametrize("path", SCRIPTS, ids=lambda p: p.name)
def test_script_compiles(path):
    py_compile.compile(str(path), doraise=True)


def test_scripts_found():
    assert len(SCRIPTS) >= 3


def test_demo_pipe_yaml_stays_valid(monkeypatch):
    """The demo script's embedded pipeline must parse and validate
    against the real description schema."""
    import yaml

    monkeypatch.syspath_prepend(str(SCRIPTS[0].parent.parent))
    # importing demo runs jax.config.update('jax_platforms','cpu'):
    # fine under the test conftest, which forces cpu anyway
    from scripts import demo

    from tmlibrary_tpu.jterator.description import PipelineDescription

    desc = PipelineDescription.from_dict(yaml.safe_load(demo.PIPE_YAML))
    desc.validate()
    assert [m.module for m in desc.modules] == [
        "smooth", "segment_primary", "measure_intensity"
    ]


def test_check_durations_parses_and_flags(tmp_path):
    """The CI durations gate reads pytest's --durations section and flags
    only over-budget ``call`` phases (setup/teardown time is pytest's
    own bookkeeping, not the test's)."""
    import sys

    sys.path.insert(0, str(SCRIPTS[0].parent))
    try:
        from check_durations import check
    finally:
        sys.path.pop(0)

    log = [
        "============ slowest 40 durations ============\n",
        "  61.20s call     tests/test_big.py::test_huge\n",
        "  70.00s setup    tests/test_big.py::test_huge\n",
        "   5.01s call     tests/test_small.py::test_fast\n",
        "some unrelated line\n",
    ]
    checked, offenders = check(log, limit=60.0)
    assert checked == 2
    assert offenders == [(61.2, "tests/test_big.py::test_huge")]
    checked, offenders = check(log, limit=120.0)
    assert offenders == []
    # no duration lines at all -> caller reports a broken invocation
    assert check(["garbage\n"], limit=60.0) == (0, [])
