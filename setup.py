"""Build hook: compile the first-party C++ host kernels into the package.

``native/tmnative.cpp`` (union-find CC labeling, Moore boundary tracing,
bounding boxes, convex hulls) is a plain ctypes shared library, not a
CPython extension — so instead of Extension/build_ext machinery it is
compiled with the ambient C++ compiler and shipped as package data
(``tmlibrary_tpu/libtmnative.so``).  ``tmlibrary_tpu.native`` builds from
the tracked source whenever the source tree is there (a checkout, an
editable install) and uses the packaged copy only in a wheel install,
which has no source tree; compiler-less environments keep working (every
native entry point has a scipy/numpy fallback, and the loader says so).
"""

import shutil
import subprocess
from pathlib import Path

from setuptools import setup
from setuptools.command.build_py import build_py

ROOT = Path(__file__).resolve().parent


class BuildWithNative(build_py):
    def run(self):
        super().run()
        src = ROOT / "native" / "tmnative.cpp"
        if not src.exists() or shutil.which("g++") is None:
            return  # fallbacks cover the native layer's absence
        out_dir = Path(self.build_lib) / "tmlibrary_tpu"
        out_dir.mkdir(parents=True, exist_ok=True)
        so = out_dir / "libtmnative.so"
        try:
            subprocess.run(
                ["g++", "-O3", "-ffp-contract=off", "-fPIC", "-std=c++17", "-shared",
                 "-o", str(so), str(src)],
                check=True, timeout=300,
            )
        except subprocess.SubprocessError:
            pass  # ship without the .so; runtime auto-build/fallback applies


setup(cmdclass={"build_py": BuildWithNative})
