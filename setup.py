"""Build hook: compile the native I/O engine into the wheel.

The pyproject metadata is the source of truth; this file exists only to
attach a custom ``build_py`` that runs ``make -C torchsnapshot_tpu/native``
so binary wheels ship the engine prebuilt under its source-hash-keyed name
(``native/build/libtss_io-<hash>.so``, the only name the loader opens; the
analogue of the reference's ``release_build.yaml`` packaging step). Environments without a
C++ toolchain still get a working package: the build falls back to
source-only, and the runtime loader (``torchsnapshot_tpu/native/__init__.py``)
compiles on first use or degrades to pure-Python file I/O.
"""

import glob
import os
import shutil
import subprocess

from setuptools import setup
from setuptools.command.build_py import build_py


class build_py_with_native(build_py):
    def run(self):
        super().run()
        src_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "torchsnapshot_tpu", "native")
        try:
            subprocess.run(["make", "-C", src_dir], check=True)
        except Exception as e:  # noqa: BLE001 - source-only wheel is valid
            print(f"native engine prebuild skipped ({e}); the runtime "
                  "loader will compile from the shipped sources on first use")
            return
        target_dir = os.path.join(
            self.build_lib, "torchsnapshot_tpu", "native", "build"
        )
        os.makedirs(target_dir, exist_ok=True)
        for so_path in glob.glob(os.path.join(src_dir, "build", "libtss_io-*.so")):
            shutil.copy2(so_path, target_dir)


setup(cmdclass={"build_py": build_py_with_native})
