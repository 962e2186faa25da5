"""The benchmark's own guards in tier-1: every configuration's architecture
(``perfbench/tests/test_architecture.py``) and ``BENCHMARK.json`` against the
contract's rules of form and its data files (``perfbench/tests/test_contract.py``).
The driver runs ``tests/`` and never ``perfbench/tests``, so the tests of
those two files are collected here as they stand.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402

for _name in ("test_architecture", "test_contract"):
    _module = run.load_module("pb_" + _name, os.path.join(ROOT, "perfbench", "tests", _name + ".py"))
    globals().update({name: obj for name, obj in vars(_module).items() if name.startswith("test_")})

# Whatever these tests start keeps its compile cache under their own tmp_path.
pytestmark = pytest.mark.usefixtures("compile_cache_dir")
