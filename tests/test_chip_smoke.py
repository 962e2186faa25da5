"""``chip_smoke.py`` and its start-of-run helpers: the explicit CPU dry run
passes end to end, the default refuses a host without an accelerator, the
compile cache can be placed from outside, and the native engine is keyed by
its source, not by mtimes."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"}


def _run(argv, env=None, timeout=600):
    return subprocess.run(
        [sys.executable] + argv,
        cwd=ROOT,
        env={**ENV, **(env or {})},
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_cpu_tiny_dry_run_passes_end_to_end(tmp_path) -> None:
    """Every leg (fork fits, HBM-filled path, device programs, four virtual
    chips, fresh-process resume) at toy widths; the compile cache goes where
    JAX_COMPILATION_CACHE_DIR says and the checkout's own stays untouched."""
    own_cache = os.path.join(ROOT, ".jax_cache")
    before = set(os.listdir(own_cache)) if os.path.isdir(own_cache) else None
    cache = str(tmp_path / "cc")
    out = _run(
        ["chip_smoke.py", "--platform", "cpu", "--tiny"],
        env={"JAX_COMPILATION_CACHE_DIR": cache},
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    # The driver's contract: the last line holds exactly these keys.
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    text = out.stdout
    assert "platform=cpu" in text
    # Nothing fills an HBM here: the steps during leg B's drain (2 to 8,
    # by when the drain ends) succeed.
    assert re.search(r"chip_smoke: leg B steps_during_drain=[2-8] drain_step_error=None\n", text)
    # A dry run reports no time and no rate.
    assert "GB/s" not in text and "not measured (platform=cpu)" in text
    for needle in (
        "[legA] read_object(params/block_0/proj/kernel) bit-exact",
        "host-captured 0 leaves",
        # Every bit pattern of every sub-32-bit float, put from the host:
        # packed and cut on the host, float16/float8 never forked.
        "[programs] slab pack: 304 leaves restore bit-exact",
        "132 leaves host-captured because a device copy would rewrite their dtype",
        "[programs] async_take of 8 big leaves / 2 MB: 5 forked",
        "[programs] fork (whole and in pieces) + transfers: 8 leaves",
        "[four] restore into transposed (tp, dp) mesh: bit-exact",
        "[four] restore into flat (4,) mesh: bit-exact",
        "[resume] bit-exact against saved step 3",
        "0 while compiling the train step the first process compiled",
    ):
        assert needle in text, needle
    assert any(n.endswith("-cache") for n in os.listdir(cache))
    after = set(os.listdir(own_cache)) if os.path.isdir(own_cache) else None
    assert after == before, "wrote under <checkout>/.jax_cache despite the env"


def test_default_refuses_a_host_without_an_accelerator() -> None:
    out = _run(["chip_smoke.py"], timeout=300)
    assert out.returncode != 0
    assert "platform is 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout  # no result line
    assert "[legA]" not in out.stdout  # refused before any take


def test_script_alone_fails(tmp_path) -> None:
    """In a directory that holds chip_smoke.py and nothing else of the repo
    there is no program to drive: non-zero, no result, and what is missing
    is the package."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--platform", "cpu", "--tiny"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "No module named 'torchsnapshot_tpu'" in out.stderr


_CACHE_PROBE = (
    "from chip_smoke import configure_compile_cache;"
    "d = configure_compile_cache(); import jax;"
    "assert jax.config.jax_compilation_cache_dir == d;"
    "assert jax.config.jax_persistent_cache_min_compile_time_secs == 0;"
    "print(d)"
)


def test_compile_cache_honours_env_and_is_otherwise_a_fixed_path(tmp_path) -> None:
    placed = str(tmp_path / "elsewhere")
    out = _run(["-c", _CACHE_PROBE], env={"JAX_COMPILATION_CACHE_DIR": placed})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == placed
    # Two processes, no env: the same in-checkout path (the path is part of
    # the cache key — a temp name, pid or timestamp would never hit).
    dirs = set()
    for _ in range(2):
        out = _run(["-c", _CACHE_PROBE])
        assert out.returncode == 0, out.stderr[-2000:]
        dirs.add(out.stdout.strip())
    assert dirs == {os.path.join(ROOT, ".jax_cache")}


def test_native_loader_ignores_a_newer_library_built_from_other_source(tmp_path) -> None:
    """After a checkout or a copy every mtime is "now": a stale library from
    another tree must not load because it looks new. The engine is keyed by
    the hash of tss_io.cpp."""
    pkg = tmp_path / "torchsnapshot_tpu"
    shutil.copytree(
        os.path.join(ROOT, "torchsnapshot_tpu"), pkg,
        ignore=shutil.ignore_patterns("__pycache__", "build"),
    )
    probe = (
        "from torchsnapshot_tpu import native;"
        "lib = native.load_native(); assert lib is not None;"
        "print(native.loaded_path()); print(lib.tss_io_version())"
    )

    def load():
        out = subprocess.run(
            [sys.executable, "-c", probe], cwd=tmp_path, env=ENV,
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.split()

    first_path, first_version = load()
    assert first_path.startswith(str(pkg / "native" / "build"))
    # Change the source (a different engine version), and make the OLD
    # library look newer than it.
    src = pkg / "native" / "tss_io.cpp"
    bumped = int(first_version) + 1
    src.write_text(
        src.read_text().replace(
            f"int tss_io_version() {{ return {first_version}; }}",
            f"int tss_io_version() {{ return {bumped}; }}",
        )
    )
    future = time.time() + 3600
    os.utime(first_path, (future, future))
    second_path, second_version = load()
    assert second_path != first_path
    assert int(second_version) == bumped
    assert second_path.startswith(str(pkg / "native" / "build"))
