"""The checkpoint target's rule: only a run's own directories, block-backed
before remote, and never RAM-backed in a measured run."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import target  # noqa: E402


@pytest.fixture
def places(tmp_path, monkeypatch):
    """Three candidate directories whose filesystem type a test sets by name."""
    types = {}
    dirs = {name: str(tmp_path / name) for name in ("tmpdir", "home", "checkout")}
    monkeypatch.setattr(target, "candidate_dirs", lambda: list(dirs.values()))
    monkeypatch.setattr(
        target, "filesystem_of",
        lambda path: {"fstype": types[os.path.basename(path)], "mount": path, "source": "x"},
    )
    monkeypatch.setattr(target, "takes_o_direct", lambda directory: True)
    return types, dirs


def test_block_backed_is_taken_before_a_remote_mount_that_comes_first(places):
    types, dirs = places
    types.update(tmpdir="9p", home="ext4", checkout="ext4")
    got = target.resolve_target(1)
    assert got["base"] == dirs["home"] and got["class"] == "block" and os.path.isdir(got["dir"])
    assert os.path.dirname(got["dir"]) == dirs["home"]
    target.release(got)
    assert not os.path.exists(got["dir"]) and os.path.isdir(dirs["home"])


def test_ram_backed_is_passed_over_and_alone_fails_a_measured_run(places):
    types, dirs = places
    types.update(tmpdir="tmpfs", home="9p", checkout="9p")
    got = target.resolve_target(1)
    assert got["base"] == dirs["home"] and got["fstype"] == "9p"
    target.release(got)
    types.update(tmpdir="tmpfs", home="tmpfs", checkout="ramfs")
    with pytest.raises(OSError, match="RAM-backed"):
        target.resolve_target(1)
    dry = target.resolve_target(1, allow_ram=True)  # the dry run reports no time
    assert dry["class"] == "ram"
    target.release(dry)


def test_no_room_anywhere_fails(places):
    types, _ = places
    types.update(tmpdir="ext4", home="ext4", checkout="ext4")
    with pytest.raises(OSError):
        target.resolve_target(1 << 62)
