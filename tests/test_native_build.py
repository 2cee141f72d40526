"""The one loader of the C++ libraries (``ray_tpu/_private/native.py``):
git holds no ``.so``, so a checkout builds them before first use."""

import os
import subprocess

import pytest

from ray_tpu._private import native


def test_a_newer_source_triggers_one_rebuild(monkeypatch):
    native.ensure_built()
    assert not native._stale()
    lib = os.path.join(native._HERE, "libtpucrc.so")
    before = os.path.getmtime(lib)
    os.utime(os.path.join(native._SRC, "util", "crc32c.cc"))   # mtime = now
    assert native._stale()
    monkeypatch.setattr(native, "_built", False)
    native.ensure_built()      # make's own rules rebuild that library only
    assert not native._stale()
    assert os.path.getmtime(lib) > before


def test_no_toolchain_raises_instead_of_degrading(monkeypatch):
    def no_make(*args, **kwargs):
        raise FileNotFoundError("make")

    monkeypatch.setattr(native, "_built", False)
    monkeypatch.setattr(native, "_stale", lambda: True)
    monkeypatch.setattr(subprocess, "run", no_make)
    with pytest.raises(RuntimeError, match="make"):
        native.load("libtpusched.so")
