"""The one loader of the C++ libraries under ``src/``.

Git holds their sources, not the ``.so`` files, so a checkout builds them
itself: before a library is first opened, ``make -C src`` runs if a
library is missing or a source is newer than the last build here (make's
own rules then decide which of the five to rebuild). Without a toolchain
that raises; nothing degrades to a Python path.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "src")
LIBS = ("libtpustore.so", "libtpucollective.so", "libtpusched.so",
        "libtpuray.so", "libtpucrc.so")

_STAMP = os.path.join(_HERE, ".native_built")   # mtime = last build here

_lock = threading.Lock()
_built = False


def _stale() -> bool:
    if not all(os.path.exists(os.path.join(_HERE, lib))
               for lib in (*LIBS, os.path.basename(_STAMP))):
        return True
    built_at = os.path.getmtime(_STAMP)
    for root, dirs, files in os.walk(_SRC):
        dirs[:] = [d for d in dirs if d != "build"]
        for f in files:
            if ((f.endswith((".cc", ".hpp")) or f == "Makefile")
                    and os.path.getmtime(os.path.join(root, f)) > built_at):
                return True
    return False


def _make() -> None:
    try:
        proc = subprocess.run(["make", "-C", _SRC],
                              capture_output=True, text=True)
    except FileNotFoundError as e:
        raise RuntimeError(
            "ray_tpu's native libraries are not built and `make` is not "
            "installed") from e
    if proc.returncode != 0:
        raise RuntimeError(
            "building ray_tpu's native libraries failed "
            f"(make -C {_SRC}):\n{proc.stderr[-4000:]}")
    with open(_STAMP, "w"):
        pass


def ensure_built() -> None:
    """Bring all five libraries up to date, once per process. Processes
    that start together (a cluster's workers on a fresh checkout)
    serialise on a file lock, and the later ones find nothing to do."""
    global _built
    with _lock:
        if _built:
            return
        # an installed package without its sources has nothing to build
        if os.path.isdir(_SRC) and _stale():
            with open(os.path.join(_SRC, ".build.lock"), "w") as lockf:
                fcntl.flock(lockf, fcntl.LOCK_EX)
                if _stale():
                    _make()
        _built = True


def load(lib: str) -> ctypes.CDLL:
    ensure_built()
    return ctypes.CDLL(os.path.join(_HERE, lib))
