"""Shared loader for the native C++ components in csrc/ (build-on-demand +
ctypes; the reference builds its native code via CMake up front)."""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

_CACHE: dict = {}


def csrc_dir() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "csrc"))


def _source_digest(root: str, so_name: str) -> str:
    """sha256 over what csrc/<so_name> is built from: its .cpp (the
    Makefile's one-source-per-library rule) and the Makefile."""
    h = hashlib.sha256()
    for name in (so_name[len("lib"):-len(".so")] + ".cpp", "Makefile"):
        with open(os.path.join(root, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def load_native_lib(so_name: str) -> ctypes.CDLL:
    """Load csrc/<so_name>, built from THIS tree's source.  Each build
    leaves the digest of its source beside the library (`<so>.src`); a
    library that is missing, has no digest, or whose digest is not that
    of the source now in the tree — a stale one, or one copied in from
    elsewhere, whatever its timestamp — is rebuilt with `make -B` first.
    (A library copied together with its matching digest is trusted: it
    was built from the same source.)  The .so and .src files are build
    outputs, never committed.  A missing compiler or a compile error
    raises with the tool's stderr."""
    if so_name in _CACHE:
        return _CACHE[so_name]
    root = csrc_dir()
    so_path, stamp = os.path.join(root, so_name), os.path.join(
        root, so_name + ".src")
    digest = _source_digest(root, so_name)
    built = None
    if os.path.exists(so_path) and os.path.exists(stamp):
        with open(stamp) as f:
            built = f.read().strip()
    if built != digest:
        try:
            subprocess.run(["make", "-B", "-C", root, so_name],
                           check=True, capture_output=True, text=True)
        except FileNotFoundError as e:
            raise RuntimeError(
                f"building {so_name} needs `make` and a C++ compiler: {e}"
            ) from e
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"building {so_name} failed:\n{e.stderr}") from e
        with open(stamp, "w") as f:
            f.write(digest)
    lib = ctypes.CDLL(so_path)
    _CACHE[so_name] = lib
    return lib
