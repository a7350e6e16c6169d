"""Self-building native generator.

The reference requires a manual `make` against an externally-downloaded
toolkit (reference: nds/tpcds-gen/Makefile:14-22, checked by nds/check.py:47-66);
we instead vendor the generator source and compile it on first use, caching
the binary next to the sources.

The binary's name carries a hash of its sources (and of the machine type it
was built for): a copied tree has no meaningful mtimes, and the binary is
git-ignored, so only "was this file built from exactly these sources" can
say whether one found on disk may be run.
"""

from __future__ import annotations

import hashlib
import os
import platform
import subprocess

NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_SOURCES = ["ndsgen.cpp"]
_HEADERS = ["ndsgen.hpp", "vocab.hpp", "rowcounts.hpp", "dims.hpp", "facts.hpp", "refresh.hpp"]
_CXXFLAGS = ["-O2", "-std=c++17"]


def binary_path() -> str:
    """ndsgen-<hash of sources, flags and machine type> beside the sources."""
    h = hashlib.sha256()
    h.update(" ".join(_CXXFLAGS + [platform.machine()]).encode())
    for f in _SOURCES + _HEADERS:
        h.update(f.encode() + b"\0")
        with open(os.path.join(NATIVE_DIR, f), "rb") as src:
            h.update(src.read())
        h.update(b"\0")
    return os.path.join(NATIVE_DIR, f"ndsgen-{h.hexdigest()[:16]}")


def ensure_built() -> str:
    """Compile ndsgen unless a binary of exactly these sources exists;
    returns the binary path.

    Compiles to a process-unique temp path and os.replace()s it in, so
    concurrent builders can't truncate a binary another process is executing.
    """
    binary = binary_path()
    if not os.path.exists(binary):
        tmp = f"{binary}.build.{os.getpid()}"
        cmd = ["g++", *_CXXFLAGS, "-o", tmp] + [
            os.path.join(NATIVE_DIR, s) for s in _SOURCES
        ]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(f"ndsgen build failed:\n{proc.stderr}")
        os.replace(tmp, binary)
    return binary
