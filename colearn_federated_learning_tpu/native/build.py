"""Build the native library with the system toolchain, cached by content.

``python -m colearn_federated_learning_tpu.native.build`` forces a build;
normally ``native.load()`` triggers it lazily on first use and callers fall
back to numpy when no toolchain is available.
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import shutil
import subprocess
import sys

_ROOT = pathlib.Path(__file__).resolve().parent
SOURCES = [_ROOT / "src" / "gather.cpp", _ROOT / "src" / "topk.cpp",
           _ROOT / "src" / "fold.cpp"]
ABI_VERSION = 3  # v3: + cl_fold_sparse_i8 / cl_fold_sparse_f32
# -ffp-contract=off: the fold kernel's (value * scale) * weight pair
# must round twice, exactly like the host oracle's two numpy
# multiplies — a contracted FMA would change bits and break the
# device-vs-host parity pins.
FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
         "-ffp-contract=off"]


def lib_path() -> pathlib.Path:
    """Where the library built from the sources AS THEY ARE lives.  The
    name carries the ABI version and a digest of the sources and flags:
    ``_build/`` is ignored by git and travels with copies of the tree, so
    a binary found there says nothing by its age — only a name derived
    from the sources' content proves it was built from them.  (A rebuild
    after a runtime mismatch also needs a fresh path: re-dlopening the
    same path returns the stale handle the process already holds.)"""
    h = hashlib.sha256(" ".join(FLAGS).encode())
    for src in SOURCES:
        h.update(src.name.encode())
        h.update(src.read_bytes())
    return (_ROOT / "_build"
            / f"libcolearn_native_v{ABI_VERSION}_{h.hexdigest()[:12]}.so")


def build(verbose: bool = False) -> pathlib.Path:
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        raise RuntimeError("no C++ compiler found")
    lib = lib_path()
    lib.parent.mkdir(parents=True, exist_ok=True)
    for stale in lib.parent.glob("*.so"):
        if stale.name != lib.name:     # built from other sources
            try:
                stale.unlink()
            except OSError:
                pass
    # Link under a private name and rename: processes starting together
    # (a federation's workers) never dlopen a half-written file.
    tmp = lib.with_name(f"{lib.name}.{os.getpid()}.tmp")
    cmd = [cxx, *FLAGS, *map(str, SOURCES), "-o", str(tmp)]
    if verbose:
        print(" ".join(cmd), file=sys.stderr)
    try:
        subprocess.run(cmd, check=True, capture_output=not verbose)
        os.replace(tmp, lib)
    finally:
        tmp.unlink(missing_ok=True)
    return lib


if __name__ == "__main__":
    print(build(verbose=True))
