"""Shared lazy g++ build for the csrc/ native runtime libraries.

One place for the compile-recipe (temp + atomic rename so concurrent
first-use across processes never dlopens a half-written .so) used by
io.native (data feed), distributed.store (TCPStore), and distributed.ps
(sparse tables). The reference builds its native runtime through a CMake
superbuild (/root/reference/CMakeLists.txt); here each library is one
translation unit compiled on first import.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT_DIR = os.path.join(REPO_ROOT, "build")


def build_native_so(src_name: str, so_name: str,
                    opt: str = "-O3") -> Optional[str]:
    """Compile csrc/<src_name> to build/<stem>.<hash>.so, where the hash
    is of the source text and the flags: a library is reused only when
    it was built from exactly this source (mtimes say nothing after a
    copy or a checkout). Returns the .so path or None on failure
    (callers degrade to pure-python paths)."""
    src = os.path.join(REPO_ROOT, "csrc", src_name)
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(opt.encode() + f.read()).hexdigest()
        os.makedirs(OUT_DIR, exist_ok=True)
    except OSError:  # missing csrc tree etc: degrade, don't raise
        return None
    stem, ext = os.path.splitext(so_name)
    so = os.path.join(OUT_DIR, f"{stem}.{digest[:16]}{ext}")
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", opt, "-shared", "-fPIC", "-pthread", "-std=c++17",
           src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, so)
        return so
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
