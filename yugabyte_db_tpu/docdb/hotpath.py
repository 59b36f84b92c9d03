"""Loader for the ybtpu_hot CPython extension (native/ybtpu_hot.c).

Auto-builds with g++ + the CPython headers on first import when the .so
is missing. Every caller has a pure-Python fallback, so environments
without a toolchain still work (same policy as storage/native_lib.py).
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sysconfig
from typing import Optional

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_SRC = os.path.join(_NATIVE_DIR, "ybtpu_hot.c")
# host-fingerprinted: a .so built on another machine must never load
# (repo snapshots travel across hosts; see hostfp.py)
from ..hostfp import host_fingerprint as _host_fp  # noqa: E402


def _src_tag() -> str:
    """Short hash of the C source: whatever loads was built from the
    file git would commit (a copied tree keeps no mtime order, so file
    times cannot say whether a .so is stale)."""
    import hashlib
    try:
        with open(_SRC, "rb") as f:
            return hashlib.sha1(f.read()).hexdigest()[:8]
    except OSError:
        return "nosrc"


_SO = os.path.join(_NATIVE_DIR, f"ybtpu_hot.{_host_fp()}.{_src_tag()}.so")

_MOD = None
_TRIED = False


last_build_error: Optional[str] = None


def _build() -> bool:
    global last_build_error
    if not os.path.exists(_SRC):
        last_build_error = f"source missing: {_SRC}"
        return False
    inc = sysconfig.get_paths()["include"]
    try:
        # -march=native is safe: the output path is host-fingerprinted,
        # so this .so can never load on a different CPU.  The compiler
        # writes to a name of this process's own and the rename publishes
        # it whole: a process that finds `_SO` never loads half of it
        tmp = f"{_SO}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O3", "-march=native", "-shared", "-fPIC",
                 f"-I{inc}", _SRC, "-o", tmp],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, _SO)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return True
    except subprocess.CalledProcessError as e:
        last_build_error = (e.stderr or b"")[-2000:].decode(
            "utf-8", "replace")
        return False
    except Exception as e:  # noqa: BLE001 — import-time must not raise
        last_build_error = repr(e)
        return False


def load():
    """The extension module, or None when unavailable."""
    global _MOD, _TRIED
    if _TRIED:
        return _MOD
    _TRIED = True
    try:
        if not os.path.exists(_SO) and not _build():
            return None
        spec = importlib.util.spec_from_file_location("ybtpu_hot", _SO)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MOD = mod
    except Exception:
        # missing source next to a shipped .so, unreadable paths, ...:
        # the pure-Python fallback must always remain available
        _MOD = None
    return _MOD


def available() -> bool:
    return load() is not None
