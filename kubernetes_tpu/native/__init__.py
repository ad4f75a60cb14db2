"""Native (C) host-plane kernels, built on first import.

The reference's runtime is compiled Go; this package gives the framework's
host plane the same native tier where it does byte-level work — the FNV-1a
hashing kernel behind universe interning (utils/hashing.py) and the
ledger scatter-add behind batch commit (state/statedb.py commit_batch).

Build strategy: compile each .c with the system C compiler into the
package's `_build/` directory the first time it is imported (a few ms,
cached thereafter) and bind it with ctypes — the image ships g++/cc but
not pybind11. Each library's file name carries a hash of its source and
compiler command, so a `.so` left over from other sources (a copied
working tree keeps ignored directories) is never loaded. Any failure (no
compiler, read-only filesystem) degrades to the pure-Python/numpy
implementations; callers check the function for None (`active()` lists
what loaded).
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import tempfile

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))  # sources; _build/ below

fnv1a64 = None          # (bytes) -> int, or None when unavailable
lanes_batch = None      # (list[bytes]) -> (np.uint32[n], np.uint32[n])
scatter_add_cols = None  # (dst2d, src2d, off, rows_i64, width) -> touched
bulk_bind = None        # (bucket, bindings, rv_base, WatchEvent, NotFound,
#                          Conflict) -> (bound, errors, events, rv_end)


def _build_lib(src_name: str, stem: str | None = None,
               extra_flags: tuple[str, ...] = (),
               loader=ctypes.CDLL) -> ctypes.CDLL | None:
    """Compile `src_name` (beside this file) into _build/ unless a build of
    the same source and command exists, and load it. Build via a temp file
    + rename so concurrent importers can race.
    `stem` names the output .so (one source can build several variants,
    e.g. commitops with/without the CPython API); `loader` picks the ctypes
    binding class (PyDLL for functions that call the Python C-API and must
    hold the GIL). Returns None on any failure (callers degrade to pure
    Python)."""
    src = os.path.join(_HERE, src_name)
    build_dir = os.path.join(_HERE, "_build")
    if stem is None:
        stem = os.path.splitext(src_name)[0]
    cmd = ["cc", "-O2", "-shared", "-fPIC", *extra_flags]
    try:
        with open(src, "rb") as f:
            key = hashlib.sha256(
                f.read() + "\0".join(cmd).encode()).hexdigest()[:16]
        lib_path = os.path.join(build_dir, f"lib{stem}-{key}.so")
        if not os.path.exists(lib_path):
            os.makedirs(build_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=build_dir, suffix=".so")
            os.close(fd)
            subprocess.run([*cmd, "-o", tmp, src],
                           check=True, capture_output=True, timeout=60)
            os.replace(tmp, lib_path)
            for stale in glob.glob(os.path.join(build_dir,
                                                f"lib{stem}-*.so")):
                if stale != lib_path:
                    # a concurrent importer may have removed it first
                    with contextlib.suppress(FileNotFoundError):
                        os.remove(stale)
        return loader(lib_path)
    except (OSError, subprocess.SubprocessError) as e:
        log.debug("native %s unavailable (%s); using pure Python",
                  src_name, e)
        return None


def _bind_fnv():
    global fnv1a64, lanes_batch

    lib = _build_lib("fnv.c")
    if lib is None:
        return
    try:
        # symbol binding stays inside the guard: a stale .so missing a
        # symbol must degrade to pure Python, not crash the import
        lib.fnv1a64.restype = ctypes.c_uint64
        lib.fnv1a64.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.fnv1a64_lanes_batch.restype = None
        lib.fnv1a64_lanes_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32)]
    except AttributeError as e:
        log.debug("native fnv symbols unavailable (%s)", e)
        return

    def _fnv1a64(data: bytes) -> int:
        return lib.fnv1a64(data, len(data))

    def _lanes_batch(items: list[bytes]):
        import numpy as np

        n = len(items)
        blob = b"".join(items)
        offsets = (ctypes.c_size_t * (n + 1))()
        pos = 0
        for i, item in enumerate(items):
            offsets[i] = pos
            pos += len(item)
        offsets[n] = pos
        lo = np.empty(n, np.uint32)
        hi = np.empty(n, np.uint32)
        lib.fnv1a64_lanes_batch(
            blob, offsets, n,
            lo.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            hi.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
        return lo, hi

    fnv1a64 = _fnv1a64
    lanes_batch = _lanes_batch


def _bind_commitops():
    global scatter_add_cols

    lib = _build_lib("commitops.c")
    if lib is None:
        return
    try:
        lib.scatter_add_cols.restype = ctypes.c_uint64
        lib.scatter_add_cols.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_float), ctypes.c_size_t, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_size_t, ctypes.c_size_t]
    except AttributeError as e:
        log.debug("native commitops symbols unavailable (%s)", e)
        return

    c_float_p = ctypes.POINTER(ctypes.c_float)
    c_int64_p = ctypes.POINTER(ctypes.c_int64)

    def _scatter_add_cols(dst, src, off: int, rows, width: int) -> int:
        """dst[rows[k], :width] += src[k, off:off+width] for every k.

        dst: C-contiguous float32 (N, W>=width); src: C-contiguous float32
        (n, F); rows: int64 (n,). Returns how many k had a nonzero source
        slice."""
        return lib.scatter_add_cols(
            dst.ctypes.data_as(c_float_p), dst.strides[0] // 4,
            src.ctypes.data_as(c_float_p), src.strides[0] // 4, off,
            rows.ctypes.data_as(c_int64_p), len(rows), width)

    scatter_add_cols = _scatter_add_cols


def _bind_bindops():
    """Bulk native bind: commitops.c rebuilt with the CPython API enabled
    (`-DKTPU_HAVE_PYTHON`), bound through PyDLL so the GIL stays held while
    the C pass walks Python objects. Needs the interpreter headers; a
    machine without them (or without cc) just keeps the pure-Python
    bind_many path."""
    global bulk_bind

    import sysconfig

    inc = sysconfig.get_paths().get("include")
    if not inc or not os.path.exists(os.path.join(inc, "Python.h")):
        log.debug("native bulk bind unavailable (no Python.h); "
                  "using pure Python")
        return
    lib = _build_lib("commitops.c", stem="bindops",
                     extra_flags=("-DKTPU_HAVE_PYTHON", f"-I{inc}"),
                     loader=ctypes.PyDLL)
    if lib is None:
        return
    try:
        lib.ktpu_bulk_bind.restype = ctypes.py_object
        lib.ktpu_bulk_bind.argtypes = [
            ctypes.py_object, ctypes.py_object, ctypes.c_ssize_t,
            ctypes.py_object, ctypes.py_object, ctypes.py_object]
    except AttributeError as e:
        log.debug("native bulk bind symbols unavailable (%s)", e)
        return

    bulk_bind = lib.ktpu_bulk_bind


def active() -> dict[str, bool]:
    """Which native kernels loaded (False: the pure-Python fallback)."""
    return {"fnv": fnv1a64 is not None,
            "commitops": scatter_add_cols is not None,
            "bulk_bind": bulk_bind is not None}


_bind_fnv()
_bind_commitops()
_bind_bindops()
