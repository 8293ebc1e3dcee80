"""Native host-runtime bindings (ctypes over ``csrc/apex_tpu_native.cpp``).

The reference builds ~20 pybind11 extensions via setup.py flags
(``setup.py:53-522``); here the single host-side shared library is built
lazily with g++ on first use, on the machine that loads it, under
``csrc/build/``. The output name carries a hash of the source and the
compiler flags, so a binary is reused only if it was built from exactly
this source with exactly these flags, and the flags name no host ISA
(no ``-march=native``): a build directory that travels with a copied
tree cannot hand this host code it cannot execute.

Everything has a pure-python fallback, mirroring apex's "Python-only
build" (reference ``README.md:130-139``): ``lib()`` returns None on a
machine with no compiler and callers take the numpy path. A build that
was attempted and failed is different — it warns with the compiler's
stderr before falling back.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings

_lock = threading.Lock()
_lib = None
_tried = False

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc", "apex_tpu_native.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(_SRC), "build")
_FLAGS = ("-O3", "-funroll-loops", "-std=c++17", "-shared", "-fPIC",
          "-pthread")


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(_FLAGS).encode())
    return os.path.join(
        _BUILD_DIR, f"libapex_tpu_native-{digest.hexdigest()[:16]}.so")


def _build() -> str | None:
    so = _so_path()
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # per-process tmp name: concurrent builders (pytest-xdist, multi-host
    # on a shared FS) each write their own file; os.replace stays atomic
    # and last-writer-wins with a complete .so
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *_FLAGS, _SRC, "-o", tmp], check=True,
                       capture_output=True, text=True, timeout=300)
    except FileNotFoundError:
        return None                     # no compiler here: numpy path
    except subprocess.CalledProcessError as e:
        warnings.warn(f"apex_tpu native build failed (numpy fallback in "
                      f"use): g++ exited {e.returncode}:\n{e.stderr}",
                      RuntimeWarning, stacklevel=3)
        return None
    except (OSError, subprocess.TimeoutExpired) as e:
        warnings.warn(f"apex_tpu native build failed (numpy fallback in "
                      f"use): {e!r}", RuntimeWarning, stacklevel=3)
        return None
    os.replace(tmp, so)
    return so


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    i8p, u8p = c.POINTER(c.c_int64), c.POINTER(c.c_uint8)
    f32p, u16p = c.POINTER(c.c_float), c.POINTER(c.c_uint16)
    vp = c.c_void_p

    lib.atp_version.restype = c.c_int
    lib.atp_flatten.argtypes = [c.POINTER(vp), i8p, c.c_int64, u8p, c.c_int]
    lib.atp_unflatten.argtypes = [u8p, i8p, c.c_int64, c.POINTER(vp), c.c_int]
    lib.atp_f32_to_bf16.argtypes = [f32p, u16p, c.c_int64, c.c_int]
    lib.atp_transform_batch_args.argtypes = [
        u8p, i8p, c.c_int64, c.c_int64, c.c_int64, c.c_int64, c.c_int64,
        c.c_int64, f32p, f32p, c.c_int, c.c_int, vp, c.c_uint64, c.c_int]
    lib.atp_loader_create.restype = vp
    lib.atp_loader_create.argtypes = [
        u8p, c.c_int64, c.c_int64, c.c_int64, c.c_int64, c.c_int64,
        f32p, f32p, c.c_int, c.c_int, c.c_int64, c.c_int, c.c_int, c.c_int]
    lib.atp_loader_submit.argtypes = [vp, i8p, c.c_int64, c.c_uint64]
    lib.atp_loader_next.restype = c.c_int64
    lib.atp_loader_next.argtypes = [vp, u8p]
    lib.atp_loader_destroy.argtypes = [vp]
    return lib


def lib() -> ctypes.CDLL | None:
    """The loaded native library, or None if it can't be built here
    (no compiler: silently; failed build or load: after a warning)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            _tried = True
            so = _build()
            if so is not None:
                try:
                    _lib = _bind(ctypes.CDLL(so))
                except OSError as e:
                    warnings.warn(f"apex_tpu native library {so} did not "
                                  f"load (numpy fallback in use): {e}",
                                  RuntimeWarning, stacklevel=2)
    return _lib


def available() -> bool:
    return lib() is not None
