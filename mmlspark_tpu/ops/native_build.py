"""Build + load machinery for the native ops libraries.

Plays the role of the reference's ``NativeLoader``
(core/env/src/main/scala/NativeLoader.java: extract shared lib from jar
resources, ``System.load`` once per JVM): here we compile each ``.cpp`` with
the system toolchain on first use, cache the ``.so`` next to the source, and
``ctypes.CDLL`` it once per process. The ``.so`` files are build outputs
(``.gitignore``), so a fresh checkout builds from the committed ``.cpp``.
Each library degrades to pure Python when it cannot be built or loaded —
LOUDLY: one stderr line per library per process says whether it was built
or which fallback is in use and why.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from dataclasses import dataclass, field
from typing import Callable

from mmlspark_tpu.core.logging_utils import get_logger

_log = get_logger("native")

_SRC_DIR = os.path.join(os.path.dirname(__file__), "native")


def _configure_decode(lib: ctypes.CDLL) -> None:
    lib.mml_decode_image.restype = ctypes.c_int
    lib.mml_decode_image.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
    ]
    lib.mml_free.restype = None
    lib.mml_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    lib.mml_decoder_version.restype = ctypes.c_char_p


def _configure_ctf(lib: ctypes.CDLL) -> None:
    lib.mml_parse_ctf.restype = ctypes.c_int
    lib.mml_parse_ctf.argtypes = [
        ctypes.c_char_p,  # path
        ctypes.c_char_p,  # label field name
        ctypes.c_char_p,  # features field name
        ctypes.c_int,     # feature_dim (<=0: dense only)
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_long),
    ]
    lib.mml_ctf_free.restype = None
    lib.mml_ctf_free.argtypes = [ctypes.POINTER(ctypes.c_double)]
    lib.mml_ctf_version.restype = ctypes.c_char_p


@dataclass
class _NativeLib:
    src: str
    so: str
    configure: Callable[[ctypes.CDLL], None]
    link_flags: list = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)
    lib: ctypes.CDLL | None = None
    build_failed: bool = False


_LIBS: dict[str, _NativeLib] = {
    "decode": _NativeLib(
        src=os.path.join(_SRC_DIR, "decode.cpp"),
        so=os.path.join(_SRC_DIR, "libmmlimg.so"),
        configure=_configure_decode,
        link_flags=["-ljpeg", "-lpng"],
    ),
    "ctf": _NativeLib(
        src=os.path.join(_SRC_DIR, "ctf.cpp"),
        so=os.path.join(_SRC_DIR, "libmmlctf.so"),
        configure=_configure_ctf,
    ),
}


def _say(message: str) -> None:
    print(f"mmlspark_tpu native ops: {message}", file=sys.stderr, flush=True)


def _fall_back(entry: _NativeLib, why: str) -> None:
    entry.build_failed = True
    _say(f"{os.path.basename(entry.src)} unavailable ({why}); using the "
         "pure-Python fallback")


def _compile(entry: _NativeLib) -> bool:
    from mmlspark_tpu.core import config

    cmd = [
        config.get("native_cc"), "-O2", "-fPIC", "-shared", "-std=c++17",
        entry.src, "-o", entry.so, *entry.link_flags,
    ]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:  # no toolchain
        _fall_back(entry, f"no toolchain: {e}")
        return False
    if res.returncode != 0:
        _log.warning("native build failed for %s:\n%s", entry.src,
                     res.stderr[-2000:])
        _fall_back(entry, f"{cmd[0]} exited {res.returncode}")
        return False
    _say(f"built {os.path.basename(entry.so)} from "
         f"{os.path.basename(entry.src)} with {cmd[0]}")
    return True


def load_native(name: str) -> ctypes.CDLL | None:
    """Compile-if-needed and dlopen a registered native library; None if
    unavailable (callers fall back to pure Python)."""
    from mmlspark_tpu.core import config

    entry = _LIBS[name]
    with entry.lock:
        if entry.lib is not None:
            return entry.lib
        if entry.build_failed:
            return None
        if not config.get("native_build"):
            return None  # Python fallbacks by configuration
        if not os.path.exists(entry.so) or os.path.getmtime(
            entry.so
        ) < os.path.getmtime(entry.src):
            if not _compile(entry):
                return None
        try:
            lib = ctypes.CDLL(entry.so)
        except OSError as e:
            _fall_back(entry, f"load failed: {e}")
            return None
        entry.configure(lib)
        entry.lib = lib
        return entry.lib


def load_library() -> ctypes.CDLL | None:
    """The image-decode library (legacy single-lib entry point)."""
    return load_native("decode")
