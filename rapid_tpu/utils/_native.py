"""ctypes bridge to the native host-runtime library (native/rapid_native.cpp).

Loads the library built from the source on disk if present (``ensure_built``
builds it when a toolchain is available), exposing batch ring-key construction and the
configuration-id fold. Every entry point has a pure-Python fallback producing
bit-identical values; ``RAPID_TPU_NO_NATIVE=1`` disables the native path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

LOG = logging.getLogger(__name__)

_REPO_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"

_lib: Optional[ctypes.CDLL] = None
_attempted = False


def _lib_path() -> Optional[Path]:
    """Where the library built from the source on disk lives, or None when
    there is no source. The name carries a hash of ``rapid_native.cpp`` (the
    Makefile computes the same one): ``native/build/`` is git-ignored, so a
    copied tree can hold a binary no commit describes, and keying the name to
    the source means such a binary is simply never the one loaded."""
    try:
        source = (_REPO_NATIVE_DIR / "rapid_native.cpp").read_bytes()
    except OSError:
        return None
    digest = hashlib.sha256(source).hexdigest()[:16]
    return _REPO_NATIVE_DIR / "build" / f"librapid_native-{digest}.so"


def ensure_built() -> bool:
    """Bring the native library up to date with ``native/rapid_native.cpp``.

    Always runs ``make``, which is a no-op when the library for this source
    exists and otherwise builds it and clears out any other (see
    ``_lib_path``). Call from setup paths (bench, test session start,
    packaging) — never from the event loop: the compile can take tens of
    seconds and would stall the protocol."""
    global _attempted
    if os.environ.get("RAPID_TPU_NO_NATIVE"):
        return False
    lib_path = _lib_path()
    if lib_path is None or not (_REPO_NATIVE_DIR / "Makefile").exists():
        return False
    _attempted = False  # allow get_lib to pick up a fresh build
    try:
        subprocess.run(
            ["make", "-C", str(_REPO_NATIVE_DIR)],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.SubprocessError) as exc:
        # No toolchain (or a failed compile) means the Python twin runs.
        LOG.warning("native build failed, using the Python twin: %r", exc)
        return False
    return lib_path.exists()


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (Python fallback). Load-only:
    runtime code paths never compile (see ensure_built)."""
    global _lib, _attempted
    if _attempted:
        return _lib
    _attempted = True
    if os.environ.get("RAPID_TPU_NO_NATIVE"):
        return None
    lib_path = _lib_path()
    if lib_path is None or not lib_path.exists():
        return None
    try:
        lib = ctypes.CDLL(str(lib_path))
        lib.rapid_xxh64.restype = ctypes.c_uint64
        lib.rapid_xxh64.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64]
        lib.rapid_ring_key.restype = ctypes.c_uint64
        lib.rapid_ring_key.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_int32,
            ctypes.c_uint64,
        ]
        lib.rapid_ring_keys_batch.restype = None
        lib.rapid_ring_keys_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.rapid_configuration_id.restype = ctypes.c_uint64
        lib.rapid_configuration_id.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_uint64,
        ]
        _lib = lib
    except OSError as exc:  # pragma: no cover
        LOG.debug("native load failed: %r", exc)
        _lib = None
    return _lib


def native_xxh64(data: bytes, seed: int) -> Optional[int]:
    lib = get_lib()
    if lib is None:
        return None
    return int(lib.rapid_xxh64(data, len(data), ctypes.c_uint64(seed)))


def _pack_hostnames(hostnames: Sequence[bytes]):
    offsets = np.zeros(len(hostnames) + 1, dtype=np.uint64)
    for i, h in enumerate(hostnames):
        offsets[i + 1] = offsets[i] + len(h)
    blob = b"".join(hostnames)
    return blob, offsets


def native_ring_keys_batch(
    hostnames: Sequence[bytes], ports: Sequence[int], k: int
) -> Optional[np.ndarray]:
    """[k, n] uint64 ring keys, or None if the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(hostnames)
    blob, offsets = _pack_hostnames(hostnames)
    ports_arr = np.asarray(ports, dtype=np.int32)
    out = np.empty((k, n), dtype=np.uint64)
    lib.rapid_ring_keys_batch(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        ports_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_uint64(n),
        ctypes.c_uint32(k),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out


def native_configuration_id(
    id_highs: Sequence[int],
    id_lows: Sequence[int],
    hostnames: Sequence[bytes],
    ports: Sequence[int],
) -> Optional[int]:
    lib = get_lib()
    if lib is None:
        return None
    highs = np.asarray(id_highs, dtype=np.uint64)
    lows = np.asarray(id_lows, dtype=np.uint64)
    blob, offsets = _pack_hostnames(hostnames)
    ports_arr = np.asarray(ports, dtype=np.int32)
    return int(
        lib.rapid_configuration_id(
            highs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            lows.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            ctypes.c_uint64(len(highs)),
            blob,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            ports_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_uint64(len(hostnames)),
        )
    )
