"""ctypes bindings for the native C++ data-plane core.

Loads ``libseldon_tpu_native.so`` (built by ``make native``; also
auto-built on first import when a toolchain is present) and exposes the
codec hot loops.  Every function has a pure-Python fallback, so the
framework runs unchanged without the library — native just makes the
1-CPU REST path faster.
"""

from __future__ import annotations

import base64 as _pyb64
import ctypes
import json as _pyjson
import logging
import os
import subprocess
from typing import List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _so_path() -> str:
    # SELDON_TPU_NATIVE_SO overrides the artifact (e.g. the TSan/ASan
    # builds from `make -C native tsan`)
    from seldon_core_tpu.runtime import knobs

    override = knobs.raw("SELDON_TPU_NATIVE_SO")
    if override:
        return override
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                        "native", "libseldon_tpu_native.so")


# the shared library's inputs (keep in sync with SRCS in native/Makefile;
# other .cc files there — e.g. remote_node.cc — build separate binaries)
_LIB_SOURCES = ("codec.cc", "frontserver.cc", "h2grpc.cc", "h2grpc.h",
                "loadgen.cc", "Makefile")


def _is_stale(so: str) -> bool:
    """True when the .so is missing or older than one of its sources —
    a stale artifact would load with a mismatched struct ABI."""
    if not os.path.exists(so):
        return True
    so_mtime = os.path.getmtime(so)
    src_dir = os.path.dirname(so)
    for name in _LIB_SOURCES:
        path = os.path.join(src_dir, name)
        if os.path.exists(path) and os.path.getmtime(path) > so_mtime:
            return True
    return False


def _build_if_stale(so: str) -> None:
    """Must be called with the build lock held."""
    if not _is_stale(so):
        return
    makefile_dir = os.path.dirname(so)
    if not os.path.exists(os.path.join(makefile_dir, "Makefile")):
        return
    try:
        subprocess.run(
            ["make", "-C", makefile_dir], check=True, capture_output=True, timeout=120
        )
    except FileNotFoundError:
        logger.info("no `make` on this host: native core not built, python lanes serve")
    except subprocess.CalledProcessError as e:
        # a toolchain is present and the sources did not build: the
        # python lanes still serve, but never silently
        logger.warning(
            "native build failed (rc=%d), python lanes serve: %s",
            e.returncode, (e.stderr or b"")[-600:].decode(errors="replace"),
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build failed, python lanes serve: %s", e)


class _BuildLock:
    """flock serializing build AND load: many microservice processes can
    start at once (ReplicaSet scale-up); an unlocked staleness fast-path
    could see a half-linked .so with a fresh mtime and dlopen garbage,
    so dlopen also happens under the lock."""

    def __init__(self, so: str):
        self._dir = os.path.dirname(so)
        self._fh = None

    def __enter__(self):
        try:
            import fcntl

            self._fh = open(os.path.join(self._dir, ".build.lock"), "w")
            fcntl.flock(self._fh, fcntl.LOCK_EX)
        except Exception as e:  # noqa: BLE001 — e.g. read-only install dir
            logger.debug("native build lock unavailable: %s", e)
            self._fh = None
        return self

    def __exit__(self, *exc):
        if self._fh is not None:
            self._fh.close()  # releases the flock
            self._fh = None
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    so = _so_path()
    with _BuildLock(so):
        _LIB = _load(so)
    return _LIB


def _load(so: str) -> Optional[ctypes.CDLL]:
    _build_if_stale(so)
    if not os.path.exists(so):
        return None
    try:
        lib = ctypes.CDLL(so)
        lib.b64_encoded_len.restype = ctypes.c_int64
        lib.b64_encoded_len.argtypes = [ctypes.c_int64]
        lib.b64_encode.restype = ctypes.c_int64
        lib.b64_encode.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p]
        lib.b64_decode.restype = ctypes.c_int64
        lib.b64_decode.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p]
        lib.json_parse_f64.restype = ctypes.c_int64
        lib.json_parse_f64.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
        ]
        lib.json_serialize_f64.restype = ctypes.c_int64
        lib.json_serialize_f64.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_char_p,
        ]
        lib.batch_gather_pad.restype = None
        lib.batch_gather_pad.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p,
        ]
        # v3 added the srt1_* framing-agreement surface (zero-copy
        # lane); v4 the CRC32C integrity-trailer twins
        if lib.native_abi_version() != 4:  # not assert: must survive python -O
            raise RuntimeError(
                "stale libseldon_tpu_native.so (ABI mismatch): rebuild with `make -C native`"
            )
        lib.srt1_item_size.restype = ctypes.c_int64
        lib.srt1_item_size.argtypes = [ctypes.c_int32]
        lib.srt1_header_bytes.restype = ctypes.c_int64
        lib.srt1_header_bytes.argtypes = [ctypes.c_int32]
        lib.srt1_magic.restype = ctypes.c_uint32
        lib.srt1_payload_bytes.restype = ctypes.c_int64
        lib.srt1_payload_bytes.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ]
        lib.srt1_crc_magic.restype = ctypes.c_uint32
        lib.srt1_crc32c.restype = ctypes.c_uint32
        # c_char_p: python bytes pass by POINTER (no staging copy) —
        # the checksum runs twice per multi-MB KV container during
        # evacuation, exactly when time and memory are tightest
        lib.srt1_crc32c.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32,
        ]
        logger.info("native data-plane core loaded from %s", so)
        return lib
    except Exception as e:  # noqa: BLE001 — missing native core degrades
        # to the python lane, never kills serving
        logger.warning("failed to load native core: %s", e)
        return None


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# base64
# ---------------------------------------------------------------------------

def b64encode(data: bytes) -> str:
    lib = get_lib()
    if lib is None:
        return _pyb64.b64encode(data).decode("ascii")
    out = ctypes.create_string_buffer(int(lib.b64_encoded_len(len(data))))
    n = lib.b64_encode(data, len(data), out)
    return out.raw[:n].decode("ascii")


def b64decode(text: str) -> bytes:
    lib = get_lib()
    if lib is None:
        return _pyb64.b64decode(text)
    raw = text.encode("ascii")
    out = ctypes.create_string_buffer(len(raw))
    n = lib.b64_decode(raw, len(raw), out)
    if n < 0:
        raise ValueError("malformed base64")
    return out.raw[:n]


# ---------------------------------------------------------------------------
# JSON number arrays
# ---------------------------------------------------------------------------

def parse_f64_array(text: str) -> np.ndarray:
    """Flat parse of a (possibly nested) JSON number array."""
    lib = get_lib()
    if lib is None:
        return np.asarray(_pyjson.loads(text), dtype=np.float64).ravel()
    raw = text.encode("ascii")
    cap = max(1, raw.count(b",") + raw.count(b"[") + 2)
    out = np.empty(cap, dtype=np.float64)
    n = lib.json_parse_f64(raw, len(raw),
                           out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), cap)
    if n < 0:
        raise ValueError("malformed JSON number array")
    return out[:n].copy()


def serialize_f64_array(arr: np.ndarray) -> str:
    """Flat JSON serialisation of a float64 array."""
    lib = get_lib()
    flat = np.ascontiguousarray(arr, dtype=np.float64).ravel()
    if lib is None:
        return _pyjson.dumps(flat.tolist())
    out = ctypes.create_string_buffer(int(flat.size) * 26 + 2)
    n = lib.json_serialize_f64(flat.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                               flat.size, out)
    return out.raw[:n].decode("ascii")


# ---------------------------------------------------------------------------
# batch assembly
# ---------------------------------------------------------------------------

def gather_pad(arrays: Sequence[np.ndarray], bucket_rows: int) -> np.ndarray:
    """Concatenate row batches and zero-pad to `bucket_rows` in one pass."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    first = arrays[0]
    row_shape = first.shape[1:]
    dtype = first.dtype
    lib = get_lib()
    if lib is None:
        total = sum(a.shape[0] for a in arrays)
        batch = np.concatenate(arrays, axis=0) if len(arrays) > 1 else first
        if total < bucket_rows:
            pad = [(0, bucket_rows - total)] + [(0, 0)] * (batch.ndim - 1)
            batch = np.pad(batch, pad)
        return batch
    row_bytes = int(np.prod(row_shape)) * dtype.itemsize
    out = np.empty((bucket_rows, *row_shape), dtype=dtype)
    k = len(arrays)
    srcs = (ctypes.c_char_p * k)(
        *[ctypes.cast(ctypes.c_void_p(a.ctypes.data), ctypes.c_char_p) for a in arrays]
    )
    rows = (ctypes.c_int64 * k)(*[a.shape[0] for a in arrays])
    lib.batch_gather_pad(srcs, rows, k, row_bytes, bucket_rows,
                         out.ctypes.data_as(ctypes.c_char_p))
    return out
