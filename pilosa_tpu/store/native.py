"""ctypes loader for the native roaring codec (native/roaring_codec.cpp).

The native slot of SURVEY.md §3.4: fragment blob parse/serialize and
dense-plane expansion in C++ at memory bandwidth.  Byte-compatible with
the pure-Python codec in :mod:`pilosa_tpu.store.roaring`, which serves
when the library is not built (``PILOSA_NO_NATIVE=1`` forces it).

Build: ``make -C native`` → ``native/libroaring_codec.so``.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

_LIB_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native",
    "libroaring_codec.so")

_ERRORS = {-1: "truncated buffer", -2: "bad magic/version",
           -3: "bad container type", -4: "output buffer too small",
           -5: "positions not sorted/unique"}


def _load():
    """The codec library, or None when it is not built (the server's
    boot log says which codec serves).  A library that IS there but
    cannot be used — built for another machine, or older than this
    module's symbol list — raises: serving on the Python codec beside
    a stale ``.so`` is a slowdown nobody asked for."""
    if os.environ.get("PILOSA_NO_NATIVE"):
        return None
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        return _bind(ctypes.CDLL(_LIB_PATH))
    except (OSError, AttributeError) as e:
        raise ImportError(
            f"{_LIB_PATH} is present but unusable ({e}); rebuild it "
            "with `make -C native`, or delete it / set "
            "PILOSA_NO_NATIVE=1 to serve on the Python codec") from e


def _bind(lib):
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.rc_cardinality.restype = ctypes.c_int64
    lib.rc_cardinality.argtypes = [u8p, ctypes.c_size_t]
    lib.rc_deserialize.restype = ctypes.c_int64
    lib.rc_deserialize.argtypes = [u8p, ctypes.c_size_t, u64p,
                                   ctypes.c_size_t]
    lib.rc_serialize.restype = ctypes.c_int64
    lib.rc_serialize.argtypes = [u64p, ctypes.c_size_t, u8p,
                                 ctypes.c_size_t]
    lib.rc_serialized_bound.restype = ctypes.c_int64
    lib.rc_serialized_bound.argtypes = [u64p, ctypes.c_size_t]
    lib.rc_expand_plane.restype = ctypes.c_int64
    lib.rc_expand_plane.argtypes = [u8p, ctypes.c_size_t, ctypes.c_uint64,
                                    u64p, ctypes.c_size_t, u32p,
                                    ctypes.c_size_t]
    lib.rc_expand_rows_into.restype = ctypes.c_int64
    lib.rc_expand_rows_into.argtypes = [u8p, ctypes.c_size_t,
                                        ctypes.c_uint64, u64p, u64p,
                                        ctypes.c_size_t, u32p,
                                        ctypes.c_size_t, ctypes.c_size_t]
    # void* so callers can pass bare addresses (see _u32p)
    lib.rc_union_u32.restype = ctypes.c_int64
    lib.rc_union_u32.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                 ctypes.c_void_p, ctypes.c_size_t,
                                 ctypes.c_void_p]
    lib.rc_diff_u32.restype = ctypes.c_int64
    lib.rc_diff_u32.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                ctypes.c_void_p, ctypes.c_size_t,
                                ctypes.c_void_p]
    return lib


_lib = _load()


def available() -> bool:
    return _lib is not None


def _check(rc: int, what: str) -> int:
    if rc < 0:
        raise ValueError(
            f"native codec {what}: {_ERRORS.get(rc, f'error {rc}')}")
    return rc


def _u8(buf) -> ctypes.POINTER(ctypes.c_uint8):
    arr = np.frombuffer(buf, dtype=np.uint8)
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), arr


def deserialize(buf: bytes) -> np.ndarray:
    ptr, keep = _u8(buf)
    card = _check(_lib.rc_cardinality(ptr, len(buf)), "cardinality")
    out = np.empty(card, dtype=np.uint64)
    got = _check(_lib.rc_deserialize(
        ptr, len(buf), out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        card), "deserialize")
    return out[:got]


def serialize(positions: np.ndarray) -> bytes:
    positions = np.ascontiguousarray(positions, dtype=np.uint64)
    p64 = positions.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
    bound = _check(_lib.rc_serialized_bound(p64, len(positions)), "bound")
    out = np.empty(bound, dtype=np.uint8)
    n = _check(_lib.rc_serialize(
        p64, len(positions),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), bound),
        "serialize")
    return out[:n].tobytes()


def expand_plane(buf: bytes, row_width: int, row_slots: np.ndarray,
                 plane: np.ndarray) -> int:
    """Expand a fragment blob directly into a zeroed dense plane
    ``uint32[n_rows, words_per_row]``; ``row_slots`` = sorted row ids of
    the plane's rows.  Returns bits set."""
    ptr, keep = _u8(buf)
    row_slots = np.ascontiguousarray(row_slots, dtype=np.uint64)
    if plane.dtype != np.uint32 or not plane.flags.c_contiguous:
        raise ValueError("plane must be C-contiguous uint32")
    return _check(_lib.rc_expand_plane(
        ptr, len(buf), row_width,
        row_slots.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        len(row_slots),
        plane.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        plane.shape[-1]), "expand_plane")


def expand_rows_into(buf, row_width: int, row_ids: np.ndarray,
                     slots: np.ndarray, plane: np.ndarray) -> int:
    """Expand a fragment blob's rows straight into caller-chosen slots
    of ``plane`` (uint32[n_rows, words_per_row]): row ``row_ids[i]``
    (sorted ascending) ORs into ``plane[slots[i]]``; rows absent from
    ``row_ids`` are skipped.  Unlike :func:`expand_plane` the slots are
    arbitrary, so the parallel plane build writes each fragment's rows
    directly into their final chunk position — no tmp slab + reorder
    copy.  The C call releases the GIL, so per-fragment expansions
    genuinely overlap across builder threads.  Returns bits set."""
    ptr, keep = _u8(buf)
    row_ids = np.ascontiguousarray(row_ids, dtype=np.uint64)
    slots = np.ascontiguousarray(slots, dtype=np.uint64)
    if len(row_ids) != len(slots):
        raise ValueError("expand_rows_into: row_ids/slots length mismatch")
    if plane.dtype != np.uint32 or not plane.flags.c_contiguous:
        raise ValueError("plane must be C-contiguous uint32")
    if plane.ndim != 2:
        raise ValueError("plane must be 2-D [n_rows, words_per_row]")
    return _check(_lib.rc_expand_rows_into(
        ptr, len(buf), row_width,
        row_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        slots.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        len(row_ids),
        plane.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        plane.shape[-1], plane.shape[0]), "expand_rows_into")


def _u32p(arr):
    # bare address (ctypes accepts ints for pointer args): data_as +
    # POINTER cast measured ~4 us/call — material on the bulk-import
    # path, which unions thousands of tiny per-row chunks per batch
    return arr.__array_interface__["data"][0]


_U32 = np.dtype(np.uint32)


def _as_u32c(a: np.ndarray) -> np.ndarray:
    # fast-path the common case (already uint32 C-contiguous): a full
    # ascontiguousarray costs ~2 us/call on the tiny per-row chunks the
    # bulk-import path feeds through here
    if a.dtype is _U32 and a.flags.c_contiguous:
        return a
    return np.ascontiguousarray(a, dtype=np.uint32)


def union_sorted_u32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Linear merge-union of two sorted-unique uint32 arrays."""
    a = _as_u32c(a)
    b = _as_u32c(b)
    out = np.empty(len(a) + len(b), dtype=np.uint32)
    k = _lib.rc_union_u32(_u32p(a), len(a), _u32p(b), len(b), _u32p(out))
    # exact-size copy: callers hold the result long-term and a view
    # would pin the oversized merge buffer
    return out[:k].copy()


def diff_sorted_u32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Linear a-minus-b of sorted-unique uint32 arrays."""
    a = _as_u32c(a)
    b = _as_u32c(b)
    out = np.empty(len(a), dtype=np.uint32)
    k = _lib.rc_diff_u32(_u32p(a), len(a), _u32p(b), len(b), _u32p(out))
    return out[:k].copy()
