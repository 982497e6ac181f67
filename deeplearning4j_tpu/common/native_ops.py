"""ctypes binding to the native runtime library (native/dl4j_tpu_native.cpp).

The reference's IO/data hot paths are native (SURVEY.md §2.9); this module
loads the C++ equivalents — IDX parsing, CSV parsing, staging-buffer pool —
and transparently builds the .so with `make` on first use if the toolchain is
available. Every caller has a pure-Python fallback, so a missing compiler
never breaks the framework (the reference's reflective-helper-with-fallback
pattern, ConvolutionLayer.java:69-76).
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libdl4j_tpu_native.so")

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


_ABI_VERSION = 7  # must match dl4j_abi_version() in dl4j_tpu_native.cpp


def build(force=False):
    """Build the library from the tracked native/dl4j_tpu_native.cpp with
    `make` (force=True rebuilds even when the .so looks fresh). Returns
    (ok, detail): the library path, or the toolchain's complaint."""
    cmd = ["make", "-C", _NATIVE_DIR] + (["-B"] if force else [])
    try:
        p = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        return False, repr(e)
    if p.returncode != 0:
        return False, (p.stderr or p.stdout).strip()[-500:]
    return True, _SO_PATH


def _try_build(force=False):
    global _build_attempted
    if _build_attempted:
        return
    _build_attempted = True
    ok, detail = build(force)
    if not ok:  # toolchain missing / build failure -> python paths, loudly
        log.warning("native build failed; every caller takes its python "
                    "path: %s", detail)


def _load_checked():
    """CDLL + ABI version check; None if missing or mismatched."""
    if not os.path.exists(_SO_PATH):
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
        lib.dl4j_abi_version.restype = ctypes.c_int64
        if lib.dl4j_abi_version() != _ABI_VERSION:
            return None
    except (OSError, AttributeError):
        return None
    return lib


def get_lib():
    """Load (rebuilding if absent or ABI-stale) the native library, or
    None. A pre-existing .so built from older sources (the .so is not
    committed) fails the version check and triggers one forced rebuild
    rather than silently disabling the native paths."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = _load_checked()
        if lib is None:
            _try_build(force=os.path.exists(_SO_PATH))
            lib = _load_checked()
        if lib is None:
            return None
        lib.dl4j_read_idx_u8.restype = ctypes.POINTER(ctypes.c_float)
        lib.dl4j_read_idx_u8.argtypes = [
            ctypes.c_char_p, ctypes.c_double,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64)]
        lib.dl4j_parse_csv.restype = ctypes.POINTER(ctypes.c_float)
        lib.dl4j_parse_csv.argtypes = [
            ctypes.c_char_p, ctypes.c_char, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
        lib.dl4j_free.argtypes = [ctypes.c_void_p]
        lib.dl4j_pool_create.restype = ctypes.c_void_p
        lib.dl4j_pool_create.argtypes = [ctypes.c_size_t]
        lib.dl4j_pool_acquire.restype = ctypes.c_void_p
        lib.dl4j_pool_acquire.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.dl4j_pool_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_size_t]
        lib.dl4j_pool_stats.restype = ctypes.c_int64
        lib.dl4j_pool_stats.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dl4j_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.dl4j_cbow_contexts.restype = ctypes.c_int64
        lib.dl4j_cbow_contexts.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        lib.dl4j_glove_cooc.restype = ctypes.c_int64
        lib.dl4j_glove_cooc.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float))]
        lib.dl4j_loader_create.restype = ctypes.c_void_p
        lib.dl4j_loader_create.argtypes = [
            ctypes.c_char_p, ctypes.c_char, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32]
        lib.dl4j_loader_next.restype = ctypes.POINTER(ctypes.c_float)
        lib.dl4j_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64)]
        lib.dl4j_loader_destroy.argtypes = [ctypes.c_void_p]
        lib.dl4j_skipgram_pairs.restype = ctypes.c_int64
        lib.dl4j_skipgram_pairs.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        lib.dl4j_bh_repulsion.restype = ctypes.c_double
        lib.dl4j_bh_repulsion.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_float)]
        lib.dl4j_bh_attraction.restype = None
        lib.dl4j_bh_attraction.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float)]
        _lib = lib
        return _lib


def available():
    return get_lib() is not None


# ---------------------------------------------------------------------------
# High-level wrappers (None on unavailability -> caller falls back)
# ---------------------------------------------------------------------------

def read_idx_u8(path, scale=1.0):
    """Parse a u8 IDX file -> float32 ndarray scaled by `scale`."""
    lib = get_lib()
    if lib is None:
        return None
    ndim = ctypes.c_int32()
    dims = (ctypes.c_int64 * 4)()
    count = ctypes.c_int64()
    ptr = lib.dl4j_read_idx_u8(str(path).encode(), float(scale),
                               ctypes.byref(ndim), dims, ctypes.byref(count))
    if not ptr:
        return None
    shape = tuple(dims[i] for i in range(ndim.value))
    n = int(np.prod(shape))
    if n != count.value:  # C-side validated count must match; never read past it
        lib.dl4j_free(ptr)
        return None
    arr = np.ctypeslib.as_array(ptr, shape=(n,)).reshape(shape).copy()
    lib.dl4j_free(ptr)
    return arr


def parse_csv(path, delimiter=",", skip_lines=0):
    """Parse a numeric CSV -> float32 [rows, cols] ndarray."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    ptr = lib.dl4j_parse_csv(str(path).encode(),
                             ctypes.c_char(delimiter.encode()),
                             int(skip_lines), ctypes.byref(rows),
                             ctypes.byref(cols))
    if not ptr:
        return None
    n = rows.value * cols.value
    if n == 0:            # empty-but-valid file sentinel
        lib.dl4j_free(ptr)
        return np.zeros((0, 0), np.float32)
    arr = np.ctypeslib.as_array(ptr, shape=(n,)).reshape(
        rows.value, cols.value).copy()
    lib.dl4j_free(ptr)
    return arr


def skipgram_pairs(ids, offsets, window, seed):
    """Corpus-level word2vec reduced-window pair generation in C++
    (the host half of the reference's native AggregateSkipGram path).

    ids: int32 concatenated tokens; offsets: int64 [n_seq+1]; returns
    (centers, outs) int32 arrays, or None when the library is missing
    (caller uses the vectorized numpy path)."""
    lib = get_lib()
    if lib is None:
        return None
    ids = np.ascontiguousarray(ids, np.int32)
    offsets = np.ascontiguousarray(offsets, np.int64)
    cap = int(ids.shape[0]) * 2 * int(window)
    centers = np.empty(cap, np.int32)
    outs = np.empty(cap, np.int32)
    n = lib.dl4j_skipgram_pairs(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        int(offsets.shape[0]) - 1, int(window), int(seed) & (2**64 - 1),
        centers.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        outs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return centers[:n], outs[:n]


def cbow_contexts(ids, offsets, window, seed):
    """Corpus-level CBOW context-row generation in C++ (sibling of
    `skipgram_pairs` for the context->center objective). Returns
    (context [rows, 2*window] int32 with -1 padding, targets [rows]
    int32), or None when the library is missing."""
    lib = get_lib()
    if lib is None:
        return None
    ids = np.ascontiguousarray(ids, np.int32)
    offsets = np.ascontiguousarray(offsets, np.int64)
    cap = int(ids.shape[0])
    context = np.empty((cap, 2 * int(window)), np.int32)
    targets = np.empty(cap, np.int32)
    n = lib.dl4j_cbow_contexts(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        int(offsets.shape[0]) - 1, int(window), int(seed) & (2**64 - 1),
        context.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        targets.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return context[:n], targets[:n]


def pack_corpus(id_lists):
    """Concatenate per-sequence id lists into (ids int32, offsets int64)
    — the corpus layout every native generator consumes."""
    ids = np.concatenate([np.asarray(s, np.int32) for s in id_lists])
    offsets = np.zeros(len(id_lists) + 1, np.int64)
    np.cumsum([len(s) for s in id_lists], out=offsets[1:])
    return ids, offsets


def glove_cooc(ids, offsets, window, symmetric):
    """Windowed 1/distance co-occurrence counting in C++ (reference
    AbstractCoOccurrences role). Returns (i, j, x) COO arrays or None when
    the library is missing."""
    lib = get_lib()
    if lib is None:
        return None
    ids = np.ascontiguousarray(ids, np.int32)
    offsets = np.ascontiguousarray(offsets, np.int64)
    pi = ctypes.POINTER(ctypes.c_int32)()
    pj = ctypes.POINTER(ctypes.c_int32)()
    px = ctypes.POINTER(ctypes.c_float)()
    n = lib.dl4j_glove_cooc(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        int(offsets.shape[0]) - 1, int(window), int(bool(symmetric)),
        ctypes.byref(pi), ctypes.byref(pj), ctypes.byref(px))
    if n < 0:
        return None
    if n == 0:
        for p in (pi, pj, px):
            lib.dl4j_free(p)
        z = np.zeros(0, np.int32)
        return z, z.copy(), np.zeros(0, np.float32)
    i = np.ctypeslib.as_array(pi, shape=(n,)).copy()
    j = np.ctypeslib.as_array(pj, shape=(n,)).copy()
    x = np.ctypeslib.as_array(px, shape=(n,)).copy()
    for p in (pi, pj, px):
        lib.dl4j_free(p)
    return i, j, x


def bh_repulsion(y, theta=0.5):
    """Barnes-Hut repulsive t-SNE forces (quadtree + theta traversal in
    C++, threaded). y: [n, 2] float32. Returns (rep [n, 2], Z) or None
    when the library is missing (caller falls back)."""
    lib = get_lib()
    if lib is None:
        return None
    y = np.ascontiguousarray(y, np.float32)
    rep = np.empty_like(y)
    z = lib.dl4j_bh_repulsion(
        y.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        int(y.shape[0]), float(theta),
        rep.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return rep, float(z)


def bh_attraction(y, row_ptr, cols, vals):
    """Sparse attractive t-SNE forces from a CSR neighbor matrix in C++.
    Returns attr [n, 2] or None when the library is missing."""
    lib = get_lib()
    if lib is None:
        return None
    y = np.ascontiguousarray(y, np.float32)
    row_ptr = np.ascontiguousarray(row_ptr, np.int64)
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    attr = np.empty_like(y)
    lib.dl4j_bh_attraction(
        y.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        int(y.shape[0]),
        row_ptr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        cols.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        attr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return attr


class PrefetchCsvLoader:
    """Multi-threaded native CSV prefetcher: worker threads parse files
    into float32 matrices off the GIL; `next()` yields them in submission
    order (the DataVec-reader + AsyncDataSetIterator host role, kept
    native per SURVEY.md §2.9). Context-manage or call close()."""

    def __init__(self, paths, delimiter=",", skip_lines=0, n_threads=4,
                 capacity=8):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        paths = [str(p) for p in paths]
        joined = "\n".join(paths).encode()
        self._handle = lib.dl4j_loader_create(
            joined, ctypes.c_char(delimiter.encode()), int(skip_lines),
            int(n_threads), int(capacity))
        if not self._handle:
            raise RuntimeError("loader creation failed")

    def next(self):
        """Next file's float32 [rows, cols] array; None when exhausted.
        Raises on a file that failed to parse."""
        if self._handle is None:
            return None
        rows = ctypes.c_int64()
        cols = ctypes.c_int64()
        ptr = self._lib.dl4j_loader_next(self._handle, ctypes.byref(rows),
                                         ctypes.byref(cols))
        if not ptr:
            if rows.value == -1:
                return None
            raise IOError("native CSV parse failed for next file")
        n = rows.value * cols.value
        if n == 0:        # empty-but-valid file sentinel
            self._lib.dl4j_free(ptr)
            return np.zeros((0, 0), np.float32)
        arr = np.ctypeslib.as_array(ptr, shape=(n,)).reshape(
            rows.value, cols.value).copy()
        self._lib.dl4j_free(ptr)
        return arr

    def __iter__(self):
        while True:
            a = self.next()
            if a is None:
                return
            yield a

    def close(self):
        if self._handle is not None:
            self._lib.dl4j_loader_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class StagingBufferPool:
    """Aligned reusable host buffers for device staging (reference role:
    ND4J AtomicAllocator host-side buffers / MagicQueue)."""

    def __init__(self, alignment=4096):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._pool = lib.dl4j_pool_create(alignment)

    def acquire(self, nbytes):
        ptr = self._lib.dl4j_pool_acquire(self._pool, int(nbytes))
        if not ptr:
            raise MemoryError(f"pool acquire({nbytes}) failed")
        return ptr

    def release(self, ptr, nbytes):
        self._lib.dl4j_pool_release(self._pool, ptr, int(nbytes))

    def as_array(self, ptr, shape, dtype=np.float32):
        n = int(np.prod(shape))
        ctype = np.ctypeslib.as_ctypes_type(np.dtype(dtype))
        buf = ctypes.cast(ptr, ctypes.POINTER(ctype * n)).contents
        return np.frombuffer(buf, dtype=dtype).reshape(shape)

    def stats(self):
        return {"allocated": self._lib.dl4j_pool_stats(self._pool, 0),
                "reused": self._lib.dl4j_pool_stats(self._pool, 1),
                "free": self._lib.dl4j_pool_stats(self._pool, 2)}

    def close(self):
        if self._pool:
            self._lib.dl4j_pool_destroy(self._pool)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
