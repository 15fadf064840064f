"""ctypes bindings for the native (C++) ingest runtime.

The library is a function of the COMMITTED source: it is named
``libtda_ingest-<hash>.so`` after the SHA-256 of ``native/
graph_ingest.cpp`` + ``native/Makefile`` (which holds the portable
build flags), built on first use when a compiler is present, and only
a binary whose name carries the current hash is ever loaded — a stale
or foreign ``.so`` lying in the package directory (built from another
source, or for another CPU) is ignored, never dlopen'ed. Every entry
point has a byte-identical NumPy fallback, so the framework works
without the library — just slower at 10M+ edge scale; which path is
in use is said once per process, on stderr and as a ``native_ingest``
telemetry event.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys

import numpy as np

from tpu_distalg.telemetry import events as tevents

_here = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(_here)), "native")
_lib = None
_load_attempted = False


def lib_path() -> str | None:
    """Where the binary matching the committed source lives (built or
    not); None when the source tree is absent (an installed package
    without ``native/``)."""
    h = hashlib.sha256()
    try:
        # same bytes, same order as the Makefile's `cat ... | sha256sum`
        for name in ("graph_ingest.cpp", "Makefile"):
            with open(os.path.join(_SRC_DIR, name), "rb") as f:
                h.update(f.read())
    except OSError:
        return None
    return os.path.join(_here, f"libtda_ingest-{h.hexdigest()[:12]}.so")


def _build(path: str) -> str | None:
    """Build ``path`` from the committed source; the failure reason on
    error, None on success."""
    try:
        subprocess.run(
            ["make", "-C", _SRC_DIR, f"TARGET={path}"], check=True,
            capture_output=True, text=True, timeout=120,
        )
    except subprocess.CalledProcessError as e:
        tail = (e.stderr or "").strip().splitlines()[-1:]
        return f"build failed: {tail[0] if tail else e}"
    except (subprocess.SubprocessError, OSError) as e:
        return f"build failed: {type(e).__name__}: {e}"
    return None


def _announce(path: str, reason: str = "") -> None:
    tevents.emit("native_ingest", path=path, reason=reason)
    print(f"[native] graph ingest path: {path}"
          f"{' (' + reason + ')' if reason else ''}", file=sys.stderr)


def load() -> ctypes.CDLL | None:
    """The loaded library, building it on first use if needed; None when
    unavailable (callers fall back to NumPy, and the reason has been
    announced)."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    path = lib_path()
    if path is None:
        _announce("numpy", f"no source under {_SRC_DIR}")
        return None
    if not os.path.exists(path):
        err = _build(path)
        if err is not None:
            _announce("numpy", err)
            return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        _announce("numpy", f"cannot load {os.path.basename(path)}: {e}")
        return None
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    lib.tda_dedupe_edges.argtypes = [i64p, i64p, ctypes.c_int64]
    lib.tda_dedupe_edges.restype = ctypes.c_int64
    lib.tda_out_degree.argtypes = [i64p, ctypes.c_int64, i32p,
                                   ctypes.c_int64]
    lib.tda_out_degree.restype = None
    lib.tda_csr_offsets.argtypes = [i64p, ctypes.c_int64, i64p,
                                    ctypes.c_int64]
    lib.tda_csr_offsets.restype = None
    lib.tda_parse_edges_text.argtypes = [ctypes.c_char_p, i64p, i64p,
                                         ctypes.c_int64]
    lib.tda_parse_edges_text.restype = ctypes.c_int64
    lib.tda_counting_sort_perm.argtypes = [i64p, ctypes.c_int64,
                                           ctypes.c_int64, i64p]
    lib.tda_counting_sort_perm.restype = ctypes.c_int32
    lib.tda_pack_edge_rows.argtypes = [i64p, i64p, f32p,
                                       ctypes.c_int64, i32p]
    lib.tda_pack_edge_rows.restype = None
    _lib = lib
    _announce("native", os.path.basename(path))
    return _lib


def available() -> bool:
    return load() is not None


def pack_edge_rows(src: np.ndarray, dst: np.ndarray,
                   w: np.ndarray) -> np.ndarray:
    """Interleave dst-sorted edge columns into packed ``(E, 3)`` int32
    cache rows ``[src, dst, bits(w)]`` — the ``csr_edge_blocks_i32``
    layout (``tpu_distalg/graphs/ingest.py``). Native path and NumPy
    fallback are byte-identical (int32 truncation of in-range ids +
    the f32 bit pattern), so a cache is deterministic in its header
    whichever path built it."""
    src = np.ascontiguousarray(src, dtype=np.int64)
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    w = np.ascontiguousarray(w, dtype=np.float32)
    n = len(src)
    out = np.empty((n, 3), dtype=np.int32)
    lib = load()
    if n and lib is not None:
        lib.tda_pack_edge_rows(src, dst, w, n, out)
        return out
    out[:, 0] = src.astype(np.int32)
    out[:, 1] = dst.astype(np.int32)
    out[:, 2] = w.view(np.int32)
    return out


def dedupe_edges_pair(edges: np.ndarray):
    """Sorted, deduplicated (src, dst) contiguous column pair from an
    (E, 2) int edge array — the zero-extra-copy native interface.

    Native path: pack-sort-unique in C++; fallback: ``np.unique(axis=0)``.
    Matches ``links.distinct()`` set semantics (reference pagerank.py:41).
    """
    edges = np.ascontiguousarray(edges, dtype=np.int64)
    lib = load()
    if lib is None or len(edges) == 0:
        uniq = np.unique(edges, axis=0)
        return np.ascontiguousarray(uniq[:, 0]), np.ascontiguousarray(
            uniq[:, 1]
        )
    src = np.ascontiguousarray(edges[:, 0])
    dst = np.ascontiguousarray(edges[:, 1])
    m = lib.tda_dedupe_edges(src, dst, len(src))
    return src[:m], dst[:m]


def dedupe_edges(edges: np.ndarray) -> np.ndarray:
    """(E', 2) stacked variant of ``dedupe_edges_pair``."""
    src, dst = dedupe_edges_pair(edges)
    return np.stack([src, dst], axis=1)


def out_degree(src: np.ndarray, n_vertices: int) -> np.ndarray:
    src = np.ascontiguousarray(src, dtype=np.int64)
    if len(src) and (m := int(src.max())) >= n_vertices:
        # the C++ histogram writes degree[src[i]] unchecked — reject
        # out-of-range ids here rather than corrupt memory
        raise ValueError(
            f"src id {m} out of range for n_vertices={n_vertices}"
        )
    lib = load()
    if lib is None:
        return np.bincount(src, minlength=n_vertices).astype(np.int32)
    deg = np.zeros((n_vertices,), dtype=np.int32)
    lib.tda_out_degree(src, len(src), deg, n_vertices)
    return deg


def csr_offsets(sorted_src: np.ndarray, n_vertices: int) -> np.ndarray:
    """Row-offset array (n_vertices+1,) for edges sorted by src."""
    sorted_src = np.ascontiguousarray(sorted_src, dtype=np.int64)
    lib = load()
    if lib is None:
        counts = np.bincount(sorted_src, minlength=n_vertices)
        out = np.zeros((n_vertices + 1,), dtype=np.int64)
        np.cumsum(counts, out=out[1:])
        return out
    out = np.zeros((n_vertices + 1,), dtype=np.int64)
    lib.tda_csr_offsets(sorted_src, len(sorted_src), out, n_vertices)
    return out


def counting_sort_perm(keys: np.ndarray, key_range: int) -> np.ndarray:
    """Stable argsort of bounded integer keys — O(n + range) counting
    sort in C++ (NumPy fallback: ``np.argsort(kind='stable')``). The
    host-prep behind PageRank's dst-sorted edge layout."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    lib = load()
    if lib is None or len(keys) == 0:
        # fallback validates too, so environments without a compiler
        # reject corrupt ids exactly like the native path's range check
        if len(keys) and (keys.min() < 0 or keys.max() >= key_range):
            raise ValueError(
                f"counting_sort_perm: key out of range [0, {key_range})"
            )
        return np.argsort(keys, kind="stable")
    perm = np.empty((len(keys),), dtype=np.int64)
    if lib.tda_counting_sort_perm(keys, len(keys), key_range, perm):
        raise ValueError(
            f"counting_sort_perm: key out of range [0, {key_range})"
        )
    return perm


def parse_edges_text(path: str, capacity: int) -> np.ndarray:
    """Parse a '#'-commented whitespace edge-list file into (E, 2) int64."""
    lib = load()
    if lib is None:
        return np.loadtxt(path, dtype=np.int64, comments="#").reshape(-1, 2)
    src = np.empty((capacity,), dtype=np.int64)
    dst = np.empty((capacity,), dtype=np.int64)
    n = lib.tda_parse_edges_text(path.encode(), src, dst, capacity)
    if n == -1:
        raise FileNotFoundError(path)
    if n == -2:
        raise ValueError(f"edge file exceeds capacity {capacity}")
    return np.stack([src[:n], dst[:n]], axis=1)
