"""ctypes bindings + build-on-demand for the native ingest hot path.

The shared library is compiled from ``tfidf_native.cpp`` with the system
g++ on first use and cached next to the source under a name that carries
a hash of the source — a copied tree keeps no mtimes, so only content can
say whether a library found there was built from this source. Everything
degrades gracefully: if no compiler is available the
framework runs on the pure-Python analyzer with identical results —
:func:`available` is the capability probe.

Binding layer only; the analysis semantics live in the C++ (and are
pinned by parity tests against the Python chain in tests/test_native.py).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
import threading

import numpy as np

from tfidf_tpu.utils.logging import get_logger

log = get_logger("native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "tfidf_native.cpp")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libtfidf_native.{digest}.so")


def _build(lib_path: str) -> bool:
    # a temp name of its own, then rename: nodes that start together
    # each build a whole file and the last rename wins, identically
    fd, tmp = tempfile.mkstemp(dir=_HERE, prefix="libtfidf_native.",
                               suffix=".so.tmp")
    os.close(fd)
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", _SRC,
           "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native build failed; using pure-Python analyzer",
                    err=repr(e))
        if os.path.exists(tmp):
            os.unlink(tmp)
        return False
    for old in glob.glob(os.path.join(_HERE, "libtfidf_native*.so")):
        if old != lib_path:   # built from a source that is gone
            try:
                os.unlink(old)
            except OSError:
                pass
    log.info("native library built", path=lib_path)
    return True


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lib_path = _lib_path()
        if not os.path.exists(lib_path) and not _build(lib_path):
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            log.warning("native library load failed", err=repr(e))
            return None
        lib.tfidf_engine_new.restype = ctypes.c_void_p
        lib.tfidf_engine_new.argtypes = [
            ctypes.c_int, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        lib.tfidf_engine_free.argtypes = [ctypes.c_void_p]
        lib.tfidf_vocab_size.restype = ctypes.c_int64
        lib.tfidf_vocab_size.argtypes = [ctypes.c_void_p]
        lib.tfidf_vocab_lookup.restype = ctypes.c_int32
        lib.tfidf_vocab_lookup.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int]
        lib.tfidf_vocab_term.restype = ctypes.c_int64
        lib.tfidf_vocab_term.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_int64]
        lib.tfidf_vocab_dump_size.restype = ctypes.c_int64
        lib.tfidf_vocab_dump_size.argtypes = [ctypes.c_void_p]
        lib.tfidf_vocab_dump.restype = ctypes.c_int64
        lib.tfidf_vocab_dump.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        lib.tfidf_analyze_doc.restype = ctypes.c_int64
        lib.tfidf_analyze_doc.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_double)]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


NONASCII = -2
OVERFLOW = -1


class NativeEngine:
    """One native analyzer+vocabulary instance.

    All native calls hold ``self._mu``: ctypes releases the GIL, and the
    C++ side mutates shared unordered_maps (vocab + scratch) — concurrent
    HTTP upload handlers and searches would otherwise race. The pure-
    Python chain this replaces was GIL-serialized; the lock restores that
    guarantee.
    """

    def __init__(self, lowercase: bool = True,
                 stopwords: tuple[str, ...] = (),
                 max_token_length: int = 255) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._mu = threading.Lock()
        stops = "\n".join(stopwords).encode("utf-8")
        self._h = ctypes.c_void_p(lib.tfidf_engine_new(
            int(lowercase), max_token_length, stops, len(stops)))
        # reusable output buffers, grown on demand (guarded by _mu)
        self._cap = 4096
        self._ids = np.empty(self._cap, np.int32)
        self._tfs = np.empty(self._cap, np.float32)
        self._len = ctypes.c_double(0.0)

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h:
            self._lib.tfidf_engine_free(h)
            self._h = None

    def vocab_size(self) -> int:
        with self._mu:
            return int(self._lib.tfidf_vocab_size(self._h))

    def lookup(self, term: str, add: bool) -> int | None:
        b = term.encode("utf-8")
        with self._mu:
            tid = self._lib.tfidf_vocab_lookup(self._h, b, len(b),
                                               int(add))
        return None if tid < 0 else int(tid)

    def term(self, tid: int) -> str:
        cap = 1024
        while True:
            buf = ctypes.create_string_buffer(cap)
            with self._mu:
                n = self._lib.tfidf_vocab_term(self._h, tid, buf, cap)
            if n == OVERFLOW:
                cap *= 4
                continue
            if n < 0:
                raise IndexError(f"term id {tid}")
            return buf.raw[:n].decode("utf-8")

    def dump_terms(self) -> list[str]:
        with self._mu:
            n = self._lib.tfidf_vocab_dump_size(self._h)
            if n == 0:
                return []
            buf = ctypes.create_string_buffer(int(n))
            wrote = self._lib.tfidf_vocab_dump(self._h, buf, n)
        assert wrote == n, (wrote, n)
        return buf.raw.decode("utf-8").split("\n")[:-1]

    def analyze(self, text: str, *, add: bool
                ) -> tuple[np.ndarray, np.ndarray, float] | None:
        """ASCII fast path: text -> (sorted ids, tfs, doc length).
        Returns None when the text needs the Python (Unicode) analyzer."""
        try:
            raw = text.encode("ascii")
        except UnicodeEncodeError:
            return None
        with self._mu:
            while True:
                n = self._lib.tfidf_analyze_doc(
                    self._h, raw, len(raw), int(add),
                    self._ids.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_int32)),
                    self._tfs.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_float)),
                    self._cap, ctypes.byref(self._len))
                if n == OVERFLOW:
                    self._cap *= 4
                    self._ids = np.empty(self._cap, np.int32)
                    self._tfs = np.empty(self._cap, np.float32)
                    continue
                if n == NONASCII:   # unreachable after the encode check
                    return None
                n = int(n)
                return (self._ids[:n].copy(), self._tfs[:n].copy(),
                        float(self._len.value))
