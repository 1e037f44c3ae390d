"""Build + ctypes bindings for the native map hot loop.

The shared library is compiled on first use with g++ (no pybind11 in the
image; ctypes keeps the binding layer dependency-free) and cached beside the
source under a name keyed by the source, the flags and the host CPU, so a
tree copied to another machine rebuilds instead of loading a library
built for a different ``-march=native``.  The C call runs with the GIL
released — ctypes drops it for foreign calls — so host IO and device
dispatch can proceed while a chunk maps.

Two wrapper flavours over the same stateful C API (``moxt_new`` /
``moxt_map`` / ``moxt_chunk_read`` / ``moxt_dict_read``):

* :class:`NativeStream` — one persistent state per workload instance.  The
  hash->bytes dictionary lives in C++ across chunks and each ``map_chunk``
  drains only the *delta* of newly seen keys, so steady-state chunks hand
  back (hash, count) arrays and ~no strings — the per-chunk Python dict
  rebuild that round 1 paid for is gone.
* :class:`NativeMapper` — the stateless per-call facade (fresh state each
  call) used by parity tests and one-shot callers.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
import threading

import numpy as np

from map_oxidize_tpu.api import MapOutput
from map_oxidize_tpu.ops.hashing import HashDictionary, split_u64
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "csrc", "moxt_native.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_CXXFLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17")


def _host_cpu_id() -> str:
    """What ``-march=native`` compiles for: the CPU model and its feature
    flags.  A library built on another host (a copied tree) then never
    matches this one's name."""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            info = f.read()
    except OSError:
        import platform

        return platform.machine() + platform.processor()
    keep = [ln for ln in info.splitlines()
            if ln.split(b":")[0].strip() in (b"model name", b"flags",
                                             b"Features", b"CPU part")]
    return "\n".join(sorted({ln.decode("utf-8", "replace") for ln in keep}))


def _so_path() -> str:
    """The library's path, keyed by source, flags and host CPU."""
    import hashlib

    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_host_cpu_id().encode())
    return os.path.join(_BUILD_DIR, f"libmoxt_native-{h.hexdigest()[:16]}.so")


def _compile() -> str:
    so = _so_path()
    if os.path.isfile(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # build to a temp name + atomic rename so concurrent importers are safe
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
    os.close(fd)
    cmd = ["g++", *_CXXFLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        os.unlink(tmp)
        raise RuntimeError(f"native build failed: {e.stderr}") from e
    os.replace(tmp, so)
    _log.info("built native map library: %s", so)
    return so


_lib = None
_lib_lock = threading.Lock()


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(_compile())
        lib.moxt_new.restype = ctypes.c_void_p
        lib.moxt_new.argtypes = [ctypes.c_int32]
        lib.moxt_free.restype = None
        lib.moxt_free.argtypes = [ctypes.c_void_p]
        lib.moxt_map.restype = ctypes.c_int32
        lib.moxt_map.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_int64]
        lib.moxt_set_unicode.restype = ctypes.c_int32
        lib.moxt_set_unicode.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
        lib.moxt_chunk_unique.restype = ctypes.c_int64
        lib.moxt_chunk_unique.argtypes = [ctypes.c_void_p]
        lib.moxt_chunk_tokens.restype = ctypes.c_int64
        lib.moxt_chunk_tokens.argtypes = [ctypes.c_void_p]
        lib.moxt_chunk_read.restype = None
        lib.moxt_chunk_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_void_p]
        lib.moxt_dict_pending.restype = None
        lib.moxt_dict_pending.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_void_p]
        lib.moxt_dict_read.restype = None
        lib.moxt_dict_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_void_p, ctypes.c_void_p]
        lib.moxt_file_open.restype = ctypes.c_void_p
        lib.moxt_file_open.argtypes = [ctypes.c_char_p]
        lib.moxt_file_close.restype = None
        lib.moxt_file_close.argtypes = [ctypes.c_void_p]
        lib.moxt_file_size.restype = ctypes.c_int64
        lib.moxt_file_size.argtypes = [ctypes.c_void_p]
        lib.moxt_map_range.restype = ctypes.c_int64
        lib.moxt_map_range.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_int64, ctypes.c_int64]
        lib.moxt_range_cut.restype = ctypes.c_int64
        lib.moxt_range_cut.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_int64, ctypes.c_int64]
        lib.moxt_map_docs_ex.restype = ctypes.c_int32
        lib.moxt_map_docs_ex.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                         ctypes.c_int64, ctypes.c_int64,
                                         ctypes.c_int32]
        lib.moxt_map_docs.restype = ctypes.c_int32
        lib.moxt_map_docs.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int64, ctypes.c_int64]
        lib.moxt_pairs_n.restype = ctypes.c_int64
        lib.moxt_pairs_n.argtypes = [ctypes.c_void_p]
        lib.moxt_pairs_read.restype = None
        lib.moxt_pairs_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_void_p]
        lib.moxt_map_range_docs.restype = ctypes.c_int64
        lib.moxt_map_range_docs.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                            ctypes.c_int64, ctypes.c_int64]
        lib.moxt_map_hashes.restype = ctypes.c_int32
        lib.moxt_map_hashes.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_int64]
        lib.moxt_hashes_n.restype = ctypes.c_int64
        lib.moxt_hashes_n.argtypes = [ctypes.c_void_p]
        lib.moxt_hashes_read.restype = None
        lib.moxt_hashes_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.moxt_map_range_hashes.restype = ctypes.c_int64
        lib.moxt_map_range_hashes.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
        lib.moxt_map_hll.restype = ctypes.c_int32
        lib.moxt_map_hll.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_int64, ctypes.c_int32]
        lib.moxt_hll_read.restype = None
        lib.moxt_hll_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.moxt_map_range_hll.restype = ctypes.c_int64
        lib.moxt_map_range_hll.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32]
        lib.moxt_resolve_begin.restype = ctypes.c_int32
        lib.moxt_resolve_begin.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                           ctypes.c_int64]
        lib.moxt_resolve_range.restype = ctypes.c_int64
        lib.moxt_resolve_range.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
        lib.moxt_resolve_found.restype = ctypes.c_int64
        lib.moxt_resolve_found.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.moxt_resolve_remaining.restype = ctypes.c_int64
        lib.moxt_resolve_remaining.argtypes = [ctypes.c_void_p]
        lib.moxt_resolve_read.restype = None
        lib.moxt_resolve_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_void_p, ctypes.c_void_p]
        lib.moxt_sort_kd.restype = ctypes.c_int32
        lib.moxt_sort_kd.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_int64]
        lib.moxt_sort_u64_blocks.restype = ctypes.c_int32
        lib.moxt_sort_u64_blocks.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        lib.moxt_count_u64.restype = ctypes.c_int64
        lib.moxt_count_u64.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                       ctypes.c_void_p, ctypes.c_void_p]
        lib.moxt_group_by_key.restype = ctypes.c_int32
        lib.moxt_group_by_key.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p]
        _lib = lib
        return _lib


def _raise_map_error(rc: int) -> None:
    """Map a native return code to the same exception type the Python
    fallback raises for that condition (tests assert error-type parity)."""
    if rc == 0:
        return
    if rc == 1:
        raise ValueError("64-bit hash collision in native map")
    if rc == 3:
        raise UnicodeDecodeError(
            "utf-8", b"", 0, 1,
            "invalid UTF-8 in unicode-mode native map (same input fails "
            "the Python fallback's chunk.decode)")
    raise RuntimeError(f"native map error {rc}")


_UNICODE_TABLES = None


def _unicode_tables():
    """(ws_cps, map_cps, map_offs, map_blob, cased_cps, ignorable_cps) numpy
    arrays generated from Python's own Unicode behavior — str.isspace() and
    str.lower() ARE the semantics the unicode tokenizer mode promises
    (wordcount.tokenize), so deriving the C++ tables from them makes parity
    hold by construction.

    The cased / case-ignorable sets (CPython's Final_Sigma context rule for
    U+03A3) are probed through ``lower()`` itself rather than re-deriving
    Unicode properties: with P1 = "AcΣ".lower() ending in final sigma and
    P2 = "ΑΣc".lower() keeping medial sigma, CPython's own backward/forward
    scans give P1∧P2 ⇔ c case-ignorable and P1∧¬P2 ⇔ c cased."""
    global _UNICODE_TABLES
    if _UNICODE_TABLES is None:
        # probing 0x110000 codepoints through str.lower() costs seconds per
        # process; the result depends only on the interpreter's Unicode
        # tables, so cache it keyed on the unidata version
        import sys
        import unicodedata

        cache = os.path.join(
            _BUILD_DIR,
            f"unicode_tables_u{unicodedata.unidata_version}"
            f"_py{sys.version_info[0]}{sys.version_info[1]}.npz")
        try:
            with np.load(cache) as z:
                _UNICODE_TABLES = tuple(
                    z[k] for k in ("ws", "cps", "offs", "blob", "cased",
                                   "ign"))
            return _UNICODE_TABLES
        except (OSError, KeyError, ValueError):
            pass
        ws = np.array([cp for cp in range(0x3001) if chr(cp).isspace()],
                      np.uint32)
        cps, offs, parts = [], [0], []
        cased, ignorable = [], []
        total = 0
        for cp in range(0x110000):
            if 0xD800 <= cp < 0xE000:
                continue  # surrogates: unencodable, never appear decoded
            c = chr(cp)
            low = c.lower()
            if low != c:
                b = low.encode("utf-8")
                cps.append(cp)
                total += len(b)
                offs.append(total)
                parts.append(b)
            p1 = ("A" + c + "Σ").lower()[-1] == "ς"
            p2 = ("ΑΣ" + c).lower()[1] == "ς"
            if p1 and not p2:
                cased.append(cp)
            elif p1 and p2:
                ignorable.append(cp)
        _UNICODE_TABLES = (
            ws,
            np.array(cps, np.uint32),
            np.array(offs, np.int64),
            np.frombuffer(b"".join(parts), np.uint8).copy(),
            np.array(cased, np.uint32),
            np.array(ignorable, np.uint32),
        )
        try:
            os.makedirs(_BUILD_DIR, exist_ok=True)
            fd, tmp = tempfile.mkstemp(suffix=".npz.tmp", dir=_BUILD_DIR)
            with os.fdopen(fd, "wb") as f:
                t = _UNICODE_TABLES
                np.savez(f, ws=t[0], cps=t[1], offs=t[2], blob=t[3],
                         cased=t[4], ign=t[5])
            os.replace(tmp, cache)
        except OSError:
            pass  # cache is best-effort; probing again next process is fine
    return _UNICODE_TABLES


class NativeStream:
    """Persistent native mapper state: per-chunk (hash, count) columns plus a
    cross-chunk C++ dictionary drained as deltas.

    Not thread-safe per instance — ``map_chunk`` serializes on a lock (the
    C++ loop is single-core-bound anyway; concurrent callers would only
    interleave on one core)."""

    def __init__(self, ngram: int = 1, tokenizer: str = "ascii"):
        if not 1 <= ngram <= 16:
            raise ValueError("ngram must be in [1, 16]")
        self._lib = _load_lib()
        self._st = self._lib.moxt_new(ngram)
        if not self._st:
            raise RuntimeError("moxt_new failed")
        self.ngram = ngram
        self.tokenizer = tokenizer
        if tokenizer == "unicode":
            ws, cps, offs, blob, cased, ign = _unicode_tables()
            rc = self._lib.moxt_set_unicode(
                self._st, ws.ctypes.data, ws.size, cps.ctypes.data,
                offs.ctypes.data, blob.ctypes.data, cps.size,
                cased.ctypes.data, cased.size, ign.ctypes.data, ign.size)
            if rc:
                raise RuntimeError(f"moxt_set_unicode failed ({rc})")
        elif tokenizer != "ascii":
            raise ValueError(f"unknown tokenizer {tokenizer!r}")
        self._lock = threading.Lock()

    def close(self) -> None:
        if self._st:
            self._lib.moxt_free(self._st)
            self._st = None

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass

    def map_chunk(self, chunk, drain_dict: bool = True) -> MapOutput:
        """Map one chunk (any buffer-protocol object: bytes, memoryview,
        bytearray — passed to C by pointer, zero-copy)."""
        view = np.frombuffer(chunk, np.uint8)
        with self._lock:
            rc = self._lib.moxt_map(self._st, view.ctypes.data, view.size)
            return self._collect_locked(rc, drain_dict)

    def _collect_locked(self, rc: int, drain_dict: bool) -> MapOutput:
        _raise_map_error(rc)
        nu = int(self._lib.moxt_chunk_unique(self._st))
        n_tokens = int(self._lib.moxt_chunk_tokens(self._st))
        hashes = np.empty(nu, np.uint64)
        counts = np.empty(nu, np.int32)
        if nu:
            self._lib.moxt_chunk_read(
                self._st, hashes.ctypes.data, counts.ctypes.data)
        d = self._drain_dict_locked() if drain_dict else HashDictionary()
        hi, lo = split_u64(hashes)
        records = max(n_tokens - (self.ngram - 1), 0) if n_tokens else 0
        return MapOutput(hi=hi, lo=lo, values=counts, dictionary=d,
                         records_in=records)

    def _iter_file_ranges(self, path: str, start_offset: int, map_range,
                          collect, what: str):
        """Shared mmap range-iteration skeleton for every file iterator:
        open/size, per-range ``map_range(file, off) -> consumed`` under the
        lock, negative-rc error mapping, stall detection, ``collect()``
        readback, close.  Yields ``(collected, next_offset)``."""
        f = self._lib.moxt_file_open(os.fsencode(path))
        if not f:
            raise OSError(f"cannot open/mmap {path!r}")
        try:
            size = int(self._lib.moxt_file_size(f))
            off = start_offset
            while off < size:
                with self._lock:
                    consumed = int(map_range(f, off))
                    if consumed < 0:
                        _raise_map_error(-consumed)
                    if consumed == 0:
                        raise RuntimeError(
                            f"native {what} stalled at {off}")
                    out = collect()
                off += consumed
                yield out, off
        finally:
            self._lib.moxt_file_close(f)

    def _range_ops(self, kind: str, chunk_bytes: int, p: int = 0):
        """``(map_range(file, off) -> consumed, collect() -> output, what)``
        of one scan kind over ``range_cut`` ranges on this stream:
        ``words`` (combine map + dictionary delta), ``hashes`` (hash-only)
        or ``hll`` (register fold).  The one-stream iterators below and
        the workers of :class:`OrderedScan` share them."""
        lib, st = self._lib, self._st
        if kind == "words":
            return (lambda f, off: lib.moxt_map_range(st, f, off,
                                                      chunk_bytes),
                    lambda: self._collect_locked(0, drain_dict=True),
                    "map_range")
        if kind == "hashes":
            return (lambda f, off: lib.moxt_map_range_hashes(
                        st, f, off, chunk_bytes),
                    lambda: self._collect_hashes_locked(0),
                    "map_range_hashes")
        if kind == "hll":
            return (lambda f, off: lib.moxt_map_range_hll(
                        st, f, off, chunk_bytes, p),
                    lambda: self._collect_hll_locked(0, p), "map_range_hll")
        raise ValueError(f"unknown range scan kind {kind!r}")

    def iter_file(self, path: str, chunk_bytes: int, start_offset: int = 0):
        """Map a file via the C++ mmap path: zero kernel->user copies, chunk
        cuts chosen in C (last newline, then last whitespace, then hard cut —
        the same bounded-carry policy as io.splitter.iter_chunks).  Yields
        ``(MapOutput, next_offset)`` per chunk; ``start_offset`` resumes at a
        previous run's cut boundary (checkpoint/resume contract: the cut
        policy is deterministic in (offset, chunk_bytes), so the resumed
        chunk stream is identical to a fresh run's tail)."""
        return self._iter_file_ranges(
            path, start_offset, *self._range_ops("words", chunk_bytes))

    def _collect_pairs_locked(self) -> MapOutput:
        n = int(self._lib.moxt_pairs_n(self._st))
        n_tokens = int(self._lib.moxt_chunk_tokens(self._st))
        hashes = np.empty(n, np.uint64)
        docs = np.empty(n, np.int64)
        if n:
            self._lib.moxt_pairs_read(self._st, hashes.ctypes.data,
                                      docs.ctypes.data)
        d = self._drain_dict_locked()
        # compact form: the host collect engine consumes (keys64, docs64)
        # directly; plane-bound consumers (checkpoint spill, device sort)
        # materialize hi/lo + (n, 2) doc planes via ensure_planes()
        return MapOutput(hi=None, lo=None, values=None, dictionary=d,
                         records_in=n_tokens, keys64=hashes, docs64=docs)

    def map_docs(self, chunk, base_doc: int = 0) -> MapOutput:
        """Inverted-index map of one chunk: one row per distinct term per
        document (doc id = ``base_doc`` + in-chunk line offset), values =
        doc-id uint32 planes ``(n, 2)``."""
        view = np.frombuffer(chunk, np.uint8)
        with self._lock:
            rc = self._lib.moxt_map_docs(self._st, view.ctypes.data,
                                         view.size, base_doc)
            if rc == 1:
                raise ValueError("64-bit hash collision in native map")
            if rc:
                raise RuntimeError(f"native map_docs error {rc}")
            return self._collect_pairs_locked()

    def iter_file_docs(self, path: str, chunk_bytes: int,
                       start_offset: int = 0):
        """mmap inverted-index map over a file; doc ids are absolute byte
        offsets of line starts.  Yields ``(MapOutput, next_offset)`` per
        chunk; ``start_offset`` resumes at a previous run's boundary (the
        doc-mode cut policy is deterministic in (offset, chunk_bytes))."""
        return self._iter_file_ranges(
            path, start_offset,
            lambda f, off: self._lib.moxt_map_range_docs(
                self._st, f, off, chunk_bytes),
            self._collect_pairs_locked, "map_range_docs")

    def map_chunk_hashes(self, chunk) -> MapOutput:
        """Hash-only map of one chunk: one raw n-gram hash per window, no
        tables, no strings (wide-key collect-reduce path).  Values are all
        ones; the engine's one final sort aggregates."""
        view = np.frombuffer(chunk, np.uint8)
        with self._lock:
            rc = self._lib.moxt_map_hashes(self._st, view.ctypes.data,
                                           view.size)
            return self._collect_hashes_locked(rc)

    def _collect_hashes_locked(self, rc: int) -> MapOutput:
        _raise_map_error(rc)
        n = int(self._lib.moxt_hashes_n(self._st))
        hashes = np.empty(n, np.uint64)
        if n:
            self._lib.moxt_hashes_read(self._st, hashes.ctypes.data)
        # compact form: keys64 only — no plane split, no ones array (counts
        # are implicit).  The host collect engine consumes this directly;
        # anything plane-bound calls out.ensure_planes().
        return MapOutput(hi=None, lo=None, values=None,
                         dictionary=HashDictionary(), records_in=n,
                         keys64=hashes)

    def iter_file_hashes(self, path: str, chunk_bytes: int,
                         start_offset: int = 0):
        """mmap hash-only map over a file; same cut policy (and therefore
        the same resume offsets) as :meth:`iter_file`.  Yields
        ``(MapOutput, next_offset)``."""
        return self._iter_file_ranges(
            path, start_offset, *self._range_ops("hashes", chunk_bytes))

    def map_chunk_hll(self, chunk, p: int):
        """HLL-fold map of one chunk: the scan max-folds (top-p-bits bucket,
        leading-zero rank) into ``2^p`` uint8 registers in C — no hash
        emission, no host-side extraction.  Returns ``(registers, n_tokens)``
        with the same register semantics as
        workloads.distinct.hll_registers."""
        view = np.frombuffer(chunk, np.uint8)
        with self._lock:
            rc = self._lib.moxt_map_hll(self._st, view.ctypes.data,
                                        view.size, p)
            return self._collect_hll_locked(rc, p)

    def _collect_hll_locked(self, rc: int, p: int):
        _raise_map_error(rc)
        regs = np.empty(1 << p, np.uint8)
        self._lib.moxt_hll_read(self._st, regs.ctypes.data)
        return regs, int(self._lib.moxt_chunk_tokens(self._st))

    def iter_file_hll(self, path: str, chunk_bytes: int, p: int,
                      start_offset: int = 0):
        """mmap HLL-fold map over a file; same cut policy (and resume
        offsets) as :meth:`iter_file_hashes`.  Yields
        ``(registers, n_tokens, next_offset)``."""
        for (regs, n_tokens), off in self._iter_file_ranges(
                path, start_offset, *self._range_ops("hll", chunk_bytes, p)):
            yield regs, n_tokens, off

    def resolve_file(self, path: str, chunk_bytes: int, hashes: np.ndarray,
                     early_stop: bool = True):
        """Recover key bytes for ``hashes`` by rescanning the corpus with
        the SAME chunk cuts the hash-only map used.  Returns
        ``(found_hashes u64, lens i32, blob bytes)``; a 64-bit collision
        involving any queried key raises (first occurrence's bytes are
        compared against every later occurrence in the scanned range).

        ``early_stop`` ends the scan as soon as every queried hash has been
        seen once — for top-k winners (by construction the most frequent
        keys) that is typically within the first chunks, making the rescan
        cost ~independent of corpus size.  The trade: the collision
        byte-check then covers the scanned prefix, not the whole corpus;
        pass ``early_stop=False`` (config ``rescan_full``) for the
        full-corpus check."""
        hashes = np.ascontiguousarray(hashes, np.uint64)
        with self._lock:
            rc = self._lib.moxt_resolve_begin(
                self._st, hashes.ctypes.data, hashes.size)
            if rc:
                raise RuntimeError(f"moxt_resolve_begin failed ({rc})")
            if hashes.size == 0:
                return (np.empty(0, np.uint64), np.empty(0, np.int32), b"")
            f = self._lib.moxt_file_open(os.fsencode(path))
            if not f:
                raise OSError(f"cannot open/mmap {path!r}")
            try:
                size = int(self._lib.moxt_file_size(f))
                off = 0
                while off < size:
                    consumed = int(self._lib.moxt_resolve_range(
                        self._st, f, off, chunk_bytes))
                    if consumed < 0:
                        _raise_map_error(-consumed)
                    if consumed == 0:
                        raise RuntimeError(
                            f"native resolve_range stalled at {off}")
                    off += consumed
                    if (early_stop
                            and self._lib.moxt_resolve_remaining(self._st)
                            == 0):
                        if off < size:
                            # the 64-bit collision byte-check covered only
                            # the scanned prefix — say exactly how much, so
                            # the guarantee's scope is visible (advisor r3;
                            # --rescan-full restores the full-corpus check)
                            _log.info(
                                "resolve early-stop at %d/%d bytes "
                                "(%.1f%%); collision byte-check covers the "
                                "scanned prefix only", off, size,
                                100.0 * off / size)
                        break
            finally:
                self._lib.moxt_file_close(f)
            nbytes = ctypes.c_int64()
            n = int(self._lib.moxt_resolve_found(self._st,
                                                 ctypes.byref(nbytes)))
            out_h = np.empty(n, np.uint64)
            out_len = np.empty(n, np.int32)
            blob = np.empty(max(int(nbytes.value), 1), np.uint8)
            if n:
                self._lib.moxt_resolve_read(
                    self._st, out_h.ctypes.data, out_len.ctypes.data,
                    blob.ctypes.data)
            return out_h, out_len, blob.tobytes()[:int(nbytes.value)]

    def _drain_dict_locked(self) -> HashDictionary:
        n = ctypes.c_int64()
        nbytes = ctypes.c_int64()
        self._lib.moxt_dict_pending(self._st, ctypes.byref(n),
                                    ctypes.byref(nbytes))
        d = HashDictionary()
        if not n.value:
            return d
        hashes = np.empty(n.value, np.uint64)
        lens = np.empty(n.value, np.int32)
        blob = np.empty(max(nbytes.value, 1), np.uint8)
        self._lib.moxt_dict_read(self._st, hashes.ctypes.data,
                                 lens.ctypes.data, blob.ctypes.data)
        # columnar delta, O(1): the per-key materialization loop runs once
        # at the consumer's first lookup, not per chunk (HashDictionary
        # docstring) — on wide key spaces this loop was the map-phase tax
        d.add_arrays(hashes, lens, blob.tobytes())
        return d

    def drain_dictionary(self) -> HashDictionary:
        """Novel (hash -> bytes) entries since the last drain."""
        with self._lock:
            return self._drain_dict_locked()


def sort_kd_or_none(keys: np.ndarray, docs: np.ndarray | None):
    """In-place stable ascending radix sort of ``keys`` (uint64) with
    ``docs`` (int64) riding along; GIL released.  Returns True on success,
    False when the native library is unavailable (caller falls back to
    numpy).  Measured ~4x numpy's stable u64 sort at 30M rows."""
    try:
        lib = _load_lib()
    except Exception:
        return False
    # in-place on raw pointers: refuse anything that is not exactly a
    # writable, contiguous (u64, i64) pair — a contiguity copy would sort
    # the copy, a wrong dtype would sort bitwise-wrong, and a read-only
    # buffer would be mutated behind numpy's back.  Declining returns
    # False so the caller's numpy fallback runs.
    def _ok(a, dt):
        return (a.dtype == np.dtype(dt) and a.ndim == 1
                and a.flags.c_contiguous and a.flags.writeable)

    if not _ok(keys, np.uint64) or (docs is not None and not (
            _ok(docs, np.int64) and docs.shape == keys.shape)):
        return False
    rc = lib.moxt_sort_kd(
        keys.ctypes.data,
        docs.ctypes.data if docs is not None else None,
        keys.shape[0])
    if rc:
        # native scratch allocation failed (it needs ~32B/row); the numpy
        # path needs less and may still succeed — fall back, don't abort
        _log.warning("native radix sort could not allocate scratch; "
                     "falling back to numpy")
        return False
    return True


def sort_u64_blocks_or_none(blocks: list) -> "np.ndarray | None":
    """Sort the concatenation of ``blocks`` (each a contiguous u64 array)
    ascending WITHOUT materializing the concatenation first: the native
    radix reads the blocks in place for its histogram and first scatter
    (the first pass IS the concatenation — ~0.3 s saved at 34M rows).
    Returns a new sorted array, or None when the native library is
    unavailable or any block is unsuitable (caller concatenates and
    sorts however it prefers)."""
    try:
        lib = _load_lib()
    except Exception:
        return None
    for b in blocks:
        if not (b.dtype == np.dtype(np.uint64) and b.ndim == 1
                and b.flags.c_contiguous):
            return None
    n = int(sum(b.shape[0] for b in blocks))
    if n == 0:
        return np.empty(0, np.uint64)
    live = [b for b in blocks if b.shape[0]]
    ptrs = (ctypes.c_void_p * len(live))(*[b.ctypes.data for b in live])
    lens = (ctypes.c_int64 * len(live))(*[b.shape[0] for b in live])
    out = np.empty(n, np.uint64)
    tmp = np.empty(n, np.uint64)
    rc = lib.moxt_sort_u64_blocks(ptrs, lens, len(live), out.ctypes.data,
                                  tmp.ctypes.data, n)
    if rc:
        _log.warning("native blocks radix sort could not allocate "
                     "scratch; falling back")
        return None
    return out


def count_u64_or_none(keys: np.ndarray):
    """Fused unique+count of u64 hash keys (the hash-only count reduce):
    MSD partition + per-bucket in-cache LSD + run emission in one native
    call — ~3x less DRAM traffic than sort + boundary-scan + gather.
    ``keys`` is read-only (the output buffer doubles as partition
    scratch).  Returns ``(uniques, counts)`` with uniques ascending, or
    None when the native library is unavailable / input unsuitable /
    scratch allocation fails (caller falls back to the sort path).
    n >= 2^31 is refused: one key with that many occurrences would
    truncate its int32 count."""
    try:
        lib = _load_lib()
    except Exception:
        return None
    if not (keys.dtype == np.dtype(np.uint64) and keys.ndim == 1
            and keys.flags.c_contiguous):
        return None
    n = int(keys.shape[0])
    if n >= 1 << 31:
        return None
    if n == 0:
        return np.empty(0, np.uint64), np.empty(0, np.int32)
    out_k = np.empty(n, np.uint64)
    out_c = np.empty(n, np.int32)
    m = int(lib.moxt_count_u64(keys.ctypes.data, n, out_k.ctypes.data,
                               out_c.ctypes.data))
    if m < 0:
        _log.warning("native count_u64 could not allocate scratch; "
                     "falling back to sort")
        return None
    return out_k[:m].copy(), out_c[:m].copy()


def group_by_key_or_none(keys: np.ndarray, docs: np.ndarray,
                         uniq: np.ndarray):
    """Group ``docs`` by ``keys`` against the known distinct-key set
    ``uniq`` (ascending u64) — the inverted-index finalize without a sort:
    an L2-resident hash->dense-id table, a counting pass, a scatter pass
    (feed order per term preserved = ascending doc ids, the sort path's
    stability contract).  Returns ``(offsets i64[m+1], docs_grouped
    i64[n])`` or None when the native library is unavailable, dtypes are
    unsuitable, scratch allocation fails, or the contract is violated
    (duplicate uniq entry / key missing from uniq) — callers fall back to
    the sort path."""
    try:
        lib = _load_lib()
    except Exception:
        return None

    def _ok(a, dt):
        return (a.dtype == np.dtype(dt) and a.ndim == 1
                and a.flags.c_contiguous)

    if not (_ok(keys, np.uint64) and _ok(docs, np.int64)
            and _ok(uniq, np.uint64) and docs.shape == keys.shape):
        return None
    n = int(keys.shape[0])
    m = int(uniq.shape[0])
    if m == 0:
        return None
    out_off = np.empty(m + 1, np.int64)
    out_docs = np.empty(max(n, 1), np.int64)
    rc = int(lib.moxt_group_by_key(
        keys.ctypes.data, docs.ctypes.data, n, uniq.ctypes.data, m,
        out_off.ctypes.data, out_docs.ctypes.data))
    if rc == -1:
        _log.warning("native group_by_key could not allocate scratch; "
                     "falling back to sort")
        return None
    if rc:
        _log.warning("group_by_key contract violation (dictionary does not "
                     "exactly cover the fed keys); falling back to sort")
        return None
    return out_off, out_docs[:n]


def _gather(buf: np.ndarray, starts: np.ndarray,
            lens: np.ndarray) -> np.ndarray:
    """The bytes ``buf[starts[i]:starts[i] + lens[i]]`` of every i, end to
    end, in one vectorised gather."""
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, np.uint8)
    ends = np.cumsum(lens)
    idx = np.arange(total, dtype=np.int64)
    idx += np.repeat(starts - (ends - lens), lens)
    return buf[idx]


class DictUnion:
    """The union of dictionary deltas drained by several streams, taken in
    file order: each key is handed on once, by the first chunk that holds
    it — exactly the rows one stream would have drained.

    Each stream of a fan-out drains its own C++ dictionary, so k streams
    drain most of a vocabulary k times; handing every repeat to the
    driver's :class:`HashDictionary` would cost its per-row Python loop k
    times over.  A repeat is dropped only if its bytes equal the kept
    key's, compared byte for byte; the same hash with other bytes raises
    the same collision error :class:`HashDictionary` does."""

    def __init__(self):
        self._h = np.empty(0, np.uint64)     # kept hashes, ascending
        self._off = np.empty(0, np.int64)    # their bytes' offsets in _blob
        self._len = np.empty(0, np.int64)
        self._blob = np.empty(0, np.uint8)
        #: repeated rows dropped so far
        self.dropped = 0

    def novel(self, delta: HashDictionary) -> HashDictionary:
        """``delta`` less the keys an earlier chunk already handed on."""
        hashes, lens, blob = delta.to_arrays()
        n = len(hashes)
        if n == 0:
            return delta
        lens = np.asarray(lens, np.int64)
        starts = np.cumsum(lens) - lens
        pos = np.searchsorted(self._h, hashes)
        seen = pos < len(self._h)
        seen[seen] = self._h[pos[seen]] == hashes[seen]
        if seen.any():
            rows = np.flatnonzero(seen)
            kept = pos[rows]
            bad = rows[self._len[kept] != lens[rows]]
            if not bad.size:
                mine = _gather(blob, starts[rows], lens[rows])
                theirs = _gather(self._blob, self._off[kept], lens[rows])
                diff = np.flatnonzero(mine != theirs)
                if diff.size:
                    ends = np.cumsum(lens[rows])
                    bad = rows[[np.searchsorted(ends, diff[0], "right")]]
            if bad.size:
                r = int(bad[0])
                k = int(pos[r])
                prev = self._blob[self._off[k]:self._off[k] + self._len[k]]
                mine = blob[starts[r]:starts[r] + lens[r]]
                raise ValueError(
                    f"64-bit hash collision: {prev.tobytes()!r} and "
                    f"{mine.tobytes()!r} both hash to {int(hashes[r]):#x}")
            self.dropped += rows.size
            if rows.size == n:
                return HashDictionary()
            new = ~seen
            hashes, lens = hashes[new], lens[new]
            blob = _gather(blob, starts[new], lens)
            starts = np.cumsum(lens) - lens
        order = np.argsort(hashes, kind="stable")
        at = np.searchsorted(self._h, hashes[order])
        self._h = np.insert(self._h, at, hashes[order])
        self._off = np.insert(self._off, at, starts[order] + len(self._blob))
        self._len = np.insert(self._len, at, lens[order])
        self._blob = np.concatenate([self._blob, blob])
        if len(hashes) == n:
            return delta
        out = HashDictionary()
        out.add_arrays(hashes, lens, blob.tobytes())
        return out


class _Failed:
    """A range's error, held in its place in file order."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class _Fanout:
    """The shared state of one :class:`OrderedScan`: its worker threads
    hold this, never the scan, so dropping the scan can stop them."""

    def __init__(self, ngram, tokenizer, path, chunk_bytes, start_offset,
                 workers, kind, p, emit, union):
        self.ngram, self.tokenizer = ngram, tokenizer
        self.path, self.chunk_bytes, self.kind, self.p = (
            path, chunk_bytes, kind, p)
        self.workers = workers
        self.emit = emit
        self.union = DictUnion() if union else None
        self.cond = threading.Condition()
        self.commit_lock = threading.Lock()
        self.f = None
        self.size = 0
        self.window = 2
        self.next_j, self.next_off = 0, start_offset  # the cut walk
        self.commit_j = 0                 # next range to hand on in order
        self.taken = 0                    # ranges the consumer took
        self.results: dict = {}           # mapped, not yet in order
        self.ready: dict = {}             # in order, not yet taken
        self.halt = False
        self.fatal: BaseException | None = None
        self.produce_s = 0.0
        self.threads: list[threading.Thread] = []
        self.registry = None
        self.tracer = None

    def start(self) -> None:
        from map_oxidize_tpu.obs.context import bind_current, current_obs
        from map_oxidize_tpu.obs.trace import Tracer

        lib = _load_lib()
        f = lib.moxt_file_open(os.fsencode(self.path))
        if not f:
            raise OSError(f"cannot open/mmap {self.path!r}")
        self.f = f
        self.size = int(lib.moxt_file_size(f))
        left = -(-max(self.size - self.next_off, 0) // self.chunk_bytes)
        k = max(1, min(self.workers, left))
        self.window = 2 * k
        obs = current_obs()
        self.tracer = (obs.tracer if obs is not None
                       else Tracer(enabled=False, sink=lambda *a: None))
        if obs is not None:
            self.registry = obs.registry
            self.registry.set("map/scan_workers", k)
        # workers observe under the consumer's job (obs/context.py)
        work = bind_current(self._work)
        self.threads = [threading.Thread(target=work, daemon=True,
                                         name=f"scan-{i}")
                        for i in range(k)]
        for t in self.threads:
            t.start()

    def _claim(self, stream):
        """The next ``(j, off, len)`` of the cut walk, or None when the
        scan is done or stopped; waits while the window is full."""
        with self.cond:
            while (not self.halt and self.next_off < self.size
                   and self.next_j >= self.taken + self.window):
                self.cond.wait()
            if self.halt or self.next_off >= self.size:
                return None
            j, off = self.next_j, self.next_off
            n = int(stream._lib.moxt_range_cut(stream._st, self.f, off,
                                               self.chunk_bytes))
            self.next_j += 1
            # a zero cut would never advance: the serial loop's stall
            self.next_off = off + n if n > 0 else self.size
            return j, off, n

    def _work(self) -> None:
        try:
            stream = NativeStream(self.ngram, self.tokenizer)
        except BaseException as e:  # noqa: BLE001 — the consumer raises it
            with self.cond:
                self.fatal = self.fatal or e
                self.cond.notify_all()
            return
        try:
            map_range, collect, what = stream._range_ops(
                self.kind, self.chunk_bytes, self.p)
            while (claim := self._claim(stream)) is not None:
                j, off, n = claim
                dur = 0.0
                try:
                    if n <= 0:
                        raise RuntimeError(f"native {what} stalled at {off}")
                    with self.tracer.span("map/produce", seq=j) as sp, \
                            stream._lock:
                        consumed = int(map_range(self.f, off))
                        if consumed < 0:
                            _raise_map_error(-consumed)
                        if consumed != n:
                            raise RuntimeError(
                                f"native {what} mapped {consumed} bytes at "
                                f"{off}, its cut is {n}")
                        item = self.emit(collect(), off + n)
                    dur = sp.dur
                except BaseException as e:  # noqa: BLE001 — raised in order
                    item = _Failed(e)
                self._file(j, item, dur)
        finally:
            stream.close()

    def _file(self, j: int, item, dur: float) -> None:
        """File range j's result, then hand on every range that is now
        next in file order (whichever worker completes the prefix does
        it; one at a time, so the dictionary union runs in file order)."""
        with self.cond:
            self.results[j] = item
            self.produce_s += dur
        with self.commit_lock:
            while True:
                with self.cond:
                    cj = self.commit_j
                    if self.halt or cj not in self.results:
                        return
                    item = self.results.pop(cj)
                dropped = 0
                if self.union is not None and not isinstance(item, _Failed):
                    out = item[0]
                    before = self.union.dropped
                    try:
                        out.dictionary = self.union.novel(out.dictionary)
                    except BaseException as e:  # noqa: BLE001
                        item = _Failed(e)
                    dropped = self.union.dropped - before
                if self.registry is not None and self.union is not None:
                    self.registry.count("map/dict_rows_deduped", dropped)
                with self.cond:
                    self.ready[cj] = item
                    self.commit_j = cj + 1
                    if isinstance(item, _Failed):
                        self.halt = True  # claim nothing past an error
                    self.cond.notify_all()

    def take(self):
        """The next range's item in file order; StopIteration at the end."""
        with self.cond:
            j = self.taken
            while j not in self.ready:
                if self.fatal is not None:
                    raise self.fatal
                if self.next_off >= self.size and j >= self.next_j:
                    raise StopIteration
                self.cond.wait()
            item = self.ready.pop(j)
            self.taken = j + 1
            self.cond.notify_all()  # a window slot is free
        if isinstance(item, _Failed):
            raise item.exc
        return item

    def close(self) -> None:
        with self.cond:
            self.halt = True
            self.cond.notify_all()
        me = threading.current_thread()
        for t in self.threads:
            if t is not me:
                t.join()
        self.threads = []
        # unmap only once no worker can be inside a range
        if self.f:
            _load_lib().moxt_file_close(self.f)
            self.f = None


class OrderedScan:
    """A native mmap range scan fanned out over several streams and handed
    back in file order.

    The cut walk is the one-stream iterators' own (``moxt_range_cut``:
    deterministic in ``(offset, chunk_bytes)``), taken one cut at a time
    under a lock: each of k worker threads claims the next cut, maps it on
    a native stream of its own (C runs with the GIL released), and files
    the result.  Results are handed on strictly in file order, so the
    consumer sees the one-stream scan's ``(output, next_offset)`` sequence
    with the same rows, records and offsets chunk by chunk; the
    dictionary deltas pass through a :class:`DictUnion`, so each key
    reaches the consumer once, as one stream would drain it.  At most
    ``2k`` ranges are claimed and not yet taken: that bounds host memory.

    k is ``workers`` capped by the ranges left; with k = 1 this is the
    one-stream schedule on a producer thread.  The job's registry gets the
    gauge ``map/scan_workers`` (k) and the counter
    ``map/dict_rows_deduped``; each range is a ``map/produce`` span on the
    job's tracer, on its worker's thread.

    Errors keep the serial contract: a map error in range j (invalid
    UTF-8, a collision, a stalled cut) is raised after every range before
    j was handed on, and stops the scan.  :meth:`close` — also run at the
    end, on an error and when the scan is dropped — stops the workers,
    joins them and unmaps the file.  ``runs_ahead`` tells a prefetcher
    (:mod:`map_oxidize_tpu.runtime.pipeline`) to pull the scan on its
    consumer's thread instead of wrapping it in a thread of its own."""

    runs_ahead = True

    def __init__(self, ngram: int, tokenizer: str, path: str,
                 chunk_bytes: int, start_offset: int, workers: int,
                 kind: str, p: int = 0, emit=lambda out, off: (out, off),
                 union: bool = False):
        self._state = _Fanout(ngram, tokenizer, path, chunk_bytes,
                              start_offset, workers, kind, p, emit, union)
        self._started = False

    @property
    def produce_s(self) -> float:
        """Summed seconds the workers spent mapping ranges."""
        return self._state.produce_s

    def then(self, fn) -> "OrderedScan":
        """Apply ``fn`` to each item on its worker thread (before the scan
        starts)."""
        emit = self._state.emit
        self._state.emit = lambda out, off: fn(emit(out, off))
        return self

    def __iter__(self):
        return self

    def __next__(self):
        if not self._started:
            self._started = True
            try:
                self._state.start()
            except BaseException:
                self.close()
                raise
        try:
            return self._state.take()
        except BaseException:
            self.close()
            raise

    def close(self) -> None:
        self._state.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class StreamPool:
    """One :class:`NativeStream` per calling thread; file scans fan out
    over streams of their own (:class:`OrderedScan`).

    A single stream serializes on its lock, which would collapse a
    multi-worker map phase onto one core; per-thread streams keep the
    GIL-released C calls truly parallel.  Each stream owns its own C++
    dictionary — the per-chunk deltas from different threads may overlap,
    but ``HashDictionary.update`` is idempotent (and collision-checking), so
    the driver-side union is still exact."""

    def __init__(self, ngram: int = 1, tokenizer: str = "ascii"):
        self.ngram = ngram
        self.tokenizer = tokenizer
        self._tls = threading.local()
        self._streams: list[NativeStream] = []
        self._lock = threading.Lock()

    def get(self) -> NativeStream:
        s = getattr(self._tls, "stream", None)
        if s is None:
            s = NativeStream(self.ngram, self.tokenizer)
            self._tls.stream = s
            with self._lock:
                self._streams.append(s)
        return s

    def map_chunk(self, chunk) -> MapOutput:
        return self.get().map_chunk(chunk)

    def iter_file(self, path: str, chunk_bytes: int, start_offset: int = 0,
                  workers: int = 1) -> OrderedScan:
        """:meth:`NativeStream.iter_file` over ``workers`` streams of the
        scan's own (:class:`OrderedScan`): the same items, in order."""
        return OrderedScan(self.ngram, self.tokenizer, path, chunk_bytes,
                           start_offset, workers, "words", union=True)

    def map_docs(self, chunk, base_doc: int = 0) -> MapOutput:
        return self.get().map_docs(chunk, base_doc)

    def iter_file_docs(self, path: str, chunk_bytes: int,
                       start_offset: int = 0):
        return self.get().iter_file_docs(path, chunk_bytes, start_offset)

    def iter_file_hashes(self, path: str, chunk_bytes: int,
                         start_offset: int = 0,
                         workers: int = 1) -> OrderedScan:
        """:meth:`NativeStream.iter_file_hashes` over ``workers`` streams."""
        return OrderedScan(self.ngram, self.tokenizer, path, chunk_bytes,
                           start_offset, workers, "hashes")

    def map_chunk_hashes(self, chunk) -> MapOutput:
        return self.get().map_chunk_hashes(chunk)

    def map_chunk_hll(self, chunk, p: int):
        return self.get().map_chunk_hll(chunk, p)

    def iter_file_hll(self, path: str, chunk_bytes: int, p: int,
                      start_offset: int = 0,
                      workers: int = 1) -> OrderedScan:
        """:meth:`NativeStream.iter_file_hll` over ``workers`` streams."""
        return OrderedScan(self.ngram, self.tokenizer, path, chunk_bytes,
                           start_offset, workers, "hll", p,
                           emit=lambda out, off: (*out, off))

    def resolve_file(self, path: str, chunk_bytes: int, hashes,
                     early_stop: bool = True):
        return self.get().resolve_file(path, chunk_bytes, hashes, early_stop)

    def close(self) -> None:
        with self._lock:
            for s in self._streams:
                s.close()
            self._streams.clear()


class NativeMapper:
    """Stateless facade: a fresh native state per call, full dictionary
    returned with every chunk.  Used by parity tests and ad-hoc callers;
    drivers use :class:`NativeStream`."""

    def __init__(self, _so_path: str | None = None):
        self._lib = _load_lib()

    def map_ngram(self, chunk: bytes, n: int) -> MapOutput:
        s = NativeStream(n)
        try:
            return s.map_chunk(chunk)
        finally:
            s.close()

    def map_wordcount(self, chunk: bytes) -> MapOutput:
        return self.map_ngram(chunk, 1)

    def map_bigram(self, chunk: bytes) -> MapOutput:
        return self.map_ngram(chunk, 2)


def load_native() -> NativeMapper:
    _load_lib()
    return NativeMapper()
