"""Word count — the reference's one and only workload.

Mapper semantics follow ``/root/reference/src/main.rs:94-101`` exactly:
whitespace-split, lowercase, **no punctuation stripping** ("the," and "the"
are distinct keys).  Two tokenizer modes:

* ``ascii`` (default): byte-level — split on ASCII whitespace, lowercase
  ASCII letters.  ``bytes.split()`` / ``bytes.lower()`` are the exact Python
  equivalents of the C++ hot loop, so native and fallback paths stay
  bit-identical.
* ``unicode``: decode UTF-8 and use ``str.split()`` / ``str.lower()`` —
  matching Rust ``split_whitespace()`` + ``to_lowercase()`` (main.rs:96-97)
  for Unicode corpora.  The C++ loop accelerates this mode too, via a UTF-8
  transform pass whose tables are generated from Python's own str.lower() /
  str.isspace() (tests/test_unicode_native.py proves bit-parity).  (Known
  delta: a handful of locale-ish case mappings, e.g. İ, differ between Rust
  and Python; both are Unicode-correct and no English corpus contains them.)

The mapper is a *combiner*: it counts within the chunk (as the reference's
per-chunk ``HashMap`` effectively does) and emits one row per distinct token,
shrinking host->HBM traffic by the chunk's duplication factor.
"""

from __future__ import annotations

from collections import Counter

import numpy as np

from map_oxidize_tpu.api import Mapper, MapOutput, SumReducer
from map_oxidize_tpu.ops.hashing import HashDictionary, moxt64_bytes, split_u64


def tokenize(chunk, mode: str = "ascii") -> list[bytes]:
    """Split + lowercase, per reference semantics (main.rs:96-97)."""
    if not isinstance(chunk, bytes):
        chunk = bytes(chunk)  # splitter may yield memoryviews
    if mode == "ascii":
        return chunk.lower().split()
    if mode == "unicode":
        return [t.encode("utf-8") for t in chunk.decode("utf-8").lower().split()]
    raise ValueError(f"unknown tokenizer mode {mode!r}")


class WordCountMapper(Mapper):
    value_shape = ()
    value_dtype = np.int32
    keys_have_dictionary = True

    def __init__(self, tokenizer: str = "ascii", use_native: bool = True):
        self.tokenizer = tokenizer
        self.use_native = use_native
        self._native = None
        if self.use_native:
            from map_oxidize_tpu.native import bindings

            self._native = bindings.stream_or_none(ngram=1,
                                                   tokenizer=tokenizer)

    def map_file(self, path: str, chunk_bytes: int, start_offset: int = 0,
                 workers: int = 1):
        """Native mmap fast path: ``(MapOutput, next_offset)`` items over the
        file in order, mapped on up to ``workers`` host cores
        (:class:`~map_oxidize_tpu.native.build.OrderedScan`), or None when
        the C++ loop is unavailable (driver falls back to the streaming
        splitter + map_chunk)."""
        if self._native is None:
            return None
        return self._native.iter_file(path, chunk_bytes, start_offset,
                                      workers)

    def map_chunk(self, chunk: bytes) -> MapOutput:
        if self._native is not None:
            # dictionary carries only the delta of newly seen keys — the
            # driver's per-chunk dictionary.update() accumulates the union
            return self._native.map_chunk(chunk)
        toks = tokenize(chunk, self.tokenizer)
        counts = Counter(toks)
        d = HashDictionary()
        hashes = np.empty(len(counts), np.uint64)
        values = np.empty(len(counts), np.int32)
        for i, (tok, c) in enumerate(counts.items()):
            h = moxt64_bytes(tok)
            d.add(h, tok)
            hashes[i] = h
            values[i] = c
        hi, lo = split_u64(hashes)
        return MapOutput(hi=hi, lo=lo, values=values, dictionary=d,
                         records_in=len(toks))


def make_wordcount(tokenizer: str = "ascii", use_native: bool = True):
    """(mapper, reducer) pair for the word-count workload."""
    return WordCountMapper(tokenizer, use_native), SumReducer()
