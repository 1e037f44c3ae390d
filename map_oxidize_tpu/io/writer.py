"""Deterministic final-result writer.

Fixes two reference defects by design (SURVEY.md §2 C8): the reference opens
``final_result.txt`` with ``write(true).create(true)`` and **no truncate**
(``/root/reference/src/main.rs:171-175``) — stale trailing bytes survive a
re-run — and writes lines in HashMap iteration order (nondeterministic).
Here the file is atomically replaced (write temp + rename) and rows are
sorted by word ascending, so identical inputs yield byte-identical outputs.

The word-count rows are written from columns (a word blob, word lengths,
counts) with array operations only: one byte-order sort, one decimal
rendering, one buffer, one ``write`` — no per-row Python on any input a
tokenizer makes (see :func:`byte_order` for the one exception).
"""

from __future__ import annotations

import os
from functools import cached_property
from operator import itemgetter
from typing import Iterable

import numpy as np

#: bytes of each word the array sort compares; words that tie on this
#: prefix and run longer are ordered per row (see :func:`byte_order`), so
#: the padded sort matrix is at most ``rows x _PREFIX_CAP`` bytes however
#: long the longest token is
_PREFIX_CAP = 32

_POW10 = np.array([10 ** k for k in range(1, 20)], np.uint64)


class CountColumns:
    """``(word, count)`` rows held as columns: row ``i`` is the word at
    ``blob[sum(lens[:i]):][:lens[i]]`` with count ``counts[i]``, rows in
    any order.  What :func:`write_final_result` writes without per-row
    Python.  ``order`` (the byte-order permutation, sorted once, on first
    use) and ``tie_rows`` come from :func:`byte_order`, which raises when
    two rows hold one word.  Iterating yields the pairs in byte order."""

    def __init__(self, blob, lens, counts):
        self.blob = np.asarray(blob, np.uint8)
        self.lens = np.asarray(lens, np.int64)
        counts = np.asarray(counts)
        if counts.dtype.kind not in "iu":  # int(): truncate, raise on nan
            counts = np.fromiter(map(int, counts.tolist()), np.int64,
                                 counts.size)
        self.counts = counts
        self.offs = np.zeros(self.lens.size + 1, np.int64)
        np.cumsum(self.lens, out=self.offs[1:])
        if self.offs[-1] != self.blob.size or counts.shape != self.lens.shape:
            raise ValueError(
                f"columns disagree: {self.blob.size} word bytes, lengths "
                f"sum to {self.offs[-1]}, {self.lens.size} lengths, "
                f"{counts.size} counts")

    @classmethod
    def from_pairs(cls, pairs: Iterable[tuple[bytes, int]]) -> "CountColumns":
        pairs = list(pairs)
        words = list(map(itemgetter(0), pairs))
        return cls(np.frombuffer(b"".join(words), np.uint8),
                   np.fromiter(map(len, words), np.int64, len(words)),
                   np.fromiter(map(int, map(itemgetter(1), pairs)), np.int64,
                               len(pairs)))

    def __len__(self) -> int:
        return int(self.lens.size)

    @cached_property
    def _byte_order(self) -> tuple[np.ndarray, int]:
        return byte_order(self.blob, self.lens, self.offs)

    @property
    def order(self) -> np.ndarray:
        return self._byte_order[0]

    @property
    def tie_rows(self) -> int:
        return self._byte_order[1]

    def __iter__(self):
        offs, blob = self.offs.tolist(), self.blob.tobytes()
        for i, c in zip(self.order.tolist(), self.counts[self.order].tolist()):
            yield blob[offs[i]:offs[i + 1]], c


def write_final_result(path: str, counts: Iterable[tuple[bytes, int]]) -> int:
    """Write ``"{word} {count}\\n"`` rows (the reference's line format,
    main.rs:178) sorted by word; atomic replace.  Returns row count.
    :class:`CountColumns` are written as they are; any other iterable
    of pairs is made into them first."""
    if not isinstance(counts, CountColumns):
        counts = CountColumns.from_pairs(counts)
    buf = _render(counts)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(buf)
    os.replace(tmp, path)
    return len(counts)


def byte_order(blob: np.ndarray, lens: np.ndarray, offs: np.ndarray
               ) -> tuple[np.ndarray, int]:
    """The permutation that puts the words in Python's ``bytes`` order
    (unsigned bytes, a prefix before its extensions), and how many rows
    needed the per-row tie path.

    Each word's first ``width`` bytes (the longest word's length rounded
    up to 8, at most ``_PREFIX_CAP``), zero-padded, become big-endian
    ``uint64`` columns.
    Rows are sorted on the first column; rows tied there are re-sorted on
    every column, then on length — zero padding ties ``b"a"`` with
    ``b"a\\0"``, and the shorter is the prefix.  Rows still tied on every
    column with a word longer than ``width`` are sorted per row as
    ``bytes``: those are the tie rows."""
    n = int(lens.size)
    if n == 0:
        return np.empty(0, np.intp), 0
    width = min(max(8, -(-int(lens.max()) // 8) * 8), _PREFIX_CAP)
    cols = _prefix_columns(blob, lens, offs, width)
    order = np.argsort(cols[:, 0])
    k0 = cols[order, 0]
    eq = k0[1:] == k0[:-1]
    tied = np.zeros(n, bool)
    tied[1:] = eq
    tied[:-1] |= eq
    pos = np.flatnonzero(tied)
    if pos.size == 0:
        return order, 0
    sub = order[pos]
    sub = sub[np.lexsort([lens[sub]] + [cols[sub, j] for j in
                                       range(cols.shape[1] - 1, -1, -1)])]
    # adjacent rows of ``sub`` equal on every column: equal words when both
    # fit the prefix, else a tie only the full bytes break; runs of such
    # rows are ``sub[bounds[r]:bounds[r + 1]]``
    same = (cols[sub[1:]] == cols[sub[:-1]]).all(axis=1)
    bounds = np.flatnonzero(np.concatenate(([True], ~same, [True])))
    dup = same & (lens[sub[1:]] == lens[sub[:-1]]) & (lens[sub[1:]] <= width)
    runs = np.unique(np.searchsorted(
        bounds, np.flatnonzero(lens[sub] > width), side="right") - 1)
    tie_rows = 0
    for a, b in zip(bounds[runs].tolist(), bounds[runs + 1].tolist()):
        if b - a < 2:
            continue
        words = sorted((blob[offs[i]:offs[i + 1]].tobytes(), i)
                       for i in sub[a:b].tolist())
        sub[a:b] = [i for _, i in words]
        dup[a:b - 1] = [x[0] == y[0] for x, y in zip(words, words[1:])]
        tie_rows += b - a
    if dup.any():
        raise RuntimeError(
            f"readback found {n - int(dup.sum())} distinct words for "
            f"{n} live keys")
    order[pos] = sub
    return order, tie_rows


def _prefix_columns(blob: np.ndarray, lens: np.ndarray, offs: np.ndarray,
                    width: int) -> np.ndarray:
    """``rows x width/8`` big-endian ``uint64`` views of each word's first
    ``width`` bytes, zero-padded."""
    n = lens.size
    pad = np.zeros((n, width), np.uint8)
    long_ = np.flatnonzero(lens > width)
    if long_.size:  # drop each long word's bytes past ``width``
        cut = np.zeros(blob.size + 1, np.int8)
        cut[offs[long_] + width] = -1
        cut[offs[long_ + 1]] = 1
        blob = blob[np.cumsum(cut[:-1], dtype=np.int8) == 0]
    pad[np.arange(width) < np.minimum(lens, width)[:, None]] = blob
    return pad.view(">u8").astype(np.uint64)


def _render(rows: CountColumns) -> np.ndarray:
    """The file's bytes: ``word␠count\\n`` for each row in byte order,
    the count in decimal exactly as ``str(int(count))`` spells it."""
    n, order, lens = len(rows), rows.order, rows.lens
    if n == 0:
        return np.empty(0, np.uint8)
    counts = rows.counts[order]
    if counts.dtype.kind == "i":
        counts = counts.astype(np.int64, copy=False)
        neg = counts < 0
        mag = counts.view(np.uint64).copy()
        # two's complement in uint64: right for int64's minimum too
        np.negative(mag, out=mag, where=neg)
    else:
        neg = np.zeros(n, bool)
        mag = counts.astype(np.uint64)
    ndig = 1 + np.searchsorted(_POW10, mag, side="right")
    width = int(ndig.max())
    # tail = ' ', '-' (negatives), the digits right-aligned, '\n'; a mask
    # keeps each row's own bytes when the matrix is flattened
    tail = np.empty((n, width + 3), np.uint8)
    tail[:, 0] = ord(" ")
    tail[:, 1] = ord("-")
    tail[:, -1] = ord("\n")
    rest = mag.astype(np.uint32) if width <= 9 else mag
    for k in range(width):
        rest, digit = np.divmod(rest, 10)
        tail[:, width + 1 - k] = digit + ord("0")
    keep = np.ones(tail.shape, bool)
    keep[:, 1] = neg
    keep[:, 2:-1] = np.arange(width) >= (width - ndig)[:, None]
    word_len = lens[order]
    tail_len = 2 + ndig + neg
    start = np.cumsum(word_len + tail_len) - (word_len + tail_len)
    out = np.empty(int(rows.offs[-1] + tail_len.sum()), np.uint8)
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    _place(out, rows.blob, lens, start[rank])
    _place(out, tail[keep], tail_len, start + word_len)
    return out


def _place(out: np.ndarray, src: np.ndarray, lens: np.ndarray,
           land: np.ndarray) -> None:
    """Copy run ``i`` of ``src`` (runs of ``lens[i]`` bytes laid end to
    end) to ``out[land[i]:land[i] + lens[i]]``.  Where each byte lands is a
    cumulative sum of ones that jumps at each run's first byte."""
    runs = np.flatnonzero(lens)
    lens, land = lens[runs], land[runs]
    dst = np.ones(src.size, np.int64)
    dst[np.cumsum(lens) - lens] = land - np.concatenate(
        ([0], land[:-1] + lens[:-1] - 1))
    np.cumsum(dst, out=dst)
    out[dst] = src


def write_postings(path: str, postings: dict[bytes, list[int]]) -> int:
    """Inverted-index output: one ``term\\td1 d2 d3...\\n`` line per term,
    terms byte-ascending, doc ids ascending — deterministic and atomic like
    write_final_result.  Returns term count."""
    tmp = f"{path}.tmp.{os.getpid()}"
    n = 0
    with open(tmp, "wb") as f:
        for term in sorted(postings):
            docs = b" ".join(str(d).encode() for d in postings[term])
            f.write(term + b"\t" + docs + b"\n")
            n += 1
    os.replace(tmp, path)
    return n


def write_postings_stream(path: str,
                          items: "Iterable[tuple[bytes, 'object']]"
                          ) -> tuple[int, int]:
    """Streaming variant of :func:`write_postings` for CSR-backed sources:
    ``items`` yields ``(term_bytes, doc_id_array)`` pairs **already in the
    intended term order** with doc ids ascending, and each line streams to
    disk as it is produced — residency is one term's postings, never the
    whole partition (the dict-of-int-lists form boxes every doc id of
    every term at once, which at multi-process scale is exactly the
    blowup the CSR design exists to avoid).  Same line format and atomic
    replace as :func:`write_postings`.  Returns ``(terms, bytes)``
    written."""
    tmp = f"{path}.tmp.{os.getpid()}"
    n = 0
    total = 0
    with open(tmp, "wb") as f:
        for term, docs in items:
            line = (term + b"\t"
                    + b" ".join(b"%d" % d for d in docs.tolist()) + b"\n")
            f.write(line)
            n += 1
            total += len(line)
    os.replace(tmp, path)
    return n, total


def format_top_words(top: list[tuple[bytes, int]], k: int) -> str:
    """The reference's stdout report (main.rs:188-191): ``Top {k} words:``
    then ``{word}: {count}`` lines."""
    lines = [f"Top {k} words:"]
    for word, count in top[:k]:
        lines.append(f"{word.decode('utf-8', 'replace')}: {count}")
    return "\n".join(lines)
