"""Word count over a seeded Zipf corpus, the shape of HiBench
``micro/wordcount`` at enwik9's size.

Generator and reference are copied from ``chip_smoke.py`` (PR 21:
``make_vocab``, ``write_corpus``, ``reference_file``; ``check_wordcount``
became ``wrong_lines`` and ``Job.check``) so that later PRs to the
program cannot move them.  Three changes: the shape numbers come from
the configuration's file; the vocabulary is the same for every seed;
blocks of draws are made in parallel threads, each from its own
generator seeded by ``(seed, block)``.  The draw is the original's
inverse-CDF search, through a guide table that gives the same index as
``np.searchsorted(cdf, u, "right")``.

The reference is the generator's own draws, merged by lowercased bytes:
what the reference program (``/root/reference/src/main.rs:94-101``:
lowercase, split on whitespace, punctuation kept) counts in this text.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: guide-table size of the inverse-CDF draw: 2^23 buckets keep the
#: fix-up loop to a step or two at a quarter-million words
_GUIDE = 1 << 23


class Corpus:
    """The corpus of one configuration and seed: vocabulary, written
    text, and the counts of every word drawn."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.seed = int(seed)
        self.vocab = make_vocab(spec)
        self.path = ""
        self.counts = np.zeros(len(self.vocab), np.int64)
        self.nbytes = 0

    @property
    def tokens(self) -> int:
        return int(self.counts.sum())

    def reference(self) -> dict[bytes, int]:
        """Counts merged by lowercased bytes (the job's key)."""
        ref: dict[bytes, int] = {}
        for w, c in zip(self.vocab, self.counts.tolist()):
            if c:
                key = w.lower()
                ref[key] = ref.get(key, 0) + c
        return ref


def make_vocab(spec: dict) -> list[bytes]:
    """Random lowercase words of ``word_letters`` letters; of every ten,
    one capitalised, one with a trailing comma, one with a trailing
    period (chip_smoke.make_vocab).  The same words at the same Zipf
    ranks for every seed: seeds that drew their own words ran up to 7%
    apart in one chip's rate (PR 22), so the seed draws only the text."""
    lo, hi = spec["word_letters"]
    rng = np.random.default_rng(0)
    alphabet = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
    vocab = []
    for i, length in enumerate(rng.integers(lo, hi + 1, size=spec["vocab"])):
        w = rng.choice(alphabet, size=int(length)).tobytes()
        r = i % 10
        if r == 7:
            w = w.capitalize()
        elif r == 8:
            w += b","
        elif r == 9:
            w += b"."
        vocab.append(w)
    return vocab


def write_corpus(corpus: Corpus, path: str, workers: int = 8) -> None:
    """Write about ``corpus_bytes`` of whole lines to ``path``: Zipf
    draws over the vocabulary, ``words_per_line`` words a line, and
    count every word drawn into ``corpus.counts``."""
    spec = corpus.spec
    V = len(corpus.vocab)
    wpl = int(spec["words_per_line"])
    nbytes = int(spec["corpus_bytes"])
    p = np.arange(1, V + 1, dtype=np.float64) ** -float(spec["zipf_s"])
    cdf = np.cumsum(p / p.sum())
    guide = np.searchsorted(cdf, np.arange(_GUIDE) / _GUIDE,
                            side="right").astype(np.int32)
    lens = np.array([len(w) for w in corpus.vocab])
    # one row per word: its bytes, then a separator slot at lens[i]
    table = np.zeros((V, int(lens.max()) + 1), np.uint8)
    for i, w in enumerate(corpus.vocab):
        table[i, :len(w)] = np.frombuffer(w, np.uint8)
    keep = np.arange(table.shape[1])[None, :] <= lens[:, None]
    block = wpl * (1 << 16)
    sep = np.full(block, ord(" "), np.uint8)
    sep[wpl - 1::wpl] = ord("\n")

    def make_block(b: int):
        u = np.random.default_rng([corpus.seed, 1, b]).random(block)
        ids = guide[(u * _GUIDE).astype(np.int64)]
        while True:  # step up to searchsorted(cdf, u, "right")
            up = cdf[ids] <= u
            if not up.any():
                break
            ids = ids + up
        ids = np.minimum(ids, V - 1)
        rows = table[ids]
        rows[np.arange(block), lens[ids]] = sep
        return ids, rows[keep[ids]]

    counts = np.zeros(V, np.int64)
    written = 0
    b = 0
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f, ThreadPoolExecutor(workers) as ex:
        while written < nbytes:
            wave = ex.map(make_block, range(b, b + 2 * workers))
            b += 2 * workers
            for ids, blob in wave:
                if written + blob.size > nbytes:
                    # cut at the last whole line that fits the target
                    nl = np.flatnonzero(blob[:nbytes - written] == 10)
                    blob = blob[:nl[-1] + 1] if nl.size else blob[:0]
                    ids = ids[:nl.size * wpl]
                f.write(blob.data)
                counts += np.bincount(ids, minlength=V)
                written += blob.size
                if blob.size == 0 or written >= nbytes:
                    written = nbytes
                    break
        # on disk before the window: the kernel would write the dirty
        # pages back some 30 s on, inside it
        f.flush()
        os.fsync(f.fileno())
    corpus.path = path
    corpus.counts = counts
    corpus.nbytes = os.path.getsize(path)


def reference_file(ref: dict[bytes, int]) -> bytes:
    """``final_result.txt`` as the reference program writes it: one
    ``word count`` line per key, sorted by word."""
    return b"".join(w + b" " + str(c).encode() + b"\n"
                    for w, c in sorted(ref.items()))


def top_k(ref: dict[bytes, int], k: int) -> list[tuple[bytes, int]]:
    """Count descending, word ascending (the reference's top-k order)."""
    return sorted(ref.items(), key=lambda kv: (-kv[1], kv[0]))[:k]


def wrong_lines(got: bytes, want: bytes) -> int:
    """Words whose line differs between two result files: missing,
    extra, or with another count.  0 when the bytes are equal."""
    if got == want:
        return 0

    def parse(blob: bytes) -> dict[bytes, bytes]:
        out = {}
        for line in blob.split(b"\n"):
            if line:
                w, _, c = line.rpartition(b" ")
                out[w] = c
        return out

    a, b = parse(got), parse(want)
    n = sum(1 for w in a.keys() | b.keys() if a.get(w) != b.get(w))
    return max(n, 1)  # same lines in another order still differ


class Job:
    """One cell's word-count jobs: the corpus made from the seed, each
    job through ``map_oxidize_tpu.runtime.run_job`` writing its own
    ``final_result.txt``, and the comparison of every window job with
    the generator's counts.  The deployment's optional ``job_config``
    holds further ``JobConfig`` fields (the CPU tests' tiny cells use it
    for small chunks)."""

    def __init__(self, config: dict, seed: int, workdir: str,
                 backend: str = "tpu"):
        self.config = config
        self.seed = seed
        self.workdir = workdir
        self.backend = backend
        self.deploy = config["deployment"]
        self.corpus = Corpus(config["data"], seed)
        self.input_bytes = 0

    def setup(self) -> None:
        write_corpus(self.corpus, os.path.join(self.workdir, "corpus.txt"),
                     min(8, os.cpu_count() or 1))
        self.input_bytes = self.corpus.nbytes

    def run(self, i: int) -> dict:
        from map_oxidize_tpu.config import JobConfig
        from map_oxidize_tpu.runtime import run_job

        out = os.path.join(self.workdir, f"result_{i}.txt")
        r = run_job(JobConfig(
            input_path=self.corpus.path, output_path=out,
            backend=self.backend, num_shards=int(self.deploy["num_shards"]),
            mapper=self.deploy["mapper"], top_k=int(self.config["top_k"]),
            metrics=False, **self.deploy.get("job_config", {})), "wordcount")
        return {"metrics": r.metrics, "out": out,
                "top": [(bytes(w), int(c)) for w, c in r.top]}

    def check(self, records: list[dict]) -> list[tuple[str, float, float]]:
        """Worst job of the window: lines of ``final_result.txt`` that
        differ from the reference, top-k entries that differ, jobs whose
        ``records_in`` is not the corpus's token count, and jobs that ran
        another mapper than the configuration states."""
        ref = self.corpus.reference()
        want = reference_file(ref)
        want_top = top_k(ref, int(self.config["top_k"]))
        lines = top = records_off = mapper_off = 0
        for rec in records:
            if rec.get("failed"):
                continue
            with open(rec["out"], "rb") as f:
                lines = max(lines, wrong_lines(f.read(), want))
            top = max(top, sum(1 for a, b in zip(rec["top"], want_top)
                               if a != b) + abs(len(rec["top"])
                                                - len(want_top)))
            m = rec["metrics"]
            records_off += m.get("records_in") != self.corpus.tokens
            mapper_off += m.get("map/mapper") != self.deploy["mapper"]
        return [("wrong_lines", lines, 0), ("wrong_top", top, 0),
                ("records_off", records_off, 0),
                ("mapper_off", mapper_off, 0)]
