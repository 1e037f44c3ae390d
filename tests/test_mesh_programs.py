"""The mesh engine's programs are built once per process: back-to-back
word counts on one mesh reuse the merge, the growth chain and the
accumulator init (``parallel/shuffle.py`` ``mesh_program``), other meshes
and the one-chip engine keep programs of their own, and every job stays
exact."""

from collections import Counter

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """~200 KB of 12-word lines over 1,500 words, some capitalised or
    punctuated, so keys merge on lowercasing and the accumulator grows
    from 256 keys through several steps."""
    rng = np.random.default_rng(29)
    words = []
    for i in range(1500):
        w = bytes(rng.choice(list(b"abcdefghij"), int(rng.integers(2, 8))))
        words.append(w.capitalize() if i % 10 == 7
                     else w + b"," if i % 10 == 8 else w)
    ids = rng.zipf(1.1, 12 * 1500) % len(words)
    lines = [b" ".join(words[j] for j in ids[i:i + 12])
             for i in range(0, ids.size, 12)]
    path = tmp_path_factory.mktemp("mesh_programs") / "corpus.txt"
    path.write_bytes(b"\n".join(lines) + b"\n")
    counts = Counter(w.lower() for w in path.read_bytes().split())
    want = b"".join(w + b" " + str(c).encode() + b"\n"
                    for w, c in sorted(counts.items()))
    return str(path), want


def _job(corpus_path: str, out: str, shards: int):
    return run_job(JobConfig(
        input_path=corpus_path, output_path=out, backend="cpu",
        num_shards=shards, mapper="native", top_k=5, metrics=False,
        chunk_bytes=1 << 15, batch_size=1 << 12,
        initial_key_capacity=256), "wordcount")


@pytest.mark.parametrize("shards", [
    pytest.param([4, 4], id="same-mesh-back-to-back"),
    pytest.param([2, 4, 2, 4], id="two-meshes"),
    pytest.param([4, 1, 4, 1], id="one-chip-between-mesh-jobs"),
])
def test_mesh_jobs_reuse_their_programs_and_stay_exact(corpus, tmp_path,
                                                       shards):
    path, want = corpus
    seen = set()
    for i, s in enumerate(shards):
        out = tmp_path / f"final_result_{i}.txt"
        m = _job(path, str(out), s).metrics
        assert out.read_bytes() == want, (i, s)
        if s in seen:
            # a shard count this process has run: nothing new to build,
            # trace or compile
            assert m["compile/total_compiles"] == 0, (i, s, m)
            assert m.get("shuffle/programs_built", 0) == 0, (i, s)
            if s > 1:
                assert m["shuffle/programs_reused"] > 0, (i, s)
        if s > 1:
            assert m["shuffle/exchanges"] > 0
            assert (m.get("shuffle/programs_built", 0)
                    + m.get("shuffle/programs_reused", 0)) > 0
        else:
            assert "shuffle/programs_reused" not in m
        seen.add(s)
