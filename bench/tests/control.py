"""The controls of the comparisons that decide ``correct``.

A control is the plain reference put in the program's place with one
step down in what the configuration states.  Each must come out not
correct under the same comparison and limit that the harness applies.

* word count (exact counts stated): the reference's counts kept in a
  16-bit signed counter, the narrower value type a later PR could be
  tempted by.  Compared line by line with the true counts.
* k-means (float32 at matmul precision ``highest`` stated): the
  reference Lloyd in float32 with both products at ``high``, three
  bfloat16 passes, written out here so that it is the same on any
  backend.  Compared by the centroid gap to the float64 reference.

``python3 bench/tests/control.py <config> <seed>...`` reads the control
at the configuration's own size, one line per seed (on the chip: the
k-means control runs there).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _jobs(kind: str):
    sys.path.insert(0, BENCH)
    from benchlib.harness import load_module

    return load_module(os.path.join(BENCH, "jobs", kind + ".py"),
                       "bench_job_" + kind)


def wordcount_control(spec: dict, seed: int, workdir: str) -> dict:
    """Lines of the control's result that differ from the reference."""
    wc = _jobs("wordcount")
    corpus = wc.Corpus(spec, seed)
    wc.write_corpus(corpus, os.path.join(workdir, "corpus.txt"),
                    min(8, os.cpu_count() or 1))
    ref = corpus.reference()
    narrow = {w: int(np.int64(c).astype(np.int16)) for w, c in ref.items()}
    return {"wrong_lines": wc.wrong_lines(wc.reference_file(narrow),
                                          wc.reference_file(ref)),
            "distinct": len(ref), "tokens": corpus.tokens}


def _dot_high(a, b):
    """``a @ b`` in three bfloat16 passes with float32 accumulation:
    hi*hi + hi*lo + lo*hi (XLA's ``Precision.HIGH``)."""
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return d(ah, bh) + (d(ah, bl) + d(al, bh))


def lloyd_high(points: np.ndarray, k: int, iters: int) -> np.ndarray:
    """The reference Lloyd (first ``k`` points, empty clusters keep
    their centroid) in float32 with :func:`_dot_high` products."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fit(p, c):
        def step(_, c):
            d2 = (c * c).sum(1) - 2.0 * _dot_high(p, c.T)
            onehot = jax.nn.one_hot(jnp.argmin(d2, 1), k, dtype=jnp.float32)
            sums = _dot_high(onehot.T, p)
            n = onehot.sum(0)
            return jnp.where(n[:, None] > 0,
                             sums / jnp.maximum(n, 1.0)[:, None], c)
        return jax.lax.fori_loop(0, iters, step, c)

    p = jax.device_put(np.asarray(points, np.float32))
    return np.asarray(fit(p, p[:k]))


def kmeans_control(spec: dict, seed: int, workdir: str) -> dict:
    km = _jobs("kmeans")
    path = os.path.join(workdir, "points.npy")
    km.write_points(spec, seed, path, min(8, os.cpu_count() or 1))
    want = km.lloyd64(path, int(spec["k"]), int(spec["iterations"]))
    got = lloyd_high(np.load(path), int(spec["k"]), int(spec["iterations"]))
    return {"centroid_gap": km.centroid_gap(got, want)}


CONTROLS = {"wordcount": wordcount_control, "kmeans": kmeans_control}


def read_control(config: dict, seed: int) -> dict:
    data = os.path.join(BENCH, ".data")
    os.makedirs(data, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=data) as d:
        return CONTROLS[config["job"]](config["data"], seed, d)


def main(argv) -> int:
    with open(os.path.join(BENCH, "configs", argv[0] + ".json")) as f:
        config = json.load(f)
    for seed in argv[1:]:
        print(json.dumps({"config": argv[0], "seed": int(seed),
                          **read_control(config, int(seed))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
