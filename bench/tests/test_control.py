"""The controls fail the comparisons that decide ``correct``.

Word count: the 16-bit counter fails the exact comparison (limit 0) at
a size this CPU holds.  K-means: the three-pass control's gap depends on
the cell's size; its readings at the cell's own size come from the chip
(``python3 bench/tests/control.py kmeans-hibench-large <seeds>``,
PERF.md §2).  Here: that the three-pass product is what XLA's ``high``
computes, between float32 and one bfloat16 pass."""

import os

import numpy as np
import pytest

import control
from benchlib.harness import BENCH, load_json

SEEDS = [2 ** 31 + 101, 2 ** 31 + 102, 2 ** 31 + 103]


@pytest.mark.parametrize("seed", SEEDS)
def test_wordcount_control_is_not_correct(seed, tmp_path):
    spec = dict(load_json(os.path.join(BENCH, "configs",
                                       "wordcount-enwik9.json"))["data"],
                corpus_bytes=3_000_000, vocab=4000)
    got = control.wordcount_control(spec, seed, str(tmp_path))
    assert got["wrong_lines"] > 0        # the cell's limit is 0


def test_three_pass_product_lies_between_float32_and_one_pass():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    a = rng.normal(0, 20, (4096, 20)).astype(np.float32)
    b = rng.normal(0, 20, (20, 10)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    high = np.asarray(control._dot_high(jnp.asarray(a), jnp.asarray(b)))
    one = np.asarray(jnp.dot(jnp.asarray(a, jnp.bfloat16),
                             jnp.asarray(b, jnp.bfloat16),
                             preferred_element_type=jnp.float32))
    f32 = a @ b
    err = [np.abs(x - exact).max() for x in (f32, high, one)]
    assert err[0] < err[1] < err[2] / 50
