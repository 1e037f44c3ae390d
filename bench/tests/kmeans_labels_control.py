"""The controls of the k-means labels cell (``bench/jobs/kmeans_labels.py``):
what takes the program's place one step down in precision, under the
same ``check`` and limits.  Each must come out not correct.

* ``high``: the reference Lloyd of ``control.py`` (first ``k`` points,
  empty clusters keep their centroid) with both products in three
  bfloat16 passes (XLA's ``Precision.HIGH``), returning its last step's
  assignment and the centroids it was made against.  ``control.py``'s
  ``_dot_high`` cuts its pieces by a round trip through
  ``astype(bfloat16)``, which XLA folds away on a TPU: there it is one
  pass, and flipped exactly the points ``Precision.DEFAULT`` flips
  (1,132,903 of 2*10^7 on a TPU v5e).  The pieces here are cut by a bit mask,
  as the program cuts its own, and flip what ``Precision.HIGH`` flips
  (1,490 above ``tau``, same seed).
* ``bf16``: the program's own ``kmeans_precision: "bf16"``, which the
  readings (``kmeans_labels_readings.py``) run through the job itself.

And one planted fault, which ``step_gap`` alone must catch:

* ``half``: the program's own resident step (``assign_and_sum`` at
  ``highest``) with every odd row weighted 0, so each step sums half the
  points while it still assigns all of them.  Its labels agree with its
  ``assigned_from``, so ``label_off`` reads 0 on it.
"""

from __future__ import annotations

import numpy as np


def dot_high(a, b):
    """``a @ b`` in three bfloat16 passes with float32 accumulation:
    hi*hi + hi*lo + lo*hi, ``hi`` the top 8 significant bits of each
    float32 operand (cut by a mask) and ``lo`` the rest rounded."""
    import jax.numpy as jnp
    from jax import lax

    def split(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32)
        hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return d(ah, bh) + (d(ah, bl) + d(al, bh))


def lloyd_high_labels(points: np.ndarray, k: int, iters: int):
    """``iters`` Lloyd steps with three-pass products: ``(c_T, c_{T-1},
    labels of the last step)`` as NumPy."""
    import jax
    import jax.numpy as jnp

    def step(p, c):
        d2 = (c * c).sum(1) - 2.0 * dot_high(p, c.T)
        cid = jnp.argmin(d2, 1).astype(jnp.int32)
        onehot = jax.nn.one_hot(cid, k, dtype=jnp.float32)
        sums = dot_high(onehot.T, p)
        n = onehot.sum(0)
        return jnp.where(n[:, None] > 0,
                         sums / jnp.maximum(n, 1.0)[:, None], c), cid

    @jax.jit
    def fit(p, c):
        prev = jax.lax.fori_loop(0, iters - 1,
                                 lambda _, cc: step(p, cc)[0], c)
        new, cid = step(p, prev)
        return new, prev, cid

    p = jax.device_put(np.asarray(points, np.float32))
    new, prev, cid = fit(p, p[:k])
    return np.asarray(new), np.asarray(prev), np.asarray(cid)


def lloyd_half_labels(points: np.ndarray, k: int, iters: int):
    """``iters`` steps of the program's resident fit that each sum only
    the even rows: ``(c_T, c_{T-1}, labels of the last step)`` as NumPy."""
    import jax
    import jax.numpy as jnp

    from map_oxidize_tpu.workloads.kmeans import assign_and_sum, exact_pieces

    def step(p, w, pieces, c):
        sums, counts, cid = assign_and_sum(p, c, k, "highest", w, pieces)
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts, 1.0)[:, None], c), cid

    @jax.jit
    def fit(p, c):
        w = (jnp.arange(p.shape[0]) % 2 == 0).astype(jnp.float32)
        pieces = exact_pieces(p)
        prev = jax.lax.fori_loop(0, iters - 1,
                                 lambda _, cc: step(p, w, pieces, cc)[0], c)
        new, cid = step(p, w, pieces, prev)
        return new, prev, cid

    p = jax.device_put(np.asarray(points, np.float32))
    new, prev, cid = fit(p, p[:k])
    return np.asarray(new), np.asarray(prev), np.asarray(cid)
