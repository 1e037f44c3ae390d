"""Fused k-means assignment + partial-sum Pallas TPU kernel.

One grid step loads a ``(TILE_N, d)`` block of points into VMEM, computes
its scores, argmin assignment, and one-hot partial sums entirely on-chip,
and accumulates the ``(k, d+1)`` partials into a VMEM-resident output
block revisited by every grid step (TPU grids run sequentially, so a
same-index output block accumulates without HBM round trips).  HBM
traffic per iteration is ONE read of the points array — no ``(n, k)``
intermediates.

In round 5 this kernel tied the XLA formulation at (n=2M, d=64, k=256)
and did not beat it (that record is in git history; it has not been
re-measured on current code).  The driver therefore keeps the XLA path
(``assign_and_sum``); this kernel stays as the tested template for
shapes XLA might handle worse.

The numerics follow ``assign_and_sum`` per mode, but for the ``highest``
partial sums, which ``assign_and_sum`` takes as three exact-product
passes over ``exact_pieces`` of the points:

* ``highest`` — f32 operands, ``Precision.HIGHEST`` matmuls;
* ``bf16`` — operands cast to bfloat16, f32 accumulation
  (``preferred_element_type``), one native MXU pass per matmul.

Zero-weight rows (``w == 0``) contribute nothing — the same padding
contract as the sharded fit, used here for the internal TILE_N padding
as well.  NOTE ``w`` rides as a ``(n, 1)`` array whose block is
``(TILE_N, 1)`` — a lane-hostile layout that measured +12ms/iter at the
bench shape; callers that can avoid weights entirely (pure tail padding)
should pass ``w=None`` and get the padding mask for free.
"""

from __future__ import annotations

import functools

import numpy as np

#: rows per grid step.  VMEM budget at k=256, d=64, f32: points block
#: 512KB + scores 2MB + one-hot 2MB + accumulator 66KB — well under the
#: ~16MB/core VMEM with room for double-buffered input blocks.
TILE_N = 2048


@functools.lru_cache(maxsize=None)
def _build(n: int, n_pad: int, d: int, k: int, precision: str,
           has_w: bool, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    grid = n_pad // TILE_N

    def kernel(*refs):
        if has_w:
            p_ref, w_ref, c_ref, acc_ref = refs
        else:
            p_ref, c_ref, acc_ref = refs
        c = c_ref[:]                                   # (k, d) f32
        p = p_ref[:]                                   # (TILE_N, d) f32
        # transpose-free contractions: an explicit .T materializes a real
        # lane/sublane shuffle per grid step under Mosaic (XLA folds it
        # into the dot); dot_general contracts the axes in place
        if precision == "bf16":
            pm = p.astype(jnp.bfloat16)
            cm = c.astype(jnp.bfloat16)

            def dot(a, b, dims):
                return lax.dot_general(
                    a, b, (dims, ((), ())),
                    preferred_element_type=jnp.float32)
        else:
            pm, cm = p, c

            def dot(a, b, dims):
                return lax.dot_general(
                    a, b, (dims, ((), ())),
                    precision=lax.Precision.HIGHEST)
        # scores: contract d with d -> (TILE_N, k)
        d2 = -2.0 * dot(pm, cm, ((1,), (1,))) + (c * c).sum(1)[None, :]
        cid = jnp.argmin(d2, axis=1)                   # (TILE_N,)
        hit = cid[:, None] == lax.broadcasted_iota(jnp.int32, (TILE_N, k), 1)
        if has_w:
            oh = hit.astype(jnp.float32) * w_ref[:]    # (TILE_N, k)
        else:
            # tail-padding mask computed in place (sublane iota of the
            # GLOBAL row index): no weight input, no lane-hostile
            # (TILE_N, 1) block
            row = (pl.program_id(0) * TILE_N
                   + lax.broadcasted_iota(jnp.int32, (TILE_N, k), 0))
            oh = jnp.where(hit & (row < n), 1.0, 0.0)
        part = jnp.concatenate(
            [dot(oh.astype(pm.dtype), pm, ((0,), (0,))),  # (k, d) on MXU
             oh.sum(0)[:, None]], axis=1)              # + counts column

        @pl.when(pl.program_id(0) == 0)
        def _():
            acc_ref[:] = part

        @pl.when(pl.program_id(0) > 0)
        def _():
            acc_ref[:] = acc_ref[:] + part

    in_specs = [pl.BlockSpec((TILE_N, d), lambda i: (i, 0))]
    if has_w:
        in_specs.append(pl.BlockSpec((TILE_N, 1), lambda i: (i, 0)))
    in_specs.append(pl.BlockSpec((k, d), lambda i: (0, 0)))
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        # every grid step maps to the SAME output block -> it stays
        # VMEM-resident and accumulates; one HBM write at the end
        out_specs=pl.BlockSpec((k, d + 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, d + 1), jnp.float32),
        interpret=interpret,
    )


def fused_assign_sum(p, c, k: int, precision: str = "highest", w=None,
                     interpret: bool = False):
    """The partial sums of :func:`workloads.kmeans.assign_and_sum` on
    TPU: returns ``(sums (k, d), counts (k,))`` (no assignment) with the
    same per-mode numerics, one pass over the points, no (n, k) HBM
    intermediates.
    Traceable under jit/fori_loop/shard_map (grid count is static in the
    padded row count).  ``w=None`` masks the internal tail padding in
    place; pass explicit weights only when rows genuinely carry them."""
    import jax.numpy as jnp

    n, d = p.shape
    n_pad = -(-n // TILE_N) * TILE_N
    if n_pad != n:
        p = jnp.pad(p, ((0, n_pad - n), (0, 0)))
        if w is not None:
            w = jnp.pad(w, (0, n_pad - n))
    pc = _build(n, n_pad, d, int(k), precision, w is not None, interpret)
    acc = pc(p, w[:, None], c) if w is not None else pc(p, c)
    return acc[:, :d], acc[:, d]
