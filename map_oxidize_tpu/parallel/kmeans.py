"""Multi-chip k-means: data-parallel device iterations over the mesh.

The TPU-natural formulation of BASELINE config #5 at scale: points are
sharded row-wise across the mesh ONCE, centroids stay replicated, and each
iteration is pure per-shard MXU work (distance matmul, one-hot matmul
partial sums) joined by a single ``psum`` of the ``(k, d+1)`` partials —
the collective moves centroids, never points.  This is the same
owner-computes pattern as the word-count shuffle with the exchange
degenerated to a reduction: integer centroid keys are dense, so the hash
bucket routing of :mod:`map_oxidize_tpu.parallel.shuffle` would be overkill.

Compare the host streaming path (:func:`workloads.kmeans.kmeans_iteration`),
which re-reads and re-ships every point each iteration: here the transfer is
paid once and ``iters`` iterations amortize it — the win grows linearly with
iteration count on the measured ~30 MB/s host->device link.

For datasets larger than even the MESH's aggregate HBM, streaming and
sharding compose (:func:`kmeans_fit_streamed` + :func:`make_stream_step_fn`,
VERDICT r5 missing #1): fixed-row chunks stream as per-shard blocks and the
same one-psum iteration body runs per chunk, prefetch-pipelined so host
block prep hides behind the mesh's work.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from map_oxidize_tpu.parallel.mesh import SHARD_AXIS, make_mesh
from map_oxidize_tpu.utils.jax_compat import device_put_handoff, shard_map


def make_fit_fn(mesh, k: int, d: int, loop_iters: int,
                precision: str = "highest"):
    """The jitted sharded iteration program: per-shard assign (distance
    matmul) + one-hot partial sums (both from
    :func:`workloads.kmeans.assign_and_sum` — the single-device step's
    exact numerics, including the ``--kmeans-precision`` bf16 mode),
    joined by ONE ``(k, d+1)`` psum per iteration.  Returns ``(c_T,
    c_{T-1}, labels)``: the replicated final centroids, the replicated
    centroids the last step assigned against, and that step's row-sharded
    ``(n_pad,)`` int32 assignment.  Shared verbatim by the
    single-controller sharded fit and the multi-process runner (same XLA
    program, different array assembly), so the paths cannot drift."""
    from map_oxidize_tpu.workloads.kmeans import assign_and_sum, exact_pieces

    def fit(p, w, c):
        """Per-shard body: p, w are this shard's block; c is replicated."""
        pieces = exact_pieces(p) if precision == "highest" else None

        def step(c):
            sums, counts, cid = assign_and_sum(p, c, k, precision, w, pieces)
            # ONE collective per iteration: the (k, d+1) partials
            joined = lax.psum(
                jnp.concatenate([sums, counts[:, None]], axis=1), SHARD_AXIS)
            sums, counts = joined[:, :d], joined[:, d]
            return jnp.where(counts[:, None] > 0,
                             sums / jnp.maximum(counts[:, None], 1.0), c), cid

        # loop_iters - 1 steps that drop their assignment, then the last,
        # which keeps this shard's rows' labels
        prev = lax.fori_loop(0, loop_iters - 1, lambda _, cc: step(cc)[0], c)
        new, cid = step(prev)
        return new, prev, cid

    from map_oxidize_tpu.obs.compile import observed_jit

    return observed_jit("kmeans/fit_sharded", jax.jit(shard_map(
        fit, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(), P(), P(SHARD_AXIS)),
    )), tag=(k, loop_iters, precision))


#: cache of jitted streamed-step executables keyed by
#: (mesh, k, precision, B, first, last) — the same persistence rationale
#: as workloads.kmeans._make_jitted: a fresh shard_map closure per fit
#: call would recompile every run and break the bench's
#: warm-run-then-timed-run discipline
_STREAM_STEPS: dict = {}


def make_stream_step_fn(mesh, k: int, precision: str = "highest",
                        dispatch_batch: int = 1):
    """The streamed twin of :func:`make_fit_fn`, scan-batched: ONE jitted
    program retires ``dispatch_batch`` (B) logical chunks per launch — a
    ``lax.scan`` over a stacked ``(B, chunk_rows, d)`` block whose scan
    body is the per-chunk assign + one-hot partial sums
    (:func:`workloads.kmeans.assign_and_sum`, the exact numerics of every
    other path) joined by ONE ``(k, d+1)`` psum per chunk.  It serves
    streamed single-device (a 1-device mesh, where the psum degenerates),
    streamed sharded, and, because the mesh may span processes, the
    multi-process runner.

    Returns ``step(block, w, c, acc, first, last)`` where ``block``/``w``
    are the ``(B, chunk_rows, d)`` / ``(B, chunk_rows)`` stacked
    row-sharded chunks and their 0/1 padding weights (a short tail block
    is padded to the SAME B with zero-weight chunks — one compiled shape
    regardless of the chunk count), ``c`` the replicated centroids and
    ``acc`` the replicated ``(k, d+1)`` running partials.  ``first``/
    ``last`` are the dispatch-folding flags (static): the accumulator
    init folds into the first block's scan and the centroid update into
    the last block's, so one iteration costs exactly ``ceil(n_chunks/B)``
    launches — B-fold fewer trips over the per-launch dispatch floor.

    The accumulator carries THROUGH the scan (init = the incoming acc,
    zeros on the first block), so the floating-point accumulation order
    is the strict left fold of per-chunk partials for ANY B — outputs
    are bit-identical across B (pinned by tests/test_dispatch_batch.py),
    which is why B is neither checkpoint nor ledger identity."""

    def step(block, w, c, acc, first: bool, last: bool,
             chunks: int | None = None):
        key = (mesh, k, precision, int(dispatch_batch), bool(first),
               bool(last))
        fn = _STREAM_STEPS.get(key)
        if fn is None:
            fn = _build_stream_step(mesh, k, precision, *key[3:])
            _STREAM_STEPS[key] = fn
        # chunks = the REAL chunk count of this block (a padded tail
        # carries dead zero-weight chunks): keeps the per-chunk
        # dispatch-gap attribution consistent with the comms
        # accounting, which also excludes dead chunks
        return fn(block, w, c, acc, observed_chunks=chunks)

    return step


def _build_stream_step(mesh, k: int, precision: str, batch: int,
                       first: bool, last: bool):
    from map_oxidize_tpu.workloads.kmeans import assign_and_sum

    def body(blocks, ws, c, acc):
        # per-shard: blocks (B, chunk_rows/S, d), ws (B, chunk_rows/S),
        # c (k, d) and acc (k, d+1) replicated
        def chunk_step(a, xs):
            chunk, w = xs
            sums, counts, _ = assign_and_sum(chunk, c, k, precision, w)
            part = lax.psum(
                jnp.concatenate([sums, counts[:, None]], axis=1),
                SHARD_AXIS)
            return a + part, None

        # carry the running partials through the scan: the left-fold
        # accumulation order is identical for every B (and a zero-weight
        # padded chunk contributes an exact-zero part)
        acc, _ = lax.scan(chunk_step,
                          jnp.zeros_like(acc) if first else acc,
                          (blocks, ws))
        if not last:
            return acc
        d = c.shape[1]
        sums, counts = acc[:, :d], acc[:, d]
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts[:, None], 1.0), c)

    from map_oxidize_tpu.obs.compile import observed_jit

    # donation: acc (arg 3) is donated when it came from the previous
    # block's output — NOT on the first block, whose acc input is the
    # ignored zero placeholder reused across iterations, and NOT on the
    # last, whose (k, d) output cannot reuse the (k, d+1) buffer anyway
    # (donating there only warns).  The staged block itself is NOT
    # donated: its (B, rows, d) buffer can alias none of the small
    # replicated outputs, so donation would only warn — the caller
    # dropping its reference after the step is what frees the block's
    # HBM at dispatch completion, keeping the device at the executing
    # block plus the prefetched one under double buffering.  Weights are
    # never donated: full blocks share one cached device-resident
    # all-ones array.
    donate = (3,) if not (first or last) else ()
    # check_vma off: shard_map's replication checker cannot
    # yet follow a psum-carrying scan (jax suggests exactly this
    # workaround); the out_specs=P() contract still enforces the
    # replicated output layout
    return observed_jit("kmeans/stream_step", jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(None, SHARD_AXIS), P(None, SHARD_AXIS), P(), P()),
        out_specs=P(), check_vma=False,
    ), donate_argnums=donate),
        tag=(k, precision, batch, first, last),
        chunks_of=lambda *a, **kw: a[0].shape[0])


def kmeans_fit_streamed(path: str, centroids, iters: int = 1,
                        chunk_rows: int = 1 << 21, mesh=None,
                        num_shards: int = 0, backend: str = "auto",
                        device=None, precision: str = "highest",
                        timings: dict | None = None, on_iter=None,
                        pipeline_depth: int = 2, obs=None,
                        dispatch_batch: int = 0):
    """Beyond-HBM k-means THROUGH the mesh (SURVEY §7 hard part (c) as
    prescribed: streaming *through the mesh*, not through one chip):
    fixed-row chunks from a memory-mapped ``.npy`` stream as per-shard
    blocks, scan-batched ``dispatch_batch`` (B) chunks per launch
    (``device_put`` against the row sharding splits each stacked block
    across the mesh), and every block runs :func:`make_stream_step_fn`'s
    one-psum-per-chunk scanned step.  With a 1-device mesh this IS the
    single-device streamed fit — same program, psum over a singleton
    axis — so the two regimes cannot drift (``workloads.kmeans.
    kmeans_fit_streamed_device`` is now a thin wrapper over this).

    ``dispatch_batch``: 0 = auto — resolved at job start by
    :func:`~map_oxidize_tpu.runtime.dispatch.resolve_dispatch_batch`
    from the measured per-launch floor, the measured host-produce of one
    chunk (probed here: the first chunk's fault-in+copy, whose pages
    stay hot for block 0's real fill), and the measured-or-roofline
    device-compute per chunk, capped by the HBM budget; the chosen B and
    its inputs are recorded as ``dispatch/*`` gauges (ledger evidence)
    and in ``timings``.

    The host block prep (mmap fault-in into a fresh ``(B, chunk_rows,
    d)`` staging buffer + optional bf16 cast + tail weight mask) and the
    async ``device_put`` both run in a :class:`~map_oxidize_tpu.runtime.
    pipeline.BlockStager` producer thread at ``pipeline_depth``, so
    staging AND transferring block i+1 overlap block i's dispatch+MXU
    work; each staged buffer's ownership passes to jax at the put
    (alias-safe on every backend) and the stager's queue backpressure
    bounds the in-flight blocks, so host staging memory and HBM both
    stay flat at pipeline-depth+1 blocks.  ``timings`` receives
    ``feed_s`` (the full block-loop wall), plus ``feed_wait_s`` and
    ``overlap_ratio`` from the stager — the measurable form of "host
    time hidden behind device dispatch".

    ``device=`` (mutually exclusive with ``mesh``/``num_shards``) pins a
    1-device mesh over that device — the single-chip entry point."""
    import time

    from map_oxidize_tpu.runtime.dispatch import (
        has_cached_auto,
        record_dispatch_batch,
        resolve_dispatch_batch,
    )
    from map_oxidize_tpu.runtime.pipeline import (
        BlockStager,
        chunk_groups,
        staged_blocks,
    )

    # framework bring-up window: mesh/backend init, the step build, and
    # the initial device puts all happen INSIDE the driver's iterate
    # phase (unlike the fold engines, which construct before the first
    # phase and land in the attribution ledger's pre-phase ``setup``
    # gauge) — measured here so it feeds the setup bucket instead of
    # the unattributed remainder.  The produce probe's wall is excluded:
    # it counts into host_produce via record_dispatch_batch.
    t_init = time.perf_counter()
    if mesh is None:
        if device is not None:
            mesh = Mesh(np.asarray([device]), (SHARD_AXIS,))
        else:
            mesh = make_mesh(num_shards, backend)
    S = mesh.shape[SHARD_AXIS]
    pts = np.load(path, mmap_mode="r")
    n, d = pts.shape
    centroids = np.asarray(centroids, np.float32)
    k = centroids.shape[0]
    cast = None
    if precision == "bf16":
        # cast host-side BEFORE the put: halves the link bytes (the
        # per-iteration re-transfer is this path's structural cost)
        import ml_dtypes

        cast = ml_dtypes.bfloat16
    # never compile/pad past the dataset, and keep shard_map's S | rows
    # invariant: one compiled shape, rows a multiple of the shard count
    chunk_rows = min(chunk_rows, -(-n // S) * S)
    chunk_rows = -(-chunk_rows // S) * S
    starts = list(range(0, n, chunk_rows))
    n_chunks = len(starts)
    buf_dtype = np.dtype(cast) if cast is not None else np.dtype(np.float32)

    # auto-B inputs measured at job start: the first chunk's fault-in +
    # copy is the host-produce probe (its pages stay hot in the page
    # cache, so block 0's real fill re-reads them cheaply).  Skipped
    # when the resolution is already memoized — the memo ignores a
    # fresh probe, and a warm resident server must not pay a full-chunk
    # copy per job for a discarded measurement.
    chunk_device_bytes = chunk_rows * d * buf_dtype.itemsize
    flops_per_chunk = 4.0 * chunk_rows * k * d
    produce_ms = None
    if (dispatch_batch == 0 and n_chunks > 1
            and not has_cached_auto("kmeans/stream_step",
                                    chunk_device_bytes, flops_per_chunk)):
        t0 = time.perf_counter()
        # a REAL fault-in + copy (+ cast): np.array forces the read —
        # an asarray of a memmap slice is a view and would measure ~0
        np.array(pts[:chunk_rows], dtype=buf_dtype)
        produce_ms = (time.perf_counter() - t0) * 1e3
    B, binfo = resolve_dispatch_batch(
        dispatch_batch, n_chunks=n_chunks,
        chunk_device_bytes=chunk_device_bytes,
        flops_per_chunk=flops_per_chunk,
        produce_ms=produce_ms, program="kmeans/stream_step")
    if obs is not None:
        record_dispatch_batch(obs.registry, B, binfo,
                              fresh_probe_ms=produce_ms)
    n_blocks = -(-n_chunks // B)

    row = NamedSharding(mesh, P(None, SHARD_AXIS))  # (B, rows, d) blocks
    rep = NamedSharding(mesh, P())
    step = make_stream_step_fn(mesh, k, precision, B)
    ones_w = jax.device_put(np.ones((B, chunk_rows), np.float32), row)
    # reused (never donated) first-step acc placeholder; its values are
    # ignored by the first=True program
    zero_acc = jax.device_put(np.zeros((k, d + 1), np.float32), rep)

    tail_w = [None]  # cached device weights of the one partial block

    def _stage(group):
        """Producer half of one block: fault in + copy (+ cast) each
        chunk into a fresh staging buffer, mask the tail, issue the
        async put.  Runs in the stager thread, overlapping the
        consumer's step.  The buffer's ownership passes to jax at the
        put (device_put_handoff: the CPU backend zero-copy-aliases
        large host buffers and an accelerator's DMA read is async, so
        reuse would corrupt in-flight blocks); host staging memory
        stays flat at pipeline-depth+1 blocks via the stager's queue
        backpressure."""
        # np.empty, not zeros: a full block overwrites every byte with
        # the mmap copy, and a blanket memset would double host write
        # traffic per block — inflating exactly the produce time the
        # auto-B roofline consumes.  Only the PADDED regions are zeroed
        # below: uninitialized memory can hold NaN/Inf bit patterns, and
        # 0-weight * NaN is NaN in the partial sums.
        buf = np.empty((B, chunk_rows, d), buf_dtype)
        for i, start in enumerate(group):
            stop = min(start + chunk_rows, n)
            buf[i, :stop - start] = pts[start:stop]
            if stop - start < chunk_rows:
                buf[i, stop - start:] = 0  # the last real chunk's pad rows
        if len(group) < B:
            buf[len(group):] = 0  # whole dead chunks of a short tail block
        partial = (len(group) < B
                   or group[-1] + chunk_rows > n)
        if partial:
            # pad to the ONE compiled (B, chunk_rows) shape; the zero
            # WEIGHT is what nulls a padding row or a padding chunk (a
            # zero vector alone would still count 1 toward whichever
            # centroid it lands on).  The tail pattern is identical
            # every iteration, so its device weights are staged once
            # and reused.
            if tail_w[0] is None:
                w_np = np.zeros((B, chunk_rows), np.float32)
                for i, start in enumerate(group):
                    w_np[i, :min(start + chunk_rows, n) - start] = 1.0
                tail_w[0] = jax.device_put(w_np, row)
            w_dev = tail_w[0]
        else:
            w_dev = ones_w
        return device_put_handoff(buf, row), w_dev, len(group)

    c_dev = jax.device_put(centroids, rep)
    if obs is not None:
        init_ms = (time.perf_counter() - t_init) * 1e3 - (produce_ms or 0)
        if init_ms > 0:
            obs.registry.count("attrib/init_ms", init_ms)
    wait_s = produce_s = 0.0
    t0 = time.perf_counter()
    # ONE stager spans every iteration: data blocks do not depend on the
    # evolving centroids, so the producer stages (and async-puts)
    # iteration i+1's first block while iteration i's tail block still
    # computes — closing the inter-iteration staging bubble a
    # per-iteration prefetcher restarts into.  Memory stays at
    # depth+1 staged blocks regardless of the iteration count.
    all_groups = chunk_groups(starts, B) * iters
    pf = None
    if pipeline_depth > 1 and len(all_groups) > 1:
        # obs rides in: the stager live-feeds pipeline/produce_ms and
        # pipeline/feed_wait_ms per block (the attribution ledger's
        # feed-wait bucket and the heartbeat's where= token read them
        # mid-iteration, not at job end)
        pf = BlockStager(all_groups, _stage, depth=pipeline_depth - 1,
                         name="kmeans/stage", obs=obs)
        blocks_it = iter(pf)
    else:
        blocks_it = staged_blocks(all_groups, _stage)
    it = 0
    acc = zero_acc
    for gi, (b_dev, w_dev, n_real) in enumerate(blocks_it):
        bi = gi % n_blocks
        out = step(b_dev, w_dev, c_dev, acc,
                   bi == 0, bi == n_blocks - 1, chunks=n_real)
        if obs is not None and S > 1:
            # comms observatory: one (k, d+1) partials psum per
            # LOGICAL chunk — recorded per real chunk so the
            # accounting identity (and the comms/*/bytes ledger
            # gate) is invariant across B; the zero-weight padded
            # chunks of a tail block move identity zeros and are
            # excluded.  Latency rides in the xprof device samples
            # of kmeans/stream_step; on a 1-device mesh the psum
            # degenerates and moves nothing.
            for _ in range(n_real):
                obs.registry.comm("psum", "kmeans/stream_step",
                                  S * k * (d + 1) * 4,
                                  shape=(k, d + 1))
        if bi == n_blocks - 1:
            c_dev = out
            acc = zero_acc
            it += 1
            if on_iter is not None:
                # snapshot hook: one extra fetch per iteration, only
                # when checkpointing asked for it.  The fetch blocks on
                # the whole iteration's device chain — a real
                # device-compute wait the attribution ledger must see
                t_fetch = time.perf_counter()
                c_host = np.asarray(c_dev)
                if obs is not None:
                    obs.registry.observe(
                        "device/compute_ms",
                        (time.perf_counter() - t_fetch) * 1e3)
                on_iter(it, c_host)
        else:
            acc = out
    if pf is not None:
        wait_s += pf.wait_s
        produce_s += pf.produce_s
    t_force = time.perf_counter()
    out = np.asarray(c_dev)  # forces the whole chain
    if obs is not None:
        # the force IS the tail of the job's device compute under async
        # dispatch (the consumer loop runs ahead; the chain materializes
        # here) — without this observation the attribution ledger would
        # report the wait as unattributed remainder
        obs.registry.observe("device/compute_ms",
                             (time.perf_counter() - t_force) * 1e3)
    if timings is not None:
        timings["feed_s"] = time.perf_counter() - t0
        timings["dispatch_batch"] = B
        if produce_s:
            timings["feed_wait_s"] = wait_s
            timings["overlap_ratio"] = round(
                max(0.0, 1.0 - wait_s / produce_s), 4)
    return out


def kmeans_fit_sharded(points, centroids, iters: int = 1, mesh=None,
                       num_shards: int = 0, backend: str = "auto",
                       on_iter=None, precision: str = "highest", obs=None):
    """Run ``iters`` k-means iterations with points sharded over the mesh.

    ``points``: host ``(n, d)`` float32 (rows pad to a multiple of the shard
    count with zero-weight rows, so padding never moves a centroid).
    Returns a :class:`~map_oxidize_tpu.workloads.kmeans.KMeansFit`: the
    final centroids, the centroids the last step assigned against, and
    that step's ``(n,)`` assignment, left on the devices.

    ``on_iter(i, centroids_np)`` (checkpoint hook): when given, the compiled
    body runs one iteration per call — points stay sharded in HBM; only the
    replicated ``(k, d)`` centroids and one psum per iteration move.

    ``obs`` times the same spans as
    :func:`workloads.kmeans.kmeans_fit_device`: ``kmeans/load`` (the
    padded host array and its weights), ``kmeans/put`` (the one sharded
    put) and, on the uninterleaved path only, ``kmeans/fit``.
    """
    from map_oxidize_tpu.obs.trace import span_of
    from map_oxidize_tpu.workloads.kmeans import KMeansFit, host_points

    if iters < 1:
        raise ValueError(f"k-means fit needs iters >= 1, got {iters}")
    if mesh is None:
        mesh = make_mesh(num_shards, backend)
    S = mesh.shape[SHARD_AXIS]
    centroids = np.asarray(centroids, np.float32)
    k = centroids.shape[0]
    with span_of(obs, "kmeans/load"):
        points = host_points(points, precision)
        n, d = points.shape
        n_pad = -(-n // S) * S
        if n_pad != n:
            points = np.concatenate(
                [points, np.zeros((n_pad - n, d), points.dtype)])
        weights = np.zeros(n_pad, np.float32)
        weights[:n] = 1.0

    fit_fn = make_fit_fn(mesh, k, d,
                         1 if on_iter is not None else iters, precision)
    row = NamedSharding(mesh, P(SHARD_AXIS))
    rep = NamedSharding(mesh, P())
    with span_of(obs, "kmeans/put"):
        p_dev = jax.device_put(points, row)
        w_dev = jax.device_put(weights, row)
        p_dev.block_until_ready()
        w_dev.block_until_ready()
    c_dev = jax.device_put(centroids, rep)
    if obs is not None and S > 1:
        # one (k, d+1) partials psum per iteration — the fit's only
        # collective (centroids move, points never do)
        for _ in range(iters):
            obs.registry.comm("psum", "kmeans/fit_sharded",
                              S * k * (d + 1) * 4, shape=(k, d + 1))
    if on_iter is None:
        with span_of(obs, "kmeans/fit"):
            # the fetch forces the chain and brings back the two (k, d)
            new, prev, labels = fit_fn(p_dev, w_dev, c_dev)
            new, prev = jax.device_get((new, prev))
        return KMeansFit(new, prev, labels[:n] if n_pad != n else labels)
    c = c_dev
    for i in range(iters):
        prev = c
        c, _, labels = fit_fn(p_dev, w_dev, c)
        on_iter(i + 1, np.asarray(c))
    return KMeansFit(np.asarray(c), np.asarray(prev),
                     labels[:n] if n_pad != n else labels)
