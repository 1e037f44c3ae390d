"""k-means workload (BASELINE config #5): streamed engine result == NumPy
oracle, device-resident path == oracle, conservation, empty clusters."""

import numpy as np
import pytest

from map_oxidize_tpu.api import SumReducer
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job
from map_oxidize_tpu.runtime.driver import make_engine, run_kmeans_job
from map_oxidize_tpu.workloads.kmeans import (
    KMeansMapper,
    assign_points,
    iter_point_chunks,
    kmeans_fit_device,
    kmeans_iteration,
    kmeans_model,
)


def _blobs(rng, n=4000, d=8, k=5):
    centers = rng.normal(0, 10, size=(k, d)).astype(np.float32)
    pts = (centers[rng.integers(0, k, size=n)]
           + rng.normal(0, 0.5, size=(n, d))).astype(np.float32)
    return pts, centers


def test_streamed_iteration_matches_oracle(rng):
    pts, init = _blobs(rng)
    cfg = JobConfig(input_path="unused", output_path="", backend="cpu",
                    batch_size=512, metrics=False)
    engine = make_engine(cfg, SumReducer(), value_shape=(pts.shape[1] + 1,),
                         value_dtype=np.float32)
    chunks = [pts[i:i + 700] for i in range(0, pts.shape[0], 700)]
    ours = kmeans_iteration(engine, init, chunks)
    want = kmeans_model(pts, init)
    np.testing.assert_allclose(ours, want, rtol=1e-4, atol=1e-4)


def test_device_fit_matches_oracle(rng):
    pts, init = _blobs(rng, n=2000, d=4, k=3)
    got = kmeans_fit_device(pts, init, iters=1).centroids
    want = kmeans_model(pts, init)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_device_fit_multi_iter_matches_repeated_oracle(rng):
    pts, init = _blobs(rng, n=1500, d=4, k=4)
    got = kmeans_fit_device(pts, init, iters=3).centroids
    want = init
    for _ in range(3):
        want = kmeans_model(pts, want)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_empty_centroid_keeps_position(rng):
    pts = np.ones((50, 2), np.float32)          # all points at (1, 1)
    init = np.array([[1.0, 1.0], [99.0, 99.0]], np.float32)
    cfg = JobConfig(input_path="unused", output_path="", backend="cpu",
                    metrics=False)
    engine = make_engine(cfg, SumReducer(), value_shape=(3,),
                         value_dtype=np.float32)
    new = kmeans_iteration(engine, init, [pts])
    np.testing.assert_allclose(new[0], [1.0, 1.0])
    np.testing.assert_allclose(new[1], [99.0, 99.0])  # empty: unchanged


def test_mapper_emits_partial_sums(rng):
    pts, init = _blobs(rng, n=300, d=3, k=4)
    out = KMeansMapper(init).map_chunk(pts)
    assert out.records_in == 300
    # counts column conserves points
    assert int(round(float(out.values[:, -1].sum()))) == 300
    # each emitted row matches a direct per-centroid sum
    cid = assign_points(pts, init)
    for hi, lo, row in zip(out.hi, out.lo, out.values):
        assert hi == 0
        m = cid == int(lo)
        np.testing.assert_allclose(row[:-1], pts[m].sum(0), rtol=1e-4)
        assert int(round(float(row[-1]))) == int(m.sum())


def test_run_kmeans_job_end_to_end(tmp_path, rng):
    pts, _ = _blobs(rng, n=3000, d=6, k=4)
    inp = tmp_path / "points.npy"
    np.save(inp, pts)
    outp = tmp_path / "centroids.npy"
    cfg = JobConfig(input_path=str(inp), output_path=str(outp),
                    backend="cpu", kmeans_k=4, kmeans_iters=2,
                    chunk_bytes=4096, metrics=False)
    res = run_job(cfg, "kmeans")
    want = np.asarray(pts[:4], np.float32)
    for _ in range(2):
        want = kmeans_model(pts, want)
    np.testing.assert_allclose(res.centroids, want, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.load(outp), res.centroids)


def test_conservation_violation_raises(rng):
    # a mapper bug that miscounts points must be caught by the count check
    pts, init = _blobs(rng, n=100, d=2, k=2)
    cfg = JobConfig(input_path="unused", output_path="", backend="cpu",
                    metrics=False)

    class Lossy(KMeansMapper):
        def map_chunk(self, points):
            out = super().map_chunk(points)
            out.records_in += 7  # claim more points than were summed
            return out

    engine = make_engine(cfg, SumReducer(), value_shape=(3,),
                         value_dtype=np.float32)
    with pytest.raises(RuntimeError, match="conservation"):
        kmeans_iteration(engine, init, [pts], mapper=Lossy(init))


def test_sharded_fit_matches_oracle(rng):
    """Multi-chip HBM-resident k-means on the 8-device virtual mesh: one
    psum per iteration, padding rows carry zero weight."""
    from map_oxidize_tpu.parallel.kmeans import kmeans_fit_sharded

    pts, init = _blobs(rng, n=2005, d=4, k=3)  # 2005 % 8 != 0: pad rows live
    got = kmeans_fit_sharded(pts, init, iters=2, num_shards=8,
                             backend="cpu").centroids
    want = init
    for _ in range(2):
        want = kmeans_model(pts, want)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_run_kmeans_job_device_paths(tmp_path, rng):
    """mapper='device' routes to the HBM-resident fit (single) and the
    sharded psum fit (mesh); both match the streamed default."""
    pts, _ = _blobs(rng, n=1600, d=5, k=4)
    inp = tmp_path / "points.npy"
    np.save(inp, pts)

    def run(mapper, shards):
        cfg = JobConfig(input_path=str(inp), output_path="", backend="cpu",
                        kmeans_k=4, kmeans_iters=2, chunk_bytes=4096,
                        mapper=mapper, num_shards=shards, metrics=False)
        return run_job(cfg, "kmeans").centroids

    streamed = run("native", 1)  # 'native' pins the streaming path
    dev1 = run("device", 1)
    dev8 = run("device", 8)
    np.testing.assert_allclose(dev1, streamed, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(dev8, streamed, rtol=1e-3, atol=1e-3)
    # 'auto' resolves to the HBM-resident fit for in-memory points,
    # bit-identically
    assert run("auto", 1).tobytes() == dev1.tobytes()


# --- checkpoint/resume (round-3: closes the last warn-and-run hole) -------

def _ck_cfg(inp, iters, ckdir, **kw):
    # mapper='native' pins the streaming path (the checkpoint tests below
    # that target the device paths override it); 'auto' would resolve to
    # the device fit for these in-memory point sets
    base = dict(input_path=str(inp), output_path="", backend="cpu",
                kmeans_k=3, kmeans_iters=iters, chunk_bytes=4096,
                checkpoint_dir=ckdir, metrics=False, mapper="native")
    base.update(kw)
    return JobConfig(**base)


def test_kmeans_checkpoint_resume_streamed(tmp_path, rng, monkeypatch):
    """A 2-iteration run's snapshot resumes a 5-iteration job at iteration
    2 (only 3 more run) and the result is byte-identical to an
    uninterrupted checkpointed 5-iteration run."""
    import os

    pts, _ = _blobs(rng, n=1200, d=4, k=3)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)

    want = run_job(_ck_cfg(inp, 5, str(tmp_path / "ck_ref")),
                   "kmeans").centroids

    ck = str(tmp_path / "ck")
    run_job(_ck_cfg(inp, 2, ck, keep_intermediates=True), "kmeans")
    assert os.path.isfile(os.path.join(ck, "snapshot.npz"))

    import map_oxidize_tpu.workloads.kmeans as wk

    calls = {"n": 0}
    orig = wk.kmeans_iteration

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(wk, "kmeans_iteration", counting)
    got = run_job(_ck_cfg(inp, 5, ck), "kmeans").centroids
    assert calls["n"] == 3, "resume must skip the 2 snapshotted iterations"
    assert got.tobytes() == want.tobytes()
    assert not os.path.isdir(ck)  # success removes the spill by default


def test_kmeans_checkpoint_resume_device(tmp_path, rng):
    """Device (HBM-resident) path: per-iteration snapshots via on_iter;
    interrupted-at-2 then resumed-to-4 equals uninterrupted checkpointed 4."""
    import os

    pts, _ = _blobs(rng, n=900, d=4, k=3)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)

    want = run_job(_ck_cfg(inp, 4, str(tmp_path / "ck_ref"),
                           mapper="device", num_shards=1),
                   "kmeans").centroids
    ck = str(tmp_path / "ck")
    run_job(_ck_cfg(inp, 2, ck, mapper="device", num_shards=1,
                    keep_intermediates=True), "kmeans")
    got = run_job(_ck_cfg(inp, 4, ck, mapper="device", num_shards=1),
                  "kmeans").centroids
    assert got.tobytes() == want.tobytes()
    assert not os.path.isdir(ck)


def test_kmeans_checkpoint_identity_mismatch_discards(tmp_path, rng):
    """A snapshot from a different k (or mode) must be discarded, not
    resumed: the k=4 run starts fresh and matches a no-checkpoint run."""
    pts, _ = _blobs(rng, n=800, d=4, k=4)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)
    ck = str(tmp_path / "ck")

    run_job(_ck_cfg(inp, 2, ck, keep_intermediates=True), "kmeans")  # k=3
    got = run_job(_ck_cfg(inp, 2, ck, kmeans_k=4), "kmeans").centroids
    want = run_job(_ck_cfg(inp, 2, None, kmeans_k=4), "kmeans").centroids
    assert got.tobytes() == want.tobytes()


def test_kmeans_snapshot_covers_all_requested_iters(tmp_path, rng):
    """Resume where the snapshot already has >= kmeans_iters iterations:
    no iteration runs, the snapshot centroids are the result, and the
    zero-work run must NOT delete the training state it merely read
    (code-review finding, round 3)."""
    import os

    pts, _ = _blobs(rng, n=600, d=3, k=3)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)
    ck = str(tmp_path / "ck")
    want = run_job(_ck_cfg(inp, 3, ck, keep_intermediates=True),
                   "kmeans").centroids
    got = run_job(_ck_cfg(inp, 2, ck), "kmeans").centroids  # 2 < 3 done
    assert got.tobytes() == want.tobytes()
    assert os.path.isfile(os.path.join(ck, "snapshot.npz")), \
        "a zero-work read must preserve the continue-training snapshot"
    # ...and the preserved state still resumes a longer job, then cleans up
    run_job(_ck_cfg(inp, 5, ck), "kmeans")
    assert not os.path.isdir(ck)


def test_kmeans_explicit_init_invalidates_foreign_snapshot(tmp_path, rng):
    """A snapshot from a different initial-centroid trajectory must be
    discarded, not silently resumed over the caller's init (code-review
    finding, round 3)."""
    pts, _ = _blobs(rng, n=500, d=3, k=3)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)
    ck = str(tmp_path / "ck")
    init_a = np.asarray(pts[:3], np.float32)
    init_b = np.asarray(pts[10:13], np.float32) + 1.0

    run_kmeans_job(_ck_cfg(inp, 2, ck, keep_intermediates=True),
                   centroids=init_a)
    got = run_kmeans_job(_ck_cfg(inp, 2, ck), centroids=init_b).centroids
    want = run_kmeans_job(_ck_cfg(inp, 2, None), centroids=init_b).centroids
    assert got.tobytes() == want.tobytes()


def test_kmeans_checkpoint_resume_sharded(tmp_path, rng):
    """Sharded HBM-resident path (kmeans_fit_sharded + on_iter): resume on
    the 8-device virtual mesh is byte-identical to an uninterrupted
    checkpointed run, and metrics count only the iterations actually run."""
    import os

    pts, _ = _blobs(rng, n=1001, d=4, k=3)  # odd n: pad rows live
    inp = tmp_path / "p.npy"
    np.save(inp, pts)

    kw = dict(mapper="device", num_shards=8)
    want = run_job(_ck_cfg(inp, 4, str(tmp_path / "ck_ref"), **kw),
                   "kmeans").centroids
    ck = str(tmp_path / "ck")
    run_job(_ck_cfg(inp, 2, ck, keep_intermediates=True, **kw), "kmeans")
    res = run_job(_ck_cfg(inp, 4, ck, **kw), "kmeans")
    assert res.centroids.tobytes() == want.tobytes()
    assert not os.path.isdir(ck)


def test_kmeans_resume_metrics_count_only_ran_iters(tmp_path, rng):
    """records_in/iters after a resume: throughput numerators must not be
    inflated by snapshotted iterations (code-review finding, round 3)."""
    pts, _ = _blobs(rng, n=500, d=3, k=3)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)
    ck = str(tmp_path / "ck")

    run_job(_ck_cfg(inp, 2, ck, keep_intermediates=True), "kmeans")
    cfg = _ck_cfg(inp, 5, ck)
    cfg.metrics = True
    res = run_job(cfg, "kmeans")
    assert res.metrics["records_in"] == 500 * 3   # only 3 iterations ran
    assert res.metrics["iters"] == 5              # result represents 5
    assert res.metrics["resumed_iters"] == 2


def test_auto_mapper_fit_cap(tmp_path, rng, monkeypatch):
    """'auto' resolves by the device-fit cap: under it -> HBM-resident fit,
    over it -> streamed (the only option at beyond-memory scale)."""
    import map_oxidize_tpu.runtime.driver as drv

    pts, _ = _blobs(rng, n=500, d=4, k=3)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)
    cfg = JobConfig(input_path=str(inp), output_path="", backend="cpu",
                    kmeans_k=3, kmeans_iters=1, metrics=True)
    dev = run_job(cfg, "kmeans")
    monkeypatch.setattr(drv, "_KMEANS_DEVICE_FIT_BYTES", 100)  # force stream
    streamed = run_job(cfg, "kmeans")
    np.testing.assert_allclose(streamed.centroids, dev.centroids,
                               rtol=1e-3, atol=1e-3)


def test_auto_resume_adopts_snapshot_mode(tmp_path, rng):
    """A snapshot cut from the STREAMED path must resume streamed even
    when mapper='auto' would heuristically pick the device fit — resume
    continues the trajectory it was cut from instead of discarding it."""
    import os

    pts, _ = _blobs(rng, n=900, d=4, k=3)
    inp = tmp_path / "p.npy"
    np.save(inp, pts)
    want = run_job(_ck_cfg(inp, 4, str(tmp_path / "ck_ref")),
                   "kmeans").centroids  # streamed, checkpointed, 4 iters

    ck = str(tmp_path / "ck")
    run_job(_ck_cfg(inp, 2, ck, keep_intermediates=True), "kmeans")
    res = run_job(_ck_cfg(inp, 4, ck, mapper="auto"), "kmeans")
    assert res.metrics.get("resumed_iters") == 2, \
        "auto must adopt the snapshot's stream mode, not invalidate it"
    assert res.centroids.tobytes() == want.tobytes()
    assert not os.path.isdir(ck)


def test_bf16_precision_convergence_parity(tmp_path, rng):
    """--kmeans-precision bf16 (VERDICT r4 #6): native single-pass bf16
    matmuls must (a) actually change the numerics (the knob is real — on
    CPU XLA emulates the bf16 operand rounding), (b) stay within bf16
    rounding of the f32-HIGHEST trajectory over 24 iterations on
    clustered data (drift bound ~bf16 epsilon relative to the data
    scale), and (c) land on the same cluster structure as the NumPy
    oracle.  Sharded and single-device bf16 share assign_and_sum, so one
    drift gate covers both formulations."""
    pts, centers = _blobs(rng, n=3000, d=16, k=8)
    # true centers as the first k rows (= the driver's init): arbitrary-
    # point init creates sliver Voronoi cells whose near-tie assignment
    # flips compound chaotically across iterations — the same reason the
    # round-4 bench parity gate seeds this way (bench.py kmeans section).
    # The knob's drift bound is about ROUNDING, not k-means instability.
    pts[:8] = centers
    inp = tmp_path / "p.npy"
    np.save(inp, pts)

    def run(precision, shards=1):
        cfg = JobConfig(input_path=str(inp), output_path="", backend="cpu",
                        kmeans_k=8, kmeans_iters=24, mapper="device",
                        num_shards=shards, metrics=False,
                        kmeans_precision=precision)
        return run_kmeans_job(cfg).centroids

    f32 = run("highest")
    b16 = run("bf16")
    assert b16.tobytes() != f32.tobytes(), \
        "bf16 mode produced bit-identical results; the knob is a no-op"
    scale = float(np.abs(pts).max())
    # bf16 has ~8 mantissa bits (eps = 2^-8); converged centroids are
    # cluster means, so per-coordinate drift stays within a few eps of
    # the data scale
    drift = float(np.abs(b16 - f32).max())
    assert drift <= 4 * 2.0**-8 * scale, \
        f"bf16 drift {drift} vs f32 exceeds the rounding bound"
    want = pts[:8].copy()
    for _ in range(24):
        want = kmeans_model(pts, want)
    np.testing.assert_allclose(b16, want, rtol=0.05, atol=0.05 * scale)

    b16_sharded = run("bf16", shards=8)
    np.testing.assert_allclose(b16_sharded, b16, rtol=1e-4, atol=1e-4)

    with pytest.raises(ValueError, match="kmeans_precision"):
        JobConfig(input_path=str(inp), output_path="",
                  kmeans_precision="f64").validate()


def test_pallas_fused_kernel_parity(rng):
    """The fused Pallas assignment+partial-sum kernel (interpret mode on
    CPU) must reproduce assign_and_sum exactly in structure: equal
    counts, close sums, both precisions, with and without weights, and
    with tail padding exercised (n not a TILE_N multiple)."""
    import jax.numpy as jnp

    from map_oxidize_tpu.ops.kmeans_kernel import TILE_N, fused_assign_sum
    from map_oxidize_tpu.workloads.kmeans import assign_and_sum

    n, d, k = TILE_N + 777, 16, 32  # forces the padding mask path
    p = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    w = jnp.asarray((rng.random(n) > 0.3).astype(np.float32))
    for prec in ("highest", "bf16"):
        for weights in (None, w):
            s1, c1 = fused_assign_sum(p, c, k, prec, w=weights,
                                      interpret=True)
            s2, c2, _ = assign_and_sum(p, c, k, prec, w=weights)
            np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
            np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                       rtol=1e-5, atol=1e-4)


def test_streamed_device_fit_matches_oracle(tmp_path, rng):
    """Round-5 (verdict r4 #5): the device-streamed fit (chunks through
    the chip, one dispatch per chunk, update folded into the last) must
    match the repeated NumPy oracle across chunking shapes — multi-chunk
    with a padded tail, and the single-chunk first==last fusion."""
    from map_oxidize_tpu.workloads.kmeans import kmeans_fit_streamed_device

    pts, centers = _blobs(rng, n=5000, d=8, k=5)
    pts[:5] = centers
    path = tmp_path / "p.npy"
    np.save(path, pts)
    init = pts[:5].copy()
    want = init
    for _ in range(3):
        want = kmeans_model(pts, want)
    for chunk_rows in (1024, 8192):
        got = kmeans_fit_streamed_device(str(path), init, iters=3,
                                         chunk_rows=chunk_rows)
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    gb = kmeans_fit_streamed_device(str(path), init, iters=3,
                                    chunk_rows=1024, precision="bf16")
    scale = float(np.abs(pts).max())
    assert float(np.abs(gb - want).max()) <= 4 * 2.0**-8 * scale


def test_auto_routes_beyond_fit_to_streamed_device(tmp_path, rng,
                                                   monkeypatch):
    """mapper='auto' with points past the device-fit budget must take the
    device-streamed route (r4 the streamed fallback was host-assign),
    produce oracle-correct centroids, record feed_s, and resume from its
    own checkpoints under the 'stream_device' mode identity."""
    import map_oxidize_tpu.runtime.driver as drv

    pts, centers = _blobs(rng, n=4000, d=6, k=3)
    pts[:3] = centers
    inp = tmp_path / "p.npy"
    np.save(inp, pts)
    monkeypatch.setattr(drv, "_kmeans_device_fit_bytes", lambda b: 1)

    cfg = JobConfig(input_path=str(inp), output_path="", backend="cpu",
                    kmeans_k=3, kmeans_iters=2, mapper="auto",
                    metrics=False)
    res = run_kmeans_job(cfg)
    want = pts[:3].copy()
    for _ in range(2):
        want = kmeans_model(pts, want)
    np.testing.assert_allclose(res.centroids, want, rtol=1e-3, atol=1e-3)
    assert "time/feed_s" in res.metrics

    # checkpointed: 1-iter run, then resume to 3 — identical to a fresh
    # 3-iter run (the snapshot's stream_device mode is adopted)
    import dataclasses

    ck = str(tmp_path / "ck")
    run_kmeans_job(dataclasses.replace(cfg, kmeans_iters=1,
                                       checkpoint_dir=ck,
                                       keep_intermediates=True))
    resumed = run_kmeans_job(dataclasses.replace(cfg, kmeans_iters=3,
                                                 checkpoint_dir=ck))
    fresh = run_kmeans_job(dataclasses.replace(cfg, kmeans_iters=3))
    np.testing.assert_array_equal(resumed.centroids, fresh.centroids)
    assert resumed.metrics.get("resumed_iters") == 1


def test_streamed_sharded_matches_oracle(tmp_path, rng):
    """Streaming x sharding composed (VERDICT r5 #2): fixed-row chunks
    stream as per-shard blocks through make_stream_step_fn's one-psum
    program over the 8-virtual-device CPU mesh, and the result matches
    the NumPy oracle — across chunking shapes (multi-chunk with a
    padded, zero-weighted tail; the single-chunk first==last fusion)
    and with a row count not divisible by the mesh."""
    from map_oxidize_tpu.parallel.kmeans import kmeans_fit_streamed

    pts, centers = _blobs(rng, n=5003, d=8, k=5)
    pts[:5] = centers
    path = tmp_path / "p.npy"
    np.save(path, pts)
    init = pts[:5].copy()
    want = init
    for _ in range(3):
        want = kmeans_model(pts, want)
    for chunk_rows in (1000, 1 << 20):  # multi-chunk+tail / single fused
        got = kmeans_fit_streamed(str(path), init, iters=3,
                                  chunk_rows=chunk_rows, num_shards=8,
                                  backend="cpu")
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    # shards=1 runs the SAME program (psum over a singleton axis) and
    # must agree with the mesh run within float-reassociation tolerance
    got1 = kmeans_fit_streamed(str(path), init, iters=3, chunk_rows=1000,
                               num_shards=1, backend="cpu")
    np.testing.assert_allclose(got1, want, rtol=1e-3, atol=1e-3)
    # bf16 chunk storage stays within rounding of the f32 oracle
    gb = kmeans_fit_streamed(str(path), init, iters=3, chunk_rows=1000,
                             num_shards=8, backend="cpu",
                             precision="bf16")
    scale = float(np.abs(pts).max())
    assert float(np.abs(gb - want).max()) <= 4 * 2.0**-8 * scale


def test_fit_budget_config_routes_stream_device(tmp_path, rng):
    """VERDICT r5 #5: the device-fit budget is a CONFIG field now —
    forcing it tiny must route mapper='auto' to stream_device (recorded
    in metrics, no monkeypatching) and still match the NumPy oracle;
    a generous budget routes the same job to the resident fit."""
    pts, centers = _blobs(rng, n=1200, d=5, k=3)
    pts[:3] = centers
    inp = tmp_path / "p.npy"
    np.save(inp, pts)

    def run(budget):
        cfg = JobConfig(input_path=str(inp), output_path="", backend="cpu",
                        kmeans_k=3, kmeans_iters=2, mapper="auto",
                        metrics=True, kmeans_device_fit_bytes=budget)
        return run_job(cfg, "kmeans")

    want = pts[:3].copy()
    for _ in range(2):
        want = kmeans_model(pts, want)

    streamed = run(budget=64)  # working set >> 64 bytes -> must stream
    assert streamed.metrics["kmeans_mode"] == "stream_device"
    np.testing.assert_allclose(streamed.centroids, want,
                               rtol=1e-3, atol=1e-3)
    assert "time/feed_s" in streamed.metrics

    resident = run(budget=1 << 40)  # everything fits -> resident
    assert resident.metrics["kmeans_mode"] == "device"
    np.testing.assert_allclose(resident.centroids, want,
                               rtol=1e-3, atol=1e-3)
