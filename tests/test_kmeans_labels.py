"""Each point's cluster from the HBM-resident k-means fits: ``labels`` and
``assigned_from`` on ``KMeansResult``, against a plain float64 NumPy
reference on seeded points, on the single-device and sharded paths, with
and without the per-iteration hook; and the fit's spans in the job's
metrics."""

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job

U = 2.0 ** -24      # float32 unit roundoff
#: rounding allowance in units of ``U * B`` (see :func:`_nearest`)
TAU_K = 8
K, ITERS = 5, 3


def _points(seed, n=3001, d=6, k=K):
    """Gaussian blobs, shuffled, so the first k points (the init) are a
    random sample; 3001 rows leave padding on an 8-way mesh."""
    rng = np.random.default_rng(seed)
    centres = rng.uniform(-50, 50, (k, d))
    pts = centres[rng.integers(0, k, n)] + rng.normal(0, 5.0, (n, d))
    return pts.astype(np.float32)


def _nearest(pts, c):
    """float64 nearest and second-nearest centroid of every point, the
    margin between their squared distances, and the rounding allowance
    ``tau``: float32 evaluation of ``|c|^2 - 2 p.c`` errs by at most a
    few ``U * (2 sum_i |p_i c_i| + |c|^2)``, and the margin subtracts two
    such values."""
    p = pts.astype(np.float64)
    c = np.asarray(c, np.float64)
    d2 = (c * c).sum(1) - 2.0 * p @ c.T
    order = np.argsort(d2, axis=1, kind="stable")
    a, b = order[:, 0], order[:, 1]
    rows = np.arange(len(p))
    mag = 2.0 * np.abs(p) @ np.abs(c).T + (c * c).sum(1)
    tau = TAU_K * U * (mag[rows, a] + mag[rows, b])
    return a, d2[rows, b] - d2[rows, a], tau


def _means(pts, labels, assigned_from):
    """float64 means of the points over ``labels``; an empty cluster
    keeps its ``assigned_from`` row."""
    p = pts.astype(np.float64)
    k = len(assigned_from)
    counts = np.bincount(labels, minlength=k)
    sums = np.zeros((k, p.shape[1]))
    np.add.at(sums, labels, p)
    return np.where(counts[:, None] > 0,
                    sums / np.maximum(counts, 1)[:, None],
                    np.asarray(assigned_from, np.float64))


def _job(tmp_path, pts, iters=ITERS, num_shards=1, ckpt=None, **kw):
    inp = tmp_path / "points.npy"
    if not inp.exists():
        np.save(inp, pts)
    cfg = dict(input_path=str(inp), output_path="", backend="cpu",
               kmeans_k=K, kmeans_iters=iters, num_shards=num_shards,
               mapper="auto", metrics=False)
    if ckpt is not None:
        cfg["checkpoint_dir"] = str(tmp_path / ckpt)
    cfg.update(kw)
    return run_job(JobConfig(**cfg), "kmeans")


PATHS = [(1, None), (8, None), (1, "ck"), (8, "ck")]


@pytest.mark.parametrize("num_shards,ckpt", PATHS)
def test_labels_are_the_float64_argmin_over_assigned_from(
        tmp_path, num_shards, ckpt):
    pts = _points(11)
    r = _job(tmp_path, pts, num_shards=num_shards, ckpt=ckpt)
    assert r.metrics["kmeans_mode"] == "device"
    labels = np.asarray(r.labels)
    assert labels.shape == (len(pts),) and labels.dtype == np.int32
    assert r.assigned_from.shape == (K, pts.shape[1])
    a, margin, tau = _nearest(pts, r.assigned_from)
    clear = margin > tau
    assert clear.mean() > 0.99
    np.testing.assert_array_equal(labels[clear], a[clear])
    # within tau the float32 argmin may pick either of the two
    assert ((labels == a) | ~clear).all()


@pytest.mark.parametrize("num_shards,ckpt", PATHS)
def test_centroids_are_the_float64_means_over_labels(
        tmp_path, num_shards, ckpt):
    pts = _points(12)
    r = _job(tmp_path, pts, num_shards=num_shards, ckpt=ckpt)
    want = _means(pts, np.asarray(r.labels), r.assigned_from)
    scale = np.abs(want).max()
    # a float32 sum of ~600 points a cluster, then one division
    np.testing.assert_allclose(r.centroids, want, rtol=0,
                               atol=1e-5 * scale)


@pytest.mark.parametrize("num_shards", [1, 8])
def test_assigned_from_is_the_fit_one_iteration_shorter(tmp_path,
                                                        num_shards):
    pts = _points(13)
    r = _job(tmp_path, pts, num_shards=num_shards)
    shorter = _job(tmp_path, pts, iters=ITERS - 1, num_shards=num_shards)
    np.testing.assert_allclose(r.assigned_from, shorter.centroids,
                               rtol=0, atol=1e-5 * np.abs(pts).max())
    # and a one-iteration fit assigned against the initial centroids
    one = _job(tmp_path, pts, iters=1, num_shards=num_shards)
    np.testing.assert_array_equal(one.assigned_from, pts[:K])


def test_streamed_paths_return_no_labels(tmp_path):
    pts = _points(14)
    host = _job(tmp_path, pts, mapper="python")
    streamed = _job(tmp_path, pts, kmeans_device_fit_bytes=1024)
    assert host.metrics["kmeans_mode"] == "stream"
    assert streamed.metrics["kmeans_mode"] == "stream_device"
    for r in (host, streamed):
        assert r.labels is None and r.assigned_from is None
    np.testing.assert_allclose(host.centroids, streamed.centroids,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("num_shards", [1, 8])
def test_fit_spans_are_in_the_job_metrics(tmp_path, num_shards):
    pts = _points(15)
    m = _job(tmp_path, pts, num_shards=num_shards).metrics
    for name in ("kmeans/load", "kmeans/put", "kmeans/fit"):
        assert m[f"span/{name}_n"] == 1
        assert 0 <= m[f"span/{name}_self_s"] <= m[f"span/{name}_s"]
    # the older keys, now written by the spans
    assert m["time/transfer_s"] == m["span/kmeans/put_s"]
    assert m["time/iter_s"] == m["span/kmeans/fit_s"]
    covered = sum(m[f"span/kmeans/{s}_s"] for s in ("load", "put", "fit"))
    assert 0 < covered <= m["time/iterate_s"] + 1e-4


def test_hooked_fit_has_no_fit_span(tmp_path):
    """The per-iteration path interleaves snapshot I/O with the steps:
    it is no device region, so it writes no ``kmeans/fit`` span."""
    pts = _points(16)
    m = _job(tmp_path, pts, ckpt="ck").metrics
    assert m["span/kmeans/put_n"] == 1
    assert "span/kmeans/fit_n" not in m and "time/iter_s" not in m


@pytest.mark.parametrize("precision", ["highest", "bf16"])
def test_host_points_is_the_array_that_is_put(tmp_path, precision):
    import ml_dtypes

    from map_oxidize_tpu.workloads.kmeans import host_points

    pts = _points(18)
    np.save(tmp_path / "p.npy", pts)
    mapped = np.load(tmp_path / "p.npy", mmap_mode="r")
    host = host_points(mapped, precision)
    assert type(host) is np.ndarray
    if precision == "bf16":
        assert host.dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(
            host.astype(np.float32),
            pts.astype(ml_dtypes.bfloat16).astype(np.float32))
    else:
        # float32 stays a view of the map: no copy, the put reads it
        assert host.dtype == np.float32 and np.shares_memory(host, mapped)
        np.testing.assert_array_equal(host, pts)


def test_bf16_sharded_fit_pads_the_cast_points(tmp_path):
    """bf16 points, cast on the host before the sharded fit pads them
    (3001 rows on 8 shards): the same fit as on one device."""
    pts = _points(19)
    one = _job(tmp_path, pts, kmeans_precision="bf16")
    eight = _job(tmp_path, pts, num_shards=8, kmeans_precision="bf16")
    np.testing.assert_array_equal(np.asarray(eight.labels),
                                  np.asarray(one.labels))
    np.testing.assert_allclose(eight.centroids, one.centroids, rtol=0,
                               atol=1e-5 * np.abs(pts).max())


def test_exact_pieces_add_up_to_the_points():
    import jax.numpy as jnp

    from map_oxidize_tpu.workloads.kmeans import exact_pieces

    rng = np.random.default_rng(17)
    x = np.concatenate([
        rng.normal(0, 50, 4096), rng.normal(0, 1e-30, 64),
        [0.0, -0.0, 1.0, -3.0, np.float32(np.pi), 2.0 ** 100,
         np.finfo(np.float32).max, np.finfo(np.float32).tiny]]
    ).astype(np.float32)
    pieces = exact_pieces(jnp.asarray(x))
    assert all(t.dtype == jnp.bfloat16 for t in pieces)
    hi, mid, lo = (np.asarray(t, np.float64) for t in pieces)
    np.testing.assert_array_equal(hi + mid + lo, x.astype(np.float64))
    # each piece holds what the one before it left: the sums stay exact
    assert (np.abs(mid) <= np.abs(hi) * 2.0 ** -7).all()
    assert (np.abs(lo) <= np.abs(mid) * 2.0 ** -7 + 2.0 ** -133).all()
