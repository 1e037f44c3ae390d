"""Points and the plain reference of the k-means cells.

Shape of HiBench ``ml/kmeans`` (``GenKMeansDataset``): ``samples`` points
of ``dimensions`` coordinates drawn from ``clusters`` Gaussian blobs, in
a shuffled stream, so the first ``k`` points (the program's initial
centroids) are a random sample.  Written as a float32 ``.npy``.

The reference is a plain NumPy Lloyd in float64, chunked by rows,
copied in spirit from ``chip_smoke.lloyd``/``assign`` (PR 21): nearest
centroid by squared distance, empty clusters keep their centroid.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from threadpoolctl import threadpool_limits

#: rows per generated block
_BLOCK = 1 << 20


def write_points(spec: dict, seed: int, path: str, workers: int = 8
                 ) -> np.ndarray:
    """Write the points to ``path`` and return the blob centres."""
    n, d, m = int(spec["samples"]), int(spec["dimensions"]), \
        int(spec["clusters"])
    rng = np.random.default_rng([int(seed), 0])
    lo, hi = spec["centre_range"]
    centres = rng.uniform(lo, hi, (m, d)).astype(np.float32)
    sigma = np.float32(spec["spread"])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = np.lib.format.open_memmap(path, mode="w+", dtype=np.float32,
                                    shape=(n, d))

    def fill(b: int) -> None:
        r = np.random.default_rng([int(seed), 1, b])
        s, e = b * _BLOCK, min(n, (b + 1) * _BLOCK)
        pts = r.standard_normal((e - s, d), dtype=np.float32)
        pts *= sigma
        pts += centres[r.integers(0, m, e - s)]
        out[s:e] = pts

    with ThreadPoolExecutor(workers) as ex:
        list(ex.map(fill, range(-(-n // _BLOCK))))
    out.flush()
    del out
    return centres


def lloyd64(path: str, k: int, iters: int, chunk: int = 1 << 18,
            workers: int = 8) -> np.ndarray:
    """``iters`` Lloyd iterations from the first ``k`` points, in
    float64; chunks of rows summed in parallel threads, their partial
    sums added in chunk order."""
    pts = np.load(path, mmap_mode="r")
    n, d = pts.shape
    c = np.asarray(pts[:k], np.float64)
    eye = np.eye(k)

    def part(s: int):
        p = np.asarray(pts[s:s + chunk], np.float64)
        lab = np.argmin(cn - 2.0 * (p @ c.T), 1)
        return eye[lab].T @ p, np.bincount(lab, minlength=k)

    # one BLAS thread per worker: OpenBLAS's own threads fight the pool
    with threadpool_limits(1, "blas"), ThreadPoolExecutor(workers) as ex:
        for _ in range(iters):
            cn = (c * c).sum(1)
            sums = np.zeros((k, d), np.float64)
            counts = np.zeros(k, np.int64)
            for ps, pc in ex.map(part, range(0, n, chunk)):
                sums += ps
                counts += pc
            c = np.where(counts[:, None] > 0,
                         sums / np.maximum(counts, 1)[:, None], c)
    return c


def centroid_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest coordinate gap between two centroid sets, as a share of
    the largest reference coordinate."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / np.abs(want).max())


class Job:
    """One cell's k-means jobs: the points made from the seed, each job
    through ``map_oxidize_tpu.runtime.run_job`` from the first ``k``
    points, and the comparison of every window job's centroids with a
    float64 Lloyd from the same start."""

    def __init__(self, config: dict, seed: int, workdir: str,
                 backend: str = "tpu"):
        self.config = config
        self.data = config["data"]
        self.seed = seed
        self.workdir = workdir
        self.backend = backend
        self.deploy = config["deployment"]
        self.path = os.path.join(workdir, "points.npy")
        self.input_bytes = 0
        #: the float64 reference's centroids, once computed
        self.want = None

    def setup(self) -> None:
        write_points(self.data, self.seed, self.path,
                     min(8, os.cpu_count() or 1))
        self.input_bytes = os.path.getsize(self.path)

    def run(self, i: int) -> dict:
        from map_oxidize_tpu.config import JobConfig
        from map_oxidize_tpu.runtime import run_job

        r = run_job(JobConfig(
            input_path=self.path, output_path="", backend=self.backend,
            num_shards=int(self.deploy["num_shards"]),
            mapper=self.deploy["mapper"], kmeans_k=int(self.data["k"]),
            kmeans_iters=int(self.data["iterations"]),
            kmeans_precision=self.deploy["precision"], metrics=False),
            "kmeans")
        return {"metrics": r.metrics,
                "centroids": np.array(r.centroids, np.float32)}

    def check(self, records: list[dict]) -> list[tuple[str, float, float]]:
        """Worst job of the window: its centroids' gap to the float64
        reference; jobs whose ``records_in`` is not points x iterations;
        jobs not on the configured (HBM-resident) path."""
        if self.want is None:
            self.want = lloyd64(self.path, int(self.data["k"]),
                                int(self.data["iterations"]))
        want = self.want
        n_iter = int(self.data["samples"]) * int(self.data["iterations"])
        gap = 0.0
        records_off = mode_off = 0
        for rec in records:
            if rec.get("failed"):
                continue
            gap = max(gap, centroid_gap(rec["centroids"], want))
            m = rec["metrics"]
            records_off += m.get("records_in") != n_iter
            mode_off += m.get("kmeans_mode") != self.deploy["kmeans_mode"]
        limit = float(self.config["limits"]["centroid_gap"])
        return [("centroid_gap", gap, limit), ("records_off", records_off, 0),
                ("mode_off", mode_off, 0)]
