"""Points, plain reference and comparison of the k-means labels cell.

The points and the jobs are those of ``kmeans.py`` (HiBench ``ml/kmeans``:
``write_points``; each job through ``runtime.run_job`` from the first
``k`` points).  Each job also returns each point's cluster: the last
step's assignment (``KMeansResult.labels``) and the centroids it was made
against (``assigned_from``).  The comparison is taken per point and per
step, where rounding cannot hide in an aggregate.

``label_off``: points whose label differs from the float64 argmin over
the program's own ``assigned_from``, counting only points whose float64
margin (second-nearest minus nearest squared distance) exceeds ``tau``.
The program ranks ``D_j = |c_j|^2 - 2 p.c_j`` in float32 at matmul
precision ``highest``.  Every rounding in ``D_j`` errs by at most
``u = 2^-24`` of a magnitude bounded by ``B_j = 2 sum_i |p_i c_ji| +
|c_j|^2``, so ``D_b - D_a`` errs by a sum of terms ``x_t delta_t``,
``|delta_t| <= u``, with ``sum |x_t| <= B_a + B_b`` to first order per
rounding path.  The worst case over 21 roundings (``gamma_21 ~ 21 u``) is
not what happens: the errors are independent and of both signs (Higham &
Mary, SIAM J. Sci. Comput. 41(5), 2019).  At this configuration
(2*10^7 points, seed 2147511027, float64 against each) the error of
``D_b - D_a`` never passed 1.9 ``u (B_a + B_b)`` in float32 on a CPU and
2.3 in a six-pass bfloat16 emulation of ``highest``, with a Gaussian
tail of sigma ~0.4; on a TPU v5e, ``highest`` flipped no point whose
margin passed 0.90, and the three-pass ``Precision.HIGH`` flipped 1,490
above ``tau``, the largest at 40.  ``tau = 8 u (B_a + B_b)``: 3.5 times
the largest ``highest`` error and ~20 sigma, so no point above it can
switch cluster under ``highest``, while ``high`` switches hundreds to
thousands above it.  The limit is 0.

``step_gap``: the largest gap between ``c_T`` and the float64 means of
the points over the program's own labels (an empty cluster keeps
``assigned_from``), as a share of the largest coordinate.  ``c_T`` is
the float32 sum of each cluster's points (on the MXU, order unknown)
over its exact float32 count (< 2^24).  A sum of ``n`` terms in any
order errs by ``sum_t s_t delta_t`` over its partial sums ``|s_t| <= t
m``, ``m`` the mean magnitude; by Hoeffding, beyond ``lambda u m
sqrt(sum t^2) ~ lambda u m n^1.5 / sqrt(3)`` with probability at most
``2 exp(-lambda^2 / 2)``.  Over the count ``n``: ``lambda u sqrt(n / 3)
m``.  With ``n <= 2*10^7``, ``lambda = 6`` (3*10^-8 a sum, 200 sums a
job) and ``m <= 1.1`` times the largest coordinate (centres within
[-50, 50], spread 5): 1.0e-3.  That worst case over summation orders is
no limit: a fit that sums only half the points stays under it, since
each centroid then moves by its sampling error, about ``5 sqrt(1/n_h -
1/n) = 3.5e-3`` at ``n = 2*10^6`` (7e-5 of a largest coordinate of 50,
some 2e-4 at the worst of 200 coordinates).  The limit is therefore
set between readings on a TPU v5e: the program's sums, three
exact-product passes, read 9.7e-6 to 1.91e-5 over 18 seeds; the fit
that sums half the points (``kmeans_labels_control.py`` ``half``) read
2.0e-4 to 3.2e-4 over 6 of them, with ``label_off`` 0 and
``centroid_gap`` as low as 7.1e-4.  The limit, 5e-5, lies 2.6 times
above the first and 4.0 times under the second.  XLA's ``HIGHEST``
product for the same sums read 1.2e-3 to 1.3e-3 on every seed.

``centroid_gap``: the final centroids against a float64 Lloyd from the
same start (``kmeans.py`` ``lloyd64``), a guard against gross error:
float32 and float64 trajectories part at every boundary switch and
compound over five steps, so it decides nothing close.  On a TPU v5e the
program read at most 4.3e-5 over 18 seeds, the ``high`` control up to
1.2e-4, the ``bf16`` path at least 5.7e-3.  Its limit, 1e-3, lies 23
times above the first and 5.7 times under the last; a wrong start or a
step left out moves centroids further (the fifth float64 step moves them
by 0.049 at 2*10^6 points).  A lost share of the points does not, and
is ``step_gap``'s to catch.

``records_off``, ``mode_off``: as in ``kmeans.py``.

Host memory stays bounded: ``run`` reads each job's labels (the fetch a
user of them makes) and keeps one copy per distinct result; the window's
later jobs are proved equal to a kept one on the device.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np
from threadpoolctl import threadpool_limits

from benchlib.harness import BENCH, HarnessError, load_module

_km = load_module(os.path.join(BENCH, "jobs", "kmeans.py"),
                  "bench_job_kmeans_for_labels")
write_points = _km.write_points
lloyd64 = _km.lloyd64
centroid_gap = _km.centroid_gap

#: float32 unit roundoff
U = 2.0 ** -24
#: ``tau`` in units of ``U * (B_a + B_b)`` (module docstring)
TAU_K = 8.0
#: rows per chunk of the float64 comparison
_ROWS = 1 << 18


class Fit(NamedTuple):
    """One distinct result of the program: what the check compares."""

    centroids: np.ndarray
    assigned_from: np.ndarray
    labels: np.ndarray
    #: the labels as the program returned them, on the device, where a
    #: later job's are compared with them (a host compare of 80 MB took
    #: 30 ms a job, a tenth of it, on a TPU v5e host)
    on_device: object = None


def compare(path: str, fit: Fit, workers: int = 8,
            tau_k: float = TAU_K) -> dict:
    """``label_off`` and ``step_gap`` of one result against the points
    at ``path``, in float64, chunked by rows over ``workers`` threads."""
    pts = np.load(path, mmap_mode="r")
    n, d = pts.shape
    c = np.asarray(fit.assigned_from, np.float64)
    k = c.shape[0]
    labels = np.asarray(fit.labels)
    if labels.shape != (n,) or c.shape != (k, d):
        return {"label_off": n, "step_gap": float("inf")}
    cn = (c * c).sum(1)
    eye = np.eye(k)

    def part(s: int):
        p = np.asarray(pts[s:s + _ROWS], np.float64)
        lab = labels[s:s + _ROWS].astype(np.int64)
        d2 = cn - 2.0 * (p @ c.T)
        two = np.argsort(d2, axis=1, kind="stable")[:, :2]
        a, b = two[:, 0], two[:, 1]
        r = np.arange(len(p))
        mag = 2.0 * (np.abs(p) @ np.abs(c).T) + cn
        tau = tau_k * U * (mag[r, a] + mag[r, b])
        valid = (lab >= 0) & (lab < k)
        off = int(((lab != a) & (d2[r, b] - d2[r, a] > tau)).sum())
        lab = np.where(valid, lab, 0)
        w = valid.astype(np.float64)
        return (off, (eye[lab] * w[:, None]).T @ p,
                np.bincount(lab, weights=w, minlength=k))

    off, sums, counts = 0, np.zeros((k, d)), np.zeros(k)
    with threadpool_limits(1, "blas"), ThreadPoolExecutor(workers) as ex:
        for o, ps, pc in ex.map(part, range(0, n, _ROWS)):
            off += o
            sums += ps
            counts += pc
    means = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts, 1)[:, None], c)
    return {"label_off": off,
            "step_gap": centroid_gap(fit.centroids, means)}


class Job:
    """One cell's k-means jobs with their labels: the points made from the
    seed, each job through ``run_job`` (HBM-resident fit), its labels
    read, and the comparison of every window job's result."""

    def __init__(self, config: dict, seed: int, workdir: str,
                 backend: str = "tpu"):
        from map_oxidize_tpu.runtime.driver import KMeansResult

        if "labels" not in {f.name for f in dataclasses.fields(KMeansResult)}:
            raise HarnessError("the program's k-means result has no labels")
        self.config = config
        self.data = config["data"]
        self.seed = seed
        self.workdir = workdir
        self.backend = backend
        self.deploy = config["deployment"]
        self.path = os.path.join(workdir, "points.npy")
        self.input_bytes = 0
        self.workers = min(8, os.cpu_count() or 1)
        #: distinct results of the program, in the order first seen
        self.fits: list[Fit] = []
        #: jitted equality of two label arrays, on their device
        self._equal = None
        #: the float64 reference's centroids, once computed
        self.want = None

    def setup(self) -> None:
        write_points(self.data, self.seed, self.path, self.workers)
        self.input_bytes = os.path.getsize(self.path)

    def _keep(self, fit: Fit) -> int:
        """Index of the kept result equal to ``fit``, keeping it if new.
        The first call compiles the label comparison (in the warm-up
        job, so the window compiles nothing)."""
        if self._equal is None:
            import jax
            import jax.numpy as jnp

            self._equal = jax.jit(lambda a, b: jnp.array_equal(a, b))
            self._equal(fit.on_device, fit.on_device)
        for i, kept in enumerate(self.fits):
            if (np.array_equal(fit.centroids, kept.centroids)
                    and np.array_equal(fit.assigned_from, kept.assigned_from)
                    and bool(self._equal(fit.on_device, kept.on_device))):
                return i
        self.fits.append(fit)
        return len(self.fits) - 1

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
        t0 = time.perf_counter()
        fit = Fit(np.array(r.centroids, np.float32),
                  np.array(r.assigned_from, np.float32),
                  np.asarray(r.labels), r.labels)
        t1 = time.perf_counter()
        kept = self._keep(fit)
        return {"metrics": r.metrics, "fit": kept, "read_s": t1 - t0,
                "keep_s": time.perf_counter() - t1}

    def reference(self) -> np.ndarray:
        if self.want is None:
            self.want = lloyd64(self.path, int(self.data["k"]),
                                int(self.data["iterations"]),
                                workers=self.workers)
        return self.want

    def check(self, records: list[dict]) -> list[tuple[str, float, float]]:
        """Worst job of the window on each number (module docstring)."""
        limits = self.config["limits"]
        n_iter = int(self.data["samples"]) * int(self.data["iterations"])
        ok = [r for r in records if not r.get("failed")]
        used = sorted({r["fit"] for r in ok})
        read = {i: compare(self.path, self.fits[i], self.workers)
                for i in used}
        want = self.reference() if used else None
        label_off = max((read[i]["label_off"] for i in used), default=0)
        step_gap = max((read[i]["step_gap"] for i in used), default=0.0)
        gap = max((centroid_gap(self.fits[i].centroids, want)
                   for i in used), default=0.0)
        records_off = sum(r["metrics"].get("records_in") != n_iter
                          for r in ok)
        mode_off = sum(r["metrics"].get("kmeans_mode")
                       != self.deploy["kmeans_mode"] for r in ok)
        if ok:
            walls = [r["end_s"] - r["start_s"] for r in ok if "end_s" in r]

            def mean(xs):
                return sum(xs) / max(len(xs), 1)

            print(f"labels: {len(used)} distinct result(s); a job's read "
                  f"{mean([r['read_s'] for r in ok]):.4f} s, kept "
                  f"{mean([r['keep_s'] for r in ok]):.4f} s (at most "
                  f"{max(r['keep_s'] for r in ok):.4f}), wall "
                  f"{mean(walls):.4f} s", file=sys.stderr)
        return [("label_off", label_off, 0),
                ("step_gap", step_gap, float(limits["step_gap"])),
                ("centroid_gap", gap, float(limits["centroid_gap"])),
                ("records_off", records_off, 0),
                ("mode_off", mode_off, 0)]
