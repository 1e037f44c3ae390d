"""The k-means labels cell (``bench/jobs/kmeans_labels.py``) on the CPU,
tiny: a run passes its checks, each planted fault (a flipped label, a
nudged centroid, a fit that sums half the points) fails the check meant
for it, the three-pass ``high`` control fails on points planted near
ties, the readers read the fit's spans, and a program whose result has
no labels is refused before any data is made."""

import dataclasses
import json
import os
import time

import jax
import numpy as np
import pytest

from benchlib import harness
from benchlib.harness import BENCH, REPO, Cell, load_json, load_module

CELL = "kmeans-large-1chip"
SEED = 2 ** 31 + 41
kl = load_module(os.path.join(BENCH, "jobs", "kmeans_labels.py"),
                 "bench_job_labels_test")
control = load_module(os.path.join(BENCH, "tests",
                                   "kmeans_labels_control.py"),
                      "bench_labels_control_test")


def _tiny_config(tmp_path, samples=40_000) -> str:
    """The cell's configuration file with its points cut."""
    conf = load_json(os.path.join(BENCH, "configs",
                                  "kmeans-hibench-large-labels.json"))
    conf["data"]["samples"] = samples
    path = os.path.join(str(tmp_path), "kmeans-labels-tiny.json")
    with open(path, "w") as f:
        json.dump(conf, f)
    return path


def _tiny_cell(tmp_path) -> Cell:
    bm = load_json(os.path.join(REPO, "BENCHMARK.json"))
    for conf in bm["configs"]:
        if conf["name"] == "kmeans-hibench-large":
            conf["file"] = _tiny_config(tmp_path)
    return Cell(CELL, bm)


def _run(tmp_path):
    jax.clear_caches()
    try:
        return harness.run_cell(
            _tiny_cell(tmp_path), SEED, 0.3, False, time.perf_counter(),
            require_tpu=False, backend="cpu", log=lambda m: None,
            data_root=str(tmp_path))
    finally:
        jax.clear_caches()


def test_tiny_run_passes_its_checks(tmp_path):
    cell = _tiny_cell(tmp_path)
    assert cell.config["job"] == "kmeans_labels"
    res = _run(tmp_path)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"label_off", "step_gap", "centroid_gap",
                                  "records_off", "mode_off"}
    assert res["checks"]["label_off"] == {"value": 0, "limit": 0}
    assert set(res["metrics"]) == {"input_mib_per_s", "setup_s"}


def _margins(pts, c):
    p = np.asarray(pts, np.float64)
    d2 = (np.asarray(c, np.float64) ** 2).sum(1) - 2.0 * p @ np.asarray(
        c, np.float64).T
    d2.sort(axis=1)
    return d2[:, 1] - d2[:, 0]


def _plant(mp, change):
    """Pass the resident fit's result through ``change`` on its way out."""
    from map_oxidize_tpu.workloads import kmeans

    orig = kmeans.kmeans_fit_device

    def fit(points, *a, **k):
        return change(np.asarray(points), orig(points, *a, **k))

    mp.setattr(kmeans, "kmeans_fit_device", fit)


def test_one_flipped_label_far_from_any_tie_fails_label_off(
        tmp_path, monkeypatch):
    def flip(points, f):
        labels = np.array(f.labels)
        i = int(np.argmax(_margins(points, f.assigned_from)))
        labels[i] = (labels[i] + 1) % len(f.assigned_from)
        return f._replace(labels=labels)

    _plant(monkeypatch, flip)
    res = _run(tmp_path)
    assert res["correct"] is False
    assert res["checks"]["label_off"]["value"] == 1


def test_one_nudged_centroid_fails_step_gap(tmp_path, monkeypatch):
    def nudge(points, f):
        c = np.array(f.centroids)
        c[3, 7] += 0.01 * np.abs(c).max()
        return f._replace(centroids=c)

    _plant(monkeypatch, nudge)
    res = _run(tmp_path)
    assert res["correct"] is False
    step = res["checks"]["step_gap"]
    assert step["value"] > step["limit"]
    assert res["checks"]["label_off"]["value"] == 0


def test_a_fit_that_sums_half_the_points_fails_step_gap(tmp_path,
                                                        monkeypatch):
    from map_oxidize_tpu.workloads import kmeans

    def half(points, centroids, iters=1, **kw):
        return kmeans.KMeansFit(*control.lloyd_half_labels(
            np.asarray(points), len(centroids), iters))

    monkeypatch.setattr(kmeans, "kmeans_fit_device", half)
    res = _run(tmp_path)
    assert res["correct"] is False
    step = res["checks"]["step_gap"]
    assert step["value"] > step["limit"]
    assert res["checks"]["label_off"]["value"] == 0


U = 2.0 ** -24


def _tie_points(seed, k=10, d=20, per=400, planted=600):
    """Points around known centroids: ``k`` centroids first (the
    program's init), then mirrored pairs ``c_j +- v`` around each, so
    every cluster's mean is its centroid.  Planted pairs put one point
    on ``a``'s side of the ``a|b`` bisector at a float64 margin of 9-30
    ``u (B_a + B_b)`` (above the check's ``tau``, below the rounding of
    three bfloat16 passes) and its mirror deep in ``a``."""
    rng = np.random.default_rng(seed)
    c = rng.uniform(-50, 50, (k, d))
    rows = [c]
    for j in range(k):
        v = rng.normal(0, 2.0, (per, d))
        rows += [c[j] + v, c[j] - v]

    def nearest(x):
        return np.argsort(((c - x) ** 2).sum(1))

    kept = 0
    while kept < planted:
        a, b = rng.choice(k, 2, replace=False)
        delta = c[a] - c[b]
        o = rng.normal(0, 3.0, d)
        o -= (o @ delta) / (delta @ delta) * delta
        mid = (c[a] + c[b]) / 2 + o
        mag = (2 * np.abs(mid) @ np.abs(c[[a, b]]).T
               + (c[[a, b]] ** 2).sum(1)).sum()
        margin = rng.uniform(9, 30) * U * mag
        v = -(1 - margin / (delta @ delta)) / 2 * delta + o
        x, mirror = c[a] + v, c[a] - v
        if list(nearest(x)[:2]) == [a, b] and nearest(mirror)[0] == a:
            rows += [x[None], mirror[None]]
            kept += 1
    return np.concatenate(rows).astype(np.float32)


def test_high_control_fails_on_points_planted_near_ties(tmp_path):
    pts = _tie_points(7)
    conf = load_json(_tiny_config(tmp_path, samples=len(pts)))
    # one step, assigned against the planted centroids themselves: a
    # flip in an earlier step would move the centroids and the ties
    conf["data"]["iterations"] = 1
    job = kl.Job(conf, SEED, str(tmp_path / "ties"), backend="cpu")
    os.makedirs(job.workdir)
    np.save(job.path, pts)
    job.input_bytes = os.path.getsize(job.path)
    program = job.run(0)
    program_checks = dict((n, v) for n, v, _ in job.check([program]))
    job.fits.append(kl.Fit(*control.lloyd_high_labels(
        pts, conf["data"]["k"], conf["data"]["iterations"])))
    high = dict(program, fit=len(job.fits) - 1)
    high_checks = dict((n, v) for n, v, _ in job.check([high]))
    assert program_checks["label_off"] == 0
    assert high_checks["label_off"] > 0


@pytest.mark.parametrize("name", ["kmeans_load_s", "kmeans_put_s",
                                  "kmeans_fit_s"])
def test_span_readers_read_a_tiny_job(name, tmp_path):
    job = kl.Job(load_json(_tiny_config(tmp_path, samples=5000)), SEED,
                 str(tmp_path / "spans"), backend="cpu")
    job.setup()
    records = [job.run(i) for i in range(2)]
    reader = load_module(os.path.join(BENCH, "metrics", name + ".py"),
                         "m_labels_" + name)
    value = reader.read(harness.RunView(job.config, records))
    assert value is not None and value >= 0
    bare = [dict(r, metrics={k: v for k, v in r["metrics"].items()
                             if not k.startswith("span/")}) for r in records]
    assert reader.read(harness.RunView(job.config, bare)) is None


def test_a_result_without_labels_is_refused_before_any_data(
        tmp_path, monkeypatch):
    from map_oxidize_tpu.runtime import driver

    @dataclasses.dataclass
    class Bare:
        centroids: np.ndarray

    monkeypatch.setattr(driver, "KMeansResult", Bare)
    with pytest.raises(harness.HarnessError, match="no labels"):
        kl.Job(load_json(_tiny_config(tmp_path)), SEED,
               str(tmp_path / "none"), backend="cpu")
    assert not os.path.exists(tmp_path / "none")


def test_readings_script_reads_every_check_at_a_tiny_size(
        tmp_path, monkeypatch, capsys):
    readings = load_module(os.path.join(BENCH, "tests",
                                        "kmeans_labels_readings.py"),
                           "bench_labels_readings_test")
    monkeypatch.setattr(readings, "CONFIG", _tiny_config(tmp_path))
    readings.main([str(SEED)])
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    row = rows[0]
    assert row["seed"] == SEED and row["highest_correct"]
    assert row["bf16"]["label_off"] > 0 and not row["bf16_correct"]
    assert set(row["high"]) == set(row["highest"])
    assert row["half"]["label_off"] == 0 and not row["half_correct"]
    assert rows[-1]["least_control_label_off"]["bf16"] > 0
    assert rows[-1]["least_half_step_gap"] == row["half"]["step_gap"] > 0


def test_after_the_warm_up_a_job_compiles_nothing(tmp_path):
    from benchlib.compiles import CompileCounter

    counter = CompileCounter().install()
    job = kl.Job(load_json(_tiny_config(tmp_path, samples=5000)), SEED,
                 str(tmp_path / "warm"), backend="cpu")
    job.setup()
    job.run(-1)
    before = counter.count
    records = [job.run(i) for i in range(2)]
    assert counter.count == before
    # both proved equal to the warm-up's result, on the device
    assert [r["fit"] for r in records] == [0, 0] and len(job.fits) == 1
