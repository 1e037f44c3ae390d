"""Each fault a cell can have, planted under the timed path, must make
``correct`` come out false.  The harness runs as it does on the chip,
less its look for one: tiny cells, on the CPU."""

import time

import jax
import numpy as np
import pytest

from benchlib import harness
from tiny import tiny_cell


def _run(name, tmp_path):
    jax.clear_caches()
    try:
        return harness.run_cell(
            tiny_cell(name, tmp_path), 2 ** 31 + 23, 0.3, False,
            time.perf_counter(), require_tpu=False, backend="cpu",
            log=lambda m: None, data_root=str(tmp_path))
    finally:
        jax.clear_caches()


def _plant_state_unchanged(mp):
    """The fold's merge step leaves the accumulator as it was."""
    from map_oxidize_tpu.parallel.engine import ShardedReduceEngine
    from map_oxidize_tpu.runtime.engine import DeviceReduceEngine

    for cls in (DeviceReduceEngine, ShardedReduceEngine):
        mp.setattr(cls, "_merge_batch", lambda self, padded: None)


def _plant_half_batch(mp):
    """Every fed batch loses its second half."""
    from map_oxidize_tpu.runtime.engine import StreamingEngineBase

    orig = StreamingEngineBase.feed

    def feed(self, out):
        out.ensure_planes()
        n = len(out) // 2
        out.hi, out.lo, out.values = out.hi[:n], out.lo[:n], out.values[:n]
        out.keys64 = None
        return orig(self, out)

    mp.setattr(StreamingEngineBase, "feed", feed)


def _plant_altered_answer(mp):
    """One count of the written result is off by one."""
    from map_oxidize_tpu.runtime import driver

    orig = driver.write_final_result

    def write(path, items):
        items = sorted(items)
        w, c = items[len(items) // 2]
        items[len(items) // 2] = (w, c + 1)
        return orig(path, items)

    mp.setattr(driver, "write_final_result", write)


def _plant_no_exchange(mp):
    """The mesh's exchange sends nothing: each shard keeps its rows."""
    from map_oxidize_tpu.parallel import shuffle

    mp.setattr(shuffle.lax, "all_to_all", lambda x, *a, **k: x)


@pytest.mark.parametrize("plant", [_plant_state_unchanged, _plant_half_batch,
                                   _plant_altered_answer])
def test_wordcount_fault_is_not_correct(plant, tmp_path, monkeypatch):
    plant(monkeypatch)
    res = _run("wc-enwik9-1chip", tmp_path)
    assert res["correct"] is False, res


@pytest.mark.parametrize("plant", [_plant_no_exchange, _plant_half_batch,
                                   _plant_altered_answer])
def test_mesh_fault_is_not_correct(plant, tmp_path, monkeypatch):
    plant(monkeypatch)
    res = _run("wc-enwik9-mesh4", tmp_path)
    assert res["correct"] is False, res


def _plant_fit_unchanged(mp):
    """The fit returns the centroids it started from."""
    from map_oxidize_tpu.workloads import kmeans

    mp.setattr(kmeans, "_kmeans_fit", lambda c, p, k, iters, prec: c)


def _plant_fit_half_batch(mp):
    """Each Lloyd step sees the first half of the points only."""
    from map_oxidize_tpu.workloads import kmeans

    orig = kmeans.assign_and_sum
    mp.setattr(kmeans, "assign_and_sum",
               lambda p, c, k, precision="highest", w=None:
               orig(p[:p.shape[0] // 2], c, k, precision))


def _plant_fit_altered(mp):
    """One coordinate of the returned centroids is nudged."""
    from map_oxidize_tpu.workloads import kmeans

    orig = kmeans.kmeans_fit_device

    def fit(*a, **k):
        c = np.array(orig(*a, **k))
        c[0, 0] += 0.05 * np.abs(c).max()
        return c

    mp.setattr(kmeans, "kmeans_fit_device", fit)


@pytest.mark.parametrize("plant", [_plant_fit_unchanged,
                                   _plant_fit_half_batch,
                                   _plant_fit_altered])
def test_kmeans_fault_is_not_correct(plant, tmp_path, monkeypatch):
    plant(monkeypatch)
    res = _run("kmeans-large-1chip", tmp_path)
    assert res["correct"] is False, res
