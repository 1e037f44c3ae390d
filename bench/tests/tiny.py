"""Tiny copies of the cells, for the CPU tests: the configurations'
files with their sizes cut, written beside the test."""

import copy
import json
import os

from benchlib.harness import REPO, Cell, load_json

#: size cuts per configuration: corpus, vocabulary, points
CUTS = {
    "wordcount-enwik9": {"corpus_bytes": 3_000_000, "vocab": 4000},
    "wordcount-enwik9-mesh4": {"corpus_bytes": 3_000_000, "vocab": 4000},
    "kmeans-hibench-large": {"samples": 40_000},
}

#: cells out of BENCHMARK.json until a later PR proves them on the chip
#: (PERF.md §7): the four-chip mesh, and k-means, whose centroid gap no
#: limit separates from its control; their files stay tested here
STAGED_CONFIGS = [
    {"name": "wordcount-enwik9-mesh4",
     "file": "bench/configs/wordcount-enwik9-mesh4.json"},
    {"name": "kmeans-hibench-large",
     "file": "bench/configs/kmeans-hibench-large.json"}]
STAGED_CELLS = [
    {"name": "wc-enwik9-mesh4", "config": "wordcount-enwik9-mesh4",
     "traffic": "closed_batch", "chips": 4},
    {"name": "kmeans-large-1chip", "config": "kmeans-hibench-large",
     "traffic": "closed_batch", "chips": 1}]


def tiny_cell(name: str, tmp_path) -> Cell:
    bm = copy.deepcopy(load_json(os.path.join(REPO, "BENCHMARK.json")))
    bm["configs"] += [dict(c) for c in STAGED_CONFIGS]
    bm["workloads"] += [dict(w) for w in STAGED_CELLS]
    for conf in bm["configs"]:
        data = load_json(os.path.join(REPO, conf["file"]))
        data["data"].update(CUTS[conf["name"]])
        # several chunks, so that keys repeat across fed batches
        data["deployment"]["job_config"] = {"chunk_bytes": 1 << 18}
        path = os.path.join(str(tmp_path), conf["name"] + ".json")
        with open(path, "w") as f:
            json.dump(data, f)
        conf["file"] = path
    return Cell(name, bm)
