"""Read the numbers that set the k-means labels cell's limits, on the chip,
in one process, through the cell's own comparison
(``bench/jobs/kmeans_labels.py`` ``Job.check``, the configured limits):
for each seed, the program as configured, the program's own bfloat16
path, the three-pass ``high`` Lloyd and the fit that sums half the points
(``kmeans_labels_control.py``) put in the program's place.

``python3 bench/tests/kmeans_labels_readings.py <seed>...``: one JSON
line per seed, then one line with the smallest ``label_off`` of each
control and the smallest ``step_gap`` of the half-sum fault.  Exits 1
where the program as configured fails a check, or where a control or the
fault passes every check."""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

from benchlib.harness import load_json, load_module  # noqa: E402

CONFIG = os.path.join(BENCH, "configs", "kmeans-hibench-large-labels.json")


def judged(job, rec: dict) -> tuple[dict, bool]:
    """Every check's value for one job's record, and whether all hold."""
    checks = job.check([rec])
    return ({n: v for n, v, _ in checks},
            all(v <= lim for _, v, lim in checks))


def main(argv) -> int:
    from map_oxidize_tpu.utils.jax_compat import configure_compile_cache

    configure_compile_cache()
    import jax
    import numpy as np

    control = load_module(os.path.join(BENCH, "tests",
                                       "kmeans_labels_control.py"),
                          "bench_labels_control")
    config = load_json(CONFIG)
    kl = load_module(os.path.join(BENCH, "jobs", "kmeans_labels.py"),
                     "bench_job_labels_readings")
    data = config["data"]
    bad = False
    least = {"bf16": None, "high": None}
    least_half = None
    for seed in map(int, argv):
        t0 = time.perf_counter()
        workdir = os.path.join(BENCH, ".data", f"labels-readings-{seed}")
        job = kl.Job(config, seed, workdir, backend=jax.default_backend())
        job.setup()
        row = {"seed": seed}
        for prec in ("highest", "bf16"):
            job.deploy = dict(config["deployment"], precision=prec)
            rec = job.run(0)
            row[prec], row[f"{prec}_correct"] = judged(job, rec)
        points = np.load(job.path)
        for name, fit in (("high", control.lloyd_high_labels),
                          ("half", control.lloyd_half_labels)):
            job.fits.append(kl.Fit(*fit(points, int(data["k"]),
                                        int(data["iterations"]))))
            row[name], row[f"{name}_correct"] = judged(
                job, dict(rec, fit=len(job.fits) - 1))
        del points
        bad |= not row["highest_correct"]
        bad |= row["bf16_correct"] or row["high_correct"]
        bad |= row["half_correct"]
        for name in least:
            v = row[name]["label_off"]
            least[name] = v if least[name] is None else min(least[name], v)
        v = row["half"]["step_gap"]
        least_half = v if least_half is None else min(least_half, v)
        row["s"] = time.perf_counter() - t0
        os.remove(job.path)
        os.rmdir(workdir)
        print(json.dumps(row), flush=True)
    print(json.dumps({"least_control_label_off": least,
                      "least_half_step_gap": least_half}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
