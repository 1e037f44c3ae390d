"""Read the numbers that set a k-means limit, on the chip, in one
process, through the harness's own comparison (``Job.check``, the
configured limit): for each seed, the program as configured (the lower
reading), the program's own bfloat16 path, and the three-pass reference
(``control.py``) put in the program's place.  ``python3
bench/tests/readings.py <config> <seed>...``; one JSON line per seed.
Exits 1 where the program as configured fails its limit, or where the
bfloat16 path or the control passes it."""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

from benchlib.harness import load_json, load_module  # noqa: E402


def judged(job, rec: dict) -> tuple[float, bool]:
    """The centroid gap of one job's record, and whether every check of
    ``Job.check`` holds for it."""
    checks = job.check([rec])
    gap = next(v for n, v, _ in checks if n == "centroid_gap")
    return gap, all(v <= lim for _, v, lim in checks)


def main(argv) -> int:
    from map_oxidize_tpu.utils.jax_compat import configure_compile_cache

    configure_compile_cache()
    import jax
    import numpy as np

    import control

    config = load_json(os.path.join(BENCH, "configs", argv[0] + ".json"))
    km = load_module(os.path.join(BENCH, "jobs", "kmeans.py"), "km_r")
    data = config["data"]
    bad = False
    for seed in map(int, argv[1:]):
        t0 = time.perf_counter()
        workdir = os.path.join(BENCH, ".data", f"readings-{seed}")
        job = km.Job(config, seed, workdir, backend=jax.default_backend())
        job.setup()
        row = {"seed": seed}
        for prec in ("highest", "bf16"):
            job.deploy = dict(config["deployment"], precision=prec)
            rec = job.run(0)
            row[f"program_{prec}"], row[f"program_{prec}_correct"] = \
                judged(job, rec)
        rec = dict(rec, centroids=control.lloyd_high(
            np.load(job.path), int(data["k"]), int(data["iterations"])))
        row["control_high"], row["control_high_correct"] = judged(job, rec)
        bad |= not row["program_highest_correct"]
        bad |= row["program_bf16_correct"] or row["control_high_correct"]
        row["s"] = time.perf_counter() - t0
        os.remove(job.path)
        os.rmdir(workdir)
        print(json.dumps(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
