"""Record the small chip trace the trace-reduction test reads
(``tests/data/v5e.xplane.pb`` and its ``.json``): three jobs, each a
jitted 1024x1024 matmul and sort, traced with the harness's profiler
options, their bounds taken from the host clock as the harness takes
them.  A second capture, with the host tracer on, checks that clock
against the trace's own: it prints the offset between each job's
``TraceAnnotation`` and the host clock's reading of it.
``python3 bench/tests/record_trace.py <out.xplane.pb>`` on the chip."""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def capture(f, x, out_dir, opts, annotate):
    import jax

    jobs = []
    t0 = time.perf_counter()
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for i in range(3):
        s = time.perf_counter()
        if annotate:
            with jax.profiler.TraceAnnotation(f"bench/job {i}"):
                f(x).block_until_ready()
        else:
            f(x).block_until_ready()
        jobs.append(((s - t0) * 1e9, (time.perf_counter() - t0) * 1e9,
                     f"bench/job {i}"))
        time.sleep(0.01)
    jax.profiler.stop_trace()
    return glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True)[0], jobs


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from benchlib.harness import profile_options

    f = jax.jit(lambda a: jnp.sort((a @ a).ravel()).sum())
    x = jnp.ones((1024, 1024))
    f(x).block_until_ready()
    d = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    # the clocks: annotations (host tracer on) against the host clock
    opts = profile_options()
    opts.host_tracer_level = 1
    path, jobs = capture(f, x, d + "/a", opts, True)
    ann = sorted((e.start_ns, e.start_ns + e.duration_ns)
                 for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/host:") for line in p.lines
                 for e in line.events if e.name.startswith("bench/job"))
    print(json.dumps({"annotation_minus_host_clock_ns": [
        [a[0] - j[0], a[1] - j[1]] for a, j in zip(ann, jobs)]}))
    path, jobs = capture(f, x, d + "/b", profile_options(), False)
    shutil.copy(path, out)
    with open(out + ".json", "w") as fh:
        json.dump({"jobs": jobs}, fh)
    shutil.rmtree(d)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
