"""The k-means fit program's share of its roofline on the chip.

Kernel time: the device time of the HBM-resident fit's XLA module
(``jit_fit``: ``workloads/kmeans.py`` ``_make_jitted``, all iterations
in one ``fori_loop``) per job, from the trace.  Least time: the larger
of the operations over peak FLOP/s and the bytes over peak HBM
bandwidth, from the shapes by :func:`cost`."""

#: the fit's module name in the trace (program id stripped)
MODULE = r"^jit_fit$"


def cost(n: int, d: int, k: int, iters: int) -> tuple[int, int]:
    """FLOPs and HBM bytes the algorithm needs for ``iters`` Lloyd
    iterations over ``n`` float32 points of ``d`` coordinates and ``k``
    centroids: 2ndk for the distance product plus 2ndk for the one-hot
    sums per iteration; one read of the points per iteration."""
    return iters * 4 * n * d * k, iters * n * d * 4


def roofline(flops: float, nbytes: float, seconds: float,
             peaks: dict) -> tuple[float, str]:
    """Share (%) of the least time in the measured one, and which bound
    binds."""
    t_flops = flops / peaks["flops"]
    t_bytes = nbytes / peaks["membw"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def read(run):
    t = run.trace
    if t is None or not run.traced_jobs or run.peaks is None:
        return None
    seconds = t.modules_matching(MODULE) / run.traced_jobs
    if seconds <= 0:
        return None
    data = run.config["data"]
    flops, nbytes = cost(int(data["samples"]), int(data["dimensions"]),
                         int(data["k"]), int(data["iterations"]))
    return roofline(flops, nbytes, seconds, run.peaks)[0]
