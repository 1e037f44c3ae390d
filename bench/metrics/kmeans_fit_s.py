"""K-means resident fit: the ``kmeans/fit`` span (``workloads/kmeans.py``
``kmeans_fit_device``: the ``fori_loop`` fit, forced by the fetch of its
two ``(k, d)`` centroid sets), mean per job.  Its whole duration, not its
self time: the compile observatory's sampled ``xprof/ready_wait``, the
wait for the device, nests inside it."""


def read(run):
    return run.mean_job("span/kmeans/fit_s")
