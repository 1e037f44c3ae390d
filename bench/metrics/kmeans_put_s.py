"""K-means put: the self time of the ``kmeans/put`` span
(``workloads/kmeans.py`` ``kmeans_fit_device``: ``device_put`` of the
points to ``block_until_ready``), mean per job."""


def read(run):
    return run.mean_job("span/kmeans/put_self_s")
