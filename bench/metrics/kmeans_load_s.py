"""K-means host load: the self time of the ``kmeans/load`` span
(``workloads/kmeans.py`` ``kmeans_fit_device``: the input's memory map
made the host array that is put: the bf16 cast, or nothing for a float32
map, whose pages the put reads), mean per job."""


def read(run):
    return run.mean_job("span/kmeans/load_self_s")
