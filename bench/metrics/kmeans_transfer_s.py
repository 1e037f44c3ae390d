"""Host->HBM put of the k-means points (``time/transfer_s``,
``workloads/kmeans.py``, ends in ``block_until_ready``), mean per
job."""


def read(run):
    return run.mean_job("time/transfer_s")
