"""The k-means fit on the device, fetch-forced (``time/iter_s``), mean
per job."""


def read(run):
    return run.mean_job("time/iter_s")
