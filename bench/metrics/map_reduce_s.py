"""Host map + device fold: the job's ``map+reduce`` phase wall
(``time/map+reduce_s``, ``runtime/driver.py``), mean per job."""


def read(run):
    return run.mean_job("time/map+reduce_s")
