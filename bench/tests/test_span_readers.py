"""The readers of the program's own spans (``audit_s``, ``feed_host_s``,
``device_wait_s``, ``job_overhead_s``) on a tiny word-count job on the
CPU: a number where the job's metrics hold the spans, nothing where
they do not."""

import os

import pytest

from benchlib import harness
from benchlib.harness import BENCH, load_module
from tiny import tiny_cell

READERS = ("audit_s", "feed_host_s", "device_wait_s", "job_overhead_s")


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "m_span_" + name)


@pytest.fixture(scope="module")
def tiny_records(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    cell = tiny_cell("wc-enwik9-1chip", tmp)
    jobmod = load_module(os.path.join(BENCH, "jobs", "wordcount.py"),
                         "wc_spans")
    job = jobmod.Job(cell.config, 2 ** 31 + 29, str(tmp / "data"),
                     backend="cpu")
    job.setup()
    return cell, [job.run(i) for i in range(2)]


@pytest.mark.parametrize("name", READERS)
def test_span_reader_reads_a_tiny_job(name, tiny_records):
    cell, records = tiny_records
    view = harness.RunView(cell.config, records)
    value = _reader(name).read(view)
    assert value is not None and value > 0
    # a mean per job of summed self times: within the job's own time,
    # its phases plus its set-up and finish
    walls = [sum(v for k, v in r["metrics"].items()
                 if k.startswith("time/"))
             + r["metrics"]["span/job/setup_s"]
             + r["metrics"]["span/job/finish_s"] for r in records]
    assert value < sum(walls) / len(walls) + 1e-3


@pytest.mark.parametrize("name", READERS)
def test_span_reader_is_none_without_its_spans(name, tiny_records):
    cell, records = tiny_records
    bare = [dict(r, metrics={k: v for k, v in r["metrics"].items()
                             if not k.startswith("span/")})
            for r in records]
    assert _reader(name).read(harness.RunView(cell.config, bare)) is None
    assert _reader(name).read(harness.RunView({}, [])) is None
