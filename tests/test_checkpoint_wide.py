"""Checkpoint/resume for the device-map drivers (engine-state snapshots —
map outputs never exist on the host there).  Each test proves
byte-identical output to an uncheckpointed run after a mid-run kill.
The per-chunk spill of the streamed workloads (inverted index included)
is tested in tests/test_checkpoint.py."""

import os

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job


def _make_corpus(path, n_lines=3000, seed=3):
    rng = np.random.default_rng(seed)
    words = [b"alpha", b"beta", b"Gamma,", b"delta.", b"eps", b"zeta"]
    with open(path, "wb") as f:
        for _ in range(n_lines):
            k = int(rng.integers(3, 9))
            f.write(b" ".join(words[int(i)] for i in rng.integers(0, 6, k)))
            f.write(b"\n")


def _cfg(corpus, out, ckdir, **kw):
    base = dict(input_path=str(corpus), output_path=str(out),
                checkpoint_dir=ckdir, chunk_bytes=8 * 1024, backend="cpu",
                metrics=False, num_map_workers=1, max_retries=0)
    base.update(kw)
    return JobConfig(**base)


def _dying_capped(monkeypatch, die_after):
    """Patch the device-map chunk iterator to raise after N chunks — the
    mid-run kill for a path whose map happens inline on device."""
    from map_oxidize_tpu.io import splitter
    from map_oxidize_tpu.runtime import device_map

    real = splitter.iter_chunks_capped

    def dying(path, chunk_bytes, start_offset=0):
        for i, c in enumerate(real(path, chunk_bytes, start_offset)):
            if i >= die_after:
                raise KeyboardInterrupt("simulated kill")
            yield c

    monkeypatch.setattr(device_map, "iter_chunks_capped", dying)


@pytest.mark.parametrize("num_shards", [1, 4])
def test_device_map_snapshot_resume(tmp_path, monkeypatch, num_shards):
    corpus = tmp_path / "c.txt"
    _make_corpus(corpus, n_lines=6000)
    ckdir = str(tmp_path / "ck")
    kw = dict(mapper="device", num_shards=num_shards, chunk_bytes=2048,
              device_chunk_keys=1 << 12)

    want = tmp_path / "want.txt"
    run_job(_cfg(corpus, want, None, **kw), "wordcount")

    # die after enough chunks that at least one snapshot was taken
    # (_SNAP_EVERY chunks single / _SNAP_EVERY groups sharded)
    from map_oxidize_tpu.runtime.device_map import _SNAP_EVERY

    die_after = _SNAP_EVERY * num_shards + 2
    _dying_capped(monkeypatch, die_after)
    got = tmp_path / "got.txt"
    with pytest.raises(KeyboardInterrupt):
        run_job(_cfg(corpus, got, ckdir, **kw), "wordcount")
    assert os.path.isfile(os.path.join(ckdir, "snapshot.npz"))

    monkeypatch.undo()  # resume runs unkilled
    res = run_job(_cfg(corpus, got, ckdir, **kw), "wordcount")
    assert got.read_bytes() == want.read_bytes()
    # the resumed run mapped fewer chunks than the total (prefix skipped)
    full = run_job(_cfg(corpus, tmp_path / "x.txt", None, **kw), "wordcount")
    assert res.metrics["chunks"] == full.metrics["chunks"]
    assert not os.path.isdir(ckdir)
