"""Device reduce: device time of the fold's programs per job, mean over
the cell's chips, from the trace.  The programs are the XLA modules of
``runtime/engine.py`` (one chip) and ``parallel/shuffle.py`` /
``parallel/engine.py`` (the mesh) that merge fed batches into the
accumulator, grow it, and finalize it; their names as the trace holds
them are in PERF.md §3."""

#: module names (program id stripped) of the fold, as the traces name
#: them (PR 22): one chip (TPU v5 lite) ``jit_merge_packed_into_accumulator``,
#: ``jit_pack_accumulator_state``, ``jit__grow_concat``; the mesh (four
#: TPU v5 lite, kept for the mesh cell's return, PERF.md §7)
#: ``jit__merge_step``, ``jit__grow``, and its init and top-k programs
#: as ``jit__lambda``
MODULES = (r"^jit_(merge_packed_into_accumulator|pack_accumulator_state"
           r"|_grow_concat|_grow|_merge_step|_lambda)$")


def read(run):
    t = run.trace
    if t is None or not run.traced_jobs:
        return None
    s = t.modules_matching(MODULES)
    return s / run.traced_jobs if s > 0 else None
