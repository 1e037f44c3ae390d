"""Exchange: device time of the collectives (all-to-all, all-gather,
and the all-reduce of the overflow count) per job, mean over the
cell's chips, from the trace's op names."""

#: HLO instruction names of the exchange's collectives, after the
#: ``module/`` prefix: the v5e compiler names them ``all_to_all.19``,
#: ``all-reduce.4`` (the merge step compiled here for v5e:2x2, PR 22)
OPS = r"/(all[-_]to[-_]all|all[-_]gather|all[-_]reduce)"


def read(run):
    t = run.trace
    if t is None or not run.traced_jobs:
        return None
    s = t.ops_matching(OPS)
    return s / run.traced_jobs if s > 0 else None
