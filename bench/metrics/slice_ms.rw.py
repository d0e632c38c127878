"""Answer slicing per read batch (ms): the ``engine.slice`` spans under
each ``engine.read`` (rows out of the host mirror, and single-path
witness extraction), summed per batch and averaged over batches (engine
host layer)."""


def read(run):
    reads = {s.span_id for s in run.spans if s.name == "engine.read"}
    if not reads:
        return None
    total = sum(s.duration_s for s in run.spans
                if s.name == "engine.slice" and s.t_end is not None
                and s.parent_id in reads)
    return 1e3 * total / len(reads)
