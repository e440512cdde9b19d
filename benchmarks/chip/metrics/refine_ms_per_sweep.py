"""Mean wall time of one refine sweep (``echopfl/ingest/refine``:
feedback probes, reassignment, expansion, merges and dissolves) in the
traced window; None when the window held no sweep."""
from chipbench import program_spans


def read(run):
    p = program_spans.of(run)
    sweeps = p.of("ingest/refine") if p is not None else []
    if not sweeps:
        return None
    return p.total_ms("ingest/refine") / len(sweeps)
