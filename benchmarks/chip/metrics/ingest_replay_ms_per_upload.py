"""Self time of the server's per-upload protocol replay
(``echopfl/ingest/replay``: branch pushes, staleness, unicast unflatten,
broadcast fan-out; its refine sweeps, syncs and flushes taken out) in the
traced window, per upload ingested."""
from chipbench import program_spans


def read(run):
    p = program_spans.of(run)
    if p is None or not run.uploads:
        return None
    return p.self_ms("ingest/replay") / run.uploads
