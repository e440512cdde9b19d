"""Wall time of the broadcast predictor's planning and fused chain
launches with their sync (``echopfl/ingest/predictor``) in the traced
window, per upload ingested."""
from chipbench import program_spans


def read(run):
    p = program_spans.of(run)
    if p is None or not run.uploads:
        return None
    return p.total_ms("ingest/predictor") / run.uploads
