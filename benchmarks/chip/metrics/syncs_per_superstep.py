"""Blocking device-to-host reads (``echopfl/sync`` spans) in the traced
window per window superstep (the harness's ingest spans)."""
from chipbench import program_spans


def read(run):
    p = program_spans.of(run)
    if p is None or not run.spans["ingest"]:
        return None
    return len(p.of("sync")) / len(run.spans["ingest"])
