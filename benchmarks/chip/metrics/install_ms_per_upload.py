"""Wall time of the program's downlink installs (``echopfl/install``:
``ClientFleet.set_models`` and ``set_model``) in the traced window, per
upload ingested."""
from chipbench import program_spans


def read(run):
    p = program_spans.of(run)
    if p is None or not run.uploads:
        return None
    return p.total_ms("install") / run.uploads
