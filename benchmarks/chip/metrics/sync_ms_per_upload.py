"""Wall time of the program's blocking device-to-host reads
(``echopfl/sync``, every site) in the traced window, per upload ingested:
the device's work the host waits for, and the copies back."""
from chipbench import program_spans


def read(run):
    p = program_spans.of(run)
    if p is None or not run.uploads:
        return None
    return p.total_ms("sync") / run.uploads
