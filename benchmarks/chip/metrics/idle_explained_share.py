"""Share of the device's idle time in the traced window that a stage span
of the program covers (any ``echopfl/`` span but ``superstep``,
``install``, ``train`` and ``ingest``), by exact interval overlap,
averaged over devices: how much of the idle time the breakdown names."""
from chipbench import program_spans


def read(run):
    p = program_spans.of(run)
    if p is None:
        return None
    share = p.idle_explained()
    return None if share is None else 100.0 * share
