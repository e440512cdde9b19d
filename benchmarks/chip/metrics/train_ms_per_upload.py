"""Wall time of the window's fleet training spans per upload ingested."""


def read(run):
    spans = run.spans["train"]
    return sum(t1 - t0 for t0, t1 in spans) * 1e3 / run.uploads if spans else None
