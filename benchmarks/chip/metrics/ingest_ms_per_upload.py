"""Wall time of the window's server ingest spans per upload ingested: the
ingest chain, the predictor chain and the refine sweeps."""


def read(run):
    spans = run.spans["ingest"]
    return sum(t1 - t0 for t0, t1 in spans) * 1e3 / run.uploads if spans else None
