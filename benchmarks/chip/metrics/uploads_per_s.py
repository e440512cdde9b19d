"""Uploads the window's supersteps ingested, per second of the window (from
its opening to the return of its last ingest)."""


def read(run):
    return run.uploads / run.window_s
