"""Share of the window outside the harness spans around the fleet's
installs and training, the uplink codec and the server's ingest: the event
loop's own host work (heap, stash, billing, scheduling)."""


def read(run):
    inside = sum(t1 - t0 for spans in run.spans.values() for t0, t1 in spans)
    return 100.0 * (run.window_s - inside) / run.window_s
