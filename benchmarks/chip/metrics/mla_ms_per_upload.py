"""Device time of latent attention (operations under the program's ``mla``
named scope: projections, YaRN rope, the attention kernel, forward and
backward) in the traced window, per upload ingested."""
from chipbench import scopes


def read(run):
    spent = scopes.scope_seconds(run, "mla")
    if spent is None or not run.uploads:
        return None
    return spent * 1e3 / run.uploads
