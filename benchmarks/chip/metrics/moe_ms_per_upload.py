"""Device time of the expert layer (operations under the program's ``moe``
named scope: gate, gathers and scatters of the routed pairs, shared
experts, forward and backward; and the grouped expert matmuls, whose
instructions carry no scope) in the traced window, per upload ingested."""
from chipbench import scopes


def read(run):
    spent = scopes.scope_seconds(run, "moe", also=scopes.GROUPED_PRODUCTS)
    if spent is None or not run.uploads:
        return None
    return spent * 1e3 / run.uploads
