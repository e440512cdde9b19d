"""Device time of the ingest chain's programs in the trace against the
least time the chip could take for the launches the window made: every
input read once and every output written once (``counts.ingest_chain_cost``);
the HBM bandwidth bounds it."""
from chipbench import counts
from chipbench.trace import module_seconds


def read(run):
    if run.trace is None or run.peaks is None or not run.chain_shapes:
        return None
    spent = module_seconds(run.trace, "ingest_chain")
    if not spent:
        return None
    least = 0.0
    for steps, centers, dim in run.chain_shapes:
        ops, nbytes = counts.ingest_chain_cost(steps, centers, dim)
        least += counts.least_time_s(ops, nbytes, run.peaks)[0]
    return 100.0 * least / spent
