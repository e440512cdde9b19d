"""The expert layer's least time against its device time in the window's
training launches: operations and bytes from the client model's
``moe_cost`` for the launches that report their routing (each
``echopfl/train/routed`` span, written after its launch's fetch, gives the
(token, expert) pairs routed to held experts; the tokens are those of the
``echopfl/train/launch`` span before it on its thread; the expert weights
are read once per such launch) over the device time of ``moe``-scoped
operations and grouped products inside every ``train_launch`` program. A
one-client launch, which fetches nothing, reports no routing: its time
counts and its work does not, so the share errs low."""
import bisect

from chipbench import counts, program_spans, scopes, spec


def read(run):
    p = program_spans.of(run)
    if p is None or run.peaks is None:
        return None
    routed = p.of("train/routed")
    if not routed:
        return None
    spent = scopes.scope_seconds(run, "moe", module="train_launch", also=scopes.GROUPED_PRODUCTS)
    if not spent:
        return None
    launches: dict = {}
    for s in p.of("train/launch"):
        launches.setdefault(s.line, []).append((s.start_ns, float(s.stats.get("tokens", 0))))
    for v in launches.values():
        v.sort()
    pairs = tokens = 0.0
    for s in routed:
        before = launches.get(s.line, [])
        i = bisect.bisect_right(before, (s.start_ns, float("inf"))) - 1
        if i < 0:
            return None
        pairs += float(s.stats.get("pairs", 0))
        tokens += before[i][1]
    model = spec.client_model(run.config["client_model"])
    ops, nbytes = model.moe_cost(run.config, pairs, tokens, len(routed))
    return 100.0 * counts.least_time_s(ops, nbytes, run.peaks)[0] / spent
