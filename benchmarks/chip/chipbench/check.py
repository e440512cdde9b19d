"""``correct``: what the timed window produced against the plain reference.

Compared, after the window has closed:

* ``train_gap`` — fleet training. A sample, drawn from the seed, of the
  uploads the window's ``train_rows`` launches produced. Each is retrained
  by the client model's ``train_reference`` (the plain reference its
  configuration names) from the model the client held (its last downlink)
  on the client's data as the client model drew it. The
  number is the largest ``|update - reference update| / |reference update|``
  (L2 over the row), where an update is the trained row minus the row
  trained from.
* ``ingest_gap`` and ``assign_miss`` — server ingest. Every other window
  superstep (those without a refine sweep or a center rollback) snapshots
  the centers and the uploaders' assignments around ``handle_uploads``;
  :func:`reference.ingest` replays the batch sequentially from the first
  snapshot. ``ingest_gap`` is the largest ``max|center - reference| /
  max|reference|`` over the clusters the batch touched, ``assign_miss`` the
  number of uploaders whose cluster differs from the reference's.
* ``ledger_bytes_off`` — the uplink and downlink byte ledger: bytes per
  event against the dense row's wire size, and one uplink per trained
  upload. Exact: its limit is 0.

With ``control=True`` the reference computed in bfloat16 takes the
program's place: the readings then say how far one precision below the
configured float32 lands, which sets each limit's upper end.
"""
from __future__ import annotations

import numpy as np

from . import counts, reference
from .harness import flat

TRAIN_SAMPLE = 192


def _rel_max(got, want) -> float:
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def readings(cell, seed, rec, data, sim, *, control: bool = False) -> dict:
    """``{number: (value, answers compared, answers above the limit)}``."""
    return {**train_readings(cell, seed, rec, data, control=control),
            **ingest_readings(cell, rec, control=control), **ledger_readings(cell, rec, sim)}


def train_readings(cell, seed, rec, data, *, control: bool = False) -> dict:
    """``train_gap``: fleet training against the client model's reference."""
    cfg, model = cell.config, cell.model
    rng = np.random.default_rng([seed, 1])
    pool = rec.samples.train
    pick = rng.choice(len(pool), size=min(TRAIN_SAMPLE, len(pool)), replace=False) if pool else []
    gaps = []
    for i in sorted(pick):
        cid, base, trained, head, lr, epochs = pool[i]
        b = flat(base).astype(np.float64)
        kw = dict(epochs=epochs, lr=lr, head_only=head)
        want = model.train_reference(cfg, b, data[cid], **kw)
        got = (model.train_reference(cfg, b, data[cid], cast=reference.bf16, **kw)
               if control else flat(trained).astype(np.float64))
        du, dr = got - b, want - b
        gaps.append(float(np.linalg.norm(du - dr) / max(np.linalg.norm(dr), 1e-30)))
    return {"train_gap": _summary(gaps, cfg["limits"]["train_gap"])}


def ingest_readings(cell, rec, *, control: bool = False) -> dict:
    """``ingest_gap`` and ``assign_miss``: server ingest against the
    sequential reference."""
    cfg = cell.config
    limits = cfg["limits"]
    gaps, miss, n_up = [], 0, 0
    beta, margin = cfg["mix_rate"], cfg["switch_margin"]
    for pre, post, uploads in rec.samples.ingest:
        ups = [(c, flat(p).astype(np.float64)) for c, p in uploads]
        args = (pre["centers"], pre["order"], ups, pre["assign"], pre["pinned"])
        ref_c, ref_chosen = reference.ingest(*args, beta=beta, switch_margin=margin)
        if control:
            got_c, got_chosen = reference.ingest(*args, beta=beta, switch_margin=margin,
                                                 cast=reference.bf16)
        else:
            got_c = {c: np.asarray(v, np.float64) for c, v in post["centers"].items()}
            got_chosen = None
        final_ref = dict(zip([c for c, _ in ups], ref_chosen))
        final_got = (dict(zip([c for c, _ in ups], got_chosen)) if control
                     else {c: post["assign"][c] for c, _ in ups})
        miss += sum(final_got[c] != final_ref[c] for c in final_ref)
        n_up += len(ups)
        gaps.extend(_rel_max(got_c[c], ref_c[c]) for c in set(ref_chosen))
    return {"ingest_gap": _summary(gaps, limits["ingest_gap"]),
            "assign_miss": (float(miss), n_up, int(miss > limits["assign_miss"]) * miss)}


def ledger_readings(cell, rec, sim) -> dict:
    """``ledger_bytes_off``: the byte ledger against the client model's row."""
    net = sim.net
    row = counts.F32 * cell.model.row_floats(cell.config)
    off = (abs(net.up_bytes - net.up_events * row) + abs(net.up_raw_bytes - net.up_events * row)
           + abs(net.down_bytes - net.down_events * row) + abs(net.up_events - rec.trained_total) * row)
    return {"ledger_bytes_off": (float(off), 1, int(off > cell.config["limits"]["ledger_bytes_off"]))}


def _summary(gaps, limit):
    if not gaps:
        return (float("nan"), 0, 0)
    return (max(gaps), len(gaps), sum(g > limit for g in gaps))


def check(cell, seed, rec, data, sim, *, control: bool = False) -> dict:
    """``correct``/``attempted``/``failed`` and each number beside its limit.
    A number with nothing compared (no sample reached it) fails. With
    ``control`` the bfloat16 reference stands in the program's place."""
    r = readings(cell, seed, rec, data, sim, control=control)
    limits = cell.config["limits"]
    numbers = {name: {"value": v, "limit": limits[name]} for name, (v, _, _) in r.items()}
    attempted = sum(n for _, n, _ in r.values())
    failed = sum(f for _, _, f in r.values())
    ok = all(n > 0 and v <= limits[name] for name, (v, n, _) in r.items())
    return {"correct": bool(ok), "attempted": int(attempted), "failed": int(failed),
            "numbers": numbers}
