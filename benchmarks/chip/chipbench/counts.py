"""Operations and bytes the benchmarked work requires, from shapes alone.

These are the yardstick's own counts: a metric divides them by a measured
time, so they count what the algorithm needs, not what a given program
happens to move. What a client's model requires (its row, its training
FLOPs) is counted by the configuration's client model (``models/``).
"""
from __future__ import annotations

F32 = 4


def ingest_chain_cost(steps: int, centers: int, dim: int) -> tuple[float, float]:
    """(operations, bytes) one ingest-chain launch requires for ``steps``
    uploads against ``centers`` live centers of ``dim`` floats.

    Bytes: every input read once (the uploads, the centers and the
    broadcast anchors) and every output written once (one blended row and
    four scalars per upload). Operations: per upload, the L1 distance to
    every center (subtract, absolute value, add), the blend (two multiplies
    and an add) and the three L1 statistics of the blended row."""
    nbytes = F32 * (steps * dim + 2 * centers * dim + steps * dim + 4 * steps)
    ops = steps * (3 * centers * dim + 3 * dim + 3 * 3 * dim)
    return float(ops), float(nbytes)


def least_time_s(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
