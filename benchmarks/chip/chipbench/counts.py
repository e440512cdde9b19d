"""Operations and bytes the benchmarked work requires, from shapes alone.

These are the yardstick's own counts: a metric divides them by a measured
time, so they count what the algorithm needs, not what a given program
happens to move.
"""
from __future__ import annotations

F32 = 4


def widths(config: dict) -> list[int]:
    return [config["input_dim"], *config["hidden"], config["num_classes"]]


def row_floats(config: dict) -> int:
    w = widths(config)
    return sum(a * b + b for a, b in zip(w[:-1], w[1:]))


def train_samples(config: dict) -> int:
    """Training samples per client after the generator's test split."""
    spc = config["samples_per_client"]
    n_total = spc + max(1, int(spc * 0.2))
    return n_total - max(1, int(n_total * 0.2))


def layer_macs(config: dict) -> list[int]:
    w = widths(config)
    return [a * b for a, b in zip(w[:-1], w[1:])]


def macs_per_sample(config: dict) -> int:
    return sum(layer_macs(config))


def train_flops_per_upload(config: dict, *, head_only: bool = False) -> int:
    """Forward and backward of every epoch's full batch. Per sample: 2 FLOPs
    per MAC forward; backward, 2 per MAC for the weight gradients of the
    layers that move and 2 per MAC to carry the gradient down to them (never
    into the input). Partial fine-tuning moves the last layer alone."""
    macs = layer_macs(config)
    backward = 2 * macs[-1] if head_only else 2 * sum(macs) + 2 * sum(macs[1:])
    return config["local_epochs"] * train_samples(config) * (2 * sum(macs) + backward)


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
