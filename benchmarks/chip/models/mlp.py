"""Client model: the paper's MLP (``repro.fl.tasks.MLP_TASK``).

A configuration names it with ``"client_model": "mlp"`` and gives its
widths as ``input_dim``, ``hidden`` and ``num_classes``, with ``task`` one of
the program's ``PAPER_TASKS``. Clients hold the generator's synthetic task
(``chipbench.generators.make_task``), and a seed relabels features, hidden
units and classes (``generators.relabel``). The counts below are the
yardstick's own, from those widths alone.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from chipbench import generators, spec

BENCH_DIR = Path(__file__).resolve().parents[1]


def widths(config: dict) -> list[int]:
    return [config["input_dim"], *config["hidden"], config["num_classes"]]


def check_sizes(config: dict) -> None:
    from repro.configs.paper_tasks import PAPER_TASKS, MLPTaskConfig

    task = config["task"]
    mlp = MLPTaskConfig(task, config["input_dim"], tuple(config["hidden"]), config["num_classes"])
    if PAPER_TASKS[task] != mlp:
        raise ValueError(f"config widths {mlp} differ from the program's {PAPER_TASKS[task]}")


def draw(config: dict, rng, program_seed: int, seed: int):
    """The clients' datasets from ``rng``, the initial MLP from
    ``program_seed``, both relabelled by ``seed``; and what each client
    takes besides."""
    import jax

    from repro.configs.paper_tasks import PAPER_TASKS
    from repro.fl.tasks import MLP_TASK

    check_sizes(config)
    task = config["task"]
    data = generators.make_task(task, config["num_clients"], rng, latent_clusters=config["latent_clusters"],
                                samples_per_client=config["samples_per_client"])
    init = MLP_TASK.init_params(jax.random.PRNGKey(program_seed), PAPER_TASKS[task])
    data, init = generators.relabel(data, init, seed)
    client = dict(num_classes=config["num_classes"], task=MLP_TASK, local_epochs=config["local_epochs"],
                  lr=config["lr"])
    return data, init, client


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


def train_reference(config: dict, base: np.ndarray, data, *, epochs: int, lr: float, head_only: bool,
                    cast=None) -> np.ndarray:
    """``base`` (a flat row) trained on ``data`` by the configuration's
    reference (``local_train`` of the file its ``reference`` key names),
    rounding every stored value with ``cast`` (the reference's float64 when
    None)."""
    ref = spec.load_module(BENCH_DIR / config["reference"])
    kw = dict(epochs=epochs, lr=lr, head_only=head_only, cast=cast or ref.f64)
    return ref.local_train(base, data.x_train, data.y_train, widths(config), **kw)
