"""The :class:`PersonalizationTask` protocol: what a workload must provide
for the fleet engine, the simulator, and the EchoPFL server to personalize
it as plane rows.

EchoPFL's coordination layer never looks inside a model: the server blends
flat rows (Eq. 1 distances + mixed-rate lerp), the fleet engine trains
batched flat rows, and feedback is a pair of class distributions (Eq. 2/3).
Everything task-specific — what a model pytree IS, how a client's dataset
becomes batched device tensors, what one local epoch does, what the
feedback histograms count — lives behind this protocol. The seed repo
hard-coded the toy MLP in ``fl/fleet.py`` / ``core/client.py`` /
``fl/experiment.py``; those layers now only call task methods.

Implementations must be hashable value objects (frozen dataclasses): the
fleet's fused launches pass the task as a static jit argument, so a task's
identity keys the compile cache the same way the flatten spec does. Arrays
a task computes with but never trains (a frozen base) are its
``operands``: the fleet hands them to every launch as an argument, so they
are never folded into a compiled program as constants.

Two tasks ship:

* :class:`MLPTask` (``REPRO_TASK=mlp``, the default) — the paper's toy-MLP
  workload, delegating 1:1 to :mod:`repro.models.mlp`. The delegation is
  call-for-call identical to the seed wiring, so default trajectories are
  bitwise-unchanged.
* ``LMTask`` (``REPRO_TASK=lm``, :mod:`repro.fl.lm_task`) — per-client
  LoRA/head deltas over a frozen transformer base; the deltas are the
  plane rows.

The per-client methods (``local_train`` / ``evaluate`` /
``feedback_inputs``) serve the loop backend and :class:`SimClient`; the
``fleet_*`` methods are jit-pure batched counterparts the fleet engine
vmaps — both views of the same arithmetic.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass
class FleetData:
    """Batched device tensors for one fleet: ``train``/``test`` are dicts of
    ``(clients, ...)`` arrays whose layout only the owning task interprets
    (the fleet gathers rows by client index inside its launches and passes
    the dict through); ``f_true`` is the (clients, J) matrix of true label
    histograms feeding the chi2 kernels; ``tokens``, where the task trains
    on tokens, each client's tokens per epoch."""

    train: dict[str, jax.Array]
    test: dict[str, jax.Array]
    f_true: np.ndarray
    tokens: np.ndarray | None = None


def pad_rows(arr: np.ndarray, n: int) -> np.ndarray:
    """Zero-pad a per-client array's leading dim to ``n`` rows."""
    if len(arr) == n:
        return arr
    return np.concatenate([arr, np.zeros((n - len(arr),) + arr.shape[1:], arr.dtype)])


@runtime_checkable
class PersonalizationTask(Protocol):
    """What the coordination layers require of a workload.

    ``name`` tags the task; ``init_params(key)`` builds the model pytree a
    client uploads (for delta-style tasks: the DELTA pytree — the frozen
    base never rides the wire and never becomes plane rows; flattening this
    pytree with ``repro.common.pytrees.flatten_spec`` defines the row).
    """

    name: str

    # ---- model surface -------------------------------------------------
    def init_params(self, key: jax.Array) -> PyTree: ...

    @property
    def operands(self) -> PyTree:
        """Device arrays every fleet launch takes as its ``ops`` argument."""
        ...

    # ---- fleet engine (batched, jit-pure, task static) -----------------
    def build_fleet_data(
        self, datasets: list[Any], shard: Callable[[jax.Array], jax.Array],
        num_classes: int,
    ) -> FleetData: ...

    def client_bytes(self, kind: str, data: dict[str, jax.Array]) -> int:
        """Device bytes one client's share of a ``train``, ``eval`` or
        ``feedback`` launch over ``data`` holds at its peak (the fleet
        sizes its client blocks from it)."""
        ...

    def fleet_local_train(
        self, ops: PyTree, params_b: PyTree, train: dict[str, jax.Array], lr: jax.Array,
        epochs: jax.Array, head: jax.Array, *, max_epochs: int,
    ) -> tuple[PyTree, jax.Array, dict[str, jax.Array]]:
        """(trained params, losses, per-client counters) for a batch of
        clients; the counters (empty for a task that counts nothing) are
        summed by the fleet after its fetch."""
        ...

    def fleet_evaluate(
        self, ops: PyTree, params_b: PyTree, test: dict[str, jax.Array]
    ) -> jax.Array: ...

    def fleet_feedback(
        self, ops: PyTree, params_b: PyTree, train: dict[str, jax.Array], num_classes: int
    ) -> tuple[jax.Array, jax.Array]: ...

    # ---- per-client (loop backend / SimClient) -------------------------
    def local_train(
        self, params: PyTree, data: Any, *, epochs: int, lr: float, head_only: bool
    ) -> tuple[PyTree, Any]: ...

    def evaluate(self, params: PyTree, data: Any) -> float: ...

    def feedback_inputs(
        self, params: PyTree, data: Any, num_classes: int
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]: ...


@dataclasses.dataclass(frozen=True)
class MLPTask:
    """The paper's toy-MLP workload (the seed behavior, bit-for-bit): every
    method delegates to :mod:`repro.models.mlp` with exactly the operands
    the pre-protocol code passed."""

    name: str = "mlp"

    # ---- model surface -------------------------------------------------
    def init_params(self, key, cfg=None):
        from repro.configs.paper_tasks import PAPER_TASKS
        from repro.models.mlp import init_mlp

        return init_mlp(cfg or PAPER_TASKS["image_recognition"], key)

    @property
    def operands(self):
        return ()

    # ---- fleet engine --------------------------------------------------
    def build_fleet_data(self, datasets, shard, num_classes):
        n_tr = max(len(d.y_train) for d in datasets)
        n_te = max(len(d.y_test) for d in datasets)
        train = {
            "x": shard(jnp.asarray(np.stack(
                [pad_rows(np.asarray(d.x_train, np.float32), n_tr) for d in datasets]))),
            "y": shard(jnp.asarray(np.stack(
                [pad_rows(np.asarray(d.y_train, np.int32), n_tr) for d in datasets]))),
            "mask": shard(jnp.asarray(np.stack(
                [pad_rows(np.ones(len(d.y_train), np.float32), n_tr) for d in datasets]))),
        }
        test = {
            "x": shard(jnp.asarray(np.stack(
                [pad_rows(np.asarray(d.x_test, np.float32), n_te) for d in datasets]))),
            "y": shard(jnp.asarray(np.stack(
                [pad_rows(np.asarray(d.y_test, np.int32), n_te) for d in datasets]))),
            "mask": shard(jnp.asarray(np.stack(
                [pad_rows(np.ones(len(d.y_test), np.float32), n_te) for d in datasets]))),
        }
        f_true = np.stack([
            d.label_histogram(num_classes).astype(np.float32) for d in datasets
        ])
        return FleetData(train=train, test=test, f_true=f_true)

    def client_bytes(self, kind, data):
        """Each sample's features and the widest activation: kilobytes."""
        return 4 * 8 * int(np.prod(data["x"].shape[1:]))

    def fleet_local_train(self, ops, params_b, train, lr, epochs, head, *, max_epochs):
        from repro.models import mlp

        params, losses = mlp.fleet_local_train(
            params_b, train["x"], train["y"], train["mask"], lr, epochs, head,
            max_epochs=max_epochs,
        )
        return params, losses, {}

    def fleet_evaluate(self, ops, params_b, test):
        from repro.models import mlp

        return mlp.fleet_evaluate(params_b, test["x"], test["y"], test["mask"])

    def fleet_feedback(self, ops, params_b, train, num_classes):
        from repro.models import mlp

        return mlp.fleet_predict_distributions(
            params_b, train["x"], train["mask"], num_classes
        )

    # ---- per-client ----------------------------------------------------
    def local_train(self, params, data, *, epochs, lr, head_only):
        from repro.models import mlp

        return mlp.local_train(
            params, jnp.asarray(data.x_train), jnp.asarray(data.y_train),
            epochs=epochs, lr=lr, head_only=head_only,
        )

    def evaluate(self, params, data):
        from repro.models import mlp

        return float(mlp.evaluate(
            params, jnp.asarray(data.x_test), jnp.asarray(data.y_test)
        ))

    def feedback_inputs(self, params, data, num_classes):
        from repro.models import mlp

        f_pred, s_soft = mlp.predict_distributions(
            params, jnp.asarray(data.x_train), num_classes
        )
        f_true = data.label_histogram(num_classes)
        return np.asarray(f_pred), f_true.astype(np.float32), np.asarray(s_soft)


MLP_TASK = MLPTask()


def get_task(name: str) -> PersonalizationTask:
    """Resolve a task implementation by name (``mlp`` | ``lm``)."""
    if name == "mlp":
        return MLP_TASK
    if name == "lm":
        from repro.fl.lm_task import default_lm_task

        return default_lm_task()
    raise ValueError(f"unknown REPRO_TASK {name!r}: expected 'mlp' or 'lm'")


def default_task() -> PersonalizationTask:
    """The REPRO_TASK env knob (default ``mlp``). Builders consult this;
    :class:`SimClient` itself defaults to the MLP task only when its
    ``task`` field is unset, so constructed fleets never change task
    mid-flight because the environment did."""
    return get_task(os.environ.get("REPRO_TASK", "mlp"))
