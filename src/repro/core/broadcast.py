"""In-cluster on-demand model broadcast (paper Sec. 5).

Decision rule: broadcast iff the predicted next model change exceeds the
accumulated change since the last broadcast,
    L1(v_hat^{t+1}, v^t)  >  L1(v^t, v_bcast^t).
Ground truth for training the predictor (Eq. 4):
    h = L1(v_c^{t-1}, v_bcast^{t-1}) - L1(v_c^{t-1}, v_c^t) >= 0  -> broadcast.

A small 2x128-unit vanilla RNN consumes the cluster's Top-K recent
L1-change records (K proportional to cluster size; we store change degrees,
not model weights, to save memory — Sec. 5.2.1) and emits P(broadcast).
It is pre-trained on 1200 synthetic historical states and fine-tuned online
on every realized ground truth. Predictor state follows the maintenance
rules of Sec. 5.2.2 under cluster expansion/merging.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.tracing import fetch

PyTree = Any
HIDDEN = 128
NUM_LAYERS = 2


def predictor_batch_enabled() -> bool:
    """``REPRO_PREDICTOR_BATCH`` knob: batch the per-cluster predictor
    learn/decide chains of a coalesced window into one fused launch
    (default on). ``0`` / ``off`` keeps the per-upload serial dispatches —
    the parity arm ci.sh exercises."""
    spec = os.environ.get("REPRO_PREDICTOR_BATCH", "1").strip().lower()
    return spec not in ("", "0", "off", "none", "no")


# ---------------------------------------------------------------- RNN model
def init_rnn(key: jax.Array, hidden: int = HIDDEN) -> PyTree:
    ks = jax.random.split(key, 2 * NUM_LAYERS + 1)
    params = {}
    dim_in = 1
    for layer in range(NUM_LAYERS):
        params[f"wx{layer}"] = jax.random.normal(ks[2 * layer], (dim_in, hidden)) / np.sqrt(dim_in)
        params[f"wh{layer}"] = jax.random.normal(ks[2 * layer + 1], (hidden, hidden)) / np.sqrt(hidden)
        params[f"b{layer}"] = jnp.zeros((hidden,))
        dim_in = hidden
    params["w_out"] = jax.random.normal(ks[-1], (hidden, 2)) / np.sqrt(hidden)
    params["b_out"] = jnp.zeros((2,))
    return params


@jax.jit
def rnn_logits(params: PyTree, seq: jax.Array) -> jax.Array:
    """seq: (T, 1) normalized change records -> (2,) [no-bcast, bcast] logits."""
    x = seq
    for layer in range(NUM_LAYERS):
        h0 = jnp.zeros((params[f"wh{layer}"].shape[0],))

        def step(h, x_t, l=layer):
            h_new = jnp.tanh(x_t @ params[f"wx{l}"] + h @ params[f"wh{l}"] + params[f"b{l}"])
            return h_new, h_new

        _, hs = jax.lax.scan(step, h0, x)
        x = hs
    return hs[-1] @ params["w_out"] + params["b_out"]


@jax.jit
def _rnn_sgd(params: PyTree, seq: jax.Array, label: jax.Array, lr: jax.Array) -> tuple[PyTree, jax.Array]:
    def loss_fn(p):
        logits = rnn_logits(p, seq)
        return -jax.nn.log_softmax(logits)[label]

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads), loss


@jax.jit
def _rnn_want(params: PyTree, seq: jax.Array) -> jax.Array:
    """Fused forward + argmax decision: one dispatch per broadcast decision
    instead of a logits launch plus two eager argmax/compare dispatches.
    Same logits, same first-index argmax tie-breaking — bitwise-identical
    decisions to the unfused form."""
    return jnp.argmax(rnn_logits(params, seq)) == 1


# ----------------------------------------------------- batched chain bodies
# Predictors carry different Top-K window lengths (k = max(top_k, size at
# creation)), so a batched launch front-pads every sequence to one common
# length and tells the RNN where the real window starts. Holding h at zero
# for t < start makes step `start` see exactly the serial initial state, so
# every arithmetic op on valid steps consumes the same values as the
# exact-k form — the trajectory stays bitwise-identical (the padded steps
# contribute exact zeros to the scan-transposed gradient accumulation).
def _rnn_logits_masked(params: PyTree, seq: jax.Array, start: jax.Array) -> jax.Array:
    """seq: (T, 1) front-padded records; rows with t < start are padding."""
    tpos = jnp.arange(seq.shape[0])
    x = seq
    for layer in range(NUM_LAYERS):
        h0 = jnp.zeros((params[f"wh{layer}"].shape[0],))

        def step(h, inp, l=layer):
            x_t, t = inp
            h_new = jnp.tanh(x_t @ params[f"wx{l}"] + h @ params[f"wh{l}"] + params[f"b{l}"])
            h_new = jnp.where(t >= start, h_new, jnp.zeros_like(h_new))
            return h_new, h_new

        # NOTE: no scan unroll here — unrolling refuses the serial op
        # schedule (XLA fuses the unrolled bodies differently) and breaks
        # the bitwise match with rnn_logits that predictor_chain guarantees
        _, hs = jax.lax.scan(step, h0, (x, tpos))
        x = hs
    return hs[-1] @ params["w_out"] + params["b_out"]


def _rnn_sgd_masked(
    params: PyTree, seq: jax.Array, label: jax.Array, lr: jax.Array, start: jax.Array
) -> PyTree:
    def loss_fn(p):
        return -jax.nn.log_softmax(_rnn_logits_masked(p, seq, start))[label]

    _, grads = jax.value_and_grad(loss_fn)(params)
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)


def rnn_chain_step(params: PyTree, pre: jax.Array, post: jax.Array, label: jax.Array,
                   learn_gate: jax.Array, decide_gate: jax.Array, lr: jax.Array,
                   start: jax.Array) -> tuple[PyTree, jax.Array]:
    """One upload's predictor work: gated SGD step on the pre-observe window,
    then the gated broadcast decision on the post-observe window. The scan
    body of :func:`repro.kernels.ops.predictor_chain`.

    The gates are ``lax.cond``s, not post-hoc selects: inside a (non-vmapped)
    scan a cond stays a real conditional, so learn-only steps skip the
    decision forward, decide-only steps skip the whole SGD, and the pad
    steps the caller appends for shape bucketing cost one branch dispatch
    instead of a full RNN forward+backward. With a post-hoc ``where`` the
    packed chain paid ~2.5x the serial path's arithmetic and lost the
    batching win on CPU."""
    params = jax.lax.cond(
        learn_gate,
        lambda p: _rnn_sgd_masked(p, pre, label, lr, start),
        lambda p: p,
        params,
    )
    want = jax.lax.cond(
        decide_gate,
        lambda p: jnp.argmax(_rnn_logits_masked(p, post, start)) == 1,
        lambda p: jnp.asarray(False),
        params,
    )
    return params, want


def build_seq(records: list, k: int) -> np.ndarray:
    """Normalized (k, 1) change-record window from a records list — the
    single source of truth for both the per-predictor serial path
    (:meth:`BroadcastPredictor._seq`) and the batched window planner, which
    replays record evolution host-side and must produce bit-identical
    operands."""
    rec = records[-k:]
    rec = [0.0] * (k - len(rec)) + rec  # zero-pad (expansion reset rule)
    norm = max(max((abs(r) for r in rec), default=0.0), 1e-12)  # match pretraining
    return np.asarray(rec, np.float32)[:, None] / norm


# ------------------------------------------------------------- per-cluster
@dataclasses.dataclass
class BroadcastPredictor:
    """Per-cluster predictor state: Top-K records + RNN weights."""

    params: PyTree
    k: int = 10
    records: list = dataclasses.field(default_factory=list)  # recent L1 change degrees
    active: bool = True  # deactivated right after expansion (Sec. 5.2.2)
    scale: float = 1.0  # running normalizer for change degrees
    decisions: int = 0
    broadcasts: int = 0

    def observe(self, change: float) -> None:
        self.records.append(float(change))
        self.records = self.records[-max(self.k, 1):]
        self.scale = 0.9 * self.scale + 0.1 * max(abs(change), 1e-12)

    def _seq(self) -> np.ndarray:
        """Normalized (k, 1) change-record window, built host-side in numpy.

        This runs on every online learn AND every RNN decision — per upload
        on the server hot path — so it must not cost device dispatches. The
        previous jnp version paid three (asarray, reshape, divide) before
        the RNN launch even started. The numpy form is bitwise-identical:
        float32 array ops with a weak python-float norm divide the same way
        under NumPy 2 promotion as under jax, and the jit boundary uploads
        the 10-float array in the same dispatch as the RNN itself."""
        return build_seq(self.records, self.k)

    def decide(self, accumulated_gap: float, fallback_threshold: float = 1.0) -> bool:
        """RNN decision; when inactive (fresh expansion) never broadcast."""
        self.decisions += 1
        if not self.active:
            self.active = True  # one suppressed decision, then resume
            return False
        if len(self.records) < 2:  # cold start: rule-based fallback
            want = accumulated_gap > fallback_threshold * self.scale
        else:
            want = bool(fetch(_rnn_want(self.params, self._seq()), "decide"))
        if want:
            self.broadcasts += 1
        return want

    def learn(self, label: int, lr: float = 1e-2):
        """Online fine-tune on the realized ground truth (Eq. 4). Returns
        the loss as a *device scalar* — this runs once per upload on the
        server hot path, and forcing a host readback here would stall the
        dispatch pipeline; call ``float()`` on it if you need the value."""
        self.params, loss = _rnn_sgd(self.params, self._seq(), jnp.asarray(label), jnp.asarray(lr))
        return loss


# ------------------------------------------------------------ maintenance
def predictor_for_expansion(parent: BroadcastPredictor, change_of_new_client: float) -> BroadcastPredictor:
    """Expansion rules: reset records to the new client (+zero pad), inherit
    RNN weights, deactivate broadcast (center is already fresh)."""
    child = BroadcastPredictor(params=parent.params, k=parent.k, scale=parent.scale)
    child.records = [float(change_of_new_client)]
    child.active = False
    return child


def predictor_for_merge(a: BroadcastPredictor, b: BroadcastPredictor) -> BroadcastPredictor:
    """Merge rules: resample Top-K records proportional to each side's
    record variance (prioritize larger weight changes), distill the two RNNs
    (weight-space average — the training-free analogue of Sec. 4.3.2 used
    for the predictor), and force an immediate broadcast (handled by caller).
    """
    va = float(np.var(a.records)) if len(a.records) > 1 else 0.0
    vb = float(np.var(b.records)) if len(b.records) > 1 else 0.0
    total = va + vb
    k = max(a.k, b.k)
    if total <= 0:
        n_a = min(len(a.records), k // 2)
    else:
        n_a = int(round(k * va / total))
    n_a = min(n_a, len(a.records))
    n_b = min(k - n_a, len(b.records))
    rec_a = sorted(a.records, key=abs)[-n_a:] if n_a else []
    rec_b = sorted(b.records, key=abs)[-n_b:] if n_b else []
    merged_params = jax.tree_util.tree_map(lambda x, y: 0.5 * (x + y), a.params, b.params)
    out = BroadcastPredictor(params=merged_params, k=k, scale=max(a.scale, b.scale))
    out.records = rec_a + rec_b
    return out


# -------------------------------------------------------------- pretraining
def pretrain_rnn(key: jax.Array, k: int = 10, num_states: int = 1200, lr: float = 5e-3) -> PyTree:
    """Pre-train on synthetic historical states (Sec. 5.2.1): decaying change
    sequences labeled by the paper's h() rule applied to a simulated L1 walk."""
    params = init_rnn(key)
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    for _ in range(num_states):
        decay = rng.uniform(0.6, 1.5)  # reversed one-step ratio spans (0.67, 1.67)
        base = rng.uniform(0.5, 2.0)
        noise = rng.uniform(0.02, 0.3)
        seq = base * decay ** np.arange(k) * (1 + noise * rng.standard_normal(k))
        seq = np.abs(seq)[::-1]  # oldest -> newest (one-step ratio is 1/decay)
        accumulated = float(np.sum(seq[-3:]))
        predicted_next = float(seq[-1] / decay)
        # Sec. 5.2.1 text rule: broadcast iff the predicted next model change
        # exceeds the accumulated recent change level ("broadcasts more
        # frequently given notable model changes; less frequently otherwise").
        # The 1.15 margin keeps flat/converged sequences on the "hold" side —
        # steady-state training shouldn't re-broadcast every aggregation.
        label = 1 if predicted_next > 1.15 * accumulated / 3 else 0
        scale = max(float(np.max(seq)), 1e-9)
        x = jnp.asarray(seq / scale, jnp.float32)[:, None]
        params, _ = _rnn_sgd(params, x, jnp.asarray(label), jnp.asarray(lr))
    return params
