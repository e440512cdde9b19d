"""Plain references for what the timed path produces, in NumPy.

Nothing here imports the program. Each function takes its inputs as host
arrays and a ``cast`` that rounds every stored value to the precision under
test: :func:`f64` for the reference itself, :func:`bf16` for the control
(the reference put in the program's place one precision below the float32
the configurations state).

* :func:`local_train` — a client's local round: full-batch gradient descent
  on the mean cross-entropy of a ReLU MLP, ``epochs`` steps at ``lr``; with
  ``head_only`` only the last layer moves (partial fine-tuning).
* :func:`ingest` — the server's sequential per-upload ingest: Eq. 1 L1
  distances to every live center, the argmin with hysteresis (a client
  leaves its cluster only for a center ``switch_margin`` closer), pinned
  partial-fine-tuning members, and the mixed-rate blend
  ``(1 - beta) * center + beta * upload`` into the chosen center.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def f64(a):
    return np.asarray(a, np.float64)


def bf16(a):
    return np.asarray(a, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


def _layers(flat, widths):
    """Split a flat row into ``[(b, w), ...]`` in the row's leaf order
    (per layer: bias then weight, as the pytree's sorted keys give)."""
    out, off = [], 0
    for din, dout in zip(widths[:-1], widths[1:]):
        b = flat[off:off + dout]
        off += dout
        w = flat[off:off + din * dout].reshape(din, dout)
        off += din * dout
        out.append((b, w))
    if off != len(flat):
        raise ValueError(f"row of {len(flat)} floats does not fit widths {widths}")
    return out


def _flat(layers):
    return np.concatenate([np.concatenate([b, w.ravel()]) for b, w in layers])


def row_floats(widths) -> int:
    return sum(din * dout + dout for din, dout in zip(widths[:-1], widths[1:]))


def local_train(flat, x, y, widths, *, epochs, lr, head_only, cast=f64):
    """Trained flat row after ``epochs`` full-batch gradient steps."""
    layers = [(cast(b), cast(w)) for b, w in _layers(f64(flat), widths)]
    x = cast(x)
    n = len(y)
    onehot = np.zeros((n, widths[-1]))
    onehot[np.arange(n), y] = 1.0
    for _ in range(epochs):
        acts = [x]
        h = x
        for i, (b, w) in enumerate(layers):
            z = cast(cast(h @ w) + b)
            h = cast(np.maximum(z, 0.0)) if i < len(layers) - 1 else z
            acts.append(h)
        logits = acts[-1]
        m = logits - logits.max(axis=1, keepdims=True)
        p = np.exp(m)
        p = cast(p / p.sum(axis=1, keepdims=True))
        g = cast((p - onehot) / n)  # d mean-NLL / d logits
        grads = []
        for i in range(len(layers) - 1, -1, -1):
            b, w = layers[i]
            grads.append((cast(g.sum(axis=0)), cast(acts[i].T @ g)))
            if i:
                g = cast(cast(g @ w.T) * (acts[i] > 0))
        grads.reverse()
        last = len(layers) - 1
        layers = [
            (b, w) if head_only and i < last
            else (cast(b - cast(lr * gb)), cast(w - cast(lr * gw)))
            for i, ((b, w), (gb, gw)) in enumerate(zip(layers, grads))
        ]
    return _flat(layers)


def ingest(centers, order, uploads, assignment, pinned, *, beta, switch_margin, cast=f64):
    """Sequential ingest of ``uploads`` (``[(client, flat row), ...]`` in
    event order) into ``centers`` (``{cluster: flat row}``; ``order`` is the
    sorted cluster list, whose position breaks distance ties). ``assignment``
    maps client -> cluster before the batch, ``pinned`` holds the
    (cluster, client) pairs under partial fine-tuning. Returns the blended
    centers and each upload's chosen cluster."""
    cm = {c: cast(v) for c, v in centers.items()}
    assign = dict(assignment)
    chosen = []
    for client, u in uploads:
        u = cast(u)
        prev = assign.get(client)
        alive = prev in cm
        if alive and (prev, client) in pinned:
            cid = prev
        else:
            d = np.asarray([np.abs(u - cm[c]).sum() for c in order])
            amin = order[int(np.argmin(d))]
            cid = amin
            if alive and prev != amin and d[order.index(amin)] > (1.0 - switch_margin) * d[order.index(prev)]:
                cid = prev
        cm[cid] = cast(cast((1.0 - beta) * cm[cid]) + cast(beta * u))
        assign[client] = cid
        chosen.append(cid)
    return cm, chosen

