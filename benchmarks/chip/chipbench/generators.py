"""The benchmark's own copy of the traffic generators.

Copied from the program's ``repro.data.synthetic.make_task`` and
``repro.fl.devices.make_device_fleet`` so that a later change to the
program cannot move the yardstick: the same seed gives the same client
datasets and the same device round times here whatever the program does.
``test_chipbench_generators.py`` pins the copies to the program's versions
on the tree they were copied from.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

TASKS = {
    "image_recognition": dict(num_classes=10, dim=128, classes_per_client=2),
    "har": dict(num_classes=6, dim=64, classes_per_client=3),
    "sound_detection": dict(num_classes=9, dim=96, classes_per_client=3),
    "file_cleaning": dict(num_classes=2, dim=128, classes_per_client=2),
}

# device classes: (speed factor on the base round time, lognormal sigma)
DEVICE_CLASSES = {
    "D1": (4.0, 0.15),
    "D2": (2.0, 0.10),
    "D3": (1.5, 0.10),
    "D4": (1.0, 0.10),
    "D5": (8.0, 0.25),
}


@dataclasses.dataclass
class ClientData:
    """One client's local split (host numpy)."""

    x_train: np.ndarray
    y_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    latent_cluster: int

    @property
    def n(self) -> int:
        return len(self.y_train)

    def label_histogram(self, num_classes: int) -> np.ndarray:
        return np.bincount(self.y_train, minlength=num_classes).astype(np.float64)


@functools.lru_cache(maxsize=None)
def _prototypes(num_classes, dim):
    rng = np.random.default_rng(12345)
    protos = rng.normal(size=(num_classes, dim))
    return protos / np.linalg.norm(protos, axis=1, keepdims=True) * 3.0


def _orthogonal(rng, dim):
    q, _ = np.linalg.qr(rng.normal(size=(dim, dim)))
    return q


def _sample(rng, num_classes, dim, n, transform, labels, noise=1.2):
    protos = _prototypes(num_classes, dim)
    x = protos[labels] @ transform.T + noise * rng.normal(size=(n, dim))
    return x.astype(np.float32), labels.astype(np.int32)


def make_task(name, num_clients, rng, latent_clusters=4, samples_per_client=256,
              test_frac=0.2):
    """Per-client datasets: latent clusters share a class subset and a
    feature transform; proportions within the subset are skewed."""
    spec = TASKS[name]
    num_classes, dim = spec["num_classes"], spec["dim"]
    transforms = np.stack([_orthogonal(rng, dim) for _ in range(latent_clusters)])
    cpc = spec["classes_per_client"]
    subsets = []
    for k in range(latent_clusters):
        start = (k * cpc) % num_classes
        subset = [(start + j) % num_classes for j in range(cpc)]
        subsets.append(np.asarray(sorted(set(subset)), np.int64))
    clients = []
    assignment = np.sort(rng.integers(0, latent_clusters, size=num_clients))
    for k in range(latent_clusters):
        for _ in np.flatnonzero(assignment == k):
            n_total = samples_per_client + max(1, int(samples_per_client * test_frac))
            props = rng.dirichlet(np.full(len(subsets[k]), 2.0))
            labels = rng.choice(subsets[k], size=n_total, p=props)
            x, y = _sample(rng, num_classes, dim, n_total, transforms[k], labels)
            n_test = max(1, int(n_total * test_frac))
            clients.append(ClientData(x[n_test:], y[n_test:], x[:n_test], y[:n_test], k))
    rng.shuffle(clients)
    return clients


def relabel(data, params, seed):
    """The same work in another order: permutations of the input features,
    of each hidden layer's units and of the classes, drawn from ``seed``,
    applied to every client's data and to the initial MLP (a list of
    ``{"w": (in, out), "b": (out,)}`` layers). The model computes the same
    function under them, so every seed replays one draw's batch sizes and
    arrivals on arrays of its own."""
    rng = np.random.default_rng([seed, 2])
    widths = [np.shape(params[0]["w"])[0]] + [np.shape(layer["w"])[1] for layer in params]
    perms = [rng.permutation(w) for w in widths]
    inv_classes = np.argsort(perms[-1])
    layers = [{"w": np.asarray(layer["w"])[perms[i]][:, perms[i + 1]],
               "b": np.asarray(layer["b"])[perms[i + 1]]} for i, layer in enumerate(params)]

    def move(d):
        return ClientData(d.x_train[:, perms[0]], inv_classes[d.y_train].astype(np.int32),
                          d.x_test[:, perms[0]], inv_classes[d.y_test].astype(np.int32),
                          d.latent_cluster)

    return [move(d) for d in data], layers


def make_device_fleet(num_clients, rng, mix, base_round_time=30.0):
    """Per-client ``{"class", "round_time"}``; ``round_time()`` draws one
    local-round duration from the shared ``rng``."""
    names = list(mix)
    weights = np.asarray([mix[n] for n in names], np.float64)
    weights = weights / weights.sum()
    counts = np.floor(weights * num_clients).astype(int)
    while counts.sum() < num_clients:
        counts[rng.integers(0, len(names))] += 1
    assign = sum(([n] * int(c) for n, c in zip(names, counts)), [])
    rng.shuffle(assign)
    fleet = []
    for key in assign:
        factor, sigma = DEVICE_CLASSES[key]
        mean_t = base_round_time * factor

        def round_time(rng_=rng, mean=mean_t, sigma=sigma):
            return float(mean * rng_.lognormal(0.0, sigma))

        fleet.append({"class": key, "round_time": round_time})
    return fleet
