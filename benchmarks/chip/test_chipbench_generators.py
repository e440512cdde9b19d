"""The benchmark's copies of the traffic generators give the same arrays as
the program's ``make_task`` and ``make_device_fleet`` for both
configurations, and the same round-time draws; a seed's relabelling leaves
the model's function unchanged."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import generators

MIX = {"D1": 0.2, "D2": 0.2, "D3": 0.2, "D5": 0.4}


@pytest.mark.parametrize("name", ["har", "image_recognition"])
def test_copies_match_the_program(name):
    from repro.data.synthetic import make_task
    from repro.fl.devices import make_device_fleet

    cfg = {"task": name, "latent_clusters": 4, "samples_per_client": 96, "device_mix": MIX,
           "base_round_time_s": 30.0}
    n = 48
    ours_rng, theirs_rng = np.random.default_rng(2**33 + 1), np.random.default_rng(2**33 + 1)
    ours = generators.make_task(cfg["task"], n, ours_rng, latent_clusters=cfg["latent_clusters"],
                                samples_per_client=cfg["samples_per_client"])
    theirs = make_task(cfg["task"], n, theirs_rng, latent_clusters=cfg["latent_clusters"],
                       samples_per_client=cfg["samples_per_client"]).clients
    assert len(ours) == len(theirs) == n
    for a, b in zip(ours, theirs):
        for key in ("x_train", "y_train", "x_test", "y_test"):
            np.testing.assert_array_equal(getattr(a, key), getattr(b, key))
        assert a.latent_cluster == b.latent_cluster
    fo = generators.make_device_fleet(n, ours_rng, cfg["device_mix"], cfg["base_round_time_s"])
    ft = make_device_fleet(n, theirs_rng, cfg["device_mix"], cfg["base_round_time_s"])
    assert [d["class"] for d in fo] == [d["class"] for d in ft]
    draws_o = [d["round_time"]() for d in fo for _ in range(3)]
    draws_t = [d["round_time"]() for d in ft for _ in range(3)]
    assert draws_o == draws_t


def _forward(layers, x):
    h = x
    for i, layer in enumerate(layers):
        h = h @ layer["w"] + layer["b"]
        if i < len(layers) - 1:
            h = np.maximum(h, 0.0)
    return h


@pytest.mark.parametrize("widths", [(64, 64, 6), (128, 128, 64, 10)])
def test_relabelling_keeps_the_function(widths):
    rng = np.random.default_rng(3)
    layers = [{"w": rng.normal(size=(a, b)), "b": rng.normal(size=b)} for a, b in zip(widths[:-1], widths[1:])]
    x = rng.normal(size=(20, widths[0])).astype(np.float32)
    y = rng.integers(0, widths[-1], size=20).astype(np.int32)
    data = [generators.ClientData(x, y, x[:5], y[:5], 0)]
    (moved,), new = generators.relabel(data, layers, 2**33 + 9)
    assert not np.array_equal(moved.x_train, x)
    want = _forward(layers, x.astype(np.float64))
    got = _forward(new, moved.x_train.astype(np.float64))
    # the relabelled logit of each relabelled class is the original one
    np.testing.assert_allclose(got[np.arange(20), moved.y_train], want[np.arange(20), y], rtol=1e-12)
    np.testing.assert_allclose(np.sort(got, axis=1), np.sort(want, axis=1), rtol=1e-12)
    again = generators.relabel(data, layers, 2**33 + 9)
    np.testing.assert_array_equal(again[0][0].x_train, moved.x_train)
