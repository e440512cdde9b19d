"""``correct`` holds the program to the reference: a clean run passes, the
bfloat16 control and each planted fault of the timed path fail.

Runs the har cell cut to 64 clients on the CPU, in-process, past the
harness's look for a chip. The faults are planted in the program for the
length of one run: a training step that hands back the model unchanged, an
ingest that leaves the centers unchanged, an ingest that takes in only
half of each batch, a trained row altered where it is produced, a blended
center altered where it is produced, and one byte too many billed.
(The cells run on one chip: there is no exchange between chips to leave
out.)
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.testing import SEED, patched, run_small, small_cell

CELL = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())[
    "workloads"][0]["name"]


@pytest.fixture(scope="module")
def clean():
    return run_small(small_cell(CELL), control=True)


def test_clean_run_is_correct(clean):
    res = clean["result"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_every_seed_replays_the_same_trajectory(clean):
    other = run_small(small_cell(CELL), seed=SEED + 1)
    assert other["result"]["correct"], other["result"]["checks"]
    assert other["info"]["warmup_trail"] == clean["info"]["warmup_trail"]
    assert other["info"]["warmup_uploads"] == clean["info"]["warmup_uploads"] > 0


def test_control_is_not_correct(clean):
    ctl = clean["control"]
    assert ctl["correct"] is False, ctl["numbers"]
    for name in ("train_gap", "ingest_gap"):  # each fails by a wide margin
        assert ctl["numbers"][name]["value"] > 3 * ctl["numbers"][name]["limit"]


def _unchanged_train(orig):
    def train_rows(self, cids, with_vecs=False):
        out = orig(self, cids, with_vecs=with_vecs)
        bases = [self.clients[self.index[c]].model for c in cids]
        return (bases, *out[1:])
    return train_rows


def _altered_train(orig):
    def train_rows(self, cids, with_vecs=False):
        out = orig(self, cids, with_vecs=with_vecs)
        trees = list(out[0])
        first = dict(trees[0][0])
        first["b"] = np.asarray(first["b"]) + np.float32(1e-2)
        trees[0] = [first] + list(trees[0][1:])
        return (trees, *out[1:])
    return train_rows


def _chain(blend):
    def make(orig):
        def ingest_chain(U, centers, bcast, prev, forced, valid, **kw):
            res = list(orig(U, centers, bcast, prev, forced, valid, **kw))
            res[1] = blend(res[1], centers, res[0], int(np.sum(valid)))
            return tuple(res)
        return ingest_chain
    return make


def _half_batch(orig):
    def handle_uploads(self, batch):
        h = max(1, len(batch) // 2)
        return orig(self, batch[:h]) + [[] for _ in batch[h:]]
    return handle_uploads


def _extra_byte(orig):
    def upload(self, nbytes, t, raw_nbytes=None, retry=False):
        if self.up_events == 0:  # each network overbills its first upload
            nbytes += 1
        return orig(self, nbytes, t, raw_nbytes=raw_nbytes, retry=retry)
    return upload


def _faults():
    from repro.core.server import EchoPFLServer
    from repro.fl.fleet import ClientFleet
    from repro.fl.network import NetworkModel
    import repro.kernels.ops as ops

    return {
        "train_unchanged": (ClientFleet, "train_rows", _unchanged_train),
        "ingest_unchanged": (ops, "ingest_chain", _chain(lambda b, c, cid, n: c[cid])),
        "half_batch": (EchoPFLServer, "handle_uploads", _half_batch),
        "train_altered": (ClientFleet, "train_rows", _altered_train),
        "center_altered": (ops, "ingest_chain", _chain(
            # the segment's last blend is its cluster's final center
            lambda b, c, cid, n: b.at[n - 1, 0].add(1e-2))),
        "extra_byte": (NetworkModel, "upload", _extra_byte),
    }


@pytest.mark.parametrize("fault", ["train_unchanged", "ingest_unchanged", "half_batch",
                                   "train_altered", "center_altered", "extra_byte"])
def test_planted_fault_is_not_correct(fault):
    obj, name, make = _faults()[fault]
    with patched(obj, name, make):
        res = run_small(small_cell(CELL))["result"]
    assert not res["correct"], (fault, res["checks"])
    assert res["failed"] > 0
