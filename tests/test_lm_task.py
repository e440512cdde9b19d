"""REPRO_TASK=lm: LoRA/head-delta personalization over a frozen LM base.

Covers the task surface (zero-init delta == base model, loss decreases,
head-only freezing), delta-only payload billing through ``model_bytes``
(the FrozenBase wrapper contributes zero bytes), loop/fleet backend
agreement, and end-to-end runs through both ``run_sync`` (FedAvg) and the
coalesced async event loop (EchoPFL)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.lm_task import (
    FrozenBase,
    LMClientData,
    default_lm_task,
    make_lm_data,
    run_lm_experiment,
)
from repro.fl.simulator import model_bytes
from repro.fl.tasks import PersonalizationTask, get_task
from repro.models.model import forward as model_forward

TASK = default_lm_task()
SMALL = dict(seq_len=16, n_train=4, n_test=2, local_epochs=1, eval_interval=60.0)


def _data(n_clients=2, seed=0):
    return make_lm_data(
        n_clients, vocab_size=TASK.cfg.vocab_size,
        n_train=4, n_test=2, seq_len=16, seed=seed,
    )


def test_is_personalization_task():
    assert isinstance(TASK, PersonalizationTask)
    assert get_task("lm") is TASK  # singleton: stable jit-cache key


def test_initial_delta_is_exact_zero_update():
    """LoRA b-factors init to zero, so the base with the init delta's
    adapters must equal the frozen base bitwise — every client starts at the
    plane origin."""
    delta = TASK.init_params(jax.random.PRNGKey(3))
    tokens = jnp.asarray(_data()[0].tokens_train)
    base_logits, _, _ = model_forward(TASK.cfg, TASK.base.params, {"tokens": tokens})
    one = jax.tree_util.tree_map(lambda x: x[None], delta)
    adapted_logits, _ = TASK._forward(TASK.operands, one, tokens[None])
    assert jnp.array_equal(base_logits, adapted_logits[0])


def test_local_train_reduces_loss():
    d = _data()[0]
    delta = TASK.init_params(jax.random.PRNGKey(0))
    tok, lab = jnp.asarray(d.tokens_train), jnp.asarray(d.labels_train)
    mask = jnp.ones((d.n,), jnp.float32)
    one = lambda x: jnp.asarray(x)[None]  # noqa: E731  a batch of one client
    p = jax.tree_util.tree_map(one, delta)
    first = float(TASK._nll(TASK.operands, p, one(tok), one(lab), one(mask))[0][0])
    for _ in range(4):
        p, loss, _ = TASK._scan_train(
            TASK.operands, p, one(tok), one(lab), one(mask), one(0.5), one(5), one(0.0),
            max_epochs=5,
        )
    assert float(loss[0]) < first - 0.2
    assert np.isfinite(float(loss[0]))


def test_head_only_freezes_block_lora():
    d = _data()[0]
    delta = TASK.init_params(jax.random.PRNGKey(0))
    one = lambda x: jnp.asarray(x)[None]  # noqa: E731  a batch of one client
    trained, _, _ = TASK._scan_train(
        TASK.operands, jax.tree_util.tree_map(one, delta),
        one(d.tokens_train), one(d.labels_train), jnp.ones((1, d.n), jnp.float32),
        one(0.5), one(2), one(1.0), max_epochs=2,
    )
    # wq LoRA untouched, head LoRA moved
    for slot in delta["wq"]:
        assert jnp.array_equal(trained["wq"][slot]["a"][0], delta["wq"][slot]["a"])
        assert jnp.array_equal(trained["wq"][slot]["b"][0], delta["wq"][slot]["b"])
    assert not jnp.array_equal(trained["head_b"][0], delta["head_b"])


def test_feedback_inputs_shapes_and_mass():
    d = _data()[0]
    delta = TASK.init_params(jax.random.PRNGKey(0))
    J = TASK.buckets
    f_pred, f_true, s_soft = TASK.feedback_inputs(delta, d, J)
    assert f_pred.shape == f_true.shape == s_soft.shape == (J,)
    # f_pred / f_true are COUNT histograms over the same n*S positions
    assert np.isclose(f_pred.sum(), d.n * d.tokens_train.shape[1])
    assert np.isclose(f_true.sum(), d.n * d.tokens_train.shape[1])
    # s_soft is a mean softmax over buckets -> sums to 1
    assert np.isclose(s_soft.sum(), 1.0, atol=1e-4)


def test_latent_clusters_share_distribution_not_samples():
    data = make_lm_data(8, vocab_size=TASK.cfg.vocab_size, latent_clusters=4,
                        n_train=8, n_test=2, seq_len=32, seed=0)
    J = TASK.buckets
    hists = np.stack([d.label_histogram(J) for d in data])
    hists /= hists.sum(axis=1, keepdims=True)
    # same latent cluster (0 and 4) -> near-identical bucket distribution
    same = np.abs(hists[0] - hists[4]).sum()
    cross = np.abs(hists[0] - hists[1]).sum()
    assert same < cross, (same, cross)
    # ...but not the same sequences
    assert not np.array_equal(data[0].tokens_train, data[4].tokens_train)


# ---------------------------------------------------------------------------
# delta-aware payload accounting
# ---------------------------------------------------------------------------


def test_frozen_base_bills_zero_bytes():
    """FrozenBase is a static pytree: payloads that carry it are billed at
    delta size only — the wire never pays for the frozen base."""
    delta = TASK.init_params(jax.random.PRNGKey(0))
    delta_bytes = model_bytes(delta)
    assert model_bytes(TASK.base) == 0
    assert model_bytes({"base": TASK.base, "delta": delta}) == delta_bytes
    # sanity: the delta is orders of magnitude smaller than the base
    base_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(TASK.base.params))
    assert delta_bytes < base_bytes / 3


def test_sim_bills_uploads_at_delta_size():
    delta_bytes = model_bytes(TASK.init_params(jax.random.PRNGKey(0)))
    _, _, _, rep = run_lm_experiment("fedavg", num_clients=4, rounds=2, **SMALL)
    assert rep.up_events > 0
    assert rep.up_bytes == rep.up_events * delta_bytes


# ---------------------------------------------------------------------------
# end-to-end
# ---------------------------------------------------------------------------


def test_run_sync_fedavg():
    task, clients, strat, rep = run_lm_experiment(
        "fedavg", num_clients=4, rounds=2, **SMALL)
    assert rep.extra["task"] == "lm"
    assert rep.up_events == 8  # 4 clients x 2 rounds
    assert 0.0 <= rep.final_acc <= 1.0
    assert len(rep.curve) > 0


def test_run_async_echopfl_coalesced(monkeypatch):
    monkeypatch.setenv("REPRO_ASYNC_COALESCE", "1")
    task, clients, strat, rep = run_lm_experiment(
        "echopfl", num_clients=4, max_time=200.0, num_clusters=2, **SMALL)
    assert rep.up_events > 0
    assert rep.extra["task"] == "lm"
    assert 0.0 <= rep.final_acc <= 1.0


def test_loop_fleet_backend_agree():
    """The batched fleet launches and the per-client loop implement the
    same task arithmetic."""
    runs = {}
    for backend in ("loop", "fleet"):
        _, _, _, rep = run_lm_experiment(
            "fedavg", num_clients=4, rounds=2, seed=1,
            client_backend=backend, **SMALL)
        runs[backend] = rep
    assert runs["loop"].up_events == runs["fleet"].up_events
    assert np.isclose(runs["loop"].final_acc, runs["fleet"].final_acc, atol=1e-5)


def test_repro_task_env_dispatch(monkeypatch):
    """run_experiment reroutes to the LM driver under REPRO_TASK=lm."""
    monkeypatch.setenv("REPRO_TASK", "lm")
    from repro.fl.experiment import run_experiment
    task, clients, strat, rep = run_experiment(
        "image_recognition", "fedavg", num_clients=4, rounds=2,
        local_epochs=1, eval_interval=60.0)
    assert rep.extra["task"] == "lm"
    assert task is TASK


# ---------------------------------------------------------------------------
# adapters on activations, the base as a launch operand, client blocks
# ---------------------------------------------------------------------------


def _mla_moe_cfg():
    """A small config of DeepSeek-V2-Lite's kind: a dense leading layer,
    MLA with YaRN, expert layers holding 2 of 4 experts (dropless)."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import reduced_config

    cfg = reduced_config(get_config("deepseek-v2-lite-16b"))
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=2), moe_dropless=True)


def test_lora_on_activations_equals_merged_weights():
    """The adapters added beside the frozen projections give the logits of
    the base with ``s * a @ b`` merged into each targeted weight and the
    head."""
    from repro.fl.lm_task import LMTask

    cfg = _mla_moe_cfg()
    task = LMTask.build(cfg, 0, lora_rank=4, targets=("wq", "w_dkv"))
    key = jax.random.PRNGKey(7)
    delta = task.init_params(key)
    delta = jax.tree_util.tree_map(  # b factors moved off zero, as after training
        lambda x: x + 0.05 * jax.random.normal(jax.random.fold_in(key, x.size), x.shape), delta)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 12)), jnp.int32)
    s = 1.0 / 4
    base = task.operands
    merged = dict(base)
    merged["lm_head"] = base["lm_head"] + s * delta["head_a"] @ delta["head_b"]
    prefix = [dict(lp) for lp in base["prefix"]]
    for i, lp in enumerate(prefix):
        mx = dict(lp["mixer"])
        for t, ab in delta["prefix"][f"layer{i}"].items():
            mx[t] = mx[t] + (s * ab["a"] @ ab["b"]).reshape(mx[t].shape)
        lp["mixer"] = mx
    merged["prefix"] = prefix
    slot = dict(base["blocks"]["slot0"])
    mx = dict(slot["mixer"])
    for t in ("wq", "w_dkv"):
        ab = delta[t]["slot0"]
        mx[t] = mx[t] + (s * jnp.einsum("pdr,prk->pdk", ab["a"], ab["b"])).reshape(mx[t].shape)
    slot["mixer"] = mx
    merged["blocks"] = {"slot0": slot}
    with jax.default_matmul_precision("highest"):
        want, _, _ = model_forward(cfg, merged, {"tokens": tokens})
        got, _ = task._forward(base, jax.tree_util.tree_map(lambda x: x[None], delta), tokens[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_train_launch_takes_the_base_as_a_parameter():
    """The fleet's train launch lowers with every base leaf among its
    parameters, and with no constant the size of a base leaf."""
    import re

    from repro.common.pytrees import flatten_spec
    from repro.fl.fleet import _train_launch_bank

    delta = TASK.init_params(jax.random.PRNGKey(0))
    spec = flatten_spec(delta)
    n = 4
    data = _data(n)
    fd = TASK.build_fleet_data(data, lambda x: x, TASK.buckets)
    bank = jnp.zeros((n, spec.dim), jnp.float32)
    idx = jnp.arange(n, dtype=jnp.int32)
    lowered = _train_launch_bank.lower(
        bank, idx, fd.train, idx, jnp.full(n, 0.1), jnp.ones(n, jnp.int32), jnp.zeros(n), TASK.operands,
        spec=spec, max_epochs=1, task=TASK, block=n)
    args = lowered.args_info[0]
    ops = args[7]
    assert jax.tree_util.tree_structure(ops) == jax.tree_util.tree_structure(TASK.operands)
    assert [a.shape for a in jax.tree_util.tree_leaves(ops)] == [
        x.shape for x in jax.tree_util.tree_leaves(TASK.operands)]
    smallest = min(x.size for x in jax.tree_util.tree_leaves(TASK.operands) if x.ndim >= 2)
    text = lowered.as_text()
    sizes = [int(np.prod([int(d) for d in m.split("x")[:-1]] or [1]))
             for m in re.findall(r"constant[^\n]*?: tensor<([0-9x]*[a-z0-9]+)>", text)]
    assert max(sizes, default=0) < smallest, max(sizes)


def test_client_blocks_give_the_one_block_result(monkeypatch):
    """A launch split into client blocks (a ``lax.map`` over blocks) trains,
    probes and evaluates every client as the one-block launch does."""
    from repro.core.client import SimClient
    from repro.fl import fleet as fleet_mod
    from repro.fl.lm_task import LMTask

    cfg = _mla_moe_cfg()
    task = LMTask.build(cfg, 0, lora_rank=4, targets=("wq", "w_dkv"), dtype=jnp.bfloat16)
    n = 6
    data = make_lm_data(n, vocab_size=cfg.vocab_size, n_train=2, n_test=2, seq_len=8, seed=3)
    clients = [SimClient(client_id=i, data=data[i], num_classes=task.buckets, device_class="D1",
                         round_time_fn=lambda: 1.0, local_epochs=1 + i % 2, lr=0.5, task=task)
               for i in range(n)]
    init = task.init_params(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    models = [jax.tree_util.tree_map(
        lambda x, i=i: x + 0.05 * jax.random.normal(jax.random.fold_in(key, i * 1000 + x.size), x.shape), init)
        for i in range(n)]
    cids = list(range(n))

    def run():
        fl = fleet_mod.ClientFleet(clients, init)
        fl.set_models(cids, models)
        trained, losses = fl.train_rows([0, 2, 3, 5])
        f_pred, _, s_soft = fl.feedback_many([(c, models[(c + 1) % n]) for c in cids])
        return ([np.asarray(harness_flat(t)) for t in trained], np.asarray(losses), f_pred, s_soft,
                fl.evaluate_fleet([None] * n))

    one = run()
    per = task.client_bytes("train", {"tokens": np.zeros((n, 2, 8))})
    monkeypatch.setattr(fleet_mod, "_device_bytes", lambda: 2 * 2 * per)  # two clients per block
    assert fleet_mod._block(task, "train", {"tokens": np.zeros((n, 2, 8))}, 8) == 2
    blocked = run()
    for a, b in zip(one[0], blocked[0]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for a, b in zip(one[1:], blocked[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def harness_flat(tree):
    return np.concatenate([np.ravel(np.asarray(x, np.float32)) for x in jax.tree_util.tree_leaves(tree)])


def test_train_launch_records_tokens_and_routed_pairs(tmp_path):
    """While the profiler records, the fleet's train launch carries the tokens
    it trains (``train/launch``) and, after its fetch, the pairs its forward
    passes routed to held experts (``train/routed``): every real client's
    token times its top-k experts held here, counted once per active epoch."""
    import glob
    import os

    from jax.profiler import ProfileData

    from repro.core.client import SimClient
    from repro.fl.fleet import ClientFleet
    from repro.fl.lm_task import LMTask

    cfg = _mla_moe_cfg()
    task = LMTask.build(cfg, 0, lora_rank=4, targets=("wq", "w_dkv"))
    data = make_lm_data(3, vocab_size=cfg.vocab_size, n_train=2, n_test=1, seq_len=8, seed=5)
    clients = [SimClient(client_id=i, data=data[i], num_classes=task.buckets, device_class="D1",
                         round_time_fn=lambda: 1.0, local_epochs=i + 1, lr=0.5, task=task) for i in range(3)]
    init = task.init_params(jax.random.PRNGKey(0))
    fleet = ClientFleet(clients, init)
    fleet.set_models([0, 1, 2], [init] * 3)
    fleet.train_rows([0, 1, 2])  # compile before the trace
    with jax.profiler.trace(str(tmp_path)):
        fleet.train_rows([0, 1, 2])
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("echopfl/train/launch", "echopfl/train/routed"):
                    stats[e.name] = dict(e.stats)
    tokens = 2 * 8 * (1 + 2 + 3)  # sequences x length x each client's epochs
    assert int(stats["echopfl/train/launch"]["tokens"]) == tokens
    routed = {k: int(v) for k, v in stats["echopfl/train/routed"].items()}
    pairs = routed["pairs"]
    held, k = cfg.moe.num_held, cfg.moe.top_k
    assert 0 < pairs <= tokens * cfg.num_periods * min(k, held)
    # the expert layers' calls in the 3 epochs (each has an active client),
    # those that ran the smallest pair buffer and the buffers' rows, each
    # at most the whole buffer of the launch's 4 padded clients' tokens
    assert routed["calls"] == 3 * sum(spec.ffn == "moe" for spec in cfg.all_layers)
    assert 0 <= routed["small"] <= routed["calls"]
    assert pairs <= routed["rows"] <= routed["calls"] * 4 * 2 * 8 * min(k, held)


def test_expert_layer_stays_a_conditional_in_a_training_step():
    """The pair buffer's rung is a real branch inside the clients' training
    step: the jaxpr of ``LMTask._scan_train`` holds the expert layer's
    conditional over its three rungs (forward, and the backward pass that
    recomputes its rung), not a select over every rung's result, which a
    ``vmap`` over the clients would make of it."""
    import dataclasses

    import jax.extend
    from repro.fl.lm_task import LMTask
    from repro.models import layers as L

    cfg = _mla_moe_cfg()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, num_experts=64, top_k=6, held=8))
    task = LMTask.build(cfg, 0, lora_rank=4, targets=("wq", "w_dkv"))
    C, n, S = 2, 2, 64
    assert len(L.pair_ladder(C * n * S, cfg.moe)) == 3
    delta = jax.vmap(task.init_params)(jax.random.split(jax.random.PRNGKey(0), C))
    tokens = jnp.zeros((C, n, S), jnp.int32)
    step = functools.partial(task._scan_train, max_epochs=2)
    jaxpr = jax.make_jaxpr(step)(task.operands, delta, tokens, tokens, jnp.ones((C, n)), jnp.ones((C,)),
                                 jnp.full((C,), 2, jnp.int32), jnp.zeros((C,)))

    def eqns(j):
        for e in j.eqns:
            yield e
            for v in e.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        yield from eqns(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        yield from eqns(sub)

    found = list(eqns(jaxpr.jaxpr))
    assert sum(e.primitive.name == "cond" and len(e.params["branches"]) == 3 for e in found) >= 2
    assert not [e for e in found if e.primitive.name == "select_n" and len(e.invars) > 3]
