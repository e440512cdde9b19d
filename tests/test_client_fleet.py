"""The vectorized client fleet (REPRO_CLIENT=fleet): loop-vs-fleet parity
of both simulator loops, masked-padding correctness for ragged client
datasets, head-only/heterogeneous-epoch masking equivalence, and the fleet
engine's plane-backed state handling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_tasks import MLPTaskConfig
from repro.core.client import SimClient
from repro.data.synthetic import ClientDataset
from repro.fl.experiment import build_clients, build_strategy, run_experiment
from repro.fl.fleet import ClientFleet
from repro.fl.simulator import Simulator
from repro.models import mlp

CFG = MLPTaskConfig("tiny", input_dim=12, hidden=(10,), num_classes=4)


def _ragged_clients(rng, sizes=(7, 12, 5, 12)):
    """SimClients with deliberately unequal train/test set sizes."""
    clients = []
    for i, n in enumerate(sizes):
        x = rng.normal(size=(n, CFG.input_dim)).astype(np.float32)
        y = rng.integers(0, CFG.num_classes, size=n).astype(np.int32)
        nt = max(2, n // 3)
        xt = rng.normal(size=(nt, CFG.input_dim)).astype(np.float32)
        yt = rng.integers(0, CFG.num_classes, size=nt).astype(np.int32)
        data = ClientDataset(x_train=x, y_train=y, x_test=xt, y_test=yt, latent_cluster=0)
        clients.append(
            SimClient(
                client_id=i, data=data, num_classes=CFG.num_classes,
                device_class="D1", round_time_fn=lambda: 1.0,
                local_epochs=3 + i % 3, lr=0.05 * (1 + i),
            )
        )
    return clients


@pytest.fixture
def params(rng):
    return mlp.init_mlp(CFG, jax.random.PRNGKey(11))


# -------------------------------------------------- masked batched variants
class TestMaskedBatchedVariants:
    def test_ragged_training_matches_per_client_path(self, rng, params):
        """fleet_local_train on zero-padded rows with validity masks must
        reproduce each client's unpadded local_train — including per-row
        lr and heterogeneous epoch budgets."""
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        trained, _ = fleet.train_cohort([c.client_id for c in clients], [params] * len(clients))
        for c, got in zip(clients, trained):
            want, _ = mlp.local_train(
                params, jnp.asarray(c.data.x_train), jnp.asarray(c.data.y_train),
                epochs=c.local_epochs, lr=c.lr,
            )
            for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_head_only_masking_equivalence(self, rng, params):
        """A head_only row in the batch must match _sgd_epoch(head_only=True):
        body layers frozen bit-exactly, head layer trained."""
        clients = _ragged_clients(rng)
        clients[1].partial_finetune = True
        fleet = ClientFleet(clients, params)
        trained, _ = fleet.train_cohort([c.client_id for c in clients], [params] * len(clients))
        c = clients[1]
        want, _ = mlp.local_train(
            params, jnp.asarray(c.data.x_train), jnp.asarray(c.data.y_train),
            epochs=c.local_epochs, lr=c.lr, head_only=True,
        )
        got = trained[1]
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
        # body layers untouched (exact zero gradient selection)
        for a, b in zip(jax.tree_util.tree_leaves(params[:-1]), jax.tree_util.tree_leaves(got[:-1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_head_only_body_stays_frozen_under_nonfinite_grads(self, rng, params):
        """Gradient masking is a select, not a multiply: even when training
        diverges (inf/nan gradients), frozen body params must stay bit-equal
        — g * 0.0 would leak NaN."""
        clients = _ragged_clients(rng)
        c = clients[1]
        c.partial_finetune = True
        c.lr = 1e30  # diverges within an epoch or two
        fleet = ClientFleet(clients, params)
        trained, _ = fleet.train_cohort([c.client_id], [params])
        for a, b in zip(jax.tree_util.tree_leaves(params[:-1]),
                        jax.tree_util.tree_leaves(trained[0][:-1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fleet_evaluate_masks_padding(self, rng, params):
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        accs = fleet.evaluate_fleet([params] * len(clients))
        for c, got in zip(clients, accs):
            want = float(mlp.evaluate(params, jnp.asarray(c.data.x_test), jnp.asarray(c.data.y_test)))
            np.testing.assert_allclose(got, want, atol=1e-6)

    def test_fleet_feedback_matches_per_client_probe(self, rng, params):
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        pairs = [(c.client_id, params) for c in clients] + [(clients[0].client_id, params)]
        f_pred, f_true, s_soft = fleet.feedback_many(pairs)
        assert f_pred.shape == (len(pairs), CFG.num_classes)
        for k, (cid, center) in enumerate(pairs):
            c = clients[cid]
            fp, ft, ss = c.feedback_inputs(center)
            np.testing.assert_array_equal(f_pred[k], fp)  # integer histograms: exact
            np.testing.assert_array_equal(f_true[k], ft)
            np.testing.assert_allclose(s_soft[k], ss, rtol=1e-6, atol=1e-7)

    def test_zero_epoch_rows_are_noops(self, rng, params):
        clients = _ragged_clients(rng)
        clients[2].local_epochs = 0
        fleet = ClientFleet(clients, params)
        trained, losses = fleet.train_cohort([c.client_id for c in clients], [params] * len(clients))
        for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(trained[2])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert losses[2] == 0.0  # matches local_train's epochs=0 loss


# --------------------------------------------------------- fleet engine state
class TestFleetEngine:
    def test_train_client_row_sliced_path_matches_cohort(self, rng, params):
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        for c in clients:
            fleet.set_model(c.client_id, params)
        tree, loss = fleet.train_client(clients[0].client_id)
        want, want_loss = mlp.local_train(
            params, jnp.asarray(clients[0].data.x_train), jnp.asarray(clients[0].data.y_train),
            epochs=clients[0].local_epochs, lr=clients[0].lr,
        )
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(tree)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
        # the model row advanced: training again continues from the new row
        np.testing.assert_allclose(
            np.asarray(fleet.model_vec(clients[0].client_id)),
            np.asarray(fleet.spec.flatten(tree)), rtol=1e-6,
        )

    def test_train_cohort_none_params_fall_back_to_model_row(self, rng, params):
        """model_for -> None means 'train from the client's own model', the
        same contract SimClient.local_train(None) honors."""
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        c = clients[0]
        start, _ = mlp.local_train(
            params, jnp.asarray(c.data.x_train), jnp.asarray(c.data.y_train),
            epochs=c.local_epochs, lr=c.lr,
        )
        fleet.set_model(c.client_id, start)
        trained, _ = fleet.train_cohort([c.client_id], [None])
        want, _ = mlp.local_train(
            start, jnp.asarray(c.data.x_train), jnp.asarray(c.data.y_train),
            epochs=c.local_epochs, lr=c.lr,
        )
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(trained[0])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_train_from_unset_model_raises(self, rng, params):
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        with pytest.raises(ValueError):
            fleet.train_client(clients[0].client_id)
        with pytest.raises(ValueError):
            fleet.train_cohort([clients[0].client_id], [None])
        with pytest.raises(ValueError):
            fleet.train_rows([clients[0].client_id])

    def test_train_rows_matches_sequential_train_client(self, rng, params):
        """The coalesced async path's batched row-sliced launch: N clients
        training from their own model rows in one launch must equal N
        train_client calls — results, row write-back, version bumps."""
        clients = _ragged_clients(rng)
        ids = [c.client_id for c in clients]
        batched = ClientFleet(clients, params)
        seq = ClientFleet(clients, params)
        for f in (batched, seq):
            for c in clients:
                f.set_model(c.client_id, params)
        trees_b, losses_b = batched.train_rows(ids)
        for cid, tree_b, loss_b in zip(ids, trees_b, losses_b):
            tree_s, loss_s = seq.train_client(cid)
            for a, b in zip(jax.tree_util.tree_leaves(tree_s), jax.tree_util.tree_leaves(tree_b)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(loss_b), float(loss_s), rtol=1e-5)
            np.testing.assert_allclose(
                np.asarray(batched.model_vec(cid)), np.asarray(seq.model_vec(cid)),
                rtol=1e-6, atol=1e-7,
            )
        # rows advanced: a second batch continues from the trained rows
        trees_b2, _ = batched.train_rows(ids[:2])
        tree_s2, _ = seq.train_client(ids[0])
        for a, b in zip(jax.tree_util.tree_leaves(tree_s2), jax.tree_util.tree_leaves(trees_b2[0])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_set_models_batches_with_last_write_wins(self, rng, params):
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        other = jax.tree_util.tree_map(lambda x: x + 1.0, params)
        third = jax.tree_util.tree_map(lambda x: x * 0.5, params)
        # duplicate client 0: the LAST write must win, like sequential sets
        fleet.set_models(
            [clients[0].client_id, clients[1].client_id, clients[0].client_id],
            [params, other, third],
        )
        np.testing.assert_allclose(
            np.asarray(fleet.model_vec(clients[0].client_id)),
            np.asarray(fleet.spec.flatten(third)), rtol=1e-7,
        )
        np.testing.assert_allclose(
            np.asarray(fleet.model_vec(clients[1].client_id)),
            np.asarray(fleet.spec.flatten(other)), rtol=1e-7,
        )

    @staticmethod
    def _payloads(rng, params, spec):
        """Fresh payload objects of every kind a downlink carries: device
        pytrees (broadcast centers), 1-D device vectors, host pytrees of
        NumPy views (unicasts) and a host pytree of float64 leaves."""
        def vec():
            return rng.normal(size=spec.dim).astype(np.float32)

        return {
            "device": jax.tree_util.tree_map(lambda x: x + jnp.float32(rng.normal()), params),
            "vector": jnp.asarray(vec()),
            "host": spec.unflatten_np(vec()),
            "host64": jax.tree_util.tree_map(lambda x: x.astype(np.float64), spec.unflatten_np(vec())),
        }

    @pytest.mark.parametrize("case", ["mixed", "one_device", "one_host", "all_distinct"])
    def test_set_models_stages_rows_bitwise_like_sequential_installs(self, rng, params, case):
        """Staged and flushed rows of one batched install are bitwise what
        sequential ``set_model`` calls stage, whatever the batch's mix of
        host and device payloads, duplicates and fan-out."""
        clients = _ragged_clients(rng, sizes=(6,) * 72)
        ids = [c.client_id for c in clients]
        batched = ClientFleet(clients, params)
        seq = ClientFleet(clients, params)
        p = self._payloads(rng, params, batched.spec)
        if case == "mixed":
            # the center fanned to most rows, unicasts, a vector, and
            # client 0 twice: the last write wins
            order = [p["device"]] * 50 + [p["host"]] * 6 + [p["vector"]] * 4 + [p["host64"]] * 4
            pairs = list(zip(ids[:64], order)) + [(ids[0], p["host"]), (ids[70], p["vector"])]
        elif case in ("one_device", "one_host"):
            pairs = [(cid, p[case[4:]]) for cid in ids]
        else:
            pairs = [(cid, self._payloads(rng, params, batched.spec)[k])
                     for cid, k in zip(ids, ["device", "vector", "host", "host64"] * 18)]
        batched.set_models([c for c, _ in pairs], [q for _, q in pairs])
        for c, q in pairs:
            seq.set_model(c, q)
        installed = sorted({c for c, _ in pairs})

        def rows(fleet):
            return [np.asarray(fleet.model_vec(cid)) for cid in installed]

        staged = rows(batched), rows(seq)
        batched.plane.flush()
        seq.plane.flush()
        for got, want in (staged, (rows(batched), rows(seq))):
            for cid, a, b in zip(installed, got, want):
                assert np.array_equal(a, b), cid

    def test_set_models_device_work_does_not_grow_with_rows(self, rng, params, monkeypatch):
        """An install of 200 rows over three payloads costs the device what
        one of 8 rows does: one flatten per distinct device payload and one
        launch, never a dispatch per row."""
        import repro.fl.fleet as fleet_mod

        clients = _ragged_clients(rng, sizes=(6,) * 200)
        ids = [c.client_id for c in clients]
        fleet = ClientFleet(clients, params)
        counts: dict = {}

        def counting(name, fn):
            def call(*a, **k):
                counts[name] = counts.get(name, 0) + 1
                return fn(*a, **k)
            return call

        monkeypatch.setattr(fleet, "_vec_of", counting("vec_of", fleet._vec_of))
        monkeypatch.setattr(fleet.spec, "flatten", counting("flatten", fleet.spec.flatten))
        monkeypatch.setattr(fleet_mod, "_install_rows",
                            counting("launch", getattr(fleet_mod, "_install_rows", None)), raising=False)

        def install(n):
            counts.clear()
            p = self._payloads(rng, params, fleet.spec)
            three = [p["device"], p["vector"], p["host"]]
            fleet.set_models(ids[:n], [three[i % 3] for i in range(n)])
            return dict(counts)

        small, large = install(8), install(200)
        assert small == large == {"vec_of": 2, "flatten": 1, "launch": 1}

    def test_set_models_programs_key_on_padded_distinct_counts(self, rng, params):
        """One, three or eight distinct host payloads over the same rows run
        one program: the install is keyed by the row count and the distinct
        counts padded, not by the exact distinct counts."""
        import repro.fl.fleet as fleet_mod

        clients = _ragged_clients(rng, sizes=(6,) * 16)
        ids = [c.client_id for c in clients]
        fleet = ClientFleet(clients, params)
        sizes = []
        for distinct in (1, 3, 8):
            hosts = [self._payloads(rng, params, fleet.spec)["host"] for _ in range(distinct)]
            fleet.set_models(ids, [hosts[i % distinct] for i in range(len(ids))])
            sizes.append(fleet_mod._install_rows._cache_size())
        assert sizes[0] == sizes[1] == sizes[2]

    def test_dataset_replacement_is_picked_up(self, rng, params):
        """Distribution drift (Fig. 18): replacing a SimClient's dataset
        mid-run must be reflected by the next fleet launch, like the loop
        backend's live reads."""
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        fleet.evaluate_fleet([params] * len(clients))
        c = clients[0]
        n = len(c.data.y_test) + 3  # also changes the padded width
        rng2 = np.random.default_rng(99)
        c.data = ClientDataset(
            x_train=c.data.x_train, y_train=c.data.y_train,
            x_test=rng2.normal(size=(n, CFG.input_dim)).astype(np.float32),
            y_test=rng2.integers(0, CFG.num_classes, size=n).astype(np.int32),
            latent_cluster=0,
        )
        accs = fleet.evaluate_fleet([params] * len(clients))
        want = float(mlp.evaluate(params, jnp.asarray(c.data.x_test), jnp.asarray(c.data.y_test)))
        np.testing.assert_allclose(accs[0], want, atol=1e-6)

    def test_eval_rows_identity_cached(self, rng, params):
        """Re-evaluating with the same center object must not rewrite eval
        rows (the per-tick gather is the plane's patched cached view)."""
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        fleet.evaluate_fleet([params] * len(clients))
        staged_before = len(fleet.plane._dirty) + len(fleet.plane._bulk)
        fleet.evaluate_fleet([params] * len(clients))
        assert len(fleet.plane._dirty) + len(fleet.plane._bulk) == staged_before == 0

    def test_unset_model_and_none_params_evaluates_to_zero(self, rng, params):
        clients = _ragged_clients(rng)
        fleet = ClientFleet(clients, params)
        fleet.set_model(clients[0].client_id, params)
        accs = fleet.evaluate_fleet([None] * len(clients))
        assert accs[1] == 0.0 and accs[2] == 0.0  # no model ever set
        want = float(mlp.evaluate(params, jnp.asarray(clients[0].data.x_test),
                                  jnp.asarray(clients[0].data.y_test)))
        np.testing.assert_allclose(accs[0], want, atol=1e-6)
        # a second tick with an unchanged model row stages no copies (the
        # model-row mirror is version-tagged), and a model write re-stages
        fleet.evaluate_fleet([None] * len(clients))
        assert not fleet.plane._dirty and not fleet.plane._bulk
        fleet.set_model(clients[0].client_id, params)
        fleet.evaluate_fleet([None] * len(clients))
        accs2 = fleet.evaluate_fleet([None] * len(clients))
        np.testing.assert_allclose(accs2[0], want, atol=1e-6)


# -------------------------------------------------------------- fleet mesh
class TestFleetMesh:
    def test_env_knob_parsing(self, monkeypatch):
        from repro.launch.mesh import fleet_mesh_from_env

        monkeypatch.setenv("REPRO_FLEET_MESH", "off")
        assert fleet_mesh_from_env() is None
        monkeypatch.delenv("REPRO_FLEET_MESH")
        assert fleet_mesh_from_env() is None
        monkeypatch.setenv("REPRO_FLEET_MESH", "1")
        m = fleet_mesh_from_env()
        assert m is not None and m.shape["plane"] == 1

    def test_meshed_fleet_matches_single_device(self, rng, params):
        """With a fleet mesh, the client-model plane and the (clients, n,
        dim) data tensors shard over the 'plane' axis; every launch's
        per-client arithmetic is unchanged, so training, eval, and feedback
        match the single-device fleet."""
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices (ci.sh multi-device leg)")
        from repro.launch.mesh import make_plane_mesh

        clients = _ragged_clients(rng)  # 4 clients: 2 row shards divide them
        ids = [c.client_id for c in clients]
        single = ClientFleet(clients, params, mesh=False)
        meshed = ClientFleet(clients, params, mesh=make_plane_mesh(2))
        assert meshed.x_train.sharding.spec[0] == "plane"
        # a fleet that does not divide the row shards falls back unsharded
        if len(jax.devices()) >= 8:
            assert ClientFleet(clients, params, mesh=make_plane_mesh(8)).mesh is None
        ta, la = single.train_cohort(ids, [params] * len(ids))
        tb, lb = meshed.train_cohort(ids, [params] * len(ids))
        for a, b in zip(ta, tb):
            for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
        for f in (single, meshed):
            for c in clients:
                f.set_model(c.client_id, params)
        np.testing.assert_allclose(
            single.evaluate_fleet([None] * len(ids)), meshed.evaluate_fleet([None] * len(ids)),
            atol=1e-6,
        )
        pairs = [(cid, params) for cid in ids]
        fa = single.feedback_many(pairs)
        fb = meshed.feedback_many(pairs)
        for x, y in zip(fa, fb):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
        ra, _ = single.train_rows(ids[:3])
        rb, _ = meshed.train_rows(ids[:3])
        for a, b in zip(ra, rb):
            for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------ simulator-level parity
def _match_reports(r1, r2, atol=5e-6):
    # virtual-time trajectory and byte accounting must be *exact*
    assert (r1.up_bytes, r1.down_bytes, r1.up_events, r1.down_events) == (
        r2.up_bytes, r2.down_bytes, r2.up_events, r2.down_events
    )
    assert [t for t, _ in r1.curve] == [t for t, _ in r2.curve]
    np.testing.assert_allclose(
        [a for _, a in r1.curve], [a for _, a in r2.curve], atol=atol
    )
    assert set(r1.per_client_acc) == set(r2.per_client_acc)
    for cid in r1.per_client_acc:
        np.testing.assert_allclose(r1.per_client_acc[cid], r2.per_client_acc[cid], atol=atol)
    assert r1.duration == r2.duration


class TestLoopFleetParity:
    def test_run_sync_parity(self):
        reports = {
            backend: run_experiment(
                "har", "fedavg", num_clients=6, seed=3, rounds=3,
                client_backend=backend, samples_per_client=48,
            )[3]
            for backend in ("loop", "fleet")
        }
        _match_reports(reports["loop"], reports["fleet"])
        assert reports["loop"].extra["rounds"] == reports["fleet"].extra["rounds"] == 3

    def test_run_async_parity_echopfl(self):
        """The event-driven trajectory — upload ordering, cluster decisions,
        broadcasts, refinement — must be unchanged when single-client
        training routes through the fleet's row-sliced path and eval ticks
        and feedback probes batch."""
        reports = {}
        extras = {}
        for backend in ("loop", "fleet"):
            r = run_experiment(
                "har", "echopfl", num_clients=6, seed=3, max_time=420,
                client_backend=backend, samples_per_client=48,
            )[3]
            reports[backend] = r
            extras[backend] = r.extra
        _match_reports(reports["loop"], reports["fleet"])
        for key in ("uploads", "clusters", "merges", "expansions", "broadcasts"):
            assert extras["loop"][key] == extras["fleet"][key], key

    def test_stale_fleet_hook_replaced_or_cleared_on_strategy_reuse(self):
        """A strategy reused across simulators must never keep probing a
        previous simulator's dead fleet: a new fleet rebinds the hook, a
        loop-backend run clears it (falling back to feedback_fn)."""
        task, clients, init = build_clients("har", 4, seed=0, samples_per_client=16)
        strat = build_strategy("echopfl", init, clients, seed=0)
        sim_a = Simulator(clients, strat, client_backend="fleet", seed=0)
        sim_a._ensure_fleet(init)
        hook_a = strat.feedback_batch_fn
        assert getattr(hook_a, "_fleet_hook", False)
        sim_b = Simulator(clients, strat, client_backend="fleet", seed=0)
        sim_b._ensure_fleet(init)
        assert strat.feedback_batch_fn is not hook_a  # rebound to B's fleet
        sim_c = Simulator(clients, strat, client_backend="loop", seed=0)
        sim_c._ensure_fleet(init)
        assert strat.feedback_batch_fn is None
        # re-running an existing fleet simulator reclaims the hook for its
        # OWN fleet (after another simulator cleared or rebound it)
        sim_a._ensure_fleet(init)
        assert strat.feedback_batch_fn._fleet is sim_a._fleet
        sim_b._ensure_fleet(init)
        assert strat.feedback_batch_fn._fleet is sim_b._fleet

    def test_invalid_backend_rejected(self):
        task, clients, init = build_clients("har", 2, seed=0, samples_per_client=16)
        strat = build_strategy("fedavg", init, clients, seed=0)
        with pytest.raises(ValueError):
            Simulator(clients, strat, client_backend="warp")
