"""The row-sharded parameter plane: sharded kernel parity, sharding
preservation through flush/grow/recycling, and trajectory identity of the
sharded server against the single-device plane.

The in-process tests need >= 2 local devices and run under the ci.sh
multi-device leg (XLA_FLAGS=--xla_force_host_platform_device_count=8,
REPRO_PLANE_MESH=auto); on the default 1-device tier-1 run they skip. The
subprocess parity test always runs: it forces an 8-device host platform in
a child interpreter and asserts the full EchoPFL server trajectory
(assignments, merges, expansions, broadcast decisions) is identical
sharded vs. single-device, with centers matching to fp tolerance.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plane import ParameterPlane
from repro.kernels import ops, ref

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices (ci.sh multi-device leg)"
)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from repro.launch.mesh import make_plane_mesh

    return make_plane_mesh()


def test_plane_mesh_env_parsing(monkeypatch):
    from repro.launch.mesh import plane_mesh_from_env

    monkeypatch.setenv("REPRO_PLANE_MESH", "off")
    assert plane_mesh_from_env() is None
    monkeypatch.setenv("REPRO_PLANE_MESH", "1")
    m = plane_mesh_from_env()
    assert m is not None and m.shape["plane"] == 1  # "1" = one shard, not auto
    monkeypatch.setenv("REPRO_PLANE_MESH", "auto")
    m = plane_mesh_from_env()
    assert (m is None) == (len(jax.devices()) == 1)


def test_explicit_unsharded_overrides_env(monkeypatch):
    from repro.core.clustering import DynamicClustering

    monkeypatch.setenv("REPRO_PLANE_MESH", "1")
    cl = DynamicClustering(2, backend="plane", mesh=False)
    assert cl.mesh is None


# -------------------------------------------------------------- sharded ops
@multi_device
class TestShardedOps:
    def test_l1_pairwise_bitwise_vs_single_device(self, mesh):
        xs = jax.random.normal(jax.random.PRNGKey(0), (11, 300))
        cs = jax.random.normal(jax.random.PRNGKey(1), (5, 300))
        got = np.asarray(ops.l1_distance_pairwise(xs, cs, mesh=mesh))
        want = np.asarray(ops.l1_distance_pairwise(xs, cs))
        np.testing.assert_array_equal(got, want)  # per-row sums: bitwise
        np.testing.assert_allclose(got, np.asarray(ref.l1_distance_pairwise_ref(xs, cs)), rtol=1e-5)

    def test_l1_pairwise_fewer_rows_than_shards(self, mesh):
        xs = jax.random.normal(jax.random.PRNGKey(2), (1, 200))
        cs = jax.random.normal(jax.random.PRNGKey(3), (3, 200))
        got = np.asarray(ops.l1_distance_pairwise(xs, cs, mesh=mesh))
        np.testing.assert_array_equal(got, np.asarray(ops.l1_distance_pairwise(xs, cs)))

    @pytest.mark.parametrize("c", [1, 3, 8, 11])
    def test_assign_and_lerp_bitwise_vs_single_device(self, mesh, c):
        u = jax.random.normal(jax.random.PRNGKey(c), (300,))
        cs = jax.random.normal(jax.random.PRNGKey(c + 100), (c, 300))
        d, i, b = ops.assign_and_lerp(u, cs, 0.25, mesh=mesh)
        ds, is_, bs = ops.assign_and_lerp(u, cs, 0.25)
        np.testing.assert_array_equal(np.asarray(d), np.asarray(ds))
        assert int(i) == int(is_)
        np.testing.assert_array_equal(np.asarray(b), np.asarray(bs))

    def test_assign_and_lerp_padded_rows_never_win(self, mesh):
        # a zero padding row would be L1-closest to a near-zero upload if the
        # mask were missing; the argmin must stay inside the real C rows
        u = jnp.full((256,), 1e-3)
        cs = jnp.stack([jnp.full((256,), 50.0), jnp.full((256,), -40.0), jnp.full((256,), 30.0)])
        d, i, b = ops.assign_and_lerp(u, cs, 0.5, mesh=mesh)
        assert 0 <= int(i) < 3
        assert int(i) == 2  # 30.0 is nearest
        assert np.all(np.isfinite(np.asarray(d)))

    def test_chi2_feedback_all_bitwise_g_vs_single_device(self, mesh):
        sizes = [2, 1, 9, 4]
        m, s = sum(sizes), len(sizes)
        k = jax.random.PRNGKey(7)
        f_pred = jax.random.uniform(k, (m, 6)) * 100
        f_true = jax.random.uniform(jax.random.PRNGKey(8), (m, 6)) * 100 + 1.0
        s_soft = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(9), (m, 6)), axis=-1)
        seg_ids = jnp.asarray(np.repeat(np.arange(s), sizes), np.int32)
        g, seg = ops.chi2_feedback_all(f_pred, f_true, s_soft, seg_ids, num_segments=s, mesh=mesh)
        g1, seg1 = ops.chi2_feedback_all(f_pred, f_true, s_soft, seg_ids, num_segments=s)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g1))  # per-member: bitwise
        # segment sums psum across shards: fp tolerance, not bitwise
        np.testing.assert_allclose(np.asarray(seg), np.asarray(seg1), rtol=1e-5, atol=1e-6)

    def test_chi2_feedback_rows_bitwise_vs_single_device(self, mesh):
        """The dissolve/expand probe path: per-row scores under the sharded
        launch are bitwise-identical to the single-device launch, including
        row counts that do not divide the shard count."""
        for m in (3, 11, 16):
            f_pred = jax.random.uniform(jax.random.PRNGKey(m), (m, 6)) * 100
            f_true = jax.random.uniform(jax.random.PRNGKey(m + 1), (m, 6)) * 100 + 1.0
            s_soft = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(m + 2), (m, 6)), axis=-1)
            got = np.asarray(ops.chi2_feedback(f_pred, f_true, s_soft, mesh=mesh))
            want = np.asarray(ops.chi2_feedback(f_pred, f_true, s_soft))
            assert got.shape == (m,)
            np.testing.assert_array_equal(got, want)
            np.testing.assert_allclose(
                got, np.asarray(ref.chi2_feedback_ref(f_pred, f_true, s_soft)), rtol=1e-5
            )


# ---------------------------------------------------------- sharded storage
@multi_device
class TestShardedPlane:
    def _assert_row_sharded(self, plane, arr):
        assert arr.sharding.is_equivalent_to(plane._sharding, arr.ndim)

    def test_capacity_rounds_to_shard_multiple(self, mesh, tiny_params):
        shards = mesh.shape["plane"]
        plane = ParameterPlane(tiny_params, capacity=shards + 1, mesh=mesh)
        assert plane.capacity % shards == 0
        self._assert_row_sharded(plane, plane._buf)

    def test_flush_preserves_sharding(self, mesh, tiny_params):
        plane = ParameterPlane(tiny_params, capacity=16, mesh=mesh)
        r0, r1 = plane.alloc(), plane.alloc()
        plane.write(r0, jnp.full((plane.dim,), 2.0))
        plane.write(r1, jnp.full((plane.dim,), 3.0))
        plane.flush()  # multi-row donated scatter
        self._assert_row_sharded(plane, plane._buf)
        plane.write(r0, jnp.full((plane.dim,), 4.0))
        plane.flush()  # single-row dynamic_update_slice
        self._assert_row_sharded(plane, plane._buf)
        np.testing.assert_array_equal(np.asarray(plane.row(r0)), 4.0)

    def test_grow_preserves_sharding_rows_and_staged_writes(self, mesh, tiny_params):
        shards = mesh.shape["plane"]
        plane = ParameterPlane(tiny_params, capacity=shards, mesh=mesh)
        kept = plane.alloc(jnp.full((plane.dim,), 5.0))
        plane.flush()
        staged = plane.alloc()
        plane.write(staged, jnp.full((plane.dim,), 6.0))  # dirty across _grow
        extra = [plane.alloc() for _ in range(shards)]  # forces _grow
        assert plane.capacity == 2 * shards
        assert plane.capacity % shards == 0
        self._assert_row_sharded(plane, plane._buf)
        got = plane.rows((kept, staged, extra[0]))
        np.testing.assert_array_equal(np.asarray(got[0]), 5.0)
        np.testing.assert_array_equal(np.asarray(got[1]), 6.0)
        np.testing.assert_array_equal(np.asarray(got[2]), 0.0)

    def test_recycled_row_zeroed_under_sharding(self, mesh, tiny_params):
        plane = ParameterPlane(tiny_params, capacity=8, mesh=mesh)
        row = plane.alloc(jnp.full((plane.dim,), 9.0))
        plane.flush()
        plane.free(row)
        again = plane.alloc()
        assert again == row
        np.testing.assert_array_equal(np.asarray(plane.row(again)), 0.0)
        np.testing.assert_array_equal(np.asarray(plane.rows((again,))[0]), 0.0)
        self._assert_row_sharded(plane, plane.matrix())

    def test_rows_on_mesh_view_is_cached_replicated_and_patched(self, mesh, tiny_params):
        """The mesh-replicated view (sharded-launch operand form) must be
        cached and incrementally patched like the local view — a sharded
        launch must not re-broadcast the whole matrix every call — and the
        two domains must coexist under distinct cache keys."""
        plane = ParameterPlane(tiny_params, capacity=16, mesh=mesh)
        r = [plane.alloc(jnp.full((plane.dim,), float(i))) for i in range(4)]
        v1 = plane.rows(tuple(r), on_mesh=True)
        assert v1.sharding.is_equivalent_to(plane._replicated, v1.ndim)
        assert (tuple(r), "mesh") in plane._views
        plane.write(r[1], jnp.full((plane.dim,), 42.0))
        v2 = plane.rows(tuple(r), on_mesh=True)  # patched, still replicated
        np.testing.assert_array_equal(np.asarray(v2[1]), 42.0)
        np.testing.assert_array_equal(np.asarray(v2[0]), 0.0)
        assert v2.sharding.is_equivalent_to(plane._replicated, v2.ndim)
        vl = plane.rows(tuple(r))  # local-domain view: same values
        np.testing.assert_array_equal(np.asarray(vl), np.asarray(v2))
        assert (tuple(r), "local") in plane._views

    def test_rows_shard_domain_is_row_sharded_and_patched(self, mesh, tiny_params):
        """The shard-local gather (on_mesh="shard"): a fleet-scale row set
        read off a mesh-committed plane must land SHARDED over the row axis
        — never funneled through one local device — and patch incrementally
        under its own cache key like the other domains."""
        from jax.sharding import NamedSharding, PartitionSpec

        plane = ParameterPlane(tiny_params, capacity=16, mesh=mesh)
        r = [plane.alloc(jnp.full((plane.dim,), float(i))) for i in range(8)]
        want = NamedSharding(mesh, PartitionSpec("plane", None))
        v1 = plane.rows(tuple(r), on_mesh="shard")
        assert v1.sharding.is_equivalent_to(want, v1.ndim)
        assert (tuple(r), "shard") in plane._views
        plane.write(r[2], jnp.full((plane.dim,), 7.0))
        v2 = plane.rows(tuple(r), on_mesh="shard")  # patched, still sharded
        np.testing.assert_array_equal(np.asarray(v2[2]), 7.0)
        np.testing.assert_array_equal(np.asarray(v2[0]), 0.0)
        assert v2.sharding.is_equivalent_to(want, v2.ndim)
        # values equal the local-domain view; uncached take() agrees too
        np.testing.assert_array_equal(np.asarray(plane.rows(tuple(r))), np.asarray(v2))
        t = plane.take(tuple(r), on_mesh="shard")
        assert t.sharding.is_equivalent_to(want, t.ndim)
        np.testing.assert_array_equal(np.asarray(t), np.asarray(v2))
        assert (tuple(r), "shard") in plane._views  # take never touches the cache

    def test_sharded_rows_feed_pairwise_kernel_without_localizing(self, mesh, tiny_params):
        """End to end: a shard-gathered row batch passes straight into the
        sharded pairwise kernel (ops._to_mesh_rows passes it through) and
        scores bitwise-identically to the single-device launch."""
        plane = ParameterPlane(tiny_params, capacity=16, mesh=mesh)
        rows = [plane.alloc(jnp.asarray(np.random.default_rng(i).standard_normal(plane.dim),
                                        jnp.float32)) for i in range(8)]
        centers = jnp.asarray(np.random.default_rng(99).standard_normal((3, plane.dim)), jnp.float32)
        U_shard = plane.rows(tuple(rows), on_mesh="shard")
        got = np.asarray(ops.l1_distance_pairwise(U_shard, centers, mesh=mesh, axis="plane"))
        want = np.asarray(ops.l1_distance_pairwise(plane.rows(tuple(rows)), centers))
        np.testing.assert_array_equal(got, want)

    def test_dim_axis_falls_back_when_not_divisible(self, tiny_params):
        # tiny_params has 187 params: prime-ish, never divisible by a model
        # axis of 2+ — the plane must fall back to row-only sharding
        if len(jax.devices()) < 4 or len(jax.devices()) % 2:
            pytest.skip("needs an even device count >= 4")
        from repro.launch.mesh import make_plane_mesh

        m2 = make_plane_mesh(len(jax.devices()) // 2, dim_shards=2)
        plane = ParameterPlane(tiny_params, capacity=8, mesh=m2)
        from jax.sharding import PartitionSpec

        assert plane._sharding.spec == PartitionSpec("plane", None)
        row = plane.alloc(jnp.full((plane.dim,), 1.5))
        np.testing.assert_array_equal(np.asarray(plane.rows((row,))[0]), 1.5)


# ----------------------------------------------------- in-process trajectory
@multi_device
class TestShardedClusteringParity:
    def _scenario(self, mesh, monkeypatch):
        from repro.core.clustering import DynamicClustering

        monkeypatch.delenv("REPRO_PLANE_MESH", raising=False)
        monkeypatch.setenv("REPRO_PLANE_MESH_MIN_ROWS", "0")  # force sharded compute
        cl = DynamicClustering(3, mix_rate=0.25, backend="plane", mesh=mesh)
        rng = np.random.default_rng(11)
        anchors = {0: 0.0, 1: 30.0, 2: 90.0}
        events = []
        for _ in range(40):
            client = int(rng.integers(0, 9))
            anchor = anchors[client % 3] + float(rng.normal() * 2.0)
            update = {"w": jnp.full((31,), anchor)}
            cid, created = cl.assign(f"c{client}", update)
            cl.aggregate(cid, update)
            events.append((f"c{client}", cid, created))
        return cl, events

    def test_sharded_matches_single_device_plane(self, mesh, monkeypatch):
        sharded, ev_sharded = self._scenario(mesh, monkeypatch)
        single, ev_single = self._scenario(False, monkeypatch)  # explicit unsharded
        assert sharded.plane.mesh is mesh and single.plane.mesh is None
        assert ev_sharded == ev_single
        assert sharded.assignment == single.assignment
        assert sharded.nearest_pair() == single.nearest_pair()
        for cid in single.clusters:
            np.testing.assert_allclose(
                np.asarray(sharded.plane.row(sharded.clusters[cid]._row)),
                np.asarray(single.plane.row(single.clusters[cid]._row)),
                rtol=1e-6, atol=1e-6,
            )


# ------------------------------------------------- forced-8-device parity
_PARITY_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.pop("REPRO_PLANE_MESH", None)
    os.environ["REPRO_PLANE_MESH_MIN_ROWS"] = "0"  # force sharded compute
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.server import EchoPFLServer
    from repro.launch.mesh import make_plane_mesh

    assert len(jax.devices()) == 8

    def vec(x):
        return {"w": jnp.full((24,), float(x))}

    def feedback_fn(client_id, center):
        err = 80.0 if client_id in ("c4", "c5") else 1.0
        f_pred = np.asarray([50.0 + err, 50.0 - err, 1.0])
        f_true = np.asarray([50.0, 50.0, 1.0])
        s_soft = np.asarray([0.9, 0.08, 0.02])
        return f_pred, f_true, s_soft

    def run(mesh):
        srv = EchoPFLServer(vec(0.0), num_initial_clusters=1, refine_every=8,
                            feedback_fn=feedback_fn, local_train_fn=lambda p: p,
                            plane_backend="plane", plane_mesh=mesh, seed=0)
        for i in range(40):
            srv.handle_upload(f"c{i % 6}", vec(40.0 * (i % 2) + 0.01 * i), 0, 8,
                              t=float(i))
        return srv

    single = run(False)  # explicit unsharded, immune to inherited env knobs
    sharded = run(make_plane_mesh(8))
    assert single.clustering.plane.mesh is None
    assert sharded.clustering.plane.mesh is not None
    assert sharded.clustering.plane._buf.sharding.spec[0] == "plane"

    # trajectory identity: every protocol decision matches
    assert sharded.clustering.assignment == single.clustering.assignment
    assert sharded.events == single.events
    ss, sg = sharded.stats(), single.stats()
    for key in ("clusters", "merges", "expansions", "staleness", "broadcasts",
                "rnn_broadcasts", "decisions", "plane_rows"):
        assert ss[key] == sg[key], (key, ss[key], sg[key])
    assert ss["expansions"] > 0  # scenario must exercise refinement
    for cid, c in single.clustering.clusters.items():
        a = sharded.clustering.clusters[cid]
        for x, y in zip(jax.tree_util.tree_leaves(a.center),
                        jax.tree_util.tree_leaves(c.center)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)
    print("PARITY-OK")
    """
)


def test_sharded_server_trajectory_parity_on_forced_8_device_host():
    """Acceptance: the sharded plane (forced 8-device host mesh) reproduces
    the single-device server trajectory on the same seed — assignments,
    merges, expansions, and broadcast decisions identical; centers within
    fp tolerance. Runs in a subprocess because the device count is fixed
    at jax init."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "PARITY-OK" in proc.stdout


_SCATTER_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.plane import ParameterPlane
    from repro.launch.mesh import make_plane_mesh

    assert len(jax.devices()) == 4
    mesh = make_plane_mesh()
    plane = ParameterPlane({"w": jnp.zeros((24,))}, capacity=8, mesh=mesh)
    rows = [plane.alloc(jnp.full((24,), float(i + 1))) for i in range(3)]
    plane.flush()  # multi-row scatter into the row-sharded buffer
    bulk = plane.alloc_many(2)
    plane.write_rows(bulk, jnp.full((2, 24), 7.0))
    buf = plane.matrix()  # bulk scatter
    assert buf.sharding.spec[0] == "plane", buf.sharding
    for i, r in enumerate(rows):
        np.testing.assert_array_equal(np.asarray(buf[r]), i + 1.0)
    np.testing.assert_array_equal(np.asarray(buf[bulk[1]]), 7.0)
    print("SCATTER-OK")
    """
)


def test_plane_mesh_scatters_rows_on_4_device_host():
    """``make_plane_mesh`` meshes take the plane's row scatters: under
    Explicit mesh axes (``jax.make_mesh``'s default) the scatter into a
    row-sharded buffer is refused with a ShardingTypeError. Runs in a
    subprocess because the device count is fixed at jax init."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SCATTER_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SCATTER-OK" in proc.stdout
