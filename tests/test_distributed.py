"""Distributed core tests on the 8-virtual-device CPU mesh.

Mirrors the reference test strategy (SURVEY.md §4): mesh/SPMD tests run
single-process multi-device; numeric parity against local math like
test_collective_api_base.py does.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as pt
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import comm_ops
from paddle_tpu.distributed.process_mesh import placements_to_spec


def make_mesh(*shape, names=None):
    return dist.ProcessMesh(
        np.arange(int(np.prod(shape))).reshape(shape), names)


class TestProcessMesh:
    def test_basic(self):
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        assert mesh.shape == [2, 4]
        assert mesh.dim_names == ["dp", "mp"]
        assert mesh.process_ids == list(range(8))
        assert mesh.get_dim_size("mp") == 4
        assert mesh.size == 8

    def test_jax_mesh(self):
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        jm = mesh.jax_mesh()
        assert jm.axis_names == ("dp", "mp")
        assert jm.devices.shape == (2, 4)

    def test_get_mesh_with_dim(self):
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        sub = mesh.get_mesh_with_dim("mp")
        assert sub.dim_names == ["mp", "dp"]
        assert sub.shape == [4, 2]
        sliced = mesh.get_mesh_with_dim("mp", 0)
        assert sliced.shape == [2]

    def test_placements_to_spec(self):
        from jax.sharding import PartitionSpec as P
        assert placements_to_spec(
            [dist.Shard(0), dist.Replicate()], ["a", "b"]) == P("a")
        assert placements_to_spec(
            [dist.Replicate(), dist.Shard(1)], ["a", "b"]) == P(None, "b")
        assert placements_to_spec(
            [dist.Shard(1), dist.Shard(1)], ["a", "b"]) == P(None, ("a", "b"))
        assert placements_to_spec(
            [dist.Replicate(), dist.Replicate()], ["a", "b"]) == P()


class TestShardTensor:
    def test_shard_and_value(self):
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        x = pt.arange(32, dtype="float32").reshape([8, 4])
        dx = dist.shard_tensor(x, mesh, [dist.Shard(0), dist.Replicate()])
        assert dx.placements[0] == dist.Shard(0)
        assert dx.process_mesh is mesh
        np.testing.assert_allclose(dx.numpy(), x.numpy())
        # Physically sharded: each dp shard holds 4 rows.
        shard_shapes = {s.data.shape for s in dx._data.addressable_shards}
        assert shard_shapes == {(4, 4)}

    def test_reshard(self):
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        x = pt.ones([8, 8])
        dx = dist.shard_tensor(x, mesh, [dist.Shard(0), dist.Replicate()])
        dy = dist.reshard(dx, mesh, [dist.Replicate(), dist.Shard(1)])
        assert dy.placements == [dist.Replicate(), dist.Shard(1)]
        np.testing.assert_allclose(dy.numpy(), np.ones((8, 8)))

    def test_partial_stores_replicated(self):
        mesh = make_mesh(8, names=["dp"])
        x = pt.ones([4, 4])
        dx = dist.shard_tensor(x, mesh, [dist.Partial()])
        assert dx.placements[0].is_partial()
        np.testing.assert_allclose(dx.numpy(), np.ones((4, 4)))

    def test_unshard(self):
        mesh = make_mesh(8, names=["x"])
        t = dist.shard_tensor(pt.arange(16, dtype="float32"), mesh,
                              [dist.Shard(0)])
        u = dist.unshard_dtensor(t)
        np.testing.assert_allclose(u.numpy(), np.arange(16, dtype=np.float32))

    def test_dtensor_from_fn(self):
        mesh = make_mesh(8, names=["x"])
        t = dist.dtensor_from_fn(pt.ones, mesh, [dist.Shard(0)], [16, 2])
        assert t.shape == [16, 2]
        np.testing.assert_allclose(t.numpy(), np.ones((16, 2)))

    def test_sharded_math_matches_local(self):
        """Global-semantics check: math on sharded tensors == local math."""
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        xn = np.random.randn(8, 16).astype(np.float32)
        wn = np.random.randn(16, 12).astype(np.float32)
        dx = dist.shard_tensor(pt.to_tensor(xn), mesh,
                               [dist.Shard(0), dist.Replicate()])
        dw = dist.shard_tensor(pt.to_tensor(wn), mesh,
                               [dist.Replicate(), dist.Shard(1)])
        out = pt.matmul(dx, dw)
        np.testing.assert_allclose(out.numpy(), xn @ wn, rtol=2e-5, atol=2e-5)


class TestShardLayer:
    def test_default_replicate(self):
        mesh = make_mesh(8, names=["dp"])
        layer = pt.nn.Linear(4, 4)
        dist.shard_layer(layer, mesh)
        assert layer.weight.process_mesh == mesh

    def test_custom_shard_fn(self):
        mesh = make_mesh(2, 4, names=["dp", "mp"])

        def shard_fn(name, sublayer, m):
            for pname, p in list(sublayer._parameters.items()):
                if p is None or p.ndim != 2:
                    continue
                t = dist.shard_tensor(p, m, [dist.Replicate(), dist.Shard(1)])
                new_p = type(p)(t._data, name=p.name)
                new_p._placements = t._placements
                new_p._process_mesh = t._process_mesh
                sublayer._parameters[pname] = new_p

        layer = pt.nn.Linear(8, 8)
        dist.shard_layer(layer, mesh, shard_fn)
        # weight got resharded by the fn
        assert layer.weight.shape == [8, 8]


class TestShardOptimizer:
    def test_stage1_shards_moments(self):
        mesh = make_mesh(8, names=["dp"])
        dist.set_mesh(mesh)
        try:
            layer = pt.nn.Linear(16, 16)
            dist.shard_layer(layer, mesh)
            opt = pt.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=layer.parameters())
            opt = dist.shard_optimizer(opt, dist.ShardingStage1("dp", mesh))
            x = pt.ones([4, 16])
            loss = layer(x).sum()
            loss.backward()
            opt.step()
            # Moment accumulators exist and are sharded on dim 0 over dp.
            accs = list(opt._inner._accumulators.values())
            assert accs, "optimizer states missing"
            m1 = accs[0]["moment1"]
            shard_shapes = {s.data.shape for s in m1.addressable_shards}
            assert shard_shapes == {(2, 16)}
        finally:
            dist.set_mesh(None)

    def test_stage3_shards_params(self):
        mesh = make_mesh(8, names=["dp"])
        layer = pt.nn.Linear(16, 4)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=layer.parameters())
        opt = dist.shard_optimizer(opt, dist.ShardingStage3("dp", mesh))
        x = pt.ones([2, 16])
        layer(x).sum().backward()
        opt.step()
        w = layer.weight
        shard_shapes = {s.data.shape for s in w._data.addressable_shards}
        assert shard_shapes == {(2, 4)}


class TestCollectiveAPI:
    def test_groups(self):
        g = dist.new_group([0, 1, 2, 3])
        assert g.nranks == 4
        assert dist.get_group(g.id) is g
        assert g.get_group_rank(2) == 2
        dist.destroy_process_group()

    def test_world_size_one_semantics(self):
        t = pt.ones([4])
        out = dist.all_reduce(t)
        np.testing.assert_allclose(out.numpy(), np.ones(4))
        lst = []
        dist.all_gather(lst, t)
        assert len(lst) == 1
        objs = []
        dist.all_gather_object(objs, {"a": 1})
        assert objs == [{"a": 1}]
        dist.barrier()

    def test_reduce_op(self):
        assert dist.ReduceOp.SUM == 0
        assert dist.ReduceOp.AVG == 4


class TestCommOps:
    """The compiled collective path (the real TPU backend) via shard_map."""

    def test_psum_all_gather_reduce_scatter(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
        data = np.arange(32, dtype=np.float32).reshape(8, 4)

        @jax.jit
        def run(x):
            def f(xs):
                s = comm_ops.all_reduce(xs, "x")          # psum
                g = comm_ops.all_gather(xs, "x", gather_dim=0)
                rs = comm_ops.reduce_scatter(g, "x", scatter_dim=0)
                return s, g, rs
            return shard_map(f, mesh=mesh, in_specs=P("x", None),
                             out_specs=(P(), P(None, None), P("x", None)),
                             check_vma=False)(x)

        s, g, rs = run(data)
        np.testing.assert_allclose(np.asarray(s), data.sum(0, keepdims=True))
        np.testing.assert_allclose(np.asarray(g), data)
        # Each device holds the full gathered copy, so psum_scatter sums 8
        # identical contributions into each scattered block.
        np.testing.assert_allclose(np.asarray(rs), 8 * data)

    def test_ppermute_ring(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
        data = np.arange(8, dtype=np.float32).reshape(8, 1)
        perm = [(i, (i + 1) % 8) for i in range(8)]

        @jax.jit
        def run(x):
            def f(xs):
                return comm_ops.p2p_permute(xs, "x", perm)
            return shard_map(f, mesh=mesh, in_specs=P("x", None),
                             out_specs=P("x", None))(x)

        out = np.asarray(run(data)).flatten()
        np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))

    def test_broadcast_axis(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
        data = np.arange(8, dtype=np.float32).reshape(8, 1)

        @jax.jit
        def run(x):
            def f(xs):
                return comm_ops.broadcast(xs, "x", src=3)
            return shard_map(f, mesh=mesh, in_specs=P("x", None),
                             out_specs=P("x", None))(x)

        out = np.asarray(run(data)).flatten()
        np.testing.assert_allclose(out, np.full(8, 3.0))

    def test_all_to_all(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
        data = np.arange(64, dtype=np.float32).reshape(8, 8)

        @jax.jit
        def run(x):
            def f(xs):
                return comm_ops.all_to_all(xs, "x", split_dim=1, concat_dim=0)
            return shard_map(f, mesh=mesh, in_specs=P("x", None),
                             out_specs=P(None, "x"))(x)

        out = np.asarray(run(data))
        # Row-sharded in, split on dim1 / concat on dim0, column-sharded out:
        # device j ends with column j — reassembly is the identity.
        np.testing.assert_allclose(out, data)


class TestFleet:
    def test_init_topology(self):
        import paddle_tpu.distributed.fleet as fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                                   "pp_degree": 1}
        hcg = fleet.init(is_collective=True, strategy=strategy)
        try:
            assert hcg.get_data_parallel_world_size() == 2
            assert hcg.get_model_parallel_world_size() == 4
            assert hcg.get_parallel_mode() == "tensor_parallel"
            assert hcg.mesh.size == 8
            assert "mp" in hcg.mesh.dim_names
            assert hcg.get_data_parallel_group().nranks == 2
        finally:
            dist.set_mesh(None)
            fleet.fleet._hcg = None

    def test_topology_queries(self):
        topo = fleet_topo = __import__(
            "paddle_tpu.distributed.fleet.topology",
            fromlist=["CommunicateTopology"]).CommunicateTopology(
                dims=[2, 1, 1, 1, 4])
        assert topo.world_size() == 8
        assert topo.get_rank(data=1, pipe=0, sharding=0, sep=0, model=2) == 6
        assert topo.get_coord(6) == (1, 0, 0, 0, 2)
        assert topo.get_comm_list("model")[0] == [0, 1, 2, 3]
        assert topo.get_axis_list("data", 0) == [0, 1, 2, 3]

    def test_mp_layers(self):
        import paddle_tpu.distributed.fleet as fleet
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        dist.set_mesh(mesh)
        try:
            col = fleet.ColumnParallelLinear(16, 32, gather_output=False,
                                             mesh=mesh)
            row = fleet.RowParallelLinear(32, 16, input_is_parallel=True,
                                          mesh=mesh)
            emb = fleet.VocabParallelEmbedding(64, 16, mesh=mesh)
            ids = pt.to_tensor(np.random.randint(0, 64, (2, 8)))
            h = emb(ids)
            assert h.shape == [2, 8, 16]
            y = col(h)
            assert y.shape == [2, 8, 32]
            # weight physically column-sharded over mp (4 ways on dim 1)
            wshapes = {s.data.shape for s in col.weight._data.addressable_shards}
            assert wshapes == {(16, 8)}
            z = row(y)
            assert z.shape == [2, 8, 16]
            # numeric parity with unsharded math
            ref = h.numpy() @ col.weight.numpy() + col.bias.numpy()
            np.testing.assert_allclose(y.numpy(), ref, rtol=2e-5, atol=2e-5)
            # ParallelCrossEntropy smoke
            ce = fleet.ParallelCrossEntropy()
            logits = pt.to_tensor(
                np.random.randn(4, 64).astype(np.float32), stop_gradient=False)
            labels = pt.to_tensor(np.random.randint(0, 64, (4, 1)))
            loss = ce(logits, labels)
            assert loss.shape == [4, 1]
        finally:
            dist.set_mesh(None)


class TestDataParallel:
    def test_wrap_and_run(self):
        mesh = make_mesh(8, names=["dp"])
        dist.set_mesh(mesh)
        try:
            layer = pt.nn.Linear(4, 4)
            dp = dist.DataParallel(layer)
            x = pt.ones([8, 4])
            y = dp(x)
            assert y.shape == [8, 4]
            with dp.no_sync():
                y2 = dp(x)
            np.testing.assert_allclose(y.numpy(), y2.numpy())
            assert layer.weight.process_mesh == mesh
        finally:
            dist.set_mesh(None)


class TestDistributedCheckpoint:
    def test_save_load_reshard(self, tmp_path):
        mesh = make_mesh(2, 4, names=["dp", "mp"])
        w = dist.shard_tensor(
            pt.to_tensor(np.arange(64, dtype=np.float32).reshape(8, 8)),
            mesh, [dist.Shard(0), dist.Replicate()])
        b = pt.ones([8])
        sd = {"w": w, "b": b, "step": 3}
        dist.save_state_dict(sd, str(tmp_path))

        # Load into a DIFFERENTLY sharded target (reshard-on-load).
        w2 = dist.shard_tensor(pt.zeros([8, 8]), mesh,
                               [dist.Replicate(), dist.Shard(1)])
        b2 = pt.zeros([8])
        sd2 = {"w": w2, "b": b2, "step": 0}
        dist.load_state_dict(sd2, str(tmp_path))
        np.testing.assert_allclose(w2.numpy(),
                                   np.arange(64).reshape(8, 8))
        np.testing.assert_allclose(b2.numpy(), np.ones(8))
        assert sd2["step"] == 3
        # target sharding preserved
        shapes = {s.data.shape for s in w2._data.addressable_shards}
        assert shapes == {(8, 2)}


class TestEnv:
    def test_env_defaults(self):
        assert dist.get_rank() == 0
        assert dist.get_world_size() == 1
        penv = dist.ParallelEnv()
        assert penv.rank == 0
        assert penv.nranks == 1
        dist.init_parallel_env()
        assert dist.is_initialized()


class TestNoSyncAccumulation:
    def test_no_sync_accumulation_parity(self):
        """Grad accumulation under no_sync == one big batch (the contract
        documented in DataParallel.no_sync)."""
        mesh = make_mesh(8, names=["dp"])
        dist.set_mesh(mesh)
        try:
            pt.seed(21)
            layer = pt.nn.Linear(16, 4)
            model = dist.DataParallel(layer)
            xin = np.random.default_rng(1).normal(
                size=(8, 16)).astype("float32")

            # accumulate two half-batches under no_sync, sync on the last
            with model.no_sync():
                ((model(pt.to_tensor(xin[:4])) ** 2).mean() / 2).backward()
            ((model(pt.to_tensor(xin[4:])) ** 2).mean() / 2).backward()
            acc = layer.weight.grad.numpy().copy()
            layer.weight.clear_grad()
            layer.bias.clear_grad()

            ((model(pt.to_tensor(xin)) ** 2).mean()).backward()
            full = layer.weight.grad.numpy()
            np.testing.assert_allclose(acc, full, rtol=1e-4, atol=1e-6)
        finally:
            dist.set_mesh(None)


class TestAsyncCheckpoint:
    def test_async_save_roundtrip(self, tmp_path):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu.distributed import checkpoint as ckpt

        sd = {"w": paddle.to_tensor(np.arange(12, dtype="float32")
                                    .reshape(3, 4)),
              "step": 7}
        handle = ckpt.async_save_state_dict(sd, str(tmp_path / "ck"))
        # caller may mutate immediately after return
        sd["w"].set_value(np.zeros((3, 4), "float32"))
        handle.result(timeout=60)
        assert handle.done()
        target = {"w": paddle.to_tensor(np.zeros((3, 4), "float32")),
                  "step": 0}
        ckpt.load_state_dict(target, str(tmp_path / "ck"))
        np.testing.assert_allclose(
            np.asarray(target["w"].numpy()),
            np.arange(12, dtype="float32").reshape(3, 4))


class TestCrossAxisGradClip:
    def test_global_norm_clip_sharded_vs_local(self):
        """VERDICT r2 gap: cross-mesh-axis clip discipline. The global
        grad norm computed over SHARDED parameters (fsdp+tp placements)
        must match the single-device computation, and the clipped update
        must be identical."""
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.distributed as dist
        import paddle_tpu.nn as nn
        from paddle_tpu.optimizer import SGD, ClipGradByGlobalNorm

        rng = np.random.default_rng(0)
        w = rng.normal(size=(8, 8)).astype("float32") * 3.0
        x = rng.normal(size=(4, 8)).astype("float32")

        def build(shard):
            lin = nn.Linear(8, 8)
            lin.weight.set_value(w)
            if shard:
                mesh = dist.ProcessMesh(
                    np.arange(8).reshape(2, 2, 2),
                    dim_names=["dp", "fsdp", "tp"])
                # weight sharded across BOTH fsdp and tp axes
                lin.weight = dist.shard_tensor(
                    lin.weight, mesh,
                    [dist.Replicate(), dist.Shard(0), dist.Shard(1)])
            opt = SGD(learning_rate=0.1, parameters=lin.parameters(),
                      grad_clip=ClipGradByGlobalNorm(1.0))
            return lin, opt

        results = []
        for shard in (False, True):
            lin, opt = build(shard)
            loss = (lin(paddle.to_tensor(x)) ** 2).sum()
            loss.backward()
            # the raw grad norm is far above the clip threshold
            gn = float(np.linalg.norm(
                np.asarray(lin.weight.grad.numpy())))
            assert gn > 1.0
            opt.step()
            results.append(np.asarray(lin.weight.numpy()))
        np.testing.assert_allclose(results[0], results[1], rtol=1e-5,
                                   atol=1e-6)
        # and the post-clip update magnitude reflects clip_norm=1.0:
        # ||delta|| = lr * ||clipped grad|| = 0.1 * ~1.0 (bias included)
        delta = np.linalg.norm(results[0] - w)
        assert delta < 0.1 + 1e-3
