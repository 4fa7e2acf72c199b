"""Communication & roofline observability (PR 8).

Covers the three tentpole pieces — the HLO collective scan
(monitor/comms.py + the lazy program analyzer), the roofline
classifier (monitor/roofline.py), and the sharding inspector
(distributed/introspect.py + the /roofline + /sharding routes — plus
the satellites: the eager/trace collective byte-count agreement (one
count per op, monitor-internal re-traces suppressed), the hardened
cost_analysis reads, and the fleet histogram-mean divergence wiring.
"""
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor import comms, fleet, mfu as mfu_mod
from paddle_tpu.monitor import programs, roofline, server
from paddle_tpu.distributed import introspect


@pytest.fixture
def mon():
    monitor.reset()
    pt.set_flags({"FLAGS_enable_monitor": True})
    yield
    pt.set_flags({"FLAGS_enable_monitor": False})
    server.stop_server()
    monitor.reset()


def _mesh(shape=(4, 2), axes=("dp", "tp")):
    n = 1
    for d in shape:
        n *= d
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


def _sharded_program(mesh=None):
    """A jitted program whose GSPMD partitioning inserts collectives,
    plus its sharded input."""
    mesh = mesh or _mesh()
    sh = NamedSharding(mesh, P("dp", "tp"))
    f = jax.jit(lambda x: (x @ x.T).sum(), in_shardings=(sh,))
    x = jax.device_put(jnp.ones((8, 8), jnp.float32), sh)
    return f, x


# ---------------------------------------------------------------------------
# HLO collective scan
# ---------------------------------------------------------------------------

_SYNTH_HLO = """
HloModule synth
ENTRY %main (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %all-reduce = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %p0), to_apply=%add
  %ag = f32[16,8]{1,0} all-gather(f32[4,8]{1,0} %all-reduce), dimensions={0}
  %rs = f32[2,8]{1,0} reduce-scatter(f32[4,8]{1,0} %all-reduce), to_apply=%add
  %cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %p0), source_target_pairs={{0,1}}
  %a2a = f32[4,8]{1,0} all-to-all(f32[4,8]{1,0} %p0), dimensions={0}
  %ars = (f32[4,8]{1,0}, f32[4,8]{1,0}) all-reduce-start(f32[4,8]{1,0} %p0), to_apply=%add
  ROOT %ard = f32[4,8]{1,0} all-reduce-done((f32[4,8]{1,0}, f32[4,8]{1,0}) %ars)
}
"""


class TestHloScan:
    def test_counts_and_bytes_by_kind(self):
        got = comms.scan_hlo_collectives(_SYNTH_HLO)
        # sync all-reduce (128B) + async start (tuple halved -> 128B);
        # the -done op never double-counts
        assert got["all_reduce"] == {"count": 2, "bytes": 256}
        assert got["all_gather"] == {"count": 1, "bytes": 512}
        assert got["reduce_scatter"] == {"count": 1, "bytes": 64}
        assert got["collective_permute"] == {"count": 1, "bytes": 128}
        assert got["all_to_all"] == {"count": 1, "bytes": 128}

    def test_no_collectives_empty(self):
        assert comms.scan_hlo_collectives(
            "ENTRY %m { ROOT %d = f32[8,8]{1,0} dot(...) }") == {}

    def test_tpu_tiled_layout_shapes(self):
        # TPU post-optimization HLO carries tiled/memory-space layout
        # annotations with parens INSIDE the braces — the async -start
        # tuples the TPU backend emits by default must still count
        hlo = (
            "%ar-start = (bf16[1024]{0:T(1024)}, bf16[1024]{0:T(1024)})"
            " all-reduce-start(bf16[1024]{0:T(1024)} %p0), to_apply=%a\n"
            "%ar-done = bf16[1024]{0:T(1024)} all-reduce-done("
            "(bf16[1024]{0:T(1024)}, bf16[1024]{0:T(1024)}) %ar-start)\n"
            "%ag = f32[8,128]{1,0:T(8,128)} all-gather("
            "f32[1,128]{1,0:T(8,128)} %p1), dimensions={0}\n")
        got = comms.scan_hlo_collectives(hlo)
        assert got["all_reduce"] == {"count": 1, "bytes": 2048}
        assert got["all_gather"] == {"count": 1, "bytes": 4096}

    def test_shape_bytes(self):
        assert comms.shape_bytes("f32[4,8]{1,0}") == 128
        assert comms.shape_bytes("bf16[2,3]") == 12
        assert comms.shape_bytes("(f32[4], u32[2])") == 24
        assert comms.shape_bytes("f32[]") == 4
        assert comms.shape_bytes("pred[8]") == 8
        assert comms.shape_bytes("mystery[4]") == 0   # unknown dtype

    def test_total_counts(self):
        assert comms.total_counts(None) == (0, 0)
        assert comms.total_counts({}) == (0, 0)
        assert comms.total_counts(
            {"all_reduce": {"count": 2, "bytes": 10},
             "all_gather": {"count": 1, "bytes": 5}}) == (3, 15)

    def test_real_sharded_program_scans_collectives(self, mon):
        f, x = _sharded_program()
        f(x)
        programs.record_jit_call(("scan", 1), "sharded", f, (x,))
        programs.analyze_pending()
        rec = programs.programs_snapshot()[0]
        assert rec["collectives"], rec
        total_ops, total_bytes = comms.total_counts(rec["collectives"])
        assert total_ops > 0 and total_bytes > 0
        assert set(rec["collectives"]) <= set(comms.COLLECTIVE_KINDS)
        g = monitor.snapshot()["gauges"]
        assert g["comm.program.collectives.total"] == total_ops
        assert g["comm.program.bytes.total"] == total_bytes
        assert g["comm.program.last_collectives"] == total_ops

    def test_single_device_program_scans_empty(self, mon):
        f = jax.jit(lambda x: x @ x)
        x = jnp.ones((8, 8), jnp.float32)
        f(x)
        programs.record_jit_call(("scan", 2), "local", f, (x,))
        programs.analyze_pending()
        rec = programs.programs_snapshot()[0]
        # analyzed (not None) but no collectives on one device
        assert rec["collectives"] == {}


# ---------------------------------------------------------------------------
# satellite: eager/trace byte agreement + count-once discipline
# ---------------------------------------------------------------------------

class TestCollectiveByteAudit:
    def test_trace_and_eager_paths_agree_and_count_once(self, mon):
        from jax import shard_map

        from paddle_tpu.distributed import collective as coll
        from paddle_tpu.distributed import comm_ops

        mesh = _mesh((8,), ("x",))
        # per-device block is [1, 4] f32 = 16 bytes
        f = jax.jit(shard_map(
            lambda x: comm_ops.all_reduce(x, "x"), mesh=mesh,
            in_specs=P("x", None), out_specs=P(None, None)))
        x = jnp.ones((8, 4), jnp.float32)

        def deltas():
            c = monitor.snapshot().get("counters", {})
            return (c.get("dist.all_reduce.calls", 0),
                    c.get("dist.all_reduce.bytes", 0),
                    c.get("dist.eager.all_reduce.calls", 0),
                    c.get("dist.eager.all_reduce.bytes", 0))

        assert deltas() == (0, 0, 0, 0)
        f(x)                                   # one trace+compile
        assert deltas() == (1, 16, 0, 0)
        f(x)                                   # cache hit: no retrace
        assert deltas() == (1, 16, 0, 0)

        # the SAME reduction (a 16-byte operand) through the eager
        # host path must count the same bytes, once per call
        t = pt.to_tensor(np.ones((1, 4), np.float32))
        coll.all_reduce(t)
        assert deltas() == (1, 16, 1, 16)

    def test_monitor_internal_retrace_is_suppressed(self, mon):
        from jax import shard_map

        from paddle_tpu.distributed import comm_ops

        mesh = _mesh((8,), ("x",))
        f = jax.jit(shard_map(
            lambda x: comm_ops.all_reduce(x, "x"), mesh=mesh,
            in_specs=P("x", None), out_specs=P(None, None)))
        x = jnp.ones((8, 4), jnp.float32)
        f(x)
        before = monitor.snapshot()["counters"]["dist.all_reduce.calls"]
        # every monitor-internal lowering: the MFU/cost capture, the
        # registry's record-time capture, and the lazy analyzer's AOT
        # compile — none may re-fire the trace-time counters
        mfu_mod.lowered_cost(f, x)
        programs.record_jit_call(("sup", 1), "sup", f, (x,))
        programs.analyze_pending()
        after = monitor.snapshot()["counters"]["dist.all_reduce.calls"]
        assert after == before

    def test_eager_host_exchange_latency_observed(self, mon):
        from paddle_tpu.distributed import collective as coll
        objs = []
        coll.all_gather_object(objs, {"a": 1})
        assert objs == [{"a": 1}]
        coll.barrier()
        h = monitor.snapshot()["histograms"]
        assert h["comm.latency.all_gather_object_ms"]["count"] == 1
        assert h["comm.latency.barrier_ms"]["count"] == 1

    def test_off_path_registers_nothing(self):
        from jax import shard_map

        from paddle_tpu.distributed import collective as coll
        from paddle_tpu.distributed import comm_ops

        monitor.reset()
        pt.set_flags({"FLAGS_enable_monitor": False})
        mesh = _mesh((8,), ("x",))
        f = jax.jit(shard_map(
            lambda x: comm_ops.all_reduce(x, "x"), mesh=mesh,
            in_specs=P("x", None), out_specs=P(None, None)))
        f(jnp.ones((8, 4), jnp.float32))
        objs = []
        coll.all_gather_object(objs, 3)
        coll.barrier()
        introspect.register_sharded_tree("off", {"w": jnp.ones(4)})
        assert monitor.snapshot() == {}
        assert introspect.sharding_snapshot()["trees"] == {}
        assert programs.programs_snapshot() == []


# ---------------------------------------------------------------------------
# satellite: hardened cost_analysis reads
# ---------------------------------------------------------------------------

class _BrokenLower:
    def lower(self, *a, **k):
        raise RuntimeError("backend says no")


class _KeylessCost:
    class _L:
        def cost_analysis(self):
            return {"utilization": 1.0}       # no flops, no bytes

    def lower(self, *a, **k):
        return self._L()


class TestCostAnalysisHardening:
    def test_raising_lower_returns_none_and_counts(self, mon):
        cost = mfu_mod.lowered_cost(_BrokenLower(), 1)
        assert cost == {"flops": None, "bytes_accessed": None}
        assert mfu_mod.lowered_flops(_BrokenLower(), 1) is None
        c = monitor.snapshot()["counters"]
        assert c["monitor.cost_analysis.unavailable"] == 2

    def test_missing_keys_return_none_and_count(self, mon):
        cost = mfu_mod.lowered_cost(_KeylessCost())
        assert cost == {"flops": None, "bytes_accessed": None}
        assert monitor.snapshot()["counters"][
            "monitor.cost_analysis.unavailable"] == 1

    def test_record_jit_call_survives_broken_backend(self, mon):
        rec = programs.record_jit_call(("broken", 1), "b",
                                       _BrokenLower(), (1,))
        # unavailable stays None on the record too — /programs and
        # /roofline never report a fabricated 0.0
        assert rec.flops is None
        assert rec.bytes_accessed is None
        assert programs.has_record(("broken", 1))

    def test_cost_analysis_value_shapes(self):
        assert mfu_mod.cost_analysis_value(None, "flops") is None
        assert mfu_mod.cost_analysis_value({"flops": 8.0}, "flops") == 8.0
        assert mfu_mod.cost_analysis_value({"flops": -1}, "flops") is None
        assert mfu_mod.cost_analysis_value(
            [{"flops": 8.0}, {"x": 1}], "flops") == 8.0
        assert mfu_mod.cost_analysis_value([{"x": 1}], "flops") is None
        # legacy 0.0-defaulting read keeps its shape
        assert mfu_mod.cost_analysis_flops({"bytes": 9}) == 0.0

    def test_answered_zero_is_not_unavailable(self, mon):
        # a pure data-movement program legitimately reports 0 flops:
        # that is an ANSWER, not an unavailable read
        class ZeroCost:
            class _L:
                def cost_analysis(self):
                    return {"flops": 0.0, "bytes accessed": 0.0}

            def lower(self, *a, **k):
                return self._L()

        cost = mfu_mod.lowered_cost(ZeroCost())
        assert cost == {"flops": 0.0, "bytes_accessed": 0.0}
        assert "monitor.cost_analysis.unavailable" not in \
            monitor.snapshot().get("counters", {})

    def test_record_program_flops_accepts_none(self, mon):
        mfu_mod.record_program_flops(None)
        assert "jit.program.flops" not in \
            monitor.snapshot().get("counters", {})

    def test_real_program_reports_bytes_accessed(self, mon):
        f = jax.jit(lambda x: x @ x)
        x = jnp.ones((16, 16), jnp.float32)
        cost = mfu_mod.lowered_cost(f, x)
        assert cost["flops"] and cost["flops"] >= 2 * 16 ** 3
        assert cost["bytes_accessed"] and cost["bytes_accessed"] > 0


# ---------------------------------------------------------------------------
# roofline classification
# ---------------------------------------------------------------------------

class TestRoofline:
    PEAKS = {"peak_flops_per_sec": 1e12,
             "peak_hbm_bytes_per_sec": 1e11,
             "peak_ici_bytes_per_sec": 1e10}

    def test_verdicts(self):
        # AI 100 >> ridge 10 -> compute-bound
        c = roofline.classify(1e9, 1e7, 0, self.PEAKS)
        assert c["verdict"] == "compute-bound"
        assert c["arithmetic_intensity"] == pytest.approx(100.0)
        # AI 1 << ridge 10 -> hbm-bound
        h = roofline.classify(1e7, 1e7, 0, self.PEAKS)
        assert h["verdict"] == "hbm-bound"
        # comm time dominates both
        m = roofline.classify(1e7, 1e7, 1e8, self.PEAKS)
        assert m["verdict"] == "comm-bound"
        assert m["t_comm_s"] == pytest.approx(1e-2)
        assert m["t_modeled_s"] == pytest.approx(1e-2)

    def test_unavailable_inputs_do_not_classify(self):
        assert roofline.classify(None, 1e7, 0, self.PEAKS)["verdict"] \
            is None
        assert roofline.classify(1e7, None, 0, self.PEAKS)["verdict"] \
            is None
        assert roofline.classify(0, 0, 0, self.PEAKS)["verdict"] is None

    def test_answered_zero_flops_classifies(self):
        # a genuine zero-FLOP data-movement program with real byte
        # traffic is trivially memory-bound — an ANSWER, not a gap
        c = roofline.classify(0.0, 1e7, 0, self.PEAKS)
        assert c["verdict"] == "hbm-bound"
        assert c["arithmetic_intensity"] == 0.0

    def test_ridge_point(self):
        assert roofline.ridge_point(1e12, 1e11) == pytest.approx(10.0)
        assert roofline.ridge_point(0, 1e11) is None

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_GBS", "100")
        monkeypatch.setenv("PADDLE_TPU_PEAK_ICI_GBS", "10")
        assert roofline.peak_hbm_bytes_per_sec() == pytest.approx(1e11)
        assert roofline.peak_ici_bytes_per_sec() == pytest.approx(1e10)
        peaks = roofline.resolve_peaks()
        assert peaks["hbm_source"] == "env"
        assert peaks["ici_source"] == "env"

    def test_generation_table(self):
        # the chip on record reports "TPU v5 lite": no generation name is
        # a substring of that, so the entry is explicit
        class V5e:
            device_kind = "TPU v5 lite"
            platform = "tpu"

        hbm = roofline._resolve_bw("PADDLE_TPU_PEAK_HBM_GBS",
                                   roofline.PEAK_HBM_GBS_TABLE,
                                   1.0, V5e())
        assert hbm["source"] == "table"
        assert hbm["generation"] == "v5e"
        assert hbm["bytes_per_sec"] == pytest.approx(819e9)
        # ONE shared resolver: the FLOPs denominator must match the
        # same generation for the same device
        fl = mfu_mod.resolve_peak("PADDLE_TPU_PEAK_FLOPS",
                                  mfu_mod.PEAK_FLOPS_TABLE, 1.0, V5e())
        assert fl["generation"] == hbm["generation"]
        assert fl["value"] == 197e12
        peaks = roofline.resolve_peaks(V5e())
        assert peaks["peak_flops_per_sec"] == 197e12
        assert peaks["peak_hbm_bytes_per_sec"] == pytest.approx(819e9)
        assert (peaks["flops_source"], peaks["hbm_source"],
                peaks["flops_generation"]) == ("table", "table", "v5e")

    def test_unknown_tpu_kind_raises(self):
        # never another part's peaks: a TPU missing from the table is an
        # error; only a non-TPU device gets the nominal figure
        class Unknown:
            device_kind = "TPU v9 mega"
            platform = "tpu"

        with pytest.raises(KeyError, match="TPU v9 mega"):
            mfu_mod.peak_flops(Unknown())
        with pytest.raises(KeyError, match="TPU v9 mega"):
            roofline.resolve_peaks(Unknown())
        assert mfu_mod.resolve_peak(
            "PADDLE_TPU_PEAK_FLOPS", mfu_mod.PEAK_FLOPS_TABLE, 1e12,
            jax.devices()[0])["source"] == "nominal"

    def test_snapshot_attribution_and_gauges(self, mon):
        f, x = _sharded_program()
        f(x)
        programs.record_jit_call(("rf", 1), "sharded", f, (x,))
        programs.note_hit(("rf", 1))           # 2 invocations
        g = jax.jit(lambda y: y * 2.0)
        y = jnp.ones((4,), jnp.float32)
        g(y)
        programs.record_jit_call(("rf", 2), "tiny", g, (y,))
        rs = roofline.roofline_snapshot(analyze=True)
        by_name = {p["name"]: p for p in rs["programs"]}
        sharded = by_name["sharded"]
        assert sharded["verdict"] in ("compute-bound", "hbm-bound",
                                      "comm-bound")
        assert sharded["invocations"] == 2
        assert sharded["collective_ops"] > 0
        assert sharded["comms_analyzed"]
        shares = [p["share"] for p in rs["programs"] if p["share"]]
        assert sum(shares) == pytest.approx(1.0, abs=0.01)
        assert rs["attribution"]["comm_fraction"] is not None
        assert rs["comm"]["programs_analyzed"] == 2
        assert rs["comm"]["programs_with_collectives"] == 1
        gauges = monitor.snapshot()["gauges"]
        assert gauges["roofline.programs.classified"] == 2
        assert "roofline.comm.modeled_fraction" in gauges

    def test_empty_registry_snapshot(self, mon):
        rs = roofline.roofline_snapshot(analyze=False)
        assert rs["programs"] == []
        assert rs["attribution"]["total_modeled_s"] == 0.0
        assert rs["attribution"]["comm_fraction"] is None


# ---------------------------------------------------------------------------
# sharding inspector
# ---------------------------------------------------------------------------

class TestShardingInspector:
    def test_describe_sharded_and_replicated_leaves(self):
        mesh = _mesh()
        tree = {
            "w": jax.device_put(jnp.ones((8, 16), jnp.float32),
                                NamedSharding(mesh, P("dp", "tp"))),
            "b": jax.device_put(jnp.ones((16,), jnp.float32),
                                NamedSharding(mesh, P())),
        }
        d = introspect.describe_tree(tree)
        by_path = {leaf["path"]: leaf for leaf in d["leaves"]}
        w = by_path["['w']"]
        assert w["spec"] == "PartitionSpec('dp', 'tp')"
        assert w["mesh_axes"] == {"dp": 4, "tp": 2}
        assert w["shard_shape"] == [2, 8]
        assert w["shard_bytes"] == 2 * 8 * 4
        assert w["replication_factor"] == pytest.approx(1.0)
        assert not w["fully_replicated"]
        b = by_path["['b']"]
        assert b["replication_factor"] == pytest.approx(8.0)
        assert b["fully_replicated"]
        assert b["shard_bytes"] == 64
        assert d["num_arrays"] == 2
        assert d["replicated_bytes"] == 64
        # uniform layout: no cross-device imbalance
        assert d["imbalance"]["devices"] == 8
        assert d["imbalance"]["relative_imbalance"] == pytest.approx(
            0.0, abs=1e-6)

    def test_imbalance_detects_single_device_tree(self):
        # unsharded arrays all live on device 0 -> max imbalance
        mesh = _mesh()
        tree = {
            "sharded": jax.device_put(jnp.ones((8, 8), jnp.float32),
                                      NamedSharding(mesh, P("dp"))),
            "host_only": jnp.ones((64,), jnp.float32),
        }
        d = introspect.describe_tree(tree)
        assert d["imbalance"]["relative_imbalance"] > 0

    def test_unsharded_and_non_array_leaves(self):
        d = introspect.describe_tree({"a": np.ones((4,), np.float32),
                                      "s": "not-an-array", "n": 3})
        assert d["num_arrays"] == 1
        leaf = d["leaves"][0]
        assert leaf["num_devices"] == 1
        assert leaf["replication_factor"] == 1.0

    def test_tensor_facade_unwraps(self):
        t = pt.to_tensor(np.ones((2, 3), np.float32))
        d = introspect.describe_tree({"t": t})
        assert d["num_arrays"] == 1
        assert d["leaves"][0]["global_bytes"] == 24

    def test_leaf_bound_truncates(self):
        tree = {f"p{i}": jnp.ones((2,), jnp.float32) for i in range(20)}
        d = introspect.describe_tree(tree, max_leaves=5)
        assert len(d["leaves"]) == 5
        assert d["truncated"]
        assert d["num_arrays"] == 20
        assert d["total_global_bytes"] == 20 * 8

    def test_register_and_snapshot(self, mon):
        mesh = _mesh()
        tree = {"w": jax.device_put(jnp.ones((8, 8), jnp.float32),
                                    NamedSharding(mesh, P("dp", "tp")))}
        introspect.register_sharded_tree("train.params", tree)
        snap = introspect.sharding_snapshot()
        assert "train.params" in snap["trees"]
        assert snap["world"]["devices"] == 8
        # monitor.reset clears the registered trees
        monitor.reset()
        assert introspect.sharding_snapshot()["trees"] == {}

    def test_ensure_tree_only_materializes_when_absent(self, mon):
        calls = []

        def make():
            calls.append(1)
            return {"w": jnp.ones((2,), jnp.float32)}

        assert introspect.ensure_sharded_tree("e.params", make)
        assert not introspect.ensure_sharded_tree("e.params", make)
        assert calls == [1]          # steady state never re-computes

    def test_engine_params_tree_recovers_after_reset(self, mon):
        """monitor.reset() mid-run must not permanently empty the
        /sharding trees view: the next dispatch re-registers the live
        engine's params, like the program registry itself."""
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L

        cfg = L.llama_tiny(num_hidden_layers=1, vocab_size=64,
                           hidden_size=32, intermediate_size=64,
                           num_attention_heads=2, num_key_value_heads=2,
                           max_position_embeddings=32)
        eng = ServingEngine(L, L.init_params(cfg, jax.random.PRNGKey(0)),
                            cfg, num_slots=1, max_len=16, page_size=8,
                            decode_chunk=2)
        assert any(k.endswith(".params")
                   for k in introspect.sharding_snapshot()["trees"])
        monitor.reset()
        assert introspect.sharding_snapshot()["trees"] == {}
        rng = np.random.default_rng(0)
        eng.run([Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, (4,)).astype(np.int32),
            max_new_tokens=2)])
        assert any(k.endswith(".params")
                   for k in introspect.sharding_snapshot()["trees"])

    def test_program_records_carry_arg_sharding(self, mon):
        f, x = _sharded_program()
        f(x)
        programs.record_jit_call(("shard", 1), "sharded", f, (x,))
        snap = introspect.sharding_snapshot()
        assert len(snap["programs"]) == 1
        prog = snap["programs"][0]
        assert prog["name"] == "sharded"
        leaf = prog["sharding"]["leaves"][0]
        assert leaf["spec"] == "PartitionSpec('dp', 'tp')"
        assert leaf["shard_bytes"] == 32


# ---------------------------------------------------------------------------
# operator endpoints + end-to-end acceptance
# ---------------------------------------------------------------------------

def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, json.load(r)


class TestEndpoints:
    def test_roofline_and_sharding_routes(self, mon):
        srv = server.start_server(port=0)
        f, x = _sharded_program()
        f(x)
        programs.record_jit_call(("ep", 1), "sharded", f, (x,))
        status, rl = _get_json(f"{srv.url}/roofline")
        assert status == 200
        assert rl["programs"][0]["name"] == "sharded"
        assert rl["programs"][0]["verdict"] is not None
        assert rl["programs"][0]["collective_ops"] > 0
        assert rl["peaks"]["ridge_point_flops_per_byte"] > 0
        status, sh = _get_json(f"{srv.url}/sharding")
        assert status == 200
        assert sh["programs"][0]["name"] == "sharded"
        status, root = _get_json(f"{srv.url}/")
        assert "/roofline" in root["routes"]
        assert "/sharding" in root["routes"]

    @pytest.mark.slow
    def test_acceptance_train_step_and_decode_in_roofline(self, mon):
        """A compiled llama train step and a ServingEngine decode
        program both appear in /roofline with nonzero FLOPs, nonzero
        bytes-accessed, a boundedness verdict, and (explicitly
        sharded) nonzero collective counts; /sharding reports per-leaf
        specs + shard bytes for the same programs. Slow lane per the
        tier-1 budget (ISSUE 8): the mesh train step compiles twice
        (once real, once for the lazy AOT analysis, ~15s);
        test_decode_program_classified + test_roofline_and_sharding_
        routes keep the decode-program and sharded-collective pins in
        the fast lane, and scripts/tpu_smoke.py roofline_scrape runs
        the full path end to end."""
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L

        # smallest config that still exercises the mesh: the /roofline
        # scrape AOT-recompiles the train step for its lazy analysis,
        # so compile weight counts double here
        cfg = L.llama_tiny(num_hidden_layers=1, vocab_size=64,
                           hidden_size=32, intermediate_size=64,
                           num_attention_heads=2, num_key_value_heads=2,
                           max_position_embeddings=64)
        mesh = _mesh((4, 2, 1), ("dp", "fsdp", "tp"))
        with mesh:
            params = L.shard_params(
                L.init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh)
            step = L.make_train_step(cfg, mesh, lr=1e-3, donate=False,
                                     guard=False)
            opt = L.adamw_init(params)
            opt = jax.device_put(
                opt, {"step": NamedSharding(mesh, P()),
                      "m": jax.tree.map(lambda a: a.sharding, params),
                      "v": jax.tree.map(lambda a: a.sharding, params)})
            ids = jax.device_put(
                jnp.zeros((8, 16), jnp.int32),
                NamedSharding(mesh, P(("dp", "fsdp"), None)))
            params, opt, _ = step(params, opt, ids)
            programs.record_jit_call(("acc", "train"),
                                     "llama.train_step", step,
                                     (params, opt, ids))

        eng = ServingEngine(L, L.init_params(cfg, jax.random.PRNGKey(1)),
                            cfg, num_slots=2, max_len=32, page_size=8,
                            decode_chunk=2)
        rng = np.random.default_rng(0)
        eng.run([Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, (6,)).astype(np.int32),
            max_new_tokens=4)])

        srv = server.start_server(port=0)
        _, rl = _get_json(f"{srv.url}/roofline")
        by_name = {p["name"]: p for p in rl["programs"]}
        train = by_name["llama.train_step"]
        decode = next(p for n, p in by_name.items()
                      if n.startswith("serving.decode_chunk"))
        for p in (train, decode):
            assert p["flops"] > 0, p
            assert p["bytes_accessed"] > 0, p
            assert p["verdict"] in ("compute-bound", "hbm-bound",
                                    "comm-bound"), p
        # the explicitly-sharded train step crosses the mesh
        assert train["collective_ops"] > 0, train

        _, sh = _get_json(f"{srv.url}/sharding")
        names = [p["name"] for p in sh["programs"]]
        assert "llama.train_step" in names
        assert any(n.startswith("serving.") for n in names)
        train_sh = next(p for p in sh["programs"]
                        if p["name"] == "llama.train_step")
        specs = {leaf["spec"] for leaf in train_sh["sharding"]["leaves"]}
        assert any(s and "PartitionSpec" in s for s in specs)
        assert all(leaf["shard_bytes"] > 0
                   for leaf in train_sh["sharding"]["leaves"])
        # the engine registered its params tree
        assert any(k.endswith(".params") for k in sh["trees"])

    def test_decode_program_classified(self, mon):
        """Fast-lane half of the acceptance pin: a ServingEngine
        decode program lands in the roofline view with measured FLOPs,
        bytes-accessed and a verdict, and the engine's params tree is
        in the sharding view (the mesh-sharded train-step half lives
        in the slow-marked acceptance test + the smoke stage)."""
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L

        cfg = L.llama_tiny(num_hidden_layers=1, vocab_size=64,
                           hidden_size=32, intermediate_size=64,
                           num_attention_heads=2, num_key_value_heads=2,
                           max_position_embeddings=32)
        eng = ServingEngine(L, L.init_params(cfg, jax.random.PRNGKey(0)),
                            cfg, num_slots=1, max_len=16, page_size=8,
                            decode_chunk=2)
        rng = np.random.default_rng(0)
        eng.run([Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, (4,)).astype(np.int32),
            max_new_tokens=3)])
        rs = roofline.roofline_snapshot(analyze=True, max_analyze=8)
        decode = next(p for p in rs["programs"]
                      if p["name"].startswith("serving.decode_chunk"))
        assert decode["flops"] > 0
        assert decode["bytes_accessed"] > 0
        assert decode["verdict"] in ("compute-bound", "hbm-bound",
                                     "comm-bound")
        assert decode["comms_analyzed"]
        snap = introspect.sharding_snapshot()
        assert any(k.endswith(".params") for k in snap["trees"])
        assert any(p["name"].startswith("serving.")
                   for p in snap["programs"])

    def test_flag_off_nothing_served_or_registered(self):
        monitor.reset()
        pt.set_flags({"FLAGS_enable_monitor": False,
                      "FLAGS_enable_monitor_server": False})
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L

        cfg = L.llama_tiny(num_hidden_layers=1)
        eng = ServingEngine(L, L.init_params(cfg, jax.random.PRNGKey(0)),
                            cfg, num_slots=1, max_len=16, page_size=8)
        rng = np.random.default_rng(0)
        eng.run([Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, (4,)).astype(np.int32),
            max_new_tokens=2)])
        assert programs.programs_snapshot() == []
        assert roofline.roofline_snapshot(analyze=False)["programs"] \
            == []
        assert introspect.sharding_snapshot()["trees"] == {}
        assert monitor.snapshot() == {}


# ---------------------------------------------------------------------------
# satellite: fleet wiring
# ---------------------------------------------------------------------------

class TestFleetCommWiring:
    def test_absent_comm_gauges_stay_none(self):
        snaps = [
            {"gauges": {"comm.program.bytes.total": 100}},
            {"gauges": {}},                     # never analyzed
        ]
        agg = fleet.aggregate_hosts(snaps)
        s = agg["scalars"]["comm.program.bytes.total"]
        assert s["hosts"] == [100, None]
        assert s["sum"] == 100                  # not zero-filled

    def test_histogram_host_means_surface_latency_divergence(self):
        # same counts, one rank 10x slower: invisible to the merged
        # sum, line 1 of the divergence report via host means
        snaps = [
            {"histograms": {"comm.latency.all_reduce_ms":
                            {"count": 10, "sum": 10.0,
                             "min": 0.5, "max": 2.0}}},
            {"histograms": {"comm.latency.all_reduce_ms":
                            {"count": 10, "sum": 100.0,
                             "min": 5.0, "max": 20.0}}},
        ]
        agg = fleet.aggregate_hosts(snaps)
        h = agg["histograms"]["comm.latency.all_reduce_ms"]
        assert h["host_means"] == [1.0, 10.0]
        assert h["count"] == 20
        div = fleet.divergence(agg)
        assert div[0]["metric"] == "comm.latency.all_reduce_ms:mean"
        assert div[0]["relative_spread"] == pytest.approx(0.9)

    def test_histogram_absent_on_some_hosts_not_divergent(self):
        snaps = [
            {"histograms": {"h.x": {"count": 2, "sum": 4.0}}},
            {"histograms": {}},
        ]
        agg = fleet.aggregate_hosts(snaps)
        assert agg["histograms"]["h.x"]["host_means"] == [2.0, None]
        # a single present mean cannot diverge
        assert all(d["metric"] != "h.x:mean"
                   for d in fleet.divergence(agg))

    def test_fleet_text_renders_host_means(self):
        payload = {
            "world_size": 2,
            "aggregate": fleet.aggregate_hosts([
                {"histograms": {"h.y": {"count": 1, "sum": 3.0}}},
                {"histograms": {"h.y": {"count": 1, "sum": 5.0}}}]),
        }
        text = fleet.expose_fleet_text(payload)
        assert 'h_y{host="0",agg="mean"} 3' in text
        assert 'h_y{host="1",agg="mean"} 5' in text
