"""monitor subsystem tests: registry types, thread-safety smoke,
Prometheus text exposition, snapshot determinism, hot-path
instrumentation (op dispatch / jit cache / tensor bytes / dataloader /
collectives), and the off-path guard (flag unset -> empty registry, no
import-time side effects).

Reference strategy: the monitor.h stats are exercised in the reference
via test/cpp/fluid/platform/monitor_test.cc (register, add, read back);
here the python registry carries the same contract plus the exposition
formats the reference exports through pybind."""
import gc
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor import StatRegistry
from paddle_tpu.monitor.exposition import sanitize_name


@pytest.fixture
def mon():
    """Fresh registry with the flag ON; teardown disables BEFORE reset
    so late Tensor finalizers can't resurrect the byte gauges."""
    monitor.reset()
    pt.set_flags({"FLAGS_enable_monitor": True})
    yield monitor
    pt.set_flags({"FLAGS_enable_monitor": False})
    monitor.reset()


class TestRegistryTypes:
    def test_counter(self):
        r = StatRegistry()
        c = r.counter("c", "doc")
        c.incr()
        c.incr(5)
        c.add(2)
        assert c.value == 8
        c.reset()
        assert c.value == 0

    def test_gauge(self):
        r = StatRegistry()
        g = r.gauge("g")
        g.set(10)
        g.add(5)
        g.sub(3)
        assert g.value == 12

    def test_gauge_peak_pair(self):
        r = StatRegistry()
        live, peak = r.gauge("live"), r.gauge("peak")
        live.add_and_max_into(100, peak)
        live.add_and_max_into(-40, peak)
        live.add_and_max_into(30, peak)
        assert live.value == 90 and peak.value == 100

    def test_histogram_stats(self):
        r = StatRegistry()
        h = r.histogram("h", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        s = h.snapshot()
        assert s["count"] == 4 and s["sum"] == 555.5
        assert s["min"] == 0.5 and s["max"] == 500.0
        assert s["avg"] == pytest.approx(138.875)
        cum = h.cumulative_buckets()
        assert cum == [(1.0, 1), (10.0, 2), (100.0, 3),
                       (float("inf"), 4)]

    def test_empty_histogram_snapshot(self):
        h = StatRegistry().histogram("h")
        assert h.snapshot() == {"count": 0, "sum": 0.0, "min": None,
                                "max": None, "avg": None}

    def test_same_name_same_object(self):
        r = StatRegistry()
        assert r.counter("x") is r.counter("x")

    def test_type_conflict_raises(self):
        r = StatRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_registry_snapshot_nested_and_empty(self):
        r = StatRegistry()
        assert r.snapshot() == {}
        r.counter("a").incr(3)
        r.gauge("b").set(7)
        r.histogram("c").observe(1.0)
        s = r.snapshot()
        assert s["counters"] == {"a": 3}
        assert s["gauges"] == {"b": 7}
        assert s["histograms"]["c"]["count"] == 1

    def test_reset_empties(self):
        r = StatRegistry()
        r.counter("a").incr()
        r.reset()
        assert len(r) == 0 and r.snapshot() == {}


class TestThreadSafety:
    def test_concurrent_counter_exact(self):
        r = StatRegistry()
        c = r.counter("n")

        def worker():
            for _ in range(2000):
                c.incr()

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 16000

    def test_concurrent_histogram_exact_count(self):
        r = StatRegistry()
        h = r.histogram("h")

        def worker(i):
            for k in range(500):
                h.observe(float(i * 500 + k))

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == 3000
        assert h.cumulative_buckets()[-1][1] == 3000

    def test_concurrent_create_same_metric(self):
        r = StatRegistry()
        got = []

        def worker():
            got.append(r.counter("shared"))

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert all(g is got[0] for g in got)


class TestExposition:
    def test_sanitize(self):
        assert sanitize_name("op.matmul.calls") == "op_matmul_calls"
        assert sanitize_name("9lives") == "_9lives"

    def test_prometheus_text(self):
        r = StatRegistry()
        r.counter("op.add.calls", "adds").incr(3)
        r.gauge("tensor.bytes.live").set(1024)
        r.histogram("lat.ms", buckets=(1.0, 10.0)).observe(5.0)
        from paddle_tpu.monitor.exposition import expose_text
        text = expose_text(r)
        assert "# HELP op_add_calls adds" in text
        assert "# TYPE op_add_calls counter" in text
        assert "op_add_calls 3" in text
        assert "# TYPE tensor_bytes_live gauge" in text
        assert "tensor_bytes_live 1024" in text
        assert "# TYPE lat_ms histogram" in text
        assert 'lat_ms_bucket{le="1"} 0' in text
        assert 'lat_ms_bucket{le="10"} 1' in text
        assert 'lat_ms_bucket{le="+Inf"} 1' in text
        assert "lat_ms_sum 5" in text
        assert "lat_ms_count 1" in text

    def test_module_expose_text(self, mon):
        monitor.counter("a.b").incr()
        assert "a_b 1" in monitor.expose_text()


class TestSnapshotDeterminism:
    def test_snapshots_identical_and_sorted(self):
        r = StatRegistry()
        for name in ("zeta", "alpha", "mid"):
            r.counter(name).incr()
        s1, s2 = r.snapshot(), r.snapshot()
        assert s1 == s2
        assert json.dumps(s1) == json.dumps(s2)
        assert list(s1["counters"]) == ["alpha", "mid", "zeta"]

    def test_dump_json_shape_and_file(self, mon, tmp_path):
        monitor.counter("x").incr(2)
        path = str(tmp_path / "m.json")
        payload = monitor.dump_json(run_id="r42", path=path)
        assert payload["run_id"] == "r42"
        assert payload["metrics"]["counters"]["x"] == 2
        assert json.load(open(path))["run_id"] == "r42"


class TestGatedHelpers:
    def test_off_path_is_noop(self):
        monitor.reset()
        assert not monitor.enabled()
        monitor.inc("nope")
        monitor.observe("nope.h", 1.0)
        monitor.set_gauge("nope.g", 5)
        monitor.record_op("add", 100)
        monitor.tensor_bytes(1024)
        assert monitor.snapshot() == {}

    def test_on_path_registers(self, mon):
        monitor.inc("yes", 2)
        monitor.observe("yes.h", 1.0)
        monitor.set_gauge("yes.g", 5)
        s = monitor.snapshot()
        assert s["counters"]["yes"] == 2
        assert s["gauges"]["yes.g"] == 5
        assert s["histograms"]["yes.h"]["count"] == 1

    def test_timed_context(self, mon):
        with monitor.timed("block.ms"):
            pass
        assert monitor.snapshot()["histograms"]["block.ms"]["count"] == 1


class TestOpDispatchInstrumentation:
    def test_eager_op_counts(self, mon):
        x = pt.to_tensor(np.ones((4, 4), "float32"))
        y = pt.to_tensor(np.ones((4, 4), "float32"))
        _ = x + y
        s = monitor.snapshot()
        assert s["counters"]["op.add.calls"] >= 1
        assert s["histograms"]["op.dispatch.wall_ns"]["count"] >= 1

    def test_flag_off_no_op_counters(self):
        monitor.reset()
        x = pt.to_tensor(np.ones((2,), "float32"))
        _ = x + x
        assert "counters" not in monitor.snapshot()


class TestTensorBytes:
    def _live(self):
        return monitor.snapshot().get("gauges", {}).get(
            "tensor.bytes.live", 0)

    def test_live_and_peak_track_construction(self, mon):
        before = self._live()
        t = pt.to_tensor(np.zeros((128, 128), "float32"))
        after = self._live()
        assert after - before >= 128 * 128 * 4
        peak = monitor.snapshot()["gauges"]["tensor.bytes.peak"]
        assert peak >= after
        del t
        gc.collect()
        assert self._live() < after

    def test_peak_survives_frees(self, mon):
        t = pt.to_tensor(np.zeros((256, 256), "float32"))
        peak = monitor.snapshot()["gauges"]["tensor.bytes.peak"]
        del t
        gc.collect()
        assert monitor.snapshot()["gauges"]["tensor.bytes.peak"] == peak

    def test_flag_flip_does_not_pin_live(self, mon):
        # a tensor counted while ON must still return its bytes when
        # freed after the flag goes OFF (asymmetric gating)
        t = pt.to_tensor(np.zeros((64, 64), "float32"))
        live_on = self._live()
        pt.set_flags({"FLAGS_enable_monitor": False})
        del t
        gc.collect()
        pt.set_flags({"FLAGS_enable_monitor": True})
        assert self._live() <= live_on - 64 * 64 * 4

    def test_reset_drops_straggler_frees(self, mon):
        # reset() with counted tensors alive: their later frees must
        # not resurrect the gauges at negative values
        t = pt.to_tensor(np.zeros((64, 64), "float32"))
        monitor.reset()
        del t
        gc.collect()
        assert "tensor.bytes.live" not in monitor.snapshot().get(
            "gauges", {})

    def test_straggler_free_cannot_corrupt_next_generation(self, mon):
        # reset() then a NEW allocation recreates the gauges; a
        # pre-reset tensor's free belongs to the old generation and
        # must not subtract from them (it would go negative)
        t1 = pt.to_tensor(np.zeros((256, 256), "float32"))
        monitor.reset()
        t2 = pt.to_tensor(np.zeros((8, 8), "float32"))
        del t1
        gc.collect()
        live = monitor.snapshot()["gauges"]["tensor.bytes.live"]
        assert live >= 8 * 8 * 4, live
        del t2


class TestDataLoaderInstrumentation:
    def test_batches_counted(self, mon):
        from paddle_tpu.io import DataLoader
        from paddle_tpu.io.dataset import TensorDataset
        xs = pt.to_tensor(np.arange(32, dtype="float32").reshape(16, 2))
        dl = DataLoader(TensorDataset([xs]), batch_size=4)
        n = sum(1 for _ in dl)
        s = monitor.snapshot()
        assert s["counters"]["dataloader.batches"] == n == 4
        assert s["histograms"]["dataloader.batch_interval_ms"]["count"] == 4
        assert s["gauges"]["dataloader.last_epoch_batches_per_sec"] > 0


class TestCollectiveInstrumentation:
    def test_compiled_collective_counts_at_trace(self, mon):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.distributed import comm_ops
        out = jax.vmap(lambda x: comm_ops.all_reduce(x, axis="i"),
                       axis_name="i")(jnp.ones((4, 2), jnp.float32))
        assert out.shape == (4, 2)
        s = monitor.snapshot()
        assert s["counters"]["dist.all_reduce.calls"] == 1
        assert s["counters"]["dist.all_reduce.bytes"] == 2 * 4

    def test_eager_collective_counts_per_call(self, mon):
        import paddle_tpu.distributed as dist
        t = pt.to_tensor(np.ones((8,), "float32"))
        dist.all_reduce(t)
        dist.all_reduce(t)
        s = monitor.snapshot()
        assert s["counters"]["dist.eager.all_reduce.calls"] == 2
        assert s["counters"]["dist.eager.all_reduce.bytes"] == 2 * 32


class TestJitCacheInstrumentation:
    def test_hit_miss_compile_latency(self, mon):
        import paddle_tpu.nn as nn
        from paddle_tpu import jit

        lin = nn.Linear(4, 4)

        @jit.to_static
        def f(x):
            return lin(x)

        x = pt.to_tensor(np.ones((2, 4), "float32"))
        with pt.no_grad():
            f(x)
            f(x)
            f(pt.to_tensor(np.ones((3, 4), "float32")))   # new signature
        s = monitor.snapshot()
        assert s["counters"]["jit.cache.miss"] == 2
        assert s["counters"]["jit.cache.hit"] == 1
        assert s["counters"]["jit.recompile"] == 1
        assert s["histograms"]["jit.compile_ms"]["count"] == 2


class TestAutotuneInstrumentation:
    def test_hit_and_miss_counted(self, mon, tmp_path):
        import jax.numpy as jnp

        from paddle_tpu.kernels import autotune as at
        cache = at.AutotuneCache(str(tmp_path / "c.json"))
        at.flash_blocks((2, 1024, 4, 128), (2, 1024, 2, 128),
                        jnp.bfloat16, True,
                        measure=lambda bq, bk: 1.0, cache=cache)
        at.flash_blocks((2, 1024, 4, 128), (2, 1024, 2, 128),
                        jnp.bfloat16, True,
                        measure=lambda bq, bk: 1.0, cache=cache)
        s = monitor.snapshot()
        assert s["counters"]["autotune.cache.miss"] == 1
        assert s["counters"]["autotune.cache.hit"] == 1
        assert s["counters"]["autotune.sweeps"] == 1


class TestAcceptance:
    def test_jitted_two_step_train_loop_snapshot(self, mon):
        """The ISSUE acceptance path: FLAGS_enable_monitor=1 + a jitted
        two-step train loop -> snapshot holds op-dispatch counters, jit
        cache hit/miss counts, and peak tensor bytes."""
        import paddle_tpu.nn as nn
        from paddle_tpu import jit
        from paddle_tpu.optimizer import SGD

        class LossNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(8, 8)

            def forward(self, x):
                return (self.lin(x) ** 2).mean()

        net = jit.to_static(LossNet())
        opt = SGD(learning_rate=0.01, parameters=net.parameters())
        x = pt.to_tensor(np.random.randn(4, 8).astype("float32"))
        for _ in range(2):
            loss = net(x)
            loss.backward()
            opt.step()
            opt.clear_grad()
        s = monitor.snapshot()
        op_counters = [k for k in s["counters"] if k.startswith("op.")
                       and k.endswith(".calls")]
        assert op_counters, s["counters"]
        assert s["counters"]["jit.cache.miss"] >= 1
        assert s["counters"]["jit.cache.hit"] >= 1
        assert s["gauges"]["tensor.bytes.peak"] > 0
        # and the whole thing round-trips through both expositions
        assert "jit_cache_miss" in monitor.expose_text()
        assert monitor.dump_json(run_id="t")["metrics"] == s


class TestOffPathGuard:
    def test_no_import_time_side_effects(self):
        """tier-1 guard (ISSUE satellite): with JAX_PLATFORMS=cpu and
        the flag unset, importing the package registers NOTHING —
        snapshot() is {} and the monitor reports disabled."""
        code = (
            "import paddle_tpu as pt\n"
            "from paddle_tpu import monitor\n"
            "assert not monitor.enabled()\n"
            "assert monitor.snapshot() == {}, monitor.snapshot()\n"
            "assert monitor.expose_text() == ''\n"
            "x = pt.to_tensor([1.0, 2.0]); _ = x + x\n"
            "assert monitor.snapshot() == {}, monitor.snapshot()\n"
            "print('GUARD_OK')\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("FLAGS_enable_monitor", None)
        out = subprocess.run(
            [sys.executable, "-c", code],
            env=env, capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        assert "GUARD_OK" in out.stdout
