"""bench.py failure semantics. Every failure is a non-zero exit after the
one JSON line: no TPU without ``--smoke``, anything ``_main`` raises (a
rung included — nothing between a rung and ``main`` catches). Watchdog: a
deadline expiring in a LATE stage (the MoE rung) emits the
already-measured headline number, not a zeroed run; before any
measurement it emits the failure record; either way the process exits 2.
Importing bench must not arm the watchdog or print anything."""
import importlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_bench(capsys):
    sys.modules.pop("bench", None)
    import bench
    importlib.reload(bench)
    assert capsys.readouterr().out == ""     # import is silent
    return bench


class TestWatchdogFire:
    def test_pre_measurement_fires_failure(self, capsys):
        b = _fresh_bench(capsys)
        b._STAGE["name"] = "init+compile"
        b._watchdog_fire()
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1
        p = json.loads(out[0])
        assert p["value"] == 0.0
        assert "init+compile" in p["error"]

    def test_post_measurement_emits_partial(self, capsys):
        b = _fresh_bench(capsys)
        b._STAGE["name"] = "moe-rung"
        b._PARTIAL["payload"] = {
            "metric": b._METRIC, "value": 123.4, "unit": "tokens/s",
            "vs_baseline": 0.5, "extra": {"mfu": 0.2}}
        b._watchdog_fire()
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1
        p = json.loads(out[0])
        assert p["value"] == 123.4                      # not zeroed
        assert "moe-rung" in p["extra"]["late_stage_timeout"]

    def test_emit_is_once_only(self, capsys):
        b = _fresh_bench(capsys)
        b._PARTIAL["payload"] = {"metric": b._METRIC, "value": 1.0,
                                 "unit": "tokens/s", "vs_baseline": 0.0}
        b._watchdog_fire()
        b._watchdog_fire()                              # second is a no-op
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1


class TestFailuresExitNonZero:
    def test_no_tpu_without_smoke_is_a_failure(self, capsys, monkeypatch):
        # the README's on-chip entry point must not substitute the CPU
        # under the device metric's name (tests run with no TPU)
        from paddle_tpu.core import compile_cache
        b = _fresh_bench(capsys)
        monkeypatch.setattr(sys, "argv", ["bench.py"])
        monkeypatch.setattr(b, "_arm_watchdog", lambda: None)
        monkeypatch.setattr(compile_cache, "enable_compile_cache",
                            lambda: "-")
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")  # main pins "cached"
        with pytest.raises(RuntimeError, match="no TPU"):
            b.main()
        p = json.loads(capsys.readouterr().out.strip())
        assert p["value"] == 0.0 and "no TPU" in p["error"]
        assert p["metric"] == "llama_train_tokens_per_sec_per_chip"

    def test_a_raising_rung_fails_the_run(self, capsys, monkeypatch):
        b = _fresh_bench(capsys)

        def rung():
            raise ValueError("Mosaic failed to compile")
        monkeypatch.setattr(b, "_main", rung)
        with pytest.raises(ValueError):
            b.main()                  # the script exits with the traceback
        p = json.loads(capsys.readouterr().out.strip())
        assert p["value"] == 0.0 and "Mosaic failed" in p["error"]

    def test_main_has_no_rung_level_error_entries(self):
        # a rung's failure used to become an "error" entry of a run that
        # exited 0; the only handler left is main()'s, which re-raises
        src = open(os.path.join(REPO, "bench.py")).read()
        body = src[src.index("def _main():"):src.index("def _decode_one_batch")]
        assert "except" not in body

    def test_a_raising_report_block_fails_the_run(self, capsys, monkeypatch):
        # an unknown TPU device_kind raises from the peak tables; the
        # report blocks used to turn that into a quiet "error" entry
        from paddle_tpu.monitor import roofline
        b = _fresh_bench(capsys)

        def unknown_kind(*a, **kw):
            raise KeyError("no peak-table entry for TPU device_kind")
        monkeypatch.setattr(roofline, "roofline_snapshot", unknown_kind)
        with pytest.raises(KeyError, match="peak-table"):
            b._roofline_block()
        # what is left guards only the printing of the failure line itself
        src = open(os.path.join(REPO, "bench.py")).read()
        assert "except Exception" not in src[src.index("def _fail("):]
