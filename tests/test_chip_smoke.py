"""chip_smoke.py off the chip: its two phases run at ``llama_tiny`` on the
CPU (kernels give way to their references there, and the counters say
so), the script itself refuses to run without a TPU, a failing phase is a
failing run, and the compile cache goes where the contract says."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import chip_smoke
from paddle_tpu import kernels
from paddle_tpu.core import compile_cache
from paddle_tpu.models import llama as L

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPhases:
    def test_trainer_one_device_and_mesh_agree(self):
        # the dispatchers are (re)installed explicitly: a test file this
        # worker ran before may have left them unregistered, and then
        # nothing counts ``flash_fallback`` (ROADMAP D7)
        kernels.register()
        cfg = L.llama_tiny()
        one = chip_smoke.train_phase(cfg, batch=4, seq=16, steps=2)
        assert one["losses"][-1] < one["losses"][0]
        # the blockwise CE is pure jnp and engages anywhere; the Pallas
        # flash kernel does not run off the chip, and is counted as such
        assert one["dispatch"]["fused_ce"] >= 1
        assert one["dispatch"]["flash_fallback"] >= 1
        assert "flash" not in one["dispatch"]

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 2, 2),
                    ("dp", "fsdp", "tp"))
        four = chip_smoke.train_phase(cfg, batch=4, seq=16, steps=2,
                                      mesh=mesh)
        assert "sharded" in four["placement"]
        assert abs(four["losses"][0] - one["losses"][0]) < 1e-4

    def test_trainer_rejects_a_wrong_first_loss(self, monkeypatch):
        monkeypatch.setattr(L, "make_train_step", lambda *a, **k: (
            lambda p, o, b: (p, o, jax.numpy.float32(0.5))))
        with pytest.raises(AssertionError, match="not near ln"):
            chip_smoke.train_phase(L.llama_tiny(), batch=2, seq=16)

    def test_server_answers_and_checks_attention(self):
        from paddle_tpu.inference import paged

        # counted once a trace of the inner jit: forget an earlier test's
        paged._kv_token_write.clear_cache()
        out = chip_smoke.serve_phase(
            L.llama_tiny(), prompt_lens=(20, 9, 30, 12),
            new_tokens=(6, 4, 8, 5), num_slots=2, page_size=8)
        assert out["requests"] == 8
        assert out["buckets"] == [16, 32]
        assert out["attn_err"] <= chip_smoke.ATTN_TOL
        # off the chip the decode attention is the reference, and counted
        assert out["dispatch"]["paged_fallback"] >= 1
        assert "paged" not in out["dispatch"]
        # and the decode step's KV write is the two scatters, counted
        assert out["dispatch"]["kv_write_fallback"] >= 1
        assert "kv_write" not in out["dispatch"]


class TestNoOffChipMode:
    def test_script_exits_nonzero_without_a_tpu(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            capture_output=True, text=True, timeout=300, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode != 0
        assert "no TPU" in r.stderr
        assert '"ok"' not in r.stdout

    @pytest.fixture(autouse=True)
    def _autotune_mode(self, monkeypatch):
        # main() pins the process to the never-measure mode; undo it
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")

    def test_a_failing_phase_fails_the_run(self, monkeypatch, capsys):
        # past the device gate, nothing catches what a phase raises
        monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "-")
        monkeypatch.setattr(chip_smoke, "require_tpu",
                            lambda: jax.devices()[0])
        monkeypatch.setattr(
            chip_smoke.roofline, "resolve_peaks",
            lambda dev: {"peak_flops_per_sec": 1.0, "flops_source": "table",
                         "peak_hbm_bytes_per_sec": 1.0,
                         "hbm_source": "table"})

        def boom(*a, **k):
            raise RuntimeError("phase failed")
        monkeypatch.setattr(chip_smoke, "train_phase", boom)
        with pytest.raises(RuntimeError, match="phase failed"):
            chip_smoke.main([])
        assert '"ok"' not in capsys.readouterr().out

    def test_peaks_that_are_not_a_table_hit_fail_the_run(self, monkeypatch):
        monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "-")
        monkeypatch.setattr(chip_smoke, "require_tpu",
                            lambda: jax.devices()[0])
        with pytest.raises(AssertionError, match="not a table hit"):
            chip_smoke.main([])       # a CPU resolves to "nominal"


class TestCompileCacheHelper:
    @pytest.fixture(autouse=True)
    def _restore(self):
        old = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", old)

    def test_placed_from_outside_sets_nothing(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want

    def test_cache_dir_is_ignored_by_git(self):
        ignored = open(os.path.join(REPO, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


def test_autotune_default_path_is_the_tracked_file(monkeypatch):
    from paddle_tpu.kernels import autotune
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE_CACHE", raising=False)
    path = autotune._cache_path()
    assert path == os.path.join(REPO, "autotune_cache.json")
    json.load(open(path))            # tracked, and valid JSON
