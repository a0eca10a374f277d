"""Platform selection, compile-cache placement (utils.platform) and the
chip smoke's CPU dry run.

The rule under test: an explicit CPU request pins the CPU; anything else
requires a TPU and exits non-zero with one line. Nothing probes, nothing
falls back."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace
from unittest import mock

import jax
import pytest

from flow_pipeline_tpu.utils import platform as plat

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _devices(platform):
    return lambda *a, **k: [SimpleNamespace(platform=platform,
                                            device_kind=platform)]


class TestCpuRequested:
    def test_only_cpu_counts(self):
        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": "cpu"}):
            assert plat.cpu_requested()
        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": "tpu,cpu"}):
            assert not plat.cpu_requested()  # priority list != cpu request
        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": "tpu"}):
            assert not plat.cpu_requested()


class TestSelectPlatform:
    @pytest.fixture(autouse=True)
    def cache(self):
        """select_platform also places the compile cache (its own tests
        are below); keep this process's jax config untouched."""
        with mock.patch.object(plat, "configure_compile_cache") as cache:
            yield cache

    def test_backend_flag_cpu_is_honoured(self, cache):
        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": ""}), \
                mock.patch.object(plat, "force_cpu") as pin, \
                mock.patch.object(jax, "devices", _devices("tpu")):
            assert plat.select_platform("cpu") == "cpu"
        pin.assert_called_once()
        cache.assert_called_once()  # placed before the first compile

    def test_env_cpu_request_is_honoured(self):
        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": "cpu"}), \
                mock.patch.object(plat, "force_cpu") as pin:
            assert plat.select_platform("tpu") == "cpu"
        pin.assert_called_once()

    def test_no_request_and_no_tpu_exits_with_one_line(self):
        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": ""}), \
                mock.patch.object(plat, "force_cpu") as pin, \
                mock.patch.object(jax, "devices", _devices("cpu")), \
                pytest.raises(SystemExit) as exc:
            plat.select_platform("tpu")
        pin.assert_not_called()  # never degrades to the CPU by itself
        reason = str(exc.value.code)  # a string code exits with status 1
        assert "a TPU is required" in reason and "is cpu" in reason
        assert "\n" not in reason

    def test_failed_backend_init_exits_too(self):
        def boom(*a, **k):
            raise RuntimeError("Unable to initialize backend 'tpu'\nno chip")

        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": "tpu,cpu"}), \
                mock.patch.object(jax, "devices", boom), \
                pytest.raises(SystemExit) as exc:
            plat.select_platform()
        assert "none (no chip)" in str(exc.value.code)

    def test_tpu_present_is_selected_without_pinning(self, cache):
        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": ""}), \
                mock.patch.object(plat, "force_cpu") as pin, \
                mock.patch.object(jax, "devices", _devices("tpu")):
            assert plat.select_platform("tpu") == "tpu"
        pin.assert_not_called()
        cache.assert_called_once()

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="tpu|cpu"):
            plat.select_platform("gpu")

    def test_cli_pipeline_exits_before_producing(self):
        from flow_pipeline_tpu import cli

        with mock.patch.dict(os.environ, {"JAX_PLATFORMS": ""}), \
                mock.patch.object(jax, "devices", _devices("cpu")), \
                mock.patch.object(cli, "_make_generator") as gen, \
                pytest.raises(SystemExit) as exc:
            cli.pipeline_main(["-produce.count", "10", "-metrics.addr", ""])
        gen.assert_not_called()
        assert "a TPU is required" in str(exc.value.code)


class TestCompileCache:
    def test_env_dir_set_means_code_sets_nothing(self):
        with mock.patch.dict(os.environ,
                             {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}), \
                mock.patch.object(jax.config, "update") as update:
            assert plat.configure_compile_cache() == "/some/dir"
        update.assert_not_called()

    def test_unset_means_the_fixed_in_checkout_path(self):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        with mock.patch.dict(os.environ, env, clear=True), \
                mock.patch.object(jax.config, "update") as update:
            got = plat.configure_compile_cache()
        # fixed: no temp name, pid or time can be part of the cache key
        assert got == os.path.join(ROOT, ".jax_cache")
        update.assert_called_once_with("jax_compilation_cache_dir", got)


class TestChipSmoke:
    def run(self, args, cwd=ROOT, script=None, **env):
        base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        return subprocess.run(
            [sys.executable, script or os.path.join(ROOT, "chip_smoke.py"),
             *args],
            cwd=cwd, env={**base, "JAX_PLATFORMS": "cpu", **env},
            capture_output=True, text=True, timeout=300)

    def test_tiny_runs_end_to_end_on_the_cpu(self, tmp_path):
        cache = tmp_path / "cache"
        out = self.run(["--tiny", "--out", str(tmp_path / "out")],
                       JAX_COMPILATION_CACHE_DIR=str(cache))
        assert out.returncode == 0, out.stderr[-2000:]
        recs = [json.loads(line) for line in out.stdout.splitlines()]
        assert recs[-1] == {"ok": True, "device": {
            "platform": "cpu", "kind": "cpu", "count": 1}}
        stages = {r["stage"]: r for r in recs[:-1]}
        assert list(stages) == ["native", "pipeline", "oracle", "cache",
                                "cms_kernels", "spread_kernels", "mesh4"]
        assert all(r["pass"] and r["platform"] == "cpu"
                   for r in stages.values())
        assert stages["pipeline"]["dataplane"] == "FusedPipeline"
        assert stages["oracle"]["flows_5m"]["bit_exact"]
        assert len(stages["oracle"]["windows"]) == 4
        assert stages["cms_kernels"]["bit_exact"]
        live = stages["cms_kernels"]["padding_leaves_the_scatter"]
        assert sorted(live) == ["all_distinct", "part_full", "zipf"]
        assert live["all_distinct"]["real"] == live["all_distinct"]["slots"]
        assert 0 < live["part_full"]["real"] < live["zipf"]["real"]
        assert all(set(forms) == {"every_slot", "ops_cms"}
                   for rec in live.values()
                   for forms in rec["ms_a_call"].values())
        # PR 47: the spread detectors' register update against its numpy
        # twin, both detectors, every row set and both register dtypes
        spread = stages["spread_kernels"]
        assert spread["bit_exact"] and spread["device_dtype"] == "int32"
        assert sorted(spread["detectors"]) == ["portscan", "superspreaders"]
        assert all(
            0 < rec["groups"] <= rec["rows"] and rec["registers_raised"]
            and set(rec["ms_a_call"]) == {
                f"{dtype}.{rows}" for dtype in ("int32", "uint8")
                for rows in ("groups", "every_row")}
            for rec in spread["detectors"].values())
        # one device: mesh4 says so instead of passing silently
        assert stages["mesh4"]["skipped"]
        assert "saw 1" in stages["mesh4"]["reason"]
        # the cache went where the environment said, and nowhere else
        assert stages["cache"]["compile_cache"]["dir"] == str(cache)
        assert stages["cache"]["step_cache_hit"] == \
            stages["cache"]["step_stored_by_pipeline_stage"]
        assert os.listdir(cache)

    def test_without_tiny_no_tpu_fails_and_prints_no_result(self, tmp_path):
        out = self.run(["--out", str(tmp_path)])
        assert out.returncode != 0
        assert out.stdout == ""
        assert "needs a TPU" in out.stderr

    def test_alone_in_a_directory_fails_and_prints_no_result(self, tmp_path):
        import shutil

        script = shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        out = self.run([], cwd=tmp_path, script=script)
        assert out.returncode != 0
        assert out.stdout == ""
