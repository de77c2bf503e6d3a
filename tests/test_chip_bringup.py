"""What the chip bring-up added, as far as a CPU can check it: where the
compile cache goes, that an unknown device has no bandwidth number, that
the launcher refuses several TPU processes, that ``bench.py`` and
``chip_smoke.py`` fail loudly instead of degrading."""

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record ``jax.config.update`` calls instead of applying them
        (conftest already configured this process's cache)."""
        from tpu_ddp.utils import compile_cache
        seen = []
        monkeypatch.setattr(compile_cache.jax.config, "update",
                            lambda k, v: seen.append((k, v)))
        return seen

    def test_env_dir_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                     updates, tmp_path):
        from tpu_ddp.utils.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert updates == []

    def test_default_is_one_fixed_path_under_the_checkout(
            self, monkeypatch, updates):
        from tpu_ddp.utils.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = enable_compile_cache()
        assert first == str(REPO / ".jax_cache")
        assert enable_compile_cache() == first  # no pid, time or tempfile
        assert updates == [("jax_compilation_cache_dir", first)] * 2

    def test_cache_dir_is_git_ignored(self):
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


class TestDeviceHbmGbps:
    @pytest.fixture(autouse=True)
    def _no_override(self, monkeypatch):
        monkeypatch.delenv("TPU_DDP_HBM_GBPS", raising=False)

    def test_known_kind(self):
        from tpu_ddp.utils.flops import device_hbm_gbps
        dev = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")
        assert device_hbm_gbps(dev) == (819.0, "device_kind 'TPU v5 lite'")

    @pytest.mark.parametrize("dev", [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v99"),
        types.SimpleNamespace(platform="cpu", device_kind="cpu"),
    ])
    def test_unknown_device_has_no_number(self, dev):
        from tpu_ddp.utils.flops import device_hbm_gbps
        value, reason = device_hbm_gbps(dev)
        assert value is None
        assert dev.device_kind in reason or dev.platform in reason

    def test_malformed_override_raises(self, monkeypatch):
        from tpu_ddp.utils.flops import device_hbm_gbps
        monkeypatch.setenv("TPU_DDP_HBM_GBPS", "8l9")
        with pytest.raises(ValueError, match="TPU_DDP_HBM_GBPS"):
            device_hbm_gbps(jax.devices()[0])

    def test_override(self, monkeypatch):
        from tpu_ddp.utils.flops import device_hbm_gbps
        monkeypatch.setenv("TPU_DDP_HBM_GBPS", "1000")
        assert device_hbm_gbps(jax.devices()[0]) == (
            1000.0, "env:TPU_DDP_HBM_GBPS")


def test_launcher_refuses_several_tpu_processes(monkeypatch):
    """platform='tpu' with nproc > 1 is refused before anything is
    spawned: nothing binds a child to one chip."""
    from tpu_ddp import launch as launch_mod

    def no_spawn(*a, **k):
        raise AssertionError("launch() spawned a process")

    monkeypatch.setattr(launch_mod.subprocess, "Popen", no_spawn)
    with pytest.raises(ValueError, match="nproc=1"):
        launch_mod.launch("part3", nproc=4, platform="tpu")
    with pytest.raises(ValueError, match="nproc=1"):
        launch_mod.launch_elastic("part3", nproc=2, platform="tpu",
                                  max_restarts=1)


class TestBenchExitCode:
    RESULT = {"metric": "m", "value": 1.0, "unit": "u",
              "vs_baseline": None, "extra": {"mfu": None, "configs": {}}}

    def _cli(self, monkeypatch, tmp_path, result):
        import bench
        monkeypatch.setattr(bench, "main", lambda: result)
        # cli() writes experiments/bench_full.json beside the module.
        monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        return bench.cli()

    def test_clean_run_exits_zero(self, monkeypatch, tmp_path, capsys):
        assert self._cli(monkeypatch, tmp_path, self.RESULT) == 0
        assert capsys.readouterr().err == ""

    def test_errored_sub_cell_exits_nonzero_and_is_listed(
            self, monkeypatch, tmp_path, capsys):
        import bench

        def boom():
            raise RuntimeError("out of memory")

        result = {**self.RESULT, "extra": {
            "mfu": None,
            "configs": {"resnet50_imagenet": bench._sub(boom)}}}
        assert self._cli(monkeypatch, tmp_path, result) == 1
        captured = capsys.readouterr()
        assert ("extra.configs.resnet50_imagenet: RuntimeError: "
                "out of memory") in captured.err
        # The headline line still prints for the cells that ran.
        assert '"metric": "m"' in captured.out


def test_chip_smoke_refuses_without_tpu_platform():
    """JAX_PLATFORMS=cpu: non-zero exit before any phase, no result on
    stdout (a CPU run must never look like a chip run)."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "JAX_PLATFORMS='cpu' does not name 'tpu'" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    """A directory that holds ``chip_smoke.py`` and nothing else of the
    repo: non-zero exit, nothing on stdout, whatever the platform."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no tpu_ddp package" in proc.stderr


class TestChipSmokeOutput:
    """``main()`` with the device and the phases stubbed (the CPU cannot
    run them): what it prints last and what it exits with."""
    P0 = {"platform": "tpu", "device_kind": "TPU v5 lite", "devices": 1,
          "device_order": [], "compile_cache_dir": "x"}

    @pytest.fixture
    def smoke(self, monkeypatch, tmp_path):
        monkeypatch.syspath_prepend(str(REPO))
        import chip_smoke
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # main() pins it
        monkeypatch.setattr(chip_smoke, "OUT_DIR", tmp_path)
        monkeypatch.setattr(chip_smoke, "p0_device", lambda: dict(self.P0))
        monkeypatch.setattr(chip_smoke, "p0_native", dict)
        return chip_smoke

    def test_last_line_has_exactly_the_drivers_keys(self, smoke,
                                                    monkeypatch, capsys):
        monkeypatch.setattr(smoke, "p2_kernels", lambda: {"compile_s": 1.0})
        assert smoke.main(["P2"]) == 0
        lines = capsys.readouterr().out.splitlines()
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1}}
        summary = json.loads(lines[-2])
        assert summary["failed"] == [] and summary["claim"] is None
        assert lines[-2].endswith('"claim": null}')
        assert [json.loads(ln)["phase"] for ln in lines[:-2]] == ["P0", "P2"]

    def test_failed_phase_is_listed_and_exits_nonzero(self, smoke,
                                                      monkeypatch, capsys):
        def boom():
            raise AssertionError("loss did not fall")

        monkeypatch.setattr(smoke, "p3_lm_trainer", boom)
        monkeypatch.setattr(smoke, "p5_four_chips",
                            lambda: {"skipped": "1 device"})
        assert smoke.main(["P3", "P5"]) == 1
        lines = capsys.readouterr().out.splitlines()
        last = json.loads(lines[-1])
        assert set(last) == {"ok", "device"} and last["ok"] is False
        assert json.loads(lines[-2])["failed"] == ["P3"]
