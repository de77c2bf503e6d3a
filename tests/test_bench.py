"""bench.py — the driver's benchmark entry point.

Guards the contract the driver depends on: every config produces one
dict with metric/value/unit/vs_baseline, shrunk to smoke size here
(real numbers come from the TPU run).
"""

import numpy as np
import pytest

import bench


class TestBenchEntry:
    @pytest.mark.slow  # full bench entrypoint run; the config plumbing is
    # covered fast by test_lm_config
    def test_headline_vgg_contract(self):
        # with_xla_flops=False skips the AOT cost-analysis recompile
        # (seconds on this host); the xla-flops path has its own test
        # below on the tiniest config.
        out = bench.run_bench(batch_size=8, timed_iters=2,
                              config="vgg11_cifar10",
                              with_xla_flops=False, end_to_end_iters=1)
        assert out["metric"] == "cifar10_vgg11_images_per_sec_per_chip"
        assert out["unit"] == "images/sec"
        assert out["value"] > 0 and np.isfinite(out["value"])
        # Tolerance, not equality: value is rounded to 0.1 before this
        # check while vs_baseline was rounded from the unrounded rate.
        assert abs(out["vs_baseline"] - out["value"] / 386.0) < 0.01
        assert out["extra"]["timed_iters"] == 2

    @pytest.mark.slow  # ViT compile: model correctness lives in test_vit
    def test_vit_config(self):
        out = bench.run_bench(batch_size=8, timed_iters=2,
                              config="vit_cifar10",
                              with_xla_flops=False, end_to_end_iters=1)
        assert out["metric"] == "cifar10_vit-tiny_images_per_sec_per_chip"
        assert out["vs_baseline"] is None  # no reference number exists
        assert out["value"] > 0

    def test_lm_config(self):
        # The ONE test that keeps with_xla_flops on (AOT cost-analysis
        # cross-check) — tiniest config, so the extra compile is cheap.
        out = bench.run_lm_bench(batch_size=2, seq_len=64, timed_iters=2,
                                 with_decode=False,
                                 model_name="TransformerLM-tiny")
        assert out["metric"] == "transformer_lm_tokens_per_sec_per_chip"
        assert out["unit"] == "tokens/sec"
        assert out["value"] > 0 and np.isfinite(out["value"])

    def test_unknown_config_rejected(self):
        with pytest.raises(ValueError, match="unknown preset"):
            bench.run_bench(config="resnet9000")

    @pytest.mark.slow  # another full bench run just to read two fields
    def test_mfu_fields_present(self, monkeypatch):
        monkeypatch.delenv("TPU_DDP_PEAK_TFLOPS", raising=False)
        out = bench.run_bench(batch_size=4, timed_iters=1,
                              config="vgg11_cifar10",
                              with_xla_flops=False, end_to_end_iters=1)
        ex = out["extra"]
        # Analytic model FLOPs: VGG-11 on 32x32 is ~153M MACs fwd/img
        # (~306 MFLOPs), train = 3x fwd.
        per_img_fwd = ex["flops_per_step"] / 3 / 4
        assert 2.5e8 < per_img_fwd < 3.5e8
        assert ex["flops_source"] == "analytic"
        assert ex["achieved_tflops"] > 0
        # CPU platform: no peak table -> mfu is null, never a wrong number.
        assert ex["mfu"] is None and ex["peak_tflops_bf16"] is None

    # test_lm_config runs the same bench entry fast; this repeats it
    # only to read the peak-flops override out of the report.
    @pytest.mark.slow
    def test_mfu_env_peak_override(self, monkeypatch):
        monkeypatch.setenv("TPU_DDP_PEAK_TFLOPS", "100")
        out = bench.run_lm_bench(batch_size=2, seq_len=64, timed_iters=1,
                                 with_xla_flops=False, with_decode=False,
                                 model_name="TransformerLM-tiny")
        ex = out["extra"]
        assert ex["peak_tflops_bf16"] == 100.0
        # Both fields are rounded (3 and 4 decimals) before comparison;
        # on CPU the values are tiny, so tolerate the rounding error.
        assert ex["mfu"] == pytest.approx(
            ex["achieved_tflops"] / 100.0, abs=2e-4)

    @pytest.mark.slow  # scan-of-4 VGG compile: minutes on 1 CPU core
    def test_multi_step_recorded_for_headline(self):
        """timed_iters >= 4 triggers the scan-of-k sub-measurement on
        the headline config (k = min(16, timed_iters), so tests compile
        a short scan); its throughput field must be present/positive."""
        out = bench.run_bench(batch_size=8, timed_iters=4,
                              config="vgg11_cifar10", end_to_end_iters=1,
                              with_xla_flops=False)
        ms = out["extra"].get("multi_step")
        assert ms is not None
        assert ms["steps_per_call"] == 4
        assert ms["images_per_sec"] > 0

    @pytest.mark.slow  # decode-scan compile: minutes on 1 CPU core
    def test_lm_decode_recorded(self):
        out = bench.run_lm_bench(batch_size=2, seq_len=512, timed_iters=1)
        dec = out["extra"].get("decode")
        assert dec is not None and "error" not in dec
        assert dec["tokens_per_sec"] > 0

    def test_compact_headline_shape(self):
        """The driver parses exactly one stdout line; it must stay small
        and carry headline + MFU (round-2 truncation regression)."""
        import json
        result = {
            "metric": "cifar10_vgg11_images_per_sec_per_chip",
            "value": 72614.0, "unit": "images/sec", "vs_baseline": 188.1,
            "extra": {
                "mfu": 0.2667,
                "batch_sweep": {"2048": {"images_per_sec": 1.0,
                                         "mfu": 0.3379},
                                "4096": {"error": "OOM"}},
                "configs": {
                    "resnet50_imagenet": {"extra": {"mfu": 0.2685}},
                    "transformer_lm": {"extra": {"mfu": 0.2744}},
                    "transformer_lm_large": {"error": "boom"},
                },
            },
        }
        c = bench.compact_headline(result)
        assert c["metric"] == result["metric"]
        assert c["value"] == result["value"]
        assert c["vs_baseline"] == result["vs_baseline"]
        assert c["mfu"] == 0.2667
        # best vgg MFU comes from the sweep; best overall across families
        assert c["mfu_by_family"]["vgg11"] == 0.3379
        assert c["best_mfu"] == 0.3379
        # errors in sweep/configs never break the compact line
        line = json.dumps(c)
        assert len(line) < 1000  # must stay within driver tail capture

    def test_dispatch_depth_sweep_smoke(self):
        """The round-6 acceptance gate at smoke scale, its
        deterministic claim: the async window (depth 2) forces fewer
        host syncs than the synchronous loop (depth 0). What it buys
        in host gap and steps a second is a rate, which a CPU shared
        with other test workers cannot judge: the chip's ladder cell
        (ROADMAP W2) is where that is read."""
        import jax.numpy as jnp

        from tpu_ddp.models.vgg import VGGModel
        from tpu_ddp.train.engine import Trainer
        from tpu_ddp.train.pipeline import depth_sweep
        from tpu_ddp.utils.config import TrainConfig

        model = VGGModel(name="tiny", cfg=(8, "M", 16, "M"),
                         compute_dtype=jnp.float32)
        trainer = Trainer(model, TrainConfig(), strategy="none")
        state = trainer.init_state()
        rng = np.random.default_rng(0)
        batches = [(rng.normal(size=(32, 4, 4, 3)).astype(np.float32),
                    rng.integers(0, 10, size=32).astype(np.int32))
                   for _ in range(10)]
        # Warm-up epoch: compile outside the timed sweep.
        state, _ = trainer.train_epoch(state, list(batches),
                                       log=lambda s: None)
        res, state = depth_sweep(trainer, state, batches, (0, 2), reps=1)
        d0, d2 = res["0"], res["2"]
        assert d2["forced_syncs"] < d0["forced_syncs"]

    def test_collectives_bench_shape(self):
        out = bench.run_collectives_bench(mb=0.5, iters=2)
        # 8-device virtual mesh in tests -> real results, not skipped.
        assert out["devices"] == 8
        assert set(out["results"]) == {"psum", "psum_scatter", "all_gather",
                                       "ppermute", "all_to_all"}
        for r in out["results"].values():
            assert r["ms"] > 0 and r["gbps"] > 0
