"""Metrics JSONL sink + profiler hooks (the reference's observability is
print-only, SURVEY.md §5 — these are framework-native extensions)."""

import json

import jax.numpy as jnp
import numpy as np

from tpu_ddp.models import get_model
from tpu_ddp.train.engine import Trainer
from tpu_ddp.utils.config import TrainConfig
from tpu_ddp.utils.metrics import MetricsLogger, from_env
from tpu_ddp.utils.profiling import profile_trace, span


class TestMetricsLogger:
    def test_writes_jsonl(self, tmp_path):
        p = tmp_path / "m.jsonl"
        with MetricsLogger(str(p), rank=3) as m:
            assert m.enabled
            m.log("train_iter", step=1, loss=2.5)
            m.log("eval", test_loss=2.1)
        lines = [json.loads(l) for l in p.read_text().splitlines()]
        assert len(lines) == 2
        assert lines[0]["event"] == "train_iter"
        assert lines[0]["rank"] == 3
        assert lines[0]["loss"] == 2.5
        assert "ts" in lines[0]

    def test_disabled_is_noop(self):
        m = MetricsLogger(None)
        assert not m.enabled
        m.log("anything", x=1)  # must not raise
        m.close()

    def test_from_env_rank_expansion(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPU_DDP_METRICS_FILE",
                           str(tmp_path / "r{rank}.jsonl"))
        m = from_env(rank=2)
        m.log("e")
        m.close()
        assert (tmp_path / "r2.jsonl").exists()

    def test_trainer_emits_metrics(self, tmp_path):
        p = tmp_path / "train.jsonl"
        cfg = TrainConfig(global_batch_size=8, log_every=1, max_iters=2)
        model = get_model("VGG11", compute_dtype=jnp.float32)
        tr = Trainer(model, cfg, strategy="none",
                     metrics=MetricsLogger(str(p)))
        rng = np.random.default_rng(0)
        batches = [(rng.normal(size=(8, 32, 32, 3)).astype(np.float32),
                    (np.arange(8) % 10).astype(np.int32))
                   for _ in range(2)]
        state = tr.init_state()
        state, _ = tr.train_epoch(state, batches, epoch=0)
        tr.evaluate(state, batches)
        events = [json.loads(l)["event"] for l in p.read_text().splitlines()]
        assert events.count("train_iter") == 2
        assert "epoch" in events
        assert "eval" in events


class TestProfiling:
    def test_noop_without_logdir(self):
        with profile_trace(None):
            pass  # must not raise

    def test_trace_writes_files(self, tmp_path):
        d = str(tmp_path / "prof")
        with profile_trace(d):
            with span("tpu_ddp.lm.train_step", step=7):
                _ = jnp.sum(jnp.arange(16.0))
        import os
        found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
        assert found, "profiler produced no trace files"
        # The span and its count come back from the written trace.
        import jax
        data = jax.profiler.ProfileData.from_file(
            next(f for f in found if f.endswith(".xplane.pb")))
        events = [e for plane in data.planes for line in plane.lines
                  for e in line.events if e.name == "tpu_ddp.lm.train_step"]
        assert [dict(e.stats) for e in events] == [{"step": 7}]
