"""Tracing (tpu_ddp/utils/profiling.py): the tables against the call
sites, both ways; under a CPU profiler session tiny runs of
``ServeEngine``, ``LMTrainer`` and ``train_epoch`` yield every span with
its counts, nested as the tables say; with no session open the same runs
give bit-equal tokens and losses (a span is a no-op, a name is metadata).
"""

import glob
import os
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.models.transformer import make_transformer
from tpu_ddp.models.vgg import VGGModel
from tpu_ddp.parallel.mesh import make_mesh
from tpu_ddp.serve import ServeEngine
from tpu_ddp.train.engine import Trainer
from tpu_ddp.train.lm import LMTrainer, make_lm_batch
from tpu_ddp.utils import profiling
from tpu_ddp.utils.config import TrainConfig
from tpu_ddp.utils.profiling import (KERNELS, PROGRAMS, SCOPES, SPANS,
                                     profile_trace, span)

ROOT = Path(__file__).resolve().parents[1]
SOURCES = sorted((ROOT / "tpu_ddp").rglob("*.py"))
SPAN_CALL = re.compile(
    r"\b(?:span(?:ned)?|mark)\(\s*(?:[\w.]+,\s*)?([^\s,)]+)")
SERVE = sorted(n for n in SPANS if n.startswith("tpu_ddp.serve."))


def _calls(pattern, sources=SOURCES) -> dict:
    """{first match group: [files]} over the program's sources."""
    found: dict = {}
    for path in sources:
        text = path.read_text()
        if path.name == "profiling.py":
            text = text.split("\ndef mark(")[0]     # the tables, not the API
        for m in pattern.finditer(text):
            found.setdefault(m.group(1), []).append(path.name)
    return found


# ---- the tables against the tree, both ways ---------------------------------

def test_every_span_call_names_a_table_entry_and_every_entry_is_emitted():
    calls = _calls(SPAN_CALL)
    literal = {c.strip('"') for c in calls if c.startswith('"')}
    assert set(calls) == {f'"{n}"' for n in literal}, \
        f"span( with a name that is not a literal: {calls}"
    assert literal == set(SPANS)
    assert all(n.startswith(profiling.PREFIX) for n in SPANS)
    for layer, covers, counts in SPANS.values():
        assert layer and covers and isinstance(counts, tuple)


def test_every_kernel_and_scope_name_is_in_its_table_and_used():
    kernels = _calls(re.compile(r'^\s+name="(\w+)",$', re.M),
                     sorted((ROOT / "tpu_ddp/ops/pallas").glob("*.py")))
    assert set(kernels) == set(KERNELS)
    assert {f for fs in kernels.values() for f in fs} == {
        "flash_attention.py", "quant_matmul.py", "sgd.py", "bn_relu.py",
        "paged_attention.py", "ssm_state_step.py", "grouped_matmul.py",
        "sparse_prefill_attention.py"}
    pallas = sum(p.read_text().count("pl.pallas_call(")
                 for p in (ROOT / "tpu_ddp/ops/pallas").glob("*.py"))
    assert pallas == len(KERNELS) == 13
    scopes = _calls(re.compile(r'jax\.named_scope\("(\w+)"\)'))
    assert set(scopes) == set(SCOPES)


def test_the_gauges_of_the_table_are_those_the_engine_observes_and_printed():
    observed = _calls(re.compile(r'metrics\.observe\(\s*"(\w+)"'),
                      [ROOT / "tpu_ddp/serve/engine.py"])
    assert set(profiling.GAUGES) == set(observed)
    section = (ROOT / "docs" / "DESIGN.md").read_text().split(
        "Tracing", 1)[1]
    for name in profiling.GAUGES:
        assert f"`{name}`" in section, name


def test_every_program_name_is_used_once_per_builder():
    used = _calls(re.compile(r"@program\((\w+)\)"))
    constants = {k: v for k, v in vars(profiling).items()
                 if k.isupper() and isinstance(v, str) and v in PROGRAMS}
    assert set(used) == set(constants)
    assert set(constants.values()) == set(PROGRAMS)
    with pytest.raises(KeyError):
        profiling.program("step")


# ---- tiny runs --------------------------------------------------------------

def _lm():
    return make_transformer("TransformerLM-tiny", max_seq_len=64,
                            compute_dtype=jnp.float32)


def _serve(model, params, **kw):
    """Six requests through four slots, prompts of one to three chunks;
    every token and log-probability, and what the scheduler's lengths
    summed to at each decode step."""
    engine = ServeEngine(model, params, num_slots=4, block_size=8,
                         prefill_chunk=8, **kw)
    rng = np.random.default_rng(3)
    reqs = [engine.submit(rng.integers(0, 1024, size=n), 5, seed=i)
            for i, n in enumerate((5, 12, 20, 7, 9, 17))]
    lengths = []
    inner = engine._run_decode_step

    def recording(dslots, *args):
        # a slot whose last row is still unread reads one position more
        lengths.append(sum(engine.sched.slots[i].length
                           + engine.sched.slots[i].ahead for i in dslots))
        return inner(dslots, *args)

    engine._run_decode_step = recording
    steps = engine.run()
    return {"tokens": [list(r.tokens) for r in reqs],
            "logprobs": [list(r.logprobs) for r in reqs],
            "rids": [r.rid for r in reqs], "lengths": lengths,
            "steps": steps,
            "prompts": [int(r.prompt.size) for r in reqs]}


def _lm_losses(model):
    trainer = LMTrainer(model, make_mesh(jax.devices()[:1]))
    state = trainer.init_state(seed=2)
    rng = np.random.default_rng(4)
    losses = []
    for _ in range(3):
        x, y = trainer.put_batch(
            *make_lm_batch(rng.integers(0, 1024, size=(2, 33))))
        state, loss = trainer.train_step(state, x, y)
        losses.append(np.asarray(loss).tolist())
    return losses


def _epoch_losses(**cfg):
    model = VGGModel(name="tiny", cfg=(8, "M", 16, "M"),
                     compute_dtype=jnp.float32)
    trainer = Trainer(model, TrainConfig(log_every=1, **cfg),
                      strategy="none")
    rng = np.random.default_rng(6)
    batches = [(rng.normal(size=(8, 4, 4, 3)).astype(np.float32),
                rng.integers(0, 10, size=8).astype(np.int32))
               for _ in range(5)]
    lines = []
    trainer.train_epoch(trainer.init_state(), batches, log=lines.append)
    return [ln for ln in lines if "loss" in ln]


# name -> run(model, params): every instrumented path once
RUNS = {
    "serve": _serve,
    "spec": lambda m, p: _serve(m, p, spec_k=2, spec_draft="self-1"),
    "lm": lambda m, p: _lm_losses(m),
    "epoch": lambda m, p: _epoch_losses(),
    "epoch_multi": lambda m, p: _epoch_losses(steps_per_dispatch=2),
}


def _read_events(logdir, prefix) -> list:
    """[(name, start, end, counts)] of the host events whose name starts
    with ``prefix``, outermost first."""
    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines if plane.name == "/host:CPU" else ():
            out.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events if e.name.startswith(prefix))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The tiny runs twice: with no profiler session (which also pays
    the compiles), then inside one, each under a marker span."""
    model = _lm()
    params = model.init(jax.random.key(0))
    plain = {key: fn(model, params) for key, fn in RUNS.items()}
    logdir = str(tmp_path_factory.mktemp("trace"))
    traced = {}
    with profile_trace(logdir):
        for key, fn in RUNS.items():
            with jax.profiler.TraceAnnotation(f"test.{key}"):
                traced[key] = fn(model, params)
    spans = _read_events(logdir, profiling.PREFIX)
    by_run = {name[5:]: [s for s in spans if lo <= s[1] and s[2] <= hi]
              for name, lo, hi, _ in _read_events(logdir, "test.")}
    return {"plain": plain, "traced": traced, "spans": by_run}


@pytest.mark.parametrize("key", sorted(RUNS))
def test_tracing_changes_no_token_and_no_loss(runs, key):
    assert runs["traced"][key] == runs["plain"][key]
    assert runs["spans"][key], "the traced run recorded no span"


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_is_emitted_with_the_counts_of_the_table(runs, name):
    hits = [s for spans in runs["spans"].values() for s in spans
            if s[0] == name]
    assert hits, f"no run emitted {name}"
    counts = set(SPANS[name][2])
    seen = set()
    for _, start, end, stats in hits:
        # only ``dry`` is ever left out: at rest, or at a trainer's first
        assert counts - {"dry"} <= set(stats) <= counts and end >= start
        assert all(isinstance(v, (int, float)) for v in stats.values())
        seen |= set(stats)
    assert seen == counts


def _parent(spans, child):
    """The innermost span that encloses ``child``."""
    at = spans.index(child)
    around = [s for s in spans[:at]
              if s[1] <= child[1] and child[2] <= s[2]]
    return min(around, key=lambda s: s[2] - s[1]) if around else None


@pytest.mark.parametrize("key", ["serve", "spec"])
def test_serve_spans_nest_as_the_table_says(runs, key):
    spans = runs["spans"][key]
    want = {"tpu_ddp.serve.step": None,
            "tpu_ddp.serve.tally": None,
            "tpu_ddp.serve.schedule": "tpu_ddp.serve.step",
            "tpu_ddp.serve.admit": "tpu_ddp.serve.schedule",
            "tpu_ddp.serve.prefill": "tpu_ddp.serve.step",
            "tpu_ddp.serve.decode": "tpu_ddp.serve.step"}
    want.update({n: "tpu_ddp.serve.decode" for n in SERVE
                 if n.startswith("tpu_ddp.serve.decode.")})
    assert {s[0] for s in spans} == set(SERVE)
    harvest = ["tpu_ddp.serve.decode.fetch", "tpu_ddp.serve.decode.emit"]
    steps = [s for s in spans if s[0] == "tpu_ddp.serve.step"]
    for s in spans:
        parent = _parent(spans, s)
        if s[0] in harvest and parent[0] == "tpu_ddp.serve.step":
            # Read back outside a decode span: the first tokens of the
            # chunks before a speculative step's own body, and the plain
            # engine's last step, read in a step with no decode left.
            assert key != "serve" or parent is steps[-2]
            continue
        assert (parent[0] if parent else None) == want[s[0]], s
    assert len(steps) == runs["traced"][key]["steps"] + 1   # the idle one
    for s in steps:
        kids = [c[0] for c in spans if _parent(spans, c) is s]
        assert kids[0] == "tpu_ddp.serve.schedule"
        assert s[3] == {}
    for d in (s for s in spans if s[0] == "tpu_ddp.serve.decode"):
        kids = [c[0] for c in spans if _parent(spans, c) is d]
        assert kids[:2] == ["tpu_ddp.serve.decode.tables",
                            "tpu_ddp.serve.decode.dispatch"]
        # the plain engine reads the step BEFORE back, first tokens and
        # decode rows apart; its first step of all has nothing to read
        assert kids[2:] in ([], harvest, harvest * 2)
        assert kids[2:] or (key == "serve" and not d[3]["ahead"])


def test_a_request_shares_its_rid_between_admit_and_prefill(runs):
    spans, run = runs["spans"]["serve"], runs["traced"]["serve"]
    admits = {s[3]["rid"]: s[3] for s in spans
              if s[0] == "tpu_ddp.serve.admit"}
    assert sorted(admits) == sorted(run["rids"])
    for rid, prompt in zip(run["rids"], run["prompts"]):
        chunks = [s[3] for s in spans if s[0] == "tpu_ddp.serve.prefill"
                  and s[3]["rid"] == rid]
        assert admits[rid]["prompt_tokens"] == prompt
        assert admits[rid]["cached_tokens"] == 0
        assert admits[rid]["waited_ms"] >= 0
        assert sum(c["tokens"] for c in chunks) == prompt   # not padded
        assert [c["start"] for c in chunks] == list(range(0, prompt, 8))
        assert [c["final"] for c in chunks] == [0] * (len(chunks) - 1) + [1]


def test_context_tokens_are_the_schedulers_own_lengths(runs):
    spans, run = runs["spans"]["serve"], runs["traced"]["serve"]
    decodes = [s[3] for s in spans if s[0] == "tpu_ddp.serve.decode"]
    assert [d["context_tokens"] for d in decodes] == run["lengths"]


def test_trainer_spans_carry_their_steps(runs):
    lm = runs["spans"]["lm"]
    assert [s[0] for s in lm] == ["tpu_ddp.lm.put_batch",
                                  "tpu_ddp.lm.train_step"] * 3
    # the test reads each loss before the next step: the device is dry
    assert [s[3] for s in lm[1::2]] == [
        {"step": 0}, {"step": 1, "dry": 1}, {"step": 2, "dry": 1}]
    assert all(s[3] == {"tokens": 64} for s in lm[0::2])
    for key, dispatches in (("epoch", 5), ("epoch_multi", 3)):
        spans = runs["spans"][key]
        names = [s[0] for s in spans]
        assert names.count("tpu_ddp.train.data_next") == 6   # 5 + the end
        assert names.count("tpu_ddp.train.dispatch") == dispatches
        assert names.count("tpu_ddp.train.harvest") == dispatches
        assert names.count("tpu_ddp.train.put_batch") == dispatches
    single = [s[3] for s in runs["spans"]["epoch"]
              if s[0] == "tpu_ddp.train.dispatch"]
    assert single == [{"it": i, "step": i} for i in range(5)]


# ---- program names reach the compiled programs ------------------------------

def _lowered():
    model = _lm()
    params = model.init(jax.random.key(0))
    geo = dict(num_slots=4, block_size=8, prefill_chunk=8)
    engine = ServeEngine(model, params, **geo)
    spec = ServeEngine(model, params, spec_k=2, spec_draft="self-1", **geo)
    tiered = ServeEngine(model, params, kv_tiers=2, hbm_blocks=9, **geo)
    lm = LMTrainer(model, make_mesh(jax.devices()[:1]))
    state = lm.init_state()
    x, y = lm.put_batch(*make_lm_batch(np.zeros((2, 33), np.int64)))
    vgg = Trainer(VGGModel(name="tiny", cfg=(8, "M", 16, "M"),
                           compute_dtype=jnp.float32),
                  TrainConfig(), strategy="none")
    batch = vgg.put_batch(np.zeros((4, 4, 4, 3), np.float32),
                          np.zeros(4, np.int32))
    return {
        profiling.SERVE_DECODE: engine.lower_decode_step,
        profiling.SERVE_PREFILL: engine.lower_prefill_step,
        profiling.SERVE_SPEC: spec.lower_spec_step,
        profiling.SERVE_DECODE_TIERED: tiered.lower_tiered_decode_step,
        profiling.SERVE_PREFILL_TIERED: tiered.lower_tiered_prefill_step,
        profiling.LM_TRAIN_STEP: lambda: lm.lower_train_step(state, x, y),
        profiling.DDP_TRAIN_STEP:
        lambda: vgg.lower_train_step(vgg.init_state(), *batch),
        profiling.DDP_EVAL_STEP:
        lambda: vgg._eval_step.lower(vgg.init_state().params, *batch[:2]),
    }


@pytest.fixture(scope="module")
def lowered():
    return _lowered()


@pytest.mark.parametrize("name", [
    profiling.SERVE_DECODE, profiling.SERVE_PREFILL, profiling.SERVE_SPEC,
    profiling.SERVE_DECODE_TIERED, profiling.SERVE_PREFILL_TIERED,
    profiling.LM_TRAIN_STEP, profiling.DDP_TRAIN_STEP,
    profiling.DDP_EVAL_STEP])
def test_lowered_program_carries_its_name(lowered, name):
    text = lowered[name]().as_text()
    assert re.search(rf"module @jit_{name}\b", text), text[:200]


def test_scopes_reach_the_lowered_decode_step(lowered):
    text = lowered[profiling.SERVE_DECODE]().as_text(debug_info=True)
    for scope in ("embed", "attn/kv_write", "attn/kv_gather", "mlp", "head",
                  "sample"):
        assert f"jit({profiling.SERVE_DECODE})/{scope}/" in text, scope
    text = lowered[profiling.LM_TRAIN_STEP]().as_text(debug_info=True)
    for scope in ("loss", "optimizer", "grad_sync"):
        assert re.search(rf"[/(]{scope}[/)]", text), scope


# ---- the operator's page ----------------------------------------------------

def test_design_page_prints_the_tables():
    page = (ROOT / "docs" / "DESIGN.md").read_text()
    section = page.split("Tracing", 1)[1]
    for name in (*SPANS, *PROGRAMS, *KERNELS, *SCOPES,
                 "TPU_DDP_PROFILE_DIR", "--trace 1", "Perfetto"):
        assert f"`{name}`" in section or name in section, name
    for name, (_, _, counts) in SPANS.items():
        row = next(ln for ln in section.splitlines()
                   if ln.startswith(f"| `{name}`"))
        for count in counts:
            assert f"`{count}`" in row, (name, count)
    assert "Tracing" in (ROOT / "README.md").read_text()


def test_span_outside_a_session_is_a_noop():
    with span("tpu_ddp.serve.step"):
        pass
    profiling.mark("tpu_ddp.serve.tally", lambda: 1 / 0)   # never called
    assert list(profiling.spanned([1, 2, 3], "tpu_ddp.train.data_next")) \
        == [1, 2, 3]


# ---- bursts -----------------------------------------------------------------

def _step_span():
    return span("tpu_ddp.serve.step")


@pytest.mark.parametrize("n, kept", [
    (1, True), (profiling.BURST_STEPS, True),
    (profiling.BURST_STEPS + 1, False), (profiling.BURST_EVERY, False),
    (profiling.BURST_EVERY + 1, True),
    (profiling.BURST_EVERY + profiling.BURST_STEPS, True),
    (profiling.BURST_EVERY + profiling.BURST_STEPS + 1, False)])
def test_burst_keeps_the_first_steps_of_every_period(n, kept):
    with profiling.burst(n):
        inside = _step_span()
        with profiling.burst(1):        # an engine stepped inside another's
            assert isinstance(_step_span(), jax.profiler.TraceAnnotation)
        again = _step_span()
    for s in (inside, again):
        assert isinstance(s, jax.profiler.TraceAnnotation) == kept
    assert isinstance(_step_span(), jax.profiler.TraceAnnotation)


def test_burst_restores_the_thread_after_an_exception():
    with pytest.raises(RuntimeError):
        with profiling.burst(profiling.BURST_STEPS + 1):
            raise RuntimeError
    assert isinstance(_step_span(), jax.profiler.TraceAnnotation)


def test_the_tiny_runs_fit_in_one_burst(runs):
    """The span tests above see every step because their runs are shorter
    than a burst; if one grows past it, they miss spans for this reason."""
    for key in ("serve", "spec"):
        assert runs["traced"][key]["steps"] + 1 <= profiling.BURST_STEPS


def test_an_engine_step_is_annotated_whole_or_not_at_all(tmp_path):
    """Steps BURST_STEPS and BURST_EVERY + 1 leave all their spans, the
    steps after each of them none, and the tokens are those of a run with
    every step in a burst."""
    model = _lm()
    params = model.init(jax.random.key(0))
    prompt = np.random.default_rng(5).integers(0, 1024, size=12)

    calls = []

    def run(jump):
        engine = ServeEngine(model, params, num_slots=2, block_size=8,
                             prefill_chunk=8)
        req = engine.submit(prompt, 6, seed=0)
        inner = engine.step
        engine.step = lambda: calls.append(engine._step_n + 1) or inner()
        for at in jump:
            engine._step_n = at
            engine.step()
            engine.step()
        engine.run()
        return list(req.tokens)

    plain = run([0, 2])
    calls.clear()
    with profile_trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.jump"):
            traced = run([profiling.BURST_STEPS - 1, profiling.BURST_EVERY])
    assert traced == plain
    (_, lo, hi, _), = _read_events(str(tmp_path), "test.")
    spans = [s for s in _read_events(str(tmp_path), profiling.PREFIX)
             if lo <= s[1] and s[2] <= hi]
    steps = [s for s in spans if s[0] == "tpu_ddp.serve.step"]
    assert calls[:3] == [profiling.BURST_STEPS, profiling.BURST_STEPS + 1,
                         profiling.BURST_EVERY + 1]
    kept = [n for n in calls
            if (n - 1) % profiling.BURST_EVERY < profiling.BURST_STEPS]
    assert len(kept) == len(calls) - 1 and len(steps) == len(kept)
    # nothing outside a step span but the tally: a step left out leaves
    # no child either
    for s in spans:
        assert s[0] == "tpu_ddp.serve.tally" \
            or any(p[1] <= s[1] and s[2] <= p[2] for p in steps), s
    assert {s[0] for s in spans} >= {"tpu_ddp.serve.prefill",
                                     "tpu_ddp.serve.schedule"}


# ---- the tally, dry dispatch, host busy time --------------------------------

TALLY = "tpu_ddp.serve.tally"
# tally count -> (gauge or counter, what of it)
FROM_LOGGER = {
    "steps": ("serve_host_busy_ms", "count"),
    "decode_steps": ("serve_decode_rows", "count"),
    "decode_ahead": ("serve_decode_ahead", None),
    "dry_steps": ("serve_decode_dry", None),
    "decode_rows": ("serve_decode_rows", "total"),
    "context_tokens": ("serve_decode_context_tokens", "total"),
    "prefill_chunks": ("serve_prefill_tokens", "count"),
    "prefill_tokens": ("serve_prefill_tokens", "total"),
    "prefill_sparse_kernel_chunks": ("serve_prefill_sparse_kernel_chunks",
                                     "total"),
    "prefill_state_carried": ("serve_prefill_state_carried", "total"),
    "kv_blocks_in_use": ("serve_kv_blocks_in_use", "total"),
    "host_busy_ms": ("serve_host_busy_ms", "total"),
    "fetch_wait_ms": ("serve_fetch_wait_ms", "total"),
    "queue_depth": ("serve_queue_depth", "total"),
    "selected_tokens": ("serve_decode_selected_tokens", "total"),
    "index_scored_tokens": ("serve_decode_index_tokens", "total"),
}


def _logged(metrics) -> dict:
    """The tally's counts as read straight from ``metrics``."""
    out = {}
    for count, (name, what) in FROM_LOGGER.items():
        if what is None:
            out[count] = metrics.counters.get(name, 0)
        else:
            out[count] = metrics.gauges.get(name, {}).get(what, 0)
    return out


@pytest.fixture(scope="module")
def tallied(tmp_path_factory):
    """Six requests through four slots with a tally before every step
    (``TALLY_S`` 0), the logger read after each step, and the host's
    clock around each ``step()``; every step is in the first burst."""
    from tpu_ddp.serve import engine as engine_mod

    model = _lm()
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, num_slots=4, block_size=8,
                         prefill_chunk=8)
    rng = np.random.default_rng(3)
    for i, n in enumerate((5, 12, 20, 7, 9, 17)):
        engine.submit(rng.integers(0, 1024, size=n), 5, seed=i)
    after, walls = [_logged(engine.metrics)], []
    logdir = str(tmp_path_factory.mktemp("tally"))
    saved = engine_mod.TALLY_S
    engine_mod.TALLY_S = 0.0
    try:
        with profile_trace(logdir):
            more = True
            while more:
                t = time.perf_counter()
                more = engine.step()
                walls.append((time.perf_counter() - t) * 1e3)
                after.append(_logged(engine.metrics))
    finally:
        engine_mod.TALLY_S = saved
    assert engine._step_n < profiling.BURST_STEPS
    return {"spans": _read_events(logdir, profiling.PREFIX),
            "after": after, "walls": walls, "engine": engine}


def test_a_tally_opens_every_step_with_the_loggers_totals(tallied):
    spans, after = tallied["spans"], tallied["after"]
    tallies = [s[3] for s in spans if s[0] == TALLY]
    assert len(tallies) == len(after) - 1
    usable = tallied["engine"].pool.total_usable
    for k, tally in enumerate(tallies):
        assert tally.pop("kv_blocks_usable") == usable
        assert tally == pytest.approx(after[k], abs=1e-9)
    # so the difference of any two is what the logger summed between
    first, last = tallies[1], tallies[-1]
    for count in first:
        assert last[count] - first[count] == pytest.approx(
            after[len(tallies) - 1][count] - after[1][count], abs=1e-9)
    # six requests on four slots: two waited while the first four filled
    assert tallied["engine"].metrics.gauges["serve_queue_depth"]["max"] == 2


def test_the_tallys_differences_are_the_spans_sums(tallied):
    """Over every step between the first tally and the last, the counts
    the spans of those steps carry add up to the tallies' differences."""
    spans = tallied["spans"]
    marks = [s for s in spans if s[0] == TALLY]
    lo, hi = marks[0][1], marks[-1][1]
    inside = [s for s in spans if lo <= s[1] < hi]
    decodes = [s[3] for s in inside if s[0] == "tpu_ddp.serve.decode"]
    chunks = [s[3] for s in inside if s[0] == "tpu_ddp.serve.prefill"]
    diff = {k: marks[-1][3][k] - marks[0][3][k] for k in marks[0][3]}
    assert diff["steps"] == sum(s[0] == "tpu_ddp.serve.step" for s in inside)
    assert diff["decode_steps"] == len(decodes)
    assert diff["decode_ahead"] == sum(d["ahead"] for d in decodes)
    assert diff["dry_steps"] == sum(d.get("dry", 0) for d in decodes)
    assert diff["context_tokens"] == sum(d["context_tokens"] for d in decodes)
    assert diff["prefill_chunks"] == len(chunks)
    assert diff["prefill_tokens"] == sum(c["tokens"] for c in chunks)
    assert diff["kv_blocks_in_use"] > 0 and diff["queue_depth"] > 0


def test_host_busy_and_fetch_wait_split_the_steps_wall_time(tallied):
    engine, walls = tallied["engine"], tallied["walls"]
    busy = engine.metrics.gauges["serve_host_busy_ms"]
    fetch = engine.metrics.gauges["serve_fetch_wait_ms"]
    assert busy["count"] == fetch["count"] == len(walls)
    assert fetch["total"] > 0 and busy["total"] > 0
    # the engine's own clock sits inside the caller's
    assert busy["total"] + fetch["total"] <= sum(walls)
    assert busy["total"] + fetch["total"] >= 0.9 * sum(walls)


def test_the_tally_is_recorded_in_a_step_the_burst_leaves_out(tmp_path):
    model = _lm()
    engine = ServeEngine(model, model.init(jax.random.key(0)), num_slots=2,
                         block_size=8, prefill_chunk=8)
    engine.submit(np.arange(12), 3, seed=0)
    engine._step_n = profiling.BURST_STEPS      # the next one is muted
    with profile_trace(str(tmp_path)):
        engine.step()
        engine.step()
    spans = _read_events(str(tmp_path), profiling.PREFIX)
    assert [s[0] for s in spans] == [TALLY]
    assert spans[0][3]["steps"] == 0 and spans[0][2] >= spans[0][1]


def test_the_tally_costs_nothing_with_no_session_open():
    model = _lm()
    engine = ServeEngine(model, model.init(jax.random.key(0)), num_slots=2,
                         block_size=8, prefill_chunk=8)
    engine._tally = lambda: 1 / 0       # would raise if it were counted
    engine.submit(np.arange(12), 3, seed=0)
    assert engine.run() > 0


def test_dry_is_1_after_the_host_waited_out_the_step_before(tmp_path):
    """The host blocks until the step before is done, then steps: the
    decode span says dry = 1 and the counter agrees; the first decode
    step, dispatched at rest, has no dry at all."""
    model = _lm()
    engine = ServeEngine(model, model.init(jax.random.key(0)), num_slots=2,
                         block_size=8, prefill_chunk=8)
    engine.submit(np.arange(5), 6, seed=0)
    with profile_trace(str(tmp_path)):
        for _ in range(4):
            if engine._unread is not None and engine._unread.rows:
                jax.block_until_ready(engine._unread.out)
                time.sleep(0.01)
            engine.step()
    decodes = [s[3] for s in _read_events(str(tmp_path), profiling.PREFIX)
               if s[0] == "tpu_ddp.serve.decode"]
    assert decodes[0]["ahead"] == 0 and "dry" not in decodes[0]
    assert [d["dry"] for d in decodes[1:]] == [1, 1, 1]
    assert engine.metrics.counters["serve_decode_dry"] == 3
    assert engine.metrics.counters["serve_decode_ahead"] == 3


@pytest.mark.parametrize("ready, want", [(True, 1), (False, 0)])
def test_lm_train_step_says_whether_the_step_before_was_done(
        tmp_path, ready, want):
    from types import SimpleNamespace

    trainer = LMTrainer(_lm(), make_mesh(jax.devices()[:1]))
    state = trainer.init_state(seed=2)
    x, y = trainer.put_batch(*make_lm_batch(np.zeros((2, 33), np.int64)))
    with profile_trace(str(tmp_path)):
        state, loss = trainer.train_step(state, x, y)
        jax.block_until_ready(loss)
        trainer._last_loss = SimpleNamespace(is_ready=lambda: ready)
        trainer.train_step(state, x, y)
    steps = [s[3] for s in _read_events(str(tmp_path), profiling.PREFIX)
             if s[0] == "tpu_ddp.lm.train_step"]
    assert steps == [{"step": 0}, {"step": 1, "dry": want}]
