"""A decoder whose attention reads a learned selection of its cached
positions (``SparseMoeLM``: a lightning indexer keeps the top-k positions
per query over a paged index-key pool, beside a dropless expert layer that
holds a share of its experts and has no shared expert) against the plain
reference (benchmark/reference/keye_vl2.py), on the CPU at a small size
with seeded random weights and float32 compute: log-probabilities and the
selected positions through chunked prefill and decode, dense attention
within the top-k, the share test, the index-key pages across block
boundaries and beside what the null block holds, every serving feature
that refuses such a model, and the shared MLP that a hybrid model still
traces.
"""

import functools
import itertools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import keye_vl2 as reference  # noqa: E402
from tpu_ddp.models import decode, sparse_moe  # noqa: E402
from tpu_ddp.models.hybrid import HybridLM  # noqa: E402
from tpu_ddp.models.sparse_moe import SparseMoeLM  # noqa: E402
from tpu_ddp.parallel.moe import dropless_moe  # noqa: E402
from tpu_ddp.serve import ServeEngine  # noqa: E402
from tpu_ddp.serve import engine as engine_mod  # noqa: E402
from tpu_ddp.serve.kv_pool import PagedKVPool  # noqa: E402

# blocks of 4 under chunks of 16: every chunk and most decode steps cross
# a page boundary
GEOM = dict(num_slots=3, block_size=4, prefill_chunk=16)
TOL = 2e-5      # float32 on both sides: only the order of sums differs
TOPK = 16


def _model(**kw):
    cfg = dict(vocab_size=512, num_layers=2, num_heads=4, num_kv_heads=2,
               head_dim_=16, d_model=64, d_ff=32, num_experts=16, top_k=4,
               held=(0, 8), index_heads=4, index_head_dim=8,
               index_rope_dim=4, index_topk=TOPK, max_seq_len=96,
               compute_dtype=jnp.float32)
    cfg.update(kw)
    return SparseMoeLM(**cfg)


def _cfg(model, topk=None) -> dict:
    """The reference's side of ``model``: the published keys it reads."""
    return {"rms_norm_eps": model.norm_eps, "rope_theta": model.rope_theta,
            "index_rope_dim": model.index_rope_dim,
            "sa_config": {"topk": topk or model.index_topk},
            "held_experts": list(model.held),
            "num_experts_per_tok": model.top_k}


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(7))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 512, size=n)


def _worst(model, params, prompt, req, topk=None) -> float:
    """Largest difference between the log-probabilities the engine
    reported for ``req`` and the reference's full forward pass."""
    full = np.concatenate([prompt, req.tokens]).astype(np.int32)
    ref = reference.log_probs(params, jnp.asarray(full[:-1]),
                              _cfg(model, topk))
    at = np.arange(len(prompt) - 1, len(full) - 1)
    return float(np.max(np.abs(ref[at, full[at + 1]]
                               - np.asarray(req.logprobs))))


# Prompts of 40-70 tokens (three to five chunks, the last padded), so the
# selection of 16 bites in prefill and in decode; five requests on three
# slots, so slots turn over and a prompt is between two chunks while its
# neighbours decode.
CASES = [(40, 6), (55, 5), (70, 4), (47, 7), (62, 3)]


def _hybrid_engine():
    """A small model of the hybrid cell's kind: Mamba-2 state, paged K/V,
    routed experts and a shared one; no index keys."""
    m = HybridLM(vocab_size=64, d_model=32, num_heads=2, num_kv_heads=1,
                 d_ff=16, shared_ff=24, num_experts=4, top_k=2, held=(0, 4),
                 ssm_heads=2, ssm_head_dim=8, ssm_state=8, ssm_chunk=8,
                 layer_types=("mamba", "attention"), max_seq_len=32,
                 compute_dtype=jnp.float32)
    return ServeEngine(m, m.init(jax.random.key(0)), num_slots=2,
                       block_size=8, prefill_chunk=8)


def _serve(model, params, cases=CASES, **kw):
    eng = ServeEngine(model, params, **dict(GEOM, **kw))
    prompts = [_prompt(n, seed=i) for i, (n, _) in enumerate(cases)]
    reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, cases)]
    eng.run()
    return eng, prompts, reqs


# ---- (a) the engine against the reference ---------------------------------------

def test_engine_logprobs_match_the_reference(model, params):
    eng, prompts, reqs = _serve(model, params)
    for p, r, (_, new) in zip(prompts, reqs, CASES):
        assert r.done and len(r.tokens) == new
        assert _worst(model, params, p, r) < TOL
    assert eng.pool.k.shape[0] == eng.pool.index.shape[0] == 2
    # a block's 4 keys of 8 side by side in one row of 32 lanes
    assert eng.pool.index.shape[1:] == (eng.pool.k.shape[1], 1, 32)
    assert eng.metrics.gauges["serve_kv_pool_bytes"]["last"] == (
        eng.pool.k.nbytes + eng.pool.v.nbytes + eng.pool.index.nbytes)
    assert eng.sched.accounting_ok()
    assert eng.pool.free_count == eng.pool.total_usable


def _recording(monkeypatch, seen):
    """``select_top`` (the decode step) and ``selection_mask`` (a prefill
    chunk) as the engine calls them, each also handing the host what it
    chose: (layer, positions of the rows, chosen positions of each row).
    The layer is the order in which a program's trace calls the function."""
    order = {"decode": itertools.count(), "prefill": itertools.count()}

    def keep(kind, layer, q_pos, chosen):
        seen.append((kind, layer, np.asarray(q_pos).reshape(-1),
                     np.asarray(chosen).reshape(-1, chosen.shape[-1])))

    def top(model, scores, q_pos):
        pos, kept = sparse_moe.select_top(model, scores, q_pos)
        layer = next(order["decode"]) % model.num_layers
        chosen = jnp.where(kept, pos, -1)
        jax.debug.callback(functools.partial(keep, "decode", layer), q_pos,
                           chosen)
        return pos, kept

    def mask(model, scores, q_pos):
        m = sparse_moe.selection_mask(model, scores, q_pos)
        layer = next(order["prefill"]) % model.num_layers
        jax.debug.callback(functools.partial(keep, "prefill", layer), q_pos,
                           m)
        return m

    monkeypatch.setattr(engine_mod, "select_top", top)
    monkeypatch.setattr(engine_mod, "selection_mask", mask)


def test_engine_selects_the_references_positions(monkeypatch):
    """Every row of every layer, prefill chunks and decode steps: the set
    of positions the engine attends over is the reference's."""
    m = _model(name="sparse-recorded")
    p = m.init(jax.random.key(11))
    seen = []
    _recording(monkeypatch, seen)
    engine_mod._build_decode_step.cache_clear()
    engine_mod._build_prefill_step.cache_clear()
    try:
        prompt, new = _prompt(70, seed=3), 6
        eng = ServeEngine(m, p, **dict(GEOM, num_slots=1))
        req = eng.submit(prompt, new)
        eng.run()
        jax.effects_barrier()
    finally:
        engine_mod._build_decode_step.cache_clear()
        engine_mod._build_prefill_step.cache_clear()
    full = np.concatenate([prompt, req.tokens]).astype(np.int32)[:-1]
    _, want = reference.forward(p, jnp.asarray(full), _cfg(m))
    want = [np.asarray(w) for w in want]
    compared = {("prefill", 0): 0, ("prefill", 1): 0,
                ("decode", 0): 0, ("decode", 1): 0}
    for kind, layer, q_pos, chosen in seen:
        for t, row in zip(q_pos, chosen):
            # a prefill chunk's padding and a step past the last fed token
            if t >= (len(prompt) if kind == "prefill" else len(full)):
                continue
            got = np.flatnonzero(row) if kind == "prefill" \
                else row[row >= 0]
            ref = want[layer][t]
            assert sorted(got) == sorted(ref[ref >= 0]), (kind, layer, t)
            assert len(got) == min(t + 1, TOPK)
            compared[kind, layer] += 1
    assert compared == {("prefill", 0): 70, ("prefill", 1): 70,
                        ("decode", 0): 5, ("decode", 1): 5}


# ---- (b) within the top-k the mixer is dense attention ---------------------------

def test_within_the_topk_the_mixer_is_dense_attention(model, params):
    """Requests whose every position is within the top 16 match the
    reference with the selection off; past it they do not."""
    short = [(9, 7), (12, 4), (5, 11)]
    _, prompts, reqs = _serve(model, params, short)
    for p, r in zip(prompts, reqs):
        assert len(p) + len(r.tokens) - 1 <= TOPK
        assert _worst(model, params, p, r, topk=10 ** 6) < TOL
    _, prompts, reqs = _serve(model, params, CASES[:2])
    for p, r in zip(prompts, reqs):
        assert _worst(model, params, p, r) < TOL
        assert _worst(model, params, p, r, topk=10 ** 6) > 100 * TOL


def test_selection_functions_agree_and_keep_everything_within_the_topk(
        model):
    scores = jax.random.normal(jax.random.key(0), (2, 5, 40))
    # rounded scores so that ties at the cutoff happen
    scores = jnp.round(scores * 2) / 2
    q_pos = jnp.array([[3, 15, 16, 30, 39], [0, 1, 20, 38, 39]])
    pos, kept = sparse_moe.select_top(model, scores, q_pos)
    mask = sparse_moe.selection_mask(model, scores, q_pos)
    assert pos.shape == (2, 5, TOPK)
    for b in range(2):
        for i in range(5):
            t = int(q_pos[b, i])
            chosen = np.asarray(pos[b, i])[np.asarray(kept[b, i])]
            assert sorted(chosen) == list(np.flatnonzero(mask[b, i]))
            assert len(chosen) == min(t + 1, TOPK)
            if t < TOPK:
                assert sorted(chosen) == list(range(t + 1))


@pytest.mark.parametrize("k", [1, 7, 128, 299, 300])
def test_top_mask_keeps_what_lax_top_k_keeps(k):
    """The sort-free selection is ``lax.top_k``'s, ties to the lower
    position: scores rounded so that ties at the cutoff happen, -inf
    past some rows' ends, -0 beside +0, 300 positions (not a multiple of
    the groups of 128)."""
    s = jax.random.normal(jax.random.key(k), (3, 4, 300))
    s = jnp.round(s * 3) / 3
    s = s.at[0, 1, 200:].set(-jnp.inf).at[1, 2].set(0.0).at[1, 2, 5].set(-0.0)
    s = sparse_moe._masked(s, jnp.full((3, 4), 299))
    got = sparse_moe.top_mask(s, k)
    _, want = jax.lax.top_k(s, k)
    for b in range(3):
        for q in range(4):
            assert sorted(np.flatnonzero(got[b, q])) == sorted(
                np.asarray(want[b, q]).tolist()), (b, q)


@pytest.mark.parametrize("T,k", [(96, 16), (300, 128), (1000, 37),
                                 (1024, 1024)])
def test_mask_positions_lists_the_true_positions_in_order(T, k):
    rng = np.random.default_rng(T + k)
    mask = np.zeros((2, 3, T), bool)
    for row in mask.reshape(-1, T):
        row[rng.choice(T, size=k, replace=False)] = True
    mask[0, 0] = False
    mask[0, 0, T - k:] = True          # all in the last groups
    got = np.asarray(sparse_moe.mask_positions(jnp.asarray(mask), k))
    for row, pos in zip(mask.reshape(-1, T), got.reshape(-1, k)):
        assert pos.tolist() == np.flatnonzero(row).tolist()


def test_index_keys_in_lane_dense_pages_score_as_plain_keys():
    """At Keye-VL-2.0's key of 64 and blocks of 16 a page is one (8, 128)
    tile, two keys a row; scores read through it are the plain ones."""
    from tpu_ddp.serve.kv_pool import index_page
    assert index_page(16, 64) == (8, 128)
    assert index_page(4, 8) == (1, 32)
    assert index_page(3, 64) == (3, 64)    # one key a row: 2 does not divide 3
    m = _model(index_head_dim=64, index_rope_dim=32)
    ks = jax.random.split(jax.random.key(3), 3)
    qi = jax.random.normal(ks[0], (2, 5, m.index_heads, 64))
    w = jax.random.normal(ks[1], (2, 5, m.index_heads))
    keys = jax.random.normal(ks[2], (2, 48, 64))
    plain = sparse_moe.index_scores(m, qi, w, keys)
    paged = sparse_moe.index_scores(m, qi, w, keys.reshape(2, 24, 128))
    assert plain.shape == paged.shape == (2, 5, 48)
    np.testing.assert_allclose(paged, plain, rtol=1e-6, atol=1e-6)


# ---- (c) the share ---------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the two halves of the experts give are the
    uncut reference's layer; there is no shared MLP to add."""
    whole = _model(held=(0, 16))
    p = whole.init(jax.random.key(5))
    blk = p["blocks"][1]
    x = jax.random.normal(jax.random.key(6), (13, whole.d_model)) * 3.0
    want = reference.moe(blk, x, _cfg(whole))
    h = whole.norm(x, blk["ln2"])
    parts = [dropless_moe(h, blk["router"], blk["w1"][lo:hi],
                          blk["w2"][lo:hi], top_k=whole.top_k,
                          held=(lo, hi)) for lo, hi in ((0, 8), (8, 16))]
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in parts)
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=TOL,
                               rtol=1e-5)
    half = dict(blk, w1=blk["w1"][8:], w2=blk["w2"][8:])
    np.testing.assert_allclose(
        parts[1], reference.moe(half, x, dict(_cfg(whole),
                                              held_experts=[8, 16])),
        atol=TOL, rtol=1e-5)
    # decode.mlp is the share alone
    cut = _model(held=(8, 16))
    np.testing.assert_allclose(decode.mlp(cut, half, h[None])[0], parts[1],
                               atol=TOL, rtol=1e-5)


# ---- (d) the index-key pages -------------------------------------------------------

def test_index_keys_are_written_at_their_positions_across_pages(model,
                                                                params):
    """Mid-flight, the index keys the pool holds for a request's positions
    (read through its block table, a page of 4 at a time) are the
    reference's, written by prefill chunks and by decode steps alike."""
    eng = ServeEngine(model, params, **GEOM)
    prompt = _prompt(47, seed=5)
    req = eng.submit(prompt, 20)
    while len(req.tokens) < 6:
        eng.step()
    slot = next(s for s in eng.sched.slots if s is not None)
    full = np.concatenate([prompt, req.tokens]).astype(np.int32)[:-1]
    inputs = []
    reference.forward(params, jnp.asarray(full), _cfg(model), inputs)
    n = len(full)
    pos = np.arange(n)
    blocks = np.asarray(slot.blocks)[pos // GEOM["block_size"]]
    c = dict(reference._consts(_cfg(model)))
    for layer, (blk, x) in enumerate(zip(params["blocks"], inputs)):
        h = reference._rms_norm(x, blk["ln1"]["scale"], c["eps"])
        with jax.default_matmul_precision("highest"):
            want = reference._index_key(blk, h, c)
        pages = eng.pool.index[layer, blocks].reshape(
            n, GEOM["block_size"], model.index_dim)
        got = pages[pos, pos % GEOM["block_size"]]
        np.testing.assert_allclose(got, want, atol=TOL, rtol=1e-5)
    eng.cancel(req)


def test_the_null_block_and_idle_slots_leak_into_nothing(model, params):
    """The null block, which idle rows write to and every table pads
    with, holds index keys that would outscore every real key and K/V
    rows a thousand times the real ones; two of the three slots stand
    idle for part of the run. The results are the reference's."""
    eng = ServeEngine(model, params, **GEOM)
    null = PagedKVPool.NULL_BLOCK
    pool = eng.pool
    pool.index = pool.index.at[:, null].set(1e3)
    pool.k = pool.k.at[:, null].set(1e3)
    pool.v = pool.v.at[:, null].set(-1e3)
    cases = [(41, 9), (13, 3)]
    prompts = [_prompt(n, seed=20 + i) for i, (n, _) in enumerate(cases)]
    reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, cases)]
    eng.run()
    for p, r in zip(prompts, reqs):
        assert _worst(model, params, p, r) < TOL


def test_a_chunk_through_the_kernel_gives_the_lax_map_forms_tokens(
        monkeypatch):
    """Heads of 128 and a table of 2,048 positions (two key blocks, the
    second masked for every query of these prompts): the chunks attend
    through the sparse prefill kernel, and the engine gives the tokens
    of the ``lax.map`` form over ``attend_masked`` and the reference's
    log-probabilities."""
    model = _model(head_dim_=128, max_seq_len=2048)
    params = model.init(jax.random.key(7))
    geom = dict(prefill_chunk=32)
    assert engine_mod.sparse_kernel_takes(model, 32, 2048, jnp.float32)
    eng, prompts, reqs = _serve(model, params, **geom)
    kernel = "sparse_prefill_attn/pallas_call"
    assert eng._sparse_kernel and kernel in eng.lower_prefill_step(
    ).as_text(debug_info=True)
    tally = eng._tally()
    assert tally["prefill_sparse_kernel_chunks"] == tally["prefill_chunks"]
    assert tally["prefill_chunks"] == sum(-(-n // 32) for n, _ in CASES)
    monkeypatch.setattr(engine_mod.sparse_prefill_attention, "supports",
                        lambda *shapes: False)
    # the programs are memoized on the geometry: build this one anew,
    # and leave none of the refused form behind
    engine_mod._build_prefill_step.cache_clear()
    try:
        plain, _, plain_reqs = _serve(model, params, **geom)
        text = plain.lower_prefill_step().as_text(debug_info=True)
    finally:
        engine_mod._build_prefill_step.cache_clear()
    assert not plain._sparse_kernel and kernel not in text
    assert plain._tally()["prefill_sparse_kernel_chunks"] == 0
    for p, r, q in zip(prompts, reqs, plain_reqs):
        assert r.tokens == q.tokens
        np.testing.assert_allclose(r.logprobs, q.logprobs, atol=TOL, rtol=0)
        assert _worst(model, params, p, r) < TOL


# ---- the counts ---------------------------------------------------------------------

def test_the_tally_counts_what_the_selection_kept(model, params):
    """One request of 40 + 6: decode steps at lengths 40..44 score 41..45
    positions each and keep 16; the prefill chunks score sum(p + 1)."""
    eng = ServeEngine(model, params, **dict(GEOM, num_slots=1))
    eng.submit(_prompt(40, seed=9), 6)
    eng.run()
    tally = eng._tally()
    assert tally["decode_rows"] == 5
    assert tally["index_scored_tokens"] == sum(range(41, 46))
    assert tally["selected_tokens"] == 5 * TOPK
    other = _hybrid_engine()
    assert other.pool.index is None
    assert other._tally()["selected_tokens"] == 0
    assert other._tally()["index_scored_tokens"] == 0
    # heads of 16 lanes: the chunks keep the lax.map form
    assert tally["prefill_chunks"] == 3
    assert tally["prefill_sparse_kernel_chunks"] == 0
    assert other._tally()["prefill_sparse_kernel_chunks"] == 0


# ---- (e) what refuses --------------------------------------------------------------

@pytest.mark.parametrize("kw,reason", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_tiers=2, hbm_blocks=9, cold_blocks=9), "kv_tiers"),
    (dict(spec_k=2), "spec_k"),
    (dict(decode_quant="int8"), "decode_quant"),
    (dict(cp_prefill="ring", mesh="sp2"), "cp_prefill"),
    (dict(mesh="sp2"), "mesh"),
])
def test_features_that_cannot_carry_index_keys_refuse_with_the_reason(
        model, params, kw, reason):
    if kw.get("mesh") == "sp2":
        from tpu_ddp.parallel.mesh import make_mesh
        kw = dict(kw, mesh=make_mesh(jax.devices()[:2], dp=1, sp=2))
    with pytest.raises(ValueError, match="caches an index key") as e:
        ServeEngine(model, params, **dict(GEOM, **kw))
    assert f"{reason} (" in str(e.value)


def test_disagg_refuses_with_the_reason(model, params):
    from tpu_ddp.fleet.disagg import DisaggEngine
    with pytest.raises(ValueError, match=r"disagg \(the edge ships"):
        DisaggEngine(model, params, **GEOM)


# ---- (f) the programs -----------------------------------------------------------------

def test_programs_name_the_indexer_and_the_gather_and_add_no_shared_mlp(
        model, params):
    eng = ServeEngine(model, params, **GEOM)
    text = eng.lower_decode_step().as_text(debug_info=True)
    for scope in ("attn/index", "attn/sparse", "attn/kv_write",
                  "mlp/moe/route", "mlp/moe/experts"):
        assert f"jit(serve_decode)/{scope}/" in text, scope
    # as a scope path: the caller's frames are in the locations too
    assert "/shared_mlp/" not in text
    text = eng.lower_prefill_step().as_text(debug_info=True)
    assert "jit(serve_prefill)/attn/index/" in text
    assert "/shared_mlp/" not in text


def test_a_hybrid_model_still_traces_its_shared_mlp():
    """The hybrid cell's model (a shared expert beside the routed ones)
    keeps its shared MLP, under its scope, in both programs."""
    eng = _hybrid_engine()
    for lower, name in ((eng.lower_decode_step, "serve_decode"),
                        (eng.lower_prefill_step, "serve_prefill")):
        text = lower().as_text(debug_info=True)
        assert f"jit({name})/mlp/shared_mlp/" in text, name
        assert "/attn/index/" not in text, name
