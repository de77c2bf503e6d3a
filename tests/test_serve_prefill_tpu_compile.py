"""``serve_prefill`` at the benchmark's serving geometry (StarCoder2-3B
widths, 30 layers, 32 slots x 4096 positions, block 16, chunk 256),
compiled HERE for a described TPU v5e: no chip, no arrays, about half a
minute. It holds what PR 32 learned on the chip (docs/DESIGN.md §19,
PERF.md section 6) and what no CPU lowering can show:

- no value of one pool layer's shape is left in the program (the
  ``pool[li]`` copy, 67 MB a time, 60 a chunk);
- the float32 attention scores, the program's largest temporaries, are
  assigned to the on-chip memory beside HBM (``S(1)`` in the compiled
  layouts) and not to HBM. Taking the layer copies out alone sent all
  30 of the 100 MB scores to HBM and made the chunk 26% slower;
  ``engine.attend_chunk`` halves them so that they stay.

The compiler decides both for the whole program, so the whole program
is what is compiled. The hybrid cell's two programs, and the sparse
cell's ``serve_prefill`` (its attention in the
``sparse_prefill_attn`` kernel: a VMEM overrun shows here, not on the
chip), are compiled the same way. One file, the topology described
inside a fixture:
only the worker that runs this file loads the TPU's library.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from tpu_ddp.models.transformer import TransformerLM
from tpu_ddp.serve import engine

SLOTS, MAX_SEQ, BLOCK, CHUNK = 32, 4096, 16, 256
BPS = MAX_SEQ // BLOCK
NUM_BLOCKS = SLOTS * BPS + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """(model, compiled text of the ENTRY computation)."""
    from jax.experimental.compilation_cache import compilation_cache
    model = TransformerLM(
        name="starcoder2-3b", vocab_size=49152, num_layers=30,
        num_heads=24, num_kv_heads=2, d_model=3072, d_ff=12288,
        max_seq_len=MAX_SEQ, param_dtype=jnp.bfloat16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    pool = sds((model.num_layers, NUM_BLOCKS, BLOCK,
                model.kv_heads * model.head_dim), jnp.bfloat16)
    args = (params, pool, pool, sds((BPS,), jnp.int32),
            sds((1, CHUNK), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((), jnp.float32), sds((), jnp.int32))
    step = engine._build_prefill_step(model, BLOCK, BPS)
    # A compile for a described chip is written to the persistent cache
    # and cannot be read back without the chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = step.trace(*args).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return model, text[text.index("ENTRY "):]


def test_no_value_of_a_pool_layers_shape(compiled):
    _, entry = compiled
    layer = re.findall(rf"bf16\[(?:1,)?{NUM_BLOCKS},{BLOCK},\d+\]", entry)
    assert not layer, sorted(set(layer))
    # ... and the pool itself is only ever a parameter or the in-place
    # result of kv_write's scatters: nothing else makes one.
    pool = rf"bf16\[30,{NUM_BLOCKS},{BLOCK},\d+\]\{{[^}}]*\}}"
    makers = set(re.findall(rf"= {pool} ([\w\-]+)\(", entry))
    assert makers <= {"parameter", "fusion", "bitcast"}, makers
    fusions = re.findall(rf"= {pool} fusion\(([^\n]*)", entry)
    assert fusions and all("kv_write/scatter" in f for f in fusions)


def test_scores_are_assigned_to_on_chip_memory(compiled):
    model, entry = compiled
    # (1, KV, G, queries of one part, keys) float32, as attend_cached
    # makes them; attend_chunk decides the queries of a part.
    shape = (rf"f32\[1,{model.kv_heads},"
             rf"{model.num_heads // model.kv_heads},(\d+),{MAX_SEQ}\]")
    made = re.findall(rf"= \(?[^=\n]*{shape}\{{([^}}]*)\}}[^=\n]* fusion\(",
                      entry)
    rows = {int(q) for q, _ in made}
    assert rows == {CHUNK // 2}, rows       # two parts of 128 rows
    assert len(made) == 2 * 30
    on_chip = sum("S(1)" in layout for _, layout in made)
    assert on_chip >= 0.9 * len(made), (
        f"{on_chip} of {len(made)} score tensors are in on-chip memory: "
        "the rest are written to HBM and read back twice, 0.37 ms a "
        "layer each (PERF.md section 6, PR 32)")


# ---- the hybrid cell's two programs (PR 33) -----------------------------------

@pytest.fixture(scope="module")
def hybrid_compiled(one_chip):
    """``serve_decode`` and ``serve_prefill`` of ``gr4h-serve-chat``'s
    configuration (published widths, 10 layers, 36 of 72 experts, 64
    slots x 4096 positions, block 16, chunk 256), compiled for the v5e:
    {program: (memory analysis, compiled text)}."""
    import json
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.lib.families import granite_hybrid
    from tpu_ddp.ops import pallas as pallas_ops

    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmark/configs/"
                      "granite-4.0-h-small-l10e36.json").read_text())
    geo = cfg["serve"]
    S, B, C = geo["num_slots"], geo["block_size"], geo["prefill_chunk"]
    bps = geo["max_seq_len"] // B
    model = granite_hybrid.build(cfg, max_seq_len=geo["max_seq_len"],
                                 param_dtype=jnp.bfloat16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    pool = sds((1, S * bps + 1, B, model.kv_heads * model.head_dim),
               jnp.bfloat16)
    state = {k: sds(v.shape, v.dtype)
             for k, v in model.state_shapes(S).items()}
    i32, f32 = jnp.int32, jnp.float32
    programs = {
        "serve_decode": (
            engine._build_decode_step(model, B, bps),
            (params, pool, pool, state, sds((S, bps), i32), sds((S,), i32),
             sds((S,), i32), sds((S,), f32), sds((S,), i32))),
        "serve_prefill": (
            engine._build_prefill_step(model, B, bps),
            (params, pool, pool, state, sds((bps,), i32), sds((1, C), i32),
             sds((), i32), sds((), i32), sds((), f32), sds((), i32),
             sds((), i32))),
    }
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    interpret = pallas_ops.interpret_mode
    pallas_ops.interpret_mode = lambda: False   # the kernel, for the chip
    out = {}
    try:
        for name, (step, args) in programs.items():
            comp = step.trace(*args).lower(
                lowering_platforms=("tpu",)).compile()
            out[name] = (comp.memory_analysis(), comp.as_text())
    finally:
        pallas_ops.interpret_mode = interpret
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out


@pytest.mark.parametrize("program,temp_gb", [("serve_decode", 0.1),
                                             ("serve_prefill", 0.3)])
def test_hybrid_programs_fit_and_update_state_and_pool_in_place(
        hybrid_compiled, program, temp_gb):
    """13.44 GB of arguments (weights 9.93, state 2.45, K/V pool 1.07) on
    a 16 GB chip leave no room for a copy of the state: the 3.52 GB of
    state and pool are aliased to the outputs, and the temporaries stay
    small (0.04 and 0.14 GB when this was written)."""
    mem, _ = hybrid_compiled[program]
    assert mem.argument_size_in_bytes == pytest.approx(13.44e9, rel=5e-3)
    assert mem.alias_size_in_bytes == pytest.approx(3.52e9, rel=5e-3)
    assert mem.temp_size_in_bytes < temp_gb * 1e9


@pytest.mark.parametrize("program,rows", [("serve_decode", 640),
                                          ("serve_prefill", 2560)])
def test_hybrid_decode_holds_the_grouped_products_and_the_paged_kernel(
        hybrid_compiled, program, rows):
    """PR 37: twenty grouped products (two a layer) in each program, as
    the ``grouped_matmul`` kernel under ``moe/experts`` (its predicate
    takes 640 and 2,560 sorted rows at these widths), not a (T, E, C)
    dispatch and no longer the TPU's own ragged dot; decode attention
    reads the pool in place. A program whose shapes the predicate
    refused would hold the twenty as ``ragged-dot-none`` custom calls:
    the two counts add up to twenty either way."""
    from tpu_ddp.ops.pallas import grouped_matmul
    mem, text = hybrid_compiled[program]
    entry = text[text.index("ENTRY "):]
    calls = re.findall(
        r"\n\s*%grouped_matmul[.\d]* = (f32\[\d+,\d+\])[^=\n]* "
        r"custom-call\(([^\n]*)", entry)
    plain = len(re.findall(r"= [^=]*custom-call\([^\n]*ragged_dot", entry)) \
        or entry.count(" %ragged-dot-none")
    assert grouped_matmul.supports(rows, 4096, 1536, jnp.bfloat16,
                                   jnp.bfloat16)
    assert grouped_matmul.supports(rows, 768, 4096, jnp.bfloat16,
                                   jnp.bfloat16)
    assert len(calls) == 20 and plain == 0
    assert sorted({shape for shape, _ in calls}) == [
        f"f32[{rows},1536]", f"f32[{rows},4096]"]
    for _, call in calls:
        assert 'custom_call_target="tpu_custom_call"' in call
        assert (f'op_name="jit({program})/mlp/moe/experts/jit(_impl)/'
                'grouped_matmul/pallas_call"') in call
    if program == "serve_decode":
        assert "paged_decode_attn" in text
    # the rows and the products of a layer are the program's largest
    # new temporaries (2,560 x 4096 float32 is 42 MB): inside the limit
    # the neighbouring test holds
    assert mem.temp_size_in_bytes < (0.1e9 if rows == 640 else 0.3e9)


def test_hybrid_decode_advances_the_state_in_the_pool_by_the_kernel(
        hybrid_compiled):
    """PR 34: one ``ssm_state_step`` call a state layer, under
    ``ssm/state``, each taking the whole state pool and giving it back
    in place. Nothing else in the program makes a value of one bank's
    state (268 MB: the slice, the select, the read-out's operand) or of
    the pool's shape: no ``select_dynamic-update-slice`` on it, no copy.
    (In the file PR 33 made for this program, not one of its own: a
    second file that describes the topology goes to another worker, and
    only one process may hold the TPU's library.)"""
    mem, text = hybrid_compiled["serve_decode"]
    entry = text[text.index("ENTRY "):]
    bank, pool = r"f32\[64,128,64,128\]", r"f32\[9,64,128,64,128\]"
    calls = re.findall(
        rf"\n\s*%ssm_state_step[.\d]* = \([^=\n]*{pool}[^=\n]*\) "
        r"custom-call\(([^\n]*)", entry)
    assert len(calls) == 9
    for call in calls:
        assert 'custom_call_target="tpu_custom_call"' in call
        assert "output_to_operand_aliasing={{1}: (6, {})}" in call
        assert ('op_name="jit(serve_decode)/ssm/state/jit(_impl)/'
                'ssm_state_step/pallas_call"') in call
    made = re.findall(
        rf"= \(?[^=\n]*(?:{bank}|{pool})[^=\n]*? ([\w\-]+)\(", entry)
    assert sorted(set(made)) == ["custom-call", "get-tuple-element",
                                 "parameter"], sorted(set(made))
    assert made.count("custom-call") == 9 and made.count("parameter") == 1
    assert "select_dynamic-update-slice" not in entry
    # the state pool, argument to output: the parameter that holds it is
    # one of the four aliased ones
    param = re.search(rf"= {pool}\{{[^}}]*\}} parameter\((\d+)\)", entry)
    head = text[:text.index("\n")]
    assert f"({param.group(1)}, {{}}, may-alias)" in head
    assert mem.temp_size_in_bytes < 0.25e9


def test_hybrid_prefill_holds_no_state_kernel(hybrid_compiled):
    _, text = hybrid_compiled["serve_prefill"]
    assert "ssm_state_step" not in text


# ---- the sparse cell's prefill chunk ----------------------------------------

@pytest.fixture(scope="module")
def keye_prefill(one_chip):
    """``serve_prefill`` of ``keye-serve-long16``'s configuration at its
    geometry (16 slots x 36,864 positions, block 16, chunk 512) and
    published widths, at 2 of its 8 layers (every layer is alike),
    compiled for the v5e: {"kernel": text, "plain": text}, the second
    with the kernel's predicate refusing, so the chunk attends in the
    ``lax.map`` parts the kernel replaces."""
    import json
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.lib.families import keye_vl2
    from tpu_ddp.ops import pallas as pallas_ops
    from tpu_ddp.ops.pallas import sparse_prefill_attention
    from tpu_ddp.serve.kv_pool import index_page

    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmark/configs/"
                      "keye-vl-2.0-30b-a3b-l8e16.json").read_text())
    geo = cfg["serve"]
    S, B, C = geo["num_slots"], geo["block_size"], geo["prefill_chunk"]
    bps, L = geo["max_seq_len"] // B, 2
    model = keye_vl2.build(dict(cfg, num_hidden_layers=L),
                           max_seq_len=geo["max_seq_len"],
                           param_dtype=jnp.bfloat16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    pool = sds((L, S * bps + 1, B, model.kv_heads * model.head_dim),
               jnp.bfloat16)
    index = sds((L, S * bps + 1) + index_page(B, model.index_dim),
                jnp.bfloat16)
    i32, f32 = jnp.int32, jnp.float32
    args = (params, pool, pool, {"index": index}, sds((bps,), i32),
            sds((1, C), i32), sds((), i32), sds((), i32), sds((), f32),
            sds((), i32), sds((), i32))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    interpret = pallas_ops.interpret_mode
    supports = sparse_prefill_attention.supports
    pallas_ops.interpret_mode = lambda: False   # the kernel, for the chip
    out = {}
    try:
        for form in ("kernel", "plain"):
            if form == "plain":
                sparse_prefill_attention.supports = lambda *shapes: False
            # not memoized: the two forms have one geometry
            step = engine._build_prefill_step.__wrapped__(model, B, bps)
            out[form] = step.trace(*args).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    finally:
        sparse_prefill_attention.supports = supports
        pallas_ops.interpret_mode = interpret
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out


def test_keye_prefill_attends_through_the_sparse_kernel(keye_prefill):
    """One ``sparse_prefill_attn`` call a layer, under ``attn``, taking
    the 512 queries (transposed to (KV, G, C, hd)) and the gathered view
    as it is: no value of the ``lax.map`` form's per-part scores or
    weights over the whole table (``[4,8,8,36864]``, eight queries of a
    part) is left, and no copy of the 75 MB view in another layout."""
    text = keye_prefill["kernel"]
    entry = text[text.index("ENTRY "):]
    calls = re.findall(
        r"\n\s*%sparse_prefill_attn[.\d]* = (bf16\[[\d,]+\])[^=\n]* "
        r"custom-call\(([^\n]*)", entry)
    assert [shape for shape, _ in calls] == ["bf16[4,8,512,128]"] * 2
    for _, call in calls:
        assert 'custom_call_target="tpu_custom_call"' in call
        assert ('op_name="jit(serve_prefill)/attn/jit(_impl)/'
                'sparse_prefill_attn/pallas_call"') in call
    parts = r"\[(?:1,)?4,8,8,36864\]|\[4,36864,8,8\]"
    assert not re.findall(parts, text)
    assert not re.findall(r"bf16\[(?:1,)?(?:4,36864|36864,4),128\]", entry)
    # the same program with the kernel refused has the parts' values
    assert len(re.findall(parts, keye_prefill["plain"])) >= 4
    assert "sparse_prefill_attn" not in keye_prefill["plain"]
