"""``serve_decode`` and ``serve_prefill`` of ``brumby-serve-doc16``'s
configuration (Brumby-14B-Base's published widths, 8 layers, 16 slots of
32,768 positions, block 16, chunk 512), compiled HERE for a described TPU
v5e: no chip, no arrays, about two minutes. The weights (8.40 GB) and the
retention state (4.36 GB) are 80% of the chip, so what this guards is
the fill: the state updated in place and aliased to the outputs, never
copied, and the temporaries small (a chunk's float32 features are made in
parts, models/retention.py ``PART_BYTES``). One file, the topology
described inside a fixture: only the worker that runs this file loads the
TPU's library.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tpu_ddp.serve import engine

ROOT = Path(__file__).resolve().parents[1]
GB = 1e9


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
    """{program: (memory analysis, compiled text of the ENTRY
    computation)}."""
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.lib.families import brumby

    cfg = json.loads((ROOT / "benchmark/configs/brumby-14b-base-l8.json")
                     .read_text())
    geo = cfg["serve"]
    S, B, C = geo["num_slots"], geo["block_size"], geo["prefill_chunk"]
    bps = geo["max_seq_len"] // B
    model = brumby.build(cfg, max_seq_len=geo["max_seq_len"],
                         param_dtype=jnp.bfloat16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    # the pool of a model without K/V layers: 0 layers
    pool = sds((0, S * bps + 1, B, model.kv_heads * model.head_dim),
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
    # A compile for a described chip is written to the persistent cache
    # and cannot be read back without the chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    out = {}
    try:
        for name, (step, args) in programs.items():
            comp = step.trace(*args).lower(
                lowering_platforms=("tpu",)).compile()
            text = comp.as_text()
            out[name] = (comp.memory_analysis(),
                         text[text.index("ENTRY "):])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out


# (program, the most its temporaries may take): 0.16 and 0.37 GB when
# this was written; a copy of one layer's state is 0.55 GB
@pytest.mark.parametrize("program,temp_gb", [("serve_decode", 0.3),
                                             ("serve_prefill", 0.5)])
def test_the_programs_fit_the_chip_with_the_state_in_place(compiled, program,
                                                           temp_gb):
    """12.76 GB of arguments (weights 8.40, state 4.36, K/V 0) on a 16 GB
    chip: the state's 4.36 GB aliased to the outputs, small temporaries,
    and the whole under the chip's memory."""
    mem, _ = compiled[program]
    assert mem.argument_size_in_bytes == pytest.approx(12.76 * GB, rel=2e-3)
    assert mem.alias_size_in_bytes == pytest.approx(4.362 * GB, rel=1e-3)
    assert mem.temp_size_in_bytes < temp_gb * GB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15 * GB


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_nothing_copies_the_state(compiled, program):
    """A value of the state pool's shape is the parameter or the in-place
    result of an update (a fusion around a dynamic-update-slice), never a
    copy: one copy of ``S`` is 4.36 GB, which the chip does not have."""
    _, entry = compiled[program]
    pool = r"f32\[8,16,8,8256,128\]\{[^}]*\}"
    made = re.findall(rf"%(\S+) = {pool} ([\w\-]+)\(", entry)
    assert made
    for name, op in made:
        assert op in {"parameter", "fusion", "dynamic-update-slice"}, name
        assert op != "fusion" or "dynamic-update-slice" in name, name
