"""The serving cell's step and prefill programs, compiled at their real
shapes for a described (not attached) TPU v5e: the paged KV pool keeps
ONE physical layout from residency through the KV write to the
attention kernel's operand, and is donated, so no program copies or
converts a pool column (PR 26).

What the earlier ``[pages, layers, heads, page, head_dim]`` pool cost:
56 pool-sized ``copy`` ops in ``jit_step`` (one per column per layer to
turn the scatter's layout into the Mosaic operand's, plus the entry
layout at both ends) and 6.5 GB of temporaries — nearly all of a 295 ms
step on the chip. These tests hold every later change to zero of them.

Nothing here runs: the TPU compiler is installed without a chip, and
compiles for a topology that is only described. The topology is
described inside a fixture (never at import: only one process may load
the TPU library, and every xdist worker imports every test file), and
every test of this kind lives in this one file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from tensorframes_tpu import kernels
from tensorframes_tpu.models import generation as gen
from tensorframes_tpu.models import transformer as tr

# gpt2-small.closed-loop: DecodeConfig(max_slots=48, page_size=16,
# max_prompt_len=896, max_new_tokens=128, num_pages=None)
SLOTS, PAGE, MAX_PAGES = 48, 16, 64
NUM_PAGES = 1 + SLOTS * MAX_PAGES          # 3,073
TEMP_LIMIT = 0.5e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cell(one_chip):
    """The cell's model config and its arguments as shapes on the
    described chip: int8 block weights, the 3,073-page pool."""
    cfg = gen.gpt_small(vocab_size=50257)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: tr.quantize_params(tr.init_params(cfg, seed=0))
    )
    pool = jax.eval_shape(lambda: gen.init_paged_kv(cfg, NUM_PAGES, PAGE))
    tree = jax.tree_util.tree_map
    return cfg, tree(on_chip, params), tree(on_chip, pool), (
        lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                            sharding=one_chip)
    )


@pytest.fixture()
def mosaic(monkeypatch):
    """Lower the kernel for Mosaic, as on the chip: ``kernels`` is told
    the backend is a TPU, so the step traces the kernel and not (as on
    this CPU) the XLA chain or the pallas interpreter."""
    monkeypatch.setattr(kernels, "is_tpu_backend", lambda: True)
    # a compile for a described chip is written to jax's persistent
    # cache but cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _pool_bytes(pool) -> int:
    return sum(int(np.prod(c.shape)) * c.dtype.itemsize
               for c in pool.values())


def _pool_sized_ops(text: str, num_pages: int = NUM_PAGES):
    """``{opcode: count}`` of the compiled module's instructions whose
    result has a pool column's shape (leading dim = the page count)."""
    ops = {}
    for m in re.finditer(
        r"^\s*(?:ROOT )?\S+ = \w+\[%d,[^\]]*\]\S* ([\w-]+)\(" % num_pages,
        text, re.M,
    ):
        ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    return ops


def _assert_in_place(compiled, pool, what: str,
                     num_pages: int = NUM_PAGES):
    stats = compiled.memory_analysis()
    ops = _pool_sized_ops(compiled.as_text(), num_pages)
    copies = {k: v for k, v in ops.items() if k.startswith("copy")}
    assert not copies, f"{what}: pool-sized copies {copies}"
    # every KV write is a scatter fused in place: one per column and
    # layer, none expanded into a whole-column dynamic-update-slice
    assert "dynamic-update-slice" not in ops, (what, ops)
    assert stats.alias_size_in_bytes == _pool_bytes(pool), (
        what, stats.alias_size_in_bytes, _pool_bytes(pool))
    assert stats.temp_size_in_bytes < TEMP_LIMIT, (
        what, stats.temp_size_in_bytes)
    return ops


def test_step_writes_the_pool_in_place(cell, mosaic):
    """``jit_step`` at 48 slots over 3,073 pages, pallas lowering, pool
    donated: no pool-sized copy, the 48 KV scatters in place, the
    outputs aliased onto the whole pool, temporaries far under one
    pool (1.51 GB)."""
    cfg, params, pool, i32 = cell
    assert kernels.selectable("decode_attn")
    step = gen.paged_decode_step_fn(cfg, PAGE, MAX_PAGES)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, i32(SLOTS), i32(SLOTS), i32(SLOTS, MAX_PAGES)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    ops = _assert_in_place(compiled, pool, "jit_step")
    assert ops.get("scatter", 0) == 4 * cfg.num_layers, ops


@pytest.mark.parametrize("bucket", [512, 1024])
def test_prefill_writes_the_pool_in_place(cell, mosaic, bucket):
    cfg, params, pool, i32 = cell
    prefill = gen.paged_prefill_fn(cfg, PAGE, MAX_PAGES)
    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, pool, i32(bucket), i32(), i32(MAX_PAGES)
    ).compile()
    _assert_in_place(compiled, pool, f"jit_prefill[{bucket}]")


def test_page_ops_write_the_pool_in_place(cell, mosaic):
    """The swap tier's restore and the prefix cache's copy-on-extend
    return the pool too: donated, they touch the pages they name."""
    cfg, _params, pool, i32 = cell
    _extract, restore, copy_page = gen.paged_page_ops_fns(MAX_PAGES)
    payload = [
        jax.ShapeDtypeStruct((MAX_PAGES,) + pool[name].shape[1:],
                             pool[name].dtype,
                             sharding=pool[name].sharding)
        for name in ("k", "v", "k_scale", "v_scale")
    ]
    for what, compiled in (
        ("restore", jax.jit(restore, donate_argnums=(0,)).lower(
            pool, i32(MAX_PAGES), *payload).compile()),
        ("copy_page", jax.jit(copy_page, donate_argnums=(0,)).lower(
            pool, i32(), i32()).compile()),
    ):
        stats = compiled.memory_analysis()
        copies = {k: v for k, v in _pool_sized_ops(
            compiled.as_text()).items() if k.startswith("copy")}
        assert not copies, (what, copies)
        assert stats.alias_size_in_bytes == _pool_bytes(pool), what
        assert stats.temp_size_in_bytes < TEMP_LIMIT, what


# gpt2-small.closed-loop-384 (PR 33): 384 slots, so the 512-row bucket,
# over the 8,193 pages the traffic fills
BIG_SLOTS, BIG_PAGES = 512, 8193

# sha256 of str(jaxpr) of the two programs at that cell's shapes, traced
# with the Mosaic kernel, as the commit before the seam
# (models/served.py) traced them: the engine's first client runs what
# it ran
STEP_JAXPR = "e25e02962955fcbacc5790d002e8e2fe5f515fd65529f54b0f9e2f7901b88d06"
PREFILL_JAXPR = (
    "18b89a9b7f6ec275a2ab1f3c5fb22ea335df241c9f8177771efe39bf94c3a876")


def _big_cell(cell):
    cfg, params, pool, i32 = cell
    on_chip = next(iter(pool.values())).sharding
    big = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip),
        jax.eval_shape(lambda: gen.init_paged_kv(cfg, BIG_PAGES, PAGE)))
    return cfg, params, big, i32


def test_step_at_the_512_row_bucket_writes_8193_pages_in_place(cell, mosaic):
    """``jit_step`` as the 384-slot cell runs it: 0 pool-sized copies,
    4.03 GB aliased, temporaries of a few MB (PERF.md section 7 left
    this case for a PR that may touch ``tests/``)."""
    cfg, params, pool, i32 = _big_cell(cell)
    step = gen.paged_decode_step_fn(cfg, PAGE, MAX_PAGES)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, i32(BIG_SLOTS), i32(BIG_SLOTS),
        i32(BIG_SLOTS, MAX_PAGES)).compile()
    ops = _assert_in_place(compiled, pool, "jit_step[512]", BIG_PAGES)
    assert ops.get("scatter", 0) == 4 * cfg.num_layers, ops
    assert _pool_bytes(pool) > 4.0e9


def test_the_seam_leaves_the_transformer_s_programs_as_they_were(
        cell, mosaic):
    """The engine builds its programs from the served model's
    (``models/served.py``); for the transformer family those are the
    functions it always traced, and their jaxprs hash as before."""
    import hashlib

    cfg, params, pool, i32 = _big_cell(cell)
    served = cfg.served_model(PAGE, MAX_PAGES * PAGE)
    assert [k.name for k in served.kinds] == ["kv"]
    assert served.kinds[0].entries == MAX_PAGES and not served.kinds[0].ring
    bare = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, pool))
    for want, fn, args in (
            (STEP_JAXPR, served.step,
             (i32(BIG_SLOTS), i32(BIG_SLOTS), i32(BIG_SLOTS, MAX_PAGES))),
            (PREFILL_JAXPR, served.prefill,
             (i32(1024), i32(), i32(MAX_PAGES)))):
        text = str(jax.make_jaxpr(fn)(*bare, *args))
        assert hashlib.sha256(text.encode()).hexdigest() == want


@pytest.mark.parametrize("bucket", [1024, 4096])
def test_packed_prefill_writes_the_pool_in_place(cell, mosaic, bucket):
    """The packed prefill at the ends of the 384-slot cell's ladder, 16
    segments, over the 8,193 pages: its attention is the packed kernel,
    one Mosaic call a layer; no pool-sized copy, every column aliased,
    temporaries far under one pool."""
    cfg, params, pool, i32 = _big_cell(cell)
    served = cfg.served_model(PAGE, MAX_PAGES * PAGE)
    compiled = jax.jit(served.packed_prefill, donate_argnums=(1,)).lower(
        params, pool, i32(bucket), i32(16), i32(16), i32(16, MAX_PAGES)
    ).compile()
    assert len(re.findall(
        r"%packed_attention[.\d]* = [^\n]*tpu_custom_call",
        compiled.as_text())) == cfg.num_layers
    _assert_in_place(compiled, pool, f"jit_packed_prefill[{bucket}]",
                     BIG_PAGES)


def test_the_packing_engine_warms_the_packed_ladder_in_place_of_the_one():
    """The 384-slot cell's engine packs: its grid's prefill ladder is the
    packed total-token ladder, shorter than the one-sequence ladder it
    replaces (5 programs for 8), on 64-row block edges, its top bucket
    four of the largest prompt's block-rounded rows and more."""
    from tensorframes_tpu.compilecache import (
        decode_warmup_grid,
        packed_prefill_buckets,
        serving_row_buckets,
    )

    one = decode_warmup_grid(BIG_SLOTS - 128, 896)
    packed = decode_warmup_grid(BIG_SLOTS - 128, 896, pack_block=64)
    assert one["prefill"] == serving_row_buckets(896) == [
        8, 16, 32, 64, 128, 256, 512, 1024]
    assert packed["prefill"] == packed_prefill_buckets(896, 64) == [
        1024, 1536, 2048, 3072, 4096]
    assert packed["decode"] == one["decode"]
    assert len(packed["prefill"]) <= len(one["prefill"])
    assert all(b % 64 == 0 for b in packed["prefill"])
    assert packed["prefill"][-1] >= 4 * 896


# sha256 of str(jaxpr) of the sparse-expert decoder's prefill (1,024-row
# bucket) and step (16 slots) at published widths, traced with the
# kernels, as the commit before the packed prefill traced them: the
# attention it shares with the packed program traces as it did
SPARSE_PREFILL_JAXPR = (
    "06a1a9f7f62d30e1e9b32f8c980a31739c77495053f6fcd1aa824c36e4670d0c")
SPARSE_STEP_JAXPR = (
    "dfa388a9953ab7f4aa96f17f2851f2eff76401dc7fb576ea1b2e707df071ff64")


def _sparse_cell():
    from tensorframes_tpu.models import sparse_decoder as sd

    cfg = sd.SparseDecoderConfig(
        vocab_size=98304, hidden=2304,
        layer_types=("sliding", "full"), num_heads=32, num_kv_heads=4,
        head_dim=128, sliding_window=1024,
        rope_full=sd.RopeSpec(500000.0, factor=16.0, original_max=8192,
                              attention_factor=1.2772588722239782),
        rope_sliding=sd.RopeSpec(500000.0), num_experts=64,
        experts_per_token=8, expert_hidden=896, max_seq_len=4608)
    layer = {k: jax.ShapeDtypeStruct(
        s, jnp.float32 if "norm" in k else jnp.bfloat16)
        for k, s in sd.layer_shapes(cfg).items()}
    params = {
        "embed": jax.ShapeDtypeStruct((98304, 2304), jnp.bfloat16),
        "final_norm": jax.ShapeDtypeStruct((2304,), jnp.float32),
        "head": jax.ShapeDtypeStruct((2304, 98304), jnp.bfloat16),
        "layers": [layer] * cfg.num_layers}
    return cfg, params


def test_the_sparse_decoder_s_programs_trace_as_they_did(mosaic):
    """The sparse-expert decoder offers no packed prefill: its engine
    joins one prompt a dispatch, and its prefill and step jaxprs hash as
    before the packed program shared its attention."""
    import hashlib

    cfg, params = _sparse_cell()
    served = cfg.served_model(16, 4608)
    assert served.packed_prefill is None
    pool = jax.eval_shape(lambda: served.init_pool(
        {"full": 15201, "window": 1 + 128 * 65}))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    for want, fn, args in (
            (SPARSE_PREFILL_JAXPR, served.prefill,
             (i32(1024), i32(), i32(288), i32(65))),
            (SPARSE_STEP_JAXPR, served.step,
             (i32(16), i32(16), i32(16, 288), i32(16, 65)))):
        text = str(jax.make_jaxpr(fn)(params, pool, *args))
        assert hashlib.sha256(text.encode()).hexdigest() == want


def test_sparse_decoder_step_compiles_in_place_at_published_widths(
        one_chip, mosaic):
    """The sparse-expert decoder's step at the widths its cell serves
    (one layer of each kind stands for the eight): the grouped, windowed
    ring walk and the expert matmuls' kernel (``gmm``, three a layer,
    in ``ragged-dot``'s place) pass Mosaic, and both page kinds are
    written in place."""
    from tensorframes_tpu.models import sparse_decoder as sd

    cfg = sd.SparseDecoderConfig(
        vocab_size=98304, hidden=2304,
        layer_types=("sliding", "full"), num_heads=32, num_kv_heads=4,
        head_dim=128, sliding_window=1024,
        rope_full=sd.RopeSpec(500000.0, factor=16.0, original_max=8192,
                              attention_factor=1.2772588722239782),
        rope_sliding=sd.RopeSpec(500000.0), num_experts=64,
        experts_per_token=8, expert_hidden=896, max_seq_len=4608)
    served = cfg.served_model(16, 4608)
    assert [(k.name, k.entries, k.ring) for k in served.kinds] == [
        ("full", 288, False), ("window", 65, True)]

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    layer = {k: jax.ShapeDtypeStruct(
        s, jnp.float32 if "norm" in k else jnp.bfloat16)
        for k, s in sd.layer_shapes(cfg).items()}
    params = {
        "embed": jax.ShapeDtypeStruct((98304, 2304), jnp.bfloat16),
        "final_norm": jax.ShapeDtypeStruct((2304,), jnp.float32),
        "head": jax.ShapeDtypeStruct((2304, 98304), jnp.bfloat16),
        "layers": [layer] * cfg.num_layers}
    # pools of the cell's order of size: a pool of a few MB the compiler
    # would prefetch whole into faster memory, which is no layout copy
    pages = {"full": 15201, "window": 1 + 128 * 65}
    pool = jax.eval_shape(lambda: served.init_pool(pages))
    tree = jax.tree_util.tree_map
    params, pool = tree(on_chip, params), tree(on_chip, pool)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = jax.jit(served.step, donate_argnums=(1,)).lower(
        params, pool, i32(16), i32(16), i32(16, 288), i32(16, 65)).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert len(re.findall(r"%gmm[.\d]* = [^\n]*tpu_custom_call", text)) \
        == 3 * cfg.num_layers
    assert len(re.findall(
        r"%paged_decode_attention[.\d]* = [^\n]*tpu_custom_call", text)) \
        == cfg.num_layers
    for kind, n in pages.items():
        copies = {k: v for k, v in _pool_sized_ops(text, n).items()
                  if k.startswith("copy")}
        assert not copies, (kind, copies)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == sum(
        _pool_bytes(cols) for cols in pool.values())
    assert stats.temp_size_in_bytes < TEMP_LIMIT
