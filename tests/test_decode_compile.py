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


def _pool_sized_ops(text: str):
    """``{opcode: count}`` of the compiled module's instructions whose
    result has a pool column's shape (leading dim = the page count)."""
    ops = {}
    for m in re.finditer(
        r"^\s*(?:ROOT )?\S+ = \w+\[%d,[^\]]*\]\S* ([\w-]+)\(" % NUM_PAGES,
        text, re.M,
    ):
        ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    return ops


def _assert_in_place(compiled, pool, what: str):
    stats = compiled.memory_analysis()
    ops = _pool_sized_ops(compiled.as_text())
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
