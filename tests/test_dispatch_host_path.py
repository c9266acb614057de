"""The decode loop's host path between two device programs: the
dispatch key of an ``aot_jit`` entry keeps a device array's component by
identity, and the paged pool hands out a sequence's page table without
rebuilding it while the sequence holds the pages it was built from.
Both are bookkeeping: what they return must equal what a rebuild gives.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorframes_tpu.models import sparse_decoder as sd
from tensorframes_tpu.models import transformer as tr
from tensorframes_tpu.ops import executor as ex
from tensorframes_tpu.serving import PagedKVPool

PAGE = 4


def _rebuilt_key(fn, leaves, treedef):
    return (treedef, fn._donate) + tuple(
        (tuple(int(d) for d in v.shape), str(v.dtype),
         bool(getattr(v, "weak_type", False)), ex._placement_token(v))
        for v in leaves)


def _args():
    params = {"w": jnp.ones((3, 2), jnp.bfloat16),
              "layers": [{"g": jnp.ones(2)}, {"g": jnp.ones(2)}]}
    return params, np.arange(3, dtype=np.int32)


def test_dispatch_key_equals_the_rebuilt_key_on_every_call():
    fn = ex.aot_jit(lambda p, x: p["w"].sum() + x.sum(), label="t_key")
    args = _args()
    leaves, treedef = jax.tree_util.tree_flatten(args)
    want = _rebuilt_key(fn, leaves, treedef)
    assert fn._key(leaves, treedef) == want          # builds the components
    assert fn._key(leaves, treedef) == want          # serves the kept ones
    # device arrays are kept, the host array is read afresh each time
    assert len(fn._leaf_tokens) == 3
    grown = (args[0], np.arange(5, dtype=np.int32))
    leaves2, treedef2 = jax.tree_util.tree_flatten(grown)
    assert fn._key(leaves2, treedef2) == _rebuilt_key(fn, leaves2, treedef2)
    assert fn._key(leaves2, treedef2) != want


def test_dispatch_key_keeps_no_array_alive_and_a_recycled_id_never_matches():
    fn = ex.aot_jit(lambda p, x: p["w"].sum() + x.sum(), label="t_weak")
    args = _args()
    leaves, treedef = jax.tree_util.tree_flatten(args)
    fn._key(leaves, treedef)
    refs = [h[0] for h in fn._leaf_tokens.values()]
    del args, leaves
    gc.collect()
    assert all(r() is None for r in refs)
    # an entry whose array died serves nothing, whatever now has its id
    other = jnp.ones((7,), jnp.float32)
    dead_id = next(iter(fn._leaf_tokens))
    fn._leaf_tokens[id(other)] = fn._leaf_tokens.pop(dead_id)
    got = fn._leaf_token(other, jax.devices()[0])
    assert got[0] == (7,) and got[1] == "float32"


def test_dispatch_key_components_follow_the_default_device():
    fn = ex.aot_jit(lambda x: x + 1, label="t_dev")
    x = jnp.ones(3)
    a, b = jax.devices()[:2]
    first = fn._leaf_token(x, a)
    assert fn._leaf_token(x, a) is first
    # another default device: the component is built again, not served
    assert fn._leaf_tokens[id(x)][1] is a
    fn._leaf_token(x, b)
    assert fn._leaf_tokens[id(x)][1] is b


def test_dispatch_key_store_is_bounded(monkeypatch):
    fn = ex.aot_jit(lambda x: x + 1, label="t_bound")
    monkeypatch.setattr(ex, "_LEAF_TOKENS_MAX", 8)
    dev = jax.devices()[0]
    keep = jnp.ones(2)
    fn._leaf_token(keep, dev)
    for i in range(40):
        fn._leaf_token(jnp.ones(3) + i, dev)       # dies at once
    assert len(fn._leaf_tokens) <= 8
    assert fn._leaf_tokens[id(keep)][0]() is keep


def test_donated_calls_go_through_the_kept_components_and_tracers_do_not():
    fn = ex.aot_jit(lambda p, x: (p * 2, x + p.sum()), label="t_donate",
                    donate_argnums=(0,))
    p = jnp.ones(4)
    for step in range(3):
        p, y = fn(p, np.float32(step))        # p is deleted and rebound
    assert float(y) == pytest.approx(2.0 + 4 * 4.0)
    inner = ex.aot_jit(lambda x: x * 3, label="t_inner")
    dev = jax.devices()[0]

    def traced(x):
        assert inner._leaf_token(x, dev)[0] == (2,)
        return x

    jax.jit(traced)(jnp.ones(2))
    assert not inner._leaf_tokens              # a tracer is never kept


def _two_kind_pool():
    served = sd.tiny().served_model(PAGE, 64)
    ring = served.kinds[1].entries
    return PagedKVPool(served, 40, PAGE,
                       extra_pages={"window": 1 + 3 * ring}), ring


def _rebuilt_table(pool, seq, kind):
    pages = pool._pages[kind].owned.get(seq, [])
    if kind == pool.kinds[0].name:
        pages = pool.seq_pages(seq)
    t = np.zeros(pool._pages[kind].kind.entries, np.int32)
    t[:len(pages)] = pages
    return t


@pytest.mark.parametrize("kind", ["full", "window"])
def test_pool_table_follows_every_alloc_and_is_a_copy(kind):
    pool, ring = _two_kind_pool()
    assert (pool.table(5, kind) == 0).all()            # holds nothing yet
    grows = 3 if kind == "window" else 12
    for n in range(1, grows + 1):
        pool.alloc(5, 1, kind)
        got = pool.table(5, kind)
        assert np.array_equal(got, _rebuilt_table(pool, 5, kind))
        assert int((got > 0).sum()) == n == pool.held(5, kind)
        got[:] = -1                                    # the caller's own
        assert np.array_equal(pool.table(5, kind),
                              _rebuilt_table(pool, 5, kind))
    # another sequence with as many pages has its own table
    pool.alloc(6, grows, kind)
    assert not np.array_equal(pool.table(6, kind), pool.table(5, kind))
    pool.free_seq(5)
    assert not any(seq == 5 for seq, _ in pool._tables)
    assert (pool.table(5, kind) == 0).all()
    assert np.array_equal(pool.table(6, kind), _rebuilt_table(pool, 6, kind))
    pool.free_seq(6)
    pool.check()
    assert not pool._tables


def test_pool_table_keeps_its_place_through_a_published_prefix():
    cfg = tr.tiny()
    pool = PagedKVPool(cfg.served_model(PAGE, PAGE * 8), 16, PAGE)
    prompt = np.arange(11, dtype=np.int32)
    pool.alloc(1, pool.pages_needed(len(prompt)))
    before = pool.table(1)
    assert pool.publish_prefix(1, prompt) == 2         # two full pages
    assert np.array_equal(pool.table(1), before)
    # a second sequence shares them, then grows: refs first, then its own
    hit, covered, cow, _ = pool.prefix_match(prompt)
    pool.prefix_acquire(2, hit)
    assert np.array_equal(pool.table(2)[:2], before[:2])
    pool.alloc(2, 1)
    got = pool.table(2)
    assert np.array_equal(got[:2], before[:2]) and got[2] > 0
    assert got[2] != before[2]
    for seq in (1, 2):
        pool.free_seq(seq)
    pool.check()
