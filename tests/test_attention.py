"""Attention kernel tests: blockwise and ring vs the dense oracle
(forward and gradients), plus the transformer wired to each impl."""

import numpy as np
import pytest

import tensorframes_tpu  # noqa: F401  (x64 config)
import jax
import jax.numpy as jnp

from tensorframes_tpu.ops import attention as att
from tensorframes_tpu.parallel import device_count, make_mesh


def _qkv(b=2, h=4, s=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, h, s, d)).astype(np.float32))
        for _ in range(3)
    )


def test_blockwise_matches_dense():
    q, k, v = _qkv()
    ref = att.dense_attention(q, k, v)
    out = att.blockwise_attention(q, k, v, block_size=16)
    assert np.allclose(ref, out, atol=1e-5)


def test_blockwise_causal():
    q, k, v = _qkv()
    ref = att.dense_attention(q, k, v, causal=True)
    out = att.blockwise_attention(q, k, v, causal=True, block_size=16)
    assert np.allclose(ref, out, atol=1e-5)


def test_blockwise_non_divisible_block():
    # seq 60 with block 16 → padding path
    q, k, v = _qkv(s=60)
    ref = att.dense_attention(q, k, v)
    out = att.blockwise_attention(q, k, v, block_size=16)
    assert np.allclose(ref, out, atol=1e-5)


def test_blockwise_grads_match_dense():
    q, k, v = _qkv(s=32)

    def loss_ref(q):
        return att.dense_attention(q, k, v).sum()

    def loss_bw(q):
        return att.blockwise_attention(q, k, v, block_size=8).sum()

    g_ref = jax.grad(loss_ref)(q)
    g_bw = jax.grad(loss_bw)(q)
    assert np.allclose(g_ref, g_bw, atol=1e-4)


@pytest.mark.skipif(device_count() < 8, reason="needs 8 virtual devices")
def test_ring_matches_dense():
    q, k, v = _qkv()
    mesh = make_mesh({"sp": 8})
    ref = att.dense_attention(q, k, v)
    out = att.ring_attention(q, k, v, mesh, axis="sp")
    assert np.allclose(ref, out, atol=1e-5)


@pytest.mark.skipif(device_count() < 8, reason="needs 8 virtual devices")
def test_ring_causal_matches_dense():
    q, k, v = _qkv()
    mesh = make_mesh({"sp": 8})
    ref = att.dense_attention(q, k, v, causal=True)
    out = att.ring_attention(q, k, v, mesh, axis="sp", causal=True)
    assert np.allclose(ref, out, atol=1e-5)


@pytest.mark.skipif(device_count() < 8, reason="needs 8 virtual devices")
def test_ring_dp_sp_mesh():
    q, k, v = _qkv()
    mesh = make_mesh({"dp": 2, "sp": 4})
    ref = att.dense_attention(q, k, v)
    out = att.ring_attention(q, k, v, mesh, axis="sp", batch_axis="dp")
    assert np.allclose(ref, out, atol=1e-5)


@pytest.mark.skipif(device_count() < 8, reason="needs 8 virtual devices")
def test_ring_grads_match_dense():
    q, k, v = _qkv(s=32)
    mesh = make_mesh({"sp": 8})

    g_ref = jax.grad(lambda q: att.dense_attention(q, k, v).sum())(q)
    g_ring = jax.grad(
        lambda q: att.ring_attention(q, k, v, mesh, axis="sp").sum()
    )(q)
    assert np.allclose(g_ref, g_ring, atol=1e-4)


@pytest.mark.skipif(device_count() < 8, reason="needs 8 virtual devices")
def test_ring_rejects_non_divisible_seq():
    q, k, v = _qkv(s=60)
    mesh = make_mesh({"sp": 8})
    with pytest.raises(ValueError, match="divisible"):
        att.ring_attention(q, k, v, mesh, axis="sp")


def test_transformer_blockwise_matches_dense():
    from tensorframes_tpu.models import transformer as tr

    cfg_d = tr.tiny()
    cfg_b = tr.tiny(attention_impl="blockwise")
    params = tr.init_params(cfg_d)
    tokens, _ = tr.synthetic_batch(cfg_d, 2, 16)
    hd = np.asarray(tr.forward(cfg_d, params, tokens), dtype=np.float32)
    hb = np.asarray(tr.forward(cfg_b, params, tokens), dtype=np.float32)
    assert np.allclose(hd, hb, atol=6e-2)  # bf16 accumulation tolerance


@pytest.mark.skipif(device_count() < 8, reason="needs 8 virtual devices")
def test_transformer_ring_sharded_train_step():
    import optax

    from tensorframes_tpu.models import transformer as tr

    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    cfg = tr.tiny(attention_impl="ring")
    params = tr.init_params(cfg)
    tx = optax.adamw(1e-3)
    step, data_sharding, param_sh, init_opt = tr.make_sharded_train_step(
        cfg, mesh, tx
    )
    tokens, targets = tr.synthetic_batch(cfg, 4, 16)
    tokens = jax.device_put(tokens, data_sharding)
    targets = jax.device_put(targets, data_sharding)
    params = jax.device_put(params, param_sh)
    opt_state = init_opt(params)
    _, _, loss = step(params, opt_state, tokens, targets)
    assert np.isfinite(float(loss))

    # ring loss ≈ dense loss on the same params/batch
    cfg_d = tr.tiny()
    ref = float(tr.loss_fn(cfg_d, tr.init_params(cfg), np.asarray(tokens), np.asarray(targets)))
    assert abs(float(loss) - ref) < 5e-2


def test_mask_rejected_by_non_dense_impls():
    from tensorframes_tpu.models import transformer as tr

    cfg = tr.tiny(attention_impl="blockwise")
    params = tr.init_params(cfg)
    tokens, _ = tr.synthetic_batch(cfg, 2, 8)
    mask = np.ones((2, 8), dtype=bool)
    with pytest.raises(NotImplementedError, match="padding mask"):
        tr.forward(cfg, params, tokens, mask=jnp.asarray(mask))


def test_ring_requires_mesh():
    from tensorframes_tpu.models import transformer as tr

    cfg = tr.tiny(attention_impl="ring")
    params = tr.init_params(cfg)
    tokens, _ = tr.synthetic_batch(cfg, 2, 8)
    with pytest.raises(ValueError, match="'sp' axis"):
        tr.forward(cfg, params, tokens)


def test_sharded_train_step_on_pure_dp_mesh():
    # the library's own default mesh has no 'sp' axis; the step must not
    # demand one
    import optax

    from tensorframes_tpu.models import transformer as tr
    from tensorframes_tpu.parallel import make_mesh

    mesh = make_mesh()  # pure dp
    cfg = tr.tiny()
    params = tr.init_params(cfg)
    tx = optax.adamw(1e-3)
    step, data_sharding, param_sh, init_opt = tr.make_sharded_train_step(
        cfg, mesh, tx
    )
    tokens, targets = tr.synthetic_batch(cfg, 8, 8)
    p = jax.device_put(params, param_sh)
    opt = init_opt(p)
    t = jax.device_put(tokens, data_sharding)
    g = jax.device_put(targets, data_sharding)
    _, _, loss = step(p, opt, t, g)
    assert np.isfinite(float(loss))


def test_seg_info_survives_feed_dict():
    import tensorframes_tpu as tfs
    from tensorframes_tpu import dtypes as dt

    df = tfs.frame_from_arrays(
        {
            "key": np.arange(12, dtype=np.int64) % 2,
            "col": np.arange(12, dtype=np.float64),
        }
    )
    ph = tfs.placeholder(dt.float64, [None], name="col_input")
    fetch = tfs.reduce_sum(ph, axis=0, name="col")
    prog = tfs.compile_program(fetch, df, reduce_mode="blocks")
    renamed = prog.rename_inputs({"col_input": "col_input"})
    assert getattr(renamed, "seg_info", None) is not None


def test_dense_attention_padding_mask():
    q, k, v = _qkv(s=8)
    pm = np.ones((2, 8), dtype=bool)
    pm[:, 6:] = False
    out = att.dense_attention(q, k, v, padding_mask=jnp.asarray(pm))
    ref = att.dense_attention(q[:, :, :, :], k[:, :, :6], v[:, :, :6])
    # queries attend only to the first 6 keys
    assert np.allclose(out, ref, atol=1e-5)


def test_ulysses_matches_dense():
    from tensorframes_tpu.ops import attention as att
    from tensorframes_tpu.parallel import make_mesh

    mesh = make_mesh({"sp": 4, "dp": 2})
    rng = np.random.default_rng(5)
    b, h, s, d = 2, 4, 16, 8
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        for _ in range(3)
    )
    got = att.ulysses_attention(q, k, v, mesh, axis="sp")
    want = att.dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ulysses_causal_matches_dense():
    from tensorframes_tpu.ops import attention as att
    from tensorframes_tpu.parallel import make_mesh

    mesh = make_mesh({"sp": 4, "dp": 2})
    rng = np.random.default_rng(6)
    b, h, s, d = 1, 4, 32, 8
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        for _ in range(3)
    )
    got = att.ulysses_attention(q, k, v, mesh, axis="sp", causal=True)
    want = att.dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ulysses_head_divisibility_error():
    from tensorframes_tpu.ops import attention as att
    from tensorframes_tpu.parallel import make_mesh

    mesh = make_mesh({"sp": 4, "dp": 2})
    q = jnp.zeros((1, 3, 16, 8), jnp.float32)  # 3 heads, sp=4
    with pytest.raises(ValueError, match="heads 3 not divisible"):
        att.ulysses_attention(q, q, q, mesh, axis="sp")


def test_transformer_ulysses_impl():
    from tensorframes_tpu.models import transformer as tr
    from tensorframes_tpu.parallel import make_mesh

    mesh = make_mesh({"sp": 4, "dp": 2})
    cfg = tr.tiny(attention_impl="ulysses")
    params = tr.init_params(cfg, seed=0)
    tokens, _ = tr.synthetic_batch(cfg, 2, 16, seed=0)
    hs = tr.forward(cfg, params, jnp.asarray(tokens), mesh=mesh)
    dense_cfg = tr.tiny(attention_impl="dense")
    want = tr.forward(dense_cfg, params, jnp.asarray(tokens))
    np.testing.assert_allclose(
        np.asarray(hs, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,  # bf16 activations
    )


# -- flash dispatch: a static choice, never a caught failure (PR 21) ---------

def test_flash_eligible_is_a_static_shape_predicate():
    def mk(seq, d, dtype=jnp.float32):
        return jnp.zeros((1, 2, seq, d), dtype)

    assert att.flash_eligible(mk(256, 64), mk(256, 64), mk(256, 64))
    assert att.flash_eligible(*(mk(128, 128, jnp.bfloat16),) * 3)
    assert att.flash_eligible(mk(128, 256), mk(128, 256), mk(128, 256))
    # seq not a whole number of 128 blocks / head_dim past 128 and not a
    # multiple of it / mixed or unsupported dtypes
    assert not att.flash_eligible(mk(200, 64), mk(200, 64), mk(200, 64))
    assert not att.flash_eligible(mk(128, 64), mk(100, 64), mk(100, 64))
    assert not att.flash_eligible(mk(128, 192), mk(128, 192), mk(128, 192))
    assert not att.flash_eligible(
        mk(128, 64), mk(128, 64, jnp.bfloat16), mk(128, 64))
    assert not att.flash_eligible(*(mk(128, 64, jnp.float64),) * 3)


def test_flash_on_cpu_is_blockwise():
    q, k, v = _qkv(s=128)
    np.testing.assert_array_equal(
        np.asarray(att.flash_attention(q, k, v, causal=True)),
        np.asarray(att.blockwise_attention(q, k, v, causal=True)),
    )


def test_flash_kernel_failure_surfaces_on_tpu(monkeypatch):
    """On a TPU an eligible call runs the pallas kernel or raises — no
    canary, no per-call except, no drop to blockwise."""
    from jax.experimental.pallas.ops.tpu import flash_attention as upstream

    def boom(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel (test)")

    monkeypatch.setattr(att, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(upstream, "flash_attention", boom)
    q = jnp.zeros((1, 2, 128, 64), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        att.flash_attention(q, q, q)
    # an ineligible shape is blockwise by choice, not by rescue
    q = jnp.zeros((1, 2, 100, 64), jnp.float32)
    assert att.flash_attention(q, q, q).shape == q.shape


@pytest.mark.parametrize("blk,lengths,seq", [
    (8, (13, 5, 8, 1), 48),       # a prompt over two blocks, a one-row one
    (16, (40, 16, 3), 96),        # three blocks long; padding blocks after
    (4, (4, 4, 4, 4), 16),        # every block its own segment, no padding
])
def test_blockwise_block_bounds_equal_block_diagonal_dense(blk, lengths, seq):
    """Packed sequences, each starting on a block edge: with each query
    block's first key block given, every row attends causally within its
    own sequence's blocks (a block outside every sequence within itself),
    as a dense causal reference masked to that block-diagonal does, for
    grouped heads and int8 keys and values with per-position scales."""
    rng = np.random.default_rng(blk)
    hq, hk, d = 4, 2, 8
    q = jnp.asarray(rng.normal(size=(1, hq, seq, d)), jnp.float32)
    k = jnp.asarray(rng.integers(-127, 128, (1, hk, seq, d)), jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, (1, hk, seq, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.02, (1, hk, seq)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.02, (1, hk, seq)), jnp.float32)
    group = np.arange(seq) // blk              # padding: its own block
    first = np.arange(seq // blk)
    at = 0
    for n in lengths:
        span = -(-n // blk) * blk
        group[at:at + span] = seq + at
        first[at // blk:(at + span) // blk] = at // blk
        at += span
    got = att.blockwise_attention(
        q, k, v, causal=True, block_size=blk, k_scale=ks, v_scale=vs,
        first_block=jnp.asarray(first, jnp.int32))
    kk = jnp.repeat(k.astype(jnp.float32) * ks[..., None], hq // hk, axis=1)
    vv = jnp.repeat(v.astype(jnp.float32) * vs[..., None], hq // hk, axis=1)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(d)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    mask = (j <= i) & (group[:, None] == group[None, :])
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(mask, sc, -1e30), axis=-1), vv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=0)


def test_blockwise_block_bounds_need_whole_blocks_and_no_window():
    q = jnp.zeros((1, 2, 12, 4), jnp.float32)
    with pytest.raises(ValueError, match="whole number of blocks"):
        att.blockwise_attention(q, q, q, causal=True, block_size=8,
                                first_block=jnp.zeros(2, jnp.int32))
    with pytest.raises(ValueError, match="causal"):
        att.blockwise_attention(q, q, q, causal=False, block_size=4,
                                first_block=jnp.zeros(3, jnp.int32))
