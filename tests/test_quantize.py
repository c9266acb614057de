"""Weight-only int8 quantization: round-trip accuracy, pytree behavior,
transformer integration (embedding quality + generation), HBM accounting."""

import numpy as np

import jax
import jax.numpy as jnp

import tensorframes_tpu as tfs
from tensorframes_tpu.ops import quantize as qt
from tensorframes_tpu.models import generation as gen
from tensorframes_tpu.models import transformer as tr


def test_quantize_roundtrip_accuracy():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 32)).astype(np.float32)
    q = qt.quantize(w)
    assert q.q.dtype == jnp.int8 and q.q.shape == w.shape
    assert q.scale.shape == (1, 32)  # per output channel
    back = np.asarray(q.dequantize())
    # symmetric int8: worst-case error is scale/2 per element
    err = np.abs(back - w)
    bound = np.asarray(q.scale)[0] / 2 + 1e-7
    assert (err <= bound).all()


def test_quantize_zero_and_outlier_channels():
    w = np.zeros((16, 4), np.float32)
    w[:, 1] = 1000.0  # outlier channel must not poison others
    w[:, 2] = 0.001
    q = qt.quantize(w)
    back = np.asarray(q.dequantize())
    np.testing.assert_allclose(back[:, 0], 0.0)
    np.testing.assert_allclose(back[:, 1], 1000.0, rtol=1e-2)
    np.testing.assert_allclose(back[:, 2], 0.001, rtol=1e-2)


def test_quantized_tensor_is_pytree_and_jits():
    w = np.random.default_rng(1).standard_normal((8, 8)).astype(np.float32)
    q = qt.quantize(w)
    fn = jax.jit(lambda x, q: x @ qt.asarray(q, x.dtype))
    x = jnp.ones((2, 8), jnp.float32)
    out = fn(x, q)  # QuantizedTensor crosses the jit boundary as a pytree
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), atol=0.2)


def test_quantize_tree_skips_small_and_int_leaves():
    params = {
        "w": np.random.default_rng(2).standard_normal((16, 16)).astype(np.float32),
        "b": np.zeros((16,), np.float32),
        "steps": np.asarray(3),
    }
    out = qt.quantize_tree(params)
    assert isinstance(out["w"], qt.QuantizedTensor)
    assert not isinstance(out["b"], qt.QuantizedTensor)
    assert not isinstance(out["steps"], qt.QuantizedTensor)


def test_transformer_quantized_embeddings_close():
    cfg = tr.tiny()
    params = tr.init_params(cfg, seed=0)
    qparams = tr.quantize_params(params)
    tokens, _ = tr.synthetic_batch(cfg, 4, 16, seed=0)
    full = np.asarray(tr.forward(cfg, params, tokens), np.float32)
    quant = np.asarray(tr.forward(cfg, qparams, tokens), np.float32)
    # int8 weights: embeddings stay close in cosine similarity per row
    a = full.reshape(4, -1)
    b = quant.reshape(4, -1)
    cos = (a * b).sum(1) / (np.linalg.norm(a, axis=1) * np.linalg.norm(b, axis=1))
    assert (cos > 0.99).all(), cos
    # ~4x weight compression on the quantized leaves
    assert qt.tree_nbytes(qparams) < 0.65 * qt.tree_nbytes(params)


def test_quantized_generation_runs():
    cfg = gen.gpt_tiny()
    params = tr.init_params(cfg, seed=0)
    qparams = tr.quantize_params(params)
    prompts = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    toks = np.asarray(gen.generate(cfg, qparams, prompts, 5))
    assert toks.shape == (2, 5)
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()


def test_quantized_scoring_via_map_blocks():
    cfg = tr.tiny()
    params = tr.quantize_params(tr.init_params(cfg, seed=0))
    tokens, _ = tr.synthetic_batch(cfg, 6, 12, seed=1)
    df = tfs.frame_from_arrays({"tokens": tokens}, num_blocks=2)
    prog = tr.embed_program(cfg, params)
    out = tfs.map_blocks(lambda tokens: prog(tokens), df)
    emb = np.stack([r["embedding"] for r in out.collect()])
    assert emb.shape == (6, cfg.hidden)
    assert np.isfinite(emb).all()


def test_quantize_tree_idempotent():
    """Re-quantizing an already-quantized tree passes leaves through
    untouched (tree_map must not descend into QuantizedTensor and
    quantize its scale array)."""
    params = {"w": np.random.default_rng(4).standard_normal((16, 16)).astype(np.float32)}
    q1 = qt.quantize_tree(params)
    q2 = qt.quantize_tree(q1)
    assert isinstance(q2["w"], qt.QuantizedTensor)
    assert not isinstance(q2["w"].scale, qt.QuantizedTensor)
    np.testing.assert_array_equal(
        np.asarray(q2["w"].dequantize()), np.asarray(q1["w"].dequantize())
    )


def test_quantized_tree_checkpoints(tmp_path):
    """QuantizedTensor trees ride the npz checkpoint backend like any
    other params (int8 q + f32 scale are just pytree leaves)."""
    from tensorframes_tpu.checkpoint import Checkpointer

    cfg = tr.tiny()
    qparams = tr.quantize_params(tr.init_params(cfg, seed=0))
    ck = Checkpointer(str(tmp_path), backend="npz")
    ck.save(1, qparams)
    back = ck.restore(step=1, like=qparams)
    lq = qparams["layers"][0]["attn"]["qkv"]
    lb = back["layers"][0]["attn"]["qkv"]
    assert isinstance(lb, qt.QuantizedTensor)
    np.testing.assert_array_equal(np.asarray(lb.q), np.asarray(lq.q))
    np.testing.assert_array_equal(np.asarray(lb.scale), np.asarray(lq.scale))
    # restored tree scores identically
    tokens, _ = tr.synthetic_batch(cfg, 2, 8, seed=0)
    np.testing.assert_array_equal(
        np.asarray(tr.forward(cfg, qparams, tokens), np.float32),
        np.asarray(tr.forward(cfg, back, tokens), np.float32),
    )


def test_quantized_conv_models_close():
    """VGG/Inception int8 trees: same scoring path, close logits."""
    from tensorframes_tpu.models import inception as inc
    from tensorframes_tpu.models import vgg

    for mod in (vgg, inc):
        cfg = mod.tiny()
        params = mod.init_params(cfg, seed=0)
        qparams = mod.quantize_params(params)
        imgs = mod.synthetic_images(cfg, 2, seed=0)
        a = np.asarray(mod.forward(cfg, params, imgs), np.float32)
        b = np.asarray(mod.forward(cfg, qparams, imgs), np.float32)
        cos = (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos > 0.98, (mod.__name__, cos)
        assert qt.tree_nbytes(qparams) < 0.5 * qt.tree_nbytes(params)


def test_int8_frozen_weights_survive_to_executable():
    """VERDICT r2 #7: make the int8 claim a NUMBER before TPU validates
    it. Round 3 found the serious bug hiding here: with weights embedded
    as HLO literals, XLA CONSTANT-FOLDED the dequantize back into a full
    f32 weight — the quantized program had byte-identical cost to f32,
    i.e. int8 did nothing. The fix is two-part: (a) the executor hoists
    program constants to runtime arguments (config.hoist_constants), and
    (b) MatMul/Conv consume QuantizedTensor natively — int8 enters the
    contraction, the per-channel scale multiplies the output, no f32
    weight is ever materialized.

    This test pins the structural facts any backend must preserve:
    the int8 weight reaches the compiled executable as ``s8`` (not
    folded), the program's hoisted parameter bytes are ~4x smaller, and
    the numerics hold. (The HBM *traffic* number is a TPU measurement —
    the CPU backend materializes the convert regardless; see BASELINE.md
    TPU checklist.)"""
    import numpy as np
    import jax

    from tensorframes_tpu.graphdef import GraphNode, _Attr, program_from_graphdef

    rng = np.random.default_rng(0)
    w = rng.standard_normal((512, 512)).astype(np.float32)

    def build(quant):
        dtype_a = _Attr()
        dtype_a.type = 1
        shape_a = _Attr()
        shape_a.shape = [-1, 512]
        val_a = _Attr()
        val_a.tensor = w
        nodes = [
            GraphNode("x", "Placeholder", [], {"dtype": dtype_a, "shape": shape_a}),
            GraphNode("w", "Const", [], {"value": val_a}),
            GraphNode("m", "MatMul", ["x", "w"], {}),
        ]
        return program_from_graphdef(nodes, fetches=["m"], quantize_weights=quant)

    def hoisted_compile(prog):
        from tensorframes_tpu.program import HoistedProgram

        hp = HoistedProgram(
            prog.fn, {"x": jax.ShapeDtypeStruct((8, 512), np.float32)}
        )
        return hp.aot_compile().as_text(), hp.const_bytes()

    hlo_f32, bytes_f32 = hoisted_compile(build(False))
    hlo_q, bytes_q = hoisted_compile(build(True))
    assert "s8[512,512]" in hlo_q, "int8 weight was folded out of the HLO"
    assert "s8[" not in hlo_f32
    # 1 MiB f32 weight vs 256 KiB int8 + 2 KiB f32 scales ≈ 4.0x
    assert bytes_f32 > 3.9 * bytes_q, (bytes_f32, bytes_q)
    # and the programs still agree numerically
    x = rng.standard_normal((4, 512)).astype(np.float32)
    got_q = np.asarray(build(True).fn({"x": x})["m"])
    want = x @ w
    np.testing.assert_allclose(got_q, want, rtol=0.05, atol=0.05 * np.abs(want).max())


def test_fused_dequant_matmul_matches_dequantize():
    """ops/quantize.matmul: (x @ q) * s must equal x @ (q * s) — the
    per-output-channel scale commutes out of the contraction, which is
    what lets int8 weights stream from HBM without a materialized
    dequantized copy (VERDICT r3 #4)."""
    import jax.numpy as jnp
    import numpy as np

    from tensorframes_tpu.ops import quantize as qz

    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 48)).astype(np.float32)
    x = rng.standard_normal((16, 64)).astype(np.float32)
    qt = qz.quantize(w)
    got = qz.matmul(jnp.asarray(x), qt)
    want = jnp.asarray(x) @ qt.dequantize(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    # plain weights pass straight through (cast to x.dtype)
    got_plain = qz.matmul(jnp.asarray(x), w)
    np.testing.assert_allclose(
        np.asarray(got_plain), x @ w, rtol=1e-6, atol=1e-6
    )
    # a scale layout that spans contracted axes falls back to explicit
    # dequantize (correctness over fusion)
    qt_row = qz.quantize(w, channel_axis=0)  # scale [in, 1]: no commute
    got_row = qz.matmul(jnp.asarray(x), qt_row)
    want_row = jnp.asarray(x) @ qt_row.dequantize(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got_row), np.asarray(want_row), rtol=2e-5, atol=2e-5
    )


def test_pallas_int8_matmul_matches_structural_fusion():
    """Round 5 (VERDICT r4 #3 'consider'): the pallas in-kernel-dequant
    matmul must agree with quantize.matmul's structural fusion across
    shapes (incl. non-tile-multiple dims and 3-D activations), run in
    interpret mode on CPU. ``chip_smoke.py`` compiles it on the chip
    and reports the outcome; its speed has no cell yet."""
    import jax.numpy as jnp

    from tensorframes_tpu.ops import quantize as qz

    rng = np.random.default_rng(0)
    for (m_shape, k, n) in [((4,), 96, 160), ((2, 3), 128, 256),
                            ((5,), 70, 100)]:
        x = jnp.asarray(
            rng.standard_normal((*m_shape, k)), jnp.float32
        )
        w = qz.quantize(
            jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        )
        want = qz.matmul(x, w)
        got = qz.matmul_pallas_int8(x, w, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )


def test_pallas_int8_matmul_gate_defaults_off():
    """The kernel is opt-in until hardware adjudicates it: with the
    config flag off (default), quantize.matmul must not attempt pallas
    on any backend."""
    import jax.numpy as jnp

    from tensorframes_tpu.config import get_config
    from tensorframes_tpu.ops import quantize as qz

    assert get_config().pallas_int8_matmul is False
    x = jnp.ones((2, 32), jnp.float32)
    w = qz.quantize(jnp.ones((32, 64), jnp.float32))
    assert not qz._pallas_int8_eligible(x, w)
    # and the default path still answers
    out = qz.matmul(x, w)
    assert out.shape == (2, 64)
