"""The served sparse-expert decoder (``models/sparse_decoder.py``) through
the decode engine's seam (``models/served.py``): rehearsal sizes, float32
activations, on the CPU.

What must hold: prefill then decode through the two-kind pool reproduce
the plain reference's full forward pass (``benchmark/reference/
mellum2.py``, its keys and values rounded to the 8 bits the cache holds)
on logits (1e-5 at the median, 5e-3 where an int8 rounding boundary
is crossed), on contexts past the window, past the ring's wrap
and past YaRN's original range; a batched step equals a solo step bit
for bit per slot; the kernel on the interpreter equals its emulation
bitwise and the XLA chain to float tolerance, for grouped heads with
and without a window; the expert shares of a layer split over four
holders add up to the whole layer's result; no token is dropped and a
token's weights sum to 1 under a router skewed onto one expert; the
YaRN frequencies equal hand-computed values; a sequence never holds
more window pages than the ring has entries, and preempt-then-replay
gives the same tokens; a model without the suffix-prefill and page-op
programs is refused ``prefix_cache`` / ``kv_swap`` at registration, and
one whose page kinds carry names without metric series is refused too; a
warmed engine compiles nothing; the expert matmuls' kernel
(``kernels/expert_matmul.py``, on the interpreter) equals the
``lax.ragged_dot`` chain, row for row whatever else is in the batch, and
the engine counts its dispatches.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import mellum2 as ref  # noqa: E402
from tensorframes_tpu.kernels import decode_attention as kda  # noqa: E402
from tensorframes_tpu.models import moe  # noqa: E402
from tensorframes_tpu.models import sparse_decoder as sd  # noqa: E402
from tensorframes_tpu.models.served import ServedModel  # noqa: E402
from tensorframes_tpu.serving import (  # noqa: E402
    DecodeConfig,
    DecodeEngine,
    PagedKVPool,
    PoolAccountingError,
    Server,
)
from tensorframes_tpu.serving import metrics as sm  # noqa: E402

PAGE = 4
SEED = 3
#: logits, float32 program against the reference with int8 keys and values
TOL = 5e-3

# the benchmark configuration's rehearsal block, under its published keys
CONFIG = {
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 97, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 0.1 * math.log(4.0) + 1.0},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
    },
}


@pytest.fixture(scope="module")
def model():
    """The tiny configuration with the reference's own float32 draws."""
    cfg = sd.tiny()
    outer = ref.make_outer_weights(CONFIG, SEED)
    params = dict(outer)
    params["layers"] = [ref.make_layer_weights(CONFIG, SEED, l)
                        for l in range(cfg.num_layers)]
    return cfg, params


def _logits_head(cfg, params, x):
    """In ``_head``'s place: the logits themselves, so that a test reads
    what the step's argmax sees."""
    h = sd._rms_norm(x, params["final_norm"], cfg.rms_eps)
    return jnp.matmul(h, params["head"], preferred_element_type=jnp.float32)


class Driven:
    """The model's two programs over a two-kind pool, driven by hand as
    the engine drives them, with logits in the tokens' place."""

    def __init__(self, model, monkeypatch, horizon=64, pages=80, slots=4):
        cfg, self.params = model
        monkeypatch.setattr(sd, "_head", _logits_head)
        self.served = cfg.served_model(PAGE, horizon)
        self.kinds = self.served.kinds
        self.pool = PagedKVPool(
            self.served, pages, PAGE,
            extra_pages={"window": 1 + slots * self.kinds[1].entries})
        self.prefill = jax.jit(self.served.prefill)
        self.step = jax.jit(self.served.step)

    def join(self, seq, prompt, bucket=32):
        for kind, n in self.pool.demand(len(prompt)).items():
            self.pool.alloc(seq, n, kind)
        padded = np.zeros(bucket, np.int32)
        padded[:len(prompt)] = prompt
        self.pool.columns, logits = self.prefill(
            self.params, self.pool.columns, padded, np.int32(len(prompt)),
            *self.pool.tables(seq))
        return np.asarray(logits)

    def decode(self, rows, bucket):
        """One step over ``rows`` = [(seq, token, pos)]; logits a row."""
        tokens = np.zeros(bucket, np.int32)
        pos = np.zeros(bucket, np.int32)
        tables = [np.zeros((bucket, k.entries), np.int32)
                  for k in self.kinds]
        for i, (seq, token, at) in enumerate(rows):
            for k in self.kinds:
                if k.pages_for(at + 1, PAGE) > self.pool.held(seq, k.name):
                    self.pool.alloc(seq, 1, k.name)
            tokens[i], pos[i] = token, at
            for table, k in zip(tables, self.kinds):
                table[i] = self.pool.table(seq, k.name)
        self.pool.columns, logits, stats = self.step(
            self.params, self.pool.columns, tokens, pos, *tables)
        self.pool.check()
        return np.asarray(logits)[:len(rows)], stats


def test_prefill_then_decode_match_the_reference_past_window_wrap_and_yarn(
        model, monkeypatch):
    """21 prompt tokens then 29 decoded: contexts reach 50 positions,
    past the 8-position window, five times round the 3-entry ring, and
    past the 16 positions YaRN's scaling starts from."""
    run = Driven(model, monkeypatch)
    rng = np.random.default_rng(0)
    plen, new = 21, 30
    toks = rng.integers(0, 97, plen + new).astype(np.int32)
    got = [run.join(1, toks[:plen])]
    for i in range(new - 1):
        logits, _ = run.decode([(1, toks[plen + i], plen + i)], bucket=2)
        got.append(logits[0])
    positions = np.arange(plen - 1, plen + new - 1)
    want = np.asarray(next(ref.logits_at(
        CONFIG, SEED, [(toks, positions)], quant="int8kv")))
    # float32 agrees to ~1e-6 except where a key or value element lies
    # on an int8 rounding boundary and the two round it apart (one step
    # of 1/127 of a head's range moves a logit by ~1e-3)
    diff = np.abs(np.stack(got) - want)
    assert np.median(diff) < 1e-5 and diff.max() < TOL
    assert run.pool.held(1, "window") == run.kinds[1].entries == 3
    assert run.pool.held(1, "full") == -(-(plen + new - 1) // PAGE)
    # and each planted fault of the reference is far outside that
    for fault in ref.FAULTS:
        off = np.asarray(next(ref.logits_at(
            CONFIG, SEED, [(toks, positions)], quant="int8kv", fault=fault)))
        assert np.abs(np.stack(got) - off).max() > 10 * TOL, fault


def test_batched_step_equals_solo_bit_for_bit(model, monkeypatch):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 97, n).astype(np.int32) for n in (5, 13, 22)]
    nxt = [int(t) for t in rng.integers(0, 97, 3)]

    def fresh():
        run = Driven(model, monkeypatch)
        for seq, p in enumerate(prompts, start=1):
            run.join(seq, p)
        return run

    batched, stats = fresh().decode(
        [(s + 1, nxt[s], len(prompts[s])) for s in range(3)], bucket=4)
    for s in range(3):
        solo, _ = fresh().decode([(s + 1, nxt[s], len(prompts[s]))], bucket=2)
        assert np.array_equal(solo[0], batched[s]), s
    counts = np.asarray(stats["expert_counts"])
    # three live rows, two experts each, in each of the four layers; the
    # padding row is not counted
    assert counts.shape == (4, 8) and (counts.sum(axis=1) == 6).all()


@pytest.mark.parametrize("nq,nkv,hd,page,entries,window,ring", [
    (4, 2, 16, 4, 9, None, False),
    (4, 2, 16, 4, 5, 12, True),
    (8, 2, 16, 4, 9, 10, False),
    (32, 4, 128, 16, 9, 64, True),
])
def test_kernel_equals_emulation_and_chain_for_grouped_heads(
        nq, nkv, hd, page, entries, window, ring):
    rng = np.random.default_rng(nq + entries)
    S, P, L = 4, 50, 2
    group = nq // nkv

    def pages(kind):
        return jnp.asarray(rng.integers(-127, 128, (P, L, page, nkv * hd)),
                           kind)

    def scales():
        base = rng.uniform(0.01, 0.02, (P, L, page, nkv))
        lanes = np.repeat(base, group, axis=-1)
        return jnp.asarray(np.pad(
            lanes, ((0, 0),) * 3 + ((0, kda.SCALE_LANES - nq),),
            constant_values=1.0), jnp.float32)

    k, v, ks, vs = pages(jnp.int8), pages(jnp.int8), scales(), scales()
    tables = jnp.asarray(
        1 + rng.permutation(P - 1)[:S * entries].reshape(S, entries),
        jnp.int32)
    top = 3 * entries * page if ring else entries * page - 1
    pos = jnp.asarray(rng.integers(0, top, S), jnp.int32)
    pos = pos.at[0].set(0).at[1].set(top - 1)
    q = jnp.asarray(rng.normal(size=(S, nq, hd)), jnp.float32)
    args = (q, k, v, ks, vs, 1, tables, pos)
    kernel = kda.paged_decode_attention(*args, interpret=True,
                                        window=window, ring=ring)
    same = kda.paged_attention_emulation(*args, window=window, ring=ring)
    chain = kda.paged_attention_reference(*args, window=window, ring=ring)
    assert np.array_equal(np.asarray(kernel), np.asarray(same))
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(chain),
                               atol=2e-6, rtol=0)
    walked = kda.pages_walked(np.asarray(pos), page, entries, window)
    if window is not None:
        # the walk covers the window's pages and never the whole context
        reach = np.asarray(pos) // page + 1
        first = np.maximum(np.asarray(pos) - (window - 1), 0) // page
        assert (walked >= reach - first).all()
        assert (walked <= window // page + 2 * kda.chunk_pages(
            page, entries)).all()


def test_band_attention_equals_dense():
    from tensorframes_tpu.ops import attention as att

    rng = np.random.default_rng(5)
    for s, blk, window in ((37, 8, None), (37, 8, 10), (64, 16, 16),
                           (5, 512, 3)):
        q = jnp.asarray(rng.normal(size=(1, 4, s, 16)), jnp.float32)
        k = jnp.asarray(rng.integers(-127, 128, (1, 2, s, 16)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, (1, 2, s, 16)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.02, (1, 2, s)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.02, (1, 2, s)), jnp.float32)
        got = att.blockwise_attention(q, k, v, causal=True, block_size=blk,
                                      window=window, k_scale=ks, v_scale=vs)
        kk = jnp.repeat(k.astype(jnp.float32) * ks[..., None], 2, axis=1)
        vv = jnp.repeat(v.astype(jnp.float32) * vs[..., None], 2, axis=1)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / 4.0
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        mask = (j <= i) & ((j > i - window) if window else True)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(mask, sc, -1e30), axis=-1), vv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=0)
    with pytest.raises(ValueError, match="causal"):
        att.blockwise_attention(q, k, v, causal=False, window=3)


def _expert_layer(rng, tokens=11, d=16, f=8, experts=8, top_k=3,
                  router=None):
    h = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    w = {n: jnp.asarray(rng.normal(size=s), jnp.float32) for n, s in (
        ("gate", (experts, d, f)), ("up", (experts, d, f)),
        ("down", (experts, f, d)))}
    if router is None:
        router = jnp.asarray(rng.normal(size=(d, experts)), jnp.float32)
    ids, weights = moe.route_topk(h, router, top_k)
    return h, w, ids, weights


def test_expert_shares_of_four_holders_add_up_to_the_whole_layer():
    h, w, ids, weights = _expert_layer(np.random.default_rng(2))
    whole = moe.routed_experts(h, ids, weights, w["gate"], w["up"],
                               w["down"])
    parts = [moe.routed_experts(
        h, ids, weights, w["gate"][lo:lo + 2], w["up"][lo:lo + 2],
        w["down"][lo:lo + 2], first_expert=lo) for lo in (0, 2, 4, 6)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=1e-4, rtol=1e-5)
    # and the whole is the plain sum over each token's own experts
    want = np.zeros_like(np.asarray(whole))
    for t in range(h.shape[0]):
        for e, p in zip(np.asarray(ids[t]), np.asarray(weights[t])):
            a = jax.nn.silu(h[t] @ w["gate"][e]) * (h[t] @ w["up"][e])
            want[t] += p * np.asarray(a @ w["down"][e])
    np.testing.assert_allclose(np.asarray(whole), want, atol=1e-4,
                               rtol=1e-5)


@pytest.fixture
def kernels_forced():
    """Select the kernels on the CPU (the pallas interpreter)."""
    from tensorframes_tpu.config import configure, get_config

    cfg = get_config()
    was = cfg.pallas_force, cfg.pallas_kernels
    configure(pallas_force=True, pallas_kernels=True)
    try:
        yield
    finally:
        configure(pallas_force=was[0], pallas_kernels=was[1])


@pytest.mark.parametrize("tokens,d,f,experts,top_k,held", [
    (11, 16, 8, 8, 3, None),       # one row tile, padded from 33 pairs
    (200, 16, 8, 8, 2, None),      # 400 pairs: four row tiles
    (64, 1792, 128, 4, 2, None),   # two k tiles of 896 on the way in
    (64, 128, 1792, 4, 2, None),   # two n tiles in, two k tiles out
    (40, 16, 8, 8, 3, (2, 3)),     # a holder of three: absent pairs last
    (40, 16, 8, 16, 1, None),      # top-1 of 16: most groups empty
])
def test_expert_kernel_equals_the_ragged_dot_chain(
        kernels_forced, tokens, d, f, experts, top_k, held):
    """``routed_experts`` over the grouped-matmul kernel (the
    interpreter) against the same call over ``lax.ragged_dot``: the
    same products folded in float32, tile by tile."""
    from tensorframes_tpu import kernels
    from tensorframes_tpu.config import configure

    h, w, ids, weights = _expert_layer(
        np.random.default_rng(tokens + d), tokens, d, f, experts, top_k)
    lo, n = held or (0, experts)
    args = (h, ids, weights, w["gate"][lo:lo + n], w["up"][lo:lo + n],
            w["down"][lo:lo + n], lo)
    assert kernels.selectable("expert_matmul")
    got = np.asarray(moe.routed_experts(*args))
    configure(pallas_force=False)
    assert not kernels.selectable("expert_matmul")
    want = np.asarray(moe.routed_experts(*args))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max(),
                               rtol=1e-5)


def test_expert_kernel_rows_do_not_depend_on_the_batch(kernels_forced):
    """Under the kernel too a token's result is its own: the first rows
    of a batch equal the same rows alone, bit for bit."""
    h, w, ids, weights = _expert_layer(np.random.default_rng(9), tokens=150)
    whole = np.asarray(moe.routed_experts(
        h, ids, weights, w["gate"], w["up"], w["down"]))
    for n in (1, 5):
        alone = np.asarray(moe.routed_experts(
            h[:n], ids[:n], weights[:n], w["gate"], w["up"], w["down"]))
        assert np.array_equal(alone, whole[:n])


def test_expert_kernel_tiles_at_the_published_widths():
    from tensorframes_tpu.kernels import expert_matmul as em

    # gate / up: 2304 -> 896; down: 896 -> 2304 (what the v5e probe
    # measured, PERF.md section 5)
    assert em.tiling(2304, 896) == (128, 768, 896)
    assert em.tiling(896, 2304) == (128, 896, 768)
    assert em.tiling(64, 32) == (128, 64, 32)


def test_engine_counts_the_expert_kernel_s_dispatches(model, kernels_forced):
    from tensorframes_tpu import kernels

    cfg, params = model
    eng = DecodeEngine("k", cfg, params, DecodeConfig(
        max_slots=2, page_size=PAGE, max_prompt_len=8, max_new_tokens=4))
    assert eng._step_kernels == ("decode_attn", "expert_matmul")
    before = {k: kernels.DISPATCHES[k].value for k in eng._step_kernels}
    eng.start()
    try:
        out = eng.submit({"prompt": np.arange(5, dtype=np.int32),
                          "max_new_tokens": 3}).result(300)
        assert np.asarray(out["tokens"]).shape == (1, 3)
    finally:
        eng.stop()
    for k, n in before.items():
        assert kernels.DISPATCHES[k].value > n
        assert kernels.INTERPRET_FALLBACKS[k].value > 0


@pytest.mark.parametrize("names,why", [
    (("full", "ring"), "ring"),          # a name with no series
    (("window", "full"), "may follow"),  # no free-pages gauge for it
    (("full", "full"), "once"),
])
def test_page_kinds_without_series_are_refused_at_registration(
        model, names, why):
    """The per-kind counters and gauges are registered at import for a
    closed set of kind names: a model with several kinds under other
    names fails where it is registered, not in the engine's thread."""
    import dataclasses

    cfg, params = model
    served = cfg.served_model(PAGE, 12)
    kinds = tuple(dataclasses.replace(k, name=n)
                  for k, n in zip(served.kinds, names))
    with pytest.raises(ValueError, match=why):
        Server().register_decode(
            "x", dataclasses.replace(served, kinds=kinds), params,
            DecodeConfig(max_slots=2, page_size=PAGE, max_prompt_len=8,
                         max_new_tokens=4))


def test_skewed_router_drops_no_token_and_weights_sum_to_one():
    rng = np.random.default_rng(4)
    d, experts = 16, 8
    # every token's largest logit is expert 5's, by a wide margin
    router = np.asarray(rng.normal(size=(d, experts)), np.float32) * 0.01
    h, w, _, _ = _expert_layer(rng, tokens=40, router=jnp.asarray(router))
    h = jnp.abs(h)
    router[:, 5] += 1.0
    ids, weights = moe.route_topk(h, jnp.asarray(router), 3)
    assert (np.asarray(ids)[:, 0] == 5).all()
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0,
                               atol=1e-6)
    counts = np.asarray(moe.expert_counts(ids, jnp.ones(40, bool), experts))
    assert counts.sum() == 40 * 3 and counts[5] == 40
    got = moe.routed_experts(h, ids, weights, w["gate"], w["up"], w["down"])
    one = 7
    want = sum(float(p) * np.asarray(
        (jax.nn.silu(h[one] @ w["gate"][e]) * (h[one] @ w["up"][e]))
        @ w["down"][e])
        for e, p in zip(np.asarray(ids[one]), np.asarray(weights[one])))
    np.testing.assert_allclose(np.asarray(got[one]), want, atol=1e-4,
                               rtol=1e-5)
    # not renormalised: the softmax's own weights, which sum to less
    _, raw = moe.route_topk(h, jnp.asarray(router), 3, renormalize=False)
    assert (np.asarray(raw).sum(axis=1) < 1.0).all()


def test_yarn_frequencies_equal_hand_computed_values():
    """The published full-layer parameters: base 500,000 over a 128
    head, factor 16 over 8,192 positions, beta 32 and 1. By hand: d(32)
    = 128 ln(8192 / 64 pi) / (2 ln 500000) = 18.08, d(1) = 34.98, so the
    ramp runs from dimension 18 to 35."""
    spec = sd.RopeSpec(500000.0, factor=16.0, original_max=8192,
                       beta_fast=32.0, beta_slow=1.0,
                       attention_factor=1.2772588722239782)
    got = sd.rope_inv_freq(spec, 128)
    plain = 500000.0 ** (-2.0 * np.arange(64) / 128)
    assert got.shape == (64,)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(got[35:], plain[35:] / 16.0, rtol=1e-12)
    for i in (19, 26, 34):
        ramp = (i - 18) / 17.0
        np.testing.assert_allclose(
            got[i], (1 - ramp) * plain[i] + ramp * plain[i] / 16.0,
            rtol=1e-12)
    assert abs(0.1 * math.log(16.0) + 1.0 - spec.attention_factor) < 1e-12
    # a sliding layer's are the plain ones, and the reference's own
    # derivation agrees on both
    np.testing.assert_allclose(
        sd.rope_inv_freq(sd.RopeSpec(500000.0), 128), plain, rtol=1e-15)
    published = {"head_dim": 128, "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}}
    np.testing.assert_allclose(ref.inv_freq(published, "full"), got,
                               rtol=1e-12)
    np.testing.assert_allclose(ref.inv_freq(published, "sliding"), plain,
                               rtol=1e-12)


def test_two_kind_pool_accounting(model):
    cfg, _ = model
    served = cfg.served_model(PAGE, 64)
    ring = served.kinds[1].entries
    pool = PagedKVPool(served, 20, PAGE,
                       extra_pages={"window": 1 + 2 * ring})
    assert pool.demand(5) == {"full": 2, "window": 2}
    assert pool.demand(50) == {"full": 13, "window": ring}
    for kind, n in pool.demand(50).items():
        pool.alloc(7, n, kind)
    pool.check()
    assert pool.held(7, "window") == ring and pool.held(7, "full") == 13
    assert pool.table(7, "window").shape == (ring,)
    assert (pool.table(7, "window") > 0).all()
    with pytest.raises(PoolAccountingError, match="window"):
        pool.alloc(7, 1, "window")  # the ring is whole
    assert pool.allocatable("window") == ring
    assert sorted(pool.columns) == ["full", "window"]
    assert pool.columns["window"]["k"].shape[:2] == (1 + 2 * ring, 3)
    assert pool.columns["full"]["k"].shape[:2] == (20, 1)
    pool.free_seq(7)
    pool.check()
    assert pool.allocatable("window") == 2 * ring
    with pytest.raises(PoolAccountingError, match="one page kind"):
        pool.page_shapes()


def _served_gap(tokens_by_prompt):
    """The widest gap by which a served token's logit lies below the
    reference's best at its position (the benchmark's comparison)."""
    rows, served = [], []
    for prompt, got in tokens_by_prompt:
        plen, new = len(prompt), len(got)
        seq = np.zeros(40, np.int32)
        seq[:plen] = prompt
        seq[plen:plen + new - 1] = got[:-1]
        rows.append((seq, plen - 1 + np.arange(new)))
        served.append(got)
    worst = 0.0
    for logits, got in zip(
            ref.logits_at(CONFIG, SEED, rows, quant="int8kv"), served):
        logits = np.asarray(logits)
        worst = max(worst, float(
            (logits.max(axis=-1) - logits[np.arange(len(got)), got]).max()))
    return worst


def _prompts(n, lo, hi, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(lo, hi + 1))).astype(
        np.int32) for _ in range(n)]


def test_engine_serves_the_reference_tokens_and_compiles_nothing_warm(model):
    from tensorframes_tpu.ops.executor import _JIT_MISSES

    cfg, params = model
    srv = Server()
    eng = srv.register_decode("sparse", cfg, params, DecodeConfig(
        max_slots=4, page_size=PAGE, max_prompt_len=24, max_new_tokens=8))
    srv.start()
    try:
        routed0 = sm.MOE_TOKENS_ROUTED.value
        walked0 = {k: sm.DECODE_ATTN_PAGES_WALKED_BY_KIND[k].value
                   for k in sm.DECODE_PAGE_KINDS}
        context0 = sm.DECODE_ATTN_PAGES_CONTEXT["window"].value
        plain0 = sm.DECODE_ATTN_PAGES_WALKED.value
        miss0 = _JIT_MISSES.value
        prompts = _prompts(9, 3, 24, seed=11)
        futs = [srv.submit("sparse", {"prompt": p, "max_new_tokens": 8})
                for p in prompts]
        outs = [np.asarray(f.result(300)["tokens"])[0] for f in futs]
        assert int(_JIT_MISSES.value - miss0) == 0
        assert _served_gap(zip(prompts, outs)) < TOL
        # solo, one at a time: the same tokens bit for bit
        for p, out in list(zip(prompts, outs))[:3]:
            again = srv.submit("sparse", {"prompt": p, "max_new_tokens": 8})
            assert np.array_equal(
                np.asarray(again.result(300)["tokens"])[0], out)
        assert sm.MOE_TOKENS_ROUTED.value > routed0
        assert sm.MOE_EXPERT_LOAD_MAX.value > 0
        for k in sm.DECODE_PAGE_KINDS:
            assert sm.DECODE_ATTN_PAGES_WALKED_BY_KIND[k].value > walked0[k]
        assert sm.DECODE_ATTN_PAGES_CONTEXT["window"].value > context0
        # the one-kind series is another model's: this one leaves it be
        assert sm.DECODE_ATTN_PAGES_WALKED.value == plain0
    finally:
        srv.stop()
    eng.pool.check()
    assert eng.pool.allocatable("window") == 4 * eng.model.kinds[1].entries


def test_window_pages_never_exceed_the_ring_and_preemption_replays(model):
    cfg, params = model
    # the full kind: one horizon (8 pages) and 6 spare; four 20-24 token
    # prompts with 8 new tokens each want up to 32 pages of it
    eng = DecodeEngine("sparse_small", cfg, params, DecodeConfig(
        max_slots=4, page_size=PAGE, max_prompt_len=24, max_new_tokens=8,
        num_pages=1 + 8 + 6))
    ring = eng.model.kinds[1].entries
    most = {"window": 0}
    alloc = eng.pool.alloc

    def watched(seq, n, kind=None):
        got = alloc(seq, n, kind)
        most["window"] = max(most["window"], eng.pool.held(seq, "window"))
        return got

    eng.pool.alloc = watched
    eng.start()
    try:
        pre0 = sm.DECODE_PREEMPTIONS.value
        prompts = _prompts(6, 20, 24, seed=12)
        futs = [eng.submit({"prompt": p, "max_new_tokens": 8})
                for p in prompts]
        outs = [np.asarray(f.result(600)["tokens"])[0] for f in futs]
        assert sm.DECODE_PREEMPTIONS.value > pre0
        assert most["window"] == ring
        # a preempted sequence resumed by replay to the same tokens: the
        # engine checks every replayed token itself, and the answers are
        # the reference's
        assert _served_gap(zip(prompts, outs)) < TOL
        solo = eng.submit({"prompt": prompts[0], "max_new_tokens": 8})
        assert np.array_equal(
            np.asarray(solo.result(300)["tokens"])[0], outs[0])
    finally:
        eng.stop(drain=True, timeout=120)
    eng.pool.check()


def test_the_engine_prefills_one_prompt_a_dispatch(model):
    """The block offers no packed prefill: its engine warms the
    one-sequence ladder and makes one prefill dispatch a join, so the
    segments counter grows by one a dispatch."""
    from tensorframes_tpu.compilecache import serving_row_buckets

    cfg, params = model
    eng = DecodeEngine("sparse_solo", cfg, params, DecodeConfig(
        max_slots=4, page_size=PAGE, max_prompt_len=24, max_new_tokens=4))
    assert eng.model.packed_prefill is None and eng._packed_prefill is None
    assert eng._prefill_buckets == serving_row_buckets(24)
    eng.start()
    try:
        steps0 = sm.DECODE_STEPS["prefill"].value
        segs0 = sm.DECODE_PREFILL_SEGMENTS.value
        futs = [eng.submit({"prompt": p, "max_new_tokens": 4})
                for p in _prompts(6, 3, 24, seed=13)]
        for f in futs:
            f.result(300)
        assert sm.DECODE_STEPS["prefill"].value - steps0 == 6
        assert sm.DECODE_PREFILL_SEGMENTS.value - segs0 == 6
    finally:
        eng.stop(drain=True, timeout=120)


def test_register_decode_refuses_tiers_the_model_has_no_programs_for(model):
    cfg, params = model
    srv = Server()
    with pytest.raises(ValueError, match="prefix_cache=True needs"):
        srv.register_decode("a", cfg, params, DecodeConfig(
            max_slots=2, page_size=PAGE, max_prompt_len=8, max_new_tokens=4,
            prefix_cache=True))
    with pytest.raises(ValueError, match="kv_swap=True needs"):
        srv.register_decode("b", cfg, params, DecodeConfig(
            max_slots=2, page_size=PAGE, max_prompt_len=8, max_new_tokens=4,
            kv_swap=True))
    # a ServedModel itself is taken as it is, and what cannot be served
    # says why
    served = cfg.served_model(PAGE, 12)
    assert isinstance(served, ServedModel) and served.suffix_prefill is None
    eng = DecodeEngine("c", served, params, DecodeConfig(
        max_slots=2, page_size=PAGE, max_prompt_len=8, max_new_tokens=4))
    assert [k.name for k in eng.model.kinds] == ["full", "window"]
    eng.stop()
    with pytest.raises(TypeError, match="served_model"):
        DecodeEngine("d", object(), params, DecodeConfig())
    # a packed prefill takes one table a prompt: two kinds cannot give it
    with pytest.raises(ValueError, match="packed_prefill"):
        dataclasses.replace(served, packed_prefill=served.prefill)


def test_new_decode_metrics_preregistered():
    from tensorframes_tpu.observability.metrics import REGISTRY

    names = {d["name"] for d in REGISTRY.snapshot()}
    for name in ("tftpu_moe_tokens_routed_total",
                 "tftpu_moe_expert_load_max_total",
                 "tftpu_decode_attn_pages_context_total",
                 "tftpu_decode_free_window_pages"):
        assert name in names, name
