"""int8 KV-cache decode: cost-model break-even analysis (VERDICT r4 #3).

The int8 thesis: single-token decode is HBM-bandwidth-bound, so halving
(vs bf16) / quartering (vs f32) the bytes of the two traffic terms that
dominate — the weights (read once per step) and the KV cache (read in
full per step) — buys wall-clock roughly in proportion, while the
quantize/dequantize ALU work rides for free under the memory roofline.
On CPU there is no such roofline gap, which is why the CPU bench shows
int8kv LOSING (r4: 18.7e3 vs 31.6e3 tok/s) — overhead with no byte win
to buy it back.

This script makes the byte claim checkable WITHOUT hardware counters:
it lowers one cached decode step (`generation._forward_cached` + LM
head — the exact fn `generate`'s scan body runs) for f32 and int8kv
variants and reads XLA's cost model (`compiled.cost_analysis()`s
"bytes accessed"), alongside the analytic traffic model
(weights + kv_cache_nbytes). Run on any backend; the TPU numbers are
the ones that matter (none recorded yet — PERF.md is where a chip run
of this goes).

CAVEAT on the cost-model column: XLA charges every
dynamic_update_slice as a full-array write at cost-analysis time —
in-place aliasing happens later, at buffer assignment — so the cache
updates over-count by roughly (num_layers × cache bytes) per step.
The ANALYTIC ratio is the defensible HBM-roofline bound; the
cost-model ratio brackets it from above. (Round-5 change: collapsing
the per-layer slice-out/.at[li].set chains to single 5-D DUS ops cut
the charged int8 bytes 7.0 GB → 2.7 GB for gpt_small.)

Usage: [JAX_PLATFORMS=cpu] python dev/int8_breakeven.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def step_bytes(cfg, batch: int, horizon: int, kv_quant: bool,
               int8_weights: bool):
    """(cost-model bytes, analytic weight bytes, analytic cache bytes)
    for ONE cached decode step at position horizon-1."""
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.models import transformer as tr

    params = tr.init_params(cfg, seed=0)
    if int8_weights:
        params = tr.quantize_params(params)
    cache = gen.init_kv_cache(cfg, batch, length=horizon, quant=kv_quant)
    tok = jnp.zeros((batch, 1), jnp.int32)

    def one_step(p, c, t):
        hs, c2 = gen._forward_cached(cfg, p, t, c, horizon - 1)
        return gen._logits(cfg, p, hs[:, -1]), c2

    lowered = jax.jit(one_step).lower(params, cache, tok)
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, list):  # older jax returns [dict]
        ca = ca[0]
    model_bytes = float(ca.get("bytes accessed", float("nan")))

    w_bytes = sum(
        int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(params)
    )
    c_bytes = gen.kv_cache_nbytes(cache)
    return model_bytes, w_bytes, c_bytes


def main() -> int:
    from tensorframes_tpu.models import generation as gen

    print(f"# backend={jax.default_backend()} devices={jax.devices()}")
    rows = []
    for name, cfg, batch, horizon in (
        ("gpt_tiny", gen.gpt_tiny(), 8, 48),
        ("gpt_small", gen.gpt_small(), 8, 1024),
    ):
        f32 = step_bytes(cfg, batch, horizon, kv_quant=False,
                         int8_weights=False)
        q = step_bytes(cfg, batch, horizon, kv_quant=True,
                       int8_weights=True)
        ratio_model = f32[0] / q[0] if q[0] else float("nan")
        ratio_analytic = (f32[1] + f32[2]) / (q[1] + q[2])
        rows.append((name, batch, horizon, f32, q, ratio_model,
                     ratio_analytic))
        print(
            f"# int8_breakeven | {name} b={batch} S={horizon} "
            f"cost_model_bytes f32={f32[0] / 1e6:.1f}MB "
            f"int8={q[0] / 1e6:.1f}MB ratio={ratio_model:.2f}x ; "
            f"analytic (weights+cache) f32={(f32[1] + f32[2]) / 1e6:.1f}MB "
            f"int8={(q[1] + q[2]) / 1e6:.1f}MB ratio={ratio_analytic:.2f}x"
        )
    print(
        "# int8_breakeven | reading: the ratio bounds the HBM-roofline "
        "decode speedup; int8 pays on a device where decode is "
        "bandwidth-bound AND the ratio-sized byte saving exceeds the "
        "quant/dequant ALU cost. CPU has no such roofline — the CPU "
        "int8kv decode number is an overhead measurement by design."
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
