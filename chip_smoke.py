#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --rehearsal     # tiny sizes on the CPU (wiring only)

Drives the main path once through the public entry points (``import
tensorframes_tpu as tfs``, ``Server``, ``serve_http``) at the full width
of models the repo already has — the source paper's verb workloads and
``gpt_small`` decode serving — with random weights from a seed, and
checks every answer against a numpy / float32 oracle. No leg's
exception is caught and continued past: the first failure ends the run
non-zero.

One process per chip: this top-level script never imports JAX. It runs
the body in a child process, then runs it again against the same
compile-cache directory; the second pass must be served from the cache
(store hits, no executor compiles). The last line of stdout is the
result, one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
(the device as JAX reports it). The line before it, ``summary: {...}``,
carries the per-leg status and both passes' cache counters. Without a TPU
(and without ``--rehearsal``) it exits non-zero before any leg and prints
no result; a rehearsal's result line starts with ``REHEARSAL`` so it can
never be read as a chip result.

The cache directory is ``JAX_COMPILATION_CACHE_DIR`` if set, else
``TFTPU_COMPILE_CACHE``, else ``.tftpu_cache/`` in the checkout
(``tensorframes_tpu.config.resolve_compile_cache_dir``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: one pass's wall-clock bound; two passes + start-up stay inside the
#: 1200 s contract because the second pass is served from the cache
PASS_TIMEOUT_S = 900
TOTAL_BUDGET_S = 1150

LEGS = ("a_inception", "b_aggregate", "c_ragged", "d_bert", "e_add3",
        "serving", "experts", "no_hidden_fallback")


# ---------------------------------------------------------------------------
# parent: no JAX here
# ---------------------------------------------------------------------------

def _run_pass(idx: int, rehearsal: bool, deadline: float) -> dict:
    """Run the body once in a child process; returns its record."""
    fd, out_path = tempfile.mkstemp(prefix=f"chip_smoke_pass{idx}_",
                                    suffix=".jsonl")
    os.close(fd)
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--pass-index", str(idx), "--out", out_path]
    if rehearsal:
        cmd.append("--rehearsal")
    t0 = time.perf_counter()
    timeout = max(30.0, min(PASS_TIMEOUT_S, deadline - time.time()))
    proc = subprocess.Popen(cmd, cwd=HERE)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        rc = 124
    finally:
        if proc.poll() is None:  # never leave the chip held
            proc.kill()
            proc.wait()
    rec = {"rc": rc, "wall_s": round(time.perf_counter() - t0, 1),
           "legs": {}, "device": None, "cache": None, "setup": {}}
    try:
        with open(out_path) as f:
            for line in f:
                d = json.loads(line)
                what = d.pop("_rec")
                if what == "leg":
                    rec["legs"][d["leg"]] = d
                else:
                    rec[what] = d
    finally:
        os.unlink(out_path)
    return rec


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout: exactly ``ok`` and ``device``, the device
    exactly ``platform``/``kind`` (text) and ``count`` (a whole number)."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])},
    })


def parent_main(args) -> int:
    tag = "REHEARSAL " if args.rehearsal else ""
    deadline = time.time() + TOTAL_BUDGET_S
    passes = []
    for idx in (1, 2):
        rec = _run_pass(idx, args.rehearsal, deadline)
        passes.append(rec)
        if rec["device"] is None:
            # the gate refused (no accelerator) or the package is not
            # here: no leg ran, there is no result to print
            print(f"chip_smoke: pass {idx} produced no device stamp "
                  f"(rc={rec['rc']}); no result", file=sys.stderr)
            return rec["rc"] or 1
        if rec["rc"] != 0:
            break
    legs = {}
    for name in LEGS:
        per = [p["legs"].get(name, {}).get("status", "not_run")
               for p in passes]
        legs[name] = per[0] if len(set(per)) == 1 else "/".join(per)
    ok = (
        len(passes) == 2
        and all(p["rc"] == 0 for p in passes)
        and all(p["legs"].get(n, {}).get("status") == "ok"
                for p in passes for n in LEGS)
    )
    failures = []
    if ok:
        warm = passes[1]["cache"]
        if not warm["store_hits"] > 0:
            failures.append("second pass: no compile-cache store hits")
        if warm["executor_compiles"] != 0:
            failures.append(
                f"second pass: {warm['executor_compiles']} executor "
                "compile(s) for shapes the first pass compiled"
            )
        ok = not failures
    for i, p in enumerate(passes, 1):
        c = p["cache"] or {}
        print(
            f"{tag}pass {i}: rc={p['rc']} set-up wall (not a metric) "
            f"{p['wall_s']}s | store_hits={c.get('store_hits')} "
            f"store_misses={c.get('store_misses')} "
            f"executor_compiles={c.get('executor_compiles')} "
            f"compile_s={c.get('compile_s')} load_s={c.get('load_s')}"
        )
    for msg in failures:
        print(f"{tag}FAIL {msg}")
    print(f"{tag}legs: " + " ".join(f"{k}={v}" for k, v in legs.items()))
    summary = {
        "ok": ok,
        "legs": legs,
        "passes": [
            {"rc": p["rc"], "setup_wall_s": p["wall_s"], "cache": p["cache"],
             "setup": p["setup"]}
            for p in passes
        ],
        "second_pass": "full (same legs as the first)",
        "claim": None,
    }
    print(f"{tag}summary: " + json.dumps(summary))
    print(tag + result_line(ok, passes[0]["device"]))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# child: the body
# ---------------------------------------------------------------------------

class Recorder:
    def __init__(self, path: str, tag: str):
        self.path = path
        self.tag = tag

    def write(self, rec: str, **fields) -> None:
        with open(self.path, "a") as f:
            f.write(json.dumps({"_rec": rec, **fields}) + "\n")

    def say(self, msg: str) -> None:
        print(f"{self.tag}{msg}", flush=True)


def _metric(name: str, field: str = "value", **labels) -> float:
    from tensorframes_tpu.observability.metrics import REGISTRY

    total = 0.0
    for d in REGISTRY.snapshot():
        if d["name"] == name and all(
            dict(d.get("labels") or {}).get(k) == v
            for k, v in labels.items()
        ):
            total += float(d.get(field, 0.0) or 0.0)
    return total


def child_main(args) -> int:
    if not __debug__:
        raise SystemExit("chip_smoke.py checks its oracles with assert; "
                         "do not run it under python -O")
    import dataclasses

    rehearsal = args.rehearsal
    rec = Recorder(args.out, "REHEARSAL " if rehearsal else "")
    t_start = time.perf_counter()

    # -- gate: first thing, before any work --------------------------------
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearsal:
        print(f"chip_smoke: JAX found platform={dev.platform!r}, not a "
              "TPU; refusing to run (pass --rehearsal for the tiny CPU "
              "wiring check)", file=sys.stderr)
        return 3
    import jaxlib

    from importlib import metadata

    try:
        libtpu_version = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu_version = "not installed"

    import numpy as np

    import tensorframes_tpu as tfs
    from tensorframes_tpu import kernels, native
    from tensorframes_tpu.config import use_compile_cache

    cache_dir = use_compile_cache(entry_point=True)
    n_dev = len(devices)
    rec.write("device", platform=dev.platform, kind=dev.device_kind,
              count=n_dev)
    rec.say(
        f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={n_dev} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version} numpy={np.__version__} "
        f"cache_dir={cache_dir} pass={args.pass_index}"
    )
    native_status = native.status()
    rec.say(f"native={native_status}")
    if native_status == "unavailable":
        raise RuntimeError("native row-packing extension unavailable "
                           "(g++ build failed?)")
    if rehearsal:
        # CPU wiring check: select the kernels anyway (the pallas
        # interpreter runs them) so every decision site executes
        tfs.configure(pallas_force=True)
    selectable = [k for k in kernels.KERNELS if kernels.selectable(k)]
    rec.say(f"selectable kernels: {selectable}")

    def leg(name):
        """Decorator: run a leg now, record its status. An exception is
        recorded and re-raised — nothing continues past a failed leg."""
        def run(fn):
            t0 = time.perf_counter()
            try:
                detail = fn() or {}
            except BaseException:
                rec.write("leg", leg=name, status="FAILED",
                          wall_s=round(time.perf_counter() - t0, 1))
                raise
            wall = round(time.perf_counter() - t0, 1)
            rec.write("leg", leg=name, status="ok", wall_s=wall, **detail)
            rec.say(f"leg {name}: ok in {wall}s (set-up included) "
                    + " ".join(f"{k}={v}" for k, v in detail.items()))
            return fn
        return run

    import jax.numpy as jnp

    # -- (a) Inception-v3 map_blocks: host frame, then device frame --------
    @leg("a_inception")
    def _():
        from tensorframes_tpu.models import inception as inc

        if rehearsal:
            cfg = inc.tiny(compute_dtype="bfloat16")
            n_rows, blocks = 16, 4
        else:
            cfg = inc.inception_v3(channel_scale=1.0)
            n_rows, blocks = 1024, 4
        params = inc.init_params(cfg, seed=0)
        images = inc.synthetic_images(cfg, n_rows, seed=0)

        def program(images):
            logits = inc.forward(cfg, params, images)
            return {"logits": logits,
                    "label": jnp.argmax(logits, axis=-1).astype(jnp.int32)}

        host = tfs.frame_from_arrays({"images": images}, num_blocks=blocks)
        out_h = tfs.map_blocks(program, host)
        lab_h = np.concatenate(
            [np.asarray(b["label"]) for b in out_h.blocks()])
        log_h = np.concatenate(
            [np.asarray(b["logits"]) for b in out_h.blocks()])
        device = host.to_device()
        out_d = tfs.map_blocks(program, device)
        [blk] = out_d.blocks()
        shard_devs = len(blk["label"].sharding.device_set)
        assert shard_devs == n_dev, (
            f"device-frame output sits on {shard_devs} device(s), "
            f"{n_dev} present"
        )
        lab_d = np.asarray(blk["label"])
        log_d = np.asarray(blk["logits"])
        assert lab_h.shape == lab_d.shape == (n_rows,)
        assert np.isfinite(log_h).all() and np.isfinite(log_d).all()
        # float32 oracle on 8 rows (true f32 products, not bf16 passes)
        ref_cfg = dataclasses.replace(cfg, compute_dtype="float32")
        ref_params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        # (weights as arguments: closure capture would bake 95 MB of
        # literals into the HLO)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(
                lambda p, x: inc.forward(ref_cfg, p, x)
            )(ref_params, images[:8]))
        scale = float(np.abs(ref).max())
        err_h = float(np.abs(log_h[:8] - ref).max())
        err_d = float(np.abs(log_d[:8] - ref).max())
        tol = 0.08 * scale + 0.02  # ~50 bf16 conv layers deep
        assert err_h <= tol and err_d <= tol, (err_h, err_d, tol)
        # the two paths run different block sizes, so bf16 rounding may
        # flip an argmax between near-tied logits — and only there
        flips = np.flatnonzero(lab_h != lab_d)
        for r in flips:
            gap = log_h[r].max() - log_h[r, lab_d[r]]
            assert gap <= tol, (
                f"row {r}: host label {lab_h[r]} vs device {lab_d[r]} "
                f"with a logit gap of {gap} (not a near-tie)"
            )
        assert len(flips) <= max(1, n_rows // 50), len(flips)
        return {"rows": n_rows, "sharded_over": shard_devs,
                "logit_err_host": round(err_h, 4),
                "logit_err_device": round(err_d, 4),
                "logit_scale": round(scale, 3),
                "label_flips_at_ties": int(len(flips))}

    # -- (b) keyed aggregate (sum + min) and reduce_blocks ------------------
    @leg("b_aggregate")
    def _():
        n, groups = (20_000, 32) if rehearsal else (1_000_000, 512)
        rng = np.random.default_rng(1)
        k = rng.integers(0, groups, n)
        v = rng.standard_normal(n).astype(np.float32)
        w = rng.standard_normal(n).astype(np.float32)
        want_sum = np.zeros(groups, np.float64)
        np.add.at(want_sum, k, v.astype(np.float64))
        abs_sum = np.zeros(groups, np.float64)
        np.add.at(abs_sum, k, np.abs(v).astype(np.float64))
        want_min = np.full(groups, np.inf, np.float32)
        np.minimum.at(want_min, k, w)

        def run(frame):
            with tfs.with_graph():
                v_in = tfs.block(frame, "v", tf_name="v_input")
                w_in = tfs.block(frame, "w", tf_name="w_input")
                agg = tfs.aggregate(
                    [tfs.reduce_sum(v_in, axis=0, name="v"),
                     tfs.reduce_min(w_in, axis=0, name="w")],
                    frame.group_by("k"),
                )
            keys = np.asarray(agg.column_values("k"))
            order = np.argsort(keys)
            assert np.array_equal(keys[order], np.arange(groups))
            got_sum = np.asarray(agg.column_values("v"))[order]
            got_min = np.asarray(agg.column_values("w"))[order]
            assert got_sum.dtype == np.float32
            err = np.abs(got_sum - want_sum)
            assert (err <= 1e-5 * abs_sum + 1e-6).all(), float(err.max())
            assert np.array_equal(got_min, want_min), "min is exact"
            return float(err.max())

        host = tfs.frame_from_arrays({"k": k, "v": v, "w": w}, num_blocks=1)
        err_host = run(host)
        device = host.to_device()
        [dblk] = device.blocks()
        shard_devs = len(dblk["v"].sharding.device_set)
        assert shard_devs == n_dev, (shard_devs, n_dev)
        err_dev = run(device)
        # reduce_blocks over [n, 2] f32
        y = rng.standard_normal((n, 2)).astype(np.float32)
        yf = tfs.frame_from_arrays({"y": y}, num_blocks=4)
        tot = tfs.reduce_blocks(
            lambda y_input: {"y": y_input.sum(axis=0)}, yf)
        got = np.asarray(tot["y"] if isinstance(tot, dict) else tot)
        want = y.astype(np.float64).sum(axis=0)
        bound = 1e-6 * np.abs(y).astype(np.float64).sum(axis=0)
        assert got.shape == (2,) and (np.abs(got - want) <= bound).all(), (
            got, want)
        return {"rows": n, "groups": groups,
                "sum_err_host": round(err_host, 6),
                "sum_err_device": round(err_dev, 6),
                "device_frame_sharded_over": shard_devs}

    # -- (c) ragged map_rows -------------------------------------------------
    @leg("c_ragged")
    def _():
        n = 400 if rehearsal else 20_000
        rng = np.random.default_rng(2)
        lens = rng.choice([8, 16, 24, 32], n)
        cells = [rng.standard_normal(int(m)).astype(np.float32)
                 for m in lens]
        frame = tfs.frame_from_rows([{"v": c} for c in cells], num_blocks=4)
        program = tfs.compile_program(
            lambda v: {"m": v.max(), "first": v[0]}, frame, block=False)
        out = tfs.map_rows(program, frame)
        got_m = np.concatenate([np.asarray(b["m"]) for b in out.blocks()])
        got_f = np.concatenate(
            [np.asarray(b["first"]) for b in out.blocks()])
        assert np.array_equal(got_m, np.asarray([c.max() for c in cells]))
        assert np.array_equal(got_f, np.asarray([c[0] for c in cells]))
        return {"rows": n, "cell_lengths": 4}

    # -- (d) BERT-base map_rows ----------------------------------------------
    @leg("d_bert")
    def _():
        from tensorframes_tpu.models import transformer as tr

        if rehearsal:
            cfg, n_rows, seq = tr.tiny(), 16, 16
        else:
            cfg, n_rows, seq = tr.bert_base(), 256, 128
        params = tr.init_params(cfg, seed=0)
        tokens, _ = tr.synthetic_batch(cfg, n_rows, seq, seed=0)
        frame = tfs.frame_from_arrays({"tokens": tokens}, num_blocks=1)
        prog = tr.embed_row_program(cfg, params)
        program = tfs.compile_program(
            lambda tokens: prog(tokens), frame, block=False)
        out = tfs.map_rows(program, frame)
        emb = np.concatenate(
            [np.asarray(b["embedding"]) for b in out.blocks()])
        assert emb.shape == (n_rows, cfg.hidden) and np.isfinite(emb).all()
        ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(
                lambda p, t: tr.forward(ref_cfg, p, t).mean(axis=1)
            )(params, tokens[:8]))
        err = float(np.abs(emb[:8] - ref).max())
        scale = float(np.abs(ref).max())
        assert err <= 0.05 * scale + 0.02, (err, scale)
        return {"rows": n_rows, "seq": seq, "embed_err": round(err, 4),
                "embed_scale": round(scale, 3)}

    # -- (e) README quickstart add-3, verbatim, 10k rows ---------------------
    @leg("e_add3")
    def _():
        df = tfs.frame_from_rows([{"x": float(i)} for i in range(10_000)])
        with tfs.with_graph():
            x = tfs.block(df, "x")
            df2 = tfs.map_blocks(tfs.add(x, 3, name="z"), df)
        rows = df2.collect()
        assert len(rows) == 10_000
        assert all(r["z"] == r["x"] + 3.0 for r in rows)
        assert rows[1234]["z"] == 1237.0
        return {"rows": len(rows)}

    # -- serving: gpt_small decode through Server + serve_http ---------------
    @leg("serving")
    def _():
        import threading
        import urllib.request

        from tensorframes_tpu.kernels import decode_attention as kda
        from tensorframes_tpu.models import generation as gen
        from tensorframes_tpu.models import transformer as tr
        from tensorframes_tpu.ops import attention as att
        from tensorframes_tpu.ops.executor import _JIT_MISSES

        if rehearsal:
            cfg = gen.gpt_tiny()
            dcfg = tfs.DecodeConfig(max_slots=4, page_size=4,
                                    max_prompt_len=16, max_new_tokens=4)
            n_req, lo = 6, 2
        else:
            cfg = gen.gpt_small()
            dcfg = tfs.DecodeConfig(max_slots=8, page_size=16,
                                    max_prompt_len=128, max_new_tokens=32)
            n_req, lo = 16, 5
        params = tr.quantize_params(tr.init_params(cfg, seed=0))
        rng = np.random.default_rng(3)
        plens = np.linspace(lo, dcfg.max_prompt_len, n_req).astype(int)
        prompts = [rng.integers(0, cfg.vocab_size, (int(m),)).astype(np.int32)
                   for m in plens]
        srv = tfs.Server()
        srv.register_decode("gen", cfg, params, dcfg)
        t0 = time.perf_counter()
        srv.start()  # default warm-up: the whole slot x prompt ladder
        warm_s = round(time.perf_counter() - t0, 1)
        httpd = None
        try:
            miss0 = _JIT_MISSES.value
            futs = [srv.submit("gen", {"prompt": p}) for p in prompts]
            outs = [np.asarray(f.result(600)["tokens"]) for f in futs]
            for o in outs:
                assert o.shape == (1, dcfg.max_new_tokens), o.shape
                assert ((o >= 0) & (o < cfg.vocab_size)).all()
            # batched == solo, through the same warmed engine
            for i in (0, n_req - 1):
                solo = np.asarray(srv.call(
                    "gen", {"prompt": prompts[i]}, timeout=600)["tokens"])
                assert np.array_equal(solo, outs[i]), (
                    f"request {i}: solo tokens differ from batched")
            # two more over HTTP
            httpd = tfs.serve_http(srv, port=0)
            port = httpd.server_address[1]
            http_out = {}

            def post(i):
                body = json.dumps({"inputs": {
                    "prompt": [int(t) for t in prompts[i]]}}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/gen", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=600) as r:
                    assert r.status == 200, r.status
                    http_out[i] = json.loads(r.read())

            threads = [threading.Thread(target=post, args=(i,))
                       for i in (1, n_req // 2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
            for i in (1, n_req // 2):
                got = np.asarray(http_out[i]["outputs"]["tokens"])
                assert np.array_equal(got.reshape(1, -1), outs[i]), (
                    f"request {i}: HTTP tokens differ from submit()")
            steady = int(_JIT_MISSES.value - miss0)
            assert steady == 0, (
                f"{steady} jit miss(es) after start(): the warm-up "
                "ladder did not cover the traffic")
        finally:
            if httpd is not None:
                httpd.shutdown()
                httpd.server_close()
            srv.stop(drain=True, timeout=120)
        # one decode step's attention at these shapes vs the float32
        # XLA chain (true f32 products; the default TPU precision would
        # round the oracle's operands to bf16)
        horizon = dcfg.max_prompt_len + dcfg.max_new_tokens
        maxp = -(-horizon // dcfg.page_size)
        S, nh, hd, pg = dcfg.max_slots, cfg.num_heads, cfg.head_dim, \
            dcfg.page_size
        P = 1 + S * maxp
        q = jnp.asarray(rng.standard_normal((S, nh, hd)), jnp.float32)
        lanes = kda.SCALE_LANES
        kp = jnp.asarray(rng.integers(-127, 128, (P, 2, pg, nh * hd)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (P, 2, pg, nh * hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(.01, .1, (P, 2, pg, lanes)), jnp.float32)
        vs = jnp.asarray(rng.uniform(.01, .1, (P, 2, pg, lanes)), jnp.float32)
        tables = jnp.asarray(
            rng.integers(1, P, (S, maxp)), jnp.int32).at[-1].set(0)
        pos = jnp.asarray(
            rng.integers(0, horizon, S), jnp.int32).at[-1].set(0)
        if "decode_attn" in selectable:
            got = np.asarray(att.paged_decode_attention(
                q, kp, vp, ks, vs, 1, tables, pos))
        else:
            got = np.asarray(kda.paged_attention_reference(
                q, kp, vp, ks, vs, 1, tables, pos))
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(kda.paged_attention_reference(
                q, kp, vp, ks, vs, 1, tables, pos))
        err = float(np.abs(got - ref).max())
        scale = float(np.abs(ref).max())
        assert np.isfinite(got).all() and err <= 2e-3 * scale, (err, scale)
        return {"requests": n_req + 2, "warmup_wall_s": warm_s,
                "steady_jit_misses": steady,
                "attn_err": float(f"{err:.3g}"),
                "attn_scale": round(scale, 2)}

    # -- the served expert layer: grouped-matmul kernel against ragged_dot --
    @leg("experts")
    def _():
        from jax import lax

        from tensorframes_tpu.kernels import expert_matmul as em
        from tensorframes_tpu.models import moe

        # the sparse-expert decoder's widths on the chip, tiny ones here
        t, d, f, e, k = (16, 64, 32, 8, 2) if rehearsal \
            else (128, 2304, 896, 64, 8)
        rng = np.random.default_rng(5)
        h = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
        router = jnp.asarray(rng.standard_normal((d, e)) * .02, jnp.bfloat16)
        wg, wu = (jnp.asarray(rng.standard_normal((e, d, f)) * .02,
                              jnp.bfloat16) for _ in range(2))
        wd = jnp.asarray(rng.standard_normal((e, f, d)) * .02, jnp.bfloat16)
        ids, weights = moe.route_topk(h, router, k)
        # routed_experts traces the kernel exactly where it is selectable
        got = np.asarray(jax.jit(moe.routed_experts)(
            h, ids, weights, wg, wu, wd))
        if "expert_matmul" in selectable:
            kernels.note_dispatch("expert_matmul", kernels.interpret_mode())
        order = jnp.argsort(ids.reshape(-1), stable=True)
        sizes = jnp.bincount(ids.reshape(-1), length=e).astype(jnp.int32)
        rows = h[order // k]
        # float32 accumulation on both sides: the same products, folded
        # a k tile at a time by the kernel
        want = lax.ragged_dot(rows, wg, sizes,
                              preferred_element_type=jnp.float32)
        one = np.asarray(em.grouped_matmul(
            rows, wg, sizes, interpret=kernels.interpret_mode()
        ) if "expert_matmul" in selectable else want)
        err = float(np.abs(one - np.asarray(want)).max())
        scale = float(np.abs(np.asarray(want)).max())
        assert np.isfinite(got).all() and err <= 2e-3 * scale, (err, scale)
        return {"pairs": t * k, "tiling": "x".join(map(str, em.tiling(d, f))),
                "gmm_err": float(f"{err:.3g}"), "scale": round(scale, 3)}

    # -- the packed prefill's attention: kernel against the XLA band fold ----
    @leg("packed_attention")
    def _():
        from tensorframes_tpu.kernels import packed_attention as kpa
        from tensorframes_tpu.ops import attention as att

        # GPT-2's heads (two a lane tile) over the 1,024-row bucket on the
        # chip, tiny ones here: three prompts, then padding blocks
        nh, hd, T, dt = (4, 8, 256, jnp.float32) if rehearsal \
            else (12, 64, 1024, jnp.bfloat16)
        blk = 64
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.standard_normal((1, nh, T, hd)), dt)
        kq, vq = (jnp.asarray(rng.integers(-127, 128, (1, nh, T, hd)),
                              jnp.int8) for _ in range(2))
        ks, vs = (jnp.asarray(rng.uniform(.002, .02, (1, nh, T)),
                              jnp.float32) for _ in range(2))
        first = np.arange(T // blk, dtype=np.int32)
        first[1:3], first[3:T // blk - 1] = 1, 3
        first = jnp.asarray(first)
        want = np.asarray(att._band_attention(
            q, kq, vq, blk, None, ks, vs, first), np.float32)
        if "packed_attn" in selectable:
            got = np.asarray(kpa.packed_attention(
                q, kq, vq, ks, vs, first, blk), np.float32)
            kernels.note_dispatch("packed_attn", kernels.interpret_mode())
        else:
            got = want
        err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max())
        # bfloat16 outputs: the two folds round the same sums apart
        assert np.isfinite(got).all() and err <= 1e-2 * scale, (err, scale)
        return {"rows": T, "heads": nh, "attn_err": float(f"{err:.3g}"),
                "scale": round(scale, 3)}

    # -- no hidden fallback: read the registry -------------------------------
    @leg("no_hidden_fallback")
    def _():
        from tensorframes_tpu.ops import quantize as qz
        from tensorframes_tpu.ops import segment

        fb = _metric("tftpu_executor_fallback_dispatch_total")
        assert fb == 0, f"{fb} executor lazy-jit fallback dispatch(es)"
        assert segment.pallas_enabled(), "disable_pallas() was thrown"
        dispatches = {}
        for kname in kernels.KERNELS:
            n_disp = _metric("tftpu_kernels_dispatch_total", kernel=kname)
            n_interp = _metric(
                "tftpu_kernels_interpret_fallback_total", kernel=kname)
            dispatches[kname] = int(n_disp)
            if not rehearsal:  # on the CPU every dispatch is interpreted
                assert n_interp == 0, (
                    f"{kname}: {n_interp} interpreted dispatch(es)")
            if kname in selectable:
                assert n_disp > 0, (
                    f"kernel {kname} is selectable on this backend but "
                    "never dispatched")
        # off by default (config.pallas_int8_matmul): compile once at a
        # gpt_small MLP shape and REPORT the outcome, not gated
        if dev.platform == "tpu":
            rng = np.random.default_rng(4)
            x = jnp.asarray(rng.standard_normal((8, 768)), jnp.bfloat16)
            wq = qz.quantize(jnp.asarray(
                rng.standard_normal((768, 3072)) * 0.02, jnp.float32))
            try:
                got = np.asarray(
                    qz.matmul_pallas_int8(x, wq).astype(jnp.float32))
                ref = np.asarray(x.astype(jnp.float32)
                                 @ (wq.q.astype(jnp.float32) * wq.scale))
                int8mm = f"compiled maxdiff={np.abs(got - ref).max():.3g}"
            except Exception as e:  # reported, not gated
                int8mm = f"refused: {type(e).__name__}: {str(e)[:160]}"
        else:
            int8mm = "not run (no TPU)"
        return {"executor_fallbacks": int(fb),
                "dispatches": json.dumps(dispatches).replace(" ", ""),
                "matmul_pallas_int8": int8mm.replace(" ", "_")}

    cache = {
        "store_hits": int(_metric("tftpu_compilecache_hits_total")),
        "store_misses": int(_metric("tftpu_compilecache_misses_total")),
        "executor_compiles": int(
            _metric("tftpu_executor_compile_seconds", "count")),
        "compile_s": round(
            _metric("tftpu_executor_compile_seconds", "sum"), 1),
        "load_s": round(
            _metric("tftpu_compilecache_load_seconds", "sum"), 2),
        "dir": cache_dir,
    }
    rec.write("cache", **cache)
    rec.write("setup", native=native_status, selectable=selectable,
              body_wall_s=round(time.perf_counter() - t_start, 1))
    rec.say(f"pass {args.pass_index} done: cache={json.dumps(cache)}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes, CPU accepted; wiring check only — "
                         "every summary line says REHEARSAL")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--pass-index", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    return child_main(args) if args.child else parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
