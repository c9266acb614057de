"""A compact, real transformer encoder — the framework's flagship model.

Written in pure JAX (explicit params pytree, no flax dependency) so every
sharding decision is visible. This powers:

* BERT-style embedding extraction through ``map_rows``/``map_blocks``
  (BASELINE config 5);
* the multi-chip training-step dry-run (``__graft_entry__.dryrun_multichip``)
  with genuine dp/tp/sp shardings over a ``jax.sharding.Mesh``.

Sharding layout (the "How to Scale Your Model" recipe: pick a mesh,
annotate, let XLA insert the ICI collectives):

* batch dim → ``dp``; sequence dim of activations → ``sp``
  (attention gathers k/v over ``sp`` via XLA-inserted all-gathers; the
  manual ring-attention kernel in ops/attention.py is the alternative
  path for long sequences);
* attention head dim and MLP hidden dim → ``tp`` (Megatron-style:
  column-parallel in, row-parallel out, one psum per block);
* everything is bfloat16 on the MXU with float32 params/optimizer state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    hidden: int = 768
    num_heads: int = 12
    num_layers: int = 12
    mlp_ratio: int = 4
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16  # activations/compute; params stay f32
    # attention implementation: 'dense' | 'blockwise' | 'flash' | 'ring' |
    # 'ulysses' (ring/ulysses = sequence parallelism over the mesh 'sp'
    # axis — ppermute ring vs all-to-all head exchange; see ops/attention.py)
    attention_impl: str = "dense"
    causal: bool = False
    # rematerialize each layer in the backward pass (jax.checkpoint):
    # trades recompute FLOPs for activation HBM — the standard lever for
    # long sequences / deep stacks
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden // self.num_heads

    @property
    def mlp_hidden(self) -> int:
        return self.hidden * self.mlp_ratio

    def served_model(self, page_size: int, horizon: int):
        """This block as the decode engine takes it (models/served.py)."""
        from .generation import served_model

        return served_model(self, page_size, horizon)


def bert_base(**kw) -> TransformerConfig:
    """BERT-base geometry (12L/768H/12 heads)."""
    return TransformerConfig(vocab_size=30_522, **kw)


def tiny(**kw) -> TransformerConfig:
    """A tiny config for tests and CPU dry-runs."""
    return TransformerConfig(
        vocab_size=128, hidden=32, num_heads=4, num_layers=2, max_seq_len=16, **kw
    )


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, seed: int = 0) -> Dict:
    """Initialize the parameter pytree (float32)."""
    k = jax.random.PRNGKey(seed)
    keys = jax.random.split(k, 4 + 4 * cfg.num_layers)
    h, m = cfg.hidden, cfg.mlp_hidden

    def dense(key, shape, scale=None):
        # float(): a numpy f64 scalar would promote the f32 weights
        # to f64 under the package's global x64 mode — f64 transformers
        # crash/stall the TPU compiler (no native f64)
        scale = float(scale if scale is not None else 1.0 / np.sqrt(shape[0]))
        return jax.random.normal(key, shape, jnp.float32) * scale

    params = {
        "embed": {
            "tok": dense(keys[0], (cfg.vocab_size, h), 0.02),
            "pos": dense(keys[1], (cfg.max_seq_len, h), 0.02),
        },
        "final_ln": {"scale": jnp.ones((h,), jnp.float32),
                     "bias": jnp.zeros((h,), jnp.float32)},
        "layers": [],
    }
    for i in range(cfg.num_layers):
        ka, kb, kc, kd = keys[4 + 4 * i : 8 + 4 * i]
        params["layers"].append(
            {
                "ln1": {"scale": jnp.ones((h,), jnp.float32),
                        "bias": jnp.zeros((h,), jnp.float32)},
                "ln2": {"scale": jnp.ones((h,), jnp.float32),
                        "bias": jnp.zeros((h,), jnp.float32)},
                "attn": {
                    "qkv": dense(ka, (h, 3 * h)),
                    "out": dense(kb, (h, h)),
                },
                "mlp": {
                    "in": dense(kc, (h, m)),
                    "in_bias": jnp.zeros((m,), jnp.float32),
                    "out": dense(kd, (m, h)),
                    "out_bias": jnp.zeros((h,), jnp.float32),
                },
            }
        )
    return params


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Dict:
    """PartitionSpec pytree: Megatron-style tensor parallelism over ``tp``.

    qkv / mlp-in are column-parallel (output dim sharded); out / mlp-out
    are row-parallel (input dim sharded) → XLA inserts one psum per block.
    """
    tp = "tp" if "tp" in mesh.shape else None  # degrade on tp-less meshes

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    layer = {
        "ln1": {"scale": ns(), "bias": ns()},
        "ln2": {"scale": ns(), "bias": ns()},
        "attn": {"qkv": ns(None, tp), "out": ns(tp, None)},
        "mlp": {
            "in": ns(None, tp),
            "in_bias": ns(tp),
            "out": ns(tp, None),
            "out_bias": ns(),
        },
    }
    return {
        "embed": {"tok": ns(), "pos": ns()},
        "final_ln": {"scale": ns(), "bias": ns()},
        "layers": [layer for _ in range(cfg.num_layers)],
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer_norm(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _attention(cfg: TransformerConfig, p, x, mask, mesh=None):
    from ..ops import attention as att
    from ..ops.quantize import matmul as _mm

    b, s, h = x.shape
    qkv = _mm(x, p["qkv"]).reshape(b, s, 3, cfg.num_heads, cfg.head_dim)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    # [b, heads, s, d]
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    impl = cfg.attention_impl
    if mask is not None and impl != "dense":
        raise NotImplementedError(
            f"attention_impl={impl!r} does not support a padding mask yet; "
            "use attention_impl='dense' for padded batches"
        )
    if impl in ("ring", "ulysses"):
        if mesh is None or "sp" not in mesh.shape:
            raise ValueError(
                f"attention_impl={impl!r} requires a mesh with an 'sp' axis "
                "passed to forward(...); got "
                f"{None if mesh is None else dict(mesh.shape)}"
            )
        if impl == "ring":
            ctx = att.ring_attention(q, k, v, mesh, axis="sp", causal=cfg.causal)
        else:
            ctx = att.ulysses_attention(
                q, k, v, mesh, axis="sp", causal=cfg.causal
            )
    elif impl == "blockwise":
        ctx = att.blockwise_attention(q, k, v, causal=cfg.causal)
    elif impl == "flash":
        ctx = att.flash_attention(q, k, v, causal=cfg.causal)
    elif impl == "dense":
        ctx = att.dense_attention(
            q, k, v, causal=cfg.causal, padding_mask=mask
        )
    else:
        raise ValueError(f"Unknown attention_impl {impl!r}")
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    return _mm(ctx, p["out"])


def _mlp(p, x):
    from ..ops.quantize import matmul as _mm

    y = _mm(x, p["in"]) + p["in_bias"].astype(x.dtype)
    y = jax.nn.gelu(y)
    return _mm(y, p["out"]) + p["out_bias"].astype(x.dtype)


def forward(
    cfg: TransformerConfig,
    params: Dict,
    tokens: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Encoder forward: int tokens [b, s] → hidden states [b, s, h].

    ``mask`` (padding mask) is honoured by the dense impl; the blockwise /
    flash / ring kernels currently assume unpadded sequences. ``mesh`` is
    required for ``attention_impl='ring'`` (sequence parallelism over its
    'sp' axis).
    """
    with jax.named_scope("embed"):
        x = params["embed"]["tok"][tokens].astype(cfg.dtype)
        s = tokens.shape[1]
        x = x + params["embed"]["pos"][:s].astype(cfg.dtype)

    def layer(x, p):
        # trace-time names on the ops (layer_<i>/attn, /mlp): what a
        # profile calls them, no run cost
        with jax.named_scope("attn"):
            x = x + _attention(
                cfg, p["attn"], _layer_norm(x, **p["ln1"]), mask, mesh)
        with jax.named_scope("mlp"):
            return x + _mlp(p["mlp"], _layer_norm(x, **p["ln2"]))

    if cfg.remat:
        # recompute each layer's activations in the backward pass instead
        # of keeping them resident: O(1) layers of activation HBM
        layer = jax.checkpoint(layer)
    for li, p in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{li}"):
            x = layer(x, p)
    with jax.named_scope("head"):
        return _layer_norm(x, **params["final_ln"])


def embed_program(cfg: TransformerConfig, params: Dict):
    """map_blocks program: token block [n, s] → {"embedding": [n, h]}.

    Mean-pooled final hidden states — BERT-style sentence embeddings
    (BASELINE config 5)."""

    def program(tokens):
        hs = forward(cfg, params, tokens)
        return {"embedding": hs.mean(axis=1).astype(jnp.float32)}

    return program


def embed_row_program(cfg: TransformerConfig, params: Dict):
    """map_rows program: one token cell [s] → {"embedding": [h]}.

    The per-row formulation of BASELINE config 5 ("BERT-base embedding
    extraction: mapRows over a tokenized text column"); map_rows vmaps it
    over the block, so the whole block still runs as one batched XLA
    program on the MXU."""

    def program(tokens):
        hs = forward(cfg, params, tokens[None, :])
        return {"embedding": hs[0].mean(axis=0).astype(jnp.float32)}

    return program


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def loss_fn(cfg: TransformerConfig, params, tokens, targets, mesh=None):
    """Causal-LM-style cross entropy against the token embedding matrix."""
    hs = forward(cfg, params, tokens, mesh=mesh)
    logits = hs.astype(jnp.float32) @ params["embed"]["tok"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return nll.mean()


def make_train_step(cfg: TransformerConfig, tx):
    """Plain (unsharded) jittable train step."""

    def step(params, opt_state, tokens, targets):
        import optax

        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def make_sharded_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    tx,
    seq_axis: Optional[str] = "sp",
    mixed_precision: bool = False,
):
    """Jit the train step over a mesh with dp/tp(/sp) shardings.

    Data: tokens/targets [b, s] → P('dp', 'sp'). Params: Megatron tp
    layout. Optimizer state mirrors param shardings. XLA's SPMD partitioner
    inserts the all-gathers/psums over ICI. ``mixed_precision=True`` casts
    the LAYER params to ``cfg.dtype`` inside the differentiated function
    — the tp all-gathers and the backward then move bf16 instead of f32
    (forward already computes in ``cfg.dtype`` via per-use casts; the
    flag shrinks the collective/grad traffic). The embedding table stays
    f32: ``loss_fn`` deliberately keeps the large-vocab logits
    contraction in f32, and the master weights the optimizer updates are
    f32 either way (no loss scaling: bf16 keeps f32's exponent range).
    """
    if seq_axis is not None and seq_axis not in mesh.shape:
        seq_axis = None  # e.g. a pure-dp mesh: sequence stays unsharded
    data_spec = P("dp", seq_axis) if seq_axis else P("dp", None)
    data_sharding = NamedSharding(mesh, data_spec)
    shardings = param_shardings(cfg, mesh)

    def run_loss(p, tokens, targets):
        if mixed_precision:
            from ..training import cast_float_leaves

            # embed stays f32 — see docstring (f32 logits head)
            p = {
                **p,
                "layers": cast_float_leaves(p["layers"], cfg.dtype),
                "final_ln": cast_float_leaves(p["final_ln"], cfg.dtype),
            }
        return loss_fn(cfg, p, tokens, targets, mesh=mesh)

    def step(params, opt_state, tokens, targets):
        import optax

        loss, grads = jax.value_and_grad(
            lambda p: run_loss(p, tokens, targets)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    # Optimizer state mirrors param shardings: init under jit with sharded
    # params — XLA propagates the tp layout into adam's mu/nu, so optimizer
    # memory scales down with tp exactly like the params.
    init_opt_state = jax.jit(tx.init, in_shardings=(shardings,))

    # opt_state in/out shardings are inferred from the (already sharded)
    # state arrays produced by init_opt_state. The step dispatches through
    # the executor's unified AOT pipeline (aot_jit): its executable is
    # compiled explicitly, keyed by the mesh/sharding/process topology,
    # and served from the persistent store on restart — the MULTICHIP
    # dryrun's second run must not pay XLA again.
    from ..ops.executor import aot_jit

    jitted = aot_jit(
        step,
        in_shardings=(shardings, None, data_sharding, data_sharding),
        out_shardings=(shardings, None, NamedSharding(mesh, P())),
        label="transformer.sharded_train_step",
    )
    return jitted, data_sharding, shardings, init_opt_state


def synthetic_batch(cfg: TransformerConfig, batch: int, seq: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    targets = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    return tokens, targets


def quantize_params(params: Dict) -> Dict:
    """Weight-only int8 quantization of the layer weights (attn qkv/out,
    mlp in/out). Embeddings, norms, and biases stay full precision —
    they are gathered/broadcast, not matmul'd, so quantizing them saves
    little and costs accuracy. The returned tree runs through the same
    ``forward`` (ops/quantize.asarray dequantizes at the matmul, which
    XLA fuses into the MXU op), at ~4x less weight HBM traffic."""
    from ..ops.quantize import quantize_tree

    return quantize_tree(
        params,
        predicate=lambda path, _: "embed" not in jax.tree_util.keystr(path),
    )
