"""Mixture-of-Experts FFN with expert parallelism (EP).

The reference has no MoE (SURVEY.md §2.7: expert parallelism — absent);
this extends the framework's parallelism inventory beyond parity, the way
ring attention did for sequence parallelism. Design is TPU-native
(GShard/Switch style), not a port:

* **Routing** is switch (top-1) with a per-shard expert capacity; dispatch
  and combine are one-hot einsums — dense MXU work with static shapes,
  no gather/scatter, no data-dependent control flow.
* **Expert parallelism** shards the expert dim of the weight stacks over
  the mesh's ``ep`` axis under ``shard_map``; tokens travel to their
  expert's device and back via two ``lax.all_to_all`` collectives over
  ICI (the EP analogue of the ring's ``ppermute``).
* Dropped tokens (over capacity) pass through on the residual path, as in
  Switch Transformers.

``moe_ffn`` (single-device einsum math) and ``moe_ffn_ep`` (shard_map +
all_to_all) compute the same function when capacity is not exceeded —
that equivalence is the correctness test.

**The served expert layer is another one**: :func:`route_topk` and
:func:`routed_experts`, at the end of this file. The switch FFN above
drops tokens past a capacity and is reached from the training dry-run
(``__graft_entry__``) and ``tests/test_moe.py`` only; the decode
engine's programs (``models/sparse_decoder.py``) route every token to
its top-k experts of all of them, drop none whatever the imbalance, and
multiply the tokens grouped by expert in grouped matmuls
(``kernels/expert_matmul.py`` where the backend runs it,
``lax.ragged_dot`` elsewhere) over the experts this holder has.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel._shard_map import shard_map as _shard_map


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    hidden: int = 64
    mlp_hidden: int = 256
    num_experts: int = 8
    # per-expert slots as a multiple of (tokens / experts); tokens over
    # capacity fall through to the residual connection
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32

    def capacity(self, tokens_per_shard: int) -> int:
        c = int(np.ceil(self.capacity_factor * tokens_per_shard / self.num_experts))
        return max(c, 1)


def init_moe_params(cfg: MoEConfig, seed: int = 0) -> Dict:
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    h, m, e = cfg.hidden, cfg.mlp_hidden, cfg.num_experts
    # python floats (weak-typed): numpy f64 scalars would promote the
    # f32 weights to f64 under the package's global x64 mode
    s_in, s_out = float(1.0 / np.sqrt(h)), float(1.0 / np.sqrt(m))
    return {
        "router": jax.random.normal(k0, (h, e), jnp.float32) * s_in,
        "w_in": jax.random.normal(k1, (e, h, m), jnp.float32) * s_in,
        "b_in": jnp.zeros((e, m), jnp.float32),
        "w_out": jax.random.normal(k2, (e, m, h), jnp.float32) * s_out,
        "b_out": jnp.zeros((e, h), jnp.float32),
    }


def moe_param_shardings(mesh: Mesh, axis: str = "ep") -> Dict:
    """Expert dim sharded over ``axis``; the router is replicated."""
    ep = axis if axis in mesh.shape else None

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    return {
        "router": ns(),
        "w_in": ns(ep, None, None),
        "b_in": ns(ep, None),
        "w_out": ns(ep, None, None),
        "b_out": ns(ep, None),
    }


# ---------------------------------------------------------------------------
# Routing (shared by both impls)
# ---------------------------------------------------------------------------

def _route(cfg: MoEConfig, router_w, x, capacity: int):
    """Switch top-1 routing with capacity.

    Returns (dispatch [t, e, c] one-hot, combine [t, e, c] gate-weighted,
    aux load-balancing stats).
    """
    logits = x.astype(jnp.float32) @ router_w  # [t, e]
    gates = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(gates, axis=-1)  # [t]
    gate = jnp.take_along_axis(gates, idx[:, None], axis=-1)[:, 0]  # [t]
    expert_1h = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)  # [t, e]
    # position of each token within its expert's queue (first-come)
    pos = jnp.cumsum(expert_1h, axis=0) * expert_1h  # [t, e]; 1-based
    pos = (pos.sum(axis=-1) - 1.0).astype(jnp.int32)  # [t]; -1 if unrouted
    keep = (pos < capacity) & (pos >= 0)
    pos_1h = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [t, c]
    dispatch = expert_1h[:, :, None] * pos_1h[:, None, :]  # [t, e, c]
    dispatch = dispatch * keep[:, None, None]
    combine = dispatch * gate[:, None, None]
    # Switch aux loss stats: fraction routed + mean gate prob per expert
    frac = expert_1h.mean(axis=0)
    prob = gates.mean(axis=0)
    return dispatch, combine, (frac, prob)


def load_balancing_loss(frac: jnp.ndarray, prob: jnp.ndarray) -> jnp.ndarray:
    """Switch Transformers aux loss: E · Σ_e frac_e · prob_e."""
    e = frac.shape[-1]
    return e * jnp.sum(frac * prob, axis=-1)


def _expert_ffn(w_in, b_in, w_out, b_out, tokens, dtype):
    """tokens [e, c, h] through each expert's 2-layer MLP (batched einsum —
    one MXU matmul per projection across all local experts)."""
    y = jnp.einsum("ech,ehm->ecm", tokens.astype(dtype), w_in.astype(dtype))
    y = jax.nn.gelu(y + b_in[:, None, :].astype(dtype))
    y = jnp.einsum("ecm,emh->ech", y, w_out.astype(dtype))
    return y + b_out[:, None, :].astype(dtype)


# ---------------------------------------------------------------------------
# Single-device reference impl
# ---------------------------------------------------------------------------

def moe_ffn(
    cfg: MoEConfig, params: Dict, x: jnp.ndarray, return_stats: bool = False
):
    """x [t, h] → [t, h]. Pure einsum dispatch/combine on one device.
    With ``return_stats`` also returns the (frac, prob) load-balancing
    stats from the routing pass (so losses don't route twice)."""
    capacity = cfg.capacity(x.shape[0])
    dispatch, combine, stats = _route(cfg, params["router"], x, capacity)
    dispatched = jnp.einsum("tec,th->ech", dispatch, x.astype(jnp.float32))
    outs = _expert_ffn(
        params["w_in"], params["b_in"], params["w_out"], params["b_out"],
        dispatched, cfg.dtype,
    )
    y = jnp.einsum("tec,ech->th", combine, outs.astype(jnp.float32))
    y = y.astype(x.dtype)
    return (y, stats) if return_stats else y


# ---------------------------------------------------------------------------
# Expert-parallel impl (shard_map + all_to_all over 'ep')
# ---------------------------------------------------------------------------

def moe_ffn_ep(
    cfg: MoEConfig,
    params: Dict,
    x: jnp.ndarray,
    mesh: Mesh,
    axis: str = "ep",
    batch_axis: Optional[str] = "dp",
    return_stats: bool = False,
):
    """x [t, h] (sharded over ``axis``×``batch_axis`` on dim 0) → [t, h],
    with experts sharded over ``axis``: each shard routes its local tokens,
    ships them to the owning expert's device (all_to_all), runs the local
    experts, and ships results back (reverse all_to_all). A ``batch_axis``
    present on the mesh additionally splits tokens data-parallel (each dp
    replica runs its own independent a2a over its ep group).
    """
    n_ep = mesh.shape[axis]
    if cfg.num_experts % n_ep != 0:
        raise ValueError(
            f"num_experts={cfg.num_experts} not divisible by mesh axis "
            f"{axis!r}={n_ep}"
        )
    e_local = cfg.num_experts // n_ep
    db = batch_axis if (batch_axis and batch_axis in mesh.shape) else None
    token_dim0 = (axis, db) if db else axis
    stat_axes = (axis, db) if db else (axis,)

    def shard_fn(router, w_in, b_in, w_out, b_out, xs):
        # xs: local tokens [t_local, h]; w_in: local experts [e_local, h, m]
        t_local = xs.shape[0]
        capacity = cfg.capacity(t_local)
        dispatch, combine, (frac, prob) = _route(cfg, router, xs, capacity)
        # global load-balance stats = mean of per-shard stats (equal sizes)
        frac = lax.pmean(frac, stat_axes)
        prob = lax.pmean(prob, stat_axes)
        # [t, e, c] → [e, c, h], expert-major so the a2a split is contiguous
        dispatched = jnp.einsum("tec,th->ech", dispatch, xs.astype(jnp.float32))
        # exchange: split experts over the ep group, concat source shards.
        # [e, c, h] → [ep, e_local, c, h]; after a2a, dim 0 indexes the
        # SOURCE shard and e_local are OUR experts.
        dispatched = dispatched.reshape(n_ep, e_local, capacity, -1)
        recv = lax.all_to_all(dispatched, axis, split_axis=0, concat_axis=0)
        # [ep(source), e_local, c, h] → [e_local, ep·c, h]
        tokens = recv.transpose(1, 0, 2, 3).reshape(e_local, n_ep * capacity, -1)
        outs = _expert_ffn(w_in, b_in, w_out, b_out, tokens, cfg.dtype)
        # reverse the exchange
        outs = outs.reshape(e_local, n_ep, capacity, -1).transpose(1, 0, 2, 3)
        back = lax.all_to_all(
            outs.astype(jnp.float32), axis, split_axis=0, concat_axis=0
        )
        # [ep(expert-group), e_local, c, h] → [e, c, h] at the source shard
        back = back.reshape(cfg.num_experts, capacity, -1)
        y = jnp.einsum("tec,ech->th", combine, back)
        return y.astype(xs.dtype), frac, prob

    y, frac, prob = _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(),                    # router replicated
            P(axis, None, None),    # w_in
            P(axis, None),          # b_in
            P(axis, None, None),    # w_out
            P(axis, None),          # b_out
            P(token_dim0, None),    # tokens sharded over ep (× dp)
        ),
        out_specs=(P(token_dim0, None), P(), P()),
        check=False,
    )(
        params["router"], params["w_in"], params["b_in"],
        params["w_out"], params["b_out"], x,
    )
    return (y, (frac, prob)) if return_stats else y


# ---------------------------------------------------------------------------
# Training helpers
# ---------------------------------------------------------------------------

def loss_fn(
    cfg: MoEConfig,
    params: Dict,
    x: jnp.ndarray,
    y: jnp.ndarray,
    mesh: Optional[Mesh] = None,
    axis: str = "ep",
    batch_axis: Optional[str] = "dp",
    aux_weight: float = 0.01,
) -> jnp.ndarray:
    """Regression loss through the MoE layer (+ Switch aux loss), runnable
    dense or expert-parallel. The aux stats come from the forward pass's
    own routing — no second routing pass."""
    if mesh is not None and axis in mesh.shape:
        out, (frac, prob) = moe_ffn_ep(
            cfg, params, x, mesh, axis=axis, batch_axis=batch_axis,
            return_stats=True,
        )
    else:
        out, (frac, prob) = moe_ffn(cfg, params, x, return_stats=True)
    mse = jnp.mean((out.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)
    return mse + aux_weight * load_balancing_loss(frac, prob)


def make_ep_train_step(
    cfg: MoEConfig,
    mesh: Mesh,
    tx,
    axis: str = "ep",
    batch_axis: Optional[str] = "dp",
):
    """Jitted expert-parallel train step over ``mesh``: tokens sharded over
    ep × dp (each dp replica owns a distinct batch slice — no redundant
    compute), expert weights sharded over ep, optimizer state mirroring
    the params."""
    db = batch_axis if (batch_axis and batch_axis in mesh.shape) else None
    shardings = moe_param_shardings(mesh, axis=axis)
    data_sharding = NamedSharding(mesh, P((axis, db) if db else axis, None))

    def step(params, opt_state, x, y):
        import optax

        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(
                cfg, p, x, y, mesh=mesh, axis=axis, batch_axis=db
            )
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    init_opt = jax.jit(tx.init, in_shardings=(shardings,))
    # unified AOT dispatch (ISSUE 10): the ep train step keys by its
    # mesh/sharding topology and restarts warm from the persistent store
    from ..ops.executor import aot_jit

    jitted = aot_jit(
        step,
        in_shardings=(shardings, None, data_sharding, data_sharding),
        out_shardings=(shardings, None, NamedSharding(mesh, P())),
        label="moe.ep_train_step",
    )
    return jitted, data_sharding, shardings, init_opt


def scoring_program(cfg: MoEConfig, params: Dict):
    """map_blocks program: token-feature block [n, hidden] →
    {"moe_out": [n, hidden]} — MoE inference through the same verb as
    every other model family (params closure-captured ≙ frozen-graph)."""

    def program(features):
        return {"moe_out": moe_ffn(cfg, params, features)}

    return program


# ---------------------------------------------------------------------------
# The served expert layer: top-k of all experts, no token dropped,
# grouped matmuls over the experts held (models/sparse_decoder.py)
# ---------------------------------------------------------------------------

def route_topk(h: jnp.ndarray, router: jnp.ndarray, top_k: int,
               renormalize: bool = True):
    """Route ``h`` [tokens, hidden] over ALL experts: softmax of ``h @
    router`` in float32, the ``top_k`` largest, their weights divided by
    their sum where ``renormalize``. Returns ``(experts [tokens, top_k]
    int32, weights [tokens, top_k] float32)``."""
    logits = jnp.matmul(h, router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = lax.top_k(probs, int(top_k))
    if renormalize:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def routed_experts(h: jnp.ndarray, experts: jnp.ndarray,
                   weights: jnp.ndarray, w_gate: jnp.ndarray,
                   w_up: jnp.ndarray, w_down: jnp.ndarray,
                   first_expert: int = 0) -> jnp.ndarray:
    """The part of the expert layer's result that THIS holder's experts
    give: ``w_gate`` / ``w_up`` [held, hidden, width] and ``w_down``
    [held, width, hidden] are experts ``first_expert .. first_expert +
    held`` of the routing's range. Every (token, expert) pair whose
    expert is held is computed, however uneven the routing; the pairs of
    absent experts add nothing here (their holders add them, and the
    shares sum to the whole layer). Pairs are grouped by expert and
    multiplied in three grouped matmuls (:func:`_grouped_matmul`: one
    pass over the pairs, the matrices of the experts that got any),
    gated-SiLU, float32 accumulation; a token's result is the weighted sum over its own
    experts in top-k order, so a row does not depend on what else is in
    the batch. ``h`` [tokens, hidden] → float32 [tokens, hidden]."""
    tokens, top_k = experts.shape
    held = int(w_gate.shape[0])
    local = experts.reshape(-1) - int(first_expert)
    # absent experts sort last and fall outside every group
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    rows = h[order // top_k]
    matmul = _grouped_matmul()
    gate, up = matmul(rows, w_gate, sizes), matmul(rows, w_up, sizes)
    act = (jax.nn.silu(gate) * up).astype(h.dtype)
    out = matmul(act, w_down, sizes)
    # the rows of absent experts lie past every group: not computed
    out = jnp.where((local[order] < held)[:, None], out, 0.0)
    # back to (token, k) order, then each token's own weighted sum
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return (out[inverse].reshape(tokens, top_k, -1)
            * weights[:, :, None]).sum(axis=1)


def _grouped_matmul():
    """``(rows [m, k], w [held, k, n], sizes [held]) -> float32 [m, n]``,
    the rows sorted by expert: the Pallas kernel where the backend runs
    it (``kernels.selectable("expert_matmul")``, asked where the
    program is traced), ``lax.ragged_dot`` elsewhere."""
    from .. import kernels as _kernels

    if _kernels.selectable("expert_matmul"):
        from ..kernels.expert_matmul import grouped_matmul

        return functools.partial(grouped_matmul,
                                 interpret=_kernels.interpret_mode())
    return functools.partial(lax.ragged_dot,
                             preferred_element_type=jnp.float32)


def expert_counts(experts: jnp.ndarray, live: jnp.ndarray,
                  num_experts: int) -> jnp.ndarray:
    """Tokens routed to each expert, over the rows ``live`` marks:
    int32 [num_experts]."""
    ids = jnp.where(live[:, None], experts, num_experts).reshape(-1)
    return jnp.bincount(ids, length=num_experts + 1)[
        :num_experts].astype(jnp.int32)
