"""Mixture-of-Experts with CSR-format dispatch (DESIGN §4).

The token→expert assignment is literally a sparse matrix: N rows (tokens),
E columns (experts), top-k nonzeros per row.  We build its *CSC-by-expert*
form on the fly exactly the way the paper builds ``row_ptr``: per-expert
counts → exclusive cumsum → pointer array; a token's slot inside its expert's
capacity buffer is its rank within the expert's run (the paper's
within-super-row offset).  Experts grouped per device are the super-row
analogue: contiguous expert blocks per model shard.

Two execution paths:
  * ``moe_apply``            — single-device / pure-SPMD (jnp only); used by
                               smoke tests and small runs.
  * ``moe_apply_ep``         — expert parallelism via shard_map: activations
                               replicated over the ``model`` axis, experts
                               sharded over it, outputs combined by psum
                               (same collective shape as a TP FFN, so the
                               MoE adds no new collective class to the
                               roofline).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init

Params = Dict[str, Any]


def moe_init(
    key,
    d_model: int,
    d_ff: int,
    num_experts: int,
    dtype=jnp.float32,
) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": dense_init(k1, d_model, num_experts, jnp.float32),
        "w_in": (jax.random.normal(k2, (num_experts, d_model, d_ff)) * scale_in).astype(dtype),
        "w_gate": (jax.random.normal(k3, (num_experts, d_model, d_ff)) * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k4, (num_experts, d_ff, d_model)) * scale_out).astype(dtype),
    }


def csr_dispatch_plan(
    expert_idx: jax.Array,  # [N, K] int32
    num_experts: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build the CSR-style dispatch plan.

    Returns (dest, keep, row_ptr):
      dest    [N*K]  flat slot = e * capacity + rank-within-expert
      keep    [N*K]  bool, False for tokens over capacity
      row_ptr [E+1]  the paper's pointer array over the expert dimension
    """
    e = expert_idx.reshape(-1)                                # [NK]
    NK = e.shape[0]
    counts = jnp.zeros((num_experts,), jnp.int32).at[e].add(1)
    row_ptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    # rank within expert: stable sort by expert id, position − run start
    order = jnp.argsort(e, stable=True)
    sorted_e = e[order]
    rank_sorted = jnp.arange(NK, dtype=jnp.int32) - row_ptr[sorted_e]
    rank = jnp.zeros((NK,), jnp.int32).at[order].set(rank_sorted)
    keep = rank < capacity
    dest = e * capacity + jnp.minimum(rank, capacity - 1)
    return dest, keep, row_ptr


def _expert_ffn(w_in, w_gate, w_out, xs):
    """xs: [E, C, D] → [E, C, D] (batched expert MLP)."""
    h = jnp.einsum("ecd,edf->ecf", xs, w_in)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, w_gate))
    return jnp.einsum("ecf,efd->ecd", h * g, w_out)


def moe_apply(
    params: Params,
    x: jax.Array,               # [B, T, D]
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    router_softmax_after_topk: bool = True,
    slot_loop: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Single-device MoE. Returns (output, aux_loss)."""
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    logits = (xf.astype(jnp.float32)) @ params["router"]      # [N, E]
    topv, topi = jax.lax.top_k(logits, top_k)                 # [N, K]
    if router_softmax_after_topk:
        weights = jax.nn.softmax(topv, axis=-1)
    else:
        weights = jax.nn.softmax(logits, axis=-1)
        weights = jnp.take_along_axis(weights, topi, axis=-1)

    # floor for tiny N (decode steps): avoid dropping tokens that a larger
    # batch would keep — keeps decode bit-consistent with full forward
    capacity = max(
        int(N * top_k / num_experts * capacity_factor), min(N * top_k, 16)
    )
    dest, keep, _ = csr_dispatch_plan(topi, num_experts, capacity)

    # scatter/gather per routing slot k: avoids materialising the [N·K, D]
    # token-replica tensor (top_k× activation memory — §Perf H3)
    buf = jnp.zeros((num_experts * capacity, D), x.dtype)
    if slot_loop:
        dest_nk = dest.reshape(N, top_k)
        keep_nk = keep.reshape(N, top_k)
        for kk in range(top_k):
            buf = buf.at[dest_nk[:, kk]].add(
                jnp.where(keep_nk[:, kk, None], xf, 0)
            )
    else:  # baseline: materialise the [N·K, D] token-replica tensor
        xr = jnp.repeat(xf, top_k, axis=0)
        buf = buf.at[dest].add(jnp.where(keep[:, None], xr, 0))
    out_buf = _expert_ffn(
        params["w_in"], params["w_gate"], params["w_out"],
        buf.reshape(num_experts, capacity, D),
    ).reshape(num_experts * capacity, D)

    if slot_loop:
        y = jnp.zeros((N, D), x.dtype)
        for kk in range(top_k):
            w_k = (weights[:, kk, None] * keep_nk[:, kk, None]).astype(x.dtype)
            y = y + out_buf[dest_nk[:, kk]] * w_k
        y = y.reshape(B, T, D)
    else:
        gathered = out_buf[dest] * (weights.reshape(-1, 1) * keep[:, None]).astype(x.dtype)
        y = gathered.reshape(N, top_k, D).sum(axis=1).reshape(B, T, D)

    # load-balance aux loss (Switch-style)
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.zeros((num_experts,)).at[topi[:, 0]].add(1.0) / N
    frac_probs = probs.mean(axis=0)
    aux = num_experts * jnp.sum(frac_tokens * frac_probs)
    return y, aux


def moe_apply_ep(
    params: Params,
    x: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    mesh,
    model_axis: str = "model",
    data_axes: Tuple[str, ...] = ("data",),
    capacity_factor: float = 1.25,
    slot_loop: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE: experts sharded over ``model_axis``.

    Activations arrive replicated over the model axis (post-attention state);
    each model shard routes all its local tokens to *its* expert slice and the
    partial outputs are psum-combined — one all-reduce of [N_loc, D], the same
    collective a dense TP FFN needs, so MoE keeps the collective roofline term
    unchanged vs dense (EXPERIMENTS §Roofline discusses this).
    """
    E = num_experts
    ep = mesh.shape[model_axis]
    assert E % ep == 0, f"experts {E} must divide model axis {ep}"
    E_loc = E // ep

    def body(router, w_in, w_gate, w_out, xs):
        B, T, D = xs.shape
        N = B * T
        xf = xs.reshape(N, D)
        logits = xf.astype(jnp.float32) @ router              # [N, E] router replicated
        topv, topi = jax.lax.top_k(logits, top_k)
        weights = jax.nn.softmax(topv, axis=-1)
        my_shard = jax.lax.axis_index(model_axis)
        e_start = my_shard * E_loc

        capacity = max(int(N * top_k / E * capacity_factor), min(N * top_k, 16))
        # local plan over my experts + one dummy bin (expert id E_loc) that
        # absorbs other shards' tokens without polluting real capacities
        local_e = topi - e_start
        mine = (local_e >= 0) & (local_e < E_loc)
        dest, keep, _ = csr_dispatch_plan(
            jnp.where(mine, jnp.clip(local_e, 0, E_loc - 1), E_loc),
            E_loc + 1,
            capacity,
        )
        keep = keep & mine.reshape(-1)

        buf = jnp.zeros(((E_loc + 1) * capacity, D), xs.dtype)
        if slot_loop:
            dest_nk = dest.reshape(N, top_k)
            keep_nk = keep.reshape(N, top_k)
            for kk in range(top_k):
                buf = buf.at[dest_nk[:, kk]].add(
                    jnp.where(keep_nk[:, kk, None], xf, 0)
                )
        else:  # baseline replica path
            xr = jnp.repeat(xf, top_k, axis=0)
            buf = buf.at[dest].add(jnp.where(keep[:, None], xr, 0))
        out_buf = _expert_ffn(
            w_in, w_gate, w_out, buf[: E_loc * capacity].reshape(E_loc, capacity, D)
        ).reshape(E_loc * capacity, D)
        out_buf = jnp.concatenate(
            [out_buf, jnp.zeros((capacity, D), out_buf.dtype)]
        )
        if slot_loop:
            y = jnp.zeros((N, D), xs.dtype)
            for kk in range(top_k):
                w_k = (weights[:, kk, None] * keep_nk[:, kk, None]).astype(xs.dtype)
                y = y + out_buf[dest_nk[:, kk]] * w_k
        else:
            gathered = out_buf[dest] * (weights.reshape(-1, 1) * keep[:, None]).astype(xs.dtype)
            y = gathered.reshape(N, top_k, D).sum(axis=1)
        y = jax.lax.psum(y, model_axis)                       # combine expert shards
        probs = jax.nn.softmax(logits, axis=-1)
        frac_tokens = jnp.zeros((E,)).at[topi[:, 0]].add(1.0) / N
        aux = E * jnp.sum(frac_tokens * probs.mean(axis=0))
        aux = jax.lax.pmean(aux, data_axes)                   # agree across shards
        return y.reshape(B, T, D), aux

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(),                                   # router replicated
            P(model_axis), P(model_axis), P(model_axis),  # experts sharded on E
            P(data_axes),                          # tokens sharded on batch
        ),
        out_specs=(P(data_axes), P()),
        check_vma=False,
    )
    return f(params["router"], params["w_in"], params["w_gate"], params["w_out"], x)
