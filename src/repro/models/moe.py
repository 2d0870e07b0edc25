"""Mixture-of-Experts FFN with expert parallelism (GShard-style a2a).

Design (DESIGN.md §5): experts live on the **data** axis — tokens are already
batch-sharded there, so dispatch is one ``all_to_all`` hop each way.  The
expert dimension is physically padded to the data-axis size when the logical
expert count is smaller (grok-1: 8 experts on a 16-wide axis → each expert
stored twice, halving its routed load); when larger, each shard owns
``E / data`` experts (deepseek: 64/16 = 4 per shard).

Capacity-based dispatch: per source shard, each expert-slot receives at most
``C = ceil(T_local * top_k * capacity_factor / n_slots)`` tokens; overflow is
dropped (standard Switch/GShard semantics) and counted in the aux metrics.
The FLOP count therefore tracks *active* parameters (6·N_active·D), which is
what §Roofline's MODEL_FLOPS expects for MoE.

Runs inside ``jax.shard_map`` over the full mesh; the TP (model) axis shards
each expert's FFN width, with a psum to complete the row-parallel second
matmul.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import DP, TP, dense_init

__all__ = ["init_moe", "moe_apply_sharded", "moe_apply_reference", "expert_slots"]


def expert_slots(n_experts: int, data_size: int) -> int:
    """Physical expert slots = lcm-style padding up to the data axis size."""
    if n_experts >= data_size:
        assert n_experts % data_size == 0
        return n_experts
    assert data_size % n_experts == 0
    return data_size


def init_moe(key, cfg, dtype, data_size: int = 16):
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    slots = expert_slots(m.n_experts, data_size)
    reps = slots // m.n_experts
    ks = jax.random.split(key, 5)

    def ew(k, d_in, d_out):
        w = jax.random.normal(k, (m.n_experts, d_in, d_out), jnp.float32) / (d_in ** 0.5)
        w = jnp.tile(w, (reps, 1, 1))  # physical replication of experts
        return w.astype(dtype)

    params = {
        "router": {"w": (jax.random.normal(ks[0], (d, m.n_experts), jnp.float32) * 0.02).astype(jnp.float32)},
        "wi": ew(ks[1], d, f),
        "wg": ew(ks[2], d, f),
        "wo": ew(ks[3], f, d),
    }
    specs = {
        "router": {"w": P(None, None)},
        "wi": P("data", None, TP),
        "wg": P("data", None, TP),
        "wo": P("data", TP, None),
    }
    if m.n_shared:
        fs = m.d_ff_shared or m.d_ff_expert
        pi, si = dense_init(ks[4], d, m.n_shared * fs, dtype, in_axis=DP)
        k2 = jax.random.split(ks[4], 3)
        pg, sg = dense_init(k2[0], d, m.n_shared * fs, dtype, in_axis=DP)
        po, so = dense_init(k2[1], m.n_shared * fs, d, dtype, in_axis=TP, out_axis=DP)
        params["shared"] = {"wi": pi, "wg": pg, "wo": po}
        specs["shared"] = {"wi": si, "wg": sg, "wo": so}
    return params, specs


def _routing(x2d, router_w, n_experts: int, top_k: int):
    """x2d (T, d) -> (top-k expert ids (T,k), gates (T,k), aux loss)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss
    me = probs.mean(axis=0)
    ce = jnp.zeros((n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0) / ids.size
    aux = n_experts * jnp.sum(me * ce)
    return ids, gates, aux


def moe_apply_sharded(params, cfg, x, mesh_axes=("data", "model")):
    """Expert-parallel MoE for use inside shard_map over the mesh.

    ``x``: the *local* activation shard (B_l, S_l, d).  Collectives:
    all_to_all over ``data`` (dispatch / return), psum over ``model``
    (row-parallel wo).
    """
    m = cfg.moe
    data_axis, model_axis = mesh_axes
    data_size = jax.lax.axis_size(data_axis)
    slots = expert_slots(m.n_experts, data_size)
    reps = slots // m.n_experts
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    ids, gates, aux = _routing(x2, params["router"]["w"], m.n_experts, m.top_k)

    # map expert -> physical slot (spread over replicas by token parity)
    tok = jnp.arange(T, dtype=jnp.int32)[:, None]
    slot = ids * reps + (tok % reps)

    C = int(max(1, -(-T * m.top_k * m.capacity_factor // slots)))
    # per (token, k) -> position within its slot's send buffer
    onehot = jax.nn.one_hot(slot.reshape(-1), slots, dtype=jnp.int32)  # (T*k, slots)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # (T*k, slots)
    my_pos = (pos * onehot).sum(-1)  # (T*k,)
    keep = my_pos < C
    dropped = 1.0 - keep.mean()

    # build send buffer (slots, C, d)
    send = jnp.zeros((slots, C, d), x.dtype)
    flat_slot = slot.reshape(-1)
    src_tok = jnp.broadcast_to(tok, (T, m.top_k)).reshape(-1)
    send = send.at[flat_slot, jnp.where(keep, my_pos, 0)].add(
        jnp.where(keep[:, None], x2[src_tok], 0)
    )
    # dispatch: each shard keeps slot block s for itself -> a2a over data
    recv = jax.lax.all_to_all(send, data_axis, split_axis=0, concat_axis=0, tiled=True)
    # recv: (data_size * (slots/data_size), C, d) == (slots, C, d) where the
    # leading axis now enumerates source shards for MY slot(s)
    slots_local = slots // data_size  # == 1 when slots == data_size
    h = recv.reshape(data_size * slots_local, C, d)

    # local expert compute (my slots' experts), TP on ff width, row-parallel
    # out; params arrive shard_map-sliced: (slots_local, d, f_local)
    wi, wg, wo = params["wi"], params["wg"], params["wo"]
    hh = h.reshape(data_size, slots_local, C, d).transpose(1, 0, 2, 3).reshape(slots_local, data_size * C, d)
    a = jnp.einsum("etd,edf->etf", hh, wi)
    g = jnp.einsum("etd,edf->etf", hh, wg)
    o = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * a, wo)
    # §Perf C2: complete the row-parallel second matmul with a
    # REDUCE-SCATTER along d instead of an all-reduce, carry only the d/16
    # slice through the return all-to-all and combine, then all-gather once.
    # Collective payload per layer: RS(1/16) + a2a(1/16) + AG(1) ≈ 0.3x the
    # [AR(1) + a2a(1)] baseline.
    model_size = jax.lax.axis_size(model_axis)
    ds = d // model_size
    o = jax.lax.psum_scatter(o.astype(x.dtype), model_axis,
                             scatter_dimension=2, tiled=True)
    o = o.reshape(slots_local, data_size, C, ds).transpose(1, 0, 2, 3).reshape(slots, C, ds)

    # return trip (d-sliced)
    back = jax.lax.all_to_all(o, data_axis, split_axis=0, concat_axis=0, tiled=True)
    # combine: gather each token's k slot outputs, weight by gates
    out_tok = back[flat_slot, jnp.where(keep, my_pos, 0)]
    out_tok = jnp.where(keep[:, None], out_tok, 0)
    combined = jnp.zeros((T, ds), jnp.float32).at[src_tok].add(
        out_tok.astype(jnp.float32) * gates.reshape(-1)[:, None]
    )
    out = jax.lax.all_gather(combined.astype(x.dtype), model_axis,
                             axis=1, tiled=True)
    out = out.reshape(B, S, d)

    if "shared" in params:
        # shared experts: plain TP FFN (wi/wg column-, wo row-parallel)
        sh = params["shared"]
        a = jnp.einsum("bsd,df->bsf", x, sh["wi"]["w"])
        g = jnp.einsum("bsd,df->bsf", x, sh["wg"]["w"])
        so = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * a, sh["wo"]["w"])
        out = out + jax.lax.psum(so, model_axis)
    return out, {"aux": aux, "dropped": dropped}


def moe_apply_reference(params, cfg, x):
    """Single-device oracle: exact top-k dense routing (no capacity drop).

    Used by unit tests to validate the sharded path (up to capacity drops)
    and by CPU smoke tests.
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    ids, gates, aux = _routing(x2, params["router"]["w"], m.n_experts, m.top_k)
    wi, wg, wo = params["wi"], params["wg"], params["wo"]
    out = jnp.zeros((T, d), jnp.float32)
    for k in range(m.top_k):
        e = ids[:, k]
        a = jnp.einsum("td,tdf->tf", x2, wi[e])
        g = jnp.einsum("td,tdf->tf", x2, wg[e])
        o = jnp.einsum("tf,tfd->td", jax.nn.silu(g) * a, wo[e])
        out = out + o.astype(jnp.float32) * gates[:, k][:, None]
    out = out.astype(x.dtype).reshape(B, S, d)
    if "shared" in params:
        sh = params["shared"]
        a = jnp.einsum("bsd,df->bsf", x, sh["wi"]["w"])
        g = jnp.einsum("bsd,df->bsf", x, sh["wg"]["w"])
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * a, sh["wo"]["w"])
    return out, {"aux": aux, "dropped": jnp.float32(0)}
