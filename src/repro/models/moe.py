"""Mixture-of-Experts with sort-based (MegaBlocks-style) dispatch and
expert-parallel all-to-all via shard_map.

Design (DESIGN.md §5): no [T, E, C] one-hot dispatch einsum — at arctic
scale (E=128) that einsum costs ~1000x the expert GEMM FLOPs and its
one-hot tensor is GBs.  Instead tokens are *sorted* by destination and
moved with gathers/scatters:

  1. route: top-k over router logits, weights softmax-normalised over the
     selected experts (Mixtral/Arctic convention) + load-balancing aux loss;
  2. first-level dispatch: bucket token copies by the *rank that owns the
     expert* (capacity-bounded, overflow dropped — GShard convention);
  3. ``jax.lax.all_to_all`` over the expert-parallel mesh axis
     (``ep_mode="model"``: experts sharded over the TP axis, e.g. phi-3.5's
     16 experts; ``ep_mode="data"``: experts sharded over the DP axis with
     full-ff replicas across TP, required for arctic's 128 x 7168 x 4864
     experts which cannot fit 16-way);
  4. second-level dispatch by local expert id -> [E_loc, C2, d] buffers;
  5. grouped SwiGLU GEMM ``einsum("ecd,edf->ecf")`` (dense MXU work);
  6. reverse the moves, combine with routing weights.

Tokens enter sequence-sharded and leave sequence-sharded: the only
collectives are the two all-to-alls — the canonical EP communication
pattern.  A pure-local path (``moe_local``) is both the single-device
fallback and the correctness oracle for the distributed path.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import TransformerConfig
from repro.distributed.sharding import ParallelCtx


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def moe_init(key, cfg: TransformerConfig, dtype):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    ep = "experts"
    p = {
        "wg": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "w_in": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
    }
    # NOTE: the d dim deliberately has NO logical name ("embed" is owned by
    # the dense layers; decode re-maps it to "model" and expert weights are
    # already 2-D sharded over experts x expert_ff).
    a = {
        "wg": (None, None),
        "w_in": (ep, None, "expert_ff"),
        "w_gate": (ep, None, "expert_ff"),
        "w_out": (ep, "expert_ff", None),
    }
    return p, a


def route(x_flat: jax.Array, wg: jax.Array, top_k: int):
    """Returns (expert_ids [T,K], weights [T,K], aux_loss scalar)."""
    logits = x_flat.astype(jnp.float32) @ wg                  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    e = wg.shape[1]
    # Switch-style load-balancing loss: E * sum_e f_e * p_e
    f_e = jnp.mean(jax.nn.one_hot(ids, e, dtype=jnp.float32).sum(axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return ids.astype(jnp.int32), w.astype(x_flat.dtype), aux


class Dispatch(NamedTuple):
    """Reverse mapping for combine: for each (token, k) pair its slot in the
    bucketed buffer (or capacity overflow -> invalid)."""

    slot: jax.Array    # i32[T*K] position in flattened [n_buckets*C, ...]
    token: jax.Array   # i32[T*K] source row
    weight: jax.Array  # f32[T*K]
    valid: jax.Array   # bool[T*K]


def sort_dispatch(bucket_ids: jax.Array, token_ids: jax.Array, weights: jax.Array,
                  n_buckets: int, capacity: int) -> Dispatch:
    """Assign each (token, k) pair a slot = bucket*capacity + rank-in-bucket
    via one stable sort; pairs past capacity are dropped (GShard policy)."""
    order = jnp.argsort(bucket_ids, stable=True)
    sb = bucket_ids[order]
    counts = jnp.zeros((n_buckets,), jnp.int32).at[sb].add(1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(sb.shape[0], dtype=jnp.int32) - starts[sb]
    valid_sorted = rank < capacity
    slot_sorted = jnp.where(valid_sorted, sb * capacity + rank, n_buckets * capacity)
    # un-sort back to pair order
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    return Dispatch(
        slot=slot_sorted[inv].astype(jnp.int32),
        token=token_ids.astype(jnp.int32),
        weight=weights,
        valid=valid_sorted[inv],
    )


def fill_buffers(disp: Dispatch, x: jax.Array, n_buckets: int, capacity: int,
                 payload: jax.Array | None = None):
    """Scatter token rows (and an optional int payload) into bucket buffers."""
    d = x.shape[-1]
    buf = jnp.zeros((n_buckets * capacity + 1, d), x.dtype)
    buf = buf.at[disp.slot].set(jnp.where(disp.valid[:, None], x[disp.token], 0.0))
    buf = buf[:-1].reshape(n_buckets, capacity, d)
    if payload is None:
        return buf
    pl = jnp.full((n_buckets * capacity + 1,), -1, jnp.int32)
    pl = pl.at[disp.slot].set(jnp.where(disp.valid, payload, -1))
    return buf, pl[:-1].reshape(n_buckets, capacity)


def combine_buffers(disp: Dispatch, out_buf: jax.Array, n_tokens: int) -> jax.Array:
    """Weighted scatter-add of expert outputs back to token rows."""
    d = out_buf.shape[-1]
    flat = jnp.concatenate([out_buf.reshape(-1, d), jnp.zeros((1, d), out_buf.dtype)])
    vals = flat[jnp.where(disp.valid, disp.slot, flat.shape[0] - 1)]
    contrib = jnp.where(disp.valid[:, None], disp.weight[:, None] * vals, 0.0)
    y = jnp.zeros((n_tokens, d), out_buf.dtype)
    return y.at[disp.token].add(contrib)


def _expert_ffn(w_in, w_gate, w_out, buf):
    """Grouped SwiGLU: buf [E_loc, C, d]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_in)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def moe_local(params: dict, x_flat: jax.Array, cfg: TransformerConfig):
    """Single-shard reference: all experts local.  Oracle for the EP path."""
    t = x_flat.shape[0]
    e, k = cfg.n_experts, cfg.top_k
    ids, w, aux = route(x_flat, params["wg"], k)
    cap = _round_up(max(1, int(t * k / e * cfg.capacity_factor)), 8)
    disp = sort_dispatch(ids.reshape(-1),
                         jnp.repeat(jnp.arange(t, dtype=jnp.int32), k),
                         w.reshape(-1), e, cap)
    buf = fill_buffers(disp, x_flat, e, cap)
    out = _expert_ffn(params["w_in"], params["w_gate"], params["w_out"], buf)
    return combine_buffers(disp, out, t), aux


def _moe_ep_body(params, x_loc, cfg: TransformerConfig, ep_axis: str,
                 n_ep: int, e_loc: int):
    """Per-device body (runs under shard_map).  x_loc: [T_loc, d]."""
    t, d = x_loc.shape
    k = cfg.top_k
    ids, w, aux = route(x_loc, params["wg"], k)

    owner = ids // e_loc                                  # destination EP rank
    c1 = _round_up(max(1, int(t * k / n_ep * cfg.capacity_factor)), 8)
    disp1 = sort_dispatch(owner.reshape(-1),
                          jnp.repeat(jnp.arange(t, dtype=jnp.int32), k),
                          w.reshape(-1), n_ep, c1)
    send, send_eid = fill_buffers(disp1, x_loc, n_ep, c1,
                                  payload=(ids % e_loc).reshape(-1))

    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, ep_axis, split_axis=0, concat_axis=0,
                                  tiled=False)

    rflat = recv.reshape(n_ep * c1, d)
    eid = recv_eid.reshape(n_ep * c1)
    # per-expert capacity: at most n_ep*c1 slots arrive in total, so cap
    # there (for e_loc==1 the cf multiplier would be pure waste).
    c2 = _round_up(max(1, int(n_ep * c1 / e_loc * cfg.capacity_factor)), 8)
    c2 = min(c2, _round_up(n_ep * c1, 8))
    # invalid slots (eid == -1) bucket to a trash expert index e_loc
    disp2 = sort_dispatch(jnp.where(eid >= 0, eid, e_loc),
                          jnp.arange(n_ep * c1, dtype=jnp.int32),
                          jnp.ones((n_ep * c1,), rflat.dtype), e_loc + 1, c2)
    buf = fill_buffers(disp2, rflat, e_loc + 1, c2)[:e_loc]
    out = _expert_ffn(params["w_in"], params["w_gate"], params["w_out"], buf)
    out = jnp.concatenate([out, jnp.zeros((1, c2, d), out.dtype)])
    back = combine_buffers(disp2, out, n_ep * c1).reshape(n_ep, c1, d)

    ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    y = combine_buffers(disp1, ret, t)
    return y, aux


def _ep2d_process(params, flat: jax.Array, cfg: TransformerConfig,
                  ep_axis: str, n_ep: int, e_loc: int):
    """Dispatch -> a2a -> grouped GEMM (local ff slice) -> a2a -> combine
    for one token chunk.  flat: [T, d] -> (partial y [T, d], aux)."""
    t, d = flat.shape
    k = cfg.top_k
    ids, w, aux = route(flat, params["wg"], k)
    owner = ids // e_loc
    c1 = _round_up(max(1, int(t * k / n_ep * cfg.capacity_factor)), 8)
    disp1 = sort_dispatch(owner.reshape(-1),
                          jnp.repeat(jnp.arange(t, dtype=jnp.int32), k),
                          w.reshape(-1), n_ep, c1)
    send, send_eid = fill_buffers(disp1, flat, n_ep, c1,
                                  payload=(ids % e_loc).reshape(-1))
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0)
    recv_eid = jax.lax.all_to_all(send_eid, ep_axis, split_axis=0, concat_axis=0)

    rflat = recv.reshape(n_ep * c1, d)
    eid = recv_eid.reshape(n_ep * c1)
    c2 = _round_up(max(1, int(n_ep * c1 / e_loc * cfg.capacity_factor)), 8)
    c2 = min(c2, _round_up(n_ep * c1, 8))
    disp2 = sort_dispatch(jnp.where(eid >= 0, eid, e_loc),
                          jnp.arange(n_ep * c1, dtype=jnp.int32),
                          jnp.ones((n_ep * c1,), rflat.dtype), e_loc + 1, c2)
    buf = fill_buffers(disp2, rflat, e_loc + 1, c2)[:e_loc]
    # local ff slice -> PARTIAL output over tp
    out = _expert_ffn(params["w_in"], params["w_gate"], params["w_out"], buf)
    out = jnp.concatenate([out, jnp.zeros((1, c2, d), out.dtype)])
    back = combine_buffers(disp2, out, n_ep * c1).reshape(n_ep, c1, d)
    ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0)
    return combine_buffers(disp1, ret, t), aux


def _moe_ep_body_2d(params, x: jax.Array, cfg: TransformerConfig,
                    ep_axis: str, tp_axis: str | None, n_ep: int, e_loc: int):
    """2-D expert sharding (arctic scale): experts over ``ep_axis`` x FFN
    width over ``tp_axis``.  Tokens enter sequence-sharded over tp, are
    all-gathered (so routing/dispatch are identical across tp ranks), the
    grouped GEMM runs on the local ff slice, and the partial outputs
    reduce-scatter back to sequence shards.  Long sequences are processed
    in ``moe_token_chunks`` sequential chunks so dispatch buffers don't
    scale with T (the arctic prefill_32k memory fix).  x: [B_l, S_loc, d]."""
    bl, sl, d = x.shape
    if tp_axis is not None:
        x_full = jax.lax.all_gather(x, tp_axis, axis=1, tiled=True)
    else:
        x_full = x
    t = bl * x_full.shape[1]
    flat = x_full.reshape(t, d)

    nc = cfg.moe_token_chunks
    if nc > 1 and t % nc == 0:
        def body(_, xc):
            yc, aux = _ep2d_process(params, xc, cfg, ep_axis, n_ep, e_loc)
            return None, (yc, aux)

        _, (ys, auxs) = jax.lax.scan(body, None, flat.reshape(nc, t // nc, d))
        y, aux = ys.reshape(t, d), jnp.mean(auxs)
    else:
        y, aux = _ep2d_process(params, flat, cfg, ep_axis, n_ep, e_loc)

    y = y.reshape(bl, -1, d)
    if tp_axis is not None:
        y = jax.lax.psum_scatter(y, tp_axis, scatter_dimension=1, tiled=True)
    return y, aux


def moe_apply(params: dict, x: jax.Array, cfg: TransformerConfig,
              ctx: ParallelCtx) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] (sequence-sharded over the TP axis when ctx has a mesh).
    Returns (y [B, S, d], aux loss)."""
    b, s, d = x.shape
    if ctx.mesh is None:
        y, aux = moe_local(params, x.reshape(-1, d), cfg)
        return y.reshape(b, s, d), aux

    from jax.sharding import PartitionSpec as P
    from repro.distributed.mesh_utils import mesh_axis_size

    mesh = ctx.mesh
    ep_axis = "model" if cfg.ep_mode == "model" else "data"
    n_ep = dict(zip(mesh.axis_names, mesh.devices.shape)).get(ep_axis, 1)
    if n_ep == 1 or cfg.n_experts % n_ep != 0:
        y, aux = moe_local(params, x.reshape(-1, d), cfg)
        return y.reshape(b, s, d), aux
    e_loc = cfg.n_experts // n_ep

    dp = ctx.mesh_axes("batch")
    sp = ctx.mesh_axes("seq_act")
    # 2-D expert sharding: ff width over the tp axis (arctic-scale experts).
    ff_axis = ctx.mesh_axes("expert_ff")
    if ff_axis is not None and (ep_axis == ff_axis
                                or cfg.moe_d_ff % mesh_axis_size(mesh, ff_axis)):
        ff_axis = None
    # decode / short sequences: sequence dim can't shard — replicate it
    # (each TP rank redoes the tiny dispatch; correctness unaffected).
    if sp is not None and s % mesh_axis_size(mesh, sp) != 0:
        sp = None
    if dp is not None and b % mesh_axis_size(mesh, dp) != 0:
        dp = None
    x_spec = P(dp, sp, None)
    w_specs = {
        "wg": P(None, None),
        "w_in": P(ep_axis, None, ff_axis),
        "w_gate": P(ep_axis, None, ff_axis),
        "w_out": P(ep_axis, ff_axis, None),
    }

    if ff_axis is not None:
        tp_for_tokens = sp  # tokens gathered/scattered over the seq axis

        def body(p, xin):
            y, aux = _moe_ep_body_2d(p, xin, cfg, ep_axis, tp_for_tokens,
                                     n_ep, e_loc)
            if tp_for_tokens is None and ff_axis is not None:
                # partial-ff outputs with replicated tokens: reduce over tp
                y = jax.lax.psum(y, ff_axis)
            aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
            return y, aux
    else:
        def body(p, xin):
            bl, sl, _ = xin.shape
            y, aux = _moe_ep_body(p, xin.reshape(bl * sl, d), cfg, ep_axis,
                                  n_ep, e_loc)
            aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
            return y.reshape(bl, sl, d), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(w_specs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    y, aux = fn({k: params[k] for k in w_specs}, x)
    return y, aux
