"""Mixture-of-Experts FFN: top-k routing, four implementations.

``moe_sparse`` — dropless serving path on one device: the T·k assignments
                 are sorted by expert, the experts run as one grouped
                 matrix product over the group sizes (megablox ``gmm``, a
                 Pallas kernel that visits only the row tiles of experts
                 that got rows, so it reads only those experts' weights),
                 and the rows are un-sorted and added up by their gates. No
                 capacity, no dropped row, no buffer of E·T rows.
``moe_dense``  — reference oracle: every expert computed for every token,
                 masked by routing weights. O(E·T·d·f) compute — the
                 numeric ground truth for the other paths in tests.

``moe_ep``     — production expert-parallel path (shard_map): tokens are
                 bucketed by destination shard with a sort (NO one-hot
                 dispatch einsums — those cost 2·T·E·C·d FLOPs, more than
                 the experts themselves), exchanged with all_to_all over the
                 'model' axis, run through the local experts as one batched
                 einsum, and returned. Capacity-dropped tokens fall back to
                 the residual (standard token-dropping semantics).
``moe_onehot`` — one-hot GSPMD expert parallelism for decode (capacity).

Routing: softmax over experts, top-k, renormalized gates (Qwen3-MoE style;
Phi-3.5's sparsemixer is approximated by the same renormalized top-k —
recorded in DESIGN.md §assumption-changes).

The sparse path names its parts for the profiler: ``router`` (router
logits, softmax, top-k), ``dispatch`` (sort, gather, un-sort, combine) and
``experts`` (the grouped products). ``moe_ffn`` returns with the output,
whichever path it takes, the routing's counters, int32 ``[rows routed,
experts that got a row, most rows one expert got]``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import ShardingRules, active_rules

__all__ = ["EXPERT_WEIGHTS", "moe_dense", "moe_ep", "moe_ffn", "moe_sparse",
           "router_topk", "expert_counts"]

# the expert weights, which the sparse path can read in place from a stack
# over layers
EXPERT_WEIGHTS = ("we_gate", "we_up", "we_down")


def router_topk(x, w_router, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [T,d] -> (gates [T,k] fp32 renormalized, ids [T,k] int32, probs)."""
    logits = jnp.einsum("td,de->te", x, w_router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, ids.astype(jnp.int32), probs


def _expert_ffn(x, wg, wi, wo):
    """Batched-expert SwiGLU: x [E,C,d], weights [E,d,f]/[E,f,d] -> [E,C,d]."""
    g = jnp.einsum("ecd,edf->ecf", x, wg)
    u = jnp.einsum("ecd,edf->ecf", x, wi)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wo)


def moe_dense(x, w_router, we_gate, we_up, we_down, *, k: int) -> jax.Array:
    """Oracle: compute all experts, combine by gates. x [T,d]."""
    T, d = x.shape
    E = w_router.shape[-1]
    gates, ids, _ = router_topk(x, w_router, k)
    # combine weight per (token, expert): [T,E]
    comb = jnp.zeros((T, E), jnp.float32)
    comb = jnp.take_along_axis(
        comb, ids, axis=1
    )  # dummy to keep shapes clear; build via scatter below
    comb = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], ids].add(gates)
    ys = _expert_ffn(
        jnp.broadcast_to(x, (E,) + x.shape), we_gate, we_up, we_down
    )  # [E,T,d]
    return jnp.einsum("te,etd->td", comb.astype(x.dtype), ys)


def expert_counts(ids, n_experts: int) -> jax.Array:
    """int32 [rows routed, experts with at least one row, largest group]
    of the assignments ``ids`` [T,k]."""
    sizes = jnp.zeros((n_experts,), jnp.int32).at[ids.reshape(-1)].add(1)
    return _counts(sizes)


def _counts(sizes):
    return jnp.stack([sizes.sum(), (sizes > 0).sum(dtype=jnp.int32),
                      sizes.max()]).astype(jnp.int32)


# VMEM that one grouped-product call may use for its double-buffered
# blocks and f32 accumulator; under the 16 MiB that Mosaic scopes by
# default on a v5e
_VMEM_BUDGET = 12 * 2 ** 20


def _tiling(k: int, n: int, tm: int, itemsize: int):
    """(tm, tk, tn) of one ``gmm`` call: the whole contraction and output
    width where they fit the budget, halving the output width until they
    do."""
    tk, tn = min(k, 2048), n

    def vmem(tn):
        return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn

    while vmem(tn) > _VMEM_BUDGET and tn % 256 == 0:
        tn //= 2
    return tm, tk, tn


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _gmm(lhs, rhs, groups, tiling):
    """``lhs`` rows grouped by ``groups`` times each group's ``rhs``: the
    compiled Mosaic kernel on a TPU, the same kernel interpreted elsewhere
    (chosen when the program is lowered, by its platform)."""
    def run(interpret):
        return lambda a, b, g: megablox.gmm(a, b, g, lhs.dtype, tiling, None,
                                            None, False, interpret)

    return jax.lax.platform_dependent(lhs, rhs, groups, tpu=run(False),
                                      default=run(True))


def moe_sparse(x, w_router, we_gate, we_up, we_down, *, k: int,
               layer: Optional[jax.Array] = None):
    """Dropless top-k MoE. x [T,d] -> (y [T,d], counters int32[3]).

    The expert weights are one layer's [E,d,f]/[E,f,d] or, with ``layer``,
    a stack [L,E,d,f]/[L,E,f,d] that the kernel reads in place at that
    layer: its group sizes then cover all L·E experts and are zero outside
    the layer, so no other layer's weights are read and no layer's are
    copied out of the stack.
    """
    T, d = x.shape
    E = w_router.shape[-1]
    N = T * k
    with jax.named_scope("router"):
        gates, ids, _ = router_topk(x, w_router, k)
    with jax.named_scope("dispatch"):
        flat = ids.reshape(-1)
        order = jnp.argsort(flat, stable=True)          # rows by expert
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        # one row tile per expert's share of the rows: 16 rows in decode,
        # up to 512 in prefill; the kernel needs whole tiles of rows
        tm = int(min(512, max(16, _next_pow2(N // E))))
        M = -(-N // tm) * tm
        xs = jnp.take(x, order // k, axis=0)
        xs = jnp.pad(xs, ((0, M - N), (0, 0)))
        groups = sizes
        if layer is not None:
            L = we_gate.shape[0]
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))
            we_gate, we_up, we_down = (
                w.reshape((L * E,) + w.shape[2:])
                for w in (we_gate, we_up, we_down))
        f = we_gate.shape[-1]
    with jax.named_scope("experts"):
        up = _tiling(d, f, tm, x.dtype.itemsize)
        g = _gmm(xs, we_gate, groups, up)
        u = _gmm(xs, we_up, groups, up)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        ys = _gmm(h, we_down, groups, _tiling(f, d, tm, x.dtype.itemsize))
    with jax.named_scope("dispatch"):
        # row j of the assignments sits at sorted position inv[j]
        inv = jnp.zeros((N,), jnp.int32).at[order].set(
            jnp.arange(N, dtype=jnp.int32))
        y = jnp.take(ys, inv, axis=0).reshape(T, k, d).astype(jnp.float32)
        y = jnp.einsum("tkd,tk->td", y, gates).astype(x.dtype)
    return y, _counts(sizes)


def _bucket_by(dest, n_buckets: int, cap: int, src_ids):
    """Sort-based bucketing: returns (slot_src [n_buckets*cap] int32 index
    into src arrays, valid [n_buckets*cap] bool). dest [N] in [0,n_buckets)."""
    N = dest.shape[0]
    order = jnp.argsort(dest)                    # stable
    sdest = dest[order]
    # rank of each element within its destination bucket
    first = jnp.searchsorted(sdest, jnp.arange(n_buckets), side="left")
    rank = jnp.arange(N) - first[sdest]
    keep = rank < cap
    slot = sdest * cap + jnp.minimum(rank, cap - 1)
    # scatter src index into slots; dropped entries never written
    slot_src = jnp.full((n_buckets * cap,), -1, jnp.int32)
    slot_src = slot_src.at[jnp.where(keep, slot, n_buckets * cap)].set(
        src_ids[order].astype(jnp.int32), mode="drop"
    )
    return slot_src, slot_src >= 0


def _moe_ep_local(x, w_router, we_gate, we_up, we_down, *, k, n_experts,
                  capacity_factor, axis_name):
    """Per-shard body (inside shard_map). x [T_loc, d]; experts [E_loc,...]."""
    T, d = x.shape
    E_loc = we_gate.shape[0]
    Pn = n_experts // E_loc                      # peers along the EP axis
    gates, ids, _ = router_topk(x, w_router, k)  # [T,k]
    flat_ids = ids.reshape(-1)                   # [T*k]
    flat_gate = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    dest = flat_ids // E_loc                     # owning peer
    cap = int(max(8, -(-(T * k * capacity_factor) // Pn)))
    cap = -(-cap // 8) * 8
    slot_src, valid = _bucket_by(dest, Pn, cap, jnp.arange(T * k, dtype=jnp.int32))

    gather_tok = jnp.where(valid, flat_tok[slot_src], 0)
    send_x = jnp.where(valid[:, None], x[gather_tok], 0).reshape(Pn, cap, d)
    send_eid = jnp.where(valid, flat_ids[slot_src] % E_loc, -1).reshape(Pn, cap)

    if axis_name is not None:
        recv_x = jax.lax.all_to_all(send_x, axis_name, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, axis_name, 0, 0, tiled=False)
    else:                                        # single-shard EP (tests)
        recv_x, recv_eid = send_x, send_eid
    recv_x = recv_x.reshape(Pn * cap, d)
    recv_eid = recv_eid.reshape(Pn * cap)

    # second bucketing: group received tokens by local expert
    C2 = -(-(Pn * cap) // E_loc)
    C2 = -(-C2 // 8) * 8
    eid_ok = jnp.where(recv_eid >= 0, recv_eid, E_loc)  # invalid -> overflow bucket
    slot2, valid2 = _bucket_by(eid_ok, E_loc + 1, C2,
                               jnp.arange(Pn * cap, dtype=jnp.int32))
    slot2 = slot2[: E_loc * C2]
    valid2 = valid2[: E_loc * C2]
    xe = jnp.where(valid2[:, None], recv_x[jnp.where(valid2, slot2, 0)], 0)
    xe = xe.reshape(E_loc, C2, d)

    ye = _expert_ffn(xe, we_gate, we_up, we_down)  # [E_loc, C2, d]

    # return to recv-slot order, then all_to_all back
    y_recv = jnp.zeros((Pn * cap, d), ye.dtype)
    y_recv = y_recv.at[jnp.where(valid2, slot2, Pn * cap)].set(
        ye.reshape(E_loc * C2, d), mode="drop"
    )
    y_send = y_recv.reshape(Pn, cap, d)
    if axis_name is not None:
        y_back = jax.lax.all_to_all(y_send, axis_name, 0, 0, tiled=False)
    else:
        y_back = y_send
    y_back = y_back.reshape(Pn * cap, d)

    # combine at source: out[tok] += gate * y  (dropped slots contribute 0)
    contrib = y_back * jnp.where(valid, flat_gate[slot_src], 0.0)[:, None].astype(
        y_back.dtype
    )
    out = jnp.zeros((T, d), y_back.dtype)
    out = out.at[jnp.where(valid, gather_tok, T)].add(contrib, mode="drop")
    return out


def moe_ep(x, w_router, we_gate, we_up, we_down, *, k, n_experts,
           capacity_factor, rules: ShardingRules) -> jax.Array:
    """Expert-parallel MoE over the 'model' mesh axis. x [B,S,d] global."""
    B, S, d = x.shape
    mesh = rules.mesh
    ep = rules.ep_axis
    batch_ax = rules.table.get("batch")
    x_spec = P(batch_ax, ep, None)               # tokens split over EP axis too
    other = tuple(a for a in mesh.axis_names if a != ep)

    body = functools.partial(
        _moe_ep_local,
        k=k,
        n_experts=n_experts,
        capacity_factor=capacity_factor,
        axis_name=ep,
    )
    fn = jax.shard_map(
        lambda xx, wr, wg, wu, wd: body(
            xx.reshape(-1, d), wr, wg, wu, wd
        ).reshape(xx.shape),
        mesh=mesh,
        in_specs=(x_spec, P(), P(ep), P(ep), P(ep)),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, w_router, we_gate, we_up, we_down)


def moe_onehot(x, w_router, we_gate, we_up, we_down, *, k, n_experts,
               capacity_factor) -> jax.Array:
    """One-hot einsum dispatch (GSPMD expert parallelism, no shard_map).

    Token count T is small here (decode), so the O(T·E·C·d) dispatch einsums
    are cheap; experts stay sharded over 'model' via the 'expert' logical
    axis and GSPMD partitions the batched-expert einsums + inserts the
    combine all-reduce. Used when the token dim cannot be split across the
    EP axis (e.g. one-token decode).
    """
    T, d = x.shape
    E = n_experts
    gates, ids, _ = router_topk(x, w_router, k)              # [T,k]
    cap = int(max(4, -(-(T * k * capacity_factor) // E)))
    # rank of each (token, slot) within its expert: counts of earlier
    # assignments to the same expert (over flattened [T*k] order)
    flat_ids = ids.reshape(-1)                               # [T*k]
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)    # [T*k, E]
    rank = jnp.cumsum(onehot, axis=0) - onehot               # exclusive
    rank = jnp.sum(rank * onehot, axis=-1)                   # [T*k]
    keep = rank < cap
    # dispatch [T*k, E, C]
    disp = (jax.nn.one_hot(flat_ids, E, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, rank, cap), cap + 1,
                             dtype=x.dtype)[:, None, :cap])
    comb = disp * gates.reshape(-1)[:, None, None].astype(x.dtype)
    x_rep = x[jnp.repeat(jnp.arange(T), k)]                  # [T*k, d]
    xe = jnp.einsum("sec,sd->ecd", disp, x_rep)              # [E,C,d]
    xe = constrain_expert(xe)
    ye = _expert_ffn(xe, we_gate, we_up, we_down)            # [E,C,d]
    ye = constrain_expert(ye)
    y = jnp.einsum("sec,ecd->sd", comb, ye)                  # [T*k, d]
    return y.reshape(T, k, d).sum(axis=1)


def constrain_expert(xe):
    from ..distributed.sharding import constrain
    return constrain(xe, "expert", None, None)


def moe_ffn(x, w_router, we_gate, we_up, we_down, *, k, n_experts,
            capacity_factor, layer: Optional[jax.Array] = None):
    """x [B,S,d] -> (y [B,S,d], counters int32[3]).

    With no sharding rules: the dropless sparse path (expert weights
    stacked over layers and read at ``layer`` when it is given). Under
    rules: sort-based shard_map EP for bulk token streams, one-hot GSPMD EP
    when the token dim cannot split over the EP axis (decode), the dense
    oracle otherwise; these take one layer's weights, and their counters
    come from a second router product.
    """
    rules = active_rules()
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    if rules is None:
        y, counts = moe_sparse(x2, w_router, we_gate, we_up, we_down, k=k,
                               layer=layer)
        return y.reshape(B, S, d), counts
    counts = expert_counts(router_topk(x2, w_router, k)[1], n_experts)
    if rules.moe_impl == "ep" and rules.ep_axis is not None:
        ep_size = rules.mesh.shape[rules.ep_axis]
        if S % ep_size == 0:
            return moe_ep(
                x, w_router, we_gate, we_up, we_down,
                k=k, n_experts=n_experts, capacity_factor=capacity_factor,
                rules=rules,
            ), counts
        y = moe_onehot(
            x2, w_router, we_gate, we_up, we_down,
            k=k, n_experts=n_experts, capacity_factor=capacity_factor,
        )
        return y.reshape(B, S, d), counts
    y = moe_dense(x2, w_router, we_gate, we_up, we_down, k=k)
    return y.reshape(B, S, d), counts
