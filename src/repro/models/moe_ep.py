"""Expert-parallel MoE via shard_map + explicit all-to-all (beyond-paper).

The default `moe.moe_mlp` keeps experts TP/FSDP-sharded and dispatches with
group-local capacity buffers — zero routing collectives, but every device
holds a slice of every expert. This module implements the classic
expert-parallel layout for models whose per-expert slab fits one device
(granite: 40 experts -> padded to 48, 3 per device at ~4.7 MB each):

  tokens sharded over the whole mesh -> local top-k routing -> per-peer
  capacity buffers -> all-to-all over the expert axis -> local expert FFNs
  -> all-to-all back -> local weighted combine.

Experts are padded to a multiple of the expert axis ("dead expert" slots
with -inf router logits) to handle E % axis != 0. The all-to-all traffic
(~2·T·k·d bytes/layer) surfaces in the dry-run's collective breakdown —
exactly the MoE roofline term the assignment calls out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig


def pad_experts(p, cfg: ModelConfig, axis_size: int):
    """Pad expert-stacked weights (E, ...) to a multiple of axis_size."""
    E = cfg.num_experts
    E_pad = -(-E // axis_size) * axis_size
    if E_pad == E:
        return p, E_pad
    pad = E_pad - E
    out = dict(p)
    for key in ("gate", "up", "down"):
        out[key] = jnp.pad(p[key], ((0, pad), (0, 0), (0, 0)))
    out["router"] = jnp.pad(p["router"], ((0, 0), (0, pad)))
    return out, E_pad


def moe_mlp_ep(p, x, cfg: ModelConfig, mesh, *, axis: str = "model",
               token_axes=("data", "model"), capacity_factor: float | None = None):
    """x: (B, S, d) -> (B, S, d). Expert weights in `p` must already be
    padded (pad_experts) and are sharded P(axis) on the expert dim. Tokens
    are flattened and sharded over `token_axes`; the all-to-all runs among
    the `axis` peers within each row of the other axes."""
    B, S, d = x.shape
    A = mesh.shape[axis]
    E = p["gate"].shape[0]
    assert E % A == 0, "pad_experts first"
    E_loc = E // A
    k = cfg.experts_per_token
    T = B * S
    n_shards = int(np.prod([mesh.shape[a] for a in token_axes]))
    assert T % n_shards == 0, (T, n_shards)
    T_loc = T // n_shards
    cf = capacity_factor or cfg.moe_capacity_factor
    # capacity per (source device, destination peer)
    C = max(1, int(np.ceil(T_loc * k / A * cf)))

    def device_fn(x_loc, router, gate_w, up_w, down_w):
        # x_loc (T_loc, d); router (d, E) replicated; weights (E_loc, d, ff)
        logits = x_loc.astype(jnp.float32) @ router.astype(jnp.float32)
        logits = jnp.where(jnp.arange(E)[None] < cfg.num_experts, logits, -1e30)
        gates, eidx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)  # (T_loc,k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        dest = (eidx // E_loc).reshape(T_loc * k)
        onehot = jax.nn.one_hot(dest, A, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        pos_in = jnp.take_along_axis(pos, dest[:, None], 1)[:, 0]
        keep = pos_in < C
        slot = jnp.where(keep, dest * C + pos_in, A * C)      # trash = A*C

        tok_of = jnp.broadcast_to(jnp.arange(T_loc)[:, None],
                                  (T_loc, k)).reshape(T_loc * k)
        send_tok = jnp.full((A * C + 1,), T_loc, jnp.int32).at[slot].set(
            tok_of, mode="drop")[: A * C]
        send_el = jnp.zeros((A * C + 1,), jnp.int32).at[slot].set(
            (eidx % E_loc).reshape(T_loc * k), mode="drop")[: A * C]
        x_pad = jnp.concatenate([x_loc, jnp.zeros((1, d), x_loc.dtype)], 0)
        send_x = x_pad[send_tok].reshape(A, C, d)
        send_el = send_el.reshape(A, C)
        send_ok = (send_tok < T_loc).reshape(A, C)

        # exchange: block i goes to peer i (tiled all-to-all over `axis`)
        a2a = lambda a: jax.lax.all_to_all(a, axis, 0, 0, tiled=True)
        recv_x = a2a(send_x).reshape(A * C, d)
        recv_el = a2a(send_el).reshape(A * C)
        recv_ok = a2a(send_ok).reshape(A * C)

        oh = (jax.nn.one_hot(recv_el, E_loc, dtype=jnp.float32)
              * recv_ok[:, None]).astype(recv_x.dtype)
        h = jnp.einsum("td,edf,te->tf", recv_x, gate_w, oh)
        h = jax.nn.silu(h) * jnp.einsum("td,edf,te->tf", recv_x, up_w, oh)
        out = jnp.einsum("tf,efd,te->td", h, down_w, oh)

        back = a2a(out.reshape(A, C, d)).reshape(A * C, d)    # to senders
        back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)], 0)
        per_choice = back[slot].reshape(T_loc, k, d)
        w = (gates * keep.reshape(T_loc, k)).astype(jnp.float32)
        return (per_choice.astype(jnp.float32) * w[..., None]).sum(1).astype(
            x_loc.dtype)

    tok_spec = P(tuple(token_axes), None)
    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(tok_spec, P(None, None),
                                 P(axis, None, None), P(axis, None, None),
                                 P(axis, None, None)),
                       out_specs=tok_spec, check_vma=False)
    out = fn(x.reshape(T, d), p["router"], p["gate"], p["up"], p["down"])
    return out.reshape(B, S, d)


def moe_ep_ref(p_padded, x, cfg: ModelConfig):
    """Single-device oracle with the same padded-expert routing semantics
    (top-k over padded logits, no capacity drops)."""
    B, S, d = x.shape
    E = p_padded["gate"].shape[0]
    k = cfg.experts_per_token
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p_padded["router"].astype(jnp.float32))
    logits = jnp.where(jnp.arange(E)[None, None] < cfg.num_experts,
                       logits, -1e30)
    gates, eidx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p_padded["gate"]))
    h = h * jnp.einsum("bsd,edf->bsef", x, p_padded["up"])
    allout = jnp.einsum("bsef,efd->bsed", h, p_padded["down"]).astype(jnp.float32)
    sel = jnp.take_along_axis(allout, eidx[..., None], axis=2)
    return (sel * gates[..., None]).sum(2).astype(x.dtype)
