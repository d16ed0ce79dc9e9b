"""Mixture-of-Experts FFN: grouped capacity buckets, shard_map dispatch, EP.

GShard/Switch-style static-shape routing, structured so a 256+-chip mesh
actually partitions it:

  1. tokens reshape into G dispatch groups (G = shards of the "tokens"
     logical axis — every chip);
  2. routing + the scatter into per-group capacity buckets run inside
     ``shard_map`` — scatters/gathers are device-LOCAL by construction
     (GSPMD's SPMD partitioner replicates batched scatters, which at 1M
     tokens would materialize the full (T*k, D) update tensor per device);
  3. the bucket tensor reshards from group-sharded to (group x expert)-
     sharded — GSPMD inserts the MoE all-to-all;
  4. expert FFNs are stacked einsums over the E dim (sharded over
     "expert" = the model axis) — plain GSPMD;
  5. a second shard_map gathers each token's k expert rows back (local).

Per-group capacity cap_g = ceil(T_g * k / E * factor); overflow tokens are
dropped (standard static-shape trade).  The Switch aux loss is computed per
group and averaged — it pushes the router toward the uniform "divisible
load" split across experts, the paper's balance condition in miniature.

Outside a sharding context (CPU smoke tests) the same math runs as a plain
vmap over groups — bit-identical routing, no mesh required.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    current_mesh,
    logical_to_pspec,
    shard_act,
    shard_count,
)
from .layers import init_dense

__all__ = ["moe_params", "moe_ffn"]


def moe_params(key, d_model: int, d_ff: int, num_experts: int, act: str, dtype):
    ks = jax.random.split(key, 4)
    n_mats = 3 if act in ("swiglu", "geglu") else 2
    p = {
        "w_router": init_dense(ks[0], d_model, num_experts, jnp.float32),
        "we_up": _expert_stack(ks[1], num_experts, d_model, d_ff, dtype),
        "we_down": _expert_stack(ks[2], num_experts, d_ff, d_model, dtype),
    }
    if n_mats == 3:
        p["we_gate"] = _expert_stack(ks[3], num_experts, d_model, d_ff, dtype)
    return p


def _expert_stack(key, e: int, d_in: int, d_out: int, dtype):
    scale = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32) * scale).astype(dtype)


def _capacity(tokens_per_group: int, num_experts: int, k: int, factor: float) -> int:
    cap = int(tokens_per_group * k / num_experts * factor) + 1
    cap = max(cap, k)
    return min(cap, tokens_per_group)


def _route_group(xt, w_router, *, num_experts: int, k: int, cap: int):
    """One dispatch group.  xt: (Tg, D).

    Returns (buckets (E, cap, D), flat_e (Tg*k,), flat_slot (Tg*k,),
    gate_vals (Tg, k), aux scalar)."""
    Tg, D = xt.shape
    E = num_experts

    logits = xt.astype(jnp.float32) @ w_router           # (Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)      # (Tg, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch aux, top-k normalized: f_e = fraction of ROUTING SLOTS to e
    # (divide by k so a perfectly balanced router scores exactly 1.0).
    onehot_all = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32).sum(1)
    aux = E * jnp.sum((onehot_all.mean(0) / k) * probs.mean(0))

    # position-in-expert ranks; earlier top-k choices win bucket slots
    running = jnp.zeros((E,), jnp.int32)
    slots = []
    for j in range(k):
        oh = jax.nn.one_hot(expert_idx[:, j], E, dtype=jnp.int32)
        within = jnp.cumsum(oh, axis=0) - oh
        pos = jnp.take_along_axis(
            within + running[None, :], expert_idx[:, j : j + 1], axis=1)[:, 0]
        slots.append(pos)
        running = running + oh.sum(0)
    slot = jnp.stack(slots, axis=1)                      # (Tg, k)
    keep = slot < cap
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    flat_e = expert_idx.reshape(-1)
    flat_slot = jnp.where(keep, slot, cap).reshape(-1)   # overflow -> pad row
    upd = jnp.repeat(xt, k, axis=0) if k > 1 else xt
    buckets = jnp.zeros((E, cap + 1, D), xt.dtype)
    buckets = buckets.at[flat_e, flat_slot].add(upd.astype(buckets.dtype))
    return buckets[:, :cap, :], flat_e, flat_slot, gate_vals, aux


def _combine_group(out_b, fe, fs, gv, *, Tg: int, k: int):
    """out_b: (E, cap, D) -> (Tg, D) via each token's k expert rows."""
    E, cap, D = out_b.shape
    pad = jnp.zeros((E, 1, D), out_b.dtype)
    padded = jnp.concatenate([out_b, pad], axis=1)
    gathered = padded[fe, fs].reshape(Tg, k, D)
    return jnp.sum(gathered.astype(jnp.float32) * gv[..., None], axis=1)


def moe_ffn(
    x: jnp.ndarray,
    p,
    *,
    num_experts: int,
    experts_per_token: int,
    act: str,
    cap_factor: float = 1.25,
    num_groups: int | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    B, S, D = x.shape
    E, k = num_experts, experts_per_token
    T = B * S
    mesh = current_mesh()
    G = num_groups or shard_count("tokens")
    if T % G:
        G = 1
    Tg = T // G
    cap = _capacity(Tg, E, k, cap_factor)

    xt = x.reshape(G, Tg, D)
    use_shard_map = mesh is not None and G == shard_count("tokens") and G > 1

    route = jax.vmap(
        lambda xg, wr: _route_group(xg, wr, num_experts=E, k=k, cap=cap),
        in_axes=(0, None))
    combine = jax.vmap(
        lambda ob, fe, fs, gv: _combine_group(ob, fe, fs, gv, Tg=Tg, k=k))

    if use_shard_map:
        gspec = logical_to_pspec(("tokens",))[0]  # physical axes of "tokens"
        g4 = lambda *rest: P(gspec, *rest)
        xt = shard_act(xt, ("tokens", None, None))
        buckets, flat_e, flat_slot, gate_vals, aux = shard_map(
            route, mesh=mesh,
            in_specs=(g4(None, None), P()),
            out_specs=(g4(None, None, None), g4(None), g4(None),
                       g4(None, None), g4()),
        )(xt, p["w_router"])
    else:
        buckets, flat_e, flat_slot, gate_vals, aux = route(xt, p["w_router"])

    # group-sharded -> (group x expert)-sharded: the MoE all-to-all.  The
    # group dim keeps only "data" here because "expert" owns the model axis.
    buckets = shard_act(buckets, ("data", "expert", None, None))

    # ---- expert FFN over stacked weights (E sharded over "expert") ----------
    up = jnp.einsum("gecd,edf->gecf", buckets, p["we_up"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    if "we_gate" in p:
        g = jnp.einsum("gecd,edf->gecf", buckets, p["we_gate"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        g = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)
        h = g * up
    else:
        h = jax.nn.gelu(up) if act == "gelu" else jnp.square(jax.nn.relu(up))
    out_buckets = jnp.einsum("gecf,efd->gecd", h, p["we_down"],
                             preferred_element_type=jnp.float32).astype(x.dtype)

    # back to group-sharded for the local combine (reverse all-to-all).
    # The intermediate (data, expert) constraint matters for the BACKWARD:
    # its transpose reshards the combine cotangent to match the einsum
    # operands' sharding before the weight-gradient contraction — without
    # it GSPMD all-gathers the full (E, d, G, cap) operand (observed 80 GiB
    # per layer).
    if use_shard_map:
        out_buckets = shard_act(out_buckets, ("data", "expert", None, None))
        out_buckets = shard_act(out_buckets, ("tokens", None, None, None))
        out = shard_map(
            combine, mesh=mesh,
            in_specs=(g4(None, None, None), g4(None), g4(None), g4(None, None)),
            out_specs=g4(None, None),
        )(out_buckets, flat_e, flat_slot, gate_vals)
    else:
        out = combine(out_buckets, flat_e, flat_slot, gate_vals)

    out = out.astype(x.dtype).reshape(B, S, D)
    return shard_act(out, ("data", "seq", None)), aux.mean()
