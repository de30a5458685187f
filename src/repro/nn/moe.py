"""Mixture-of-Experts (DeepSeek-V2 style: shared + routed experts, top-k).

Expert parallelism: routed expert weights are sharded over the 'model' mesh
axis. Activations entering the block are replicated over 'model' (they are
batch-sharded over 'data'/'pod'), so each model shard selects and computes
only the tokens routed to its local experts, then the partial outputs are
combined with a single psum over 'model' — one collective per MoE layer, the
same volume as a tensor-parallel all-reduce.

Dispatch is capacity-based gather/scatter (no (tokens, E, C) one-hot einsum):
FLOPs per shard = E_local * C * d * ff * 6, i.e. the *active* FLOPs, so the
roofline numbers reflect real MoE arithmetic rather than a dense-mix upper
bound. Tokens overflowing an expert's capacity are dropped (GShard-style),
capacity_factor controls slack.

On a mesh without a usable 'model' axis (CPU tests) the same inner routine
runs unsharded with E_local = E, so numerics are identical by construction
up to two deliberate, standard EP semantics: (1) capacity is enforced per
data shard, so *which* overflowing tokens drop depends on the DP sharding
(at capacity_factor where no drops occur the paths agree to float tolerance);
(2) the load-balance aux is averaged per shard then pmean'd — an unbiased
per-device estimator (Switch-style) that differs from the global product of
means by O(cross-shard routing covariance).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig, MoEConfig
from repro.common.sharding import active_rules, with_logical_constraint
from repro.nn.core import ParamSpec, fan_in_init
from repro.nn.mlp import mlp_apply, mlp_spec


def moe_spec(cfg: ModelConfig):
    m: MoEConfig = cfg.moe
    d, f, e = cfg.d_model, m.expert_ff, m.num_experts
    spec = {
        "router": {"w": ParamSpec((d, e), ("embed", None), fan_in_init(0))},
        "gate_w": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"),
                            fan_in_init(1)),
        "up_w": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"),
                          fan_in_init(1)),
        "down_w": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed"),
                            fan_in_init(1)),
    }
    if m.num_shared_experts:
        spec["shared"] = mlp_spec(d, f * m.num_shared_experts, glu=True)
    return spec


def _capacity(n_tokens: int, m: MoEConfig) -> int:
    c = int(n_tokens * m.top_k / m.num_experts * m.capacity_factor)
    return max(8, (c + 7) // 8 * 8)


def _expert_ffn(xe, gate_w, up_w, down_w, compute_dtype):
    """xe: (E_loc, C, d) -> (E_loc, C, d)."""
    g = jnp.einsum("ecd,edf->ecf", xe, gate_w.astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, up_w.astype(compute_dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, down_w.astype(compute_dtype))


def _dispatch_compute(
    x_flat: jnp.ndarray,      # (N, d)
    top_ids: jnp.ndarray,     # (N, k) int32, global expert ids
    top_gates: jnp.ndarray,   # (N, k)
    gate_w, up_w, down_w,     # (E_loc, d, f) / (E_loc, f, d)
    e_start: int,
    capacity: int,
    compute_dtype,
) -> jnp.ndarray:
    n, k = top_ids.shape
    e_loc = gate_w.shape[0]
    local_id = top_ids - e_start
    is_local = (local_id >= 0) & (local_id < e_loc)
    local_id = jnp.where(is_local, local_id, e_loc)          # e_loc = sentinel

    onehot = (local_id.reshape(n * k, 1)
              == jnp.arange(e_loc, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                # position in expert
    pos_sel = jnp.sum(pos * onehot, axis=1)                  # (N*k,)
    valid = is_local.reshape(-1) & (pos_sel < capacity)
    slot = jnp.where(valid, local_id.reshape(-1) * capacity + pos_sel,
                     e_loc * capacity)                       # OOB -> dropped

    token_row = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    dispatch_idx = jnp.full((e_loc * capacity,), n, dtype=jnp.int32)
    dispatch_idx = dispatch_idx.at[slot].set(token_row, mode="drop")
    slot_gate = jnp.zeros((e_loc * capacity,), dtype=jnp.float32)
    slot_gate = slot_gate.at[slot].set(top_gates.reshape(-1), mode="drop")

    x_pad = jnp.concatenate(
        [x_flat, jnp.zeros((1, x_flat.shape[1]), x_flat.dtype)], axis=0)
    xe = x_pad[dispatch_idx].reshape(e_loc, capacity, -1)
    ye = _expert_ffn(xe, gate_w, up_w, down_w, compute_dtype)
    ye = ye.reshape(e_loc * capacity, -1) * slot_gate[:, None].astype(ye.dtype)

    out = jnp.zeros((n + 1, x_flat.shape[1]), dtype=ye.dtype)
    out = out.at[dispatch_idx].add(ye)
    return out[:n]


def _route(x_flat, router_w, m: MoEConfig, compute_dtype):
    logits = jnp.einsum("nd,de->ne", x_flat,
                        router_w.astype(compute_dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, m.top_k)
    top_gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # load-balance aux (Switch/GShard style)
    e = m.num_experts
    dispatch_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_ids, e, dtype=jnp.float32), axis=1), axis=0
    ) / m.top_k
    prob_frac = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(dispatch_frac * prob_frac)
    return top_ids.astype(jnp.int32), top_gates, aux


def moe_apply(
    params,
    x: jnp.ndarray,          # (B, S, d)
    cfg: ModelConfig,
    compute_dtype=jnp.bfloat16,
):
    """Returns (y, aux_loss)."""
    m: MoEConfig = cfg.moe
    b, s, d = x.shape
    rules = active_rules()
    mesh = rules.mesh if rules is not None else None
    model_size = 1
    if mesh is not None and "model" in mesh.axis_names:
        model_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    use_ep = (
        mesh is not None
        and model_size > 1
        and m.num_experts % model_size == 0
    )

    x = with_logical_constraint(x.astype(compute_dtype), ("batch", "seq", None))

    if use_ep:
        e_loc = m.num_experts // model_size
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_local = (b * s) // _mesh_size(mesh, batch_axes)
        capacity = _capacity(n_local, m)

        # Cast expert weights to the compute dtype while still FSDP-sharded
        # (constraint pins the layout) so the all-gather feeding shard_map
        # moves bf16, not fp32 — half the dominant collective volume.
        w_axes = ("experts", "embed", "expert_mlp")
        gate_w = with_logical_constraint(
            params["gate_w"].astype(compute_dtype), w_axes)
        up_w = with_logical_constraint(
            params["up_w"].astype(compute_dtype), w_axes)
        down_w = with_logical_constraint(
            params["down_w"].astype(compute_dtype),
            ("experts", "expert_mlp", "embed"))

        def local_fn(x_blk, router_w, gate_w, up_w, down_w):
            bb, ss, dd = x_blk.shape
            x_flat = x_blk.reshape(bb * ss, dd)
            top_ids, top_gates, aux = _route(x_flat, router_w, m, compute_dtype)
            e_start = jax.lax.axis_index("model") * e_loc
            y = _dispatch_compute(x_flat, top_ids, top_gates,
                                  gate_w, up_w, down_w,
                                  e_start, capacity, compute_dtype)
            y = jax.lax.psum(y, axis_name="model")
            aux = jax.lax.pmean(aux, axis_name=batch_axes + ("model",))
            return y.reshape(bb, ss, dd), aux

        bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None)
        y, aux = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(bspec, P(None, None), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=(bspec, P()),
            check_vma=False,
        )(x, params["router"]["w"], gate_w, up_w, down_w)
    else:
        x_flat = x.reshape(b * s, d)
        capacity = _capacity(b * s, m)
        top_ids, top_gates, aux = _route(x_flat, params["router"]["w"], m,
                                         compute_dtype)
        y = _dispatch_compute(x_flat, top_ids, top_gates,
                              params["gate_w"], params["up_w"],
                              params["down_w"], 0, capacity, compute_dtype)
        y = y.reshape(b, s, d)

    if "shared" in params:
        y = y + mlp_apply(params["shared"], x, cfg, compute_dtype)
    y = with_logical_constraint(y, ("batch", "seq", None))
    return y, aux * m.router_aux_weight


def _mesh_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    return n
