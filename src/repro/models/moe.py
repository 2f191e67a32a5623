"""Mixture-of-Experts MLP (Mixtral 8×7b, Grok-1) — GShard-style top-k
capacity routing inside ``shard_map``.

Baseline design (DESIGN.md §6, hillclimbed in EXPERIMENTS.md §Perf):

* tokens stay sharded over (``data`` × ``model``) — routing, dispatch and
  expert GEMMs are token-local, so no all-to-all is needed;
* expert weights are stored fully sharded (ZeRO-3: ``d`` over ``data``,
  ``d_ff`` over ``model``) and all-gathered *inside* the region once per
  layer — the collective cost this trades for the all-to-all is exactly
  what the roofline's collective term exposes;
* dispatch is scatter-based (no ``(tokens, E, cap)`` one-hot): each
  (token, slot) pair computes its expert rank via a cumsum and scatters
  into the ``(E, cap, d)`` buffer; tokens beyond capacity are dropped
  (capacity_factor 1.25 train / 2.0 decode, the GShard convention).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current_mesh, current_rules
from repro.models.common import ACTIVATIONS, ParamSpec


def moe_mlp_specs(d_model: int, d_ff: int, act: str = "silu", *,
                  n_experts: int = 8) -> dict:
    E = n_experts
    specs = {
        "w_router": ParamSpec((d_model, E), ("p_none", "p_none"), "scaled"),
        "w_up": ParamSpec((E, d_model, d_ff), ("p_expert", "p_embed", "p_mlp"),
                          "scaled"),
        "w_down": ParamSpec((E, d_ff, d_model), ("p_expert", "p_mlp", "p_embed"),
                            "scaled"),
    }
    if act in ("silu", "gelu"):
        specs["w_gate"] = ParamSpec((E, d_model, d_ff),
                                    ("p_expert", "p_embed", "p_mlp"), "scaled")
    return specs


def _gather_full(w, dims_axes):
    """all-gather a ZeRO-sharded weight back to full inside shard_map."""
    for dim, axis in dims_axes:
        w = jax.lax.all_gather(w, axis, axis=dim, tiled=True)
    return w


def _moe_local(x, wr, wg, wu, wd, *, top_k: int, cap_frac: float, act: str,
               gather: tuple):
    """Per-shard MoE: route → scatter-dispatch → expert GEMMs → combine.

    x (b_l, s_l, d) local tokens; weights local ZeRO shards (re-gathered).
    """
    fn = ACTIVATIONS[act]
    if gather:
        wu = _gather_full(wu, gather)
        wd = _gather_full(wd, [(2 if d == 1 else 1, a) for d, a in gather])
        if wg is not None:
            wg = _gather_full(wg, gather)

    b, s, d = x.shape
    E = wr.shape[1]
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)          # (t, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)  # mixtral renorm

    cap = max(8, int(t * top_k * cap_frac / E + 0.999))
    cap = min(cap, t * top_k)

    # rank of each (token, slot) among same-expert assignments (token order)
    e_flat = idx.reshape(-1)                               # (t*k,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)    # (t*k, E)
    ranks = (jnp.cumsum(onehot, axis=0) - onehot)          # exclusive
    r_flat = jnp.take_along_axis(ranks, e_flat[:, None], axis=1)[:, 0]
    keep = (r_flat < cap)
    r_safe = jnp.where(keep, r_flat, 0)

    xk = jnp.repeat(xt, top_k, axis=0)                     # (t*k, d)
    contrib = jnp.where(keep[:, None], xk, 0.0)
    x_disp = jnp.zeros((E, cap, d), xt.dtype).at[e_flat, r_safe].add(
        jnp.where(keep[:, None], contrib, 0.0))

    h = jnp.einsum("ecd,edf->ecf", x_disp, wu)
    if wg is not None:
        h = fn(jnp.einsum("ecd,edf->ecf", x_disp, wg)) * h
    else:
        h = fn(h)
    y_disp = jnp.einsum("ecf,efd->ecd", h, wd)             # (E, cap, d)

    y_tok = y_disp[e_flat, r_safe]                         # (t*k, d)
    y_tok = y_tok * (keep[:, None] * gate_vals.reshape(-1)[:, None]).astype(
        y_tok.dtype)
    y = jnp.sum(y_tok.reshape(t, top_k, d), axis=1)
    return y.reshape(b, s, d).astype(x.dtype)


def _moe_ep(x, wr, wg, wu, wd, *, top_k: int, cap_frac: float, act: str,
            n_experts: int, model_size: int):
    """§Perf-2: expert-parallel MoE — tokens move, weights (mostly) stay.

    The 'model' axis is factored as (E experts × fs replicas), fs =
    model_size // E; device j serves expert j // fs for token-sub-batch
    j % fs.  Per layer:
    1. weight reshard: one all_to_all redistributes the resident f-shards
       so each device reconstructs its OWN expert's full (d, f) — ≈ E·3·d·f
       / model_size bytes per device instead of all-gathering all experts;
    2. route + capacity-dispatch locally;
    3. all_to_all tokens to their expert's replica group (cap split fs
       ways), expert GEMMs, all_to_all back, combine — 2 activation
       all-to-alls of ≈ t·k·cf·d bytes.
    grok-1 per device per layer: ≈1.2 GB weights + 0.5 GB tokens vs the
    gather variant's 9.7 GB weight broadcast."""
    fn = ACTIVATIONS[act]
    E = n_experts
    fs = model_size // E
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    def reshard_weight(w, f_axis):
        # w local: (E, d/16, f/16) (or (E, f/16, d/16) for w_down).
        # gather the FSDP 'data' axis first (small), then all_to_all the
        # f-shards: peer p contributes its f-columns of MY expert.
        d_axis = 1 if f_axis == 2 else 2
        w = jax.lax.all_gather(w, "data", axis=d_axis, tiled=True)
        # send[p] = my f-shard of expert p//fs  → (model, d, f/16)
        send = jnp.take(w, jnp.arange(model_size) // fs, axis=0)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv[p] = peer p's f-shard of my expert → concat on the f axis
        return jnp.concatenate(
            [recv[p] for p in range(model_size)], axis=f_axis - 1)

    wu_f = reshard_weight(wu, f_axis=2)            # (d, f)
    wg_f = reshard_weight(wg, f_axis=2) if wg is not None else None
    wd_f = reshard_weight(wd, f_axis=1)            # (f, d)

    logits = xt.astype(jnp.float32) @ wr.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    cap = max(8, int(t * top_k * cap_frac / E + 0.999))
    cap = cap + (-cap) % fs                        # replica-divisible

    e_flat = idx.reshape(-1)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot
    r_flat = jnp.take_along_axis(ranks, e_flat[:, None], axis=1)[:, 0]
    keep = r_flat < cap
    r_safe = jnp.where(keep, r_flat, 0)
    xk = jnp.repeat(xt, top_k, axis=0)
    x_disp = jnp.zeros((E, cap, d), xt.dtype).at[e_flat, r_safe].add(
        jnp.where(keep[:, None], xk, 0.0))

    # tokens → expert owners: slice j gets expert j//fs, cap-chunk j%fs
    send = x_disp.reshape(E, fs, cap // fs, d).reshape(model_size,
                                                       cap // fs, d)
    recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                              tiled=False)          # (model, cap/fs, d)
    tok = recv.reshape(model_size * (cap // fs), d)
    h = tok @ wu_f
    if wg_f is not None:
        h = fn(tok @ wg_f) * h
    else:
        h = fn(h)
    y = h @ wd_f                                    # full FFN, no partials
    back = jax.lax.all_to_all(y.reshape(model_size, cap // fs, d), "model",
                              split_axis=0, concat_axis=0, tiled=False)
    y_full = back.reshape(E, fs, cap // fs, d).reshape(E, cap, d)

    y_tok = y_full[e_flat, r_safe]
    y_tok = y_tok * (keep[:, None] * gate_vals.reshape(-1)[:, None]).astype(
        y_tok.dtype)
    out = jnp.sum(y_tok.reshape(t, top_k, d), axis=1)
    return out.reshape(b, s, d).astype(x.dtype)


def moe_apply(params: dict, x: jax.Array, act: str = "silu", *,
              top_k: int = 2, capacity_factor: float = 1.25,
              variant: str = "gather") -> jax.Array:
    """MoE MLP entry point (drop-in for ``mlp_apply`` in the dense block)."""
    mesh, rules = current_mesh(), current_rules()
    wg = params.get("w_gate")
    if mesh is None or not rules:
        return _moe_local(x, params["w_router"], wg, params["w_up"],
                          params["w_down"], top_k=top_k,
                          cap_frac=capacity_factor, act=act, gather=())

    batch = tuple(rules.get("batch") or ())
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msize = sizes.get("model", 1)
    n_experts = params["w_up"].shape[0]
    # decode-time single tokens can't shard the seq dim over 'model'
    seq_shardable = x.shape[1] % msize == 0
    use_ep = (variant == "ep" and seq_shardable and msize >= n_experts
              and msize % n_experts == 0 and "data" in sizes)
    xspec = P(bspec, "model" if seq_shardable else None, None)
    # expert weights stored (E, d@data, f@model); re-laid-out inside
    upspec = P(None, "data", "model")
    dnspec = P(None, "model", "data")
    gather = ((1, "data"), (2, "model"))

    if use_ep:
        body = partial(_moe_ep, top_k=top_k, cap_frac=capacity_factor,
                       act=act, n_experts=n_experts, model_size=msize)
    else:
        body = partial(_moe_local, top_k=top_k, cap_frac=capacity_factor,
                       act=act, gather=gather)
    args = [x, params["w_router"], wg, params["w_up"], params["w_down"]]
    specs = [xspec, P(None, None), upspec if wg is not None else P(None, None),
             upspec, dnspec]
    if wg is None:
        args[2] = jnp.zeros((1, 1), x.dtype)  # placeholder, ungathered
    fn = shard_map(
        lambda x_, wr_, wg_, wu_, wd_: body(
            x_, wr_, wg_ if wg is not None else None, wu_, wd_),
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=xspec,
        # vma can't infer replication through gathers/all-to-alls
        check_vma=False,
    )
    return fn(*args)


def make_moe_mlp_fns(cfg: ModelConfig, decode: bool = False):
    """(specs_fn, apply_fn) pair for the dense trunk's MLP slot."""

    def specs_fn(d_model, d_ff, act):
        return moe_mlp_specs(d_model, cfg.moe_dff_, act, n_experts=cfg.n_experts)

    cf = 2.0 if decode else cfg.capacity_factor

    def apply_fn(p, x, act):
        return moe_apply(p, x, act, top_k=cfg.top_k, capacity_factor=cf)

    return specs_fn, apply_fn
