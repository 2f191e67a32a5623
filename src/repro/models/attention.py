"""Attention: chunked (flash-style) train/prefill path + flash-decoding.

Design (DESIGN.md §6):

* The residual stream is sequence-sharded (Megatron-SP).  QKV projections
  are plain einsums under GSPMD constraints; the attention *core* runs
  inside ``shard_map`` so chunking/masking is pure local compute with
  explicit collectives:

  - ``tp`` strategy — q heads sharded over ``model``; KV (GQA heads <
    axis) replicated; no collective inside the core.
  - ``fsdp_cp`` strategy — q sequence sharded over ``model`` (context
    parallelism); KV all-gathered once inside the core.

* The core is flash-style: ``lax.map`` over q blocks × ``lax.scan`` over
  KV chunks with running (max, sum, acc) — the ``(S, S)`` score matrix is
  never materialized, which is what makes ``prefill_32k`` lowerable.  The
  whole core is ``jax.checkpoint``-ed: backward recomputes the chunk loop
  (FlashAttention backward) instead of saving per-chunk stats.

* Decode is flash-decoding: the KV cache is sequence-sharded (over
  ``model``, plus ``data``/``pod`` for ``long_500k``); each shard computes
  partial (max, sumexp, acc) and a ``pmax``+``psum`` pair combines —
  O(heads·d) bytes on the wire per token instead of the cache.

Masks are computed from explicit global *position* tensors, so causal,
sliding-window (Mixtral/Gemma local layers) and cache-validity masking is
one code path, and context-parallel offsets come for free.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, current_rules, logical_constraint as lc
from repro.models.common import ParamSpec, rms_norm

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def attention_specs(d_model: int, n_heads: int, n_kv: int, head_dim: int,
                    qk_norm: bool = False) -> dict:
    specs = {
        "wq": ParamSpec((d_model, n_heads, head_dim),
                        ("p_embed_attn", "p_heads", "p_head_dim"), "scaled"),
        "wk": ParamSpec((d_model, n_kv, head_dim),
                        ("p_embed_attn", "p_kv_heads", "p_head_dim"), "scaled"),
        "wv": ParamSpec((d_model, n_kv, head_dim),
                        ("p_embed_attn", "p_kv_heads", "p_head_dim"), "scaled"),
        "wo": ParamSpec((n_heads, head_dim, d_model),
                        ("p_heads", "p_head_dim", "p_embed_attn"), "scaled"),
    }
    if qk_norm:
        specs["q_norm"] = ParamSpec((head_dim,), ("p_none",), "zeros")
        specs["k_norm"] = ParamSpec((head_dim,), ("p_none",), "zeros")
    return specs


def project_qkv(params: dict, x: jax.Array, eps: float = 1e-6):
    """x (b, s, d) → q (b, s, h, hd), k/v (b, s, n_kv, hd), with constraints."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", x, params["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", x, params["wv"])
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], eps)
        k = rms_norm(k, params["k_norm"], eps)
    q = lc(q, "batch", "q_seq", "heads", "head_dim")
    k = lc(k, "batch", "q_seq", "kv_heads", "head_dim")
    v = lc(v, "batch", "q_seq", "kv_heads", "head_dim")
    return q, k, v


def project_out(params: dict, attn: jax.Array) -> jax.Array:
    out = jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    return lc(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# local flash-style core (runs per shard)
# ---------------------------------------------------------------------------


def _mask(q_pos, k_pos, *, causal: bool, window):
    """(bq,)×(bk,) positions → (bq, bk) additive mask (0 / NEG_INF)."""
    valid = k_pos[None, :] >= 0
    if causal:
        valid &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        # w <= 0 means "full attention" (traced per-layer switch, e.g. gemma3)
        in_window = (q_pos[:, None] - k_pos[None, :]) < jnp.where(w > 0, w, 1 << 30)
        valid &= in_window
    return jnp.where(valid, 0.0, NEG_INF)


def _flash_core(q, k, v, q_pos, k_pos, *, causal, window, softcap, scale,
                q_block: int = 512, kv_block: int = 1024):
    """Local chunked attention.  q (b, sq, n, g, d); k/v (b, sk, n, d);
    q_pos (b, sq); k_pos (b, sk) → out (b, sq, n, g, d).  fp32 accumulation.
    """
    b, sq, n, g, d = q.shape
    sk = k.shape[1]
    qb = min(q_block, sq)
    while sq % qb:
        qb //= 2
    kb = min(kv_block, sk)
    while sk % kb:
        kb //= 2
    nq, nk = sq // qb, sk // kb

    qf = (q.astype(jnp.float32) * scale).reshape(b, nq, qb, n, g, d)
    qf = jnp.moveaxis(qf, 1, 0)                       # (nq, b, qb, n, g, d)
    qp = jnp.moveaxis(q_pos.reshape(b, nq, qb), 1, 0)  # (nq, b, qb)
    kf = k.astype(jnp.float32).reshape(b, nk, kb, n, d)
    vf = v.astype(jnp.float32).reshape(b, nk, kb, n, d)
    kp = k_pos.reshape(b, nk, kb)

    def per_qblock(args):
        qblk, qpos = args                              # (b, qb, n, g, d), (b, qb)

        def kv_step(carry, inp):
            m, lse, acc = carry
            kblk, vblk, kpos = inp                     # (b, kb, n, d) ×2, (b, kb)
            s = jnp.einsum("bqngd,bknd->bngqk", qblk, kblk)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            msk = jax.vmap(lambda qp_, kp_: _mask(qp_, kp_, causal=causal,
                                                  window=window))(qpos, kpos)
            s = s + msk[:, None, None, :, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = lse * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bngqk,bknd->bngqd", p, vblk)
            return (m_new, l_new, acc_new), None

        # derive the carries from qblk so their varying-manual-axes type
        # matches the loop outputs exactly (q's vma ⊇ k's in every layout)
        zq = jnp.moveaxis(qblk * 0.0, 1, 3)            # (b, n, g, qb, d)
        m0 = zq[..., 0] + NEG_INF
        l0 = zq[..., 0]
        a0 = zq
        (m, lse, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0), jnp.moveaxis(kp, 1, 0)),
        )
        out = acc / jnp.maximum(lse[..., None], 1e-30)  # (b, n, g, qb, d)
        return jnp.moveaxis(out, 3, 1)                 # (b, qb, n, g, d)

    out = jax.lax.map(jax.checkpoint(per_qblock), (qf, qp))  # (nq, b, qb, n, g, d)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, n, g, d)
    return out


def _local_attention(q, k, v, q_pos, k_pos, *, causal, window, softcap,
                     gather_axis=None):
    """Per-shard body: optional KV all-gather (context parallelism), then
    the flash core over GQA-grouped heads."""
    b, sq, h, d = q.shape
    n = k.shape[2]
    g = h // n
    if gather_axis is not None:
        k = jax.lax.all_gather(k, gather_axis, axis=1, tiled=True)
        v = jax.lax.all_gather(v, gather_axis, axis=1, tiled=True)
        k_pos = jax.lax.all_gather(k_pos, gather_axis, axis=1, tiled=True)
    qg = q.reshape(b, sq, n, g, d)
    out = _flash_core(qg, k, v, q_pos, k_pos, causal=causal, window=window,
                      softcap=softcap, scale=1.0 / (d ** 0.5))
    return out.reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def _batch_axes(rules):
    ax = rules.get("batch")
    return tuple(ax) if ax else ()


def multihead_attention(q, k, v, q_pos, k_pos, *, causal=True, window=None,
                        softcap=0.0):
    """Train/prefill attention.  q (b, s, h, hd); k/v (b, s, n_kv, hd);
    positions (b, s) int32.  Runs in shard_map when a mesh is active."""
    mesh, rules = current_mesh(), current_rules()
    if mesh is None or not rules:
        return _local_attention(q, k, v, q_pos, k_pos, causal=causal,
                                window=window, softcap=softcap)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msize = sizes.get("model", 1)
    batch = _batch_axes(rules)
    bprod = 1
    for a in batch:
        bprod *= sizes.get(a, 1)
    if q.shape[0] % max(bprod, 1):
        batch = ()                       # tiny batch: replicate it
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)

    tp_heads = rules.get("heads") is not None and q.shape[2] % msize == 0
    seq_ok = q.shape[1] % msize == 0 and k.shape[1] % msize == 0
    if tp_heads:
        # GQA + head-TP: repeat kv to q-head count so per-shard grouping is
        # index-free (shard s's q heads pair with their own kv copies).
        g = q.shape[2] // k.shape[2]
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        qspec = P(bspec, None, "model", None)
        kspec = P(bspec, None, "model", None)
        pspec = P(bspec, None)
        gather_axis = None
    elif seq_ok:
        qspec = P(bspec, "model", None, None)
        kspec = P(bspec, "model", None, None)
        pspec = P(bspec, "model")
        gather_axis = "model"
    else:
        # degenerate (single-token prefill etc.): replicate over 'model'
        qspec = P(bspec, None, None, None)
        kspec = P(bspec, None, None, None)
        pspec = P(bspec, None)
        gather_axis = None

    body = partial(_local_attention, causal=causal, window=window,
                   softcap=softcap, gather_axis=gather_axis)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(qspec, kspec, kspec, pspec, pspec),
        out_specs=qspec,
    )
    return fn(q, k, v, q_pos, k_pos)


def _partials(qg, k, v, q_pos, k_pos, *, window, softcap, causal=True):
    """(m, l, acc) partial-softmax stats for one KV segment."""
    s = jnp.einsum("bqngd,bknd->bngqk", qg, k.astype(jnp.float32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    msk = jax.vmap(lambda qp_, kp_: _mask(qp_, kp_, causal=causal, window=window))(
        q_pos, k_pos
    )
    s = s + msk[:, None, None, :, :]
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    lse = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bngqk,bknd->bngqd", p, v.astype(jnp.float32))
    return m, lse, acc


def _decode_body(q, k, v, q_pos, k_pos, k_self, v_self, *, window, softcap,
                 kv_axes, has_self, causal=True):
    """Flash-decoding: per-shard partials over the cache segment, a
    pmax+psum combine across KV shards, then the (replicated) self-token
    contribution folded in — the new token's KV never touches the cache
    inside the layer scan (it is scattered in once, outside)."""
    b, sq, h, d = q.shape
    n = k.shape[2]
    g = h // n
    qg = (q.astype(jnp.float32) / (d ** 0.5)).reshape(b, sq, n, g, d)

    m_l, l_l, acc_l = _partials(qg, k, v, q_pos, k_pos, window=window,
                                softcap=softcap, causal=causal)
    if kv_axes:
        m = jax.lax.pmax(m_l, kv_axes)
        corr = jnp.exp(m_l - m)
        lse, acc = jax.lax.psum((l_l * corr, acc_l * corr[..., None]), kv_axes)
    else:
        m, lse, acc = m_l, l_l, acc_l

    if has_self:
        # self tokens are always in-window and causal-valid for themselves
        m_s, l_s, acc_s = _partials(qg, k_self, v_self, q_pos, q_pos,
                                    window=window, softcap=softcap)
        m2 = jnp.maximum(m, m_s)
        c1, c2 = jnp.exp(m - m2), jnp.exp(m_s - m2)
        lse = lse * c1 + l_s * c2
        acc = acc * c1[..., None] + acc_s * c2[..., None]

    out = acc / jnp.maximum(lse[..., None], 1e-30)     # (b, n, g, q, d)
    return jnp.moveaxis(out, 3, 1).reshape(b, sq, h, d).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, q_pos, kv_pos, *, window=None,
                     softcap=0.0, self_kv=None, causal=True):
    """Single-token (or few-token) decode against a sharded KV cache.

    q (b, sq, h, hd); caches (b, S, n_kv, hd); q_pos (b, sq); kv_pos (b, S)
    with −1 marking unwritten slots.  ``self_kv=(k_new, v_new)`` (b, sq,
    n_kv, hd) folds the current token(s) in without a cache rewrite.
    """
    mesh, rules = current_mesh(), current_rules()
    has_self = self_kv is not None
    k_self, v_self = self_kv if has_self else (
        jnp.zeros_like(q[:, :, : k_cache.shape[2]]), jnp.zeros_like(q[:, :, : k_cache.shape[2]])
    )
    if mesh is None or not rules:
        return _decode_body(q, k_cache, v_cache, q_pos, kv_pos, k_self, v_self,
                            window=window, softcap=softcap, kv_axes=(),
                            has_self=has_self, causal=causal)

    kv_axes = tuple(rules.get("kv_seq") or ())
    kv_axes = tuple(a for a in kv_axes if a in mesh.axis_names)
    batch = tuple(a for a in _batch_axes(rules) if a not in kv_axes)
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    kvspec = kv_axes if len(kv_axes) > 1 else (kv_axes[0] if kv_axes else None)

    qspec = P(bspec, None, None, None)
    cspec = P(bspec, kvspec, None, None)
    sspec = P(bspec, None, None, None)
    fn = shard_map(
        partial(_decode_body, window=window, softcap=softcap, kv_axes=kv_axes,
                has_self=has_self, causal=causal),
        mesh=mesh,
        in_specs=(qspec, cspec, cspec, P(bspec, None), P(bspec, kvspec),
                  sspec, sspec),
        out_specs=qspec,
    )
    return fn(q, k_cache, v_cache, q_pos, kv_pos, k_self, v_self)
