"""Attention (GQA/MQA + MLA) and FFN (dense GLU + MoE) layers."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map

from repro.models.common import dense_init, rmsnorm, rmsnorm_init, rope
from repro.models.transformer.config import TransformerConfig

NEG_INF = -1e30


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------


def gqa_init(key, cfg: TransformerConfig):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.params_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, kv * hd, dt),
        "wv": dense_init(ks[2], d, kv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    return p


def _qkv(p, x, cfg: TransformerConfig):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (
        q.reshape(b, s, h, hd),
        k.reshape(b, s, kv, hd),
        v.reshape(b, s, kv, hd),
    )


def _sdpa(q, k, v, mask, scale):
    """q: [B,S,H,D], k/v: [B,T,KV,D] (KV divides H).  f32 softmax."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    q = q.reshape(b, s, kvh, groups, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, v.shape[-1])


CHUNK_THRESHOLD = 8192   # switch to online-softmax attention above this S
BQ, BK = 512, 1024       # query/key block sizes (f32 score block ≤ B·H·BQ·BK)


def _sdpa_chunked(q, k, v, scale, bq: int = BQ, bk: int = BK):
    """Memory-efficient causal attention (online softmax over KV blocks).

    The O(S²) score matrix never materializes: a double ``lax.scan`` over
    (query blocks × key blocks) carries the running (max, denom, accum) —
    the standard FlashAttention recurrence expressed in pure JAX so XLA
    keeps live memory at O(BQ·BK) per (batch, head).  Fully-masked key
    blocks still execute (a static-shape tradeoff; see EXPERIMENTS.md §Perf
    for the skip-upper-triangle iteration)."""
    b, s, h, d = q.shape
    t = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    bq = bq if s % bq == 0 and s >= bq else s
    bk = bk if t % bk == 0 and t >= bk else t
    nq, nk = s // bq, t // bk
    dv = v.shape[-1]

    qb = q.reshape(b, nq, bq, kvh, g, d).transpose(1, 0, 3, 4, 2, 5)  # [nq,b,kv,g,bq,d]
    kb = k.reshape(b, nk, bk, kvh, d).transpose(1, 0, 3, 2, 4)        # [nk,b,kv,bk,d]
    vb = v.reshape(b, nk, bk, kvh, dv).transpose(1, 0, 3, 2, 4)

    q_pos = jnp.arange(bq)
    k_pos = jnp.arange(bk)

    def q_block(_, qi):
        q_blk, q_idx = qi                                   # [b,kv,g,bq,d]

        def k_block(carry, ki):
            m, l, acc = carry
            k_blk, v_blk, k_idx = ki
            scores = (
                jnp.einsum("bkgqd,bktd->bkgqt", q_blk, k_blk).astype(jnp.float32)
                * scale
            )
            causal = (q_idx * bq + q_pos)[:, None] >= (k_idx * bk + k_pos)[None, :]
            scores = jnp.where(causal[None, None, None], scores, NEG_INF)
            blk_max = scores.max(axis=-1)
            new_m = jnp.maximum(m, blk_max)
            safe_m = jnp.where(new_m > NEG_INF / 2, new_m, 0.0)
            p = jnp.exp(scores - safe_m[..., None])
            p = jnp.where(causal[None, None, None], p, 0.0)
            corr = jnp.exp(jnp.where(m > NEG_INF / 2, m - safe_m, NEG_INF))
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p, v_blk.astype(jnp.float32)
            )
            return (new_m, l_new, acc_new), None

        init = (
            jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, bq), jnp.float32),
            jnp.zeros((b, kvh, g, bq, dv), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            k_block, init, (kb, vb, jnp.arange(nk))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, out = jax.lax.scan(
        q_block, None, (qb, jnp.arange(nq))
    )                                                        # [nq,b,kv,g,bq,dv]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, dv)
    return out.astype(q.dtype)


def gqa_attention(p, x, positions, cfg: TransformerConfig, kv_cache=None, cache_len=None):
    """Returns (out, new_kv).  kv_cache = (k, v) ring buffers for decode."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    scale = cfg.head_dim ** -0.5

    if kv_cache is None:
        if s >= CHUNK_THRESHOLD:
            out = _sdpa_chunked(q, k, v, scale)
        else:
            t = jnp.arange(s)
            mask = (t[:, None] >= t[None, :])[None, None, None]  # key ≤ query
            out = _sdpa(q, k, v, mask, scale)
        out = out.reshape(b, s, -1) @ p["wo"]
        return out, (k, v)

    ck, cv = kv_cache
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_len, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_len, axis=1)
    t = ck.shape[1]
    kpos = jnp.arange(t)
    qpos = positions[0] if positions.ndim else positions
    mask = (kpos[None, :] <= (qpos + jnp.arange(s))[:, None])[None, None, None]
    out = _sdpa(q, ck, cv, mask, scale)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, (ck, cv)


# --------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# --------------------------------------------------------------------------


def mla_init(key, cfg: TransformerConfig):
    d, h = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (
        cfg.kv_lora_rank,
        cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim,
        cfg.v_head_dim,
    )
    dt = cfg.params_dtype
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], d, h * (dn + dr), dt),
        "w_dkv": dense_init(ks[1], d, r + dr, dt),       # joint compress + rope key
        "w_uk": dense_init(ks[2], r, h * dn, dt),
        "w_uv": dense_init(ks[3], r, h * dv, dt),
        "wo": dense_init(ks[4], h * dv, d, dt),
        "kv_norm": rmsnorm_init(r, dt),
    }


def mla_attention(p, x, positions, cfg: TransformerConfig, kv_cache=None, cache_len=None):
    """MLA with compressed-KV cache; decode uses the *absorbed* formulation
    (W_uk folded into the query, attention runs in the latent space) so the
    per-step cost is O(S·r), not O(S·H·dn)."""
    b, s, _ = x.shape
    h = cfg.n_heads
    r, dn, dr, dv = (
        cfg.kv_lora_rank,
        cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim,
        cfg.v_head_dim,
    )
    scale = (dn + dr) ** -0.5

    q = (x @ p["wq"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    ckv = x @ p["w_dkv"]                                  # [B,S,r+dr]
    c_kv, k_rope = ckv[..., :r], ckv[..., r:]
    c_kv = rmsnorm(p["kv_norm"], c_kv)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    if kv_cache is None:
        # training/prefill: expand per-head keys/values (standard formulation)
        k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, dn)
        v = (c_kv @ p["w_uv"]).reshape(b, s, h, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))],
            axis=-1,
        )
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        if s >= CHUNK_THRESHOLD:
            out = _sdpa_chunked(qf, k, v, scale)
        else:
            mask = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])[
                None, None, None
            ]
            out = _sdpa(qf, k, v, mask, scale)
        out = out.reshape(b, s, -1) @ p["wo"]
        return out, (c_kv, k_rope)

    # decode with absorbed projections against the latent cache
    cc, cr = kv_cache                                     # [B,T,r], [B,T,dr]
    cc = jax.lax.dynamic_update_slice_in_dim(cc, c_kv, cache_len, axis=1)
    cr = jax.lax.dynamic_update_slice_in_dim(cr, k_rope, cache_len, axis=1)
    t = cc.shape[1]
    w_uk = p["w_uk"].reshape(r, h, dn)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)    # absorb W_uk into q
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat, cc)
        + jnp.einsum("bshd,btd->bhst", q_rope, cr)
    ).astype(jnp.float32) * scale
    qpos = positions[0] if positions.ndim else positions
    mask = (jnp.arange(t)[None, :] <= (qpos + jnp.arange(s))[:, None])[None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhst,btr->bshr", probs, cc)     # attend in latent space
    w_uv = p["w_uv"].reshape(r, h, dv)
    out = jnp.einsum("bshr,rhd->bshd", out_lat, w_uv)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, (cc, cr)


# --------------------------------------------------------------------------
# FFN: GLU + MoE (sort-dispatch + ragged GEMM)
# --------------------------------------------------------------------------


def glu_init(key, d: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d, d_ff, dtype),
        "w_up": dense_init(ks[1], d, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d, dtype),
    }


def glu_apply(p, x, activation: str):
    act = jax.nn.silu if activation == "swiglu" else jax.nn.gelu
    return (act(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def moe_init(key, cfg: TransformerConfig):
    d, e, dff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    dt = cfg.params_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": dense_init(ks[1], d, e * dff, dt).reshape(e, d, dff) * 1.0,
        "w_up": dense_init(ks[2], d, e * dff, dt).reshape(e, d, dff),
        "w_down": dense_init(ks[3], e * dff, d, dt).reshape(e, dff, d),
    }
    if cfg.n_shared_experts:
        p["shared"] = glu_init(
            ks[4], d, cfg.d_ff_expert * cfg.n_shared_experts, dt
        )
    return p


def moe_apply(p, x, cfg: TransformerConfig):
    """Token-choice top-k MoE via sort + ragged GEMM (MegaBlocks-style).

    Dispatch is a relational group-by: stable-sort the (token, expert) pairs
    by expert, run one grouped GEMM per projection over contiguous expert
    segments (``jax.lax.ragged_dot``), scatter-add back weighted by router
    probs.  EP shards the expert dim of the weights over the ``model`` axis.

    If a mesh context is active (repro.distributed.context), dispatch runs
    under an explicit ``shard_map`` EP region instead of GSPMD propagation —
    the §Roofline fix for the replicated scatter-combine all-reduce.
    """
    from repro.distributed.context import get_mesh

    if get_mesh() is not None and cfg.n_experts > 1:
        return _moe_apply_ep(p, x, cfg)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, d)
    t = xt.shape[0]

    logits = (xt.astype(jnp.float32)) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                 # [T,k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(-1)                             # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    tok = order // k
    xs = xt[tok]                                           # [T*k, d] sorted by expert
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

    act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
    gate = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    up = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    h = act(gate) * up
    ys = jax.lax.ragged_dot(h, p["w_down"], group_sizes)   # [T*k, d]

    w = top_p.reshape(-1)[order].astype(ys.dtype)
    out = jnp.zeros((t, d), ys.dtype).at[tok].add(ys * w[:, None])

    if cfg.n_shared_experts:
        out = out + glu_apply(p["shared"], xt, cfg.activation)

    # Switch-style load-balance auxiliary loss
    density = jnp.mean(
        jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=(0, 1)
    )
    router_mean = probs.mean(0)
    aux = cfg.router_aux_coef * e * jnp.sum(density * router_mean) * k
    return out.reshape(b, s, d), aux


def _moe_apply_ep(p, x, cfg: TransformerConfig):
    """Explicit expert-parallel MoE (shard_map): experts sharded over
    ``model``; each shard computes ONLY its local experts' contributions to
    the (dp-sharded, tp-replicated) tokens, then one bf16 psum combines —
    payload T_loc × d per layer instead of GSPMD's repeated replicated
    scatter-combines (measured 2 orders of magnitude less collective traffic
    on deepseek/granite train; see EXPERIMENTS.md §Perf-MoE)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.context import get_dp_axes, get_mesh

    mesh = get_mesh()
    dp = get_dp_axes()
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tp = mesh.shape["model"]
    e_loc = e // tp

    def local(x_l, router, w_gate_l, w_up_l, w_down_l):
        bl, sl, _ = x_l.shape
        xt = x_l.reshape(-1, d)
        t = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        shard = jax.lax.axis_index("model")
        lo = shard * e_loc
        # keep only assignments routed to this shard's experts
        local_e = top_i - lo
        mine = (local_e >= 0) & (local_e < e_loc)
        flat_e = jnp.where(mine, local_e, e_loc).reshape(-1)   # e_loc = drop bin
        order = jnp.argsort(flat_e, stable=True)
        tok = order // k
        xs = xt[tok]
        group_sizes = jnp.bincount(flat_e, length=e_loc + 1).astype(jnp.int32)

        act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        pad = jnp.zeros((1,) + w_gate_l.shape[1:], w_gate_l.dtype)
        wg = jnp.concatenate([w_gate_l, pad], 0)
        wu = jnp.concatenate([w_up_l, pad], 0)
        pad_d = jnp.zeros((1,) + w_down_l.shape[1:], w_down_l.dtype)
        wd = jnp.concatenate([w_down_l, pad_d], 0)
        h = act(jax.lax.ragged_dot(xs, wg, group_sizes)) * jax.lax.ragged_dot(
            xs, wu, group_sizes
        )
        ys = jax.lax.ragged_dot(h, wd, group_sizes)

        w = jnp.where(mine, top_p, 0.0).reshape(-1)[order].astype(ys.dtype)
        partial = jnp.zeros((t, d), ys.dtype).at[tok].add(ys * w[:, None])
        out = jax.lax.psum(partial, "model")                 # the ONE combine

        density = jnp.mean(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=(0, 1))
        aux_l = cfg.router_aux_coef * e * jnp.sum(density * probs.mean(0)) * k
        aux = jax.lax.pmean(jax.lax.pmean(aux_l, "model"), dp[-1])
        return out.reshape(bl, sl, d), aux

    out, aux = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),
            P(),                                  # router replicated
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        out = out + glu_apply(p["shared"], x.reshape(-1, d), cfg.activation).reshape(
            b, s, d
        )
    return out, aux
