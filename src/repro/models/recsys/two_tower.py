"""Two-tower retrieval (Yi et al., RecSys'19 / Covington, RecSys'16).

Huge sparse embedding tables → EmbeddingBag (the relational hot path; the
Pallas ``embed_bag`` kernel serves it) → per-tower MLP 1024-512-256 →
normalized dot interaction → in-batch sampled softmax with logQ correction.
``retrieval_scores`` scores one query batch against the full candidate corpus
as a single batched GEMM + top-k (no loops).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from jax import shard_map

from repro.models.common import mlp_apply, mlp_init
from repro.relational.embedding import embedding_bag, sampled_softmax_loss


@dataclass(frozen=True)
class RecsysConfig:
    name: str = "two-tower-retrieval"
    embed_dim: int = 256
    tower_dims: tuple[int, ...] = (1024, 512, 256)
    user_vocab: int = 5_000_000
    item_vocab: int = 2_000_000
    user_fields: int = 4            # multi-hot categorical fields per user
    item_fields: int = 2
    field_hots: int = 8             # ids per field (bag size)
    n_dense_feat: int = 13
    temperature: float = 0.05
    dtype: str = "float32"


def init_params(key, cfg: RecsysConfig):
    ks = jax.random.split(key, 4)
    d = cfg.embed_dim
    return {
        "user_table": jax.random.normal(ks[0], (cfg.user_vocab, d)) * 0.01,
        "item_table": jax.random.normal(ks[1], (cfg.item_vocab, d)) * 0.01,
        "user_mlp": mlp_init(
            ks[2],
            (cfg.user_fields * d + cfg.n_dense_feat,) + cfg.tower_dims,
        ),
        "item_mlp": mlp_init(ks[3], (cfg.item_fields * d,) + cfg.tower_dims),
    }


def user_tower(params, user_ids, user_dense, cfg: RecsysConfig):
    """user_ids: int32[B, F_u, K] multi-hot; user_dense: f32[B, n_dense]."""
    b = user_ids.shape[0]
    bags = [
        embedding_bag(params["user_table"], user_ids[:, f])
        for f in range(cfg.user_fields)
    ]
    x = jnp.concatenate(bags + [user_dense], axis=-1)
    q = mlp_apply(params["user_mlp"], x, act=jax.nn.relu)
    return q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-6)


def item_tower(params, item_ids, cfg: RecsysConfig):
    bags = [
        embedding_bag(params["item_table"], item_ids[:, f])
        for f in range(cfg.item_fields)
    ]
    x = jnp.concatenate(bags, axis=-1)
    v = mlp_apply(params["item_mlp"], x, act=jax.nn.relu)
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)


def forward(params, batch, cfg: RecsysConfig):
    q = user_tower(params, batch["user_ids"], batch["user_dense"], cfg)
    v = item_tower(params, batch["item_ids"], cfg)
    return q, v


def loss(params, batch, cfg: RecsysConfig):
    q, v = forward(params, batch, cfg)
    return sampled_softmax_loss(
        q, v, log_q=batch.get("log_q"), temperature=cfg.temperature
    )


# --------------------------------------------------------------------------
# sharded path: vocab-sharded tables with masked local lookup + psum
# --------------------------------------------------------------------------


def sharded_bags(
    table, ids, mesh, dp_axes, tp: str = "model", scatter: bool = False,
    wire_dtype=None,
):
    """EmbeddingBag over a vocab-sharded table without materializing it.

    The table is sharded P(tp, None); each shard looks up only the ids that
    fall in its vocab range (others contribute zero) and one collective over
    ``tp`` assembles the full bags — the canonical sharded-embedding pattern.

    ``scatter=False`` (baseline): ``psum`` — every chip gets all B_loc bags
    (bytes ∝ B_loc·F·D per chip).
    ``scatter=True`` (§Perf variant): ``psum_scatter`` — bags come back
    sharded over ``tp`` along the batch dim (bytes ∝ B_loc·F·D / tp), and
    the tower MLPs run batch-parallel on the tp axis too; only the final
    [B, D] tower outputs are re-gathered for the in-batch softmax.
    ids: int32[B, F, K] (-1 pad) → f32[B(, /tp), F, D].
    """
    from jax.sharding import PartitionSpec as P

    def local(table_l, ids_l):
        vloc = table_l.shape[0]
        lo = jax.lax.axis_index(tp) * vloc
        rel = ids_l - lo
        ok = (ids_l >= 0) & (rel >= 0) & (rel < vloc)
        rows = jnp.take(table_l, jnp.clip(rel, 0, vloc - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, 0.0)
        bags = rows.sum(axis=2)                              # [B_loc,F,D]
        if wire_dtype is not None:
            bags = bags.astype(wire_dtype)                   # compress payload
        if scatter:
            out = jax.lax.psum_scatter(bags, tp, scatter_dimension=0, tiled=True)
        else:
            out = jax.lax.psum(bags, tp)
        return out.astype(table_l.dtype)

    out_batch = (tuple(dp_axes) + (tp,)) if scatter else tuple(dp_axes)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(tp, None), P(dp_axes, None, None)),
        out_specs=P(out_batch, None, None),
        check_vma=False,
    )(table, ids)


def forward_sharded(
    params, batch, cfg: RecsysConfig, mesh, dp_axes, scatter=False, wire_dtype=None
):
    ub = sharded_bags(
        params["user_table"], batch["user_ids"], mesh, dp_axes,
        scatter=scatter, wire_dtype=wire_dtype,
    )
    ib = sharded_bags(
        params["item_table"], batch["item_ids"], mesh, dp_axes,
        scatter=scatter, wire_dtype=wire_dtype,
    )
    b = ub.shape[0]
    dense = batch["user_dense"]
    if scatter:
        # match the batch-scattered bags (GSPMD reshards the small dense feats)
        from jax.sharding import PartitionSpec as P

        dense = jax.lax.with_sharding_constraint(
            dense, jax.sharding.NamedSharding(mesh, P(tuple(dp_axes) + ("model",), None))
        )
    x = jnp.concatenate([ub.reshape(b, -1), dense], axis=-1)
    q = mlp_apply(params["user_mlp"], x, act=jax.nn.relu)
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-6)
    v = mlp_apply(params["item_mlp"], ib.reshape(b, -1), act=jax.nn.relu)
    v = v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)
    return q, v


def loss_sharded(
    params, batch, cfg: RecsysConfig, mesh=None, dp_axes=("data",),
    scatter=False, wire_dtype=None,
):
    q, v = forward_sharded(
        params, batch, cfg, mesh, dp_axes, scatter=scatter, wire_dtype=wire_dtype
    )
    return sampled_softmax_loss(
        q, v, log_q=batch.get("log_q"), temperature=cfg.temperature
    )


def serve_scores(params, batch, cfg: RecsysConfig, mesh=None, dp_axes=("data",)):
    """Online/offline scoring of (user, item) pairs → scores [B]."""
    if mesh is not None:
        q, v = forward_sharded(params, batch, cfg, mesh, dp_axes)
    else:
        q, v = forward(params, batch, cfg)
    return jnp.sum(q * v, axis=-1) / cfg.temperature


def retrieval_scores(params, batch, candidate_vecs, cfg: RecsysConfig, top_k: int = 100):
    """Score queries against a pre-embedded candidate corpus.

    candidate_vecs: f32[n_candidates, D] — one batched GEMM, then top-k."""
    q = user_tower(params, batch["user_ids"], batch["user_dense"], cfg)
    scores = q @ candidate_vecs.T / cfg.temperature
    return jax.lax.top_k(scores, top_k)
