"""Distributed tests: run in a subprocess with 8 virtual devices so the main
pytest process keeps the default single-device view."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import make_mesh

out = {}
mesh = make_mesh((4, 2), ("data", "model"))

# --- sharded PBME TC equals the oracle ---
from repro.core.distributed import tc_fixpoint_sharded
from repro.core.bitmatrix import bitmatrix_to_edges
rng = np.random.default_rng(0)
n = 60
edges = np.unique(rng.integers(0, n, size=(150, 2)), axis=0).astype(np.int32)
a = np.zeros((n, n), bool); a[edges[:, 0], edges[:, 1]] = True
r = a.copy()
while True:
    r2 = r | (r @ a)
    if (r2 == r).all(): break
    r = r2
m, n_pad, iters = tc_fixpoint_sharded(edges, n, mesh)
got = {(u, v) for u, v in bitmatrix_to_edges(jax.device_get(m), n_pad) if u < n and v < n}
out["pbme_sharded_ok"] = got == set(zip(*np.nonzero(r)))

# --- compressed DP step tracks uncompressed ---
from repro.models.transformer import TransformerConfig, init_params, lm_loss
from repro.train import init_train_state, make_compressed_dp_step, make_train_step
from repro.optim.grad_compress import compress_state_init
from repro.data.tokens import TokenStream
cfg = TransformerConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                        d_ff=64, vocab=64, dtype="float32", param_dtype="float32")
params = init_params(jax.random.PRNGKey(0), cfg)
s1, s2 = init_train_state(params), init_train_state(params)
err = compress_state_init(params)
stream = TokenStream(cfg.vocab, batch=8, seq_len=16, seed=0)
stepc = make_compressed_dp_step(lm_loss, cfg, mesh, "data")
stepu = make_train_step(lm_loss, cfg, donate=False)
for i in range(3):
    b = {k: jnp.asarray(v) for k, v in stream.batch(i).items()}
    s1, err, m1 = stepc(s1, err, b)
    s2, m2 = stepu(s2, b)
diff = max(float(jnp.abs(a - b).max())
           for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)))
out["compressed_dp_diff"] = diff

# --- sharded embedding bags equal the dense path ---
from repro.models.recsys import two_tower as tt
from repro.relational.embedding import embedding_bag
cfg_r = tt.RecsysConfig(user_vocab=64, item_vocab=32, embed_dim=8,
                        tower_dims=(16, 8), user_fields=2, item_fields=2,
                        field_hots=3, n_dense_feat=4)
p = tt.init_params(jax.random.PRNGKey(1), cfg_r)
ids = jnp.asarray(rng.integers(-1, 64, size=(8, 2, 3)).astype(np.int32))
dense = jnp.stack([embedding_bag(p["user_table"], ids[:, f]) for f in range(2)], axis=1)
shard = tt.sharded_bags(p["user_table"], ids, mesh, ("data",), "model")
out["sharded_bag_err"] = float(jnp.abs(dense - shard).max())

# --- explicit shard_map EP MoE equals the dense dispatch path ---
from repro.distributed.context import mesh_context
cfg_m = TransformerConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                          d_ff=64, vocab=64, moe=True, n_experts=8, top_k=2,
                          n_shared_experts=1, d_ff_expert=16,
                          dtype="float32", param_dtype="float32")
pm = init_params(jax.random.PRNGKey(7), cfg_m)
from repro.models.transformer import forward
tm = jax.random.randint(jax.random.PRNGKey(8), (4, 8), 0, cfg_m.vocab)
dense_out, _ = forward(pm, tm, cfg_m)
mesh2 = make_mesh((2, 4), ("data", "model"))
with mesh_context(mesh2, ("data",)):
    ep_out, _ = jax.jit(lambda p, t: forward(p, t, cfg_m))(pm, tm)
out["ep_moe_err"] = float(jnp.abs(dense_out - ep_out).max())

# --- sharded LM train step runs end to end on the mesh ---
from repro.distributed.sharding import param_sharding, batch_sharding
state_sds = jax.eval_shape(lambda: init_train_state(init_params(jax.random.PRNGKey(0), cfg)))
state_sh = param_sharding(state_sds, mesh)
b = {k: jnp.asarray(v) for k, v in stream.batch(0).items()}
b_sh = batch_sharding(b, mesh)
state = jax.device_put(init_train_state(params), state_sh)
b = jax.device_put(b, b_sh)
step = jax.jit(make_train_step(lm_loss, cfg, donate=False, jit=False),
               in_shardings=(state_sh, b_sh))
state, metrics = step(state, b)
out["sharded_train_loss_finite"] = bool(jnp.isfinite(metrics["loss"]))

print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def subproc_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_pbme_sharded_matches_oracle(subproc_results):
    assert subproc_results["pbme_sharded_ok"]


def test_compressed_dp_tracks_uncompressed(subproc_results):
    assert subproc_results["compressed_dp_diff"] < 1e-3


def test_sharded_embedding_bags_exact(subproc_results):
    assert subproc_results["sharded_bag_err"] < 1e-5


def test_sharded_train_step_runs(subproc_results):
    assert subproc_results["sharded_train_loss_finite"]


def test_ep_moe_matches_dense_dispatch(subproc_results):
    assert subproc_results["ep_moe_err"] < 1e-5


def test_collective_bytes_parser():
    from repro.distributed.hlo import collective_bytes

    hlo = """
      %ag = f32[128,256]{1,0} all-gather(f32[8,256] %x), dimensions={0}
      %ar = bf16[1024]{0} all-reduce(bf16[1024] %y), to_apply=%add
      %p = (f32[64]{0}, f32[64]{0}) collective-permute(f32[64] %z, f32[64] %w)
    """
    got = collective_bytes(hlo)
    assert got["all-gather"] == 128 * 256 * 4
    assert got["all-reduce"] == 1024 * 2
    assert got["collective-permute"] == 64 * 4 * 2
    assert got["total"] == sum(
        v for k, v in got.items() if k != "total"
    )


def test_param_sharding_rules():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.distributed import make_mesh
    from repro.distributed.sharding import param_sharding

    mesh = make_mesh((1, 1), ("data", "model"))
    params = {
        "embed": jnp.zeros((16, 8)),
        "layers": {"attn": {"wq": jnp.zeros((2, 8, 8)), "wo": jnp.zeros((2, 8, 8))},
                    "ffn": {"w_gate": jnp.zeros((2, 4, 8, 8))}},
    }
    sh = param_sharding(params, mesh)
    assert sh["embed"].spec == P("model", None)
    assert sh["layers"]["attn"]["wq"].spec == P(None, None, "model")
    assert sh["layers"]["attn"]["wo"].spec == P(None, "model", None)
    assert sh["layers"]["ffn"]["w_gate"].spec == P(None, "model", None, None)
