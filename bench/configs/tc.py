"""Transitive closure over a Gn-p graph: the program, its data and its plain reference.

Data is ``gnp_graph`` of ``repro.data.graphs`` copied here: a directed
Gn-p graph without self loops, as the RecStep paper's G5K (§6, Fig. 10).
The reference is a dense boolean closure in plain ``jnp`` by repeated
squaring, R ← R ∪ R·R from R = arc, which holds every path of length 1 to
2^k after k steps: no code of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PROGRAM = """
tc(x,y) :- arc(x,y).
tc(x,y) :- tc(x,z), arc(z,y).
"""
EDB = ("arc",)
IDB = "tc"


def base_facts(config: dict) -> dict[str, np.ndarray]:
    """Gn-p(n, p) drawn with generator seed ``data_seed``."""
    d = config["dataset"]
    n, p = int(d["n"]), float(d["p"])
    rng = np.random.default_rng(d["data_seed"])
    m = rng.binomial(n * n, p)
    flat = rng.choice(n * n, size=m, replace=False)
    src, dst = flat // n, flat % n
    keep = src != dst
    edges = np.stack([src[keep], dst[keep]], axis=1).astype(np.int32)
    return {"arc": np.unique(edges, axis=0)}


@jax.jit
def _square(r):
    # {0,1} bf16 products accumulate exactly in f32
    rr = jnp.dot(r, r, preferred_element_type=jnp.float32) > 0
    nxt = jnp.maximum(r, rr.astype(jnp.bfloat16))
    return nxt, jnp.any(nxt != r)


def reference(edb: dict[str, np.ndarray], n: int) -> jax.Array:
    """bool[n, n] with ``[x, y]`` true iff a path of length ≥ 1 leads x → y."""
    m = np.zeros((n, n), np.float32)
    arc = edb["arc"]
    m[arc[:, 0], arc[:, 1]] = 1.0
    r = jnp.asarray(m, jnp.bfloat16)
    changed = True
    while changed:
        r, changed = _square(r)
        changed = bool(changed)
    return r > 0
