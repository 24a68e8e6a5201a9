"""Paper Fig. 8 + Table 4: scale-up and CPU-efficiency analogues.

Fig 8 varies cores 2→40; the container has one core, so the scale-up axis
becomes the *device count of the sharded PBME step* (subprocess per point,
since the device count is locked at jax init).  CPU efficiency (Table 4)
= 1 / (runtime × devices).

The children run on virtual CPU devices.  On an accelerator host the parent
may already hold the chip, and a child cannot build its mesh from one chip,
so the section refuses to start there."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax

from benchmarks.common import emit

_CHILD = r"""
import json, time
import jax, numpy as np
from repro.distributed import make_mesh
from repro.core.distributed import tc_fixpoint_sharded
from repro.data.graphs import gnp_graph

ndev = {ndev}
mesh = make_mesh(({rows}, {cols}), ("data", "model"))
edges = gnp_graph(400, p=0.02, seed=0)
t0 = time.time()
m, n_pad, iters = tc_fixpoint_sharded(edges, 400, mesh)
jax.block_until_ready(m)
print(json.dumps({{"seconds": time.time() - t0, "iters": iters}}))
"""


def run(points=((1, 1, 1), (2, 2, 1), (4, 2, 2), (8, 4, 2))):
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"fig8 runs on virtual CPU devices in child processes; on a "
            f"{backend!r} host the children would contend for the chip"
        )
    base = None
    for ndev, rows, cols in points:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        env.setdefault("PYTHONPATH", "src")
        res = subprocess.run(
            [sys.executable, "-c", _CHILD.format(ndev=ndev, rows=rows, cols=cols)],
            capture_output=True,
            text=True,
            env=env,
            timeout=600,
        )
        if res.returncode != 0:
            emit(f"fig8_scaleup_dev{ndev}", 0.0, f"FAIL:{res.stderr[-100:]}")
            continue
        data = json.loads(res.stdout.strip().splitlines()[-1])
        if base is None:
            base = data["seconds"]
        ce = 1.0 / (data["seconds"] * ndev)
        emit(
            f"fig8_scaleup_dev{ndev}",
            data["seconds"],
            f"speedup={base / data['seconds']:.2f};table4_cpu_eff={ce:.2e}",
        )


if __name__ == "__main__":
    run()
