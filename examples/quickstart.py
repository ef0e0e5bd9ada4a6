"""Quickstart: the MPIX layer in 60 lines (paper Listings 1-4).

Runs on 8 forced host devices — same code runs on a TPU pod by swapping
the mesh.  Shows: (1) drop-in collective replacement with a selectable
algorithm, (2) a persistent locality-aware neighborhood collective.

    PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import api as mpix
from repro.core.plan import CommGraph, build_plan, run_shardmap
from repro.core.topology import Topology
from repro import compat

mesh = compat.make_mesh((2, 4), ("pod", "data"))
x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)

# --- Listing 1 -> 2: replace the collective, pick the algorithm --------
for algo in ("xla", "ring_rs_ag", "hierarchical", "auto"):
    f = jax.jit(compat.shard_map(
        lambda v: mpix.mpix_allreduce(v, ("pod", "data"), algorithm=algo),
        mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(None),
        check_vma=False))
    with jax.set_mesh(mesh):
        out = np.asarray(f(x))
    assert np.allclose(out, x.reshape(8, 1, 4).sum(0))
    print(f"mpix_allreduce[{algo:>13s}] ok -> {out[0][:4]}")

# --- Listing 3 -> 4: persistent neighborhood alltoallv -----------------
rng = np.random.default_rng(0)
graph = CommGraph.random(8, n_local=4, degree=3, rng=rng, dup_frac=0.8)
topo = Topology(nranks=8, ranks_per_pod=4)
plan = build_plan(graph, topo, aggregate=True)      # init once ...
std = build_plan(graph, topo, aggregate=False)
print(f"neighbor plan: DCN bytes {std.traffic()['dcn']} -> "
      f"{plan.traffic()['dcn']} (locality-aware dedupe), "
      f"DCN msgs {std.traffic()['msgs_dcn']} -> "
      f"{plan.traffic()['msgs_dcn']}")

values = np.stack([rng.normal(size=(4, 2)).astype(np.float32)
                   for _ in range(8)])
g = jax.jit(compat.shard_map(                          # ... execute often
    lambda v: run_shardmap(plan, v, ("pod", "data")),
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    check_vma=False))
with jax.set_mesh(mesh):
    recv = np.asarray(g(values.reshape(8 * 4, 2)))
print("neighbor exchange ok, recv shape", recv.shape)
print("quickstart OK")
