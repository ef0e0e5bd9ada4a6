"""Subprocess check: the shard_map executor's two round lowerings.

Usage: ``check_slice_rounds.py N`` on N forced host devices.  Runs every
registered allreduce, reduce_scatter, allgather and alltoall schedule of
a flat N-rank topology through ``ShardMapTransport`` in float32 and
bfloat16 and compares each result bitwise with
``SimTransport.run_reference``.  With N = 4 it also compiles the
``recursive_halving_doubling`` ``mpix_allreduce`` and counts the ops that
the table lowering would put in it.  The last stdout line is JSON:

  {"schedules": {label: {"bitwise", "rounds", "slice_rounds",
                         "table_rounds"}},
   "hlo": {"gather", "scatter", "concatenate"} (N = 4 only)}

Run via tests/test_executor.py (needs its own process: jax device count
is locked at first init).
"""
import json
import os
import re
import sys

N = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N}"
os.environ.setdefault("REPRO_VALIDATE_SCHEDULES", "1")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import api, executor
from repro.core.algorithms import REGISTRY
from repro.core.topology import flat_topology
from repro.core.transport import ShardMapTransport, SimTransport

COLLECTIVES = ("allreduce", "reduce_scatter", "allgather", "alltoall")
DTYPES = (np.float32, jnp.bfloat16)

mesh = compat.make_mesh((N,), ("r",))
topo = flat_topology(N)
tr = ShardMapTransport(N, ("r",), topo=topo)
rng = np.random.default_rng(0)
result = {"schedules": {}}
for coll in COLLECTIVES:
    for name, builder in REGISTRY[coll].items():
        sched = builder(topo)
        f = jax.jit(compat.shard_map(
            lambda b: tr.run(sched, b), mesh=mesh,
            in_specs=P("r"), out_specs=P("r"), check_vma=False))
        same = True
        for dtype in DTYPES:
            x = rng.normal(size=(N, sched.num_slots, 3, 2)).astype(dtype)
            want = SimTransport(N).run_reference(sched, x)
            with jax.set_mesh(mesh):
                got = np.asarray(f(x.reshape((-1, 3, 2))))
            same &= np.array_equal(want.reshape(got.shape), got)
        st = executor.get_executor(sched, topo=topo).stats()
        result["schedules"][f"{coll}.{name}"] = {
            "bitwise": bool(same), "rounds": st["rounds_after"],
            "slice_rounds": st["slice_rounds"],
            "table_rounds": st["table_rounds"]}

if N == 4:
    g = jax.jit(compat.shard_map(
        lambda v: api.mpix_allreduce(
            v, "r", algorithm="recursive_halving_doubling"),
        mesh=mesh, in_specs=P("r"), out_specs=P("r"), check_vma=False))
    x = rng.normal(size=(N * 64, 128)).astype(np.float32)
    with jax.set_mesh(mesh):
        hlo = g.lower(x).compile().as_text()
    result["hlo"] = {op: len(re.findall(rf"\s{op}\(", hlo))
                     for op in ("gather", "scatter", "concatenate")}

print(json.dumps(result))
