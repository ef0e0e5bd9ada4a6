"""Subprocess check: the 3-level (2 pods x 4x2 torus) conformance sweep
on 16 forced host devices.

Two halves:
  1. executor equivalence — SimTransport and ShardMapTransport are
     bit-exact on every registered schedule (dense families incl. the
     staged builders + partitioned) and both neighborhood plan modes,
     for float32 and bfloat16;
  2. staged-vs-flat — on the device path, every staged dense builder
     produces bit-exact results vs its flat reference on integer-valued
     payloads (exact sums for any reduction order).

This is the ShardMap half of tests/test_hierarchical.py; the
SimTransport half (oracles, modeled time, traffic bounds) runs there
without devices.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ.setdefault("REPRO_VALIDATE_SCHEDULES", "1")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.algorithms import REGISTRY
from repro.core.plan import CommGraph, build_plan
from repro.core.schedule import NotApplicable
from repro.core.topology import torus_topology
from repro.core.transport import ShardMapTransport, SimTransport

TOPO = torus_topology(2, 4, 2)          # (dcn-2, torus_y-4, torus_x-2)
N, FEAT = TOPO.nranks, 2
AXES = ("pod", "y", "x")
MESH = compat.make_mesh((2, 4, 2), AXES)
DTYPES = {"float32": np.float32, "bfloat16": jnp.bfloat16}
FLAT = {"allgather": "ring", "allreduce": "ring_rs_ag",
        "reduce_scatter": "ring", "alltoall": "pairwise"}

rng = np.random.default_rng(0)
failures = []
checked = 0


def shardmap_run(sched, x):
    tr = ShardMapTransport(N, AXES)
    f = jax.jit(compat.shard_map(
        lambda b: tr.run(sched, b), mesh=MESH,
        in_specs=P(AXES), out_specs=P(AXES), check_vma=False))
    with jax.set_mesh(MESH):
        got = np.asarray(f(x.reshape(N * sched.num_slots, FEAT)))
    return got.reshape(N, sched.num_slots, FEAT)


# -- half 1: executor equivalence on every registered schedule -------------
schedules = []
for coll, algos in REGISTRY.items():
    for name, builder in algos.items():
        try:
            schedules.append((f"{coll}.{name}", builder(TOPO)))
        except NotApplicable:          # e.g. pow2-only on this topo
            continue
graph = CommGraph.random(N, n_local=6, degree=4, rng=rng, dup_frac=0.8)
for aggregate in (False, True):
    plan = build_plan(graph, TOPO, aggregate=aggregate)
    schedules.append((plan.name, plan.schedule))

for dt_name, dtype in DTYPES.items():
    for label, sched in schedules:
        x = rng.normal(size=(N, sched.num_slots, FEAT)).astype(dtype)
        want = SimTransport(N).run(sched, x)
        got = shardmap_run(sched, x)
        checked += 1
        if not np.array_equal(np.asarray(want), got):
            failures.append(("sim-vs-shardmap", label, dt_name))
            print(f"sim-vs-shardmap {dt_name:8s} {label:40s} FAIL")
print(f"sim-vs-shardmap: {len(schedules)} schedules x {len(DTYPES)} dtypes")

# -- half 2: staged == flat reference on the device path -------------------
ints = rng.integers(-8, 8, (N, N, FEAT)).astype(np.float32)
for coll, flat_name in FLAT.items():
    if coll == "allgather":
        buf = np.zeros((N, N, FEAT), np.float32)
        for r in range(N):
            buf[r, r] = ints[r, 0]
    else:
        buf = ints
    outs = {}
    for name in ("staged", flat_name):
        sched = REGISTRY[coll][name](TOPO)
        x = buf
        if sched.num_slots > N:        # separate recv region (pairwise)
            x = np.concatenate(
                [buf, np.zeros((N, sched.num_slots - N, FEAT),
                               np.float32)], axis=1)
        outs[name] = shardmap_run(sched, x)[:, : sched.result_slots]
    checked += 1
    staged_out, flat_out = outs["staged"], outs[flat_name]
    if coll == "reduce_scatter":
        ok = all(np.array_equal(staged_out[r, r], flat_out[r, r])
                 for r in range(N))
    else:
        ok = np.array_equal(staged_out, flat_out)
    if not ok:
        failures.append(("staged-vs-flat", coll, "float32"))
        print(f"staged-vs-flat {coll:16s} FAIL")
print(f"staged-vs-flat: {len(FLAT)} collectives on {N} devices")

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print(f"checked {checked} cases on the 3-level 2x(4x2) torus")
print("ALL OK")
