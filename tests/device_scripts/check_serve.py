"""Subprocess check (8 host devices): the serving path on real shards.

  1. ``jit_decode_step`` shardings: the launcher-path decode step's
     cache output actually lands with the cache specs' NamedShardings,
     and at least one KV leaf is genuinely partitioned (not
     replicated) on the 8-device mesh — the bare-``jax.jit`` bug this
     PR fixed silently replicated everything;
  2. KV-transfer plans are bit-exact vs the gather oracle on the
     *shardmap* and *pallas* transports (the sim/reference sweep runs
     in tests/test_serve_engine.py);
  3. a continuous-batching trace drains with ``transport="shardmap"``
     — the engine's per-batch ragged plans executed by real ppermutes.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax

from repro import compat, configs
from repro.core import kvtransfer
from repro.core.topology import Topology
from repro.models import model as M
from repro.serve.engine import ContinuousBatchingEngine, EngineConfig
from repro.serve.step import (ServeOptions, jit_decode_step, place,
                              token_spec)
from repro.serve.traffic import poisson_workload, run_workload

failures = []

# ---- 1. decode-step cache shardings on the 8-device mesh -----------------
cfg = configs.get_smoke("smollm-360m")
mesh = compat.make_mesh((4, 2), ("data", "model"))
with jax.set_mesh(mesh):
    params = M.init_params(jax.random.key(0), cfg)
    cache = M.init_cache(cfg, 4, 8)
    decode, (pspec, cspec) = jit_decode_step(
        cfg, mesh, ServeOptions(), params, cache)
    params = place(mesh, params, pspec)
    cache = place(mesh, cache, cspec)
    tok = place(mesh, jax.numpy.zeros((4, 1), jax.numpy.int32),
                token_spec(mesh, ServeOptions()))
    nxt, cache2 = decode(params, cache, tok)
    jax.block_until_ready(nxt)

leaves = jax.tree.leaves(cache2)
specs = jax.tree.leaves(cspec, is_leaf=lambda x: hasattr(x, "_normalized_spec")
                        or type(x).__name__ == "PartitionSpec")
got_sharded = 0
for leaf, spec in zip(leaves, specs):
    sh = leaf.sharding
    want_spec = tuple(spec)
    got_spec = tuple(sh.spec) if hasattr(sh, "spec") else None
    # normalize trailing Nones (jax may trim/extend them)
    strip = lambda t: tuple(x for x in t if x is not None)
    if strip(want_spec) != strip(got_spec or ()):
        failures.append(("cache-sharding", want_spec, got_spec))
    if strip(want_spec):
        got_sharded += 1
        if sh.is_fully_replicated:
            failures.append(("cache-replicated", want_spec))
print(f"decode cache: {len(leaves)} leaves, {got_sharded} partitioned "
      f"({'ok' if not failures else 'FAIL'})")
if got_sharded == 0:
    failures.append(("no-sharded-cache-leaf",))

# ---- 2. transfer plans bit-exact on shardmap + pallas --------------------
rng = np.random.default_rng(0)
topo = Topology(8, 4)
B = 8
pool = rng.normal(size=(8, B, 2, 4)).astype(np.float32)
moves = [kvtransfer.BlockMove(s, (s + j) % B, 4 + (s + j) % 4,
                              (2 * s + j) % B)
         for s in range(4) for j in range(3)]
# dedupe dst rows (the generator above may collide)
seen, clean = set(), []
for m in moves:
    if (m.dst, m.dst_row) not in seen:
        seen.add((m.dst, m.dst_row))
        clean.append(m)
for aggregate in (False, True):
    tp = kvtransfer.build_transfer_plan(
        clean, topo, blocks_per_rank=B, aggregate=aggregate,
        block_bytes=32)
    for transport in ("shardmap", "pallas"):
        res = kvtransfer.run_transfer(tp, pool, transport=transport)
        ok = kvtransfer.verify_bitwise(tp, pool, res)
        print(f"transfer aggregate={aggregate!s:5s} {transport:8s} "
              f"rounds={tp.schedule.num_rounds:3d} "
              f"{'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(("transfer", aggregate, transport))

# ---- 3. continuous batching on the shardmap transport --------------------
eng = ContinuousBatchingEngine(EngineConfig(transport="shardmap"))
m = run_workload(eng, poisson_workload(0, arrival_rate=8.0, tenants=2,
                                       n_requests=10, max_prompt=32))
ok = (m["completed"] == m["submitted"] == 10
      and m["kv_transfer"]["plans"] >= 1
      and all(p.in_use == 0 for p in eng.pools.values()))
print(f"continuous shardmap: {m['completed']}/{m['submitted']} requests, "
      f"{m['kv_transfer']['plans']} plans {'ok' if ok else 'FAIL'}")
if not ok:
    failures.append(("continuous-shardmap", m))

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print("ALL OK")
