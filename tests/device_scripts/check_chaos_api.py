"""Subprocess check: the TRACE-TIME recovery ladder on the real mpix_*
shard_map execution paths, with seeded chaos injected through
``api.set_chaos`` (every transport the api constructs is wrapped).

Covered here (needs 8 host devices, own process):
  * transient injected failure + ``resilience="off"`` -> retried on the
    same rung, output bitwise correct, DegradationReport recorded;
  * the same failure WITHOUT resilience -> typed ``TransportError``
    surfaces at trace time (never a silent wrong answer);
  * persistent failure on every schedule-backed substrate -> the ladder
    degrades through the other transport and the refit algorithms to
    the xla-native terminal rung, output still correct;
  * hang campaign + per-attempt deadline -> timeout attempts recorded,
    recovery still bitwise;
  * ``tuner.measure_schedule(deadline_s=)`` -> typed
    ``MeasurementTimeout`` instead of a wedged measurement.

Run via tests/test_chaos.py."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import api, tuner
from repro.core.algorithms import REGISTRY
from repro.core.chaos import FaultPlan
from repro.core.topology import flat_topology
from repro.core.transport import TransportError

N = 4
mesh = compat.make_mesh((N,), ("data",))
rng = np.random.default_rng(0)
x = rng.integers(-8, 8, (N * 4, 3)).astype(np.float32)


def allgather_under(resilience):
    """Fresh trace each call (chaos fires at trace time; jit caching
    would replay the faulted trace's result otherwise)."""
    f = jax.jit(compat.shard_map(
        lambda v: api.mpix_allgather(v, "data", algorithm="ring",
                                     transport="shardmap",
                                     resilience=resilience),
        mesh=mesh, in_specs=P("data"), out_specs=P(None),
        check_vma=False))
    with jax.set_mesh(mesh):
        return np.asarray(f(x))


want = allgather_under(None)           # fault-free oracle
assert want.tobytes() == x.tobytes()   # allgather of the shards == x

# 1. transient fail + armed ladder -> recovered bitwise, report recorded
api.take_degradations()
api.set_chaos(FaultPlan(11, "fail", times=1))
got = allgather_under("off")
api.set_chaos(None)
assert got.tobytes() == want.tobytes(), "transient recovery not bitwise"
reps = api.take_degradations()
assert len(reps) == 1 and reps[0].degraded
assert any(a.outcome == "fault" for a in reps[0].attempts)
assert reps[0].attempts[-1].outcome == "ok"
print("transient fail recovered:", reps[0].summary())

# 2. same fault, no resilience -> typed TransportError at trace time
api.set_chaos(FaultPlan(11, "fail", times=1))
try:
    allgather_under(None)
    raise SystemExit("expected TransportError without resilience")
except TransportError as e:
    print("unarmed fault is typed:", type(e).__name__)
finally:
    api.set_chaos(None)

# 3. persistent fail everywhere -> ladder ends on the xla-native rung
api.take_degradations()
api.set_chaos(FaultPlan(11, "fail", times=None))
got = allgather_under({"verify": "off", "max_retries": 1,
                       "backoff_s": 1e-4})
api.set_chaos(None)
assert got.tobytes() == want.tobytes(), "xla-rung recovery not bitwise"
reps = api.take_degradations()
assert len(reps) == 1 and reps[0].refit_algorithm == "xla"
assert reps[0].recovered_with == "xla"
print("persistent fail degraded to xla:", reps[0].summary())

# 4. hang campaign + deadline -> timeout attempts recorded, recovered
api.take_degradations()
api.set_chaos(FaultPlan(5, "hang", times=1, delay_s=30.0))
got = allgather_under({"verify": "off", "deadline_s": 5.0,
                       "backoff_s": 1e-4})
api.set_chaos(None)
assert got.tobytes() == want.tobytes(), "hang recovery not bitwise"
reps = api.take_degradations()
assert len(reps) == 1
assert any(a.outcome == "timeout" for a in reps[0].attempts)
print("hang hit the deadline then recovered:", reps[0].summary())

# 5. measure_schedule deadline -> typed MeasurementTimeout
topo = flat_topology(N)
sched = REGISTRY["allgather"]["ring"](topo)
t = tuner.measure_schedule(sched, topo, slot_elems=64, repeats=1)
assert t > 0
try:
    tuner.measure_schedule(sched, topo, slot_elems=64, repeats=1,
                           deadline_s=1e-6)
    raise SystemExit("expected MeasurementTimeout")
except tuner.MeasurementTimeout as e:
    print("measurement deadline is typed:", e)

# 6. allreduce path too: transient fail under the armed ladder
def allreduce_under(resilience):
    f = jax.jit(compat.shard_map(
        lambda v: api.mpix_allreduce(v, "data", algorithm="ring_rs_ag",
                                     transport="shardmap",
                                     resilience=resilience),
        mesh=mesh, in_specs=P("data"), out_specs=P(None),
        check_vma=False))
    with jax.set_mesh(mesh):
        return np.asarray(f(x))


want_ar = allreduce_under(None)
api.take_degradations()
api.set_chaos(FaultPlan(2, "fail", times=1))
got_ar = allreduce_under("off")
api.set_chaos(None)
assert got_ar.tobytes() == want_ar.tobytes()
assert len(api.take_degradations()) == 1
print("allreduce transient fail recovered bitwise")

print("ALL OK")
