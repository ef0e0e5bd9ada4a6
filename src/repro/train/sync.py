"""Explicit DP gradient synchronization through the MPIX layer.

The ``fsdp`` train mode leaves gradient reduction to the XLA partitioner
(the "system MPI" substrate).  This module is the paper-faithful
*explicit* path: parameters replicated over the data axes, the gradient
all-reduce issued by us with a publicly selectable algorithm —
``xla | ring_rs_ag | recursive_halving_doubling | hierarchical`` — plus
two distributed-optimization extensions:

  * bucketing (``buckets > 1``): the gradient pytree is flattened into
    independent buckets so XLA can overlap bucket k's collective with
    bucket k+1's producer (partitioned-communication pillar, §2.3);
  * DCN compression (``compress_dcn``): hierarchical sync where the
    intra-pod reduce runs in bf16/f32 over ICI and only the inter-pod
    hop is int8-quantized with error feedback (heterogeneous-path
    pillar, §2.4 — spend precision where the wire is slow).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import api as mpix
from repro.optim.compress import compress_int8, decompress_int8


# lanes per row of the flattened gradient.  Every sync path moves the
# gradient as one 2-D [rows, _ROW] f32 array: XLA's TPU compiler takes
# minutes over one 1-D concatenation of every leaf at model size, and
# seconds over the same bytes in rows.
_ROW = 1024


def _flatten(tree, mult: int):
    """Every leaf as f32 rows of ``_ROW`` (zero padded), concatenated:
    [R, _ROW] with R a multiple of ``mult``."""
    leaves, tdef = jax.tree.flatten(tree)
    rows = []
    for l in leaves:
        f = l.reshape(-1).astype(jnp.float32)
        rows.append(jnp.pad(f, (0, (-f.size) % _ROW)).reshape(-1, _ROW))
    flat = jnp.concatenate(rows)
    flat = jnp.pad(flat, ((0, (-flat.shape[0]) % mult), (0, 0)))
    return flat, (tdef, [l.shape for l in leaves],
                  [l.dtype for l in leaves], [l.size for l in leaves])


def _unflatten(flat, meta):
    tdef, shapes, dtypes, sizes = meta
    out, off = [], 0
    for shp, dt, sz in zip(shapes, dtypes, sizes):
        n = -(-sz // _ROW)
        out.append(flat[off: off + n].reshape(-1)[:sz].reshape(shp)
                   .astype(dt))
        off += n
    return jax.tree.unflatten(tdef, out)


def _axes_size(names) -> int:
    n = 1
    for a in names:
        n *= jax.lax.axis_size(a)
    return n


def dp_allreduce(grads, axis_names, *, algorithm="xla", buckets=1,
                 denom=None, transport="shardmap", resilience=None):
    """Sum-allreduce a gradient pytree over ``axis_names`` (call inside
    shard_map), divided by ``denom`` (scalar; e.g. the psum'd live-token
    count so per-shard sum-losses combine into an exact global mean).
    ``transport`` selects the substrate for schedule-backed algorithms
    ("shardmap" | "pallas" | "auto"; ignored by "xla").  ``resilience``
    arms the api recovery ladder for each bucket's collective."""
    names = (axis_names,) if isinstance(axis_names, str) \
        else tuple(axis_names)
    n = _axes_size(names)
    if denom is None:
        denom = n
    buckets = max(1, buckets)
    # each bucket's rows divide by n: the allreduce cuts its chunks
    # along the row axis
    flat, meta = _flatten(grads, buckets * n)
    parts = flat.reshape((buckets, -1, _ROW))
    done = [mpix.mpix_allreduce(parts[i], names, algorithm=algorithm,
                                transport=transport,
                                resilience=resilience)
            for i in range(buckets)]
    return _unflatten(jnp.concatenate(done) / denom, meta)


# dp_algorithm (allreduce registry) -> its (reduce_scatter, allgather)
# halves, so the overlap path accepts the same names as dp_allreduce
_RS_AG = {
    "ring_rs_ag": ("ring", "ring"),
    "recursive_halving_doubling": ("recursive_halving",
                                   "recursive_doubling"),
}


def dp_allreduce_overlap(grads, axis_names, *, algorithm="xla",
                         chunks=2, denom=None, max_norm=None,
                         transport="shardmap", resilience=None):
    """Pipelined DP sync fused with gradient clipping: reduce-scatter
    chunks, per-shard norm/clip compute between the halves, allgather
    chunks — the optimizer-side compute runs on 1/N of the data while
    other chunks are on the wire (compute-comm overlap on the grad
    path), and chunk k's allgather can overlap chunk k+1's
    reduce-scatter.

    Returns ``(grads, gnorm)`` — bitwise the same *averaging* as
    ``dp_allreduce`` and the same clip rule as
    ``optim.clip_by_global_norm`` (scale = min(1, max_norm/(gnorm +
    1e-9))), but the global norm is computed from the scattered shards:
    the shards partition the reduced vector, so the psum of per-shard
    square-norms is the EXACT global square-norm (no cross terms), one
    scalar crossing the wire instead of a second full pass.  With
    ``max_norm=None`` no clip is applied (gnorm still returned)."""
    names = (axis_names,) if isinstance(axis_names, str) \
        else tuple(axis_names)
    if chunks < 1:
        raise ValueError(
            f"dp_allreduce_overlap: chunks must be >= 1, got {chunks}")
    n = _axes_size(names)
    if denom is None:
        denom = n
    # each chunk's rows divide by n so the scatter dim divides
    flat, meta = _flatten(grads, chunks * n)
    parts = flat.reshape((chunks, -1, _ROW))
    rs_alg, ag_alg = _RS_AG.get(algorithm, (algorithm, algorithm))
    shards = []
    gsq = jnp.float32(0)
    for i in range(chunks):
        sh = mpix.mpix_reduce_scatter(parts[i], names,
                                      algorithm=rs_alg,
                                      transport=transport,
                                      resilience=resilience) / denom
        gsq = gsq + jnp.sum(jnp.square(sh))
        shards.append(sh)
    gnorm = jnp.sqrt(jax.lax.psum(gsq, names))
    if max_norm is not None:
        scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-9))
        shards = [sh * scale for sh in shards]
    outs = [mpix.mpix_allgather(sh, names, algorithm=ag_alg,
                                transport=transport,
                                resilience=resilience)
            for sh in shards]
    return _unflatten(jnp.concatenate(outs), meta), gnorm


def dp_allreduce_compressed(grads, residual, *, intra_algorithm="xla",
                            denom=None, resilience=None):
    """Hierarchical DP sync with int8 + error feedback on the DCN hop.

    Call inside shard_map over ("pod", "data").  Steps:
      1. intra-pod sum over "data" (full precision, ICI),
      2. int8-quantize (grad + EF residual), exchange over "pod"
         (ppermute ring), dequantize-accumulate,
      3. new residual = what quantization lost this step,
      4. divide by ``denom`` (global live-token count).
    Returns (synced grads, new residual).
    """
    Q = jax.lax.axis_size("pod")
    D = jax.lax.axis_size("data")
    if denom is None:
        denom = Q * D
    flat, meta = _flatten(grads, D)
    flat = mpix.mpix_allreduce(flat, "data", algorithm=intra_algorithm,
                               resilience=resilience)
    if residual is None:
        res_flat = jnp.zeros_like(flat)
    else:
        res_flat, _ = _flatten(residual, D)
    x = flat + res_flat
    q, s = compress_int8(x)
    sent = decompress_int8(q, s, x.shape, jnp.float32)
    new_res = x - sent
    # ring exchange of the quantized payload across pods
    acc = sent
    perm = [(i, (i + 1) % Q) for i in range(Q)]
    qc, sc = q, s
    for _ in range(Q - 1):
        qc = jax.lax.ppermute(qc, "pod", perm)
        sc = jax.lax.ppermute(sc, "pod", perm)
        acc = acc + decompress_int8(qc, sc, x.shape, jnp.float32)
    out = acc / denom
    return _unflatten(out, meta), _unflatten(new_res, meta)
