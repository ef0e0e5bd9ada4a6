"""Plain reference of a Llama-style decoder (SmolLM), its loss, AdamW and
its weights, written from the published description.

Straightforward ``jax.numpy`` in float32 with ``precision="highest"``
matmuls: no kernels, no cache, no sharding, and nothing imported from
the program.  ``fp8`` gives the control: every matmul operand rounded to
float8 with a per-tensor scale (e4m3 forward, e5m2 gradients), the
precision one step below the bfloat16 the configuration states.

Weights live in this module's own layout:
``{"embed", "final_norm", "layers": {name: [L, ...]}}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits: ``jax.random.key`` keeps
    only the low 32 bits, so the high word is folded in."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def dims(c: dict) -> dict:
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return {"d": c["hidden_size"], "f": c["intermediate_size"],
            "v": c["vocab_size"], "L": c["num_hidden_layers"],
            "H": c["num_attention_heads"], "K": c["num_key_value_heads"],
            "D": hd}


def param_shapes(c: dict) -> dict:
    n = dims(c)
    d, f, L, H, K, D = n["d"], n["f"], n["L"], n["H"], n["K"], n["D"]
    return {"embed": (n["v"], d), "final_norm": (d,),
            "layers": {"attn_norm": (L, d), "wq": (L, d, H * D),
                       "wk": (L, d, K * D), "wv": (L, d, K * D),
                       "wo": (L, H * D, d), "mlp_norm": (L, d),
                       "w_gate": (L, d, f), "w_up": (L, d, f),
                       "w_down": (L, f, d)}}


def init_params(key, c: dict, dtype=jnp.bfloat16) -> dict:
    """normal(0, initializer_range) matrices and unit norm scales, in
    ``dtype``.  Jit it: one device call makes every leaf."""
    shapes = param_shapes(c)
    flat, tdef = jax.tree.flatten(shapes,
                                  is_leaf=lambda s: isinstance(s, tuple))
    names = jax.tree.leaves(
        jax.tree.map_with_path(lambda p, _: jax.tree_util.keystr(p), shapes,
                               is_leaf=lambda s: isinstance(s, tuple)))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, shp, name in zip(keys, flat, names):
        if "norm" in name:
            out.append(jnp.ones(shp, dtype))
        else:
            out.append((c["initializer_range"]
                        * jax.random.normal(k, shp, F32)).astype(dtype))
    return jax.tree.unflatten(tdef, out)


def _scaled_round(x, dtype, top):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _round_fp8(x):
    """fp8 training's rounding with per-tensor scales: float8_e4m3fn on
    the way forward, float8_e5m2 for the gradient on the way back."""
    return _scaled_round(x, F8, F8_MAX)


def _round_fp8_fwd(x):
    return _round_fp8(x), None


def _round_fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, 57344.0),)


_round_fp8.defvjp(_round_fp8_fwd, _round_fp8_bwd)


def _operand(x, fp8: bool):
    return _round_fp8(x) if fp8 else x.astype(F32)


def _mm(a, b, fp8):
    return jnp.matmul(_operand(a, fp8), _operand(b, fp8), precision=HIGHEST)


def _einsum(spec, a, b, fp8):
    return jnp.einsum(spec, _operand(a, fp8), _operand(b, fp8),
                      precision=HIGHEST)


def _rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, positions, theta):
    """Rotary embedding, rotate-half form: x [S, H, D], positions [S]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions[:, None].astype(F32) * jnp.asarray(inv, F32)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(c, fp8):
    n = dims(c)
    H, K, D, eps = n["H"], n["K"], n["D"], c["rms_norm_eps"]

    def layer(x, lp):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _rmsnorm(x, lp["attn_norm"], eps)
        q = _rope(_mm(h, lp["wq"], fp8).reshape(S, H, D), pos,
                  c["rope_theta"])
        k = _rope(_mm(h, lp["wk"], fp8).reshape(S, K, D), pos,
                  c["rope_theta"])
        v = _mm(h, lp["wv"], fp8).reshape(S, K, D)
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        s = _einsum("qhd,khd->hqk", q, k, fp8) * (D ** -0.5)
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = _einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, fp8)
        x = x + _mm(o.reshape(S, H * D), lp["wo"], fp8)
        h = _rmsnorm(x, lp["mlp_norm"], eps)
        g = jax.nn.silu(_mm(h, lp["w_gate"], fp8)) * _mm(h, lp["w_up"], fp8)
        return x + _mm(g, lp["w_down"], fp8), None

    return layer


def logits(params, c: dict, tokens, *, fp8: bool = False, remat=False):
    """One sequence: tokens [S] -> float32 logits [S, V]."""
    layer = _layer(c, fp8)
    if remat:
        layer = jax.checkpoint(layer)
    x = params["embed"].astype(F32)[tokens]
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], c["rms_norm_eps"])
    return _mm(x, params["embed"].T, fp8)


def seq_loss_sum(params, c, tokens, labels, *, fp8=False):
    """Sum of next-token cross-entropy over the labels >= 0 of one
    sequence, and their count."""
    lg = logits(params, c, tokens, fp8=fp8, remat=True)
    mask = labels >= 0
    gold = jnp.take_along_axis(lg, jnp.where(mask, labels, 0)[:, None],
                               -1)[:, 0]
    nll = (jax.nn.logsumexp(lg, -1) - gold) * mask
    return nll.sum(), mask.sum()


def loss_and_grad(params, c, batch, *, fp8=False, rows=None):
    """Mean loss over the live labels of ``batch`` and its gradient in
    float32, one sequence at a time.  ``rows`` limits both to a subset
    of the batch (the faults that leave part of the batch out)."""
    tokens, labels = batch["tokens"], batch["labels"]
    if rows is not None:
        tokens, labels = tokens[rows], labels[rows]
    p32 = jax.tree.map(lambda p: p.astype(F32), params)
    grad_fn = jax.value_and_grad(
        lambda p, t, l: seq_loss_sum(p, c, t, l, fp8=fp8), has_aux=True)

    def body(acc, row):
        (s, n), g = grad_fn(p32, *row)
        return jax.tree.map(jnp.add, acc, (s, n, g)), None

    zero = (jnp.zeros((), F32), jnp.zeros((), jnp.int32),
            jax.tree.map(jnp.zeros_like, p32))
    (s, n, g), _ = jax.lax.scan(body, zero, (tokens, labels))
    denom = jnp.maximum(n, 1).astype(F32)
    return s / denom, jax.tree.map(lambda x: x / denom, g)


def lr_at(step, o: dict):
    """Linear warm-up to ``peak_lr`` then cosine decay to a tenth of it;
    ``step`` counts updates already applied."""
    step = jnp.asarray(step, F32)
    w, t, peak = o["warmup_steps"], o["total_steps"], o["peak_lr"]
    warm = peak * step / max(w, 1)
    frac = jnp.clip((step - w) / max(t - w, 1), 0.0, 1.0)
    cos = peak * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < w, warm, cos)


def adamw(params, grads, mu, nu, count, o: dict):
    """Global-norm clipping, then one AdamW update in float32; the new
    parameters are stored in the parameters' own dtype."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["max_grad_norm"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(count, o)
    t = count + 1
    c1 = 1.0 - o["b1"] ** t
    c2 = 1.0 - o["b2"] ** t
    mu = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g,
                      nu, grads)

    def upd(p, m, v):
        p32 = p.astype(F32)
        step = (m / c1) / (jnp.sqrt(v / c2) + o["eps"]) \
            + o["weight_decay"] * p32
        return (p32 - lr * step).astype(p.dtype)

    return jax.tree.map(upd, params, mu, nu), mu, nu, grads


def leaf_norms(tree) -> dict:
    """{"embed": |x|, "layers.wq": |x|, ...}: the float32 2-norm of each
    leaf of a tree in this module's layout."""
    out = {}
    for path, x in jax.tree.flatten_with_path(tree)[0]:
        name = ".".join(str(getattr(k, "key", k)) for k in path)
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
    return out


def train_step(p, mu, nu, count, b, c: dict, o: dict, *, fp8=False,
               rows=None, denom_rows=None):
    """One reference training step on batch ``b``.  Returns the new
    (p, mu, nu), the loss before the update and the norms of the clipped
    gradient by leaf.

    ``rows`` keeps part of the batch (a fault: the rest left out);
    ``denom_rows``, where given, is the batch the loss is divided by
    (a fault: a shard's gradient taken for the whole batch's)."""
    loss, g = loss_and_grad(p, c, b, fp8=fp8, rows=rows)
    if denom_rows is not None:
        live = jnp.maximum((b["labels"][denom_rows] >= 0).sum(), 1)
        mine = jnp.maximum((b["labels"][rows] >= 0).sum(), 1)
        g = jax.tree.map(lambda x: x * (mine / live), g)
    p, mu, nu, g = adamw(p, g, mu, nu, count, o)
    return p, mu, nu, loss, leaf_norms(g)


def train_run(key, batches, c: dict, o: dict, **fault):
    """The reference run of ``len(batches)`` training steps from the
    weights of ``key``, one compiled step at a time so that the state is
    updated in place.  Returns (losses, the norms of the first clipped
    gradient by leaf, the norms of the parameters' change by leaf)."""
    p = jax.jit(lambda k: init_params(k, c))(key)
    mu = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), p)
    nu = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), p)
    step = jax.jit(lambda p, mu, nu, n, b: train_step(
        p, mu, nu, n, b, c, o, **fault), donate_argnums=(0, 1, 2))
    losses, first = [], None
    for i, b in enumerate(batches):
        p, mu, nu, loss, gn = step(p, mu, nu, jnp.int32(i), b)
        losses.append(float(loss))
        first = jax.device_get(gn) if first is None else first
    del mu, nu
    change = jax.device_get(jax.jit(lambda p, k: leaf_norms(jax.tree.map(
        lambda a, b: a.astype(F32) - b.astype(F32), p,
        init_params(k, c))))(p, key))
    return (losses, {k: float(v) for k, v in first.items()},
            {k: float(v) for k, v in change.items()})
