"""Plain reference of a DeepSeek-V3-layout decoder (Moonlight-16B-A3B),
written from the published description (the ``deepseek_v3`` modelling
code's equations) and nothing imported from the program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching.  Multi-head latent attention in its published, non-absorbed
form (the latent is up-projected to per-head keys and values); the MoE
layer scores all routed experts with a sigmoid, selects the top k by
score plus the selection-only bias, normalises the chosen scores and
scales them by ``routed_scaling_factor``, with no capacity and no drops.
``fp8`` gives the control: every matmul operand rounded to float8 with a
per-tensor scale, as in ``bench.reference.smollm``.

The expert share.  The file's ``n_routed_experts`` is the number of
experts held here, ``expert_share["first_expert"]`` the first of them,
and ``expert_share["router_experts"]`` the router's published width.
Slots routed to experts that are not held add nothing, as in the
program.  Expert ``e`` of each layer is made from ``fold_in(key, e)``,
so a share's weights are the uncut model's same experts.

Departures from the published model, each deliberate:

- no multi-token-prediction head (Moonlight has none:
  ``num_nextn_predict_layers`` 0);
- only the direct query projection (``q_lora_rank`` null) is written;
- weights are random (``initializer_range``), the router's selection
  bias included (the checkpoint's bias is learnt, never zero).

Rotary embedding is the published interleaved-pair form (each head's
rope columns gathered to halves, then rotate-half).  The program
rotates halves of its rope columns directly, so its ``w_q`` and ``w_kr``
are this layout's columns under that fixed permutation
(``bench.program_deepseek``): the same function, not an approximation.

Weights live in this module's own layout: ``{"embed", "final_norm",
"lm_head", "dense_layers": {name: [k_dense, ...]}, "moe_layers": {name:
[L - k_dense, ...]}}``, made in bfloat16 and cast to float32 one layer
at a time inside the layer scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.smollm import F32, HIGHEST, _einsum, _mm, _rmsnorm, \
    _rope, seed_key  # noqa: F401  (seed_key is the drivers')

def dims(c: dict) -> dict:
    if c.get("q_lora_rank") is not None:
        raise ValueError(f"{c['name']}: only the direct query projection "
                         f"(q_lora_rank null) is written here")
    share = c["expert_share"]
    return {"d": c["hidden_size"], "V": c["vocab_size"],
            "L": c["num_hidden_layers"], "kd": c["first_k_dense_replace"],
            "H": c["num_attention_heads"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "r": c["kv_lora_rank"], "f": c["intermediate_size"],
            "fe": c["moe_intermediate_size"],
            "fs": c["moe_intermediate_size"] * c["n_shared_experts"],
            "E": share["router_experts"], "held": c["n_routed_experts"],
            "e0": share["first_expert"], "k": c["num_experts_per_tok"]}


def _attn_shapes(n, L):
    d, H = n["d"], n["H"]
    return {"attn_norm": (L, d), "q_proj": (L, d, H * (n["dn"] + n["dr"])),
            "kv_a": (L, d, n["r"] + n["dr"]), "kv_norm": (L, n["r"]),
            "kv_b": (L, n["r"], H * (n["dn"] + n["dv"])),
            "o_proj": (L, H * n["dv"], d), "mlp_norm": (L, d)}


def param_shapes(c: dict) -> dict:
    """Every leaf's shape; the routed experts' are the held share's."""
    n = dims(c)
    d, kd, Lm = n["d"], n["kd"], n["L"] - n["kd"]
    dense = _attn_shapes(n, kd) | {
        "w_gate": (kd, d, n["f"]), "w_up": (kd, d, n["f"]),
        "w_down": (kd, n["f"], d)}
    moe = _attn_shapes(n, Lm) | {
        "gate": (Lm, d, n["E"]), "gate_bias": (Lm, n["E"]),
        "w_gate": (Lm, n["held"], d, n["fe"]),
        "w_up": (Lm, n["held"], d, n["fe"]),
        "w_down": (Lm, n["held"], n["fe"], d),
        "shared_gate": (Lm, d, n["fs"]), "shared_up": (Lm, d, n["fs"]),
        "shared_down": (Lm, n["fs"], d)}
    return {"embed": (n["V"], d), "final_norm": (d,),
            "lm_head": (d, n["V"]), "dense_layers": dense,
            "moe_layers": moe}


ROUTED = ("w_gate", "w_up", "w_down")


def init_params(key, c: dict, dtype=jnp.bfloat16) -> dict:
    """normal(0, initializer_range) matrices and the router's bias, unit
    norm scales, in ``dtype``.  A routed expert's weights come from
    ``fold_in`` of its leaf's key with the expert's global index, so
    they do not depend on which share holds it.  Jit it: one device
    call makes every leaf."""
    n = dims(c)
    shapes = param_shapes(c)
    std = c["initializer_range"]
    names = jax.tree.leaves(jax.tree.map_with_path(
        lambda p, _: jax.tree_util.keystr(p), shapes,
        is_leaf=lambda s: isinstance(s, tuple)))
    keys = dict(zip(sorted(names), jax.random.split(key, len(names))))

    def make(path, shp):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return jnp.ones(shp, dtype)
        k = keys[name]
        if "moe_layers" in name and path[-1].key in ROUTED:
            # [Lm, held, a, b]: expert e0 + j from fold_in(k, e0 + j)
            one = shp[:1] + shp[2:]
            ex = [jax.random.normal(jax.random.fold_in(k, n["e0"] + j), one,
                                    F32) for j in range(n["held"])]
            return (std * jnp.stack(ex, 1)).astype(dtype)
        return (std * jax.random.normal(k, shp, F32)).astype(dtype)

    return jax.tree.map_with_path(make, shapes,
                                  is_leaf=lambda s: isinstance(s, tuple))


def _rope_pairs(x, positions, theta):
    """The published rotary form: x [S, H, D] with rope pairs (2i, 2i+1)
    gathered to halves, then rotate-half."""
    S, H, D = x.shape
    x = x.reshape(S, H, D // 2, 2).swapaxes(-1, -2).reshape(S, H, D)
    return _rope(x, positions, theta)


def _attention(x, lp, c, n, fp8):
    """Causal MLA over one sequence, x [S, d]: the latent up-projected to
    per-head keys and values."""
    S = x.shape[0]
    pos = jnp.arange(S)
    H, dn, dr, dv, r = n["H"], n["dn"], n["dr"], n["dv"], n["r"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = _rmsnorm(x, lp["attn_norm"], eps)
    q = _mm(h, lp["q_proj"], fp8).reshape(S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope_pairs(q[..., dn:], pos, theta)
    kv_a = _mm(h, lp["kv_a"], fp8)
    ckv = _rmsnorm(kv_a[:, :r], lp["kv_norm"], eps)
    k_pe = _rope_pairs(kv_a[:, None, r:], pos, theta)[:, 0]       # [S, dr]
    kv = _mm(ckv, lp["kv_b"], fp8).reshape(S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (_einsum("qhd,khd->hqk", q_nope, k_nope, fp8)
         + _einsum("qhd,kd->hqk", q_pe, k_pe, fp8)) * (dn + dr) ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = _einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, fp8)
    return x + _mm(o.reshape(S, H * dv), lp["o_proj"], fp8)


def _swiglu(h, g, u, dwn, fp8):
    return _mm(jax.nn.silu(_mm(h, g, fp8)) * _mm(h, u, fp8), dwn, fp8)


def route(h, lp, c, n, fp8=False):
    """Combine weights of the held experts [S, held] for h [S, d]: sigmoid
    scores of all ``E`` routed experts, the top k by score plus bias,
    their scores normalised and scaled; zero where an expert is not
    chosen."""
    scores = jax.nn.sigmoid(_mm(h, lp["gate"], fp8))               # [S, E]
    _, idx = jax.lax.top_k(scores + lp["gate_bias"].astype(F32), n["k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    held = n["e0"] + jnp.arange(n["held"])
    return jnp.sum(w[:, :, None] * (idx[:, :, None] == held), 1)


def moe(h, lp, c, n, fp8=False):
    """The held share of a MoE layer plus its shared experts: h [S, d]
    (already normed) -> [S, d]."""
    cw = route(h, lp, c, n, fp8)
    g = _einsum("sd,edf->sef", h, lp["w_gate"], fp8)
    u = _einsum("sd,edf->sef", h, lp["w_up"], fp8)
    y = _einsum("sef,efd->sed", jax.nn.silu(g) * u, lp["w_down"], fp8)
    routed = jnp.einsum("sed,se->sd", y, cw, precision=HIGHEST)
    return routed + _swiglu(h, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], fp8)


def _dense_layer(c, n, fp8):
    def layer(x, lp):
        x = _attention(x, lp, c, n, fp8)
        h = _rmsnorm(x, lp["mlp_norm"], c["rms_norm_eps"])
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"],
                           fp8), None
    return layer


def _moe_layer(c, n, fp8):
    def layer(x, lp):
        x = _attention(x, lp, c, n, fp8)
        h = _rmsnorm(x, lp["mlp_norm"], c["rms_norm_eps"])
        return x + moe(h, lp, c, n, fp8), None
    return layer


def logits(params, c: dict, tokens, *, start: int = 0, fp8: bool = False):
    """One sequence: tokens [S] -> float32 logits [S - start, V] of the
    positions from ``start`` on (all positions are run through the
    layers; only the head is cut)."""
    n = dims(c)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(_dense_layer(c, n, fp8), x,
                            params["dense_layers"])
        x, _ = jax.lax.scan(_moe_layer(c, n, fp8), x, params["moe_layers"])
        x = _rmsnorm(x[start:], params["final_norm"], c["rms_norm_eps"])
        return _mm(x, params["lm_head"], fp8)
