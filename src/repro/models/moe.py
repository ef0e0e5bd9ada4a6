"""Mixture-of-experts feed-forward.

Routing follows the arch configs: softmax top-k (jamba) or sigmoid
scores with a selection-only bias and normalized top-k weights
(deepseek-v3, moonshot/Moonlight), plus optional shared experts that see
every token (deepseek: 1 shared + 256 routed; Moonlight: 2 + 64).

A layer may hold a share of the experts (``MoEConfig.experts_held``,
expert parallelism): the router scores all ``n_experts``, the layer
computes only the experts it holds, each weighted by its routing weight,
and slots routed to experts it does not hold add nothing.  The shared
experts are computed in every share.

Two compute paths:
  * ``forward`` — einsum-dense dispatch: every held expert multiplies
    every token, masked by routing weights.  Exact, with no capacity;
    the decode path, where an expert sees at most one token per request
    and the step reads every held expert's weights anyway.
  * ``forward_dropless`` — capacity-bounded gather dispatch used by the
    distributed train step (tokens sorted to experts, EP alltoall handled
    one level up in train/).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import mlp
from repro.models.common import dense_init, split_keys
from repro.models.config import MoEConfig


def init(key, cfg: MoEConfig, d_model: int) -> dict:
    ks = split_keys(key, ["router", "experts", "shared"])
    ek = jax.random.split(ks["experts"], 3)
    E = cfg.n_held
    p = {
        "router": dense_init(ks["router"], (d_model, cfg.n_experts),
                             scale=d_model ** -0.5).astype(jnp.float32),
        # stacked held experts: [n_held, ...]
        "w_gate": _stack(ek[0], E, d_model, cfg.d_expert),
        "w_up": _stack(ek[1], E, d_model, cfg.d_expert),
        "w_down": _stack(ek[2], E, cfg.d_expert, d_model, transpose=True),
    }
    if cfg.router == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared:
        p["shared"] = mlp.init(ks["shared"], d_model,
                               cfg.d_expert * cfg.n_shared)
    return p


def _stack(key, e, a, b, transpose=False):
    shape = (e, b, a) if transpose else (e, a, b)
    w = dense_init(key, shape)
    return jnp.swapaxes(w, 1, 2) if transpose else w


def route(p, cfg: MoEConfig, x):
    """x: [T, d] -> (weights [T, k], idx [T, k], probs [T, E])."""
    logits = x.astype(jnp.float32) @ p["router"]
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"]          # bias only biases selection
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel = scores
    _, idx = jax.lax.top_k(sel, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return (w * cfg.route_scale).astype(x.dtype), idx, scores


def forward(p, cfg: MoEConfig, x, act: str = "silu"):
    """Dense-dispatch MoE: x [B, S, d] -> [B, S, d]."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    w, idx, _ = route(p, cfg, xt)                  # [T,k], [T,k]
    # [T, k, n_held]: an expert id outside the share has an all-zero row
    onehot = jax.nn.one_hot(idx - cfg.expert_offset, cfg.n_held,
                            dtype=x.dtype)
    cw = jnp.einsum("tk,tke->te", w, onehot)       # [T, E] combine weights
    h = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, p["w_up"])
    h = mlp.ACT[act](h) * u
    y = jnp.einsum("tef,efd->ted", h, p["w_down"])
    out = jnp.einsum("ted,te->td", y, cw)
    if cfg.n_shared:
        out = out + mlp.forward(p["shared"], xt, act)
    return out.reshape(B, S, d)


def forward_dropless(p, cfg: MoEConfig, x, act: str = "silu",
                     capacity_factor: float = 1.25):
    """Capacity-bounded gather dispatch: tokens are bucketed per held
    expert (static capacity C = int(T * k / n_experts * factor)); overflow
    drops, and so do slots routed to experts outside the share.
    This is the single-device form of the EP dispatch in train/."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    w, idx, _ = route(p, cfg, xt)
    E, K = cfg.n_held, cfg.top_k
    C = max(1, int(T * K / cfg.n_experts * capacity_factor))
    flat_e = idx.reshape(-1) - cfg.expert_offset             # [T*K]
    held = (flat_e >= 0) & (flat_e < E)
    flat_e = jnp.where(held, flat_e, 0)
    # position of each (token, slot) within its expert bucket
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32) \
        * held[:, None]                                      # [T*K, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1)
    pos = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]  # [T*K]
    keep = held & (pos < C)
    dest = jnp.where(keep, flat_e * C + pos, E * C)          # drop slot
    buckets = jnp.zeros((E * C + 1, d), xt.dtype)
    src = jnp.repeat(xt, K, axis=0)
    buckets = buckets.at[dest].set(src)
    be = buckets[: E * C].reshape(E, C, d)
    h = mlp.ACT[act](jnp.einsum("ecd,edf->ecf", be, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", be, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    flat_y = jnp.concatenate([ye.reshape(E * C, d),
                              jnp.zeros((1, d), xt.dtype)])
    gathered = flat_y[dest].reshape(T, K, d)
    out = jnp.einsum("tkd,tk->td", gathered, w)
    if cfg.n_shared:
        out = out + mlp.forward(p["shared"], xt, act)
    return out.reshape(B, S, d)


def aux_loss(cfg: MoEConfig, probs, idx):
    """Switch-style load-balance loss over router probs [T,E], idx [T,k]."""
    E = cfg.n_experts
    load = jax.nn.one_hot(idx, E).sum((0, 1)) / idx.shape[0]  # frac routed
    imp = probs.mean(0)
    return E * jnp.sum(load * imp)
