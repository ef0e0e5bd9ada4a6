"""Moonlight-16B-A3B (a deepseek_v3 layout) against its plain reference,
``bench/reference/deepseek.py``, at a small size on the CPU with seeded
random weights; the latent-space decode against the up-projected
attention it replaces; the held expert share against the uncut layer;
and the decode step's shape at Moonlight's widths.

The program runs here with float32 weights (and cache), so it and the
reference compute the same function in the same precision and agree to
float32 round-off: the tolerances below are a few hundred times f32's
epsilon on values of order one.
"""
import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program_deepseek as program  # noqa: E402
from bench.reference import deepseek as ref  # noqa: E402
from repro import configs  # noqa: E402
from repro.models import blocks, mla, moe  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.config import BlockSpec, MLAConfig  # noqa: E402
from repro.serve.step import ServeOptions, init_serve_cache, \
    make_decode_step  # noqa: E402

MOONLIGHT = json.loads(
    (ROOT / "bench/configs/moonlight-16b-a3b.json").read_text())
# Moonlight's file at a small size: every mechanism, narrow widths
TINY = dict(MOONLIGHT, name="tiny-moonlight", hidden_size=64,
            intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=16, num_hidden_layers=3, vocab_size=256,
            num_experts_per_tok=3, n_routed_experts=8,
            expert_share={"router_experts": 8, "first_expert": 0,
                          "chips_per_layer": 1},
            initializer_range=0.1)
TOL = 2e-4
F32 = jnp.float32


def f32(tree):
    return jax.tree.map(lambda a: a.astype(F32) if jnp.issubdtype(
        a.dtype, jnp.floating) else a, tree)


def share(c, first, held):
    return dict(c, n_routed_experts=held,
                expert_share=dict(c["expert_share"], first_expert=first))


@pytest.fixture(scope="module")
def tiny():
    ref_p = jax.jit(lambda k: ref.init_params(k, TINY))(jax.random.key(3))
    prog_p = f32(program.program_params(ref_p, TINY))
    tokens = jax.random.randint(jax.random.key(4), (2, 12), 0,
                                TINY["vocab_size"])
    return program.model_config(TINY), ref_p, prog_p, tokens


def test_forward_logits_match_reference(tiny):
    cfg, ref_p, prog_p, tokens = tiny
    got = jax.jit(lambda p, t: M.forward(p, cfg, t))(prog_p, tokens)
    for b in range(tokens.shape[0]):
        want = ref.logits(ref_p, TINY, tokens[b])
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   atol=TOL, rtol=TOL)


def test_prefill_then_decode_matches_reference(tiny):
    """The prompt prefilled token by token through ``make_decode_step``,
    then greedy tokens decoded; every position's logits from the decode
    step against the reference's full forward pass over the served
    sequence, and each served token is the reference's best."""
    cfg, ref_p, prog_p, tokens = tiny
    B, P, G = tokens.shape[0], tokens.shape[1], 6
    cache0 = f32(init_serve_cache(cfg, B, P + G))
    step = jax.jit(make_decode_step(cfg, None, ServeOptions()))
    cache = cache0
    for i in range(P - 1):
        _, cache = step(prog_p, cache, tokens[:, i:i + 1])
    tok, served = tokens[:, P - 1:], []
    for _ in range(G):
        tok, cache = step(prog_p, cache, tok)
        served.append(tok)
    seqs = jnp.concatenate([tokens] + served, 1)          # [B, P + G]

    logit_step = jax.jit(lambda p, c, t: M.decode_step(p, cfg, c, t))
    cache, got = cache0, []
    for i in range(P + G - 1):
        lg, cache = logit_step(prog_p, cache, seqs[:, i:i + 1])
        got.append(lg[:, 0])
    got = jnp.stack(got, 1)                               # [B, P+G-1, V]
    for b in range(B):
        want = ref.logits(ref_p, TINY, seqs[b, :-1])
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   atol=TOL, rtol=TOL)
        lg = want[P - 1:]
        gap = jnp.max(lg, -1) - jnp.take_along_axis(
            lg, seqs[b, P:, None], -1)[:, 0]
        assert float(jnp.max(gap)) <= TOL


@pytest.mark.parametrize("t", [0, 9, 15])
@pytest.mark.parametrize("q_lora_rank", [None, 24],
                         ids=["direct_q", "compressed_q"])
def test_absorbed_decode_matches_attend(q_lora_rank, t):
    """Latent-space decode against ``_attend``'s up-projected keys and
    values over the same cache, in float32."""
    cfg = MLAConfig(q_lora_rank=q_lora_rank, kv_lora_rank=16,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    n_heads=4, rope_theta=50000.0)
    B, S, d = 3, 16, 64
    ks = jax.random.split(jax.random.key(t), 4)
    p = f32(mla.init(ks[0], cfg, d))
    x = jax.random.normal(ks[1], (B, 1, d), F32)
    cache = {"ckv": jax.random.normal(ks[2], (B, S, 16), F32),
             "kr": jax.random.normal(ks[3], (B, S, 1, 8), F32),
             "len": jnp.int32(t)}
    got, new = jax.jit(lambda p, x, c: mla.decode_step(p, cfg, x, c))(
        p, x, cache)

    def want(p, x, c):
        pos = jnp.full((B, 1), c["len"], jnp.int32)
        q_nope, q_rope, ckv, k_rope = mla._latents(p, cfg, x, pos, 1e-6)
        c2 = c["ckv"].at[:, t].set(ckv[:, 0])
        r2 = c["kr"].at[:, t].set(k_rope[:, 0])
        mask = jnp.broadcast_to(jnp.arange(S) <= t, (B, 1, S))
        return mla._attend(p, cfg, q_nope, q_rope, c2, r2, mask), c2, r2

    y, c2, r2 = jax.jit(want)(p, x, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(y), atol=TOL,
                               rtol=TOL)
    np.testing.assert_array_equal(np.asarray(new["ckv"]), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(new["kr"]), np.asarray(r2))
    assert int(new["len"]) == t + 1


def _layer_params(lp, first, held):
    """One reference MoE layer's leaves as the program's ``moe`` tree,
    holding experts [first, first + held)."""
    sl = slice(first, first + held)
    return f32({"router": lp["gate"], "router_bias": lp["gate_bias"],
                "w_gate": lp["w_gate"][sl], "w_up": lp["w_up"][sl],
                "w_down": lp["w_down"][sl],
                "shared": {"w_gate": lp["shared_gate"],
                           "w_up": lp["shared_up"],
                           "w_down": lp["shared_down"]}})


def test_held_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts: each share's layer output, with the
    shared experts (computed in every share) counted once, sums to the
    uncut reference layer; a reference share made from the same key
    holds the uncut model's same experts."""
    key = jax.random.key(5)
    x = jax.random.normal(jax.random.key(6), (1, 10, 64), F32)
    uncut = ref.init_params(key, TINY)["moe_layers"]
    lp0 = jax.tree.map(lambda a: a[0], uncut)
    n = ref.dims(TINY)
    want = ref.moe(x[0], lp0, TINY, n)
    shared = ref._swiglu(x[0], lp0["shared_gate"], lp0["shared_up"],
                         lp0["shared_down"], False)
    cfg = program.model_config(TINY).moe
    prog_sum, ref_sum = 0.0, 0.0
    for s in range(4):
        cs = share(TINY, 2 * s, 2)
        mine = jax.tree.map(lambda a: a[0],
                            ref.init_params(key, cs)["moe_layers"])
        np.testing.assert_array_equal(np.asarray(mine["w_up"]),
                                      np.asarray(lp0["w_up"][2 * s:2 * s + 2]))
        ref_sum = ref_sum + ref.moe(x[0], mine, cs, ref.dims(cs))
        scfg = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * s)
        prog_sum = prog_sum + moe.forward(_layer_params(lp0, 2 * s, 2),
                                          scfg, x)[0]
    np.testing.assert_allclose(np.asarray(ref_sum - 3 * shared),
                               np.asarray(want), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(prog_sum - 3 * shared),
                               np.asarray(want), atol=TOL, rtol=TOL)
    dropless = moe.forward_dropless(
        _layer_params(lp0, 2, 2),
        dataclasses.replace(cfg, experts_held=2, expert_offset=2), x,
        capacity_factor=float(cfg.n_experts))[0]
    np.testing.assert_allclose(
        np.asarray(dropless),
        np.asarray(moe.forward(_layer_params(lp0, 2, 2), dataclasses.replace(
            cfg, experts_held=2, expert_offset=2), x)[0]),
        atol=TOL, rtol=TOL)


def test_decode_moe_drops_no_token():
    """Every token of a decode batch routed to expert 0: the decode
    block's MoE gives the dense-dispatch result, with no slot dropped."""
    cfg = configs.get_smoke("moonshot-v1-16b-a3b")
    spec = BlockSpec("none", "moe")
    p = f32(blocks.init_block(jax.random.key(0), spec, cfg))
    p["moe"]["router_bias"] = p["moe"]["router_bias"].at[0].set(10.0)
    x = jax.random.normal(jax.random.key(1), (8, 1, cfg.d_model), F32)
    _, idx, _ = moe.route(p["moe"], cfg.moe, x[:, 0])
    assert bool((idx == 0).any(-1).all())
    got, _ = blocks.decode(p, spec, cfg, x, {})
    want = blocks.forward(p, spec, cfg, x, positions=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)


_DOT = re.compile(r"stablehlo\.dot_general\b.*->\s*tensor<([0-9x]+)x\w+>")


def _dot_shapes(text):
    return [tuple(int(n) for n in m.group(1).split("x"))
            for m in map(_DOT.search, text.splitlines()) if m]


def test_decode_step_forms_no_per_position_key_or_value():
    """Moonlight's widths, its 8-expert share, batch 32, cache 2304: no
    dot in the lowered decode step yields a [B, S, ...] tensor of a
    key's or value's size (H x 128 a position).  ``_attend`` on the same
    cache is the control: its lowering has them."""
    cfg = configs.get_config("moonshot-v1-16b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=8))
    a = cfg.mla
    B, S = 32, 2304
    kv_elems = B * S * a.n_heads * min(a.qk_nope_head_dim, a.v_head_dim)

    def offending(shapes):
        return [s for s in shapes if B in s and S in s
                and int(np.prod(s)) >= kv_elems]

    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: init_serve_cache(cfg, B, S))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    step = make_decode_step(cfg, None, ServeOptions())
    shapes = _dot_shapes(jax.jit(step).lower(params, cache, tokens)
                         .as_text())
    assert shapes and offending(shapes) == []

    sds = jax.ShapeDtypeStruct
    H, bf = a.n_heads, jnp.bfloat16
    lp = jax.tree.map(lambda x: sds(x.shape[1:], x.dtype),
                      params["periods"]["b0"]["mla"])
    control = jax.jit(lambda p, qn, qr, c, r, m: mla._attend(
        p, a, qn, qr, c, r, m)).lower(
        lp, sds((B, 1, H, a.qk_nope_head_dim), bf),
        sds((B, 1, H, a.qk_rope_head_dim), bf),
        sds((B, S, a.kv_lora_rank), bf),
        sds((B, S, 1, a.qk_rope_head_dim), bf), sds((B, 1, S), jnp.bool_))
    assert len(offending(_dot_shapes(control.as_text()))) == 2


def test_sharding_treats_a_held_stack_as_experts():
    """A stack of ``experts_held`` rows is sharded as an expert stack:
    its expert axis over ``model``."""
    from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P
    from repro.train import sharding
    cfg = configs.get_smoke("moonshot-v1-16b-a3b")
    cfg = dataclasses.replace(cfg, d_model=256, moe=dataclasses.replace(
        cfg.moe, d_expert=256, experts_held=2, expert_offset=4))
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    assert params["periods"]["b0"]["moe"]["w_up"].shape == (2, 2, 256, 256)
    mesh = AbstractMesh((1, 2), ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
    specs = sharding.param_specs(params, cfg, mesh)["periods"]["b0"]["moe"]
    for name in ("w_gate", "w_up", "w_down"):
        assert specs[name] == P(None, "model", "data", None), name
