"""The benchmark's adapter to the program for a ``deepseek_v3``
configuration file (Moonlight-16B-A3B): the program's model
configuration, and the weights of ``bench.reference.deepseek`` moved
into the program's parameter tree.  Nothing here computes; it names,
splits and permutes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import deepseek as ref


def model_config(c: dict):
    """``repro.models.config.ModelConfig`` of a ``deepseek_v3`` file with
    one group, sigmoid ``noaux_tc`` routing and an expert share."""
    from repro.models.config import BlockSpec, MLAConfig, ModelConfig, \
        MoEConfig
    if (c["model_type"] != "deepseek_v3" or c["hidden_act"] != "silu"
            or c["scoring_func"] != "sigmoid"
            or c["topk_method"] != "noaux_tc" or c["n_group"] != 1
            or c["moe_layer_freq"] != 1):
        raise ValueError(f"{c['name']}: only single-group sigmoid "
                         f"deepseek_v3 routing in every layer after the "
                         f"dense ones is driven here")
    kd = c["first_k_dense_replace"]
    share = c["expert_share"]
    return ModelConfig(
        name=c["name"], d_model=c["hidden_size"],
        vocab_size=c["vocab_size"], d_ff=c["intermediate_size"],
        prefix=(BlockSpec("mla", "mlp"),) * kd,
        period=(BlockSpec("mla", "moe"),),
        n_periods=c["num_hidden_layers"] - kd,
        mla=MLAConfig(q_lora_rank=c["q_lora_rank"],
                      kv_lora_rank=c["kv_lora_rank"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"],
                      n_heads=c["num_attention_heads"],
                      rope_theta=c["rope_theta"]),
        moe=MoEConfig(n_experts=share["router_experts"],
                      top_k=c["num_experts_per_tok"],
                      d_expert=c["moe_intermediate_size"],
                      n_shared=c["n_shared_experts"], router="sigmoid",
                      route_scale=c["routed_scaling_factor"],
                      norm_topk=c["norm_topk_prob"],
                      experts_held=c["n_routed_experts"],
                      expert_offset=share["first_expert"]),
        mlp_act="silu", norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"])


def rope_order(dr: int) -> np.ndarray:
    """Columns of the published interleaved rope pairs in the order the
    program's rotate-half takes them: evens, then odds."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def _mla(L: dict, n: dict) -> dict:
    """A stack of the reference's attention leaves as the program's
    ``mla`` tree (leading layer axis kept)."""
    H, dn, dr, dv, r = n["H"], n["dn"], n["dr"], n["dv"], n["r"]
    perm = rope_order(dr)
    lead = L["q_proj"].shape[:-2]
    q = L["q_proj"].reshape(*lead, n["d"], H, dn + dr)
    q = jnp.concatenate([q[..., :dn], q[..., dn:][..., perm]], -1)
    kv_b = L["kv_b"].reshape(*lead, r, H, dn + dv)
    return {"w_q": q.reshape(*lead, n["d"], H * (dn + dr)),
            "w_dkv": L["kv_a"][..., :r],
            "kv_norm": L["kv_norm"],
            "w_kr": L["kv_a"][..., r:][..., perm],
            "w_uk": kv_b[..., :dn].reshape(*lead, r, H * dn),
            "w_uv": kv_b[..., dn:].reshape(*lead, r, H * dv),
            "wo": L["o_proj"]}


def program_params(p: dict, c: dict) -> dict:
    """The reference layout as the program's tree: the dense layers as
    ``prefix`` blocks, the MoE layers as one scanned period."""
    n = ref.dims(c)
    D, M = p["dense_layers"], p["moe_layers"]
    dense_mla = _mla(D, n)
    prefix = [{"norm_mixer": D["attn_norm"][i],
               "mla": jax.tree.map(lambda a: a[i], dense_mla),
               "norm_ff": D["mlp_norm"][i],
               "mlp": {k: D[k][i] for k in ("w_gate", "w_up", "w_down")}}
              for i in range(n["kd"])]
    moe = {"router": M["gate"].astype(jnp.float32),
           "router_bias": M["gate_bias"].astype(jnp.float32),
           "w_gate": M["w_gate"], "w_up": M["w_up"], "w_down": M["w_down"],
           "shared": {"w_gate": M["shared_gate"], "w_up": M["shared_up"],
                      "w_down": M["shared_down"]}}
    return {"embed": p["embed"], "final_norm": p["final_norm"],
            "lm_head": p["lm_head"], "prefix": prefix,
            "periods": {"b0": {"norm_mixer": M["attn_norm"],
                               "mla": _mla(M, n), "norm_ff": M["mlp_norm"],
                               "moe": moe}}}
