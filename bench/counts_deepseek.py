"""Operations and bytes that a ``deepseek_v3`` decode step needs on the
chip that holds one expert share, computed from the configuration file.

As in ``bench.counts``, these count the model as published, not what
the compiled program happens to do: attention reads the latent cache of
the positions filled so far (latent space: ``kv_lora_rank`` +
``qk_rope_head_dim`` numbers a position), and the routed experts count
for the share of a token's ``top_k`` slots that lands on the held
experts on average (``top_k * held / router_experts``).
"""
from __future__ import annotations

from bench.counts import DTYPE_BYTES


def param_counts(c: dict) -> dict:
    """Parameters the chip holds, by part: ``embed`` (the input table),
    ``routed`` (the held experts of every MoE layer) and ``other``
    (everything else: attention, the dense layers' MLP, routers, shared
    experts, norms and the output head)."""
    d, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    kd = c["first_k_dense_replace"]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    fe = c["moe_intermediate_size"]
    E = c["expert_share"]["router_experts"]
    attn = (d * H * (dn + dr) + d * (r + dr) + r + r * H * (dn + dv)
            + H * dv * d + 2 * d)                    # + the two norms
    dense_mlp = 3 * d * c["intermediate_size"]
    moe_other = d * E + E + 3 * d * fe * c["n_shared_experts"]
    routed = (L - kd) * c["n_routed_experts"] * 3 * d * fe
    other = (L * attn + kd * dense_mlp + (L - kd) * moe_other
             + d + (0 if c["tie_word_embeddings"] else d * V))
    return {"embed": V * d, "routed": routed, "other": other}


def held_param_count(c: dict) -> int:
    return sum(param_counts(c).values())


def decode_flops_per_token(c: dict, context: float) -> float:
    """2 per multiplied parameter: every non-routed parameter but the
    input table (a gather), and the held experts for the share of a
    token's slots routed to them; plus, per layer, the latent scores
    (2 H context (kv_lora_rank + qk_rope_head_dim)) and the latent
    output (2 H context kv_lora_rank) over ``context`` positions."""
    n = param_counts(c)
    share = c["num_experts_per_tok"] / c["expert_share"]["router_experts"]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    attn = 2.0 * H * context * (r + c["qk_rope_head_dim"]) \
        + 2.0 * H * context * r
    return (2.0 * (n["other"] + share * n["routed"])
            + c["num_hidden_layers"] * attn)


def decode_bytes_per_step(c: dict, batch: int, context: float) -> float:
    """Bytes a decode step must read from HBM: every held parameter once
    (the input table counted whole), and the latent cache of ``context``
    filled positions of each of ``batch`` sequences."""
    pb = DTYPE_BYTES[c["param_dtype"]]
    kb = DTYPE_BYTES[c["cache_dtype"]]
    lat = (c["num_hidden_layers"] * batch * context
           * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * kb)
    return pb * held_param_count(c) + lat
