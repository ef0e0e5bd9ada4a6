"""The benchmark's adapter to the program under test: the program's model
configuration built from a configuration file, and the benchmark's
weights moved into the program's parameter tree.  Nothing here computes;
it only names."""
from __future__ import annotations

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")


def model_config(c: dict):
    """``repro.models.config.ModelConfig`` of a Llama-style
    configuration file."""
    from repro.models.config import AttnConfig, BlockSpec, ModelConfig
    if c["model_type"] != "llama" or c["hidden_act"] != "silu":
        raise ValueError(f"{c['name']}: only Llama-style SiLU decoders "
                         f"are driven here")
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], d_model=c["hidden_size"],
        vocab_size=c["vocab_size"], d_ff=c["intermediate_size"],
        prefix=(), period=(BlockSpec("attn", "mlp"),),
        n_periods=c["num_hidden_layers"],
        attn=AttnConfig(n_heads=c["num_attention_heads"],
                        n_kv_heads=c["num_key_value_heads"], head_dim=hd,
                        rope_theta=c["rope_theta"]),
        mlp_act="silu", tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"])


def program_params(p: dict) -> dict:
    """The reference layout (``bench.reference.smollm``) as the
    program's tree: one scanned period of one attention+MLP block."""
    L = p["layers"]
    return {"embed": p["embed"], "final_norm": p["final_norm"],
            "periods": {"b0": {
                "norm_mixer": L["attn_norm"],
                "attn": {k: L[k] for k in ATTN},
                "norm_ff": L["mlp_norm"],
                "mlp": {k: L[k] for k in MLP}}}}


def reference_params(p: dict) -> dict:
    """The program's tree in the reference layout (inverse of
    ``program_params``)."""
    b = p["periods"]["b0"]
    layers = {"attn_norm": b["norm_mixer"], "mlp_norm": b["norm_ff"]}
    layers.update({k: b["attn"][k] for k in ATTN})
    layers.update({k: b["mlp"][k] for k in MLP})
    return {"embed": p["embed"], "final_norm": p["final_norm"],
            "layers": layers}
