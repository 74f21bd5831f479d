"""Qwen3 dense decoder (model_type "qwen3"): what its published config
means to the reference and to the program.

Pre-norm RMSNorm blocks; grouped-query attention without biases, with a
per-head RMSNorm of q and k before rotary embedding (rotate-half, base
`rope_theta`); SwiGLU MLP; final RMSNorm; head tied to the embedding
when `tie_word_embeddings`."""
from bench.lib.decoder import Arch


def arch(c: dict) -> Arch:
    return Arch(
        d=c["hidden_size"], layers=c["num_hidden_layers"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], ff=c["intermediate_size"],
        vocab=c["vocab_size"], norm="rms", eps=c["rms_norm_eps"],
        act=c["hidden_act"], gated=True, bias=c["attention_bias"],
        qk_norm=True, rope_theta=float(c["rope_theta"]), learned_pos=0,
        tied=c["tie_word_embeddings"])


def program_config(c: dict) -> dict:
    """Keyword arguments of the program's ModelConfig for this config."""
    return dict(
        family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=True, qkv_bias=c["attention_bias"],
        o_bias=c["attention_bias"], rope_theta=float(c["rope_theta"]),
        gated_mlp=True, act=c["hidden_act"], norm="rmsnorm",
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        max_seq_len=c["max_position_embeddings"])
