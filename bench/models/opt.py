"""OPT decoder (model_type "opt"), the paper's biased-linear family:
what its published config means to the reference and to the program.

Pre-LayerNorm blocks (`do_layer_norm_before`); multi-head attention
with biases on q, k, v and the output projection; ReLU MLP `ffn_dim`
wide with biases; learned absolute positions added to the token
embedding (no rotary embedding); final LayerNorm; head tied to the
embedding.  HF's table keeps two unused leading rows (position p reads
row p + 2); here the table holds `max_position_embeddings` rows and
position p reads row p, the same model under a shifted random table."""
from bench.lib.decoder import Arch

EPS = 1e-5   # torch.nn.LayerNorm's default; OPT's config does not state it


def arch(c: dict) -> Arch:
    if not c["do_layer_norm_before"]:
        raise ValueError("post-LayerNorm OPT (350m) is not covered")
    if c["word_embed_proj_dim"] != c["hidden_size"]:
        raise ValueError("an embedding projection is not covered")
    return Arch(
        d=c["hidden_size"], layers=c["num_hidden_layers"],
        heads=c["num_attention_heads"], kv_heads=c["num_attention_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        ff=c["ffn_dim"], vocab=c["vocab_size"], norm="layer", eps=EPS,
        act=c["activation_function"], gated=False, bias=True,
        qk_norm=False, rope_theta=0.0,
        learned_pos=c["max_position_embeddings"],
        tied=c.get("tie_word_embeddings", True))


def program_config(c: dict) -> dict:
    """Keyword arguments of the program's ModelConfig.  The program
    applies rotary embedding in every attention layer unless
    `rope_fraction` is 0, which OPT needs."""
    return dict(
        family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_attention_heads"], d_ff=c["ffn_dim"],
        vocab_size=c["vocab_size"], qkv_bias=True, o_bias=True,
        mlp_bias=True, gated_mlp=False, act=c["activation_function"],
        norm="layernorm", norm_eps=EPS, pos_emb="learned",
        max_seq_len=c["max_position_embeddings"], rope_fraction=0.0,
        tie_embeddings=c.get("tie_word_embeddings", True))
