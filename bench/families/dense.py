"""The dense decoder family: attention and one MLP (SwiGLU or GELU) in
every layer, one layer a scan step of the program.

``program_config`` is the only part that imports the program; the
reference (reference.Model) and the work shape (work.Shape) import
nothing of it.
"""

from bench.harness import reference as REF
from bench.harness import work as WK

Reference = REF.Model
Shape = WK.Shape


def program_config(c: dict):
    """The program's configuration object for a configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        mlp_activation=c["mlp"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        layer_pattern=("attn",))
