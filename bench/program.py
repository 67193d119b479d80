"""How the benchmark hands a configuration file to the program under test.

The program takes a ``ModelConfig``; every size in it is set here from the
benchmark's configuration file, so the program's own registry entry for the
architecture contributes only the kind of its blocks.
"""

from __future__ import annotations


def model_config(c: dict):
    from repro.configs import get_arch

    H = c["num_attention_heads"]
    return get_arch(c["name"]).scaled(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=H, n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["hidden_size"] // H, rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        dtype=c["torch_dtype"], mlp_type="swiglu", pattern=("attn",),
    )


def param_shapes(cfg, ctx):
    """The program's parameter layout, as shapes only."""
    import jax

    from repro.models import init_lm

    return jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg, ctx))


def mesh(shape):
    from repro.launch.mesh import make_mesh

    return make_mesh(tuple(shape), ("data", "model"))
