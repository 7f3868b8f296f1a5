"""Dense Llama-architecture models (``architectures: ["LlamaForCausalLM"]``
with ``hidden_act: silu``): the program's `ModelConfig`, seeded weights,
and the operations a served token costs.

`model_config` maps the file's published keys onto the program's
`ModelConfig`. `init_weights` makes seeded bfloat16 weights on the
device in one jitted call, laid out as the program's dense stack takes
them (``cycles["l0"]`` leaves stacked over layers). Norm gains are
stored as offsets from 1, which is how the program applies them; the
plain reference (``reference/llama.py``) reads the same arrays with
that convention.

`prefill_flops` and `decode_flops` count model operations for
``metrics/step_mfu.py``: every weight matrix of a layer (q, k, v, the
output projection and a SwiGLU MLP), attention over the context with
causal prefill counting only the scores it needs, and the output head
at the one position that yields a token.
"""
from __future__ import annotations

from chipbench.model import prng_key


def model_config(conf: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig
    arch = conf["architectures"]
    if arch != ["LlamaForCausalLM"] or conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: only dense Llama-architecture "
                         f"configurations map onto the served path; got "
                         f"{arch} / {conf['hidden_act']}")
    return ModelConfig(
        name=conf["name"], arch_type="dense", source=conf["source"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"], mlp_kind="swiglu",
        tie_embeddings=conf["tie_word_embeddings"])


def weight_bytes(cfg) -> int:
    d, H, K, hd, f, V, L = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim, cfg.d_ff, cfg.vocab_size,
                            cfg.num_layers)
    layer = d * (H + 2 * K) * hd + H * hd * d + 3 * d * f + 2 * d
    return 2 * (L * layer + 2 * V * d + d)


def _init(key, cfg):
    import jax
    import jax.numpy as jnp
    d, H, K, hd, f, V, L = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim, cfg.d_ff, cfg.vocab_size,
                            cfg.num_layers)
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 12))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(bf)

    layer = {
        # gains 1 + N(0, 0.1): a reference that drops a norm's gain fails
        "ln1": normal((L, d), 0.1),
        "attn": {"wq": normal((L, d, H, hd), d ** -0.5),
                 "wk": normal((L, d, K, hd), d ** -0.5),
                 "wv": normal((L, d, K, hd), d ** -0.5),
                 "wo": normal((L, H, hd, d), (H * hd) ** -0.5)},
        "ln2": normal((L, d), 0.1),
        "mlp": {"wi": normal((L, d, 2, f), d ** -0.5),
                "wo": normal((L, f, d), f ** -0.5)},
    }
    return {"embed": normal((V, d), 1.0),
            "final_norm": normal((d,), 0.1),
            "lm_head": normal((d, V), d ** -0.5),
            "prefix": (), "cycles": {"l0": layer}, "rest": ()}


def init_weights(cfg, seed: int):
    """Seeded bfloat16 weights on the default device, one jitted call."""
    import functools

    import jax
    if cfg.tie_embeddings:
        raise ValueError(f"{cfg.name}: tied embeddings are not laid out")
    return jax.jit(functools.partial(_init, cfg=cfg))(prng_key(seed))


def layer_matmul_flops(cfg):
    """Operations of one token through one layer's weight matrices."""
    d, H, K, hd, f = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.d_ff)
    return 2 * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * f)


def attention_flops(cfg, context):
    """One query token attending over ``context`` positions, one layer."""
    return 4 * cfg.num_heads * cfg.head_dim * context


def head_flops(cfg):
    return 2 * cfg.d_model * cfg.vocab_size


def prefill_flops(cfg, n_pre, s):
    """A prefill of ``s`` tokens after ``n_pre`` positions already in
    the cache, with the output head at its last position."""
    L = cfg.num_layers
    att = sum(attention_flops(cfg, n_pre + i + 1) for i in range(s))
    return L * (s * layer_matmul_flops(cfg) + att) + head_flops(cfg)


def decode_flops(cfg, context):
    """One decode step of one sequence whose new token sees ``context``
    positions."""
    return cfg.num_layers * (layer_matmul_flops(cfg)
                             + attention_flops(cfg, context)) \
        + head_flops(cfg)
