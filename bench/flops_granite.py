"""Operations of the granite-4.0-h cells, from the published config keys.

Kept with the benchmark so that no change to the program can move them.
"""
from __future__ import annotations

from bench.flops import model_flops


def param_count(cfg: dict) -> int:
    """Parameters of a granite-4.0-h LM (no experts) over the configuration's
    ``num_hidden_layers`` first layers, tied embeddings, unpadded vocabulary:
    each layer a mixer (Mamba2 or GQA attention) and the shared MLP, each
    with its RMSNorm."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    din = cfg["mamba_expand"] * d
    H, G, N = cfg["mamba_n_heads"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    conv_dim = din + 2 * G * N
    mamba = (d * (2 * din + 2 * G * N + H)          # in_proj
             + conv_dim * (cfg["mamba_d_conv"] + 1)  # conv weight and bias
             + 3 * H + din + din * d)               # dt_bias, A_log, D; norm
    hd = d // cfg["num_attention_heads"]
    attn = d * hd * (2 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])
    mlp = 3 * d * cfg["shared_intermediate_size"]
    per = {"mamba": mamba, "attention": attn}
    emb = V * d * (1 if cfg.get("tie_word_embeddings", True) else 2)
    return emb + sum(per[k] + mlp + 2 * d for k in kinds) + d


def attention_score_flops(cfg: dict, tokens: float, *, train: bool) -> float:
    """The causal attention scores the 6·N / 2·N rule leaves out:
    ``Q K^T`` and ``P V`` over half the sequence on average, 2·S·H·hd per
    token and attention layer forward, three times that trained."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    n_attn = cfg["layer_types"][:cfg["num_hidden_layers"]].count("attention")
    per_token = 2.0 * cfg["seq_len"] * H * (d // H) * n_attn
    return (3.0 if train else 1.0) * per_token * tokens


def window_flops(cfg: dict, eval_tokens: float, trained_tokens: float) -> float:
    """Model FLOPs of a window: 2·N per evaluated token, 6·N per trained
    token, plus the attention scores. Recomputation does not count."""
    n = param_count(cfg)
    return (model_flops(n, eval_tokens, train=False)
            + model_flops(n, trained_tokens, train=True)
            + attention_score_flops(cfg, eval_tokens, train=False)
            + attention_score_flops(cfg, trained_tokens, train=True))
