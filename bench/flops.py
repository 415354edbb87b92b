"""Operations the benchmark divides by measured time.

Kept with the benchmark so that no change to the program can move them.
"""
from __future__ import annotations


def mamba2_param_count(cfg: dict) -> int:
    """Parameters of a Mamba2 LM with tied embeddings, unpadded vocabulary
    (the count a model card states; padding rows are never a target)."""
    d, L, V = cfg["d_model"], cfg["num_hidden_layers"], cfg["vocab_size"]
    din = cfg["expand"] * d
    ns, g, P = cfg["state_size"], cfg["n_groups"], cfg["head_dim"]
    h = din // P
    conv_dim = din + 2 * g * ns
    in_proj = d * (2 * din + 2 * g * ns + h)
    per_layer = (in_proj + conv_dim * cfg["conv_kernel"] + conv_dim
                 + din * d + 3 * h + din + d)
    emb = V * d * (1 if cfg.get("tie_word_embeddings", True) else 2)
    return emb + L * per_layer + d


def model_flops(n_params: int, tokens: float, *, train: bool) -> float:
    """Model FLOPs: 6·N per trained token (forward and backward), 2·N per
    token evaluated forward only. Recomputation does not count. The rule of
    ``launch/dryrun.model_flops``, copied."""
    return (6.0 if train else 2.0) * n_params * tokens

