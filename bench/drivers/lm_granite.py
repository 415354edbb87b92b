"""LM cells on granite-4.0-h: the LM cell's loop, window and comparison
(``bench/drivers/lm.py``) on a Mamba2 / attention hybrid with an MLP after
every mixer (``repro.models.pattern_lm``), compared with
``bench/refs/granite_h.py``.

The configuration file holds the published ``config.json`` keys, cut as
its ``reduced`` list says; :meth:`Cell._model_config` maps them onto the
program's ``ModelConfig``. The program's granite configuration is imported
first, so a tree that lacks it fails here, before anything compiles.
"""
from __future__ import annotations

import contextlib
import dataclasses

from repro.configs import granite_4_0_h_micro as program_config

from bench import flops_granite
from bench.drivers import lm
from bench.refs import granite_h as ref


@contextlib.contextmanager
def _granite_reference():
    """``lm.Cell`` reads its reference from ``bench.drivers.lm.ref``: point
    that at granite's while a method of the base class runs."""
    saved, lm.ref = lm.ref, ref
    try:
        yield
    finally:
        lm.ref = saved


class Cell(lm.Cell):
    def _model_config(self):
        c = self.cfg
        L = c["num_hidden_layers"]
        if c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * \
                c["hidden_size"]:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")
        return dataclasses.replace(
            program_config.CONFIG, num_layers=L,
            layer_pattern=tuple(c["layer_types"][:L]),
            d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["shared_intermediate_size"], vocab_size=c["vocab_size"],
            ssm_state=c["mamba_d_state"], ssm_head_dim=c["mamba_d_head"],
            ssm_groups=c["mamba_n_groups"], ssm_expand=c["mamba_expand"],
            ssm_conv_width=c["mamba_d_conv"], ssm_chunk=c["mamba_chunk_size"],
            norm_eps=c["rms_norm_eps"],
            embedding_multiplier=c["embedding_multiplier"],
            residual_multiplier=c["residual_multiplier"],
            logits_scaling=c["logits_scaling"],
            attention_scale=c["attention_multiplier"],
            attn_chunk=c["attn_chunk"], dtype=c["dtype"],
            param_dtype=c["param_dtype"], remat=c["remat"],
            tie_embeddings=c["tie_word_embeddings"])

    def setup(self) -> None:
        with _granite_reference():
            super().setup()

    def reference(self, precision: str) -> dict:
        with _granite_reference():
            return super().reference(precision)

    def model_params(self) -> int:
        return flops_granite.param_count(self.cfg)

    def window_flops(self) -> float:
        """The traced window's model FLOPs (``bench/flops_granite.py``)."""
        c = self.counts
        return flops_granite.window_flops(self.cfg, c["eval_tokens"],
                                          c["trained_tokens"])
