"""The granite cell at a size a test run holds, with the comparison's
limits at that size.

Three layers with the attention layer in the middle and the published
multipliers; every width cut, the 2048-token attention replaced by 32
tokens in 16-token blocks (so the blocked ``chunked_sdpa`` runs, as it
does on the chip). Readings at this size (CPU; sound seeds 1-6 and 31337,
float8 control seeds 1-4 and 31337, the fault seeds 1-3 and 31337):

  eval_gap    program 9.6e-7 .. 4.9e-6; float8 control 9.6e-6 .. 3.3e-5;
              residual multiplier dropped on the attention branch 2.8e-5
              .. 9.9e-5
  m_gap       program 4.8e-3 .. 2.2e-2; float8 control 1.0e-2 .. 2.0e-2;
              the dropped multiplier 2.66 .. 2.87
  update_gap  program 9.5e-4 .. 5.6e-3; float8 control 4.6e-3 .. 1.2e-2

Only ``eval_gap`` tells the float8 control from the program: the worst
leaf of the moment or of the update is one of the small ones (``D``,
``A_log``, the norm scales, the conv bias), where both read relative errors
of the same size. Their limits catch a wrong update, not a precision. With
the program in float32 every gap reads under 1.5e-7.
"""
GRANITE_CELL = "lm_granite4h_micro.drift"
GRANITE_LIMITS = {"eval_gap": 7e-6, "m_gap": 0.05, "update_gap": 0.03}
SMALL = dict(
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, shared_intermediate_size=128, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8, vocab_size=512,
    attn_chunk=16)


def granite_small(cfg, traffic):
    cfg.update(SMALL, seq_len=32, reservoir_n=64, train_batch=4,
               ref_block_rows=2, ref_train_rows=2,
               limits=dict(cfg["limits"], **GRANITE_LIMITS))
    traffic.update(per_tick=8, prefill_ticks=40, ring_ticks=200,
                   trace_seconds=0.2)
