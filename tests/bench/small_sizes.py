"""The LM cell at a size a test run holds, with the comparison's limits at
that size.

The cell's own limits are set from chip readings at its own size. At this
one-layer size the readings differ, so the tests hold it to limits set the
same way from readings at this size (CPU, seeds 1-6 and 31337):

  eval_gap    program 9e-6 .. 3.6e-5; float8 control 1.2e-4 .. 2.1e-4;
              half the batch left out 2.8e-4 .. 4.0e-4
  m_gap       program 2.0e-3 .. 2.3e-2; half the batch 6.9e-2 .. 3.4e-1
  update_gap  program 1.7e-3 .. 1.1e-2; optimizer state unchanged 1
"""
LM_CELL = "lm_mamba2_370m.drift"
LM_LIMITS = {"eval_gap": 7e-5, "m_gap": 0.05, "update_gap": 0.03}


def lm_small(cfg, traffic):
    cfg.update(n_layer=1, d_model=64, vocab_size=512, d_state=16, headdim=16,
               chunk_size=8, seq_len=32, reservoir_n=64, train_batch=4,
               ref_block_rows=2, limits=dict(cfg["limits"], **LM_LIMITS))
    traffic.update(per_tick=8, prefill_ticks=40, ring_ticks=200,
                   trace_seconds=0.2)
