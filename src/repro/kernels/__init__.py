"""Pallas TPU kernels for the perf-critical compute hot spots.

  * flash_attention  -- blocked online-softmax attention (causal/SWA/GQA):
                        MXU-aligned [block_q, block_k] tiles resident in VMEM,
                        scores never touch HBM.
  * ssd_scan         -- the Mamba2 SSD forward, fused: per chunk, C.B^T once
                        per group, each head's [Q, Q] scores in VMEM, the
                        inter-chunk state carried in VMEM; x, B, C read from
                        the conv output in the model's layout. ``models/ssm``
                        routes every call that is not differentiated and is
                        lowered for a TPU to it.
  * tbs_step         -- the sampler hot path: a whole R-TBS tick's composed
                        slot map applied as ONE two-source row gather
                        (reservoir + arriving batch; 32-bit word copies, so
                        bit-exact for every dtype; DESIGN.md Sec. 11).
  * reservoir_compact -- stable keep-mask compaction for sample
                        materialization (``latent.compact_items`` /
                        ``api.materialize_view``): a jnp prefix sum builds
                        the slot map, the ``tbs_step`` gather applies it.

Each package ships ``ops.py`` (the wrapper its callers use; for
``tbs_step`` and ``reservoir_compact`` backend-keyed: compiled Pallas on TPU,
jnp oracle off-TPU, ``impl="interpret"`` for CPU CI kernel validation) and
``ref.py`` (pure-jnp oracle); tests sweep shapes/dtypes with
assert_allclose.
"""
