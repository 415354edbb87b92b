"""Fused TBS-step payload pass -- the sampler hot path as ONE kernel.

A full R-TBS tick (paper Alg. 2: decay -> downsample -> batch insert /
victim replacement) is expressed by :mod:`repro.core.rtbs` as a single
slot-index map ``src[cap]`` over TWO sources -- the old reservoir
(``src < cap``) and the arriving batch (``src >= cap``) -- composed from the
per-stage maps in O(cap) integer ops. The kernel applies that map as one
row gather by DMA of 32-bit words: each payload row moves HBM -> HBM once
per tick, and every dtype is copied bit for bit. See DESIGN.md Sec. 11.
"""
from . import ops, ref  # noqa: F401
