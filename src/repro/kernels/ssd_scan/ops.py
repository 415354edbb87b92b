"""The SSD kernel in the model's layout (``models/ssm.py``)."""
from __future__ import annotations

import jax.numpy as jnp

from . import kernel


def ssd_fused(xBC, dt, A, D, *, head_dim, groups, state, chunk,
              interpret=False):
    """xBC [B, S, H*P + 2*G*N] (the conv output: x, B, C); dt [B, S, H]
    (post-softplus); A [H] (< 0); D [H] -> (y [B, S, H*P] with the D skip,
    final state [B, H, N, P] f32)."""
    Bsz, _, H = dt.shape
    f32 = jnp.float32
    y, st = kernel.ssd_fused(
        xBC, dt.astype(f32).transpose(0, 2, 1), A.astype(f32).reshape(H, 1),
        D.astype(f32), head_dim=head_dim, groups=groups,
        state=state, chunk=chunk, interpret=interpret)
    st = st.reshape(Bsz, state, H, head_dim).transpose(0, 2, 1, 3)
    return y, st
