"""Mamba2 SSD forward: the whole chunked scan in one Pallas TPU kernel.

Grid = (batch row, block of hb heads, chunk), the chunk axis innermost and
sequential. The block's ``[N, hb*P]`` f32 state is the final-state output
block, which stays in VMEM while the chunk index moves, so the recurrence
never round-trips HBM. A step computes ``C.B^T`` once for its group and
reuses it for every head of the block; each head's ``[Q, Q]`` decay and
score tensors live only in VMEM, in row blocks of ``qb`` rows that stop at
the diagonal (the block above it is all masked).

Inputs in the model's layout (no transposes of x, B or C): x and the group
block of B and C are read straight from the conv output ``xBC``
``[B, S, H*P + 2*G*N]`` by two BlockSpecs; dt ``[B, H, S]`` (f32,
post-softplus); A ``[H, 1]`` f32; D ``[H]`` f32 in SMEM. Outputs: y
``[B, S, H*P]`` in x's dtype with the D skip added, and the final state
``[B, N, H*P]`` in f32.

Precision: the cumsum, the exponents, the decays and the scores stay in f32;
only the ``scores . x`` product takes bf16 operands (as the jnp path's
``ssd_chunked`` does); every dot accumulates in f32. The inter-chunk and
state products keep f32 operands at ``_STATE_PRECISION``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The jnp path's f32 state einsums carry no precision; XLA lowers them on the
# TPU at DEFAULT, rounding the f32 operands to bf16 for one MXU pass with f32
# accumulation (the optimized HLO converts the state to bf16 before its
# convolution). The kernel names that precision; Mosaic's DEFAULT is the
# same single bf16 pass.
_STATE_PRECISION = jax.lax.Precision.DEFAULT
# x block bytes a step may hold: 32 heads of the mamba2-370m shape (1 MiB)
# and 40 of zamba2-2.7b's (1.25 MiB) fit, 80 do not
_BLOCK_BYTES = 2 << 20


def head_block(H, G, P, Q, itemsize):
    """Heads a grid step takes: the most that divide a group's heads, tile
    (dt's 8 sublanes, x's 128 lanes) and keep the x block within
    ``_BLOCK_BYTES``; a group's heads where none tiles (shapes that only the
    interpreter runs)."""
    rep = H // G
    fits = [hb for hb in range(1, rep + 1)
            if rep % hb == 0 and (hb % 8 == 0 or hb == H)
            and hb * P % 128 == 0 and Q * hb * P * itemsize <= _BLOCK_BYTES]
    return max(fits, default=rep)


def _dot(a, b, precision=None):
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _kernel(x_ref, bc_ref, dt_ref, a_ref, d_ref, y_ref, st_ref, *,
            Q, N, P, G, rep, hb, qb):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = ii >= jj
    dt = dt_ref[0]                               # [hb, Q]
    # inclusive cumsum of the log-decay along the chunk, as a product with
    # the upper-triangular ones at f32 precision
    cl = _dot(dt * a_ref[...], (ii <= jj).astype(f32), hi)   # [hb, Q]
    clt = cl.T                                   # [Q, hb]
    last = cl[:, Q - 1:]                         # [hb, 1]
    # each head's chunk decay exp(cl_last) as a [1, P] row (a product with
    # a selector, exact at f32 precision): Mosaic broadcasts a [1, 1] value
    # along lanes or along sublanes, not both at once
    pick = jax.lax.broadcasted_iota(jnp.int32, (Q, P), 0) == Q - 1
    decay_rows = jnp.exp(_dot(cl, pick.astype(f32), hi))   # [hb, P]
    h0 = pl.program_id(1) * hb

    if G == 1:
        Bm, Cm = bc_ref[0, :, :N], bc_ref[0, :, N:]
    else:  # the block's heads share one group
        g = h0 // rep
        Bm = bc_ref[0, :, pl.ds(g * N, N)]
        Cm = bc_ref[0, :, pl.ds((G + g) * N, N)]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)   # [Q, Q]
    cf = Cm.astype(f32)                          # [Q, N]
    btf = Bm.astype(f32).T                       # [N, Q]

    for h in range(hb):
        cols = slice(h * P, (h + 1) * P)
        x = x_ref[0, :, cols]                    # [Q, P]
        xf = x.astype(f32)
        dr, cr, cc = dt[h:h + 1], cl[h:h + 1], clt[:, h:h + 1]
        st = st_ref[0, :, cols]                  # [N, P]
        # inter: y_i += exp(cl_i) C_i . state
        inter = jnp.exp(cc) * _dot(cf, st, _STATE_PRECISION)
        for r in range(Q // qb):
            # intra: scores[i, j] = (C_i . B_j) exp(cl_i - cl_j) dt_j for
            # j <= i, over the row block's columns up to its diagonal; the
            # exponent is masked, not its result: above the diagonal it
            # grows with the chunk and exp overflows
            rows, kc = slice(r * qb, (r + 1) * qb), (r + 1) * qb
            seg = jnp.where(tri[rows, :kc], cc[rows] - cr[:, :kc], -jnp.inf)
            scores = cb[rows, :kc] * jnp.exp(seg) * dr[:, :kc]
            y = (_dot(scores.astype(x.dtype), x[:kc]) + inter[rows]
                 + d_ref[h0 + h] * xf[rows])
            y_ref[0, rows, cols] = y.astype(y_ref.dtype)
        # state = exp(cl_last) state + sum_j exp(cl_last - cl_j) dt_j B_j x_j
        w = jnp.exp(last[h:h + 1] - cr) * dr     # [1, Q]
        st_ref[0, :, cols] = (st * decay_rows[h:h + 1]
                              + _dot(btf * w, xf, _STATE_PRECISION))


def ssd_fused(xbc, dt, a, d, *, head_dim, groups, state, chunk,
              interpret=False):
    """xbc [B, S, H*P + 2*G*N] (x, then B and C of every group); dt [B, H, S]
    f32 (post-softplus); a [H, 1] f32 (A < 0); d [H] f32 (the D skip) -> (y
    [B, S, H*P] in xbc's dtype, final state [B, N, H*P] f32)."""
    Bsz, S = xbc.shape[:2]
    H, P, G, N = dt.shape[1], head_dim, groups, state
    Q = min(chunk, S)
    assert S % Q == 0 and H % G == 0 and xbc.shape[2] == H * P + 2 * G * N
    hb = head_block(H, G, P, Q, xbc.dtype.itemsize)
    assert (H // G) % hb == 0, (H, G, hb)
    qb = 128 if Q % 128 == 0 else Q
    # B and C as one lane block of xbc where it tiles into them, else a copy
    width = 2 * G * N
    bc, col = (xbc, H * P // width) if H * P % width == 0 else (
        xbc[..., H * P:], 0)
    kernel = functools.partial(_kernel, Q=Q, N=N, P=P, G=G, rep=H // G,
                               hb=hb, qb=qb)
    return pl.pallas_call(
        kernel,
        grid=(Bsz, H // hb, S // Q),
        in_specs=[
            pl.BlockSpec((1, Q, hb * P), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, Q, width), lambda b, j, c: (b, c, col)),
            pl.BlockSpec((1, hb, Q), lambda b, j, c: (b, j, c)),
            pl.BlockSpec((hb, 1), lambda b, j, c: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, hb * P), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, N, hb * P), lambda b, j, c: (b, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, H * P), xbc.dtype),
            jax.ShapeDtypeStruct((Bsz, N, H * P), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xbc, bc, dt, a, d)
