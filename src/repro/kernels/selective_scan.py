"""Mamba-1 selective scan in one pass, the state held in VMEM.

For each channel d and state index n the recurrence is

    h[n, d] <- exp(dt_t[d] * A[n, d]) * h[n, d] + dt_t[d] * x_t[d] * B_t[n]
    y_t[d]   = sum_n C_t[n] * h[n, d]

followed by the D skip and the z gate, y_t = (y_t + D * x_t) * silu(z_t).
The grid tiles the batch, the channels (``block_d`` lanes) and the time
axis (``block_t`` tokens); the time blocks of one channel tile run in
order and carry the state between them in a float32 (N, block_d) VMEM
scratch: N on sublanes, the channels on lanes.  Each time block streams
in its dt, x and z rows and its B and C columns, steps through the tokens
one at a time and writes y.  Nothing of size (S, d_inner, N) is ever
built, in VMEM or in HBM: the kernel reads its inputs once and writes y
once.

B and C go in transposed, (batch, N, S), so a token's B_t is one lane of
an (N, 128) tile.  The token loop runs in groups of ``GROUP`` tokens: one
packed bfloat16 sublane tile of the x and z rows.  A group's B and C
columns are rotated to lanes 0..GROUP-1 once, and each token then takes
its column by a static lane slice, broadcast across the channel lanes.
A token's y row is the sublane sum over N; the group's rows gather in a
(GROUP, block_d) scratch, so y is stored a whole tile at a time.

Everything is float32 (A = -exp(A_log), dt, the state, the products and
the sums); y is written in x's dtype.  A sequence that does not fill its
last time block is padded with dt = 0 and x = 0, where exp(0 * A) = 1 and
the input term is 0: the state passes the padded rows unchanged, and
their y rows are dropped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# tokens a step of the inner loop takes: one packed bf16 (16, 128) tile
GROUP = 16
BLOCK_T = 512
BLOCK_D = 512


def _kernel(n_chunks, dt_ref, x_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
            h0_ref, y_ref, hl_ref, h_scr, y_scr):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[0]

    A = a_ref[...]                                        # (N, TD)
    Dk = d_ref[...]                                       # (1, TD)

    def chunk(c, h):
        off = pl.multiple_of(c * LANES, LANES)
        bc = b_ref[0, :, pl.ds(off, LANES)]               # (N, 128)
        cc = c_ref[0, :, pl.ds(off, LANES)]

        def group(g, h):
            row = pl.multiple_of(off + g * GROUP, GROUP)
            dt = dt_ref[0, pl.ds(row, GROUP), :]          # (G, TD) f32
            x = x_ref[0, pl.ds(row, GROUP), :].astype(jnp.float32)
            dtx = dt * x
            # this group's B and C columns to lanes 0..G-1
            shift = lax.rem(LANES - g * GROUP, LANES)
            bg = pltpu.roll(bc, shift, 1)
            cg = pltpu.roll(cc, shift, 1)
            for i in range(GROUP):
                dA = jnp.exp(dt[i:i + 1] * A)             # (N, TD)
                h = dA * h + dtx[i:i + 1] * bg[:, i:i + 1]
                y_scr[i:i + 1, :] = jnp.sum(cg[:, i:i + 1] * h, axis=0,
                                            keepdims=True)
            z = z_ref[0, pl.ds(row, GROUP), :].astype(jnp.float32)
            y = (y_scr[...] + Dk * x) * (z * jax.nn.sigmoid(z))
            y_ref[0, pl.ds(row, GROUP), :] = y.astype(y_ref.dtype)
            return h

        return lax.fori_loop(0, LANES // GROUP, group, h)

    h = lax.fori_loop(0, n_chunks, chunk, h_scr[...])
    h_scr[...] = h

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        hl_ref[0] = h


def _round_up(n, m):
    return -(-n // m) * m


def selective_scan(dt, B_ssm, C_ssm, x, z, A_log, D, h0, *,
                   block_t=BLOCK_T, block_d=BLOCK_D, interpret=None):
    """The selective scan with the D skip and the z gate.

    dt: (B, S, di) float32 (after the softplus); B_ssm, C_ssm: (B, S, N);
    x, z: (B, S, di); A_log: (di, N); D: (di,); h0: (B, di, N) float32.
    ``di`` is a multiple of 128.  Returns (y (B, S, di) in x's dtype,
    h_last (B, di, N) float32)."""
    Bsz, S, di = dt.shape
    N = A_log.shape[1]
    if di % LANES:
        raise ValueError(f"d_inner {di} is not a multiple of {LANES}")
    td = next(w for w in (block_d, 256, LANES) if w <= di and di % w == 0)
    ts = min(block_t, _round_up(S, LANES))
    sp = _round_up(S, ts)

    def pad_t(a, axis):
        pads = [(0, 0)] * a.ndim
        pads[axis] = (0, sp - S)
        return jnp.pad(a, pads)

    f32 = jnp.float32
    args = (pad_t(dt.astype(f32), 1), pad_t(x, 1), pad_t(z, 1),
            pad_t(B_ssm.astype(f32).swapaxes(1, 2), 2),
            pad_t(C_ssm.astype(f32).swapaxes(1, 2), 2),
            -jnp.exp(A_log.astype(f32)).T,                # (N, di)
            D.astype(f32).reshape(1, di),
            h0.astype(f32).swapaxes(1, 2))                # (B, N, di)

    row = pl.BlockSpec((1, ts, td), lambda b, j, t: (b, t, j))
    col = pl.BlockSpec((1, N, ts), lambda b, j, t: (b, 0, t))
    chan = pl.BlockSpec((N, td), lambda b, j, t: (0, j))
    state = pl.BlockSpec((1, N, td), lambda b, j, t: (b, 0, j))
    blocks = (ts * td * (4 + 3 * x.dtype.itemsize) + 2 * N * ts * 4 +
              3 * N * td * 4 + td * 4)
    y, h_last = pl.pallas_call(
        functools.partial(_kernel, ts // LANES),
        out_shape=(jax.ShapeDtypeStruct((Bsz, sp, di), x.dtype),
                   jax.ShapeDtypeStruct((Bsz, N, di), f32)),
        grid=(Bsz, di // td, sp // ts),
        in_specs=[row, row, row, col, col, chan,
                  pl.BlockSpec((1, td), lambda b, j, t: (0, j)), state],
        out_specs=(row, state),
        scratch_shapes=[pltpu.VMEM((N, td), f32),
                        pltpu.VMEM((GROUP, td), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * blocks + (8 << 20)),
        name="mamba_selective_scan",
        interpret=interpret if interpret is not None else False,
    )(*args)
    return y[:, :S], h_last.swapaxes(1, 2)
