"""Pallas TPU chunked SSD scan (Mamba2 / jamba hot loop).

The SSD duality lets the selective-scan be computed as dense chunk-local
matmuls (MXU work) plus a tiny cross-chunk recurrence. The kernel maps
chunks to the innermost *sequential* grid axis and carries the (P, N) state
in VMEM scratch — the recurrence never touches HBM:

  grid = (batch, heads, n_chunks)
  per chunk:  L = exp(segsum(dtA))           (chunk, chunk) fp32
              y_diag = ((C B^T) * L) @ (x*dt)           intra-chunk, MXU
              y_off  = (C @ state_in) * exp(cumsum dtA) inter-chunk
              state  = state * exp(sum dtA) + (B * decay)^T @ (x*dt)

B/C are head-shared (ngroups=1, MQA-style) so their blocks are indexed
ignoring the head axis. Oracle: ``repro.models.mamba2.ssd_chunked_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cumsums(d_col: jax.Array, d_row: jax.Array):
    """Inclusive cumsum of one (T,) vector given as a (T, 1) column and a
    (1, T) row; returns it in both layouts, plus the lower-triangle mask.
    Masked 2-D reductions keep every value 2-D in the TPU's native layout:
    no 1-D vector, ``cumsum`` or transpose inside the kernel."""
    T = d_col.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    zero = jnp.zeros((T, T), jnp.float32)
    cs_col = jnp.where(jj <= ii, d_row + zero, zero).sum(axis=1, keepdims=True)
    cs_row = jnp.where(ii <= jj, d_col + zero, zero).sum(axis=0, keepdims=True)
    return cs_col, cs_row, ii >= jj


def _ssd_kernel(
    x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref,  # inputs
    y_ref, fs_ref,                                 # outputs: y, final state
    state_scr,                                     # VMEM scratch: (P, N) fp32
    *,
    chunk: int,
):
    b, h, ci = (pl.program_id(i) for i in range(3))
    n_c = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)        # (chunk, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)  # (chunk, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)  # (1, chunk)
    A = a_ref[h]                               # scalar for this head (SMEM)
    Bm = b_ref[0].astype(jnp.float32)          # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)          # (chunk, N)

    xdt = x * dt_col
    # inclusive cumsum of dA = dt * A; L = exp(segsum(dA)), 0 above the
    # diagonal
    dA_cs, dA_cs_row, lower = _cumsums(dt_col * A, dt_row * A)
    L = jnp.where(lower, jnp.exp(dA_cs - dA_cs_row), 0.0)  # (chunk, chunk)
    dA_sum = (dt_col * A).sum(axis=0, keepdims=True)       # (1, 1)

    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (chunk, chunk)
    y_diag = jax.lax.dot_general(
        CB * L, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (chunk, P)

    state_in = state_scr[...]                  # (P, N)
    y_off = jax.lax.dot_general(
        Cm, state_in, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(dA_cs)                         # (chunk, P)

    y_ref[...] = (y_diag + y_off).reshape(y_ref.shape).astype(y_ref.dtype)

    decay_states = jnp.exp(dA_sum - dA_cs)     # (chunk, 1)
    upd = jax.lax.dot_general(
        xdt, Bm * decay_states, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # (P, N)
    state_scr[...] = state_in * jnp.exp(dA_sum) + upd

    @pl.when(ci == n_c - 1)
    def _fin():
        fs_ref[...] = state_scr[...].reshape(fs_ref.shape)


def ssd_scan(
    x: jax.Array,   # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) (softplus'd)
    A: jax.Array,   # (H,) negative
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:  # dt=0 padding is state-neutral
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    n_c = Sp // chunk
    xt = x.transpose(0, 2, 1, 3)  # (B,H,S,P)
    # dt per head as a column (B,H,S,1) and a row (B,H,1,S): both blocks
    # keep the TPU's (8, 128) tiling on their last two dims
    dtt = dt.transpose(0, 2, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, fs = pl.pallas_call(
        kernel,
        grid=(Bsz, H, n_c),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # A: (H,) scalars
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xt, dtt[..., None], dtt[:, :, None, :], A.astype(jnp.float32), Bm, Cm)
    y = y.transpose(0, 2, 1, 3)[:, :S]
    return y.astype(x.dtype), fs
