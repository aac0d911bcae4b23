"""Blocked pairwise-correlation kernel (the AggregativeOperation hot spot).

corr[i, j] = 1 - (|c_i|^2 + |c_j|^2 - 2 <c_i, c_j>)  over DFT coefficient
vectors c (flattened [N, K], K = 2 * n_coeffs). The Gram matrix <c_i, c_j>
is a blocked [I_t x K] x [K x J_t] MXU matmul; K is padded to the 128 lane
width by ops.py. This is the paper's 12.5M-pairs workload: after DFT
bucket pruning only candidate blocks are evaluated (mask via bucket
adjacency happens outside; the kernel is the dense inner engine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xi_ref, xj_ref, sqi_ref, sqj_ref, out_ref):
    xi = xi_ref[...]                       # [I_t, K]
    xj = xj_ref[...]                       # [J_t, K]
    gram = jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # [I_t, J_t]
    out_ref[...] = 1.0 - (sqi_ref[...] + sqj_ref[...] - 2.0 * gram)


@functools.partial(jax.jit, static_argnames=("i_tile", "j_tile", "interpret"))
def pairwise_corr(x: jax.Array, *, i_tile: int = 256, j_tile: int = 256,
                  interpret: bool = True) -> jax.Array:
    """x [N, K] flattened normalized DFT coeffs -> corr estimates [N, N]."""
    n, k = x.shape
    sq = jnp.sum(x * x, axis=-1)
    grid = (n // i_tile, n // j_tile)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((i_tile, k), lambda i, j: (i, 0)),
            pl.BlockSpec((j_tile, k), lambda i, j: (j, 0)),
            pl.BlockSpec((i_tile, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, j_tile), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((i_tile, j_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(x, x, sq[:, None], sq[None, :])
