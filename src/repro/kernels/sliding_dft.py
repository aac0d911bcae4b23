"""Batched sliding-DFT step kernel (StatStream over thousands of streams).

One tick per stream:  X_F <- (X_F + delta) * e^{2 pi i F / n}, delta =
x_in - x_out, vectorized over S streams x F coefficients with complex
arithmetic in (re, im) planes. Pure VPU elementwise kernel; the win over
stock XLA is fusing the 6-op complex multiply + mask into one VMEM pass
over the [S, F] coefficient planes (memory-roofline workload).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(re_ref, im_ref, delta_ref, mask_ref, twr_ref, twi_ref,
            out_re_ref, out_im_ref):
    re = re_ref[...]                       # [S_t, F]
    im = im_ref[...]
    delta = delta_ref[...]                 # [S_t, 1]
    mask = mask_ref[...]
    twr = twr_ref[...]                     # [1, F]
    twi = twi_ref[...]

    re2 = re + delta
    new_re = re2 * twr - im * twi
    new_im = re2 * twi + im * twr
    out_re_ref[...] = jnp.where(mask > 0, new_re, re)
    out_im_ref[...] = jnp.where(mask > 0, new_im, im)


@functools.partial(jax.jit, static_argnames=("s_tile", "interpret"))
def sliding_dft_step(re: jax.Array, im: jax.Array, delta: jax.Array,
                     mask: jax.Array, tw_re: jax.Array, tw_im: jax.Array,
                     *, s_tile: int = 512, interpret: bool = True):
    """re/im [S, F] f32, delta/mask [S] f32, tw_re/tw_im [F] f32."""
    s, f = re.shape
    grid = (s // s_tile,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((s_tile, f), lambda i: (i, 0)),
            pl.BlockSpec((s_tile, f), lambda i: (i, 0)),
            pl.BlockSpec((s_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((s_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_tile, f), lambda i: (i, 0)),
            pl.BlockSpec((s_tile, f), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((s, f), jnp.float32),
                   jax.ShapeDtypeStruct((s, f), jnp.float32)],
        interpret=interpret,
    )(re, im, delta[:, None], mask[:, None], tw_re[None, :], tw_im[None, :])
