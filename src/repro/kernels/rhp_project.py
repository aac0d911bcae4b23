"""Stacked RHP/SimHash projection kernel: routed row-add of sign rows.

RHP state is b running hyperplane dot products per synopsis ([n, b] f32);
a batch of T tuples adds ``v_t * sgn_t`` into its routed row. Because the
sign matrix is DENSE (every tuple touches all b planes), the update is a
pure matmul — no one-hot bucket side:

    state[syn, :] += sum_t (syn_t == syn) * v_t * sgn[t, :]
                   =       A @ sgn
    A[syn, t] = (syn_t == syn) * v_t

i.e. an [S_tile x T_tile] x [T_tile x B_tile] MXU matmul per grid cell,
the densest of the scatter kernels, at ``Precision.HIGHEST`` (no bf16
rounding of the f32 values). ``syn``/``values`` ride as [1, T] rows.
Grid: (S_tiles, B_tiles, T_tiles), T innermost; the state block seeds the
t == 0 accumulation and the operand is aliased to the output (in place).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(syn_ref, val_ref, sgn_ref, state_ref, out_ref, *, s_tile,
            t_tile):
    s = pl.program_id(0)
    t = pl.program_id(2)
    s_ids = s * s_tile + jax.lax.broadcasted_iota(
        jnp.int32, (s_tile, t_tile), 0)
    a = jnp.where(s_ids == syn_ref[...], val_ref[...], 0.0)     # [S_t, T_t]

    @pl.when(t == 0)
    def _init():
        out_ref[...] = state_ref[...]

    out_ref[...] += jax.lax.dot_general(
        a, sgn_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                      # [S_t, B_t]


@functools.partial(jax.jit, static_argnames=("s_tile", "b_tile", "t_tile",
                                             "interpret"))
def rhp_project_update(state: jax.Array, syn_idx: jax.Array,
                       values: jax.Array, signs: jax.Array, *,
                       s_tile: int = 128, b_tile: int = 128,
                       t_tile: int = 512,
                       interpret: bool = True) -> jax.Array:
    """state [n, b] f32 += routed sign-row add. syn_idx [1, T] i32 (-1
    matches no row), values [1, T] f32 (mask pre-folded), signs [T, b]
    f32. All dims must be tile multiples (ops.py pads)."""
    n, b = state.shape
    t_total = syn_idx.shape[1]
    grid = (n // s_tile, b // b_tile, t_total // t_tile)
    return pl.pallas_call(
        functools.partial(_kernel, s_tile=s_tile, t_tile=t_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_tile), lambda s, b_, t: (0, t)),
            pl.BlockSpec((1, t_tile), lambda s, b_, t: (0, t)),
            pl.BlockSpec((t_tile, b_tile), lambda s, b_, t: (t, b_)),
            pl.BlockSpec((s_tile, b_tile), lambda s, b_, t: (s, b_)),
        ],
        out_specs=pl.BlockSpec((s_tile, b_tile), lambda s, b_, t: (s, b_)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(syn_idx, values, signs, state)
