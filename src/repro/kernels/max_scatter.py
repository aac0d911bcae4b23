"""Stacked max-scatter kernel: HyperLogLog registers and bit-set sketches.

Serves every sketch whose update raises a few positions of its routed row
and whose merge is an elementwise max:

  * HyperLogLog: one register per tuple, raised to the tuple's rank;
  * Bloom filters: k hash positions per tuple set to 1 (OR == max on
    {0, 1} int32 lanes);
  * FM/PCSA bitmaps: one flattened (map, bit) position per tuple
    (``ops.fm_update`` flattens the [maps, bits] plane).

Update rule per output tile (synopsis tile s, position tile m):

    state[syn, m] = max(state[syn, m],
                        max_{t, h} upd_t * [syn_t == syn] * [pos_th == m])

Max has no matmul form, so this is a VPU sweep. Per-tuple operands ride
with T on the SUBLANE axis (``syn``/``upd`` as [T, 1] columns, ``pos`` as
[T, k]) so that reducing a [T_tile, M_tile] plane over T leaves one state
row laid out along lanes, ready to store; the sweep walks the S_tile rows
of the state block one at a time. Grid: (S_tiles, M_tiles, T_tiles) with T
innermost; the state operand is aliased to the output (in place).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(syn_ref, pos_ref, upd_ref, state_ref, out_ref, *, s_tile,
            m_tile, k):
    s = pl.program_id(0)
    m_ = pl.program_id(1)
    t = pl.program_id(2)

    m_ids = m_ * m_tile + jax.lax.broadcasted_iota(jnp.int32, (1, m_tile), 1)
    pos = pos_ref[...]                                   # [T_t, k]
    hit_m = pos[:, 0:1] == m_ids                         # [T_t, M_t]
    for h in range(1, k):
        hit_m = hit_m | (pos[:, h:h + 1] == m_ids)
    syn = syn_ref[...]                                   # [T_t, 1]
    upd = upd_ref[...]                                   # [T_t, 1]

    @pl.when(t == 0)
    def _init():
        out_ref[...] = state_ref[...]

    for r in range(s_tile):
        v = jnp.where(hit_m & (syn == s * s_tile + r), upd, 0)
        out_ref[r:r + 1, :] = jnp.maximum(
            out_ref[r:r + 1, :], jnp.max(v, axis=0, keepdims=True))


@functools.partial(jax.jit, static_argnames=("s_tile", "m_tile", "t_tile",
                                             "interpret"))
def max_scatter_update(state: jax.Array, syn_idx: jax.Array, pos: jax.Array,
                       upd: jax.Array, *, s_tile: int = 8,
                       m_tile: int = 512, t_tile: int = 256,
                       interpret: bool = True) -> jax.Array:
    """state [n, m] i32 = max-scatter of T tuples. syn_idx [T, 1] i32 (-1
    matches no row), pos [T, k] i32 positions, upd [T, 1] i32 (0 = no-op;
    state lanes are >= 0). All dims must be tile multiples (ops.py
    pads)."""
    n, m = state.shape
    t_total, k = pos.shape
    grid = (n // s_tile, m // m_tile, t_total // t_tile)
    return pl.pallas_call(
        functools.partial(_kernel, s_tile=s_tile, m_tile=m_tile, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_tile, 1), lambda s, m_, t: (t, 0)),
            pl.BlockSpec((t_tile, k), lambda s, m_, t: (t, 0)),
            pl.BlockSpec((t_tile, 1), lambda s, m_, t: (t, 0)),
            pl.BlockSpec((s_tile, m_tile), lambda s, m_, t: (s, m_)),
        ],
        out_specs=pl.BlockSpec((s_tile, m_tile), lambda s, m_, t: (s, m_)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.int32),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(syn_idx, pos, upd, state)
