"""Sketch scatter-add as a dense one-hot matmul on the MXU.

THE central TPU adaptation of the paper's hot loop. CPU/Flink (and GPU)
update a CountMin/AMS sketch with scatter-adds; TPUs hate scatter but love
dense matmuls. A block of T updates routed to a stack of sketches becomes,
for each hash row j,

    counts[syn, j, w] += sum_t (syn_t == syn) * v_t * s_tj * (idx_tj == w)
                       =        A_j @ B_j^T
    A_j[syn, t] = (syn_t == syn) * v_t * s_tj       (one-hot rows, weighted)
    B_j[w, t]   = (idx_tj == w)                     (one-hot buckets)

i.e. an [S_tile x T_tile] x [T_tile x W_tile] matmul per hash row per grid
cell — MXU work, zero scatter. The same kernel serves CountMin (sign == 1)
and AMS/count-sketch (sign == ±1), and the stacked thousands-of-synopses
path (paper's slot sharing) for free via the `syn` one-hot.

Layout (Mosaic's (8, 128) block rule): per-tuple operands ride with T on
the lane axis — ``syn``/``values`` as [1, T], ``idx``/``signs`` as [d, T]
— and the state block spans the whole hash-row axis, (S_tile, d, W_tile).
Grid: (S_tiles, W_tiles, T_tiles); T is innermost so each output tile is
revisited consecutively and accumulated in VMEM, starting from the state
block (the state operand is aliased to the output: in place). The matmul
runs at ``Precision.HIGHEST`` so weighted f32 updates do not round through
bf16 — integer-valued weights stay byte-equal to the XLA scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(syn_ref, idx_ref, val_ref, sgn_ref, cnt_ref, out_ref, *,
            s_tile, w_tile, t_tile, depth):
    s = pl.program_id(0)
    w_ = pl.program_id(1)
    t = pl.program_id(2)

    s_ids = s * s_tile + jax.lax.broadcasted_iota(
        jnp.int32, (s_tile, t_tile), 0)
    w_ids = w_ * w_tile + jax.lax.broadcasted_iota(
        jnp.int32, (w_tile, t_tile), 0)
    hit = s_ids == syn_ref[...]                          # [S_t, T_t]
    val = val_ref[...]                                   # [1, T_t]

    @pl.when(t == 0)
    def _init():
        out_ref[...] = cnt_ref[...]

    for j in range(depth):
        a = jnp.where(hit, val * sgn_ref[j:j + 1, :], 0.0)          # [S_t, T_t]
        b = (w_ids == idx_ref[j:j + 1, :]).astype(jnp.float32)    # [W_t, T_t]
        out_ref[:, j, :] += jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                   # [S_t, W_t]


@functools.partial(jax.jit, static_argnames=("s_tile", "w_tile", "t_tile",
                                             "interpret"))
def onehot_scatter_add(counts: jax.Array, syn_idx: jax.Array,
                       idx: jax.Array, values: jax.Array,
                       signs: jax.Array, *, s_tile: int = 128,
                       w_tile: int = 256, t_tile: int = 512,
                       interpret: bool = True) -> jax.Array:
    """counts [n, d, w] += one-hot scatter of T updates. All dims must be
    multiples of their tiles (ops.py pads).

    syn_idx [1, T] i32 (-1 matches no row), idx [d, T] i32, values [1, T]
    f32 (mask folded in), signs [d, T] f32. Updates ``counts`` in place.
    """
    n, d, w = counts.shape
    t_total = syn_idx.shape[1]
    grid = (n // s_tile, w // w_tile, t_total // t_tile)
    return pl.pallas_call(
        functools.partial(_kernel, s_tile=s_tile, w_tile=w_tile,
                          t_tile=t_tile, depth=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_tile), lambda s, w_, t: (0, t)),
            pl.BlockSpec((d, t_tile), lambda s, w_, t: (0, t)),
            pl.BlockSpec((1, t_tile), lambda s, w_, t: (0, t)),
            pl.BlockSpec((d, t_tile), lambda s, w_, t: (0, t)),
            pl.BlockSpec((s_tile, d, w_tile), lambda s, w_, t: (s, 0, w_)),
        ],
        out_specs=pl.BlockSpec((s_tile, d, w_tile),
                               lambda s, w_, t: (s, 0, w_)),
        out_shape=jax.ShapeDtypeStruct((n, d, w), jnp.float32),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(syn_idx, idx, values, signs, counts)
