"""Public jit'd wrappers around the Pallas kernels + the update-kernel
registry.

These are the entry points the engine uses. Each wrapper:
  * does the hashing / layout prep in plain jnp (cheap, fusable),
  * pads every dimension to its kernel tile,
  * runs the kernel compiled by Mosaic on a TPU backend and in interpret
    mode elsewhere, so the kernels VALIDATE on CPU (see ``_interpret``),
  * exposes the same signature as the core/ scatter path so the engine
    can flip between `backend="xla"` and `backend="pallas"`.

Kernel dispatch is a REGISTRY, not a type ladder: a kind declares
``update_kernel = "<name>"`` and :func:`resolve_update_kernel` returns the
matching builder's update function, with the signature of
``batched.stacked_update`` minus the kind. Kinds without a declaration
fall back to ``batched.stacked_update`` in the engine.
"""
from __future__ import annotations

import logging
import os
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import batched, federated, hashing
# the counters this module's code bumps; ``GATEWAY_TICKS`` is re-exported
# too, under the name benchmarks read it by (``kops.GATEWAY_TICKS``)
from repro.tracing import (DISPATCH_COUNT, GATEWAY_TICKS,  # noqa: F401
                           KERNEL_CACHE_SIZE, TRACE_COUNT, named)
from . import (max_scatter, onehot_matmul, rhp_project, sliding_dft,
               pairwise_corr as pc)

_logger = logging.getLogger("repro.kernels")

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")
_interpret_logged = False


def _interpret() -> bool:
    """Pallas interpret mode: never on a TPU backend, where every kernel
    is compiled by Mosaic (an explicit ``SDE_PALLAS_INTERPRET=1`` there
    raises); on by default elsewhere, so the kernels validate on CPU.
    ``SDE_PALLAS_INTERPRET=0`` off-TPU forces Mosaic lowering — how a CPU
    process compiles the kernels for a described TPU. The chosen mode is
    logged once per process. Read at trace time — flipping the env var
    mid-session only affects programs not yet traced."""
    global _interpret_logged
    raw = os.environ.get("SDE_PALLAS_INTERPRET", "").strip().lower()
    backend = jax.default_backend()
    if raw and raw not in _TRUTHY + _FALSY:
        raise ValueError(
            f"SDE_PALLAS_INTERPRET={raw!r} not understood — use one of "
            f"{_TRUTHY + _FALSY} or unset for auto")
    if backend == "tpu" and raw in _TRUTHY:
        raise ValueError(
            f"SDE_PALLAS_INTERPRET={raw} on a TPU backend: the kernels "
            "run compiled there, never interpreted — unset it")
    if raw:
        mode, why = raw in _TRUTHY, f"SDE_PALLAS_INTERPRET={raw}"
    else:
        mode, why = backend != "tpu", f"auto (jax backend: {backend})"
    if not _interpret_logged:
        _logger.info("pallas interpret mode: %s [%s]", mode, why)
        _interpret_logged = True
    return mode


def _pad_to(x: jax.Array, mult: int, axis: int = 0, value=0):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# ---------------------------------------------------------------------------
# blue path: hashed stream routing. The engine keeps each kind stack's
# stream->row map in an open-addressing hash table (service/routing.py
# owns the host-side inserts); this is the device half — a vectorized
# fixed-bound linear probe traced INSIDE the fused update programs, so
# routing arbitrary 63-bit stream ids still costs zero extra dispatches.
# ---------------------------------------------------------------------------

# numpy scalars: a bare python int overflows jit's weak-int32 parsing
_ROUTE_GOLDEN = np.uint32(0x9E3779B9)
_EMPTY_HI = np.uint32(0xFFFFFFFF)   # hi half of an empty slot; valid ids
                                    # < 2**63 have hi <= 2**31-1


def route_probe(keys_lo: jax.Array, keys_hi: jax.Array, rows: jax.Array,
                sid_lo: jax.Array, sid_hi: jax.Array, *,
                n_probe: int) -> jax.Array:
    """Rows for a batch of stream ids via linear probing: ``-1`` for
    unrouted ids. Keys are stored as uint32 (lo, hi) halves so the probe
    needs no 64-bit lanes; ``n_probe`` is the static trip count (the
    table's longest insert displacement, pow2-rounded by the engine so
    retraces stay bounded). The probe is a ``fori_loop`` gather chain —
    plain jnp, fusable into the caller's single blue-path dispatch. The
    slot hash must stay in lockstep with ``service.routing.slot_hash``.
    """
    size_mask = jnp.int32(keys_lo.shape[0] - 1)
    sid_lo = sid_lo.astype(jnp.uint32)
    sid_hi = sid_hi.astype(jnp.uint32)
    h = hashing.mix32(sid_lo ^ hashing.mix32(sid_hi ^ _ROUTE_GOLDEN))
    slot0 = (h & jnp.uint32(keys_lo.shape[0] - 1)).astype(jnp.int32)

    def body(_, carry):
        row, slot, done = carry
        k_hi = keys_hi[slot]
        hit = (keys_lo[slot] == sid_lo) & (k_hi == sid_hi)
        empty = k_hi == _EMPTY_HI
        row = jnp.where(hit & ~done, rows[slot], row)
        done = done | hit | empty
        slot = jnp.where(done, slot, (slot + 1) & size_mask)
        return row, slot, done

    row0 = jnp.full(sid_lo.shape, -1, jnp.int32)
    done0 = jnp.zeros(sid_lo.shape, bool)
    row, _, _ = jax.lax.fori_loop(0, n_probe, body, (row0, slot0, done0))
    return row


def _source_fold(out: jax.Array, idx: jax.Array, contrib: jax.Array,
                 source_rows: jax.Array) -> jax.Array:
    """Add a fresh single sketch into the data-source rows: out [n, d, w]
    indexed at source_rows += scatter(contrib at idx). Both CM and AMS
    merges are linear, so adding the batch's fresh sketch is exact; work
    is proportional to the number of source rows, not capacity."""
    n, d, w = out.shape
    rows = jnp.arange(d)[None, :]
    fresh = jnp.zeros((d, w), jnp.float32).at[rows, idx].add(contrib)
    return out.at[source_rows].add(fresh[None])


# ---------------------------------------------------------------------------
# red path: cached stacked-estimate dispatch (mirrors the engine's _update
# cache). ONE jitted program per (kind, out-sharding) answers any batch of
# ad-hoc/continuous queries against that kind's stack: per-row estimates are
# computed where the rows live (the [capacity] axis stays `synopsis`-sharded
# inside the program, so no state gather crosses the mesh) and only the tiny
# estimate vectors are replicated to the host via ``out_shardings``. Each
# program is named by its kind (``estimate_all_CountMin``, ...), so a device
# trace says which kind it answered. Its counters live in ``repro.tracing``.
# ---------------------------------------------------------------------------


_KIND_CACHES: list["KindCache"] = []


class KindCache:
    """Bounded replacement for the old ``lru_cache(maxsize=None)`` jit
    caches: a dict keyed by tuples whose FIRST element is the kind
    instance, so an engine can evict every compiled program belonging to
    a kind it stops serving. Size is exported via ``KERNEL_CACHE_SIZE``
    (one gauge per cache name)."""

    def __init__(self, name: str):
        self.name = name
        self._entries: Dict[tuple, Any] = {}
        _KIND_CACHES.append(self)

    def get(self, key: tuple, build: Callable[[], Any]) -> Any:
        try:
            return self._entries[key]
        except KeyError:
            pass
        fn = self._entries[key] = build()
        KERNEL_CACHE_SIZE[self.name] = len(self._entries)
        return fn

    def evict_kind(self, kind) -> int:
        dead = [k for k in self._entries if k[0] == kind]
        for k in dead:
            del self._entries[k]
        if dead:
            KERNEL_CACHE_SIZE[self.name] = len(self._entries)
        return len(dead)

    def clear(self) -> int:
        n = len(self._entries)
        self._entries.clear()
        KERNEL_CACHE_SIZE[self.name] = 0
        return n


def evict_kind_caches(kind) -> int:
    """Drop every cached compiled program keyed to ``kind`` across all
    registered caches (estimate + engine update/step). Returns the number
    of entries evicted. Value-equal kind instances share entries, so this
    only forgets programs no OTHER engine could be sharing once the kind
    is value-unique to the evicting engine — eviction is a recompile-cost
    policy, never a correctness concern."""
    return sum(c.evict_kind(kind) for c in _KIND_CACHES)


def kernel_cache_size() -> int:
    """Total compiled entries across all kind caches (== the sum of the
    ``KERNEL_CACHE_SIZE`` gauges)."""
    return sum(len(c._entries) for c in _KIND_CACHES)


_ESTIMATE_ALL = KindCache("estimate_all")
_ESTIMATE_MERGED = KindCache("estimate_merged")
_ESTIMATE_COLLECTIVE = KindCache("estimate_collective")
_ESTIMATE_SUBPOP = KindCache("estimate_subpop")


def _estimate_all_fn(kind, out_sharding):
    name = type(kind).__name__

    def build():
        def program(state, rows, *query_args):
            TRACE_COUNT[name] += 1      # runs only when jit (re)traces
            return batched.stacked_estimate(kind, state, rows, *query_args)

        kw = {}
        if out_sharding is not None:
            kw["out_shardings"] = out_sharding
        return jax.jit(named(program, f"estimate_all_{name}"), **kw)

    return _ESTIMATE_ALL.get((kind, out_sharding), build)


def estimate_all(kind, state, rows: jax.Array, *query_args,
                 out_sharding=None):
    """Batched red-path entry point: estimates for ``rows`` of ``state``
    with per-query args (leading axis == rows) in ONE jitted dispatch.

    ``out_sharding`` replicates the (small) estimate outputs when the stack
    is `synopsis`-sharded over a mesh; pass None off-mesh.
    """
    DISPATCH_COUNT[type(kind).__name__] += 1
    return _estimate_all_fn(kind, out_sharding)(state, rows, *query_args)


def _estimate_merged_fn(kind):
    name = type(kind).__name__

    def build():
        def program(states, *query_args):
            TRACE_COUNT[name] += 1
            merged = federated.merge_reduce(kind, states)
            one = jax.tree.map(lambda x: x[None], merged)
            return batched.stacked_estimate(
                kind, one, jnp.zeros((1,), jnp.int32), *query_args)

        return jax.jit(named(program, f"estimate_merged_{name}"))

    return _ESTIMATE_MERGED.get((kind,), build)


def _estimate_subpop_fn(kind, n_rows, out_sharding):
    name = type(kind).__name__

    def build():
        def program(state, rows, *query_args):
            TRACE_COUNT[name] += 1
            sub = jax.tree.map(lambda x: x[rows], state)
            merged = federated.merge_reduce(kind, sub)
            one = jax.tree.map(lambda x: x[None], merged)
            return batched.stacked_estimate(
                kind, one, jnp.zeros((1,), jnp.int32), *query_args)

        kw = {}
        if out_sharding is not None:
            kw["out_shardings"] = out_sharding
        return jax.jit(named(program, f"estimate_subpop_{name}"), **kw)

    return _ESTIMATE_SUBPOP.get((kind, n_rows, out_sharding), build)


def estimate_subpop(kind, state, rows: jax.Array, *query_args,
                    out_sharding=None):
    """Subpopulation red path: gather a covering set of ``rows`` from a
    kind's stack, tree-merge them (``federated.merge_reduce``) and
    estimate the merged synopsis — ONE jitted dispatch, the
    ``subpop_query`` analog of ``estimate_merged``. Returns a leading
    [1] query axis. The covering set is NOT padded — padding would
    double-count sum-merge kinds — so the program retraces per distinct
    covering-set size (bounded by the distinct predicate shapes a
    workload issues; the gauge is ``KERNEL_CACHE_SIZE['estimate_subpop']``).
    """
    DISPATCH_COUNT[type(kind).__name__] += 1
    return _estimate_subpop_fn(kind, int(rows.shape[0]), out_sharding)(
        state, rows, *query_args)


def estimate_merged(kind, states_stacked, *query_args):
    """Federated red path: tree-merge a [S, ...] stack of per-site partial
    states and estimate the result, fused into ONE jitted dispatch (the
    responsible-site synthesis of paper Case 2/3). Returns a leading [1]
    query axis like ``estimate_all`` with a single row."""
    DISPATCH_COUNT[type(kind).__name__] += 1
    return _estimate_merged_fn(kind)(states_stacked, *query_args)


def _estimate_collective_fn(kind, mesh, axis_name):
    name = type(kind).__name__

    def build():
        def program(states, *query_args):
            TRACE_COUNT[name] += 1

            def shard_fn(shard, *qargs):
                local = jax.tree.map(lambda x: jnp.squeeze(x, 0), shard)
                merged = federated.merge_over_axis(kind, local, axis_name)
                one = jax.tree.map(lambda x: x[None], merged)
                return batched.stacked_estimate(
                    kind, one, jnp.zeros((1,), jnp.int32), *qargs)

            fn = jax.shard_map(shard_fn, mesh=mesh,
                            in_specs=(P(axis_name),) + (P(),) * len(
                                query_args),
                            out_specs=P(), check_vma=False)
            return fn(states, *query_args)

        return jax.jit(named(program, f"estimate_collective_{name}"))

    return _ESTIMATE_COLLECTIVE.get((kind, mesh, axis_name), build)


def estimate_collective(kind, states_stacked, *query_args, mesh, axis_name):
    """Federated red path as a REAL collective (paper Case 2/3 over DCN):
    ``states_stacked`` is a [S, ...] pytree SHARDED over ``axis_name`` —
    shard s is site s's local partial state, resident on site s's device —
    and the merge runs INSIDE the compiled program
    (``federated.merge_over_axis``: psum/pmax/all_gather over the site
    axis), with the stacked estimate executed on the merged result. One
    jitted dispatch, no host gather; the per-shard merge result is
    identical on every site, so the replicated output IS the responsible
    site's answer. Output layout matches ``estimate_merged`` (leading [1]
    query axis); the same TRACE_COUNT/DISPATCH_COUNT probes apply."""
    DISPATCH_COUNT[type(kind).__name__] += 1
    return _estimate_collective_fn(kind, mesh, axis_name)(
        states_stacked, *query_args)


# ---------------------------------------------------------------------------
# blue path: per-kind update wrappers. Every wrapper takes routed rows
# (``syn_idx``, -1 = drop) — the engine runs ``route_probe`` ahead of the
# kernel inside the same jitted dispatch. ``source_rows`` indexes
# data-source rows fed by every unmasked tuple: their delta is one fresh
# single sketch of the batch merged into just those rows (O(source rows),
# not O(capacity)), fused into the same dispatch.
# ---------------------------------------------------------------------------


def countmin_update(counts: jax.Array, syn_idx: jax.Array, items: jax.Array,
                    values: jax.Array, mask: jax.Array, *, seeds: jax.Array,
                    log2_width: int, weighted: bool = True,
                    source_rows: jax.Array | None = None) -> jax.Array:
    """Pallas-backed stacked CountMin update. counts [n, d, w]."""
    idx = hashing.bucket_hash(items, seeds, log2_width)
    v = values if weighted else jnp.ones_like(values)
    vm = v * mask.astype(jnp.float32)
    out = _scatter_call(counts, syn_idx, idx, vm, jnp.ones(idx.shape,
                                                           jnp.float32))
    if source_rows is not None:
        out = _source_fold(out, idx, jnp.broadcast_to(vm[:, None], idx.shape),
                           source_rows)
    return out


def ams_update(counts: jax.Array, syn_idx: jax.Array, items: jax.Array,
               values: jax.Array, mask: jax.Array, *, seeds: jax.Array,
               log2_width: int,
               source_rows: jax.Array | None = None) -> jax.Array:
    """Pallas-backed stacked AMS/count-sketch update. counts [n, d, w]."""
    idx = hashing.bucket_hash(items, seeds, log2_width)
    sgn = hashing.sign_hash(items, seeds)
    v = values * mask.astype(jnp.float32)
    out = _scatter_call(counts, syn_idx, idx, v, sgn)
    if source_rows is not None:
        out = _source_fold(out, idx, v[:, None] * sgn, source_rows)
    return out


def _scatter_call(counts, syn_idx, idx, values, signs):
    """counts [n, d, w] += one-hot scatter; idx/signs [T, d]."""
    n, d, w = counts.shape
    t_tile = 512
    s_tile = min(128, _round_up(n, 8))
    w_tile = min(256, w)
    # padded tuples get syn -1 / value 0 -> match nothing
    syn = _pad_to(syn_idx.astype(jnp.int32), t_tile, value=-1)[None, :]
    idx = _pad_to(idx.astype(jnp.int32), t_tile, value=-1).T
    values = _pad_to(values.astype(jnp.float32), t_tile)[None, :]
    signs = _pad_to(signs.astype(jnp.float32), t_tile).T
    padded = jnp.pad(counts, ((0, (-n) % s_tile), (0, 0), (0, (-w) % w_tile)))
    out = onehot_matmul.onehot_scatter_add(
        padded, syn, idx, values, signs, s_tile=s_tile, w_tile=w_tile,
        t_tile=t_tile, interpret=_interpret())
    return out[:n, :, :w]


def hll_update(regs: jax.Array, syn_idx: jax.Array, items: jax.Array,
               mask: jax.Array, *, seed: int, p: int,
               source_rows: jax.Array | None = None) -> jax.Array:
    """Pallas-backed stacked HLL update. regs [n, m]. Data-source rows
    take an elementwise max with a fresh single-HLL of the batch."""
    bucket, raw_rank = _hll_prep(items, seed, p)
    rank = jnp.where(mask, raw_rank, 0).astype(jnp.int32)
    out = _max_call(regs, syn_idx, bucket[:, None], rank)
    if source_rows is not None:
        fresh = jnp.zeros((regs.shape[1],), jnp.int32).at[bucket].max(rank)
        out = out.at[source_rows].max(fresh[None, :])
    return out


def _hll_prep(items, seed: int, p: int):
    h = hashing.hash_u32(items, seed)
    bucket = (h >> np.uint32(32 - p)).astype(jnp.int32)
    rest = (h << np.uint32(p)).astype(jnp.uint32)
    raw_rank = jnp.where(rest == 0, 32 - p + 1, hashing.clz32(rest) + 1)
    return bucket, raw_rank


def bloom_update(bits: jax.Array, syn_idx: jax.Array, items: jax.Array,
                 mask: jax.Array, *, seeds: jax.Array, log2_bits: int,
                 source_rows: jax.Array | None = None) -> jax.Array:
    """Pallas-backed stacked Bloom update. bits [n, m] int32 0/1; each
    tuple ORs its k hash positions into its routed row. Data-source rows
    take the OR (== max) of a fresh single-filter of the batch."""
    idx = hashing.bucket_hash(items, seeds, log2_bits)          # [T, k]
    upd = mask.astype(jnp.int32)
    out = _max_call(bits, syn_idx, idx, upd)
    if source_rows is not None:
        u = jnp.broadcast_to(upd[:, None], idx.shape)
        fresh = jnp.zeros((bits.shape[1],), jnp.int32).at[idx].max(u)
        out = out.at[source_rows].max(fresh[None])
    return out


def fm_update(state: jax.Array, syn_idx: jax.Array, which: jax.Array,
              pos: jax.Array, mask: jax.Array, *,
              source_rows: jax.Array | None = None) -> jax.Array:
    """Pallas-backed stacked FM/PCSA update. state [n, maps, bits] int32
    0/1; each tuple sets bit (which, pos) of its routed row — one
    position of the row-major flattened [maps * bits] plane. The caller
    provides (which, pos) from the kind's hash split (``FMSketch
    ._which_pos``)."""
    n, maps, nbits = state.shape
    upd = mask.astype(jnp.int32)
    flat_pos = (which * nbits + pos).astype(jnp.int32)
    out = _max_call(state.reshape(n, maps * nbits), syn_idx,
                    flat_pos[:, None], upd).reshape(state.shape)
    if source_rows is not None:
        fresh = jnp.zeros((maps, nbits), jnp.int32).at[which, pos].max(upd)
        out = out.at[source_rows].max(fresh[None])
    return out


def _max_call(state, syn_idx, pos, upd):
    """state [n, m] i32 max-scatter; pos [T, k] positions, upd [T]."""
    n, m = state.shape
    t_tile = 256
    s_tile = 8
    m_tile = m if m <= 512 else 512
    syn = _pad_to(syn_idx.astype(jnp.int32), t_tile, value=-1)[:, None]
    pos = _pad_to(pos.astype(jnp.int32), t_tile, value=-1)
    upd = _pad_to(upd.astype(jnp.int32), t_tile)[:, None]  # pad 0 => no-op
    padded = jnp.pad(state, ((0, (-n) % s_tile), (0, (-m) % m_tile)))
    out = max_scatter.max_scatter_update(
        padded, syn, pos, upd, s_tile=s_tile, m_tile=m_tile, t_tile=t_tile,
        interpret=_interpret())
    return out[:n, :m]


def rhp_update(state: jax.Array, syn_idx: jax.Array, items: jax.Array,
               values: jax.Array, mask: jax.Array, *, seeds: jax.Array,
               source_rows: jax.Array | None = None) -> jax.Array:
    """Pallas-backed stacked RHP/SimHash update. state [n, b] f32; each
    tuple adds ``v * sign_row`` into its routed row (dense — a matmul).
    Data-source rows add the batch's summed projection (linear merge)."""
    sgn = hashing.sign_hash(items, seeds)                       # [T, b]
    v = values * mask.astype(jnp.float32)
    out = _rhp_call(state, syn_idx, v, sgn)
    if source_rows is not None:
        out = out.at[source_rows].add(jnp.sum(sgn * v[:, None], axis=0)[None])
    return out


def _rhp_call(state, syn_idx, values, signs):
    n, b = state.shape
    t_tile = 512
    s_tile = min(128, _round_up(n, 8))
    b_tile = min(128, b)
    syn = _pad_to(syn_idx.astype(jnp.int32), t_tile, value=-1)[None, :]
    values = _pad_to(values.astype(jnp.float32), t_tile)[None, :]
    signs = _pad_to(signs.astype(jnp.float32), t_tile)
    b_pad = (-b) % b_tile
    signs = jnp.pad(signs, ((0, 0), (0, b_pad)))
    padded = jnp.pad(state, ((0, (-n) % s_tile), (0, b_pad)))
    out = rhp_project.rhp_project_update(
        padded, syn, values, signs, s_tile=s_tile, b_tile=b_tile,
        t_tile=t_tile, interpret=_interpret())
    return out[:n, :b]


# ---------------------------------------------------------------------------
# the update-kernel registry. A kind opts into the Pallas blue path by
# declaring ``update_kernel = "<name>"``; the engine resolves the name here
# at dispatch time — no isinstance ladder anywhere. Every registered
# builder returns an update fn with the signature of
# ``batched.stacked_update`` minus the kind:
#
#     fn(state, syn_idx, items, values, mask, source_rows) -> state'
#
# where ``syn_idx`` holds routed rows (-1 = unrouted) and ``source_rows``
# may be None.
# ---------------------------------------------------------------------------

UPDATE_KERNELS: Dict[str, Callable] = {}


def register_update_kernel(name: str, builder: Callable, *,
                           overwrite: bool = False) -> None:
    """Register ``builder(kind) -> update_fn`` under ``name``. Kinds
    reference kernels by name (``update_kernel = name``), so plugged
    kinds can reuse a stock kernel or bring their own without the engine
    learning any new types."""
    if name in UPDATE_KERNELS and not overwrite:
        raise ValueError(f"update kernel {name!r} already registered "
                         "(pass overwrite=True to replace)")
    UPDATE_KERNELS[name] = builder


def resolve_update_kernel(kind):
    """The registry lookup the engine dispatches through: returns the
    kind's built update fn, or None when the kind declares no
    ``update_kernel`` (engine falls back to ``batched.stacked_update``)."""
    name = getattr(kind, "update_kernel", None)
    if name is None:
        return None
    builder = UPDATE_KERNELS.get(name)
    if builder is None:
        raise KeyError(
            f"{type(kind).__name__} declares update_kernel={name!r} but no "
            f"such kernel is registered — register_update_kernel({name!r}, "
            "builder) first, or drop the declaration to use the XLA "
            "fallback")
    return builder(kind)


register_update_kernel("countmin_scatter", lambda kind: (
    lambda st, syn, it, v, m, src: countmin_update(
        st, syn, it, v, m, seeds=kind._seeds(), log2_width=kind.log2_width,
        weighted=kind.weighted, source_rows=src)))
register_update_kernel("ams_scatter", lambda kind: (
    lambda st, syn, it, v, m, src: ams_update(
        st, syn, it, v, m, seeds=kind._seeds(), log2_width=kind.log2_width,
        source_rows=src)))
register_update_kernel("hll_max", lambda kind: (
    lambda st, syn, it, v, m, src: hll_update(
        st, syn, it, m, seed=kind.seed, p=kind.p, source_rows=src)))
register_update_kernel("bloom_bitset", lambda kind: (
    lambda st, syn, it, v, m, src: bloom_update(
        st, syn, it, m, seeds=kind._seeds(), log2_bits=kind.log2_bits,
        source_rows=src)))
register_update_kernel("fm_bitmap", lambda kind: (
    lambda st, syn, it, v, m, src: fm_update(
        st, syn, *kind._which_pos(it), m, source_rows=src)))
register_update_kernel("rhp_project", lambda kind: (
    lambda st, syn, it, v, m, src: rhp_update(
        st, syn, it, v, m, seeds=kind._seeds(), source_rows=src)))


def dft_step(re: jax.Array, im: jax.Array, delta: jax.Array,
             mask: jax.Array, tw_re: jax.Array, tw_im: jax.Array):
    """Pallas-backed batched sliding-DFT tick. re/im [S, F]."""
    s, f = re.shape
    s_tile = 512 if s % 512 == 0 else (s if s <= 512 else 128)
    pad = (-s) % s_tile
    if pad:
        re = jnp.pad(re, ((0, pad), (0, 0)))
        im = jnp.pad(im, ((0, pad), (0, 0)))
        delta = jnp.pad(delta, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    out_re, out_im = sliding_dft.sliding_dft_step(
        re, im, delta.astype(jnp.float32), mask.astype(jnp.float32),
        tw_re, tw_im, s_tile=s_tile, interpret=_interpret())
    return out_re[:s], out_im[:s]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 128,
                    bk: int = 128) -> jax.Array:
    """Streaming-softmax attention, O(S) HBM. q/k/v [BH, S, D]; pads S
    to block multiples (padded keys are masked by the causal/neg-inf
    path: padded QUERIES produce garbage rows which are sliced off)."""
    from . import flash_attention as fa
    bh, sq, d = q.shape
    sk = k.shape[1]
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        # padded keys get -inf via causal mask only when causal; for
        # non-causal, pad keys with -inf-producing zeros is unsafe ->
        # require divisibility there
        assert causal or pk == 0, "non-causal needs Sk % bk == 0"
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    out = fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                             interpret=_interpret())
    return out[:, :sq]


def corr_matrix(coeffs: jax.Array, *, tile: int = 256) -> jax.Array:
    """Pairwise correlation estimates from [N, F, 2] or [N, K] coeffs."""
    x = coeffs.reshape(coeffs.shape[0], -1).astype(jnp.float32)
    n, k = x.shape
    t = min(tile, n)
    n_pad = (-n) % t
    k_pad = (-k) % 128                    # MXU lane alignment
    x = jnp.pad(x, ((0, n_pad), (0, k_pad)))
    out = pc.pairwise_corr(x, i_tile=t, j_tile=t, interpret=_interpret())
    return out[:n, :n]
