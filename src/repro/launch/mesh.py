"""Production mesh construction (a FUNCTION — importing this module never
touches jax device state).

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the pod axis
carries data parallelism + federated synopsis merges over DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.configs.base import ModelConfig
from repro.sharding.specs import MeshRules


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """A device mesh whose axes are all ``AxisType.Auto`` — every mesh of
    this repo comes from here. ``jax.make_mesh`` defaults to Explicit
    axes, under which the row-sharded scatter of the blue path cannot
    resolve its output sharding (``SDE`` refuses such a mesh)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_federation_mesh(n_sites: int | None = None, *, devices=None):
    """1-D mesh whose single ``site`` axis carries the federation's
    synopsis merges: one lead device per geo-dispersed site, the axis
    playing the role of the DCN links between clusters. Pass the result
    to ``Federation(mesh=...)`` — each site's SDE state is pinned to its
    slice and ``federated.merge_over_axis`` runs over the axis. On a
    production multi-pod mesh, hand ``make_production_mesh(multi_pod=
    True)`` to ``Federation`` instead: the ``pod`` axis plays the site
    role and the federation takes one lead device per pod."""
    devs = list(devices) if devices is not None else jax.devices()
    n = n_sites if n_sites is not None else len(devs)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"federation mesh needs one device per site: asked for {n} "
            f"sites, have {len(devs)} devices")
    return make_mesh((n,), ("site",), devices=devs[:n])


def try_federation_mesh(n_sites: int, *, devices=None):
    """``make_federation_mesh`` when the host has a device per site, else
    None — the one-liner demos/benchmarks use to fall back to the
    host-merge federation on single-device machines."""
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n_sites:
        return None
    return make_federation_mesh(n_sites, devices=devs)


def make_debug_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (tests)."""
    n = n_devices or len(jax.devices())
    if n >= 4:
        return make_mesh((n // 2, 2), ("data", "model"))
    return make_mesh((n, 1), ("data", "model"))


def rules_for(cfg: ModelConfig, *, mode: str = "train") -> MeshRules:
    """Per-architecture sharding rules (see DESIGN.md and configs)."""
    if not cfg.tensor_parallel and mode == "train":
        # small models, TRAIN: batch over (data, model) — 256-way DP
        # inside a pod, plus pure cross-pod DP on the pod axis; weights
        # FSDP over "model"; zero TP collectives. Serving keeps TP rules
        # (the 32k KV caches need the model axis; §Perf iterations).
        return MeshRules(
            batch=("data", "model"),
            fsdp="model", tensor=None, expert=None, seq=None,
            kv_seq="model",
        )
    fsdp = "data" if cfg.dense_fsdp else None
    if mode in ("prefill", "decode"):
        # serving: re-gathering FSDP weights every decoded token dominates
        # the step. Replicate over the data axis whenever the TP shard of
        # the NON-expert weights fits HBM (expert stacks are managed
        # separately: EP-resident decode or shard_map FSDP).
        if cfg.dense_param_count() * 2 / 16 < 12e9:
            fsdp = None
    return MeshRules(
        batch=("pod", "data"),
        fsdp=fsdp,
        tensor="model",
        expert=cfg.expert_axis,
        seq=("model" if (cfg.seq_shard_activations and mode == "train")
             else None),
        kv_seq="model",
    )
