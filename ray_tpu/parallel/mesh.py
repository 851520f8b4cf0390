"""Device mesh construction and logical-axis sharding rules.

This is the TPU-native replacement for the reference's process-group plumbing
(`python/ray/util/collective/collective.py`, `python/ray/train/v2/jax/config.py`):
instead of wiring NCCL communicators between actors, we build a single
`jax.sharding.Mesh` over all chips and express every parallelism strategy
(dp/fsdp/sp/tp/ep/pp) as named mesh axes. XLA inserts the ICI/DCN collectives.

Axis order is slowest-varying first so that DCN-crossing axes (dp, pp) get the
outermost mesh dimensions and ICI-local axes (tp) the innermost, matching the
physical topology (tp traffic must ride ICI; dp allreduces tolerate DCN).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Mapping, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.utils.platform import watch_compiles

P = PartitionSpec

# Canonical mesh axis names, outermost (DCN-tolerant) to innermost (ICI-only).
MESH_AXES = ("pp", "dp", "fsdp", "sp", "ep", "tp")

# Hierarchical data-parallel sub-axes: the dp axis expressed as
# (slow-fabric hosts) x (fast-fabric local devices), so a compiled train
# step can emit reduce-scatter/all-gather over `dp_intra` (ICI) and keep
# the `dp_inter` (DCN) hop shard-sized — the two-level schedule INSIDE
# the program instead of staged in Python (util/collective/hierarchy.py).
DP_SUB_AXES = ("dp_inter", "dp_intra")
HIER_MESH_AXES = ("pp", "dp_inter", "dp_intra", "fsdp", "sp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Degree of each parallelism axis. Product must equal the device count.

    Any axis left at -1 is inferred to absorb the remaining devices (at most
    one axis may be -1).
    """

    pp: int = 1    # pipeline stages
    dp: int = 1    # pure data parallel (gradients allreduced)
    fsdp: int = 1  # data parallel with parameters sharded (ZeRO-3 style)
    sp: int = 1    # sequence/context parallel (ring attention axis)
    ep: int = 1    # expert parallel (MoE)
    tp: int = 1    # tensor (megatron) parallel

    def degrees(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in MESH_AXES}

    def resolved(self, n_devices: int) -> "MeshConfig":
        d = self.degrees()
        unknown = [a for a, v in d.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one axis may be -1, got {unknown}")
        known = math.prod(v for v in d.values() if v != -1)
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {known}")
            d[unknown[0]] = n_devices // known
        if math.prod(d.values()) != n_devices:
            raise ValueError(
                f"mesh {d} has {math.prod(d.values())} slots but {n_devices} devices")
        return MeshConfig(**d)


def adaptive_mesh_config(
    requested: Union[MeshConfig, Mapping[str, int]],
    n_devices: int,
    shrink_axes: Sequence[str] = ("dp", "fsdp"),
) -> MeshConfig:
    """Fit `requested` to what `n_devices` can actually hold.

    Elastic-training companion to `MeshConfig.resolved`: instead of
    erroring when the device count no longer matches (a worker or host
    was lost mid-run), shrink the `shrink_axes` — outermost data axes
    first, the ones whose degree is a pure throughput knob — toward 1
    until the mesh fits, and grow them back (up to the requested degree)
    when capacity returns. Model-parallel axes (tp/pp/ep/sp) are never
    changed: their degree is baked into parameter shapes, so a mesh that
    cannot hold them is a hard error, same as before.

    The returned config may use only a SUBSET of `n_devices` (odd
    survivor counts); build the mesh over `devices[:cfg.resolved-total]`.
    """
    if isinstance(requested, Mapping):
        requested = MeshConfig(**dict(requested))
    d = requested.degrees()
    if any(v == -1 for v in d.values()):
        return requested.resolved(n_devices)
    fixed = math.prod(v for a, v in d.items() if a not in shrink_axes)
    if fixed <= 0 or n_devices < fixed:
        raise ValueError(
            f"{n_devices} devices cannot hold fixed axes "
            f"{ {a: v for a, v in d.items() if a not in shrink_axes} } "
            f"(product {fixed})")
    # floor, don't reject: 3 survivors with tp=2 means a dp=1,tp=2 mesh on
    # 2 of them — the caller slices devices[:cfg.total] (an odd survivor
    # count mid-recovery must not hard-error the restart)
    budget = n_devices // fixed
    # shrink the LAST shrink axis first (innermost data axis) so the
    # outer/data-parallel degree survives longest; grow in reverse
    for axis in reversed(list(shrink_axes)):
        while d[axis] > 1 and math.prod(d[a] for a in shrink_axes) > budget:
            d[axis] = (d[axis] // 2) if d[axis] % 2 == 0 else 1
    got = math.prod(d[a] for a in shrink_axes)
    if got > budget:
        raise ValueError(
            f"cannot shrink {tuple(shrink_axes)} below {got} to fit "
            f"budget {budget} ({n_devices} devices)")
    # absorb leftover capacity into the FIRST shrink axis (grow-back on
    # rejoin), never past the requested degree
    first = list(shrink_axes)[0]
    while (got * 2 <= budget
           and d[first] * 2 <= requested.degrees()[first]):
        d[first] *= 2
        got *= 2
    return MeshConfig(**d)


def build_mesh(
    config: Union[MeshConfig, Mapping[str, int], None] = None,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """Build a Mesh with the canonical axis names.

    `devices` defaults to all local jax devices. The device array is reshaped
    in canonical axis order; on real slices callers should pass devices from
    `jax.experimental.mesh_utils.create_device_mesh` for ICI-optimal layout
    (we do that automatically when the topology is a known slice shape).
    """
    watch_compiles()    # whoever builds a mesh is about to compile on it
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if config is None:
        config = MeshConfig(dp=len(devices))
    if isinstance(config, Mapping):
        config = MeshConfig(**dict(config))
    config = config.resolved(len(devices))
    shape = tuple(config.degrees()[a] for a in MESH_AXES)
    try:
        # ICI-aware layout when available (real TPU slices).
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def build_hierarchical_mesh(
    config: Union[MeshConfig, Mapping[str, int], None] = None,
    devices: Optional[Sequence[Any]] = None,
    topology: Optional[Any] = None,
) -> Mesh:
    """`build_mesh` variant whose dp axis is split into the
    `(dp_inter, dp_intra)` sub-axes of a hosts x local-devices
    `collective.Topology`.

    Flat-dp callers are untouched: `build_mesh` still produces the
    canonical single-`dp` mesh, and every spec written against it keeps
    working. This factory is opt-in for the fused hierarchical gradient
    sync (`train/spmd.py`): the dp degree must equal
    `topology.inter * topology.intra`, and the dp slot of the device
    array is laid out row-major hosts x local — the same layout
    `Topology.mesh` uses — so `dp_inter` groups cross the slow fabric
    and `dp_intra` groups stay on the fast one.

    `topology` defaults to the physical layout of the dp devices
    (`topology_from_devices` shape: processes x min local chips); on a
    single-process CI backend that degenerates to inter=1, so tests pass
    an explicit `Topology(2, 2)` to emulate 2 hosts x 2 devices.
    """
    from ray_tpu.util.collective.hierarchy import Topology

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if config is None:
        config = MeshConfig(dp=len(devices))
    if isinstance(config, Mapping):
        config = MeshConfig(**dict(config))
    config = config.resolved(len(devices))
    if topology is None:
        phys = topology_from_devices(devices)
        if config.dp % max(phys.intra, 1) == 0 and phys.intra > 1:
            topology = Topology(inter=config.dp // phys.intra,
                                intra=phys.intra)
        else:
            topology = Topology(inter=config.dp, intra=1)
    if topology.inter * topology.intra != config.dp:
        raise ValueError(
            f"dp={config.dp} devices cannot form a "
            f"{topology.inter}x{topology.intra} (inter x intra) topology")
    d = config.degrees()
    shape = (d["pp"], topology.inter, topology.intra, d["fsdp"], d["sp"],
             d["ep"], d["tp"])
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, HIER_MESH_AXES)


def dp_axis_names(mesh: Mesh) -> tuple:
    """The mesh axes carrying pure data parallelism: the
    `(dp_inter, dp_intra)` sub-axes on a hierarchical mesh, the single
    `dp` axis otherwise. Empty when the mesh has neither."""
    names = tuple(getattr(mesh, "axis_names", ()) or ())
    if all(a in names for a in DP_SUB_AXES):
        return DP_SUB_AXES
    if "dp" in names:
        return ("dp",)
    return ()


def is_hierarchical_mesh(mesh: Mesh) -> bool:
    return dp_axis_names(mesh) == DP_SUB_AXES


def hier_topology(mesh: Mesh):
    """The `collective.Topology` a hierarchical mesh's dp sub-axes
    express, with axis names bound to the MESH axis names (so program
    builders written against `Topology.inter_axis`/`intra_axis` lower
    over this mesh directly)."""
    if not is_hierarchical_mesh(mesh):
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} carry no "
            f"(dp_inter, dp_intra) sub-axes; build_hierarchical_mesh makes "
            f"one")
    from ray_tpu.util.collective.hierarchy import Topology

    return Topology(inter=int(mesh.shape[DP_SUB_AXES[0]]),
                    intra=int(mesh.shape[DP_SUB_AXES[1]]),
                    inter_axis=DP_SUB_AXES[0], intra_axis=DP_SUB_AXES[1])


def rules_for_mesh(mesh: Mesh,
                   rules: Optional["LogicalRules"] = None) -> dict:
    """DEFAULT_RULES (plus overrides) rewritten for `mesh`'s dp spelling:
    on a hierarchical mesh every rule naming `dp` names the
    `(dp_inter, dp_intra)` pair instead, so logical specs like "batch"
    shard over both sub-axes without model code changing."""
    merged = {**DEFAULT_RULES, **(rules or {})}
    if not is_hierarchical_mesh(mesh):
        return merged
    out = {}
    for k, v in merged.items():
        axes = (v,) if isinstance(v, str) else v
        if axes and "dp" in axes:
            axes = tuple(a for ax in axes
                         for a in (DP_SUB_AXES if ax == "dp" else (ax,)))
            out[k] = axes
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# Logical axis rules (flax-style) and a current-mesh context so model code can
# write `constrain(x, "batch", "seq", "embed")` without threading a mesh.
# ---------------------------------------------------------------------------

# logical axis -> mesh axis (or tuple of mesh axes, or None for replicated)
LogicalRules = Mapping[str, Union[str, tuple, None]]

DEFAULT_RULES: dict[str, Union[str, tuple, None]] = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",            # activation sequence axis (context parallelism)
    "embed": "fsdp",        # parameter hidden axis: ZeRO-3 shard over fsdp
    "mlp": "tp",
    "heads": "tp",
    "kv": None,
    "vocab": "tp",
    "expert": "ep",
    "stage": "pp",
    "layers": None,
}


class _MeshContext(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict[str, Any] = dict(DEFAULT_RULES)


_ctx = _MeshContext()


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[LogicalRules] = None):
    """Install `mesh` (and optionally override logical rules) for this thread."""
    prev_mesh, prev_rules = _ctx.mesh, _ctx.rules
    _ctx.mesh = mesh
    if rules is not None:
        _ctx.rules = {**DEFAULT_RULES, **rules}
    try:
        yield mesh
    finally:
        _ctx.mesh, _ctx.rules = prev_mesh, prev_rules


def current_mesh() -> Optional[Mesh]:
    return _ctx.mesh


def logical_to_spec(*logical_axes: Optional[str], rules: Optional[LogicalRules] = None) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec via the active rules.

    Mesh axes consumed by an earlier logical axis are dropped (a mesh axis may
    only appear once in a PartitionSpec).
    """
    rules = dict(rules) if rules is not None else _ctx.rules
    used: set = set()
    parts = []
    for name in logical_axes:
        if name is None:
            parts.append(None)
            continue
        mesh_axes = rules.get(name)
        if mesh_axes is None:
            parts.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        keep = tuple(a for a in mesh_axes if a not in used)
        used.update(keep)
        if not keep:
            parts.append(None)
        elif len(keep) == 1:
            parts.append(keep[0])
        else:
            parts.append(keep)
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def named_sharding(*logical_axes: Optional[str], mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or current_mesh()
    if mesh is None:
        raise RuntimeError("no active mesh: wrap in `use_mesh(mesh)` or pass mesh=")
    return NamedSharding(mesh, logical_to_spec(*logical_axes))


_constrain_suppressed = threading.local()


@contextlib.contextmanager
def suppress_constraints():
    """Disable `constrain` inside the with-block (trace-time scope).

    FULL-manual shard_map regions (the CPU pipeline lowering in
    parallel/pipeline.py) reject with_sharding_constraint over manual
    axes; stage functions written for auto sharding still call
    `constrain`, so the manual lowering wraps their trace in this."""
    prev = getattr(_constrain_suppressed, "on", False)
    _constrain_suppressed.on = True
    try:
        yield
    finally:
        _constrain_suppressed.on = prev


def constrain(x, *logical_axes: Optional[str]):
    """`with_sharding_constraint` by logical axis names; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None or getattr(_constrain_suppressed, "on", False):
        return x
    spec = logical_to_spec(*logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def axis_size(mesh: Mesh, *axes: str) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def topology_from_devices(devices: Optional[Sequence[Any]] = None):
    """Physical hosts x local-devices `collective.Topology` of a device
    list (default: all devices) — the descriptor the hierarchical
    collectives consume. Processes are the inter (DCN) axis, each
    process's local chips the intra (ICI) axis; asymmetric hosts
    truncate to the common minimum so the 2D mesh stays rectangular."""
    from ray_tpu.util.collective.hierarchy import (Topology,
                                                   device_rows_by_process)

    rows = device_rows_by_process(
        list(devices) if devices is not None else jax.devices())
    return Topology(inter=len(rows), intra=min(len(r) for r in rows))


def traced_on(mesh: Mesh, fn):
    """`fn`, traced with `mesh` in sight (`current_mesh`) and `constrain`
    off: for a program whose placement is GSPMD's, from its arguments'
    shardings, and whose Pallas calls have to take a shard each through
    `shard_map`, which needs the mesh (the serving engine's tensor-parallel
    decode step). At the end of the file: `constrain`'s line is in the
    training programs' compile-cache keys."""
    def traced(*args):
        with use_mesh(mesh), suppress_constraints():
            return fn(*args)

    traced.__name__ = fn.__name__          # the jitted program's name
    return traced
